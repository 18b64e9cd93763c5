"""A step in row groups (``models/llama_cache.py`` "Row groups") against the
rectangle, on the CPU in float32, for the twins whose blocks take more than
one group: Llama (the shared softmax core), Mixtral (the experts on the flat
axis), EvaByte (ring writes, summaries and the kernel's view group by
group), and the two that hold a state slot a sequence, Phi-4-mini-flash
(convolution tails, scans, rings and the shared pages group by group) and
Granite 4.0-H (convolution tails, the one-position kernel for the decode
group and the block form for the prefill group, pages): their cache is a
dict of arrays, every one of which is compared, and a row's slot rides in
the last column of its table.

The work of one mixed step: a decode group of four rows at one slot each
(three rows at different depths, for EvaByte one of them in its second
window, and a dead row) and a prefill group of three rows at a chunk each (a
row that ends mid-chunk behind a context, for EvaByte in its second window
where the chunk completes a summary; a row that fills its chunk from position
0 and completes two, and starts from a zero state in a slot that held
another; a dead row).  The second decode row is also past Phi-4's window.  The same work as one rectangle of seven
rows at the chunk is what the engine ran before, and what the benchmark's
check still feeds.

The two tests that take a ``family`` run over the three softmax twins here and
over the two slot-holding ones in ``test_row_groups_slots.py``, a worker each.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.evabyte import EvaByteConfig
from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
from deepspeed_tpu.models.llama import LlamaConfig
from deepspeed_tpu.models.llama_cache import (PagedKVConfig, flat_positions, live_slots, over_row_groups, sampled_rows,
                                              slot_in_chunk)
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
from deepspeed_tpu.models.mixtral import PRESETS as MIXTRAL_PRESETS

PAGE, WIDTH, TABLE = 16, 32, 24
WINDOW = 256
GROUPS = ((4, 1), (3, WIDTH))
ROWS = sum(rows for rows, _ in GROUPS)
KV = PagedKVConfig(num_pages=1 + ROWS * TABLE, page_size=PAGE, max_pages_per_seq=TABLE)
CONFIGS = {
    "llama": LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
                         rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False),
    "mixtral": dataclasses.replace(MIXTRAL_PRESETS["tiny"], dtype=jnp.float32, remat=False, drop_tokens=False,
                                   num_hidden_layers=2, max_position_embeddings=512),
    "evabyte": EvaByteConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
                             num_key_value_heads=4, max_position_embeddings=2048, window_size=WINDOW,
                             chunk_size=PAGE, dtype=jnp.float32, param_dtype=jnp.float32),
    # the slot-holding twins, at the fewest layers their patterns allow: Phi-4's eight (two [Mamba, window]
    # pairs, the middle pair, a [memory unit, cross] pair), Granite's period of two
    "phi4flash": Phi4FlashConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=8,
                                 num_attention_heads=4, num_key_value_heads=2, sliding_window=32,
                                 max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32),
    "granitehybrid": GraniteHybridConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                         shared_intermediate_size=64, num_hidden_layers=2,
                                         layer_types=("mamba", "attention"), num_attention_heads=4,
                                         num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                                         max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32),
}
#: per row: (tokens of context in the arena before the step, real tokens in the step); the
#: second decode row and the first prefill row are past EvaByte's first window
CONTEXT = [5, WINDOW + 14, 2, 0, WINDOW, 0, 0]
LENS = [1, 1, 1, 0, WIDTH - 3, WIDTH, 0]
TOL = 2e-4  # float32 rounding through two layers (tests/unit/inference/test_evabyte.py)


def _twin(family, impl):
    cfg = dataclasses.replace(CONFIGS[family], attention_impl=impl)
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    twin = build_cache_model(cfg, PAGE)
    cache = cache_twin(cfg).init_cache(cfg, KV, jnp.float32, ROWS + 1, WIDTH)
    tables = 1 + np.arange(ROWS * TABLE, dtype=np.int32).reshape(ROWS, TABLE)
    if cache_geometry(cfg, PAGE).state_slots:   # a row's slot in its last column, no two rows' the same
        tables[:, -1] = 1 + np.arange(ROWS)[::-1]
    tables = jnp.asarray(tables)
    one = jnp.zeros((1, ), jnp.int32)
    params = nn.meta.unbox(jax.jit(twin.init)(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), one, tables[:1],
                                              cache, jnp.ones((1, ), jnp.int32)))

    def bump(path, x):  # EvaByte's summary vectors at unit scale, so that a summary matters
        name = jax.tree_util.keystr(path)
        return jax.random.normal(jax.random.PRNGKey(len(name)), x.shape, x.dtype) if "adaptive" in name else x

    if cache_geometry(cfg, PAGE).state_slots:   # every slot holds something a row that starts must not see
        cache = {k: v if k == "pages" else v.at[:, 1:].set(0.5) for k, v in cache.items()}

    return cfg, twin, jax.tree_util.tree_map_with_path(bump, params), cache, tables


def _with_context(apply, params, cache, tables, ids):
    """The cache after every row's context went in, in rectangles of WIDTH (a
    chunk never crosses EvaByte's window: WINDOW % WIDTH == 0)."""
    for at in range(0, max(CONTEXT), WIDTH):
        lens = np.clip(np.asarray(CONTEXT) - at, 0, WIDTH).astype(np.int32)
        start = np.minimum(at, CONTEXT).astype(np.int32)
        toks = np.zeros((ROWS, WIDTH), np.int32)
        for r in range(ROWS):
            toks[r, :lens[r]] = ids[r, start[r]:start[r] + lens[r]]
        cache = apply(params, jnp.asarray(toks), jnp.asarray(start), tables, cache, jnp.asarray(lens), True, None)[1]
    return cache


def _mixed_step(ids, pad_id=0):
    """(the step's tokens as the rectangle [ROWS, WIDTH], as the flat axis of
    GROUPS [T], each live slot's index on the flat axis by row); padding
    slots hold ``pad_id``."""
    rect = np.full((ROWS, WIDTH), pad_id, np.int32)
    for r in range(ROWS):
        rect[r, :LENS[r]] = ids[r, CONTEXT[r]:CONTEXT[r] + LENS[r]]
    flat, first, r0 = [], [], 0
    for rows, width in GROUPS:
        first += [sum(len(f) for f in flat) + width * i for i in range(rows)]
        flat.append(rect[r0:r0 + rows, :width].reshape(-1))
        r0 += rows
    return rect, np.concatenate(flat), first


def _arrays(cache):
    """A cache's arrays by name: the one arena, or a slot-holding twin's dict."""
    return {k: np.asarray(v) for k, v in (cache.items() if isinstance(cache, dict) else [("arena", cache)])}


@functools.lru_cache(maxsize=None)   # the contexts and the four programs serve the clean and the poisoned run
def _prepared(family, impl):
    cfg, twin, params, cache, tables = _twin(family, impl)
    ids = np.random.default_rng(0).integers(1, cfg.vocab_size - 1, (ROWS, max(CONTEXT) + WIDTH), dtype=np.int32)
    apply = jax.jit(twin.apply, static_argnums=(6, 7))
    return cfg, apply, params, _with_context(apply, params, cache, tables, ids), tables, ids


@functools.lru_cache(maxsize=None)   # the clean run serves both tests of a (family, impl)
def _run(family, impl, pad_id=0, poison=False):
    cfg, apply, params, cache, tables, ids = _prepared(family, impl)
    if poison:  # the last token id's embedding is NaN, and only padding slots hold that id
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x.at[cfg.vocab_size - 1].set(jnp.nan) if "embed" in jax.tree_util.keystr(path) else x,
            params)
    rect, flat, first = _mixed_step(ids, pad_id)
    start, lens = jnp.asarray(CONTEXT, jnp.int32), jnp.asarray(LENS, jnp.int32)
    out = {
        "first": first, "before": _arrays(cache),
        "flat": apply(params, jnp.asarray(flat), start, tables, cache, lens, False, GROUPS),
        "flat_last": apply(params, jnp.asarray(flat), start, tables, cache, lens, True, GROUPS),
    }
    if not poison:  # the rectangle is what the clean run is held to
        out["rect"] = apply(params, jnp.asarray(rect), start, tables, cache, lens, False, None)
        out["rect_last"] = apply(params, jnp.asarray(rect), start, tables, cache, lens, True, None)
    return out


SLOT_HOLDING = [name for name in sorted(CONFIGS) if cache_geometry(CONFIGS[name], PAGE).state_slots]


def families(names):
    """A module's ``pytest_generate_tests``: its tests that take a ``family`` run over ``names`` of ``CONFIGS``."""

    def pytest_generate_tests(metafunc):
        if "family" in metafunc.fixturenames:
            metafunc.parametrize("family", names)

    return pytest_generate_tests


pytest_generate_tests = families([name for name in sorted(CONFIGS) if name not in SLOT_HOLDING])


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_two_groups_give_the_rectangles_logits_and_arena(family, impl):
    out = _run(family, impl)
    (rect, arena_rect), (flat, arena_flat) = out["rect"], out["flat"]
    assert rect.shape[:2] == (ROWS, WIDTH) and flat.shape[0] == sum(r * w for r, w in GROUPS)
    for r in range(ROWS):
        at = out["first"][r]
        np.testing.assert_allclose(flat[at:at + LENS[r]], rect[r, :LENS[r]], atol=TOL, rtol=TOL)
    arena_rect, arena_flat = _arrays(arena_rect), _arrays(arena_flat)
    for name, before in out["before"].items():
        np.testing.assert_allclose(arena_flat[name], arena_rect[name], atol=1e-5, rtol=1e-5, err_msg=name)
        assert (arena_flat[name] != before).any(), f"the step wrote nothing to {name}"
    # the head over the sampled rows: each row's last real token of its group
    (rect_last, _), (flat_last, arena_last) = out["rect_last"], out["flat_last"]
    assert flat_last.shape == rect_last.shape == (ROWS, 1) + rect.shape[2:]
    live = np.asarray(LENS) > 0
    np.testing.assert_allclose(flat_last[live], rect_last[live], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(flat_last[live, 0], np.stack([rect[r, LENS[r] - 1] for r in np.flatnonzero(live)]),
                               atol=TOL, rtol=TOL)
    for name, arena in _arrays(arena_last).items():
        np.testing.assert_array_equal(arena, arena_flat[name], err_msg=name)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_nan_in_a_padding_slot_reaches_no_live_row_and_no_page_but_the_null_page(family, impl):
    """Padding slots (a dead row's, and those behind a row's real tokens)
    hold a token whose embedding is NaN: the flat axis's products keep it in
    its slot, the page writes send it to the null page as zeros, the
    attention masks it; a slot-holding twin's recurrences take nothing from
    such a slot either, and every state slot but the scratch slot 0 holds
    what it held."""
    clean = _run(family, impl)
    dirty = _run(family, impl, pad_id=CONFIGS[family].vocab_size - 1, poison=True)
    (want, arena_want), (got, arena_got) = clean["flat"], dirty["flat"]
    # under a tied embedding the poisoned token's own logit is NaN in every row: the other columns are held
    cols = slice(0, -1 if getattr(CONFIGS[family], "tie_word_embeddings", False) else None)
    for r in range(ROWS):
        at = clean["first"][r]
        assert np.isfinite(got[at:at + LENS[r], cols]).all()
        np.testing.assert_allclose(got[at:at + LENS[r], cols], want[at:at + LENS[r], cols], atol=TOL, rtol=TOL)
    assert np.isnan(np.asarray(got[:, cols])).any(), "no padding slot carried the NaN: the test tests nothing"
    arena_want = _arrays(arena_want)
    for name, arena in _arrays(arena_got).items():   # page 0, ring page 0 and slot 0 are the second axis's first
        np.testing.assert_allclose(arena[:, 1:], arena_want[name][:, 1:], atol=1e-5, rtol=1e-5, err_msg=name)
    live = np.asarray(LENS) > 0
    np.testing.assert_allclose(dirty["flat_last"][0][live][..., cols], clean["flat_last"][0][live][..., cols],
                               atol=TOL, rtol=TOL)


def test_flat_axis_helpers():
    groups = ((2, 1), (2, 3))
    start, lens = jnp.asarray([7, 0, 10, 20]), jnp.asarray([1, 0, 2, 3])
    assert slot_in_chunk(groups).tolist() == [0, 0, 0, 1, 2, 0, 1, 2]
    assert flat_positions(groups, start).tolist() == [7, 0, 10, 11, 12, 20, 21, 22]
    assert live_slots(groups, lens).tolist() == [True, False, True, True, False, True, True, True]
    x = jnp.arange(8.0)[:, None]
    assert sampled_rows(x, lens, True, groups)[:, 0, 0].tolist() == [0.0, 1.0, 3.0, 7.0]  # a dead row: its first slot
    assert sampled_rows(x, lens, False, groups) is x
    seen = []

    def attend(arena, q, table, lens):
        seen.append((q.shape, table.shape, lens.tolist()))
        return q * 2, arena + 1

    out, arena = over_row_groups(groups, attend, 0, (x, ), (jnp.zeros((4, 5)), lens))
    assert seen == [((2, 1, 1), (2, 5), [1, 0]), ((2, 3, 1), (2, 5), [2, 3])] and arena == 2
    assert out[:, 0].tolist() == (2 * np.arange(8.0)).tolist()


def test_tokens_that_do_not_fill_their_groups_are_refused():
    cfg, twin, params, cache, tables = _twin("llama", "reference")
    with pytest.raises(ValueError, match="row groups"):
        twin.apply(params, jnp.zeros((10, ), jnp.int32), jnp.zeros((ROWS, ), jnp.int32), tables, cache,
                   jnp.zeros((ROWS, ), jnp.int32), False, GROUPS)
