"""Phi-4-mini-flash (SambaY: Mamba, window attention, one full-attention
layer whose keys and values the cross-attention layers share, gated memory
units, differential attention) against its plain reference
(``benchmark/refs/phi4flash.py``) on the CPU at a small size: the
full-sequence model and the serving twin through pages and state slots (the
engine over them is in ``test_phi4flash_engine.py``, the chip's check at the
rehearsal size in ``test_phi4flash_check.py``).

Small size: 8 layers (two [Mamba, window] pairs, the middle pair, one [GMU,
cross] pair), hidden 128, 4 query and 2 key heads of 32 (one key pair, two
query pairs), ``d_state`` 16, window 32, page 16, chunks of 32 at most: a
slot's ring is 5 pages = 80 rows, so a sequence of 200 tokens wraps it twice.

The weights are drawn so that every mixer matters: ``A = -(1..16)``, ``dt``
in [1e-3, 1e-1], ``D = 1`` as published Mamba has them, matrices at
``1 / sqrt(fan_in)`` so that every mixer's output is of the residual's order
(under the benchmark's rule, 0.02 for all of them, a wrong scan could hide
behind the residual).  Everything is float32; the tolerance is its rounding
through eight layers.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
from deepspeed_tpu.models.phi4flash_cache import Phi4FlashForCausalLMWithCache, init_cache, page_heads, ring_pages

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import phi4flash as ref  # noqa: E402

WINDOW, PAGE, CHUNK = 32, 16, 32
CFG = Phi4FlashConfig(vocab_size=512, hidden_size=128, intermediate_size=256, num_hidden_layers=8,
                      num_attention_heads=4, num_key_value_heads=2, sliding_window=WINDOW,
                      max_position_embeddings=4096, dtype=jnp.float32, param_dtype=jnp.float32)
REF_CFG = {f: getattr(CFG, f) for f in ("num_attention_heads", "num_key_value_heads", "num_hidden_layers",
                                        "sliding_window", "layer_norm_eps")}
TOL = 2e-4
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)


@pytest.fixture(scope="module")
def params():
    p = nn.meta.unbox(Phi4FlashForCausalLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "dt_proj']['bias" in name:      # softplus(bias) log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(key, x.shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return jnp.log(jnp.expm1(dt))
        if name.endswith("['bias']") or "conv_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "lambda" in name:
            return 0.3 * jax.random.normal(key, x.shape)
        return x                           # matrices: lecun_normal; A_log = log(1..16); D = 1

    return jax.tree_util.tree_map_with_path(draw, p)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 2 * 200).reshape(2, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's logits of both whole sequences."""
    return [np.asarray(ref.forward(params, jnp.asarray(row), REF_CFG)[0]) for row in ids]


# ---------------------------------------------------------------- (a) the model


def _full(params, tokens):
    """The full-sequence model; jitted where it is called, one program a length."""
    return Phi4FlashForCausalLM(CFG).apply(params, tokens)


@pytest.mark.parametrize("length", [10, WINDOW, WINDOW + 1, 200])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_full)(params, jnp.asarray(ids[:1, :length]))[0]
    assert got.shape == (length, CFG.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want[0][:length], atol=TOL)


@pytest.mark.parametrize("zeroed", ["self_decoder']['mamba']['mixer']['out_proj", "mid_mamba']['mixer']['D",
                                    "cross_decoder']['gmu']['mixer']['out_proj", "lambda_q1", "sub_norm",
                                    "cross_decoder']['cross']['mixer']['o_proj", "self_decoder']['attn']['mixer']['v_proj"])
def test_every_mixer_matters_under_these_weights(params, ids, want, zeroed):
    """The guard of the guard: with one mixer's parameters zeroed the
    comparison fails by two orders of magnitude."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    got = jax.jit(_full)(broken, jnp.asarray(ids[:1]))[0]
    assert float(np.abs(np.asarray(got) - want[0]).max()) > 100 * TOL


# --------------------------------------------- (b) the twin, through slots, rings and pages


def _feed(params, rows, plans, tables, attention_impl="reference", n_slots=4, cache=None):
    """Feed ``rows`` (token ids a row) through the twin, row ``i`` in the
    chunk lengths ``plans[i]`` (0: the row sits a step out), all rows in one
    batch; per row the logits of every position fed, and the cache."""
    twin = Phi4FlashForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    if cache is None:
        cache = init_cache(CFG, KV, jnp.float32, n_slots, CHUNK)
    step = jax.jit(lambda c, t, s, n: twin.apply(params, t, s, jnp.asarray(tables), c, n))
    pos, out = [0] * len(rows), [[] for _ in rows]
    with jax.default_matmul_precision("highest"):
        for lens in zip(*plans):
            width = 1 if max(lens) == 1 else CHUNK
            toks = np.zeros((len(rows), width), np.int32)
            for i, n in enumerate(lens):
                toks[i, :n] = rows[i][pos[i]:pos[i] + n]
            logits, cache = step(cache, jnp.asarray(toks), jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32))
            for i, n in enumerate(lens):
                out[i].append(np.asarray(logits[i, :n]))
                pos[i] += n
    return [np.concatenate(o) for o in out], cache


def _table(first_page, n_pages, slot, width=14):
    """A block-table row: consecutive pages, then zeros, the slot in the last column."""
    row = np.zeros(width, np.int32)
    row[:n_pages] = first_page + np.arange(n_pages)
    row[-1] = slot
    return row


PLANS = {
    # the ring of 80 rows wraps at 80 and 160; the window's edge crosses every chunk
    "aligned_chunks_then_decode": [32] * 5 + [1] * 40,
    "chunks_that_start_and_end_inside_a_page": [7, 32, 20, 12, 32, 5, 27, 32, 9] + [1] * 24,
    "decode_from_the_second_token": [1] * 100,
    "one_short_chunk": [19],
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_twin_chunks_then_decode_match_reference(params, ids, want, plan):
    got, _ = _feed(params, ids[:1], [PLANS[plan]], _table(1, 13, slot=1)[None])
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_table_built_for_the_linear_layout_runs_in_the_scratch_slot(params, ids, want):
    """The benchmark's check builds its own table as ``program_logits`` does:
    columns ``0 .. ceil(len / page) - 1`` hold consecutive pages from page 1,
    every other column 0, and it passes no slot: the row's last column reads
    0, the scratch slot."""
    n_tokens, width = 180, 14
    pages_each = -(-n_tokens // PAGE)
    table = np.zeros((1, width), np.int32)
    table[0, :pages_each] = 1 + 0 * pages_each + np.arange(pages_each)
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 32, 32, 8] + [1] * 12], table)     # the last chunk has padding
    np.testing.assert_allclose(got[0], want[0][:180], atol=TOL)


def test_two_sequences_in_one_batch_with_different_starts(params, ids, want):
    """Row 1 starts three steps after row 0 and runs in chunks of its own;
    later a decode row rides beside a prefill chunk."""
    tables = np.stack([_table(1, 13, slot=2), _table(20, 13, slot=1)])
    plans = [[32, 32, 32, 32, 1, 1, 1, 1, 1] + [1] * 10, [0, 0, 0, 17, 32, 32, 32, 3, 1] + [1] * 10]
    got, _ = _feed(params, ids, plans, tables)
    for i in range(2):
        np.testing.assert_allclose(got[i], want[i][:len(got[i])], atol=TOL)


def test_twin_matches_reference_through_the_paged_kernel(params, ids, want):
    """The same through ``ds_paged_attention`` (interpreted on the CPU): the
    rings through the table built on the device and the window bound, the
    shared pages by the middle layer and the cross-attention layer."""
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 30] + [1] * 6], _table(1, 13, slot=3)[None], attention_impl="flash")
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


@pytest.fixture(scope="module")
def six_pairs(ids):
    """The configuration of six key pairs, its weights, and the reference's
    logits of both whole sequences."""
    cfg = dataclasses.replace(CFG, hidden_size=192, num_attention_heads=24, num_key_value_heads=12)
    p = nn.meta.unbox(Phi4FlashForCausalLM(cfg).init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))
    ref_cfg = {**REF_CFG, "num_attention_heads": 24, "num_key_value_heads": 12}
    return cfg, p, [np.asarray(ref.forward(p, jnp.asarray(row), ref_cfg)[0]) for row in ids]


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_key_pairs_in_groups_of_device_pages_match_reference(ids, six_pairs, impl):
    """Six key pairs do not fill the chip's tiles of 8, so a page is kept as
    three device pages of two pairs and the kernel runs a row a (sequence,
    group), eight query heads each: the published 10 pairs' form (5 groups of
    2).  Two sequences, one of them in the scratch slot with a padded chunk."""
    cfg, p, want_wide = six_pairs
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    assert page_heads(cfg) == 2 and page_heads(Phi4FlashConfig()) == 2 and page_heads(CFG) == 1
    twin = Phi4FlashForCausalLMWithCache(cfg, page_size=PAGE)
    cache = init_cache(cfg, KV, jnp.float32, 3, CHUNK)
    assert cache["pages"].shape == (1, 3 * 64, PAGE, 2, 2, 16) and cache["ring"].shape[1] == 3 * (1 + 3 * 5)
    tables = jnp.asarray(np.stack([_table(1, 8, slot=0), _table(30, 8, slot=2)]))
    step = jax.jit(lambda c, t, s, n: twin.apply(p, t, s, tables, c, n))
    pos = [0, 0]
    with jax.default_matmul_precision("highest"):
        for lens in ([32, 20], [32, 32], [9, 32], [1, 1], [1, 1]):
            toks = np.zeros((2, max(lens)), np.int32)
            for i, n in enumerate(lens):
                toks[i, :n] = ids[i, pos[i]:pos[i] + n]
            logits, cache = step(cache, jnp.asarray(toks), jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32))
            for i, n in enumerate(lens):
                np.testing.assert_allclose(logits[i, :n], want_wide[i][pos[i]:pos[i] + n], atol=TOL)
                pos[i] += n


def test_a_slot_used_before_gives_what_a_fresh_one_gives(params, ids, want):
    """A row whose ``start_pos`` is 0 starts from a zero recurrent state and
    sees no row of the ring's last owner."""
    table = _table(1, 13, slot=1)[None]
    _, cache = _feed(params, ids[1:], [[32] * 6], table)                 # another sequence, 192 tokens deep
    got, _ = _feed(params, ids[:1], [[32, 32, 32] + [1] * 8], table, cache=cache)
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_state_that_is_not_carried_fails_the_comparison(params, ids, want):
    """The guard of the guard for the slots: fed in two chunks with the
    recurrent state zeroed between them, the second chunk is far off."""
    table = _table(1, 13, slot=1)[None]
    _, cache = _feed(params, ids[:1], [[32]], table)
    cache = {**cache, "ssm": jnp.zeros_like(cache["ssm"])}
    twin = Phi4FlashForCausalLMWithCache(CFG, page_size=PAGE)
    logits, _ = twin.apply(params, jnp.asarray(ids[:1, 32:64]), jnp.asarray([32], jnp.int32), jnp.asarray(table), cache,
                           jnp.asarray([32], jnp.int32))
    assert float(np.abs(np.asarray(logits[0]) - want[0][32:64]).max()) > 100 * TOL


def test_a_sequence_holds_one_layer_s_pages_and_one_slot():
    """What the cache is: pages for one layer, and in a slot 2 rings of 5
    pages, 3 recurrent states and 3 convolution tails."""
    cache = init_cache(CFG, KV, jnp.float32, n_slots=4, chunk=CHUNK)
    assert ring_pages(CFG, PAGE, CHUNK) == (WINDOW + CHUNK) // PAGE + 1 == 5
    assert {k: v.shape for k, v in cache.items()} == {
        "pages": (1, 64, PAGE, 2, 1, 64), "ring": (2, 1 + 4 * 5, PAGE, 2, 1, 64),
        "ssm": (3, 4, 16, 256), "conv": (3, 4, 3, 256)}
    assert cache["ssm"].dtype == jnp.float32
    full = Phi4FlashConfig()
    big = jax.eval_shape(lambda: init_cache(full, PagedKVConfig(6400, 16, 194), jnp.bfloat16, 33, 128))
    # 10 key pairs a token in 5 device pages of 2 (whole tiles on the chip): 5,120 B a token all the same
    assert big["ring"].shape[1] == 5 * (1 + 33 * 41) and big["pages"].shape == (1, 5 * 6400, 16, 2, 2, 128)
    per_slot = sum(int(np.prod(v.shape[:1] + v.shape[2:])) * v.dtype.itemsize * (5 * 41 if k == "ring" else 1)
                   for k, v in big.items() if k != "pages")
    assert 29.0e6 < per_slot < 31.0e6                                      # 8 rings of 656 rows, 9 states and tails
