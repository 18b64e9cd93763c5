"""Granite 4.0-H (Mamba-2 layers whose states live in state slots, a few
position-free grouped-query attention layers with pages of their own, muP
multipliers) against its plain reference (``benchmark/refs/granitehybrid.py``,
whose recurrence goes position by position) on the CPU at a small size: the
full-sequence model, the block form of the recurrence, the kernel of the
one-position update, the serving twin through pages and slots (the engine over
them is in ``test_granite_hybrid_engine.py``).

Small size: 8 layers, two periods of [Mamba, Mamba, attention, Mamba];
hidden 256; 8 query and 4 key heads of 32 (all four key heads packed into
one page head of 128 lanes); 16 Mamba heads of 32 over a state of 32; page
16, chunks of 32 at most.

The Mamba parameters come from the model's own initialisers, which are the
published ones: ``A`` uniform in [1, 16], ``softplus(dt_bias)`` log-uniform in
[0.001, 0.1], ``D`` = 1, so ``exp(dt A)`` is 0.2 to 0.999 a position and a
state a hundred positions back still counts (under the benchmark's rule ``A``
is about -1 and ``dt`` about 0.7: a state forgets in a few positions).
Matrices at ``1 / sqrt(fan_in)``, norm weights away from 1, a convolution
bias.  Everything is float32; the tolerance is its rounding through eight
layers.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig, GraniteHybridForCausalLM, ssd_blocks, ssd_chunk,
                                                 ssd_update_reference)
from deepspeed_tpu.models.granite_hybrid_cache import (GraniteHybridForCausalLMWithCache, init_cache, kv_pack,
                                                       slot_state_bytes)
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.ops.ssd_update import FRESH, LIVE, ssd_update

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import granitehybrid as ref  # noqa: E402

PAGE, CHUNK = 16, 32
PATTERN = ("mamba", "mamba", "attention", "mamba")
CFG = GraniteHybridConfig(vocab_size=512, hidden_size=256, intermediate_size=256, shared_intermediate_size=256,
                          num_hidden_layers=8, layer_types=PATTERN * 2, num_attention_heads=8, num_key_value_heads=4,
                          mamba_n_heads=16, mamba_d_head=32, mamba_d_state=32, max_position_embeddings=4096,
                          dtype=jnp.float32, param_dtype=jnp.float32)
REF_KEYS = ("num_attention_heads", "num_key_value_heads", "layer_types", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "rms_norm_eps", "attention_multiplier", "embedding_multiplier", "residual_multiplier",
            "logits_scaling")
REF_CFG = {f: getattr(CFG, f) for f in REF_KEYS}
TOL = 2e-4
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)


def _draw(cfg, seed=0):
    p = nn.meta.unbox(GraniteHybridForCausalLM(cfg).init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "conv_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "embedding" in name:            # of the order the multiplier of 12 was made for
            return x * 4.0
        return x                           # matrices: lecun_normal; A, dt_bias, D: the published initialisation

    return jax.tree_util.tree_map_with_path(draw, p)


@pytest.fixture(scope="module")
def params():
    return _draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 200).reshape(3, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's logits of the whole sequences."""
    return [np.asarray(ref.forward(params, jnp.asarray(row), REF_CFG)[0]) for row in ids]


# ---------------------------------------------------------------- (a) the model


def test_the_pattern_has_a_period():
    assert CFG.period == 4 and CFG.per_period("mamba") == 3 and CFG.per_period("mamba", before=3) == 2
    full = GraniteHybridConfig()
    assert full.period == 10 and full.per_period("mamba") == 9 and full.per_period("attention", before=6) == 1
    assert [i for i, k in enumerate(full.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert full.count("mamba") == 36 and full.count("attention") == 4 and full.conv_dim == 4352
    assert [ref.layer_place(CFG.layer_types, i) for i in (0, 3, 6)] == [(0, "layer_0"), (0, "layer_3"), (1, "layer_2")]


def test_published_sizes_give_the_published_parameter_count():
    full = GraniteHybridConfig()
    shapes = jax.eval_shape(GraniteHybridForCausalLM(full).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(shapes))) == 3_191_396_096


def test_the_mamba_parameters_are_initialised_as_published(params):
    mixer = params["params"]["periods"]["layer_0"]["mixer"]
    a, dt = np.exp(np.asarray(mixer["A_log"])), np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() - a.min() > 5.0
    assert 1e-3 <= dt.min() * 1.001 and dt.max() <= 0.1001 and np.all(np.asarray(mixer["D"]) == 1.0)


def test_a_config_with_routed_experts_is_taken_and_one_with_positions_refused():
    """Since PR 56 the siblings with routed experts are built (``test_granite_moe_hybrid.py``)."""
    assert GraniteHybridConfig(num_local_experts=32, num_experts_per_tok=4).router_width == 32
    with pytest.raises(NotImplementedError, match="no\\s+positional encoding"):
        GraniteHybridConfig(position_embedding_type="rope")


def _full(params, tokens):
    """The full-sequence model; jitted where it is called, one program a length."""
    return GraniteHybridForCausalLM(CFG).apply(params, tokens)


@pytest.mark.parametrize("length", [10, 128, 129, 200])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_full)(params, jnp.asarray(ids[:1, :length]))[0]
    assert got.shape == (length, CFG.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want[0][:length], atol=TOL)


@pytest.mark.parametrize("zeroed", ["layer_0']['mixer']['out_proj", "layer_3']['mixer']['D",
                                    "layer_2']['mixer']['v_proj", "mixer']['norm", "conv_kernel",
                                    "shared_mlp']['output_linear"])
def test_every_part_matters_under_these_weights(params, ids, want, zeroed):
    """The guard of the guard: with one part's parameters zeroed the
    comparison fails by two orders of magnitude."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    got = jax.jit(_full)(broken, jnp.asarray(ids[:1]))[0]
    assert float(np.abs(np.asarray(got) - want[0]).max()) > 100 * TOL


@pytest.mark.parametrize("scalar, conventional", [("embedding_multiplier", 1.0), ("attention_multiplier", 32 ** -0.5),
                                                  ("residual_multiplier", 1.0), ("logits_scaling", 1.0)])
def test_each_mup_scalar_is_applied(params, ids, want, scalar, conventional):
    """The model agrees with the reference (above); the reference with one
    multiplier at the value a conventional transformer has is far from both,
    so a model that dropped the multiplier would fail the comparison."""
    other = np.asarray(ref.forward(params, jnp.asarray(ids[0]), {**REF_CFG, scalar: conventional})[0])
    assert float(np.abs(other - want[0]).max()) > 100 * TOL
    with jax.default_matmul_precision("highest"):
        got = GraniteHybridForCausalLM(dataclasses.replace(CFG, **{scalar: conventional})).apply(
            params, jnp.asarray(ids[:1]))[0]
    np.testing.assert_allclose(got, other, atol=TOL * max(1.0, float(np.abs(other).max())))


def test_attention_has_no_positional_encoding():
    """One attention layer and nothing else: with no rotary the last
    position's logits do not change when the tokens before it change places
    (with rotary, or any other encoding of position, they would), in the
    model and in the reference."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=1, layer_types=("attention", ))
    p = _draw(cfg, seed=1)
    toks = np.random.default_rng(1).integers(1, cfg.vocab_size, 40)
    moved = np.concatenate([toks[:-1][::-1], toks[-1:]])
    ref_cfg = {**REF_CFG, "layer_types": cfg.layer_types}
    with jax.default_matmul_precision("highest"):
        a, b = (GraniteHybridForCausalLM(cfg).apply(p, jnp.asarray(t[None]))[0] for t in (toks, moved))
    ra, rb = (ref.forward(p, jnp.asarray(t), ref_cfg)[0] for t in (toks, moved))
    np.testing.assert_allclose(a[-1], b[-1], atol=1e-5)
    np.testing.assert_allclose(ra[-1], rb[-1], atol=1e-5)
    np.testing.assert_allclose(a, ra, atol=TOL)
    assert float(np.abs(np.asarray(a[-2]) - np.asarray(b[-2])).max()) > 100 * TOL     # the others do change


# ------------------------------------------------- (b) the recurrence's two forms and the kernel


def _recurrence_inputs(batch, length, seed=0, h=4, p=8, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(x=jax.random.normal(k[0], (batch, length, h, p)),
                dt=jnp.exp(jax.random.uniform(k[1], (batch, length, h), minval=np.log(1e-3), maxval=np.log(0.5))),
                a=-jax.random.uniform(k[2], (h, ), minval=1.0, maxval=16.0),
                b_mat=jax.random.normal(k[3], (batch, length, n)), c_mat=jax.random.normal(k[4], (batch, length, n)),
                state=jax.random.normal(k[5], (batch, h, p, n)))


def _position_by_position(x, dt, a, b_mat, c_mat, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_update_reference(x[:, t] * dt[:, t, :, None], jnp.exp(dt[:, t] * a), b_mat[:, t], c_mat[:, t],
                                        state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("length", [1, 7, 128])
def test_block_form_equals_the_recurrence_position_by_position(length):
    """Rows of one block carry ``length``, fewer and no tokens: ``dt`` = 0 at
    a padded position, which leaves the state alone."""
    args = _recurrence_inputs(3, length, seed=length)
    lens = np.array([length, max(length - 3, 0), 0])
    args["dt"] = jnp.where(np.arange(length)[None, :, None] < lens[:, None, None], args["dt"], 0.0)
    with jax.default_matmul_precision("highest"):
        y, state = ssd_chunk(**args)
        want_y, want_state = _position_by_position(**args)
    for row, n in enumerate(lens):
        np.testing.assert_allclose(y[row, :n], want_y[row, :n], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(state[2], args["state"][2])          # the row with no token: bit for bit


def test_blocks_of_a_long_sequence_carry_the_state():
    args = _recurrence_inputs(2, 300, seed=5)
    with jax.default_matmul_precision("highest"):
        y, state = ssd_blocks(**args, block=128)
        want_y, want_state = _position_by_position(**args)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_ssd_update_kernel_equals_the_jnp_update_on_the_arena():
    """``ds_ssd_update`` in interpret mode: rows in scattered slots of layer
    1 of a three-layer arena, one row without a token, one that starts a
    sequence; every other state of the arena is left as it was."""
    layers, slots, h, p, n, b = 3, 6, 32, 16, 128, 5
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    arena = jax.random.normal(k[0], (layers, slots, h, p, n))
    slot = np.array([4, 0, 1, 5, 2])
    flags = np.array([LIVE, 0, LIVE | FRESH, LIVE, LIVE])
    xdt, decay = jax.random.normal(k[1], (b, h, p)), jax.random.uniform(k[2], (b, h))
    b_mat, c_mat = jax.random.normal(k[3], (b, n)), jax.random.normal(k[4], (b, n))
    y, new = jax.jit(lambda a: ssd_update(a, jnp.int32(1), jnp.asarray(slot), jnp.asarray(flags), xdt, decay, b_mat,
                                          c_mat, interpret=True))(arena)
    before = np.asarray(arena)[1, slot]
    before[2] = 0.0                                                   # FRESH: whatever the slot held
    want_y, want_state = ssd_update_reference(xdt, decay, b_mat, c_mat, jnp.asarray(before))
    live = flags & LIVE > 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live], rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[~live].any()
    np.testing.assert_allclose(np.asarray(new)[1, slot[live]], np.asarray(want_state)[live], rtol=1e-6, atol=1e-6)
    untouched = np.ones((layers, slots), bool)
    untouched[1, slot[live]] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(arena)[untouched])


# --------------------------------------------------- (c) the twin, through slots and pages


def _feed(params, rows, plans, tables, attention_impl="reference", n_slots=6, cache=None, cfg=CFG, start=None):
    """Feed ``rows`` (token ids a row) through the twin from positions
    ``start`` (0) on, row ``i`` in the chunk lengths ``plans[i]`` (0: the row
    sits a step out), all rows in one batch; per row the logits of every
    position fed, and the cache.  A step whose rows carry one token at most
    is a decode step (width 1)."""
    twin = GraniteHybridForCausalLMWithCache(dataclasses.replace(cfg, attention_impl=attention_impl), page_size=PAGE)
    if cache is None:
        cache = init_cache(cfg, KV, jnp.float32, n_slots, CHUNK)
    step = jax.jit(lambda c, t, s, n: twin.apply(params, t, s, jnp.asarray(tables), c, n))
    pos, out = list(start or [0] * len(rows)), [[] for _ in rows]
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


def _table(pages, slot, width=14):
    """A block-table row: the pages, then zeros, the slot in the last column."""
    row = np.zeros(width, np.int32)
    row[:len(pages)] = pages
    row[-1] = slot
    return row


PLANS = {
    "aligned_chunks_then_decode": [32] * 5 + [1] * 40,
    "chunks_that_start_and_end_inside_a_page": [7, 32, 20, 12, 32, 5, 27, 32, 9] + [1] * 24,
    "decode_from_the_second_token": [1] * 100,
    "one_short_chunk": [19],
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_twin_chunks_then_decode_match_reference(params, ids, want, plan):
    got, _ = _feed(params, ids[:1], [PLANS[plan]], _table(1 + np.arange(13), slot=1)[None])
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_table_built_for_the_linear_layout_runs_in_the_scratch_slot(params, ids, want):
    """The benchmark's check builds its own table as ``program_logits`` does:
    consecutive pages from page 1, every other column 0, and no slot: the
    row's last column reads 0, the scratch slot."""
    table = np.zeros((1, 14), np.int32)
    table[0, :12] = 1 + np.arange(12)
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 32, 32, 8] + [1] * 12], table)     # the last chunk has padding
    np.testing.assert_allclose(got[0], want[0][:180], atol=TOL)


def test_three_sequences_in_scattered_slots_and_pages_in_one_batch(params, ids, want):
    """Rows in slots 4, 1 and 3 on pages that interleave; row 2 starts at
    position 0 in the step in which rows 0 and 1 continue, and later decode
    rows ride beside a prefill chunk."""
    tables = np.stack([_table(np.arange(1, 40, 3), slot=4), _table(np.arange(3, 42, 3), slot=1),
                       _table(np.arange(2, 41, 3), slot=3)])
    plans = [[32, 32, 32, 1, 1, 1, 1, 1] + [1] * 10,
             [17, 32, 32, 32, 3, 1, 1, 1] + [1] * 10,
             [0, 0, 32, 32, 32, 5, 1, 1] + [1] * 10]
    got, _ = _feed(params, ids, plans, tables)
    for i in range(3):
        np.testing.assert_allclose(got[i], want[i][:len(got[i])], atol=TOL)


def test_twin_matches_reference_through_the_paged_kernel(params, ids, want):
    """The same through ``ds_paged_attention`` (interpreted on the CPU): four
    key heads in one page head of 128 lanes, a query in its key head's
    lanes, the scale the configuration's multiplier."""
    assert kv_pack(CFG) == 4 and kv_pack(GraniteHybridConfig()) == 2
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 30] + [1] * 6], _table(np.arange(5, 18), slot=3)[None],
                   attention_impl="flash")
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_padded_row_and_a_finished_row_leave_every_slot_but_scratch_untouched(params, ids):
    """A padded row (no sequence: table of zeros, no token) and a row whose
    sequence carries no token in this step, in a chunk step and in a decode
    step: the states and tails of every slot but scratch, the finished row's
    own included, and every page but the null page stay bit for bit."""
    tables = np.stack([_table(np.arange(1, 14), slot=2), _table(np.arange(20, 33), slot=5), _table([], slot=0)])
    _, cache = _feed(params, ids, [[32, 32], [32, 7], [0, 0]], tables)
    for lens in ([32, 0, 0], [1, 0, 0]):
        _, after = _feed(params, ids, [[n] for n in lens], tables, cache=cache, start=[64, 39, 0])
        for name in ("ssm", "conv"):
            keep = [s for s in range(6) if s not in (0, 2)]
            np.testing.assert_array_equal(np.asarray(after[name])[:, keep], np.asarray(cache[name])[:, keep])
            assert np.abs(np.asarray(after[name])[:, 2] - np.asarray(cache[name])[:, 2]).max() > 0
        mine = np.asarray(tables[0][:13])
        others = np.setdiff1d(np.arange(1, KV.num_pages), mine)
        np.testing.assert_array_equal(np.asarray(after["pages"])[:, others], np.asarray(cache["pages"])[:, others])


def test_a_slot_used_before_gives_what_a_fresh_one_gives(params, ids, want):
    """A row whose ``start_pos`` is 0 starts from a zero state and a zero
    convolution tail, through a chunk and through a one-token step."""
    table = _table(1 + np.arange(13), slot=1)[None]
    _, cache = _feed(params, ids[1:2], [[32] * 6], table)                 # another sequence, 192 tokens deep
    for plan in ([32, 32, 32] + [1] * 8, [1] * 20):
        got, _ = _feed(params, ids[:1], [plan], table, cache=cache)
        np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_state_that_is_not_carried_fails_the_comparison(params, ids, want):
    """The guard of the guard for the slots: the recurrent state zeroed a
    hundred positions back, the logits are still far off (under the published
    initialisation a state does not forget in a few positions)."""
    table = _table(1 + np.arange(13), slot=1)[None]
    _, cache = _feed(params, ids[:1], [[32]], table)
    cache = {**cache, "ssm": jnp.zeros_like(cache["ssm"])}
    got, _ = _feed(params, ids[:1], [[32] * 4 + [4]], table, cache=cache, start=[32])
    worst = [float(np.abs(got[0][lo:hi] - want[0][32 + lo:32 + hi]).max()) for lo, hi in ((0, 32), (128, 132))]
    assert worst[0] > 100 * TOL and worst[-1] > 10 * TOL, worst        # positions 160-163: 130 after the loss


def test_a_sequence_holds_four_layers_pages_and_one_slot():
    """What the cache is: pages for every attention layer under one table, and
    in a slot every Mamba layer's state and convolution tail."""
    cache = init_cache(CFG, KV, jnp.float32, n_slots=4, chunk=CHUNK)
    assert {k: v.shape for k, v in cache.items()} == {
        "pages": (2, 64, PAGE, 2, 1, 128), "ssm": (6, 4, 16, 32, 32), "conv": (6, 4, 3, 512 + 64)}
    assert cache["ssm"].dtype == jnp.float32
    full = GraniteHybridConfig()
    big = jax.eval_shape(lambda: init_cache(full, PagedKVConfig(8300, 16, 258), jnp.bfloat16, 33, 128))
    assert big["pages"].shape == (4, 8300, 16, 2, 4, 128) and big["ssm"].shape == (36, 33, 64, 64, 128)
    assert big["conv"].shape == (36, 33, 3, 4352)
    assert slot_state_bytes(full) == 36 * 2_097_152
    per_token = int(np.prod(big["pages"].shape[:1] + big["pages"].shape[3:])) * 2
    assert per_token == 8192                                             # 4 layers x 2 x 8 heads x 64 x bfloat16
