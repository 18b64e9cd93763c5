"""Solar-Open2 (gated delta-rule linear attention, KDA, whose matrix states
live in state slots; gated position-free grouped-query attention with pages;
a share of the routed experts held) against its plain reference
(``benchmark/refs/solar_open2.py``, whose recurrence goes position by
position) on the CPU at a small size: the full-sequence model, the three
forms of the recurrence, the cell's sizes by ``eval_shape``.  The twin is in
``test_solar_open2_twin.py``, the engine in ``test_solar_open2_engine.py``.

Small size: 8 layers, two periods of [GQA, KDA, KDA, KDA]; hidden 128; 4
query and 2 key heads of 32; 4 linear heads of 32; a router of 16 experts of
width 64, 4 a token, of which this share holds experts 8-15; one shared
expert.

The KDA parameters come from the model's own initialisers, which are the
published ones: ``exp(A_log)`` log-uniform in [1, 16], ``softplus(dt_bias)``
log-uniform in [0.001, 0.1], so a channel keeps 0.2 to 0.999 of its state a
position and a state a hundred positions back still counts (under the
benchmark's rule ``g`` is about -0.7 a position: a state forgets in a few).
Matrices at ``1 / sqrt(fan_in)``, norm weights away from 1, a selection bias
of the size of the score gaps.  Everything is float32; the tolerance is its
rounding through eight layers.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.solar_open2 import (SolarOpen2Config, SolarOpen2ForCausalLM, kda_chunk, kda_recurrent,
                                              kda_update_reference)
from deepspeed_tpu.models.solar_open2_cache import init_cache, slot_state_bytes
from deepspeed_tpu.ops.kda_update import FRESH, LIVE, kda_update

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark")
sys.path.insert(0, BENCH)
from refs import solar_open2 as ref  # noqa: E402

LINEAR = {"short_conv_kernel_size": 4, "head_dim": 32, "num_heads": 4, "num_kv_heads": None}
CFG = SolarOpen2Config(vocab_size=512, hidden_size=128, moe_intermediate_size=64, num_hidden_layers=8,
                       num_attention_heads=4, num_key_value_heads=2, head_dim=32, linear_attn_config=LINEAR,
                       n_routed_experts=8, router_experts=16, first_expert=8, num_experts_per_tok=4,
                       max_position_embeddings=4096, dtype=jnp.float32, param_dtype=jnp.float32)
TOL = 2e-4


def ref_cfg(cfg):
    """The configuration as the reference reads it: the file's keys."""
    return {"num_hidden_layers": cfg.num_hidden_layers, "gqa_layers": list(cfg.gqa_layers),
            "num_attention_heads": cfg.num_attention_heads, "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "linear_attn_config": cfg.linear, "use_gqa_gate": cfg.use_gqa_gate,
            "kda_allow_neg_eigval": cfg.kda_allow_neg_eigval, "n_routed_experts": cfg.n_routed_experts,
            "first_expert": cfg.first_expert, "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.rms_norm_eps, "vocab_size": cfg.vocab_size}


def draw(cfg, seed=0):
    p = nn.meta.unbox(jax.jit(SolarOpen2ForCausalLM(cfg).init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))

    def one(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "e_score_correction_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        if "embedding" in name:
            return x * 40.0                # rows of the order of 1
        return x                           # matrices: lecun_normal; A_log, dt_bias: the published initialisation

    return jax.tree_util.tree_map_with_path(one, p)


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(jax.jit(lambda p, t: ref.forward(p, t, ref_cfg(CFG))[0])(params, jnp.asarray(ids)))


# ---------------------------------------------------------------- (a) the model


def test_the_pattern_has_a_period_and_the_share_its_place():
    assert CFG.layer_types == ("gqa", "kda", "kda", "kda") * 2 and CFG.period == 4
    assert CFG.per_period("kda") == 3 and CFG.per_period("kda", before=3) == 2 and CFG.count("gqa") == 2
    assert CFG.held == (8, 8) and CFG.router_width == 16
    full = SolarOpen2Config()
    assert full.gqa_layers == tuple(range(0, 48, 4)) and full.period == 4 and full.count("kda") == 36
    assert full.held is None and full.kda_width == 8192
    # the published list, kept whole in a configuration of four layers
    cut = SolarOpen2Config(num_hidden_layers=4, gqa_layers=tuple(range(0, 48, 4)))
    assert cut.layer_types == ("gqa", "kda", "kda", "kda")
    assert [ref.layer_place(list(CFG.layer_types), i) for i in (0, 3, 6)] == [(0, "layer_0"), (0, "layer_3"),
                                                                              (1, "layer_2")]


@pytest.mark.parametrize("field, value, words", [("kda_use_full_proj", True, "full-rank gate projections"),
                                                 ("use_rope", True, "no positional encoding"),
                                                 ("first_k_dense_replace", 1, "leading dense layers"),
                                                 ("tie_word_embeddings", True, "tie_word_embeddings")])
def test_what_is_not_computed_is_refused_in_words(field, value, words):
    with pytest.raises(NotImplementedError, match=words):
        SolarOpen2Config(**{field: value})


def test_a_share_that_does_not_lie_inside_the_router_is_refused():
    with pytest.raises(ValueError, match="inside\\s+the router"):
        SolarOpen2Config(n_routed_experts=40, router_experts=320, first_expert=300)


def test_the_kda_parameters_are_initialised_as_published(params):
    mixer = params["params"]["periods"]["layer_1"]["mixer"]
    a, dt = np.exp(np.asarray(mixer["A_log"])), np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.max() / a.min() > 2.0
    assert 1e-3 <= dt.min() * 1.001 and dt.max() <= 0.1001 and dt.max() / dt.min() > 20.0


def _full(params, tokens):
    return SolarOpen2ForCausalLM(CFG).apply(params, tokens)


_FULL = jax.jit(_full)      # one program a length, whatever test calls it


@pytest.mark.parametrize("length", [129, 200])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = _FULL(params, jnp.asarray(ids[None, :length]))[0]
    assert got.shape == (length, CFG.vocab_size)
    np.testing.assert_allclose(got, want[:length], atol=TOL)


@pytest.mark.parametrize("zeroed", ["layer_0']['mixer']['g_proj", "layer_1']['mixer']['f_b_proj",
                                    "layer_2']['mixer']['b_proj", "layer_3']['mixer']['g_b_proj", "conv_kernel",
                                    "e_score_correction_bias", "w_down"])
def test_every_part_matters_under_these_weights(params, ids, want, zeroed):
    """The guard of the guard: with one part's parameters zeroed the
    comparison fails by an order of magnitude or more."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    with jax.default_matmul_precision("highest"):
        got = _FULL(broken, jnp.asarray(ids[None]))[0]
    assert float(np.abs(np.asarray(got) - want).max()) > 30 * TOL


@pytest.mark.parametrize("without", ["state", "kda", "gqa", "expert"])
def test_the_references_controls_are_far_from_the_reference(params, ids, want, without):
    """What the on-chip test holds the limits against: the reference without
    the state term, the KDA mixers, the GQA mixers or one held expert."""
    other = np.asarray(ref.forward(params, jnp.asarray(ids), ref_cfg(CFG), without=(without, ))[0])
    assert float(np.abs(other - want).max()) > 100 * TOL


def test_a_state_further_back_than_a_chunk_still_counts(params, ids, want):
    """Under the published initialisation the logits of position 199 change
    when a token 150 positions back changes, through the KDA layers alone
    (the attention layers' gates zeroed): a recurrence that forgot what lies
    further back than a chunk of 128 would pass under the benchmark's rule
    and fails here."""
    only_kda = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if "layer_0']['mixer']['o_proj" in jax.tree_util.keystr(path) else x,
        params)
    moved = np.array(ids)
    moved[49] = (moved[49] + 7) % CFG.vocab_size
    cfg = ref_cfg(CFG)
    a, b = (np.asarray(ref.forward(only_kda, jnp.asarray(t), cfg)[0][-1]) for t in (ids, moved))
    assert float(np.abs(a - b).max()) > 10 * TOL


# ------------------------------------------------- (b) the recurrence's three forms


def _recurrence_inputs(batch, length, seed=0, h=4, dk=32, dv=32, decay=(1e-3, 1.0)):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    return dict(q=unit(jax.random.normal(k[0], (batch, length, h, dk))) * dk**-0.5,
                k=unit(jax.random.normal(k[1], (batch, length, h, dk))),
                v=jax.random.normal(k[2], (batch, length, h, dv)),
                g=-jnp.exp(jax.random.uniform(k[3], (batch, length, h, dk), minval=np.log(decay[0]),
                                              maxval=np.log(decay[1]))),
                beta=2.0 * jax.random.uniform(k[4], (batch, length, h)),
                state=jax.random.normal(k[5], (batch, h, dk, dv)))


def _dead_past(args, lens):
    """Positions at and after a row's length carry no token: ``g``, ``beta``, ``k`` zero."""
    length = args["q"].shape[1]
    live = jnp.asarray(np.arange(length)[None, :] < np.asarray(lens)[:, None])
    return {**args, "g": jnp.where(live[..., None, None], args["g"], 0.0),
            "beta": jnp.where(live[..., None], args["beta"], 0.0),
            "k": jnp.where(live[..., None, None], args["k"], 0.0)}


@pytest.mark.parametrize("length", [1, 128, 150])
def test_chunked_form_equals_the_recurrence_position_by_position(length):
    """Rows of one chunk carry ``length``, fewer and no tokens."""
    lens = np.array([length, max(length - 3, 0), 0])
    args = _dead_past(_recurrence_inputs(3, length, seed=length), lens)
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(kda_chunk)(**args)
        want_o, want_state = jax.jit(kda_recurrent)(**args)
    for row, n in enumerate(lens):
        np.testing.assert_allclose(o[row, :n], want_o[row, :n], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(state[2], args["state"][2])          # the row with no token: bit for bit


def test_a_chunk_in_which_a_channel_forgets_everything_stays_finite():
    """Log-decays of -0.5 to -8 a position: inside a chunk of 128 a channel's
    running sum ``G`` falls far below -100 (to -400), where ``exp(-G)``
    overflows float32; the chunked form takes differences ``G_t - G_s <= 0``
    alone and agrees with the recurrence."""
    args = _recurrence_inputs(2, 128, seed=3, decay=(0.5, 8.0))
    assert float(jnp.cumsum(args["g"], axis=1).min()) < -300
    with jax.default_matmul_precision("highest"):
        o, state = jax.jit(kda_chunk)(**args)
        want_o, want_state = jax.jit(kda_recurrent)(**args)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(state)).all()
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


def test_kda_update_kernel_equals_the_recurrence_on_one_sequence():
    """``ds_kda_update`` in interpret mode, position after position on one
    sequence in slot 3 of layer 1 (its first position ``FRESH`` over a slot
    that holds something else), against the recurrence and the chunked form
    from a zero state."""
    args = _recurrence_inputs(1, 24, seed=11)
    args["state"] = jnp.zeros_like(args["state"])
    arena = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4, 32, 32))
    step = jax.jit(lambda a, flag, q, k, v, g, beta: kda_update(a, jnp.int32(1), jnp.array([3]), flag, q, k, v, g,
                                                                beta, interpret=True))
    outs, now = [], arena
    for t in range(24):
        flag = jnp.array([LIVE | (FRESH if t == 0 else 0)])
        o, now = step(now, flag, *(args[n][:, t] for n in ("q", "k", "v", "g", "beta")))
        outs.append(o)
    with jax.default_matmul_precision("highest"):
        want_o, want_state = jax.jit(kda_recurrent)(**args)
        chunk_o, chunk_state = jax.jit(kda_chunk)(**args)
    np.testing.assert_allclose(jnp.stack(outs, axis=1), want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(now[1, 3], want_state[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(chunk_o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(chunk_state, want_state, rtol=2e-4, atol=2e-4)


def test_kda_update_kernel_equals_the_jnp_update_on_the_arena():
    """Rows in scattered slots of layer 1 of a three-layer arena, one row
    without a token, one that starts a sequence; every other state of the
    arena is left as it was.  Two head blocks (64 heads)."""
    layers, slots, h, d, b = 3, 6, 64, 16, 5
    args = _recurrence_inputs(b, 1, seed=2, h=h, dk=d, dv=d)
    arena = jax.random.normal(jax.random.PRNGKey(4), (layers, slots, h, d, d))
    slot = np.array([4, 0, 1, 5, 2])
    flags = np.array([LIVE, 0, LIVE | FRESH, LIVE, LIVE])
    now = {n: args[n][:, 0] for n in ("q", "k", "v", "g", "beta")}
    o, new = jax.jit(lambda a: kda_update(a, jnp.int32(1), jnp.asarray(slot), jnp.asarray(flags), **now,
                                          interpret=True))(arena)
    before = np.asarray(arena)[1, slot]
    before[2] = 0.0                                                   # FRESH: whatever the slot held
    want_o, want_state = kda_update_reference(**now, state=jnp.asarray(before))
    live = flags & LIVE > 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], rtol=1e-5, atol=1e-5)
    assert not np.asarray(o)[~live].any()
    np.testing.assert_allclose(np.asarray(new)[1, slot[live]], np.asarray(want_state)[live], rtol=1e-5, atol=1e-6)
    untouched = np.ones((layers, slots), bool)
    untouched[1, slot[live]] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(arena)[untouched])


# ------------------------------------------------- (c) the cell's configuration


def _cell():
    with open(os.path.join(BENCH, "configs", "solar-open2-250b-serve-1chip.json")) as f:
        return json.load(f)


def _cell_config(cell):
    names = {f.name for f in dataclasses.fields(SolarOpen2Config)}
    return SolarOpen2Config(**{k: v for k, v in cell.items() if k in names}, param_dtype=jnp.bfloat16)


def test_the_cells_parameter_count_is_the_programs_own():
    """``parameters.count`` of the configuration file is ``eval_shape`` of
    the program's own init at the file's sizes; every published width is
    unchanged and the cut is the three keys ``reduced`` names."""
    cell = _cell()
    cfg = _cell_config(cell)
    shapes = jax.eval_shape(SolarOpen2ForCausalLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(shapes)))
    assert count == cell["parameters"]["count"] and 2 * count == cell["parameters"]["bytes_bfloat16"]
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (4096, 64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_size) == (64, 128, 4)
    assert (cfg.moe_intermediate_size, cfg.num_experts_per_tok, cfg.router_width, cfg.n_shared_experts) == \
        (1280, 8, 320, 1)
    assert set(cell["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg.held == (0, 40) and cfg.layer_types == ("gqa", "kda", "kda", "kda") and cfg.vocab_size == 24576
    assert cell["published"] == {"num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}


def test_a_sequence_of_the_cell_holds_one_layers_pages_and_one_slot():
    cell = _cell()
    cfg = _cell_config(cell)
    kv = cell["engine"]["kv"]
    slots = cell["engine"]["scheduler"]["max_seqs"] + 1
    big = jax.eval_shape(lambda: init_cache(cfg, PagedKVConfig(kv["num_pages"], kv["page_size"], 2178), jnp.bfloat16,
                                            slots, 128))
    assert big["pages"].shape == (1, kv["num_pages"], 16, 2, 8, 128)
    assert big["kda"].shape == (3, slots, 64, 128, 128) and big["kda"].dtype == jnp.float32
    assert big["conv"].shape == (3, slots, 3, 24576)
    assert slot_state_bytes(cfg) == 3 * 4_194_304
    per_token = int(np.prod(big["pages"].shape[3:])) * 2
    assert per_token == 4096 and per_token * 16 == 65536                 # 2 x 8 heads x 128 x bfloat16; a page
    small = init_cache(CFG, PagedKVConfig(64, 16, 20), jnp.float32, n_slots=4, chunk=32)
    assert {k: v.shape for k, v in small.items()} == {
        "pages": (2, 64, 16, 2, 2, 32), "kda": (6, 4, 4, 32, 32), "conv": (6, 4, 3, 384)}
