"""Trinity (``afmoe``: gated grouped-query attention of two kinds, window
layers with rotary positions beside position-free full layers, a sandwich of
four norms, a leading dense layer and a sigmoid router of which a share of the
experts is held) against its plain reference (``benchmark/refs/trinity.py``)
on the CPU at a small size: the full-sequence model, the reference's
controls, the geometry's counts, and the cell's sizes by ``eval_shape``.  The
twin and the engine are in ``test_trinity_engine.py``, the shares in
``test_trinity_share.py``.

Small size (the rehearsal sizes of ``trinity-large-preview-serve-1chip``): 5
layers ``[S | S S S F]`` (the published list kept whole: layer 0 and layers
8-11); hidden 128; 4 query and 2 key heads of 32; a window of 64; a router of
16 experts of width 64, 4 a token, of which this share holds experts 8-15;
one shared expert; rings with a slack of 64 tokens.  Matrices at ``1 /
sqrt(fan_in)``, norm weights away from 1, a selection bias of the size of the
score gaps.  Everything is float32; the tolerance is its rounding through
five layers.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.cache_zoo import cache_geometry
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.trinity import FULL, SLIDING, TrinityConfig, TrinityForCausalLM
from deepspeed_tpu.models.trinity_cache import init_cache, ring_pages

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark")
sys.path.insert(0, BENCH)
from refs import trinity as ref  # noqa: E402

CFG = TrinityConfig(vocab_size=512, hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
                    num_hidden_layers=5, num_dense_layers=1, expert_layers_from=8, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=32, sliding_window=64, num_experts=8, router_experts=16,
                    first_expert=8, run_tokens=64, max_position_embeddings=4096, dtype=jnp.float32,
                    param_dtype=jnp.float32)
TOL = 2e-4
WIDTH = 352     # the one padded width of this family's references: past two laps of a ring of 144 rows


def ref_cfg(cfg):
    """The configuration as the reference reads it: the file's keys."""
    keys = ("num_hidden_layers", "num_dense_layers", "expert_layers_from", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta", "sliding_window", "rms_norm_eps", "num_experts",
            "first_expert", "num_experts_per_tok", "route_norm", "route_scale", "num_shared_experts", "mup_enabled",
            "hidden_size", "vocab_size")
    return {**{k: getattr(cfg, k) for k in keys}, "layer_types": list(cfg.layer_types)}


def draw(cfg, seed=0):
    p = nn.meta.unbox(jax.jit(TrinityForCausalLM(cfg).init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))

    def one(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "expert_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        return x                           # matrices: lecun_normal; the embedding N(0, 0.02) times sqrt(hidden)

    return jax.tree_util.tree_map_with_path(one, p)


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, WIDTH)


_REF = jax.jit(lambda p, t, without: ref.forward(p, t, ref_cfg(CFG), without=without)[0], static_argnums=2)


def reference(params, ids, without=()):
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF(params, jnp.asarray(ids), tuple(without)))


@pytest.fixture(scope="module")
def want(params, ids):
    return reference(params, ids)


# ---------------------------------------------------------------- (a) the configuration


def test_the_layers_run_are_the_dense_ones_and_the_expert_layers_from_their_place():
    assert CFG.kinds == (SLIDING, ) * 4 + (FULL, ) and CFG.count(SLIDING) == 4 and CFG.count(FULL) == 1
    assert [CFG.index(i) for i in range(5)] == [0, 1, 2, 3, 0]
    assert CFG.held == (8, 8) and CFG.router_width == 16
    assert ref.layer_kinds(ref_cfg(CFG)) == list(CFG.kinds)
    full = TrinityConfig()
    assert full.kinds == ((SLIDING, ) * 3 + (FULL, )) * 15 and full.held is None and full.count(FULL) == 15
    # the published list, kept whole in a configuration of five layers
    cut = TrinityConfig(num_hidden_layers=5, num_dense_layers=1, expert_layers_from=8, layer_types=full.layer_types,
                        num_experts=32, router_experts=256)
    assert cut.kinds == CFG.kinds and cut.held == (0, 32)
    straight_on = TrinityConfig(num_hidden_layers=5, num_dense_layers=1)
    assert straight_on.kinds == (SLIDING, SLIDING, SLIDING, FULL, SLIDING)


@pytest.mark.parametrize("field, value, words", [("score_func", "softmax", "scores by sigmoid"),
                                                 ("n_group", 2, "group-limited choice"),
                                                 ("num_limited_groups", 4, "group-limited choice"),
                                                 ("rope_scaling", {"type": "yarn"}, "scaled rotary"),
                                                 ("hidden_act", "gelu", "SwiGLU"),
                                                 ("tie_word_embeddings", True, "tie_word_embeddings"),
                                                 ("layer_types", ("chunked_attention", ) * 60, "only sliding")])
def test_what_is_not_computed_is_refused_in_words(field, value, words):
    with pytest.raises(NotImplementedError, match=words):
        TrinityConfig(**{field: value})


def test_a_share_or_a_cut_that_does_not_fit_is_refused():
    with pytest.raises(ValueError, match="inside\\s+the router"):
        TrinityConfig(num_experts=32, router_experts=256, first_expert=250)
    with pytest.raises(ValueError, match="too few"):
        TrinityConfig(num_hidden_layers=5, num_dense_layers=1, expert_layers_from=58,
                      layer_types=TrinityConfig().layer_types)


# ---------------------------------------------------------------- (b) the model


def _full(params, tokens):
    return TrinityForCausalLM(CFG).apply(params, tokens)


_FULL = jax.jit(_full)      # one program, whatever test calls it


def test_full_sequence_model_matches_reference(params, ids, want):
    with jax.default_matmul_precision("highest"):
        got = _FULL(params, jnp.asarray(ids[None]))[0]
    assert got.shape == (WIDTH, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("without", ref.CONTROLS)
def test_the_references_controls_are_far_from_the_reference(params, ids, want, without):
    """What the on-chip test holds its limits against: the reference with no
    window, rotary on the full layer too, no output gate, no head norms, one
    held expert fewer, ``route_scale`` 1."""
    assert float(np.abs(reference(params, ids, (without, )) - want).max()) > 100 * TOL


def test_an_unknown_control_is_refused(params, ids):
    with pytest.raises(ValueError, match="unknown controls"):
        ref.forward(params, jnp.asarray(ids[:8]), ref_cfg(CFG), without=("windows", ))


@pytest.mark.parametrize("zeroed", ["layers_0']['self_attn']['gate_proj", "layers_4']['self_attn']['q_norm",
                                    "layers_2']['post_attention_layernorm", "layers_3']['mlp']['expert_bias",
                                    "layers_0']['mlp']['up_proj", "w_down", "shared_experts"])
def test_every_part_matters_under_these_weights(params, ids, want, zeroed):
    """The guard of the guard: with one part's parameters zeroed the
    comparison fails by an order of magnitude or more."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    with jax.default_matmul_precision("highest"):
        got = _FULL(broken, jnp.asarray(ids[None]))[0]
    assert float(np.abs(np.asarray(got) - want).max()) > 30 * TOL


def test_a_key_behind_the_window_reaches_a_query_through_the_full_layer_alone(params, ids):
    """The last position's logits move when a token 200 positions back
    changes; with the full layer's output projection zeroed they move far
    less than with it: behind five windows of 64 (a window layer passes a
    token on by 63 positions a layer: 4 layers reach 252) little else carries it."""
    moved = np.array(ids)
    moved[WIDTH - 330] = (moved[WIDTH - 330] + 7) % CFG.vocab_size
    blind = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if "layers_4']['self_attn']['o_proj" in jax.tree_util.keystr(path) else x,
        params)
    a, b = (reference(params, t)[-1] for t in (ids, moved))
    c, d = (reference(blind, t)[-1] for t in (ids, moved))
    assert float(np.abs(a - b).max()) > 10 * TOL and float(np.abs(c - d).max()) < 1e-6


# ---------------------------------------------------------------- (c) the geometry's counts


def test_geometry_ends_a_run_where_the_rings_slack_ends_and_counts_the_rings():
    g = cache_geometry(CFG, 16)
    assert g.state_slots and g.chunk_runs and g.window == 64 and g.run_tokens == 64
    assert ring_pages(CFG, 16) == 9 and g.ring_rows == 144
    assert g.chunk_limit(0, 32) == 32 and g.chunk_limit(96, 128) == 64 and g.chunk_limit(5000, 64) == 64
    # a chunk of 32 from position 40: every query's window rows; the ring held once, seen by the last query
    counts = g.state_counts(40, 32)
    t = np.arange(40, 72)
    assert counts["window_rows_visible"] == int(np.minimum(t + 1, 64).sum())
    assert counts["ring_rows_held"] == 144 and counts["ring_rows_seen"] == 64
    # four fused decode rounds from position 10: the ring held a round, seen by each round's query
    counts = g.state_counts(10, 4, calls=4)
    assert counts["ring_rows_held"] == 4 * 144 and counts["ring_rows_seen"] == 11 + 12 + 13 + 14
    assert "ring_rows_held" not in g.state_counts(10, 0)


# ---------------------------------------------------------------- (d) the cell's sizes


def _cell_config():
    import harness
    with open(os.path.join(BENCH, "configs", "trinity-large-preview-serve-1chip.json")) as f:
        raw = json.load(f)
    return raw, harness.program_config(raw)


def test_the_cells_parameter_count_is_the_programs_own():
    raw, cfg = _cell_config()
    shapes = jax.eval_shape(TrinityForCausalLM(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    leaves = jax.tree.leaves(nn.meta.unbox(shapes))
    count = sum(int(np.prod(leaf.shape)) for leaf in leaves)
    assert count == raw["parameters"]["count"] == 4321903872
    assert raw["parameters"]["bytes_bfloat16"] == 2 * count
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert cfg.kinds == (SLIDING, ) * 4 + (FULL, ) and cfg.held == (0, 32) and cfg.router_width == 256
    # every published width, and the reduced keys beside their published values
    for key, value in {"hidden_size": 3072, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
                       "moe_intermediate_size": 3072, "intermediate_size": 12288, "sliding_window": 4096,
                       "num_experts_per_tok": 4, "route_scale": 2.448}.items():
        assert raw[key] == value
    assert raw["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
                                "vocab_size": 200192}
    assert sorted(raw["reduced"]) == sorted(raw["published"])
    assert len(raw["layer_types"]) == 60


def test_the_cells_slot_and_page_bytes():
    raw, cfg = _cell_config()
    e = raw["engine"]
    kv = PagedKVConfig(num_pages=e["kv"]["num_pages"], page_size=e["kv"]["page_size"], max_pages_per_seq=2100)
    sched = e["scheduler"]
    cache = jax.eval_shape(lambda: init_cache(cfg, kv, jnp.bfloat16, sched["max_seqs"] + 1, sched["prefill_chunk"]))
    n_ring = ring_pages(cfg, kv.page_size)
    assert n_ring == -(-(4096 + cfg.run_tokens) // 16) + 1
    assert cache["ring"].shape == (4, 1 + 33 * n_ring, 16, 2, 8, 128)
    assert cache["pages"].shape == (1, kv.num_pages, 16, 2, 8, 128)
    page_bytes = 16 * 2 * 8 * 128 * 2
    assert page_bytes == 65536                                      # 4,096 B a token in the full layer
    assert 4 * n_ring * page_bytes == raw["assumed"]["bytes"]["rings_a_sequence"]
    assert cache["pages"].size * 2 == raw["assumed"]["bytes"]["pages"]
    assert cache["ring"].size * 2 == raw["assumed"]["bytes"]["rings"]
    geometry = cache_geometry(cfg, 16)
    assert geometry.run_tokens == cfg.run_tokens >= sched["prefill_chunk"] and geometry.ring_rows == 16 * n_ring
