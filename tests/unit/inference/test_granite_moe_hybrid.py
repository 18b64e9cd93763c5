"""Granite 4.0-H with routed experts beside the shared MLP (granite-4.0-h-small's
layer: a softmax router over the chosen logits, the experts held here a share
of the router's, Mamba-2 states in slots, a position-free attention layer in
pages) against its plain reference (``benchmark/refs/granitemoehybrid.py``) on
the CPU at a small size: the full-sequence model, the serving twin through
slots and pages, the engine over it with slots freed and taken again, the
shares of one layer, a padded slot, and the sizes at the published widths.

Small size: 8 layers, two periods of [Mamba, Mamba, attention, Mamba] (so the
blocks read their layer of the periods' stack of banks); hidden 128; 4 query
and 2 key heads of 32; 8 Mamba heads of 32 over a state of 32; a router of 8,
3 a token, experts 4-7 held (width 64) beside a shared MLP of 96; page 16,
chunks of 32.  Weights as ``test_granite_hybrid.py`` draws them (the published
Mamba-2 initialisation; matrices at ``1 / sqrt(fan_in)``; norm weights away
from 1); the router's matrix times 4, so that the three chosen logits lie
well apart from the fourth and float32 rounding moves no choice, and the
experts' second matrix times 4, so that one expert's absence shows.
Everything is float32; the tolerance is the dense sibling's.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import SlotPagesGeometry
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig, GraniteHybridForCausalLM, GraniteHybridLayer,
                                                 GraniteMoE)
from deepspeed_tpu.models.granite_hybrid_cache import (GraniteHybridForCausalLMWithCache, init_cache, kv_pack,
                                                       slot_state_bytes)
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.moe import sharded_moe

from reference_greedy import greedy
from test_granite_hybrid import _feed as sibling_feed
from test_granite_hybrid import _table

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import granitemoehybrid as ref  # noqa: E402

PAGE, CHUNK = 16, 32
PATTERN = ("mamba", "mamba", "attention", "mamba")
CFG = GraniteHybridConfig(vocab_size=512, hidden_size=128, intermediate_size=64, shared_intermediate_size=96,
                          num_hidden_layers=8, layer_types=PATTERN * 2, num_attention_heads=4, num_key_value_heads=2,
                          mamba_n_heads=8, mamba_d_head=32, mamba_d_state=32, num_local_experts=4,
                          num_experts_per_tok=3, router_experts=8, first_expert=4, max_position_embeddings=4096,
                          dtype=jnp.float32, param_dtype=jnp.float32)
REF_KEYS = ("num_attention_heads", "num_key_value_heads", "layer_types", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "rms_norm_eps", "attention_multiplier", "embedding_multiplier", "residual_multiplier",
            "logits_scaling", "num_local_experts", "num_experts_per_tok", "first_expert")
TOL = 2e-4
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)
#: the published sizes of granite-4.0-h-small as the benchmark's configuration cuts them
SMALL = dict(vocab_size=50176, hidden_size=4096, intermediate_size=768, shared_intermediate_size=1536,
             num_hidden_layers=10, layer_types=("mamba", ) * 5 + ("attention", ) + ("mamba", ) * 4,
             num_attention_heads=32, num_key_value_heads=8, mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
             num_local_experts=36, num_experts_per_tok=10, router_experts=72, attention_multiplier=0.0078125,
             logits_scaling=16.0)


def ref_cfg(cfg):
    return {f: getattr(cfg, f) for f in REF_KEYS}


def _draw(cfg, seed=0):
    p = nn.meta.unbox(GraniteHybridForCausalLM(cfg).init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "conv_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "embedding" in name or "router" in name or "w_down" in name:
            return x * 4.0                 # the embedding as the multiplier of 12 was made for; clear choices; experts
                                           # that weigh as much as the mixers
        return x                           # matrices: lecun_normal; A, dt_bias, D: the published initialisation

    return jax.tree_util.tree_map_with_path(draw, p)


@pytest.fixture(scope="module")
def params():
    return _draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 160).reshape(3, 160)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's logits of the whole sequences, and their router margins."""
    forward = jax.jit(lambda row: ref.forward(params, row, ref_cfg(CFG)))
    with jax.default_matmul_precision("highest"):
        out = [forward(jnp.asarray(row)) for row in ids]
    assert min(float(margin.min()) for _, margin in out) > 1e-5     # no choice that float32 rounding could move
    return [np.asarray(logits) for logits, _ in out]


def _full(params, tokens):
    return GraniteHybridForCausalLM(CFG).apply(params, tokens)


# ---------------------------------------------------------------- (a) the model


def test_a_config_states_its_share_of_the_router():
    assert CFG.router_width == 8 and CFG.held == (4, 4) and CFG.period == 4
    whole = GraniteHybridConfig(num_local_experts=72, num_experts_per_tok=10, hidden_size=4096, intermediate_size=768,
                                shared_intermediate_size=1536, mamba_n_heads=128)
    assert whole.router_width == 72 and whole.held is None
    dense = GraniteHybridConfig()
    assert dense.router_width == 0 and dense.held is None
    with pytest.raises(ValueError, match="must lie\\s+inside"):
        GraniteHybridConfig(**{**SMALL, "first_expert": 40})
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        GraniteHybridConfig(**{**SMALL, "num_experts_per_tok": 0})
    with pytest.raises(ValueError, match="belong to routed experts"):
        GraniteHybridConfig(num_experts_per_tok=2)


@pytest.mark.parametrize("length", [10, 129, 160])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_full)(params, jnp.asarray(ids[:1, :length]))[0]
    assert got.shape == (length, CFG.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want[0][:length], atol=TOL)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_term_matters_under_these_weights(params, ids, want, control):
    """The guard of the guard: the reference without the routed term, the
    shared MLP, the recurrent state or one held expert is far from the model."""
    with jax.default_matmul_precision("highest"):
        other = np.asarray(ref.forward(params, jnp.asarray(ids[0]), ref_cfg(CFG), without=(control, ))[0])
    assert float(np.abs(other - want[0]).max()) > 25 * TOL


def test_the_softmax_is_over_the_chosen_logits_as_published():
    """``dropless_moe``'s softmax over all experts, the chosen renormalised, is
    the published top-k of the logits and then the softmax over the k."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (50, 8))
    top, idx = jax.lax.top_k(logits, 3)
    published = jax.nn.softmax(top, axis=-1)
    gates = jax.nn.softmax(logits, axis=-1)
    vals, idx2 = jax.lax.top_k(gates, 3)
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_allclose(vals / vals.sum(-1, keepdims=True), published, rtol=1e-5)


def test_published_sizes_give_the_counts_the_configuration_file_states():
    """The parameter count, a slot's and a page's bytes of the benchmark's cut
    of granite-4.0-h-small (one period, 36 of 72 experts, half the vocabulary)
    by ``jax.eval_shape``."""
    cfg = GraniteHybridConfig(**SMALL)
    shapes = nn.meta.unbox(jax.eval_shape(GraniteHybridForCausalLM(cfg).init, jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    layer = shapes["params"]["periods"]["layer_0"]
    assert count(layer["block_sparse_moe"]["experts"]) == 36 * 3 * 4096 * 768 == 339_738_624
    assert count(layer["block_sparse_moe"]["router"]) == 4096 * 72
    assert count(layer["shared_mlp"]) == 4096 * 3072 + 1536 * 4096
    assert count(layer["mixer"]) == 4096 * 16768 + 8192 * 4096 + 4 * 8448 + 8448 + 3 * 128 + 8192
    assert count(shapes["params"]["periods"]["layer_5"]["mixer"]) == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert count(shapes) == 4_757_211_776
    assert cfg.conv_dim == 8448 and kv_pack(cfg) == 1
    cache = jax.eval_shape(lambda: init_cache(cfg, PagedKVConfig(17440, 16, 546), jnp.bfloat16, 33, 128))
    assert cache["pages"].shape == (1, 17440, 16, 2, 8, 128) and cache["ssm"].shape == (9, 33, 128, 64, 128)
    assert cache["conv"].shape == (9, 33, 3, 8448)
    assert slot_state_bytes(cfg) == 9 * 4_194_304
    assert int(np.prod(cache["pages"].shape[2:])) * 2 == 65_536                  # a page: 16 x 2 x 8 x 128 x bfloat16


# --------------------------------------------------- (b) the shares of one layer, a padded slot


WHOLE = dataclasses.replace(CFG, num_local_experts=8, router_experts=None, first_expert=0)


def share_of(first):
    return dataclasses.replace(CFG, num_local_experts=4, router_experts=8, first_expert=first)


@pytest.fixture(scope="module")
def block():
    """The uncut expert block's parameters, a shared MLP's and 96 tokens of unit size."""
    x = jax.random.normal(jax.random.PRNGKey(5), (96, CFG.hidden_size))
    layer = GraniteHybridLayer(WHOLE, "attention")
    p = nn.meta.unbox(layer.init(jax.random.PRNGKey(3), x, lambda mixer, h: (h, None)))["params"]
    moe = {**p["block_sparse_moe"], "router": {"kernel": 4.0 * p["block_sparse_moe"]["router"]["kernel"]}}
    return moe, p["shared_mlp"], x


def cut(moe, first):
    return {**moe, "experts": {k: w[first:first + 4] for k, w in moe["experts"].items()}}


def _ref_routed(cfg, moe, x):
    bank = {k: w[None] for k, w in moe["experts"].items()}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.routed(x, moe["router"]["kernel"], bank, 0, ref_cfg(cfg), "f32")[0])


def test_the_two_shares_add_up_to_the_uncut_layer(block):
    """Experts 0-3 and 4-7 of one layer, the shared MLP counted once, give the
    uncut reference's layer; neither share alone does."""
    moe, shared_mlp, x = block
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared(x, shared_mlp, "f32"))
    uncut = _ref_routed(WHOLE, moe, x) + shared
    parts = [_ref_routed(share_of(first), cut(moe, first), x) for first in (0, 4)]
    np.testing.assert_allclose(parts[0] + parts[1] + shared, uncut, atol=2e-5)
    assert all(np.abs(part + shared - uncut).max() > 1e-2 for part in parts)
    assert np.abs(parts[0] - parts[1]).max() > 1e-2


@pytest.mark.parametrize("first", [0, 4])
@pytest.mark.parametrize("grouped", [True, False], ids=["sorted", "dense"])
def test_the_programs_block_gives_its_shares_part(block, first, grouped, monkeypatch):
    """``GraniteMoE`` with ``held=(first, 4)`` against the reference given the
    same share, in both forms of the dropless layer."""
    moe, _, x = block
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: grouped)
    with jax.default_matmul_precision("highest"):
        got = GraniteMoE(share_of(first)).apply({"params": cut(moe, first)}, x[None])[0]
    np.testing.assert_allclose(got, _ref_routed(share_of(first), cut(moe, first), x), atol=2e-5)


def test_a_padded_slot_reaches_no_expert(block):
    """A decode bucket of 16 slots of which 5 carry a token: the held
    experts' row counts are those of the live rows' choices alone, the padded
    slots' rows of the routed sum are exact zeros in both forms, and what a
    padded slot holds reaches no live row."""
    moe, _, x = block
    cfg, params = share_of(4), {"params": cut(moe, 4)}
    live = np.zeros(16, bool)
    live[[0, 3, 4, 9, 15]] = True
    tokens = jnp.where(live[:, None], x[:16], 1e3)
    out, sown = GraniteMoE(cfg).apply(params, tokens[None], jnp.asarray(live)[None], mutable=["intermediates"])
    counts = np.asarray(sown["intermediates"]["exp_counts"][0])
    chosen = np.asarray(jax.lax.top_k(x[:16] @ moe["router"]["kernel"], 3)[1])[live]
    np.testing.assert_array_equal(counts, [(chosen == e).sum() for e in range(4, 8)])
    assert 0 < counts.sum() < 15 and not np.asarray(out)[0, ~live].any()
    np.testing.assert_allclose(np.asarray(out)[0, live], _ref_routed(cfg, cut(moe, 4), x[:16])[live], atol=2e-5)
    _, sown = GraniteMoE(cfg).apply(params, tokens[None], jnp.zeros((1, 16), bool), mutable=["intermediates"])
    assert not np.asarray(sown["intermediates"]["exp_counts"][0]).any()


# --------------------------------------------------- (c) the twin, through slots and pages


def _feed(params, rows, plans, tables, attention_impl="reference"):
    """The dense sibling's feeder (row ``i`` in the chunk lengths ``plans[i]``, 0: the row sits a step out;
    all rows in one batch) over this configuration."""
    return sibling_feed(params, rows, plans, tables, attention_impl, cfg=CFG)


def test_twin_chunks_then_decode_match_reference(params, ids, want):
    """Chunks that start and end inside a page (so rows of the rectangle are
    padding the router must not see), then decode."""
    got, _ = _feed(params, ids[:1], [[7, 32, 20, 12, 32, 5] + [1] * 24], _table(1 + np.arange(13), slot=1)[None])
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_three_sequences_in_scattered_slots_and_pages_in_one_batch(params, ids, want):
    """Rows in slots 4, 1 and 3 on pages that interleave; row 2 starts while
    rows 0 and 1 continue, a row sits steps out (its slots of the rectangle
    live for no expert), decode rows ride beside prefill chunks; the attention
    layers through the paged kernel (one key head a page head: ``kv_pack`` 1)."""
    tables = np.stack([_table(np.arange(1, 40, 3), slot=4), _table(np.arange(3, 42, 3), slot=1),
                       _table(np.arange(2, 41, 3), slot=3)])
    plans = [[32, 32, 32, 1, 1, 1, 1, 1] + [1] * 6,
             [17, 32, 32, 32, 3, 1, 1, 1] + [1] * 6,
             [0, 0, 32, 32, 32, 5, 1, 1] + [1] * 6]
    got, _ = _feed(params, ids, plans, tables, attention_impl="flash")
    for i in range(3):
        np.testing.assert_allclose(got[i], want[i][:len(got[i])], atol=TOL)


def test_the_dense_sibling_keeps_its_parameter_tree_and_its_path():
    """``num_local_experts`` 0: no expert block in the tree, the shared MLP of
    ``shared_intermediate_size`` the layer's MLP."""
    from test_granite_hybrid import CFG as DENSE
    shapes = nn.meta.unbox(jax.eval_shape(GraniteHybridForCausalLM(DENSE).init, jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))
    layer = shapes["params"]["periods"]["layer_0"]
    assert sorted(layer) == ["input_layernorm", "mixer", "post_attention_layernorm", "shared_mlp"]
    assert layer["shared_mlp"]["input_linear"]["kernel"].shape == (2, 256, 512)
    moe = nn.meta.unbox(jax.eval_shape(GraniteHybridForCausalLM(CFG).init, jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8), jnp.int32)))["params"]["periods"]["layer_0"]
    assert sorted(moe) == ["block_sparse_moe", "input_layernorm", "mixer", "post_attention_layernorm", "shared_mlp"]
    assert moe["block_sparse_moe"]["experts"]["w_down"].shape == (2, 4, 64, 128)
    assert moe["block_sparse_moe"]["router"]["kernel"].shape == (2, 128, 8)


# ------------------------------------------------------------------ (d) the engine


def _engine(params, max_seqs=4):
    return InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(
        kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                         decode_bucket=max_seqs),
        max_new_tokens=10, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32))


def test_engine_serves_two_sequences_and_reuses_their_slots(params, ids):
    """``InferenceEngineV2 -> warm_all -> generate`` by the registry's entry:
    prefill in chunks of 32, fused decode with two of the bucket's four slots
    padding, slots released at the flush and taken again by the other
    sequence; both rounds give the full-sequence model's greedy tokens."""
    continuations = [greedy(_full, params, ids[i, :n], 10, 96, "highest") for i, n in ((0, 70), (1, 45))]
    eng = _engine(params)
    assert isinstance(cache_twin(CFG).model(CFG, page_size=PAGE), GraniteHybridForCausalLMWithCache)
    assert isinstance(eng.kv.geometry, SlotPagesGeometry) and cache_geometry(CFG, PAGE).state_bytes == \
        slot_state_bytes(CFG) == 4 * 6 * 8 * 32 * 32
    assert eng._experts_per_tok == 3 and eng._router_experts == 8
    assert eng.warm_all()["fallback"] == 0
    prompts = [ids[0, :70].tolist(), ids[1, :45].tolist()]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=10)
        assert eng.kv.slot_allocator.free_pages == 4 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=10)
    assert first == continuations and second == continuations[::-1]
    rows = [r.to_row() for r in eng.anatomy.steps]
    assert sum(r["expert_rows"] for r in rows) == 3 * sum(r["tokens_real"] for r in rows) > 0
