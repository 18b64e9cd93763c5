"""MiniCPM-SALA (block-selected sparse attention over pages in one layer of
four, Lightning linear attention whose matrix states live in state slots,
MiniCPM's muP scalings) against its plain reference
(``benchmark/refs/minicpm_sala.py``, whose recurrence goes position by
position) on the CPU at a small size: the full-sequence model, the three
forms of the recurrence, the one-position kernel, what a cut in depth keeps,
the cell's sizes by ``eval_shape``.  The selection is in
``test_minicpm_sala_select.py``, the twin in ``test_minicpm_sala_twin.py``,
the engine in ``test_minicpm_sala_engine.py``.

Small size: 4 layers [minicpm4, lightning-attn, lightning-attn, minicpm4]
standing for the published layers 9-12; hidden 128; 4 query and 2 key heads
of 32; 4 linear heads of 32; compressed keys of 16 tokens at a stride of 8,
blocks of 32, a window of 64, 2 chosen blocks, dense under 128: a query from
position 160 on leaves blocks out.  Matrices at ``1 / sqrt(fan_in)``, norm
weights away from 1.  Everything is float32; the tolerance is its rounding
through four layers.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.minicpm_sala import (PUBLISHED_MIXERS, MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
                                               decay_slopes, lightning_chunk, lightning_recurrent,
                                               lightning_update_reference)
from deepspeed_tpu.ops.lightning_update import FRESH, LIVE, lightning_update

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark")
sys.path.insert(0, BENCH)
from refs import minicpm_sala as ref  # noqa: E402

SPARSE = {"kernel_size": 16, "kernel_stride": 8, "block_size": 32, "init_blocks": 1, "window_size": 64, "topk": 2,
          "dense_len": 128}
CFG = MiniCPMSALAConfig(vocab_size=512, hidden_size=128, intermediate_size=192, num_hidden_layers=4,
                        num_attention_heads=4, num_key_value_heads=2, head_dim=32, lightning_nh=4, lightning_nkv=4,
                        lightning_head_dim=32, mixer_types=PUBLISHED_MIXERS[9:12] + PUBLISHED_MIXERS[16:17],
                        sparse=SPARSE, first_layer=9, published_layers=32,
                        max_position_embeddings=4096, dtype=jnp.float32, param_dtype=jnp.float32)
TOL = 2e-4
LENGTH = 240


def ref_cfg(cfg):
    """The configuration as the reference reads it: the file's keys."""
    return {"num_hidden_layers": cfg.num_hidden_layers, "mixer_types": list(cfg.mixer_types),
            "num_attention_heads": cfg.num_attention_heads, "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "lightning_nh": cfg.lightning_nh, "lightning_head_dim": cfg.lightning_head_dim,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta, "scale_emb": cfg.scale_emb,
            "scale_depth": cfg.scale_depth, "mup_denominator": cfg.mup_denominator,
            "dim_model_base": cfg.dim_model_base, "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
            "sparse": cfg.sparse_config, "first_layer": cfg.first_layer, "published_layers": cfg.published_layers}


def draw(cfg, seed=0):
    p = nn.meta.unbox(jax.jit(MiniCPMSALAForCausalLM(cfg).init)(jax.random.PRNGKey(seed),
                                                                 jnp.zeros((1, 8), jnp.int32)))

    def one(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "embedding" in name:
            return x * 4.0                 # rows of the order of 1 under scale_emb
        return x                           # matrices: lecun_normal

    return jax.tree_util.tree_map_with_path(one, p)


_full = MiniCPMSALAForCausalLM(CFG).apply


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, LENGTH)


@pytest.fixture(scope="module")
def want(params, ids):
    """(the reference's logits, its selection margins)."""
    return jax.tree.map(np.asarray, jax.jit(lambda p, t: ref.forward(p, t, ref_cfg(CFG)))(params, jnp.asarray(ids)))


def rel(got, want):
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


# ---------------------------------------------------------------- (a) the model


def test_the_layers_run_by_kind_and_a_cut_keeps_the_published_scalings():
    assert CFG.mixer_types == ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
    assert CFG.runs == (("minicpm4", 0, 1), ("lightning-attn", 1, 2), ("minicpm4", 3, 1))
    assert CFG.count("lightning-attn") == 2 and CFG.count("minicpm4", before=3) == 1
    full = MiniCPMSALAConfig()
    assert full.mixer_types == PUBLISHED_MIXERS and full.count("minicpm4") == 8 and len(full.runs) == 9
    assert full.list_blocks == 128 and full.sparse_config["topk"] == 64
    # the muP residual scale is the published one at any depth
    for depth in (2, 8, 32):
        assert MiniCPMSALAConfig(num_hidden_layers=depth).residual_scale == pytest.approx(1.4 / 32**0.5)
    # the cell's layers: the published 9-16
    cell = MiniCPMSALAConfig(num_hidden_layers=8, mixer_types=PUBLISHED_MIXERS[9:17], first_layer=9,
                             published_layers=32)
    assert cell.mixer_types == ("minicpm4", ) + ("lightning-attn", ) * 6 + ("minicpm4", )
    assert ref.runs(list(cell.mixer_types)) == [list(r) for r in cell.runs]


def test_a_layers_decay_is_its_published_indexs_under_a_cut():
    full = MiniCPMSALAConfig()
    cut = MiniCPMSALAConfig(num_hidden_layers=8, mixer_types=PUBLISHED_MIXERS[9:17], first_layer=9,
                            published_layers=32)
    for i in (1, 6):
        np.testing.assert_allclose(decay_slopes(cut, i), decay_slopes(full, 9 + i), rtol=1e-6)
        np.testing.assert_allclose(decay_slopes(cut, i), ref.log_decay({"lightning_nh": 32, "num_hidden_layers": 8,
                                                                        "first_layer": 9, "published_layers": 32}, i),
                                   rtol=1e-6)
    # by hand: head 32 of layer 10 keeps exp(-2^-8 (1 - 10/31 + 1e-5)) a position
    assert float(jnp.exp(decay_slopes(cut, 1))[-1]) == pytest.approx(np.exp(-2.0**-8 * (1 - 10 / 31 + 1e-5)), rel=1e-6)
    assert float(jnp.max(decay_slopes(cut, 7))) < 0


def test_full_sequence_model_equals_the_reference(params, ids, want):
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(_full)(params, jnp.asarray(ids)[None])[0])
    assert rel(got, want[0]) < TOL
    # the sequence is long enough for the selection to leave blocks out, and the margin says where
    assert np.isinf(want[1][:160]).all() and np.isfinite(want[1][-32:]).all()


def test_the_references_controls_change_the_logits(params, ids, want):
    """What ``tests/tpu/minicpm_sala_check.py`` asks of the chip: the
    reference without the state term, with a dense walk in the sparse layers'
    place and with the selection one block further on reads differently
    (under ``dense_len`` the last two change nothing)."""
    fwd = jax.jit(lambda p, t, w: ref.forward(p, t, ref_cfg(CFG), without=w)[0], static_argnums=2)
    for without, least in (("state", 0.05), ("sparse", 1e-3), ("shift", 1e-3)):
        changed = np.asarray(fwd(params, jnp.asarray(ids), (without, )))
        err = np.linalg.norm(changed - want[0], axis=-1) / np.linalg.norm(want[0], axis=-1)
        assert np.median(err[-32:]) > least, (without, np.median(err[-32:]))
        if without != "state":
            assert err[:128].max() == 0


# ------------------------------------------------------ (b) the recurrence's forms


def _recurrence_inputs(b=3, c=70, h=4, d=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(keys[i], (b, c, h, d)) for i in range(3))
    return q * d**-0.5, k, v, decay_slopes(CFG, 1), 0.3 * jax.random.normal(keys[3], (b, h, d, d))


def test_recurrent_chunked_and_one_position_forms_agree():
    q, k, v, log_decay, state = _recurrence_inputs()
    with jax.default_matmul_precision("highest"):
        o_r, s_r = lightning_recurrent(q, k, v, log_decay, state)
        for block in (16, 128):        # several blocks with a ragged last one, and one block wider than the chunk
            o_c, s_c = jax.jit(lambda *a: lightning_chunk(*a, block=block))(q, k, v, log_decay, state)
            np.testing.assert_allclose(o_c, o_r, atol=2e-5, rtol=2e-5)
            np.testing.assert_allclose(s_c, s_r, atol=2e-5, rtol=2e-5)
        # a row whose chunk carries fewer tokens: the positions behind them leave the state alone
        lens = jnp.asarray([70, 23, 0])
        o_c, s_c = lightning_chunk(q, k, v, log_decay, state, lens, block=16)
        for i, n in enumerate([70, 23, 0]):
            o_i, s_i = lightning_recurrent(q[i:i + 1, :n], k[i:i + 1, :n], v[i:i + 1, :n], log_decay, state[i:i + 1])
            np.testing.assert_allclose(o_c[i, :n], o_i[0], atol=2e-5, rtol=2e-5)
            np.testing.assert_allclose(s_c[i], s_i[0], atol=2e-5, rtol=2e-5)
        o_1, s_1 = lightning_update_reference(q[:, 0], k[:, 0], v[:, 0], log_decay, state)
    np.testing.assert_allclose(o_1, o_r[:, 0], atol=1e-6)


def test_fast_heads_do_not_overflow_in_the_chunked_form():
    q, k, v, _, state = _recurrence_inputs(b=1, c=256)
    log_decay = jnp.asarray([-30.0, -3.0, -0.5, -1e-4])       # a head that forgets everything in a position
    o_c, s_c = lightning_chunk(q, k, v, log_decay, state)
    o_r, s_r = lightning_recurrent(q, k, v, log_decay, state)
    assert np.isfinite(o_c).all() and np.isfinite(s_c).all()
    np.testing.assert_allclose(o_c, o_r, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("heads", [4, 48])
def test_lightning_update_kernel_advances_the_arena_in_place(heads):
    """``ds_lightning_update`` (interpreted) against the ``jax.numpy`` form:
    rows in scattered slots of layer 1 of an arena of 3, a fresh row, a row
    that carries no token; nothing else of the arena moves.  48 heads go as
    two head blocks of 24."""
    b, d, slots = 5, 32, 7
    keys = jax.random.split(jax.random.PRNGKey(heads), 5)
    arena = jax.random.normal(keys[0], (3, slots, heads, d, d))
    q, k = (jax.random.normal(keys[i], (b, heads, d)) for i in (1, 2))
    v = jax.random.normal(keys[3], (b, heads, d))
    log_decay = -jnp.exp2(-8.0 * jnp.arange(1, heads + 1) / heads)
    slot = jnp.asarray([3, 6, 1, 0, 5])
    flags = jnp.asarray([LIVE, LIVE | FRESH, LIVE, 0, LIVE])
    o, new = lightning_update(arena, jnp.asarray(1), slot, flags, q, k, v, log_decay)
    start = jnp.where((flags & FRESH)[:, None, None, None] != 0, 0.0, arena[1, slot])
    o_want, s_want = lightning_update_reference(q, k, v, log_decay, start)
    live = np.asarray(flags & LIVE) != 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o_want)[live], atol=1e-5, rtol=1e-5)
    assert not np.asarray(o)[~live].any()
    np.testing.assert_allclose(np.asarray(new[1, slot])[live], np.asarray(s_want)[live], atol=1e-5, rtol=1e-5)
    untouched = np.ones((3, slots), bool)
    untouched[1, np.asarray(slot)[live]] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(arena)[untouched])


# --------------------------------------------------------- (c) the cell's sizes


def test_parameter_count_slots_and_pages_at_the_cells_sizes():
    import harness
    with open(os.path.join(BENCH, "configs", "minicpm-sala-9b-serve-1chip.json")) as f:
        config = json.load(f)
    cfg = harness.program_config(config)
    assert cfg.mixer_types == PUBLISHED_MIXERS[9:17] and cfg.first_layer == 9 and cfg.published_layers == 32
    assert cfg.sparse_config == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "init_blocks": 1,
                                 "window_size": 2048, "topk": 64, "dense_len": 8192}
    abstract = nn.meta.unbox(jax.eval_shape(MiniCPMSALAForCausalLM(cfg).init, jax.random.PRNGKey(0),
                                            jnp.zeros((1, 128), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))  # noqa: E731
    assert count(abstract["run_0"]) == count(abstract["run_2"]) == 253_763_840
    assert count(abstract["run_1"]) == 6 * 285_221_248
    assert count(abstract["norm"]) == 4096
    assert count(abstract["embed_tokens"]) + count(abstract["lm_head"]) == 601_686_016
    assert count(abstract) == 2_820_545_280 == config["parameters"]["count"]
    from deepspeed_tpu.models.cache_zoo import cache_twin
    from deepspeed_tpu.models.llama_cache import PagedKVConfig
    from deepspeed_tpu.models.minicpm_sala_cache import slot_state_bytes
    kv = PagedKVConfig(num_pages=config["engine"]["kv"]["num_pages"], page_size=16, max_pages_per_seq=4162)
    cache = jax.eval_shape(lambda: cache_twin(cfg).init_cache(cfg, kv, jnp.bfloat16, 33, 128))
    assert cache["pages"].shape == (2, kv.num_pages, 16, 2, 2, 128) and cache["ckeys"].shape == (2, 33, 4162, 2, 128)
    assert cache["state"].shape == (6, 33, 32, 128, 128) and cache["state"].dtype == jnp.float32
    assert slot_state_bytes(cfg) == 6 * 32 * 128 * 128 * 4 == 12_582_912
    # a token: 2 KB of pages and 64 B of compressed keys (a slot: 4,162 columns of 1 KB over both layers)
    assert 2 * 2 * 2 * 128 * 2 == 2048 and 2 * 2 * 128 * 2 // 16 == 64
