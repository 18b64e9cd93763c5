"""Xing4.0 (latent attention through latent pages, a four-stream
hyper-connected residual, sigmoid-routed experts beside a shared one) against
its plain reference (``benchmark/refs/xing4.py``, the expanded attention a
head at a time) on the CPU at a small size: the full-sequence model, the
absorbed kernel, the hyper-connected sublayer; the serving twin through pages
in rectangles and in two row groups and the engine are in
``test_xing4_twin.py``, which takes this file's sizes and weights.

Small size: one dense and two expert layers; hidden 64, four streams; 4 heads
of 16 + 8 (values 16) over latents of 32 + 8; 8 experts of 32, 2 a token;
page 16, chunks of 32.

The weights are drawn so that the new mathematics is visible (under the
benchmark's N(0, 0.02^2) ``Hres`` is nearly uniform, ``Hpre`` 0.5 and the
router's bias nothing): ``b_res`` with a strong diagonal, ``a`` of order 1,
``phi`` at ``1 / sqrt(n C)``, a router bias of the scores' own size, norm
weights away from 1.  Everything is float32; the tolerance is its rounding.
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
from deepspeed_tpu.models.xing4 import (HyperConnection, Xing4Config, Xing4ForCausalLM, expanded_attention,
                                        rope_inv_freq, sinkhorn)
from deepspeed_tpu.ops import mla_attention

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import xing4 as ref  # noqa: E402

PAGE, CHUNK = 16, 32
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64, "type": "yarn"}
CFG = Xing4Config(vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
                  first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2, rope_scaling=YARN,
                  max_position_embeddings=4096, dtype=jnp.float32, param_dtype=jnp.float32)
REF_CFG = {**{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}, "rope_scaling": YARN}
TOL = 2e-4
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=13)


def _draw(cfg, seed=0):
    p = nn.meta.unbox(jax.jit(Xing4ForCausalLM(cfg).init)(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)))
    n = cfg.hc_mult

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        noise = jax.random.normal(key, x.shape)
        if name.endswith("['a']"):                     # of order 1
            return 1.0 + 0.3 * noise
        if name.endswith("['b']"):                     # Hpre, Hpost of every size; Hres with a strong diagonal
            diag = jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])
            return noise + diag
        if name.endswith("['phi']"):
            return noise / np.sqrt(x.shape[-2])
        if "e_score_correction_bias" in name:          # of the scores' own size
            return 0.2 * noise
        if "norm" in name:                             # norm weights away from 1
            return 1.0 + 0.3 * noise
        return x                                       # matrices: lecun_normal, the embedding N(0, 0.02^2)

    return jax.tree_util.tree_map_with_path(draw, p)


@pytest.fixture(scope="module")
def params():
    return _draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 120).reshape(3, 120)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's logits of the whole sequences."""
    fwd = jax.jit(lambda p, row: ref.forward(p, row, REF_CFG)[0])
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fwd(params, jnp.asarray(row))) for row in ids]


# ---------------------------------------------------------------- (a) the model


def test_the_cells_sizes_give_the_parameter_count_the_configuration_states():
    """One dense and six expert layers at every published width: 5,538M."""
    cell = Xing4Config(num_hidden_layers=7, first_k_dense_replace=1)
    shapes = jax.eval_shape(Xing4ForCausalLM(cell).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(shapes)))
    attention = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584 + 768 + 512
    hyper = 2 * (14336 * 24 + 14336 + 3 + 24)
    layer = attention + hyper + 2 * 3584
    dense, experts = 3 * 3584 * 9216, 65 * 3 * 3584 * 1024 + 3584 * 64 + 64
    assert count == 7 * layer + dense + 6 * experts + 2 * 131072 * 3584 + 3584 == 5_537_859_578
    with open(os.path.join(os.path.dirname(ref.__file__), "..", "configs", "xing4.0-29b-a4b-serve-1chip.json")) as f:
        import json
        assert json.load(f)["parameters"]["count"] == count


def test_yarn_blends_the_frequencies_between_the_published_dimensions():
    full = Xing4Config(rope_scaling={**YARN, "original_max_position_embeddings": 4096})
    freq, plain = np.asarray(rope_inv_freq(full)), 1.0 / 10000**(np.arange(0, 64, 2) / 64)
    # 32 rotations fit into 4096 positions up to dimension 10.5, one up to 22.5: ramp from 10 to 23
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64, rtol=1e-6)
    assert np.all(np.diff(freq) < 0) and plain[16] / 64 < freq[16] < plain[16]
    assert abs(full.softmax_scale - 192**-0.5 * (0.1 * np.log(64) + 1)**2) < 1e-9


def _full(params, tokens):
    """The full-sequence model; jitted where it is called, one program a shape."""
    return Xing4ForCausalLM(CFG).apply(params, tokens)


@pytest.mark.parametrize("length", [97])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_full)(params, jnp.asarray(ids[:2, :length]))
    for i in range(2):
        np.testing.assert_allclose(np.asarray(got[i]), want[i][:length], atol=TOL)


@pytest.mark.parametrize("zeroed", ["dense_layers_0']['attn_hc']['b", "layers']['mlp_hc']['phi",
                                    "layers']['mlp']['e_score_correction_bias", "layers']['self_attn']['q_b_proj"])
def test_every_part_matters_under_these_weights(params, ids, want, zeroed):
    """A part that is left out moves the logits by far more than the tolerance."""
    cut = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(_full)(cut, jnp.asarray(ids[:1, :64])))[0]
    assert np.abs(got - want[0][:64]).max() > 50 * TOL


def test_the_reference_in_blocks_gives_the_unblocked_numbers(params, monkeypatch):
    row = jnp.asarray(np.random.default_rng(3).integers(1, CFG.vocab_size, 256))
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(ref, "_ROWS", 64)
        blocked, margin = jax.jit(lambda p: ref.forward(p, row, REF_CFG, first=192))(params)
        monkeypatch.setattr(ref, "_ROWS", 4096)
        whole, margin_whole = jax.jit(lambda p: ref.forward(p, row, REF_CFG, first=192))(params)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), atol=2e-5)
    np.testing.assert_allclose(np.asarray(margin), np.asarray(margin_whole), atol=1e-6)


# ------------------------------------------------- (b) the hyper-connected sublayer


def test_sinkhorn_gives_a_doubly_stochastic_matrix_in_twenty_steps():
    """Entries anywhere in the clamp's range [-30, 30].  Where one entry a row
    leads (what a trained ``b_res`` with its strong diagonal gives) rows and
    columns sum to 1 within 1e-5; from any matrix in the range the columns do
    (the last step is theirs) and the rows are as near as twenty steps bring
    them, which is the published truncation and the reference's too."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    lead = jax.vmap(lambda k: jax.random.permutation(k, jnp.eye(4)))(jax.random.split(k1, 256))
    logits = jnp.where(lead > 0, jax.random.uniform(k2, (256, 4, 4), minval=10.0, maxval=30.0),
                       jax.random.uniform(k3, (256, 4, 4), minval=-30.0, maxval=-10.0))
    m = np.asarray(sinkhorn(logits, 20, 1e-6), np.float64)
    assert m.min() >= 0 and np.abs(logits).max() <= 30
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    anywhere = np.asarray(sinkhorn(jax.random.uniform(k2, (256, 4, 4), minval=-30.0, maxval=30.0), 20, 1e-6), np.float64)
    np.testing.assert_allclose(anywhere.sum(-2), 1.0, atol=1e-5)
    assert 1e-3 < np.abs(anywhere.sum(-1) - 1.0).max() < 1.0 and anywhere.min() >= 0
    # fewer steps leave the rows further off: the twenty are applied
    assert np.abs(np.asarray(sinkhorn(logits, 1, 1e-6)).sum(-1) - 1).max() >= np.abs(m.sum(-1) - 1).max()


def test_a_sublayer_with_identity_maps_is_the_plain_residual():
    """``Hres = I``, ``Hpre = Hpost = e_0``: stream 0 is ``x + F(x)``, the others pass."""
    hc = HyperConnection(CFG)
    x = jax.random.normal(jax.random.PRNGKey(2), (5, CFG.hc_mult, CFG.hidden_size))
    fn = lambda u: (jnp.tanh(u) * 3.0, None)  # noqa: E731
    p = nn.meta.unbox(hc.init(jax.random.PRNGKey(0), x, fn))
    n, e0 = CFG.hc_mult, jnp.eye(CFG.hc_mult)[0]
    b = jnp.concatenate([60.0 * e0 - 30.0, jnp.where(e0 > 0, 0.0, -30.0), (60.0 * jnp.eye(n) - 30.0).reshape(-1)])
    p = {"params": {**p["params"], "a": jnp.zeros(3), "b": b}}
    got, _ = hc.apply(p, x, fn)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(x[:, 0] + fn(x[:, 0])[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[:, 1:]), np.asarray(x[:, 1:]), atol=1e-5)


# --------------------------------------------------- (c) absorbed against expanded


def _latent_case(b, c, starts, lens, width=13):
    """Random projections of ``b`` rows: a history of ``starts`` tokens and a
    chunk of ``c`` of which ``lens`` carry a token, on scattered pages."""
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    h, nope, rope, rank = CFG.num_attention_heads, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.kv_lora_rank
    total = max(s + c for s in starts)
    q_nope, q_pe = jax.random.normal(k[0], (b, total, h, nope)), jax.random.normal(k[1], (b, total, h, rope))
    c_kv, k_pe = jax.random.normal(k[2], (b, total, rank)), jax.random.normal(k[3], (b, total, rope))
    w_kvb = jax.random.normal(k[4], (rank, h, nope + CFG.v_head_dim)) / np.sqrt(rank)
    table = np.asarray(jax.random.permutation(k[5], np.arange(1, KV.num_pages))[:b * width]).reshape(b, width)
    lanes = mla_attention.latent_lanes(CFG.latent_dim)
    rows = jnp.pad(jnp.concatenate([c_kv, k_pe], -1), ((0, 0), (0, 0), (0, lanes - CFG.latent_dim)))
    pages = jnp.zeros((KV.num_pages, PAGE, lanes))
    # the history and the chunk, written as the twin writes them
    pages = mla_attention.write_latent(pages, rows, jnp.asarray(table), jnp.zeros(b, jnp.int32), PAGE,
                                       jnp.asarray([s + n for s, n in zip(starts, lens)], jnp.int32))
    take = lambda a: jnp.stack([a[i, s:s + c] for i, s in enumerate(starts)])  # noqa: E731
    q = jnp.concatenate([jnp.einsum("bchd,lhd->bchl", take(q_nope), w_kvb[..., :nope]), take(q_pe)], -1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, lanes - CFG.latent_dim)))
    full = expanded_attention(CFG, q_nope, q_pe, c_kv, k_pe, w_kvb)           # causal over the whole sequences
    want = jnp.stack([full[i, s:s + c] for i, s in enumerate(starts)])
    return q, pages, jnp.asarray(table), w_kvb, want


@pytest.mark.parametrize("c, starts, lens", [(1, (150, 0, 37, 5), (1, 1, 0, 1)), (160, (0, 23, 100), (160, 130, 0))])
def test_absorbed_through_pages_equals_expanded(c, starts, lens):
    """The kernel and its jnp contract against the expanded form over whole
    sequences: decode rows, a chunk of two query blocks that starts inside a
    page, rows with no token."""
    q, pages, table, w_kvb, want = _latent_case(len(starts), c, starts, lens)
    args = (q, pages, table, jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32), PAGE)
    kw = dict(d_v=CFG.kv_lora_rank, scale=CFG.softmax_scale)
    with jax.default_matmul_precision("highest"):
        by_kernel = mla_attention.mla_absorbed_pallas(*args, **kw, interpret=True)
        by_jnp = mla_attention.mla_absorbed_reference(*args, **kw)
        expand = lambda o: jnp.einsum("bchl,lhd->bchd", o, w_kvb[..., CFG.qk_nope_head_dim:])  # noqa: E731
        for i, n in enumerate(lens):
            for got in (by_kernel, by_jnp):
                np.testing.assert_allclose(np.asarray(expand(got)[i, :n]), np.asarray(want[i, :n]), atol=TOL)
                assert not np.asarray(got[i, n:]).any()                     # slots without a token come out zero
