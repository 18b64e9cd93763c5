"""On-chip Pallas kernel regression tests (VERDICT r2 weakness 4: every
kernel's on-chip verification previously lived only in commit messages).

One test per kernel family — flash fwd+bwd, paged decode, quant pack/unpack,
splash block-sparse fwd+bwd — asserting bf16 numerics against jnp goldens
computed on the same chip, plus a flash-beats-chunked perf floor at the
headline bench shape.  Run on a TPU host with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu -q

Under ``DS_TPU_TESTS=1`` a missing chip fails the run (tests/conftest.py);
without it this directory skips.  Timings fence with ``block_until_ready``.
"""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


# ----------------------------------------------------------------- flash


def test_flash_fwd_bwd_bf16_vs_golden():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import reference_attention
    from deepspeed_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, D = 2, 1024, 8, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32)**2)

    def loss_g(q, k, v):
        return jnp.sum(reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                           v.astype(jnp.float32), causal=True)**2)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    gold = jax.jit(lambda q, k, v: reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - gold)))
    assert err < 4e-2, f"flash fwd bf16 deviates from f32 golden by {err}"

    gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
    gg = jax.jit(jax.grad(loss_g, argnums=(0, 1, 2)))(q, k, v)
    for a, b, n in zip(gf, gg, "qkv"):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert not np.isnan(a).any(), f"d{n} has nans"
        denom = max(1.0, np.abs(b).max())
        rel = np.abs(a - b).max() / denom
        assert rel < 5e-2, f"d{n} rel err {rel}"


def _model_step_time(attention_impl, remat_policy, steps=10):
    """Bench-shaped training step time (6 of the 125M preset's 12 layers to halve
    compile time; the attention cost per layer is identical).  Isolated
    single-op timings proved unreliable in BOTH directions (scan/pallas
    interaction, XLA DCE of untaken grads), so the floor is asserted on the
    metric that is actually stable and actually matters: the end-to-end
    step."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                      num_hidden_layers=6, num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=1024, rope_theta=1e4, scan_layers=False,
                      remat=True, remat_policy=remat_policy, attention_impl=attention_impl)
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(cfg), config={
        "train_batch_size": 8, "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2}, "bf16": {"enabled": True}, "steps_per_print": 0})
    ids = np.random.default_rng(0).integers(0, 32000, (8, 1024), dtype=np.int32)
    b = {"input_ids": ids, "labels": ids}
    for _ in range(3):
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        for _ in range(steps):
            loss = engine.train_batch(batch=b)
        jax.block_until_ready(loss)
        best = min(best, (time.time() - t0) / steps)
    return best


def test_flash_beats_chunked_perf_floor():
    """The flagship claim from r2's verdict: the flash path must win (or at
    worst tie within noise) against XLA-chunked at the headline bench shape
    in the real training step it ships in."""
    t_flash = _model_step_time("flash", "flash_saveable")
    t_chunk = _model_step_time("chunked", "dots_with_no_batch_dims_saveable")
    assert t_flash <= t_chunk * 1.02, (
        f"flash step {t_flash*1e3:.1f} ms vs chunked {t_chunk*1e3:.1f} ms — kernel lost its edge")


def test_flash_gqa_native_llama3_shape_on_chip():
    """GQA-native kernels at the Llama-3-8B head shape (32q/8kv, d=128):
    numerics vs f32 golden, and the native path must not be slower than
    running the kernels at full MHA width over repeated KV (what the
    pre-r4 wrapper materialized — 4x the KV HBM traffic)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import reference_attention
    from deepspeed_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, HK, D = 1, 1024, 32, 8, 128
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, HK, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, HK, D), jnp.bfloat16)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    gold = jax.jit(lambda q, k, v: reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - gold)))
    assert err < 4e-2, f"GQA fwd bf16 deviates by {err}"

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32)**2)

    def loss_g(q, k, v):
        return jnp.sum(reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                           v.astype(jnp.float32), causal=True)**2)

    gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))(q, k, v)
    gg = jax.jit(jax.grad(loss_g, argnums=(0, 1, 2)))(q, k, v)
    for a, b, n in zip(gf, gg, "qkv"):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert not np.isnan(a).any(), f"d{n} has nans"
        rel = np.abs(a - b).max() / max(1.0, np.abs(b).max())
        assert rel < 5e-2, f"d{n} rel err {rel}"

    # perf: native GQA vs the kernels at full width over repeated KV
    k32, v32 = jnp.repeat(k, H // HK, axis=2), jnp.repeat(v, H // HK, axis=2)
    g = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))

    def bench(k, v, reps=300):
        jax.block_until_ready(g(q, k, v))
        t0 = time.time()
        for _ in range(reps):
            r = g(q, k, v)
        jax.block_until_ready(r)
        return (time.time() - t0) / reps

    t_gqa, t_mha = bench(k, v), bench(k32, v32)
    assert t_gqa <= t_mha * 1.05, (
        f"GQA-native fwd+bwd {t_gqa*1e3:.2f} ms vs repeated-KV MHA {t_mha*1e3:.2f} ms")


# ----------------------------------------------------------------- paged


# (chunk, heads, kv heads, head_dim, page, start positions).  The first row
# is the shape the kernel first ran at; the rest are chip_smoke.py's serving
# geometry — Llama-3-8B heads (32q/8kv, d=128, rep 4), page 16, prefill
# chunk 128 and decode chunk 1 — whole and at the TP=4 share (8q/2kv).
# Start positions are deliberately not multiples of the page or the chunk.
# The last three are pages the chip's tiling pads, which the pipeline brings
# (ops/paged_attention._copies_pages): Falcon-7B's one key head of 64 lanes
# at a chunk and at one token, and a Mixtral shard of tensor_parallel=8.
PAGED_GEOMETRIES = [
    (4, 8, 4, 64, 8, (0, 5, 13)),
    (128, 32, 8, 128, 16, (0, 200, 1337)),
    (1, 32, 8, 128, 16, (0, 200, 1337)),
    (128, 8, 2, 128, 16, (0, 200, 1337)),
    (1, 8, 2, 128, 16, (0, 200, 1337)),
    (128, 71, 1, 64, 16, (0, 200, 1337)),
    (1, 71, 1, 64, 16, (0, 200, 1337)),
    (1, 4, 1, 128, 16, (0, 200, 1337)),
]


@pytest.mark.parametrize("c,h,n_kv,d,page_size,starts", PAGED_GEOMETRIES)
def test_paged_kernel_bf16_on_chip(c, h, n_kv, d, page_size, starts):
    """Compiled paged kernel vs the jnp golden (f32) on the same chip."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama_cache import _write_pages, paged_attention
    from deepspeed_tpu.ops.paged_attention import paged_attention_pallas

    rng = np.random.default_rng(0)
    b = len(starts)
    start_pos = np.asarray(starts, np.int32)
    max_pages = -(-(int(start_pos.max()) + c) // page_size) + 1
    num_pages = 1 + b * max_pages
    chunk_lens = np.array([c, max(1, c - 1), 1], np.int32)
    block_table = np.zeros((b, max_pages), np.int32)
    next_page = 1
    for i in range(b):
        needed = -(-(int(start_pos[i]) + c) // page_size)
        block_table[i, :needed] = np.arange(next_page, next_page + needed)
        next_page += needed
    # every slot random: what lies past a row's length is masked by both
    # implementations, so only visible history has to be meaningful
    pages = jnp.asarray(rng.normal(size=(1, num_pages, page_size, 2, n_kv, d)), jnp.bfloat16)   # an arena of one layer
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.bfloat16)
    k_new = jnp.asarray(rng.normal(size=(b, c, n_kv, d)), jnp.bfloat16)
    v_new = jnp.asarray(rng.normal(size=(b, c, n_kv, d)), jnp.bfloat16)
    bt, sp, cl = jnp.asarray(block_table), jnp.asarray(start_pos), jnp.asarray(chunk_lens)

    # write the chunk like the cache twin does, then attend both ways
    pages = _write_pages(pages, k_new, v_new, bt, sp, page_size, cl, layer=0)

    gold = jax.jit(lambda q, pages: paged_attention(
        q.astype(jnp.float32), pages[0].astype(jnp.float32), bt, sp, cl, page_size))(q, pages)
    kernel = jax.jit(lambda q, pages: paged_attention_pallas(q, pages, bt, sp, cl, page_size, layer=0,
                                                             interpret=False))
    # the kernel by its name: no other form of the same attention stands in
    assert "ds_paged_attention" in kernel.lower(q, pages).as_text()
    got = kernel(q, pages)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - gold)))
    # bf16 probabilities and outputs against an f32 golden: 2^-8 relative
    # on O(1) values, with headroom; a wrong page or mask is O(1)
    assert err < 4e-2, f"paged kernel bf16 deviates by {err}"


@pytest.mark.parametrize("window", [0, 512], ids=["shared_pages", "window_ring"])
def test_paged_kernel_decode_form_20_live_rows_of_160_on_chip(window):
    """The kernel's decode form at ``phi4flash_reason``'s shape (160 rows of 2
    key heads and 4 queries each, one position a row), 20 rows live in runs of
    five (four slots' groups of key pairs) and 140 dead: one stream of page
    copies over the live rows, against the jnp golden on the same chip, under
    the window layers' bound and under none."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama_cache import paged_attention
    from deepspeed_tpu.ops.paged_attention import paged_attention_pallas, takes_decode_form

    rng = np.random.default_rng(55)
    rows, n_q, n_kv, d, page, width = 160, 8, 2, 128, 16, 41 if window else 257
    assert takes_decode_form(1, n_kv, d, 2)
    start, lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
    for slot, at in zip((0, 7, 8, 31), (526, 511, 640, 513) if window else (0, 127, 1537, 4100)):
        start[5 * slot:5 * slot + 5], lens[5 * slot:5 * slot + 5] = at, 1
    table = rng.integers(1, 4096, (rows, width)).astype(np.int32)
    pages = jnp.asarray(rng.normal(size=(2, 4096, page, 2, n_kv, d)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(rows, 1, n_q, d)), jnp.bfloat16)
    bt, sp, cl = jnp.asarray(table), jnp.asarray(start), jnp.asarray(lens)
    gold = jax.jit(lambda q, pages: paged_attention(q.astype(jnp.float32), pages[1].astype(jnp.float32), bt, sp, cl,
                                                    page, sliding_window=window, scale=0.125))(q, pages)
    kernel = jax.jit(lambda q, pages: paged_attention_pallas(q, pages, bt, sp, cl, page, layer=1, window=window,
                                                             scale=0.125, interpret=False))
    assert "ds_paged_attention" in kernel.lower(q, pages).as_text()
    got = kernel(q, pages).astype(jnp.float32)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - gold))) < 4e-2
    np.testing.assert_array_equal(np.asarray(got)[lens == 0], 0)


# ----------------------------------------------------------------- quant


def test_quant_pack_bit_exact_on_chip():
    import jax.numpy as jnp
    from deepspeed_tpu.ops.quant_kernels import (dequantize_int4_pallas, dequantize_int8_pallas,
                                                 quantize_int4_pallas, quantize_int8_pallas)
    from deepspeed_tpu.ops.quantizer import (dequantize_int4, dequantize_int8, quantize_int4,
                                             quantize_int8)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4096, )), jnp.float32)

    q_k, s_k = quantize_int8_pallas(x, block=256, interpret=False)
    q_j, s_j = quantize_int8(x, 256)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_j))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), rtol=1e-6)
    d_k = dequantize_int8_pallas(q_k, s_k, x.shape, interpret=False)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(dequantize_int8(q_j, s_j, x.shape)),
                               rtol=1e-6)

    q4_k, s4_k = quantize_int4_pallas(x, block=256, interpret=False)
    q4_j, s4_j = quantize_int4(x, 256)
    np.testing.assert_array_equal(np.asarray(q4_k), np.asarray(q4_j))
    d4_k = dequantize_int4_pallas(q4_k, s4_k, x.shape, interpret=False)
    np.testing.assert_allclose(np.asarray(d4_k),
                               np.asarray(dequantize_int4(q4_j, s4_j, x.shape)), rtol=1e-6)


# ----------------------------------------------------------------- splash


def test_splash_sparse_fwd_bwd_bf16_on_chip():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention.pallas_kernel import sparse_attention_pallas
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import sparse_attention

    rng = np.random.default_rng(4)
    B, H, S, D, block = 1, 2, 512, 64, 128
    nb = S // block
    layout = np.zeros((H, nb, nb), np.int64)
    for h in range(H):
        for r in range(nb):
            layout[h, r, max(0, r - 1):r + 1] = 1   # local band
    layout[0, :, 0] = 1                             # + global column on head 0
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.bfloat16)

    def loss_p(q, k, v):
        return jnp.sum(sparse_attention_pallas(q, k, v, layout, block, causal=True,
                                               interpret=False).astype(jnp.float32)**2)

    def loss_j(q, k, v):
        return jnp.sum(sparse_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                                        v.astype(jnp.float32), layout, block, causal=True)**2)

    out = jax.jit(lambda q, k, v: sparse_attention_pallas(
        q, k, v, layout, block, causal=True, interpret=False))(q, k, v)
    gold = jax.jit(lambda q, k, v: sparse_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), layout, block,
        causal=True))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - gold)))
    assert err < 4e-2, f"splash fwd bf16 deviates by {err}"

    gp = jax.jit(jax.grad(loss_p, argnums=(0, 1, 2)))(q, k, v)
    gj = jax.jit(jax.grad(loss_j, argnums=(0, 1, 2)))(q, k, v)
    for a, b, n in zip(gp, gj, "qkv"):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert not np.isnan(a).any(), f"d{n} has nans"
        rel = np.abs(a - b).max() / max(1.0, np.abs(b).max())
        assert rel < 6e-2, f"d{n} rel err {rel}"


def test_flash_q_offset_staged_equals_full_on_chip():
    """r5 staged-FPDT substrate: per-group triangular kernel calls with
    q_position_offset reproduce the full causal kernel on the chip to a
    bf16 ulp (same kernels — only the table/mask shift and the gcd-clamped
    block size differ)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S, H, D = 2, 1024, 8, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)

    full = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)

    @jax.jit
    def staged(q, k, v):
        G, glen = 4, S // 4
        outs = []
        for g in range(G):
            outs.append(flash_attention(q[:, g * glen:(g + 1) * glen],
                                        k[:, :(g + 1) * glen], v[:, :(g + 1) * glen],
                                        causal=True, q_position_offset=g * glen))
        return jnp.concatenate(outs, axis=1)

    got = staged(q, k, v)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - full.astype(jnp.float32))))
    # group boundaries shrink bq (gcd clamp 512 -> 256), reordering the
    # online-softmax accumulation: a bf16-ulp of drift is expected (equal
    # block sizes ARE bit-exact — asserted in the CPU interpret tests)
    assert err < 4e-3, f"staged q_offset kernel deviates from full causal by {err}"

    # grads through the staged decomposition track the full kernel's
    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32)**2)

    gs = jax.jit(jax.grad(lambda q, k, v: loss(staged, q, k, v), argnums=(0, 1, 2)))(q, k, v)
    gf = jax.jit(jax.grad(lambda q, k, v: loss(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b, n in zip(gs, gf, "qkv"):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = max(1.0, np.abs(b).max())
        assert np.abs(a - b).max() / denom < 2e-2, f"d{n} staged-vs-full mismatch"


# ----------------------------------------------------------------- grouped experts


def _dense_mixture(cfg, p, x):
    """``Qwen2MoeSparseMLP`` as it stood until PR 31: every expert multiplies
    every token ([B, S, NE, *] intermediates) and the routing weights zero all
    but k of the results.  Kept here as the grouped product's reference."""
    import jax
    import jax.numpy as jnp
    dt, f32 = cfg.dtype, jnp.float32
    probs = jax.nn.softmax(x.astype(f32) @ p["gate"]["kernel"], axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = (jax.nn.one_hot(topi, cfg.num_experts, dtype=f32) * topv[..., None]).sum(-2)
    h = jnp.einsum("bse,nem->bsnm", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bse,nem->bsnm", x, p["w_up"].astype(dt))
    y = jnp.einsum("bsnm,nme->bsne", jax.nn.silu(h) * u, p["w_down"].astype(dt))
    out = jnp.einsum("bsne,bsn->bse", y.astype(f32), weights)
    shared = (jax.nn.silu(x @ p["shared_gate_proj"]["kernel"].astype(dt)) *
              (x @ p["shared_up_proj"]["kernel"].astype(dt))) @ p["shared_down_proj"]["kernel"].astype(dt)
    gate = jax.nn.sigmoid(x.astype(f32) @ p["shared_expert_gate"]["kernel"])
    return (out + gate * shared.astype(f32)).astype(x.dtype)


def test_qwen2_moe_grouped_experts_match_dense_mixture_at_cell_widths():
    """The train cell's expert layer at its own widths (60 experts of 1408
    over hidden 2048, 4 a token, 4,096 tokens a chip, bf16): output and
    gradients of the grouped product against the dense mixture it replaced."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn
    from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeSparseMLP

    cfg = Qwen2MoeConfig(num_hidden_layers=1)  # the published widths are the defaults
    assert (cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size, cfg.num_experts_per_tok) == (2048, 60, 1408, 4)
    layer = Qwen2MoeSparseMLP(cfg)
    kx, kr, kp = jax.random.split(jax.random.PRNGKey(31), 3)
    x = jax.random.normal(kx, (2, 2048, cfg.hidden_size), jnp.bfloat16)
    r = jax.random.normal(kr, x.shape, jnp.float32)
    params = nn.meta.unbox(jax.jit(layer.init)(kp, x))["params"]

    def run(fn):
        f = jax.jit(jax.value_and_grad(lambda p: jnp.sum(fn(p, x).astype(jnp.float32) * r)))
        out = jax.jit(fn)(params, x)
        loss, grads = jax.block_until_ready(f(params))
        t0 = time.perf_counter()
        for _ in range(5):
            loss, grads = f(params)
        jax.block_until_ready(grads)
        return out, grads, (time.perf_counter() - t0) / 5

    got, got_grads, got_s = run(lambda p, x: layer.apply({"params": p}, x))
    want, want_grads, want_s = run(lambda p, x: _dense_mixture(cfg, p, x))
    print(f"\nqwen2_moe layer fwd+bwd at 4096 tokens: grouped {got_s * 1e3:.2f} ms, dense mixture {want_s * 1e3:.2f} ms")

    rel = lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()) /
                             jnp.linalg.norm(b.astype(jnp.float32).ravel()))
    assert rel(got, want) < 1e-2, f"output deviates from the dense mixture by {rel(got, want)}"
    for name in ("w_gate", "w_up", "w_down"):
        err = rel(got_grads[name], want_grads[name])
        print(f"d{name}: {err:.2e}")
        assert np.isfinite(np.asarray(got_grads[name], np.float32)).all(), f"d{name} is not finite"
        assert err < 2e-2, f"d{name} deviates from the dense mixture's by {err}"
    err = rel(got_grads["gate"]["kernel"], want_grads["gate"]["kernel"])
    assert err < 5e-2, f"the router's gradient deviates by {err}"


# ------------------------------------------------------- grouped product


#: the grouped product's operands in the benchmark's cells: (rows, contraction, columns, groups, the first group
#: with rows, how many have them, rows in all); Mixtral's bank is the stack of 3 layers of which the second is read
GROUPED_ON_CHIP = {
    "mixtral_gate_and_up_doc_load": (4096, 4096, 14336, 24, 8, 8, 340),
    "mixtral_down_chat_load": (4096, 14336, 4096, 24, 8, 8, 290),
    "mixtral_gate_and_up_full_step": (4096, 4096, 14336, 24, 16, 8, 4096),
    "qwen15_moe_gate_and_up": (16384, 2048, 1408, 60, 0, 60, 16384),
    "qwen15_moe_down": (16384, 1408, 2048, 60, 0, 60, 16384),
    # a decode step in the sorted form (PR 47): Xing4's 12 rows of 4 over the fourth of six layers' 64 experts
    # (some 34 touched), Solar-Open2's 32 rows of 8 of which 24 fall on the third of four layers' 40 held (some 18)
    "xing4_gate_and_up_decode": (48, 3584, 1024, 384, 192, 64, 48),
    "xing4_down_decode": (48, 1024, 3584, 384, 192, 64, 48),
    "solar_gate_and_up_decode": (256, 4096, 1280, 160, 80, 40, 24),
    "solar_down_decode": (256, 1280, 4096, 160, 80, 40, 24),
}


def _grouped_operands(name):
    import jax
    import jax.numpy as jnp
    m, k, n, g, first, some, live = GROUPED_ON_CHIP[name]
    sizes = np.zeros(g, np.int32)
    sizes[first:first + some] = np.random.default_rng(m + live).multinomial(live, np.ones(some) / some)
    kl, kr, kw = jax.random.split(jax.random.PRNGKey(live), 3)
    lhs = jax.random.normal(kl, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(kr, (g, k, n), jnp.bfloat16) * k**-0.5
    weight = jax.random.normal(kw, (m, n), jnp.bfloat16) * (jnp.arange(m) < live)[:, None]
    return lhs, rhs, jnp.asarray(sizes), live, weight


def _ms(fn, *args, n=10):
    import jax
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / n * 1e3


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("name", list(GROUPED_ON_CHIP))
def test_grouped_kernel_matches_ragged_dot_on_chip(name):
    """``ds_gmm`` at a cell's operands against ``jax.lax.ragged_dot`` on the
    same chip, over the groups' rows: both multiply bfloat16 and add in
    float32, so they differ by the order of the sum."""
    import jax
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul, takes_kernel
    assert takes_kernel()
    lhs, rhs, sizes, live, _ = _grouped_operands(name)
    kernel = jax.jit(grouped_matmul)
    assert "ds_gmm" in kernel.lower(lhs, rhs, sizes).as_text()
    got, got_ms = _ms(kernel, lhs, rhs, sizes)
    want, want_ms = _ms(jax.jit(jax.lax.ragged_dot), lhs, rhs, sizes)
    print(f"\n{name}: ds_gmm {got_ms:.3f} ms, ragged_dot {want_ms:.3f} ms")
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.isfinite(np.asarray(got[:live], np.float32)).all()
    assert _rel(got[:live], want[:live]) < 4e-3


@pytest.mark.parametrize("name", ["qwen15_moe_gate_and_up", "qwen15_moe_down"])
def test_grouped_kernel_gradients_match_ragged_dots_on_chip(name):
    """The ``custom_vjp`` at the train cell's operands, under
    ``jax.checkpoint`` as the train step has it: the input gradient (``ds_gmm``
    with the bank read transposed) and the weight gradient (``ds_tgmm``)
    against ``ragged_dot``'s own."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    lhs, rhs, sizes, _, weight = _grouped_operands(name)

    def grads(product):
        loss = lambda a, b, s, w: jnp.sum((jax.checkpoint(product)(a, b, s) * w).astype(jnp.float32))  # noqa: E731
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    kernel = grads(grouped_matmul)
    text = kernel.lower(lhs, rhs, sizes, weight).as_text()
    assert "ds_gmm" in text and "ds_tgmm" in text and "ragged_dot" not in text
    got, got_ms = _ms(kernel, lhs, rhs, sizes, weight)
    want, want_ms = _ms(grads(jax.lax.ragged_dot), lhs, rhs, sizes, weight)
    print(f"\n{name}: forward again and both gradients {got_ms:.3f} ms, ragged_dot's {want_ms:.3f} ms")
    for g, w, which in zip(got, want, ("input", "bank")):
        assert g.dtype == w.dtype and np.isfinite(np.asarray(g, np.float32)).all(), which
        assert _rel(g, w) < 4e-3, (which, _rel(g, w))


def test_rows_in_no_group_do_not_reach_the_experts_output_on_chip():
    """A serving step's padding at Mixtral's widths (2 of 8 experts a token,
    2,048 slots of which 170 carry a token): the padded rows hold NaN, sort
    behind the last group and come out exact zeros; the live rows equal what
    the same tokens give with zeros for padding; with a stack of banks and a
    layer's index the same."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe.sharded_moe import dropless_moe
    d, f, e, s = 4096, 14336, 8, 2048
    ks = jax.random.split(jax.random.PRNGKey(33), 5)
    x = jax.random.normal(ks[0], (s, d), jnp.bfloat16)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    stack = tuple(jax.random.normal(k, (2, e) + shape, jnp.bfloat16) * 0.02
                  for k, shape in zip(ks[2:], ((d, f), (d, f), (f, d))))
    mask = jnp.arange(s) % 12 == 5
    # the banks are arguments: a closed-over constant of 1.9 GB would be written into the program's text
    run = jax.jit(lambda x, stack, layer: dropless_moe(x, logits, stack, 2, mask, None, layer))
    assert "ds_gmm" in run.lower(x, stack, 1).as_text()
    clean, _, counts = run(jnp.where(mask[:, None], x, 0), stack, 1)
    dirty, _, _ = run(jnp.where(mask[:, None], x, jnp.nan), stack, 1)
    assert int(counts.sum()) == 2 * int(mask.sum())
    assert np.isfinite(np.asarray(dirty)).all()
    assert not np.asarray(dirty)[~np.asarray(mask)].any()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))
    other, _, _ = run(x, stack, 0)
    assert _rel(other[np.asarray(mask)], clean[np.asarray(mask)]) > 0.5  # another layer's banks: the index is read


#: a decode step's expert layer: (rows, experts a row, the router's experts, held, hidden, expert width, layers)
DECODE_LAYERS = {"xing4_12_rows": (12, 4, 64, None, 3584, 1024, 6), "solar_32_rows": (32, 8, 320, (0, 40), 4096, 1280, 4)}


@pytest.mark.parametrize("name", list(DECODE_LAYERS))
def test_a_decode_steps_experts_take_the_sorted_form_on_chip(name, monkeypatch):
    """The rows of a decode bucket cannot touch most of a bank of narrow
    experts, so the layer takes the sorted form by ``takes_sorted``'s own
    answer: ``ds_gmm`` in the program, the dense form's values, and its time
    beside the dense form's with every row live and with two."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    s, k, e, held, d, f, layers = DECODE_LAYERS[name]
    count = e if held is None else held[1]
    ks = jax.random.split(jax.random.PRNGKey(47), 5)
    x = jax.random.normal(ks[0], (s, d), jnp.bfloat16)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    stack = tuple(jax.random.normal(kk, (layers, count) + shape, jnp.bfloat16) * shape[0]**-0.5
                  for kk, shape in zip(ks[2:], ((d, f), (d, f), (f, d))))

    def layer():    # the banks are arguments: see the test above
        return jax.jit(lambda x, stack, mask: sharded_moe.dropless_moe(x, logits, stack, k, mask, None, layers // 2,
                                                                       True, "sigmoid", None, 1.0, held))

    assert sharded_moe.takes_sorted(s, k, e)
    sorted_form = layer()
    assert "ds_gmm" in sorted_form.lower(x, stack, jnp.ones((s, ), bool)).as_text()
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: False)
    dense_form = layer()
    assert "ds_gmm" not in dense_form.lower(x, stack, jnp.ones((s, ), bool)).as_text()
    for live in (s, 2):
        mask = jnp.arange(s) < live
        (got, _, counts), got_ms = _ms(sorted_form, x, stack, mask, n=20)
        (want, _, want_counts), want_ms = _ms(dense_form, x, stack, mask, n=20)
        print(f"\n{name}, {live} rows live, {int((np.asarray(counts) > 0).sum())} of {count} experts touched: "
              f"sorted {got_ms:.3f} ms, dense {want_ms:.3f} ms")
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
        assert not np.asarray(got)[live:].any() and np.isfinite(np.asarray(got)).all()
        assert _rel(got[:live], want[:live]) < 1e-2


def test_the_live_rows_choose_the_experts_form_in_mixtrals_decode_bucket_on_chip(monkeypatch):
    """Mixtral's layer read out of a stack of three at the decode bucket's 16
    slots, whose slots say "dense": with a mask the program holds both forms
    under one conditional, and at 1, 4, 8, 12 and 16 live rows it costs what
    the better forced form costs (within 3%: the conditional itself is lost
    in a branch of 1-4 ms, and no bank is copied) and gives the values of the
    form the rule names for those rows."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import sharded_moe
    s, k, e, d, f, layers = 16, 2, 8, 4096, 14336, 3
    ks = jax.random.split(jax.random.PRNGKey(48), 5)
    x = jax.random.normal(ks[0], (s, d), jnp.bfloat16)
    logits = jax.random.normal(ks[1], (s, e), jnp.float32)
    stack = tuple(jax.random.normal(kk, (layers, e) + shape, jnp.bfloat16) * shape[0]**-0.5
                  for kk, shape in zip(ks[2:], ((d, f), (d, f), (f, d))))
    rule = sharded_moe.takes_sorted

    def layer():    # the banks are arguments: a closed-over constant would be written into the program's text
        return jax.jit(lambda x, stack, mask: sharded_moe.dropless_moe(x, logits, stack, k, mask, None, 1))

    assert not rule(s, k, e) and sharded_moe.sorted_up_to(k, e) == 11
    forms = {"rule": layer()}
    text = forms["rule"].lower(x, stack, jnp.ones((s, ), bool)).compile().as_text()
    assert "ds_gmm" in text and " conditional(" in text
    for name in ("sorted", "dense"):
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: name == "sorted")
        forms[name] = layer()
        assert ("ds_gmm" in forms[name].lower(x, stack, jnp.ones((s, ), bool)).as_text()) == (name == "sorted")
    for live in (1, 4, 8, 12, 16):
        mask = jnp.arange(s) < live
        out, ms = {}, {}
        for name, fn in forms.items():
            runs = [_ms(fn, x, stack, mask, n=20) for _ in range(3)]
            (out[name], _, counts), ms[name] = runs[0][0], min(t for _, t in runs)
        named = "sorted" if rule(live, k, e) else "dense"
        print(f"\nmixtral 16 slots, {live} live, {int((np.asarray(counts) > 0).sum())} of 8 experts touched: "
              f"rule {ms['rule']:.3f} ms ({named}), sorted {ms['sorted']:.3f}, dense {ms['dense']:.3f}")
        np.testing.assert_array_equal(np.asarray(out["rule"]), np.asarray(out[named]))
        assert not np.asarray(out["rule"])[live:].any() and _rel(out["rule"][:live], out["dense"][:live]) < 1e-2
        assert ms["rule"] <= 1.03 * min(ms["sorted"], ms["dense"]), (live, ms)


# ------------------------------------------------- a mixed step in row groups


def _load_cell(config, traffic):
    import json
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        return cfg, json.load(f)


@pytest.mark.parametrize("config,traffic", [("mixtral-8x7b-serve-1chip", "long_prompt_short_answer"),
                                            ("evabyte-6.5b-serve-1chip", "bytes_doc")])
def test_two_group_step_matches_the_rectangle_at_cell_widths_on_chip(config, traffic):
    """One mixed plan at a doc cell's widths, weights and depth (eight rows
    decoding at contexts a request of the cell reaches, one prompt's chunk of
    100 tokens behind 2,048): the two-group program ``((16, 1), (1, 128))``
    against the rectangle ``((16, 128), )`` the engine ran before and the
    benchmark's check still feeds.  Logits of every live row within the
    cell's own check limit (the 90th percentile, as the check holds its
    positions: a router near a tie picks another expert under any rounding),
    the prompt's pages the same, and the time of each program a step,
    printed."""
    _two_groups_against_the_rectangle(config, *_load_cell(config, traffic))


def _two_groups_against_the_rectangle(config, cfg, traffic):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "benchmark"))
    import harness
    from kinds import serve_open_loop

    from deepspeed_tpu.inference.v2.engine_v2 import _table_width, build_cache_model
    from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin

    econf = serve_open_loop.engine_config(cfg, traffic)
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, 35, jax.devices()[:1])
    page, chunk, width = econf.kv.page_size, econf.scheduler.prefill_chunk, _table_width(pcfg, econf.kv)
    twin = build_cache_model(pcfg, page)
    # eight rows decoding and one prompt's chunk: (context, tokens in the step, the row's pages)
    longest = (width - 2) * page if cache_geometry(pcfg, page).pages_immutable else 24000
    live = [(int(300 + (longest - 300) * i / 7), 1) for i in range(8)] + [(16 * chunk, chunk - 28)]
    live = [(ctx, n, 1 + i * width + np.arange(width)) for i, (ctx, n) in enumerate(live)]  # every column its own page
    kv = econf.kv.__class__(num_pages=1 + len(live) * width, page_size=page, max_pages_per_seq=econf.kv.max_pages_per_seq)
    shape = jax.eval_shape(lambda: cache_twin(pcfg).init_cache(pcfg, kv, econf.kv_dtype, 17, chunk))
    # a context that is not blank, the same for both programs; the null page zero
    fresh = jax.jit(lambda: (0.5 * jax.random.normal(jax.random.PRNGKey(1), shape.shape, shape.dtype)).at[:, 0].set(0))
    ids = np.random.default_rng(35).integers(1, cfg["vocab_size"], (len(live), chunk))
    prompt_pages = live[-1][2][:17 * chunk // page + 1]

    def run(groups, at):
        """``at``: for each of ``live`` its row among the groups' concatenated rows."""
        rows = sum(r for r, _ in groups)
        first, t0 = [], 0
        for n_rows, w in groups:
            first += [t0 + w * i for i in range(n_rows)]
            t0 += n_rows * w
        toks, start = np.zeros((t0, ), np.int32), np.zeros((rows, ), np.int32)
        tables, lens = np.zeros((rows, width), np.int32), np.zeros((rows, ), np.int32)
        for i, (ctx, n, pages) in enumerate(live):
            toks[first[at[i]]:first[at[i]] + n] = ids[i, :n]
            start[at[i]], lens[at[i]], tables[at[i]] = ctx, n, pages
        toks, start, tables, lens = map(jnp.asarray, (toks, start, tables, lens))
        if len(groups) == 1:
            fn = jax.jit(lambda p, c: twin.apply(p, toks.reshape(groups[0]), start, tables, c, lens, True),
                         donate_argnums=1)
        else:
            fn = jax.jit(lambda p, c: twin.apply(p, toks, start, tables, c, lens, True, groups), donate_argnums=1)
        logits, arena = fn(params, fresh())
        logits = np.asarray(logits[np.asarray(at), 0], np.float32)
        written = np.asarray(arena[:, prompt_pages], np.float32)
        # once more before the clock starts: the arena a program hands back is placed as the
        # program's own results are, and the first call on it is a second trace of the program
        _, arena = fn(params, arena)
        jax.block_until_ready(arena)
        t0 = time.perf_counter()
        for _ in range(10):
            out, arena = fn(params, arena)
        jax.block_until_ready(out)
        return logits, written, (time.perf_counter() - t0) / 10 * 1e3

    want, pages_want, rect_ms = run(((16, chunk), ), list(range(9)))               # the plan's nine rows first
    got, pages_got, groups_ms = run(((16, 1), (1, chunk)), list(range(8)) + [16])  # the prompt in a group of its own
    errs = np.asarray([_rel(g, w) for g, w in zip(got, want)])
    limit = min(cfg["check"]["limits"].values())
    print(f"\n{config}: rectangle ((16, {chunk}), ) {rect_ms:.2f} ms a step, two groups ((16, 1), (1, {chunk})) "
          f"{groups_ms:.2f} ms; logits two groups against rectangle, ||d|| / ||ref|| over the live rows: "
          f"p50 {np.median(errs):.5f} p90 {np.percentile(errs, 90):.5f} max {errs.max():.5f} (limit {limit})")
    assert np.isfinite(got).all()
    assert np.percentile(errs, 90) <= limit, errs
    assert _rel(pages_got, pages_want) <= limit
    assert groups_ms < rect_ms


@pytest.mark.parametrize("config,traffic", [("mixtral-8x7b-serve-1chip", "long_prompt_short_answer"),
                                            ("evabyte-6.5b-serve-1chip", "bytes_doc")])
def test_a_run_of_four_chunks_matches_a_chunk_a_step_at_cell_widths_on_chip(config, traffic):
    """One prompt's four consecutive chunks (128, 128, 128, 100 tokens behind
    2,048: inside one window of EvaByte's ring) as the four rows of
    ``((16, 1), (4, 128))`` in one step, beside eight rows that decode, against
    the same chunks fed one a step through ``((16, 1), (1, 128))``: the logits
    of every position of the run and of the decode rows within the cell's
    check limit (the 90th percentile, as the check holds its positions: a
    router near a tie picks another expert under any rounding, and a step of
    528 slots takes the sorted form of the experts where one of 144 takes the
    dense one), the prompt's pages the same, and the time of each program a
    step, printed (t4 and t1 of PERF.md section 6, PR 42)."""
    import jax
    import jax.numpy as jnp
    cfg, traffic = _load_cell(config, traffic)
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "benchmark"))
    import harness
    from kinds import serve_open_loop

    from deepspeed_tpu.inference.v2.engine_v2 import _table_width, build_cache_model
    from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin

    econf = serve_open_loop.engine_config(cfg, traffic)
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, 42, jax.devices()[:1])
    page, chunk, width = econf.kv.page_size, econf.scheduler.prefill_chunk, _table_width(pcfg, econf.kv)
    twin = build_cache_model(pcfg, page)
    assert cache_geometry(pcfg, page).chunk_runs
    longest = (width - 2) * page if cache_geometry(pcfg, page).pages_immutable else 24000
    decoding = [(int(300 + (longest - 300) * i / 7), 1 + i * width + np.arange(width)) for i in range(8)]
    start0, lens_run = 16 * chunk, [chunk, chunk, chunk, chunk - 28]
    prompt_table = 1 + 8 * width + np.arange(width)
    kv = econf.kv.__class__(num_pages=1 + 9 * width, page_size=page, max_pages_per_seq=econf.kv.max_pages_per_seq)
    shape = jax.eval_shape(lambda: cache_twin(pcfg).init_cache(pcfg, kv, econf.kv_dtype, 17, chunk))
    fresh = jax.jit(lambda: (0.5 * jax.random.normal(jax.random.PRNGKey(1), shape.shape, shape.dtype)).at[:, 0].set(0))
    rng = np.random.default_rng(42)
    ids_decode, ids_prompt = rng.integers(1, cfg["vocab_size"], 8), rng.integers(1, cfg["vocab_size"], sum(lens_run))
    prompt_pages = prompt_table[:(start0 + sum(lens_run)) // page + 1]

    def batch(rows, chunks):
        """The arrays of ``((16, 1), (rows, chunk))`` with the prompt's ``chunks`` (indices of ``lens_run``) in its rows."""
        toks, start = np.zeros((16 + rows * chunk, ), np.int32), np.zeros((16 + rows, ), np.int32)
        tables, lens = np.zeros((16 + rows, width), np.int32), np.zeros((16 + rows, ), np.int32)
        for i, (ctx, pages) in enumerate(decoding):
            toks[i], start[i], lens[i], tables[i] = ids_decode[i], ctx, 1, pages
        for j, c in enumerate(chunks):
            at = sum(lens_run[:c])
            toks[16 + j * chunk:16 + j * chunk + lens_run[c]] = ids_prompt[at:at + lens_run[c]]
            start[16 + j], lens[16 + j], tables[16 + j] = start0 + at, lens_run[c], prompt_table
        return tuple(map(jnp.asarray, (toks, start, tables, lens)))

    def program(rows):
        groups = ((16, 1), (rows, chunk))
        return jax.jit(lambda p, c, toks, start, tables, lens: twin.apply(p, toks, start, tables, c, lens, False, groups),
                       donate_argnums=1)

    def timed(fn, args, arena):
        _, arena = fn(params, arena, *args)           # the arena a program hands back: a second trace (as above)
        jax.block_until_ready(arena)
        t0 = time.perf_counter()
        for _ in range(10):
            out, arena = fn(params, arena, *args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 10 * 1e3

    def live(logits, chunks):
        """[positions, vocab]: the decode rows' logits, then those of the chunks' tokens."""
        logits = np.asarray(logits, np.float32)
        return np.concatenate([logits[:8]] + [logits[16 + j * chunk:16 + j * chunk + lens_run[c]]
                                              for j, c in enumerate(chunks)])

    four, one = program(4), program(1)
    logits, arena = four(params, fresh(), *batch(4, [0, 1, 2, 3]))
    got = live(logits, [0, 1, 2, 3])
    pages_got = np.asarray(arena[:, prompt_pages], np.float32)
    t4 = timed(four, batch(4, [0, 1, 2, 3]), arena)
    arena, want = fresh(), []
    for c in range(4):
        logits, arena = one(params, arena, *batch(1, [c]))
        want.append(live(logits, [c])[0 if c == 0 else 8:])
    want = np.concatenate(want)
    pages_want = np.asarray(arena[:, prompt_pages], np.float32)
    t1 = timed(one, batch(1, [3]), arena)
    del logits
    errs = np.asarray([_rel(g, w) for g, w in zip(got, want)])
    limit = min(cfg["check"]["limits"].values())
    print(f"\n{config}: a chunk a step ((16, 1), (1, {chunk})) t1 {t1:.2f} ms, a run of four ((16, 1), (4, {chunk})) t4 "
          f"{t4:.2f} ms; logits run against chunk a step, ||d|| / ||ref||: decode rows p90 "
          f"{np.percentile(errs[:8], 90):.5f} max {errs[:8].max():.5f}, the run's {errs.size - 8} positions p50 "
          f"{np.median(errs[8:]):.5f} p90 {np.percentile(errs[8:], 90):.5f} max {errs[8:].max():.5f}, its last "
          f"{errs[-1]:.5f} (limit {limit}); pages {_rel(pages_got, pages_want):.5f}")
    assert np.isfinite(got).all() and got.shape == want.shape
    assert np.percentile(errs[8:], 90) <= limit and np.percentile(errs[:8], 90) <= limit, errs
    assert _rel(pages_got, pages_want) <= limit
    assert t4 < 4 * t1
