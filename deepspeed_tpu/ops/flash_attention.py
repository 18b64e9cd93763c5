"""Pallas TPU flash attention — forward AND backward kernels.

TPU-native replacement for the reference's fused attention kernels
(ref: csrc/transformer/inference softmax/attention kernels and the
FlashAttention integration the reference defers to, e.g.
deepspeed/sequence/fpdt_layer.py:510 which assumes a flash kernel).

Forward: online-softmax tiling over a scalar-prefetched lower-triangular
block table (see the design banner below) with running max / normaliser /
accumulator carried in VMEM scratch across the innermost ("arbitrary")
grid dimension.  The kernel also emits the per-row logsumexp so the
backward never re-runs the softmax reduction.

Backward: the standard two-kernel FlashAttention-2 split — a dq kernel
sweeping kv blocks per q row, and a dk/dv kernel sweeping q blocks per kv
column; both recompute p = exp(s - lse) per tile from the saved lse (O(S)
residuals, never the [S, S] score matrix), and delta = rowsum(do · o) per
tile from the o/do blocks already resident in VMEM.

All matmuls feed the MXU bf16 operands with f32 accumulation — measured
0.59 vs 0.37 step MFU at B8/S1024/H12/D64 against the pre-rewrite kernels
that cast to f32 first and ran a dense grid over transposed [B·H, S, D]
copies.
"""

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import get_trace_mesh, in_manual_mesh, traced_for_tpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
LANE = 128  # TPU lane width: per-row scalars are stored lane-broadcast


# ---------------------------------------------------------------------------
# v2 kernels: transpose-free packed layout + triangular grid.
#
# The model's natural activation layout is [B, S, H·D] (what the qkv
# projections write and what o_proj reads).  v1 transposed to [B·H, S, D]
# at every kernel entry/exit — 8 HBM-round-trip transposes per layer
# counting the backward.  v2 never transposes: the kernels index head h's
# column slice directly out of the packed [B, S, H·D] array via BlockSpec
# index maps (a reshape [B,S,H,D]→[B,S,H·D] is a free bitcast).  GQA is
# NATIVE (round 4): kv stays packed at its real [B, S, HK·D] width and the
# head grid iterates over kv-head groups — exploiting that the rep query
# heads sharing kv head g are CONTIGUOUS in the packed layout (q head i
# attends kv head i // rep), so one kv block of Pk heads pairs with one q
# block of P = Pk·rep heads at packed offsets hh·Pk·d / hh·P·d.  No
# repeated-KV materialization (at Llama-3-8B's 32q/8kv the repeat cost 4×
# KV HBM traffic), and the dk/dv kernel group-sums the rep query heads'
# contributions in VMEM scratch instead of a post-hoc reshape-sum.
#
# For causal masks the (q-block, kv-block) pairs are flattened into a
# scalar-prefetched lower-triangular table, so blocks above the diagonal
# are neither computed NOR DMA'd — the v1 grid fetched k/v for every
# skipped block, ~37% wasted bandwidth at S=1024 with 256-blocks.  The
# table also marks which blocks straddle the diagonal (see _mask_if_diag
# for why the mask still runs unconditionally).
# ---------------------------------------------------------------------------


def _tri_table(nq, nk, bq, bk, causal, transpose=False, q_offset=0):
    """Flattened block schedule. Rows: 0=iq, 1=ik, 2=first, 3=last, 4=diag.

    ``transpose=False``: row-major sweep (for each q block, its admitted kv
    blocks) — the fwd/dq accumulation order.  ``transpose=True``:
    column-major (for each kv block, its admitted q blocks) — the dk/dv
    order.  first/last flag the accumulation-window boundaries in either
    order.  ``q_offset`` (static) shifts the queries' GLOBAL positions:
    query row r sits at position q_offset + r — the FPDT staged path runs
    one triangular kernel call per (q group x kv prefix) with the group's
    offset, keeping causality exact without a merge pass."""
    import numpy as np
    cols = []
    if not transpose:
        for i in range(nq):
            hi = min(nk, -(-(q_offset + (i + 1) * bq) // bk)) if causal else nk
            for j in range(hi):
                diag = 1 if (causal and (j + 1) * bk - 1 > q_offset + i * bq) else 0
                cols.append((i, j, 1 if j == 0 else 0, 1 if j == hi - 1 else 0, diag))
    else:
        for j in range(nk):
            # clamp so every kv column gets ≥1 entry even when the whole
            # column sits above the causal diagonal (sk > sq): the lone
            # visited block is then fully masked, p ≡ 0, and the dk/dv
            # output block is correctly written as zeros instead of left
            # uninitialized
            lo = min(max(0, (j * bk - q_offset) // bq), nq - 1) if causal else 0
            rows = list(range(lo, nq))
            for n, i in enumerate(rows):
                diag = 1 if (causal and (j + 1) * bk - 1 > q_offset + i * bq) else 0
                cols.append((i, j, 1 if n == 0 else 0, 1 if n == len(rows) - 1 else 0, diag))
    tab = np.asarray(cols, dtype=np.int32).T  # [5, T]
    return tab


def _mask_if_diag(s, tab_ref, t, bq, bk, q_offset=0):
    """Causal mask, no-op'd via the table's diag flag for fully-visible
    blocks.  Measured on v5e: a real lax.cond branch around the masking
    costs ~13% step time (78 vs 69 ms at bench shapes) — the branch breaks
    Mosaic's software pipelining — so the select runs unconditionally and
    the diag flag just widens ``keep`` to all-true."""
    qpos = q_offset + tab_ref[0, t] * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = tab_ref[1, t] * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = (qpos >= kpos) | (tab_ref[4, t] == 0)
    return jnp.where(keep, s, DEFAULT_MASK_VALUE)


def _gqa_native_ok(d, h, hk):
    """GQA-native blocks put all rep = h//hk query heads sharing a kv block
    into ONE invocation, so scratch and q/o/lse blocks scale with P·d.
    Mainstream GQA (rep ≤ 8) fits easily; MQA-extreme shapes (e.g. Falcon's
    71q/1kv) would blow VMEM — those fall back to repeated KV.  Judged on
    the NARROWEST tile-legal width (the packing heuristic can always fall
    back to it)."""
    rep = h // hk
    min_legal = min(p for p in range(1, hk + 1)
                    if hk % p == 0 and ((p * d) % LANE == 0 or p == hk))
    # ≈2 MB f32 accumulator scratch at bq=512, plus three P-wide q/o/do
    # blocks and a P-wide lse block in the backward — mainstream GQA
    # (rep ≤ 8 at d=128) stays native, Falcon-style 71q/1kv falls back
    return min_legal * rep * d <= 1024


# Widest packed block (query heads x head_dim lanes) the packing heuristic
# targets for SUB-LANE head dims (d < 128, where a single head is not
# tile-legal on its own).  r5: the r4 kernels used the MINIMAL tile-legal
# width (2 heads at d=64), leaving the grid many small steps.  Measured on
# v5e at bench shapes (B24 S1024 H12 D64, fwd+bwd, dispatch amortized
# in-program): Pk=2 8.22 ms, Pk=4 7.86, Pk=6 7.77 (-5.5%), Pk=12 OOMs
# scoped VMEM (17.2M > 16M limit) and Pk=12@bq256 8.27.  384 lanes → Pk=6
# at d=64.  Lane-aligned head dims (d % 128 == 0, e.g. d=128) bypass the
# target entirely and keep their measured r4 geometry Pk=1 — widening them
# to Pk=2/3 is an UNMEASURED shape class (and the in-kernel head loop's
# scratch re-OOMs well before the wider block pays off).
PACK_TARGET = int(os.environ.get("DS_FLASH_PACK_TARGET", "384"))


def _pack_width(d, h, rep=1):
    """KV heads per block.  The packed minor dim must be tile-legal: a
    multiple of the 128-lane width (or ALL heads — a block equal to the
    full array minor dim is always accepted).  Lane-aligned head dims take
    the Pk=1 fast path: one head is already tile-legal, and that is the
    geometry every d=128 measurement (r4/r5) was taken at — the PACK_TARGET
    widening below is only measured for sub-lane dims.  Among the legal
    sub-lane widths, take the LARGEST whose query-side lane width
    (rep x kv heads x d) stays within PACK_TARGET — per-grid-step work
    scales with the width while per-step overhead is fixed."""
    if d % LANE == 0:
        return 1
    legal = [p for p in range(1, h + 1)
             if h % p == 0 and ((p * d) % LANE == 0 or p == h)]
    fitting = [p for p in legal if p * rep * d <= PACK_TARGET]
    return max(fitting) if fitting else min(legal)


def _fwd2_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, *rest, scale, bq, bk, P, d, rep, q_offset):
    lse_ref = rest[0] if len(rest) % 3 == 1 else None
    scr = rest[1:] if lse_ref is not None else rest
    ms, ls, accs = scr[:P], scr[P:2 * P], scr[2 * P:3 * P]
    t = pl.program_id(2)

    @pl.when(tab_ref[2, t] == 1)
    def _init():
        for p in range(P):
            ms[p][:] = jnp.full_like(ms[p], -jnp.inf)
            ls[p][:] = jnp.zeros_like(ls[p])
            accs[p][:] = jnp.zeros_like(accs[p])

    for pk in range(P // rep):  # kv heads in this block
        # operands stay in their storage dtype (bf16): the MXU takes bf16
        # inputs at full rate with f32 accumulation — casting to f32 first
        # runs the matmuls at ~1/8 MXU throughput
        k = k_ref[0, :, pk * d:(pk + 1) * d]  # [bk, d]
        v = v_ref[0, :, pk * d:(pk + 1) * d]  # [bk, d]
        for r in range(rep):  # query heads sharing kv head pk
            p = pk * rep + r
            q = q_ref[0, :, p * d:(p + 1) * d]  # [bq, d]
            s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = _mask_if_diag(s, tab_ref, t, bq, bk, q_offset)
            m_prev = ms[p][:]
            l_prev = ls[p][:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            ls[p][:] = alpha * l_prev + jnp.sum(pr, axis=1, keepdims=True)
            accs[p][:] = accs[p][:] * alpha + jax.lax.dot_general(
                pr.astype(v.dtype), v, (((1, ), (0, )), ((), ())), preferred_element_type=jnp.float32)
            ms[p][:] = m_new

    @pl.when(tab_ref[3, t] == 1)
    def _finalize():
        for p in range(P):
            l = jnp.maximum(ls[p][:], 1e-30)
            o_ref[0, :, p * d:(p + 1) * d] = (accs[p][:] / l).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0, p] = jnp.broadcast_to(ms[p][:] + jnp.log(l),
                                                 lse_ref[0, p].shape).astype(lse_ref.dtype)


def _flash_fwd2(q, k, v, *, h, hk, causal, block_q, block_k, interpret, emit_lse=True, q_offset=0):
    # q [B, Sq, H·D], k/v [B, Sk, HK·D] (GQA-native: kv at its real width)
    # → o [B, Sq, H·D], lse [B, H, Sq, LANE]
    b, sq, hd = q.shape
    _, sk, _ = k.shape
    d = hd // h
    rep = h // hk
    Pk = _pack_width(d, hk, rep)  # kv heads per block (tile-legal kv minor dim)
    P = Pk * rep  # query heads per block — contiguous in the packed layout
    # clamp to a divisor: gcd keeps blocks maximal for seq lens that are
    # 128-multiples but not block-multiples (e.g. sq=768 with block 512 → 256)
    bq = math.gcd(min(block_q, sq), sq)
    bk = math.gcd(min(block_k, sk), sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    assert h % P == 0 and hk % Pk == 0, (h, hk, P, Pk)
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / (d**0.5)
    tab = _tri_table(nq, nk, bq, bk, causal, q_offset=q_offset)
    grid = (b, hk // Pk, tab.shape[1])

    kernel = functools.partial(_fwd2_kernel, scale=scale, bq=bq, bk=bk, P=P, d=d, rep=rep,
                               q_offset=q_offset)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh)),
            pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
            pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
        ],
        out_specs=[pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh))] + ([
            pl.BlockSpec((1, P, bq, LANE), lambda b, hh, t, tab: (b, hh, tab[0, t], 0))] if emit_lse else []),
        scratch_shapes=([pltpu.VMEM((bq, 1), jnp.float32)] * P +
                        [pltpu.VMEM((bq, 1), jnp.float32)] * P +
                        [pltpu.VMEM((bq, d), jnp.float32)] * P),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # lse stored in the INPUT dtype: the lane-broadcast layout makes it
        # the LARGEST kernel operand (B·H·S·128 — written once, re-read by
        # BOTH backward kernels).  bf16 runs halve that traffic (lse error
        # ~2⁻⁹·|lse| scales p by ≲1.5%, comparable to the bf16 dot noise
        # already present); f32 runs keep f32 lse and f32-grade grads
        out_shape=[jax.ShapeDtypeStruct((b, sq, hd), q.dtype)] + ([
            jax.ShapeDtypeStruct((b, h, sq, LANE),
                                 jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32)]
            if emit_lse else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ds_flash_fwd",
    )(tab, q, k, v)
    return (out[0], out[1]) if emit_lse else (out[0], None)


def _bwd2_block(tab_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *, scale, bq, bk, P, d, p, rep, q_offset):
    """Shared per-(block, sub-head) backward math: returns (pr, ds).

    ``p`` indexes the query head within the block; its kv head is
    ``p // rep`` (GQA-native — kv blocks are Pk = P/rep heads wide)."""
    t = pl.program_id(2)
    pk = p // rep
    # bf16 MXU operands + f32 accumulation throughout (see fwd kernel note)
    q = q_ref[0, :, p * d:(p + 1) * d]
    k = k_ref[0, :, pk * d:(pk + 1) * d]
    v = v_ref[0, :, pk * d:(pk + 1) * d]
    do = do_ref[0, :, p * d:(p + 1) * d]
    o = o_ref[0, :, p * d:(p + 1) * d]
    lse = lse_ref[0, p][:, :1].astype(jnp.float32)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=1, keepdims=True)
    s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _mask_if_diag(s, tab_ref, t, bq, bk, q_offset)
    pr = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = pr * (dp - delta) * scale
    return q, k, do, pr.astype(v.dtype), ds.astype(v.dtype)


def _dq2_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *scr,
                scale, bq, bk, P, d, rep, q_offset):
    t = pl.program_id(2)

    @pl.when(tab_ref[2, t] == 1)
    def _init():
        for p in range(P):
            scr[p][:] = jnp.zeros_like(scr[p])

    for p in range(P):
        _, k, _, _, ds = _bwd2_block(tab_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                                     scale=scale, bq=bq, bk=bk, P=P, d=d, p=p, rep=rep,
                                     q_offset=q_offset)
        scr[p][:] += jax.lax.dot_general(ds, k, (((1, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(tab_ref[3, t] == 1)
    def _finalize():
        for p in range(P):
            dq_ref[0, :, p * d:(p + 1) * d] = scr[p][:].astype(dq_ref.dtype)


def _dkv2_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref, *scr,
                 scale, bq, bk, P, d, rep, q_offset):
    t = pl.program_id(2)
    Pk = P // rep
    dk_scr, dv_scr = scr[:Pk], scr[Pk:]

    @pl.when(tab_ref[2, t] == 1)
    def _init():
        for pk in range(Pk):
            dk_scr[pk][:] = jnp.zeros_like(dk_scr[pk])
            dv_scr[pk][:] = jnp.zeros_like(dv_scr[pk])

    # the rep query heads sharing a kv head accumulate into ONE dk/dv
    # scratch — the GQA group-sum happens in VMEM, not as a post-pass
    for p in range(P):
        pk = p // rep
        q, _, do, pr, ds = _bwd2_block(tab_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                                       scale=scale, bq=bq, bk=bk, P=P, d=d, p=p, rep=rep,
                                       q_offset=q_offset)
        dv_scr[pk][:] += jax.lax.dot_general(pr, do, (((0, ), (0, )), ((), ())),
                                             preferred_element_type=jnp.float32)
        dk_scr[pk][:] += jax.lax.dot_general(ds, q, (((0, ), (0, )), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(tab_ref[3, t] == 1)
    def _finalize():
        for pk in range(Pk):
            dk_ref[0, :, pk * d:(pk + 1) * d] = dk_scr[pk][:].astype(dk_ref.dtype)
            dv_ref[0, :, pk * d:(pk + 1) * d] = dv_scr[pk][:].astype(dv_ref.dtype)


def _flash_bwd2(q, k, v, o, lse, do, *, h, hk, causal, block_q, block_k, interpret, q_offset=0):
    # packed q/o/do [B, Sq, H·D], k/v [B, Sk, HK·D] (GQA-native); dk/dv
    # returned at the real HK width — the group-sum over the rep query
    # heads sharing a kv head happens inside the dkv kernel's scratch
    b, sq, hd = q.shape
    _, sk, _ = k.shape
    d = hd // h
    rep = h // hk
    Pk = _pack_width(d, hk, rep)
    P = Pk * rep
    # clamp to a divisor: gcd keeps blocks maximal for seq lens that are
    # 128-multiples but not block-multiples (e.g. sq=768 with block 512 → 256)
    bq = math.gcd(min(block_q, sq), sq)
    bk = math.gcd(min(block_k, sk), sk)
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / (d**0.5)

    def specs(bq, bk):
        return [
            pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh)),
            pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
            pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
            pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh)),
            pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh)),
            pl.BlockSpec((1, P, bq, LANE), lambda b, hh, t, tab: (b, hh, tab[0, t], 0)),
        ]

    tab_r = _tri_table(nq, nk, bq, bk, causal, q_offset=q_offset)
    dq = pl.pallas_call(
        functools.partial(_dq2_kernel, scale=scale, bq=bq, bk=bk, P=P, d=d, rep=rep,
                          q_offset=q_offset),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hk // Pk, tab_r.shape[1]),
            in_specs=specs(bq, bk),
            out_specs=pl.BlockSpec((1, bq, P * d), lambda b, hh, t, tab: (b, tab[0, t], hh)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)] * P,
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ds_flash_dq",
    )(tab_r, q, k, v, o, do, lse)

    tab_c = _tri_table(nq, nk, bq, bk, causal, transpose=True, q_offset=q_offset)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv2_kernel, scale=scale, bq=bq, bk=bk, P=P, d=d, rep=rep,
                          q_offset=q_offset),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hk // Pk, tab_c.shape[1]),
            in_specs=specs(bq, bk),
            out_specs=[
                pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
                pl.BlockSpec((1, bk, Pk * d), lambda b, hh, t, tab: (b, tab[1, t], hh)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2 * Pk,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, sk, hk * d), k.dtype),
            jax.ShapeDtypeStruct((b, sk, hk * d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ds_flash_dkv",
    )(tab_c, q, k, v, o, do, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Forward only, not causal, keys masked at a length a batch row: what an
# encoder over a padded bucket needs (an image's patches padded to a bucket of
# a vision tower: models/kimi_vl.py).  Same packed layout and the same
# ``_pack_width`` as above, so heads of a width that is no multiple or divisor
# of the lane width (72) go through it sixteen to a block, each cut out of the
# block at an offset that is no multiple of 128.  The grid is every (query
# block, key block) pair: a block of keys wholly behind the length is masked
# like the rest (the buckets are chosen so that there are few).
# ---------------------------------------------------------------------------


def _fwd_keylen_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *scr, scale, bk, P, d):
    ms, ls, accs = scr[:P], scr[P:2 * P], scr[2 * P:3 * P]
    b, ik = pl.program_id(0), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        for p in range(P):
            ms[p][:] = jnp.full_like(ms[p], -jnp.inf)
            ls[p][:] = jnp.zeros_like(ls[p])
            accs[p][:] = jnp.zeros_like(accs[p])

    n_keys = len_ref[b]
    for p in range(P):
        q = q_ref[0, :, p * d:(p + 1) * d]
        k = k_ref[0, :, p * d:(p + 1) * d]
        v = v_ref[0, :, p * d:(p + 1) * d]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())), preferred_element_type=jnp.float32) * scale
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n_keys, s, DEFAULT_MASK_VALUE)
        m_prev, l_prev = ms[p][:], ls[p][:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        ls[p][:] = alpha * l_prev + jnp.sum(pr, axis=1, keepdims=True)
        accs[p][:] = accs[p][:] * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1, ), (0, )), ((), ())), preferred_element_type=jnp.float32)
        ms[p][:] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        for p in range(P):
            o_ref[0, :, p * d:(p + 1) * d] = (accs[p][:] / jnp.maximum(ls[p][:], 1e-30)).astype(o_ref.dtype)


def flash_attention_keylen(q, k, v, kv_len, *, block_q: int = 256, block_k: int = 512,
                           interpret: Optional[bool] = None):
    """Non-causal attention of q, k, v [B, S, H, D] in which row ``b`` sees its
    first ``kv_len[b]`` keys (>= 1) and no other: a padded bucket.  S is a
    multiple of 128.  Forward only.  Query rows behind the length are
    computed like the rest and are the caller's to drop."""
    b, s, h, d = q.shape
    if s % LANE:
        raise ValueError(f"flash_attention_keylen: {s} positions are no multiple of {LANE}")
    if interpret is None:
        interpret = not traced_for_tpu()
    P = _pack_width(d, h)
    bq, bk = math.gcd(min(block_q, s), s), math.gcd(min(block_k, s), s)
    spec_q = pl.BlockSpec((1, bq, P * d), lambda b, hh, iq, ik, n: (b, iq, hh))
    spec_k = pl.BlockSpec((1, bk, P * d), lambda b, hh, iq, ik, n: (b, ik, hh))
    out = pl.pallas_call(
        functools.partial(_fwd_keylen_kernel, scale=1.0 / (d**0.5), bk=bk, P=P, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // P, s // bq, s // bk),
            in_specs=[spec_q, spec_k, spec_k],
            out_specs=spec_q,
            scratch_shapes=([pltpu.VMEM((bq, 1), jnp.float32)] * 2 * P + [pltpu.VMEM((bq, d), jnp.float32)] * P),
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            # sixteen heads a block at d = 72: 32 lane-padded [bq, 1] carries and 16 accumulators beside the blocks
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="ds_flash_fwd_keylen",
    )(jnp.asarray(kv_len, jnp.int32), q.reshape(b, s, h * d), k.reshape(b, s, h * d), v.reshape(b, s, h * d))
    return out.reshape(b, s, h, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret, q_offset=0):
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, q_offset, emit_lse=False)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, q_offset=0, emit_lse=True):
    # [B, S, H, D] in/out; kernels run on the packed [B, S, H·D] view
    # (a FREE reshape — same memory layout, no transpose).  GQA-native:
    # kv stays at its real HK width — the kernels pair each kv-head block
    # with the contiguous run of query heads that share it (no repeated-KV
    # materialization; 4× less KV HBM traffic at Llama-3-8B's 32q/8kv)
    b, sq, h, d = q.shape
    _, sk, hk_real, _ = k.shape
    assert h % hk_real == 0, f"query heads {h} not a multiple of kv heads {hk_real}"
    hk = hk_real
    if hk != h and not _gqa_native_ok(d, h, hk):
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
        hk = h
    qp = q.reshape(b, sq, h * d)
    kp = k.reshape(b, sk, hk * d)
    vp = v.reshape(b, sk, hk * d)
    out, lse = _flash_fwd2(qp, kp, vp, h=h, hk=hk, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret, emit_lse=emit_lse,
                           q_offset=q_offset)
    if emit_lse:
        # named so remat policies can SAVE the kernel outputs (see
        # models/llama._resolve_remat_policy 'flash_saveable'): without
        # this, per-block jax.checkpoint re-runs the forward kernel in the
        # backward before the dq/dkv kernels — three attention passes
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
    res = (qp, kp, vp, out, lse, (b, sq, sk, h, hk, hk_real, d))
    return out.reshape(b, sq, h, d), res


def _bwd(causal, block_q, block_k, interpret, q_offset, res, g):
    qp, kp, vp, out, lse, (b, sq, sk, h, hk, hk_real, d) = res
    do = g.reshape(b, sq, h * d)
    dq, dk, dv = _flash_bwd2(qp, kp, vp, out, lse, do, h=h, hk=hk, causal=causal,
                             block_q=block_q, block_k=block_k, interpret=interpret,
                             q_offset=q_offset)
    dq = dq.reshape(b, sq, h, d)
    dk = dk.reshape(b, sk, hk, d)
    dv = dv.reshape(b, sk, hk, d)
    if hk != hk_real:
        # VMEM-cap fallback ran the kernels over repeated KV: group-sum the
        # per-query-head kv grads back onto the real kv heads
        rep = hk // hk_real
        dk = dk.reshape(b, sk, hk_real, rep, d).sum(axis=3)
        dv = dv.reshape(b, sk, hk_real, rep, d).sum(axis=3)
    # otherwise dk/dv are already at the real HK width — the GQA group-sum
    # happened inside the dkv kernel's scratch accumulation
    return dq, dk, dv


def _flash_fwd_with_res(q, k, v, causal, block_q, block_k, interpret, q_offset=0):
    return _fwd(q, k, v, causal, block_q, block_k, interpret, q_offset)


_flash_attention.defvjp(_flash_fwd_with_res, _bwd)


def _flash_sharded(q, k, v, causal, block_q, block_k, interpret, mesh, q_offset=0):
    """Run the kernels inside shard_map over the governing (trace) mesh.

    Mosaic custom calls cannot be auto-partitioned by GSPMD — a multi-device
    jit containing a Pallas call must wrap it in shard_map.  Batch shards
    over the data axes; heads shard over the seq/tensor axes when divisible
    (the layout Ulysses' all-to-all and AutoTP establish); a non-divisible
    dim replicates (correct, just not distributed)."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS
    b, _, h, _ = q.shape
    hk = k.shape[2]
    batch_axes = tuple(a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1)
    nb = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    if nb > 1 and b % nb:
        batch_axes = ()
    head_axes = tuple(a for a in (SEQ_AXIS, TENSOR_AXIS) if mesh.shape.get(a, 1) > 1)
    nh = math.prod(mesh.shape[a] for a in head_axes) if head_axes else 1
    if nh > 1 and (h % nh or hk % nh):
        head_axes = ()
    spec = P(batch_axes or None, None, head_axes or None, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: _flash_attention(q_, k_, v_, causal, block_q, block_k, interpret,
                                            q_offset),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call out_shapes carry no varying-mesh-axes annotation
        check_vma=False)
    return fn(q, k, v)


def flash_attention(q,
                    k,
                    v,
                    *,
                    causal: bool = True,
                    segment_ids=None,
                    sliding_window: int = 0,
                    block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None,
                    q_position_offset: int = 0):
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    GQA (fewer kv heads) is kernel-native: kv blocks stay at the real kv
    width and each pairs with the contiguous group of query heads sharing
    it; kv grads are group-summed in kernel scratch (ref: the reference's
    blocked GQA attention, deepspeed/inference/v2/kernels/ragged_ops/).
    ``segment_ids``/``sliding_window`` fall back to the chunked jnp path
    (packed-sequence masking in-kernel is a follow-up).
    """
    if (segment_ids is not None or (sliding_window and sliding_window > 0)
            or q.shape[1] % LANE != 0 or k.shape[1] % LANE != 0):
        # packed-sequence masking in-kernel is a follow-up; ragged lengths
        # would force sub-128 blocks that violate TPU tiling
        if q_position_offset:
            raise ValueError("q_position_offset requires 128-aligned seq lens and no "
                             "segment/window masks (the chunked fallback has no offset)")
        from ..models.llama import chunked_attention
        return chunked_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                                 sliding_window=sliding_window)
    if interpret is None:
        interpret = not traced_for_tpu()
    if isinstance(q, jax.core.Tracer) and not in_manual_mesh():
        mesh = get_trace_mesh()
        if mesh is not None and mesh.size > 1:
            return _flash_sharded(q, k, v, causal, block_q, block_k, interpret, mesh,
                                  q_position_offset)
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret, q_position_offset)
