"""FPDT — Fully Pipelined Distributed Transformer (long-context attention).

Reference: ``deepspeed/sequence/fpdt_layer.py`` — chunks the local sequence,
streams KV chunks through device memory with host offload + double
buffering, and merges partial attention results with an online softmax
(``update_out_and_lse:58``; classes ``FPDT_Attention:971``,
``_FPDTGPUOffloadingAttentionImpl_:510``).

TPU-native realisation:

* ``chunked_attention`` — a ``lax.scan`` over KV chunks with the online-
  softmax recurrence.  Peak memory is O(S·chunk) instead of O(S²); XLA
  pipelines the chunk loads against the matmuls (the reference's hand-rolled
  double buffering is program order here).
* ``fpdt_attention`` — adds query chunking (outer scan), bounding live
  attention state to O(chunk²) per step: the full FPDT memory profile.
* Host offload: rather than manually shuttling KV chunks (the reference's
  ``FPDT_Offloading_Wrapper``), pair ``fpdt_attention`` with
  ``jax.checkpoint`` offload policies (``offload_dot_with_no_batch_dims`` /
  ``save_and_offload_only_these_names``) so XLA schedules HBM↔host DMAs —
  see ``runtime/activation_checkpointing``.
* Combined with Ulysses (``sequence/layer.py``) or ring attention
  (``sequence/ring.py``) for the distributed dimension: Ulysses/ring shard
  the sequence across chips; FPDT chunking bounds the per-chip working set.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def update_out_and_lse(out, lse, block_out, block_lse):
    """Merge a new attention block into (out, lse) running state.

    Parity with ref ``sequence/fpdt_layer.py:58 update_out_and_lse``:
    out/block_out: [B, H, Sq, D] fp32; lse/block_lse: [B, H, Sq]
    (log-sum-exp including the running max).  Returns the merged pair.
    """
    lse_new = jnp.logaddexp(lse, block_lse)
    out_new = (out * jnp.exp(lse - lse_new)[..., None] +
               block_out * jnp.exp(block_lse - lse_new)[..., None])
    return out_new, lse_new


def _chunk_partials(q, k_chunk, v_chunk, q_pos, k_pos, scale, causal):
    """(out, lse) partials of one q-block × kv-chunk product.
    q: [B, Sq, H, D]; k/v_chunk: [B, C, Hkv, D] → out [B,H,Sq,D], lse [B,H,Sq].

    The matmuls keep their STORAGE dtype operands with f32 accumulation —
    bf16 inputs run the MXU at full rate; the r4 version upcast q AND k to
    f32 first, running both einsums at ~1/8 MXU throughput, which is most
    of why FPDT measured 3.95x slower than flash at 32k (r4, docs/PERF.md).
    The softmax bookkeeping (max/exp/log) stays f32."""
    nh, nkv = q.shape[2], k_chunk.shape[2]
    if nkv != nh:
        rep = nh // nkv
        k_chunk = jnp.repeat(k_chunk, rep, axis=2)
        v_chunk = jnp.repeat(v_chunk, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_chunk,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_chunk.dtype), v_chunk,
                     preferred_element_type=jnp.float32)
    # normalise to a (out, lse) pair: out already implicitly scaled by exp(m)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    return out, lse


def chunked_attention(q, k, v, *, chunk_size: int, causal: bool = True,
                      q_offset: int = 0, k_offset: int = 0):
    """Attention with the KV sequence streamed in chunks (inner FPDT loop).

    q: [B, Sq, H, D]; k/v: [B, Sk, Hkv, D]; Sk must divide by chunk_size.
    ``q_offset``/``k_offset`` are global position offsets (used by the outer
    query-chunk loop and by sequence-sharded callers).
    """
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    assert sk % chunk_size == 0, f"Sk={sk} not divisible by chunk_size={chunk_size}"
    n_chunks = sk // chunk_size
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    k_chunks = k.reshape(b, n_chunks, chunk_size, *k.shape[2:]).swapaxes(0, 1)
    v_chunks = v.reshape(b, n_chunks, chunk_size, *v.shape[2:]).swapaxes(0, 1)

    out0 = jnp.zeros((b, nh, sq, hd), jnp.float32)
    lse0 = jnp.full((b, nh, sq), _NEG_INF, jnp.float32)

    # per-chunk remat: without it the scan VJP stacks every chunk's
    # [B,H,Sq,chunk] score residuals — at S=32k that is the full S^2 score
    # matrix (24 GB measured), the exact thing FPDT exists to avoid.  The
    # backward recomputes one chunk's partials at a time instead.
    partials = jax.checkpoint(
        lambda q_, k_, v_, qp, kp: _chunk_partials(q_, k_, v_, qp, kp, scale, causal))

    def step(carry, inputs):
        out, lse = carry
        idx, k_c, v_c = inputs
        k_pos = k_offset + idx * chunk_size + jnp.arange(chunk_size)
        c_out, c_lse = partials(q, k_c, v_c, q_pos, k_pos)
        return update_out_and_lse(out, lse, c_out, c_lse), None
        # (a lax.cond skip of above-diagonal chunks was measured SLOWER on
        # v5e — 441 vs 334 ms at S=32k attention fwd+bwd, the branch breaks
        # the scan's software pipelining despite halving FLOPs; triangular
        # savings come from the STAGED flash path in fpdt_attention instead)

    (out, lse), _ = jax.lax.scan(step, (out0, lse0),
                                 (jnp.arange(n_chunks), k_chunks, v_chunks))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _flash_group_ok(q, k, sq, sk):
    """Staged-flash eligibility: the kernel path needs 128-aligned seq lens
    and a TPU-lowerable environment; GQA handled kernel-natively."""
    from ..ops.flash_attention import LANE
    return sq % LANE == 0 and sk % LANE == 0


def fpdt_attention(q, k, v, *, causal: bool = True, segment_ids=None,
                   query_chunk_size: int = 512, kv_chunk_size: int = 512,
                   q_offset: int = 0, k_offset: int = 0, use_flash: Optional[bool] = None,
                   flash_groups: int = 8):
    """Double-chunked attention: outer loop over query chunks, inner sweep
    over KV chunks (ref: FPDT_Attention:971 — both loops, minus the manual
    host staging which remat/offload policies supply declaratively).

    STAGED-FLASH path (r5, default on TPU when shapes allow): the query
    sequence splits into ``flash_groups`` groups and each group runs ONE
    triangular Pallas flash call against its visible kv PREFIX
    (``q_position_offset`` keeps causality exact in-kernel), wrapped in
    ``jax.checkpoint`` so only the group OUTPUTS survive to the backward —
    the FPDT memory profile at kernel-grade FLOPs.  The per-group prefix
    also realises the triangle structurally: total work is
    (G+1)/2G of the full square (a lax.cond skip inside the jnp scan was
    measured SLOWER — it breaks scan pipelining).  The jnp double-scan
    remains the fallback (CPU tests, ragged shapes, explicit
    use_flash=False)."""
    if segment_ids is not None:
        raise NotImplementedError("fpdt_attention does not support segment_ids yet")
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    eligible = (causal and q_offset == 0 and k_offset == 0 and sq == sk
                and _flash_group_ok(q, k, sq, sk))
    if use_flash and not eligible:
        # an explicit request must not silently drop offsets / assume sq==sk
        raise ValueError(
            "use_flash=True requires causal self-attention with q_offset=0, "
            f"k_offset=0, sq == sk and 128-aligned lengths (got causal={causal}, "
            f"q_offset={q_offset}, k_offset={k_offset}, sq={sq}, sk={sk})")
    if use_flash is None:
        use_flash = eligible
    if use_flash:
        from ..ops.flash_attention import flash_attention
        G = flash_groups
        while G > 1 and (sq % G or (sq // G) % 128):
            G //= 2
        glen = sq // G
        outs = []
        for g in range(G):
            q_grp = jax.lax.slice_in_dim(q, g * glen, (g + 1) * glen, axis=1)
            k_pfx = jax.lax.slice_in_dim(k, 0, (g + 1) * glen, axis=1)
            v_pfx = jax.lax.slice_in_dim(v, 0, (g + 1) * glen, axis=1)
            grp = jax.checkpoint(
                lambda q_, k_, v_, off=g * glen: flash_attention(
                    q_, k_, v_, causal=True, q_position_offset=off))
            outs.append(grp(q_grp, k_pfx, v_pfx))
        return jnp.concatenate(outs, axis=1)
    qc = min(query_chunk_size, sq)
    assert sq % qc == 0, f"Sq={sq} not divisible by query_chunk_size={qc}"
    n_q = sq // qc
    if n_q == 1:
        return chunked_attention(q, k, v, chunk_size=min(kv_chunk_size, k.shape[1]),
                                 causal=causal, q_offset=q_offset, k_offset=k_offset)

    q_chunks = q.reshape(b, n_q, qc, nh, hd).swapaxes(0, 1)

    def one_q_chunk(idx_and_chunk):
        idx, q_c = idx_and_chunk
        return chunked_attention(q_c, k, v, chunk_size=min(kv_chunk_size, k.shape[1]),
                                 causal=causal,
                                 q_offset=q_offset + idx * qc, k_offset=k_offset)

    # outer remat bounds the map VJP's saved state to the q-chunk OUTPUTS:
    # each q-chunk's inner KV scan is recomputed (and re-chunk-rematted)
    # during its own backward — O(chunk^2) live, the FPDT memory profile
    outs = jax.lax.map(jax.checkpoint(one_q_chunk), (jnp.arange(n_q), q_chunks))
    return outs.swapaxes(0, 1).reshape(b, sq, nh, hd)


def _current_sharding(ndim: int, memory_kind: str):
    """Batch-sharded NamedSharding on the global mesh (or single-device)
    with the given memory kind."""
    from ..comm.mesh import BATCH_AXES, get_global_mesh, has_global_mesh
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding
    if has_global_mesh():
        mesh = get_global_mesh()
        spec = PartitionSpec(*([BATCH_AXES] + [None] * (ndim - 1)))
        return NamedSharding(mesh, spec, memory_kind=memory_kind)
    return SingleDeviceSharding(jax.devices()[0], memory_kind=memory_kind)


def host_kv(k, v):
    """Place the full K/V on HOST memory (the FPDT offloading KV store,
    ref: sequence/fpdt_layer.py:510 _FPDTGPUOffloadingAttentionImpl_ — there
    a hand-managed pinned-host tensor pair; here a memory_kind placement).
    Feed the results to ``fpdt_host_offload_attention`` (jit the caller with
    matching pinned_host in_shardings to keep them host-resident)."""
    host = _current_sharding(k.ndim, "pinned_host")
    return jax.device_put(k, host), jax.device_put(v, host)


def fpdt_host_offload_attention(q, k, v, *, chunk_size: int = 512, causal: bool = True,
                                q_offset: int = 0, k_offset: int = 0):
    """Chunked attention whose KV lives in HOST memory: each iteration
    slices one chunk from the host-resident K/V and copies it into device
    memory before the matmuls (explicit ``jax.device_put`` inside the scan —
    XLA's latency-hiding scheduler overlaps chunk i+1's host→HBM copy with
    chunk i's compute, which is the reference's double buffering,
    ref: fpdt_layer.py:510).  Device-resident working set is O(chunk), not
    O(S); the [B, Sk, H, D] KV never materializes in HBM."""
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    assert sk % chunk_size == 0, f"Sk={sk} not divisible by chunk_size={chunk_size}"
    n_chunks = sk // chunk_size
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)
    dev = _current_sharding(k.ndim, "device")

    out0 = jnp.zeros((b, nh, sq, hd), jnp.float32)
    lse0 = jnp.full((b, nh, sq), _NEG_INF, jnp.float32)

    # per-chunk remat, same as chunked_attention: the scan VJP must not
    # stack every chunk's [B,H,Sq,chunk] score residuals (the full S^2
    # matrix at long context)
    partials = jax.checkpoint(
        lambda q_, k_, v_, qp, kp: _chunk_partials(q_, k_, v_, qp, kp, scale, causal))

    def step(carry, idx):
        out, lse = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, idx * chunk_size, chunk_size, 1)
        v_c = jax.lax.dynamic_slice_in_dim(v, idx * chunk_size, chunk_size, 1)
        k_c = jax.device_put(k_c, dev)   # host → HBM, one chunk
        v_c = jax.device_put(v_c, dev)
        k_pos = k_offset + idx * chunk_size + jnp.arange(chunk_size)
        c_out, c_lse = partials(q, k_c, v_c, q_pos, k_pos)
        return update_out_and_lse(out, lse, c_out, c_lse), None

    (out, lse), _ = jax.lax.scan(step, (out0, lse0), jnp.arange(n_chunks))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


class FPDTAttention:
    """Drop-in attention impl (``attn_fn(q, k, v, causal=..)``) combining
    FPDT chunking with optional Ulysses resharding when a ``seq`` mesh axis
    is live (ref class: sequence/fpdt_layer.py:971 FPDT_Attention)."""

    def __init__(self, query_chunk_size: int = 512, kv_chunk_size: int = 512,
                 ulysses: bool = True):
        self.query_chunk_size = query_chunk_size
        self.kv_chunk_size = kv_chunk_size
        self.ulysses = ulysses

    def __call__(self, q, k, v, *, causal: bool = True, segment_ids=None):
        inner = partial(fpdt_attention, causal=causal, segment_ids=segment_ids,
                        query_chunk_size=self.query_chunk_size,
                        kv_chunk_size=self.kv_chunk_size)
        if self.ulysses:
            from .layer import DistributedAttention
            return DistributedAttention(lambda q, k, v, **kw: inner(q, k, v))(q, k, v)
        return inner(q, k, v)
