"""Ring attention — true context parallelism over the ``seq`` mesh axis.

The reference has no blockwise ring attention (SURVEY §2.3: long-context
there is Ulysses + FPDT chunking, ``deepspeed/sequence/fpdt_layer.py``).  On
TPU a ring schedule is the natural long-context design: KV blocks rotate
around the ICI ring via ``lax.ppermute`` while each device accumulates
attention for its resident Q block with an online-softmax merge — the same
math as FPDT's ``update_out_and_lse`` (ref: sequence/fpdt_layer.py:58) but
with the chunk stream coming from neighbours over ICI instead of from host
memory.  Sequence length per device stays constant as the ``seq`` axis grows,
so context scales linearly with chips.

Design notes:
  * SPMD via ``shard_map``; the per-step ``ppermute`` is independent of that
    step's block compute, so XLA's latency-hiding scheduler overlaps the
    collective-permute with the attention matmuls (the hand-rolled double
    buffering of the reference's FPDT falls out of program order).
  * Causal skip: a block whose source rank sits strictly after ours is fully
    masked; a per-device ``lax.cond`` skips its FLOPs entirely.  Rank r
    computes r+1 of the P blocks — the usual causal ring imbalance; the
    ``striped`` layout (each rank holds an interleaved stripe of the
    sequence, see ``striped_ring_attention``) rebalances it.
  * Gradients flow through ``lax.scan`` + ``ppermute`` transpose rules, so
    the backward pass is itself a ring program — no custom VJP needed.
"""

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..comm.mesh import BATCH_AXES, SEQ_AXIS, TENSOR_AXIS, get_global_mesh

_NEG_INF = -1e30


def _match_vma(like):
    """Return a fn casting an unvarying array to the varying-manual-axes set
    of ``like`` (shard_map vma typing; no-op outside shard_map)."""
    axes = jax.typeof(like).vma
    if not axes:
        return lambda x: x
    return lambda x: jax.lax.pcast(x, tuple(axes), to="varying")


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool):
    """Ring attention on local shards [B, s_local, H(local), D].  Block
    partials and the online-softmax merge are shared with FPDT
    (fpdt_layer._chunk_partials / update_out_and_lse)."""
    from .fpdt_layer import _chunk_partials, update_out_and_lse
    ring = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, sq, nh, hd = q.shape
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    q_pos = me * sq + jnp.arange(sq)

    out0 = jnp.zeros((b, nh, sq, hd), jnp.float32)
    lse0 = jnp.full((b, nh, sq), _NEG_INF, jnp.float32)
    # match the varying-manual-axes type of the computed branch so the causal
    # skip cond and the scan carry typecheck under shard_map's vma system
    out0, lse0 = jax.tree.map(_match_vma(q), (out0, lse0))
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def step(carry, t):
        out, lse, k_blk, v_blk, src_block = carry
        k_pos = src_block * sq + jnp.arange(sq)

        def compute(args):
            out, lse = args
            b_out, b_lse = _chunk_partials(q32, k_blk, v_blk, q_pos, k_pos, scale, causal)
            return update_out_and_lse(out, lse, b_out, b_lse)

        if causal:
            # Fully-masked block (source strictly after us): skip its FLOPs.
            visible = src_block <= me
            out, lse = jax.lax.cond(visible, compute, lambda args: args, (out, lse))
        else:
            out, lse = compute((out, lse))

        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        src_nxt = jax.lax.ppermute(src_block, axis_name, perm)
        return (out, lse, k_nxt, v_nxt, src_nxt), None

    (out, lse, _, _, _), _ = jax.lax.scan(step, (out0, lse0, k, v, me),
                                          jnp.arange(ring))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, sq, H, D]


def ring_attention(q, k, v, *, causal: bool = True, segment_ids=None,
                   mesh=None, seq_axis: str = SEQ_AXIS):
    """Context-parallel attention on globally [B, S, H, D] arrays whose S dim
    is sharded over ``seq_axis``.  Falls back to the jnp reference when the
    mesh has no sequence axis (so it is safe as a default attention impl)."""
    mesh = mesh or get_global_mesh()
    if mesh.shape.get(seq_axis, 1) == 1:
        from ..models.llama import reference_attention
        return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids)
    if segment_ids is not None:
        raise NotImplementedError("ring attention does not support segment_ids yet")

    q_spec, kv_spec = _qkv_specs(mesh, q.shape, k.shape, seq_axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec)
    def mapped(q, k, v):
        return _ring_attention_local(q, k, v, axis_name=seq_axis, causal=causal)

    return mapped(q, k, v)


def _qkv_specs(mesh, q_shape, kv_shape, seq_axis: str):
    """[B, S, H, D] specs: batch over the data axes when divisible, sequence
    over the ring axis, heads over tensor ONLY when both the q and the kv head
    counts divide the tensor axis — otherwise heads stay replicated (sharding
    just one of them would break the GQA head↔group alignment per shard)."""
    import numpy as _np
    bsz_axes = [a for a in BATCH_AXES if mesh.shape.get(a, 1) > 1]
    bspec = tuple(bsz_axes) if bsz_axes and q_shape[0] % int(
        _np.prod([mesh.shape[a] for a in bsz_axes])) == 0 else None
    tp_size = mesh.shape.get(TENSOR_AXIS, 1)
    hspec = (TENSOR_AXIS if tp_size > 1 and q_shape[2] % tp_size == 0
             and kv_shape[2] % tp_size == 0 else None)
    return (P(bspec, seq_axis, hspec, None), P(bspec, seq_axis, hspec, None))


def striped_ring_attention(q, k, v, *, causal: bool = True, segment_ids=None,
                           mesh=None, seq_axis: str = SEQ_AXIS):
    """Load-balanced ("zigzag") causal ring attention.

    The plain causal ring gives rank r work proportional to r+1.  Here each
    rank holds TWO half-blocks — the r-th from the front of the sequence and
    the r-th from the back — so every rank sees the same masked/unmasked mix.
    The caller must lay out the sequence in zigzag order (see
    ``zigzag_reorder`` / ``zigzag_restore``); positions are reconstructed
    internally for the causal mask.
    """
    mesh = mesh or get_global_mesh()
    ring = mesh.shape.get(seq_axis, 1)
    if ring == 1:
        from ..models.llama import reference_attention
        return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids)
    if segment_ids is not None:
        raise NotImplementedError("striped ring attention does not support segment_ids")

    q_spec, kv_spec = _qkv_specs(mesh, q.shape, k.shape, seq_axis)

    from .fpdt_layer import _chunk_partials, update_out_and_lse

    @partial(jax.shard_map, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec)
    def mapped(q, k, v):
        me = jax.lax.axis_index(seq_axis)
        b, sl, nh, hd = q.shape
        half = sl // 2
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
        q32 = q.astype(jnp.float32)
        # local halves: front block index = me, back block index = 2*ring-1-me
        front, back = me, 2 * ring - 1 - me
        pos = jnp.concatenate([front * half + jnp.arange(half),
                               back * half + jnp.arange(half)])
        out0 = jnp.zeros((b, nh, sl, hd), jnp.float32)
        lse0 = jnp.full((b, nh, sl), _NEG_INF, jnp.float32)
        out0, lse0 = jax.tree.map(_match_vma(q), (out0, lse0))
        perm = [(j, (j + 1) % ring) for j in range(ring)]

        def step(carry, t):
            out, lse, k_blk, v_blk, src_front, src_back = carry
            k_pos = jnp.concatenate([src_front * half + jnp.arange(half),
                                     src_back * half + jnp.arange(half)])
            b_out, b_lse = _chunk_partials(q32, k_blk, v_blk, pos, k_pos, scale, causal)
            out, lse = update_out_and_lse(out, lse, b_out, b_lse)
            k_nxt = jax.lax.ppermute(k_blk, seq_axis, perm)
            v_nxt = jax.lax.ppermute(v_blk, seq_axis, perm)
            sf = jax.lax.ppermute(src_front, seq_axis, perm)
            sb = jax.lax.ppermute(src_back, seq_axis, perm)
            return (out, lse, k_nxt, v_nxt, sf, sb), None

        (out, lse, _, _, _, _), _ = jax.lax.scan(
            step, (out0, lse0, k, v, front, back), jnp.arange(ring))
        return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)

    return mapped(q, k, v)


def zigzag_reorder(x, ring: int, axis: int = 1):
    """Permute a sequence dim into the zigzag layout consumed by
    ``striped_ring_attention``: rank r gets chunks (r, 2*ring-1-r)."""
    n = x.shape[axis]
    assert n % (2 * ring) == 0, f"seq len {n} not divisible by 2*ring={2*ring}"
    chunk = n // (2 * ring)
    idx = []
    for r in range(ring):
        idx.extend(range(r * chunk, (r + 1) * chunk))
        idx.extend(range((2 * ring - 1 - r) * chunk, (2 * ring - r) * chunk))
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def zigzag_restore(x, ring: int, axis: int = 1):
    """Inverse of ``zigzag_reorder``."""
    n = x.shape[axis]
    assert n % (2 * ring) == 0, f"seq len {n} not divisible by 2*ring={2*ring}"
    chunk = n // (2 * ring)
    idx = []
    for r in range(ring):
        idx.extend(range(r * chunk, (r + 1) * chunk))
        idx.extend(range((2 * ring - 1 - r) * chunk, (2 * ring - r) * chunk))
    inv = [0] * n
    for new_pos, old_pos in enumerate(idx):
        inv[old_pos] = new_pos
    return jnp.take(x, jnp.asarray(inv), axis=axis)
