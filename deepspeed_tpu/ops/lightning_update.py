"""One position of Lightning linear attention on the slot arena, in place.

    S <- lambda_h S + k v^T;   o = S^T q

for every row of a decode step, with a constant decay a head: the row's state
``[H, K, V]`` float32 read out of the arena ``[layers, slots, H, K, V]`` by
its slot index, updated and written back where it lies.  The arena is aliased
input to output, so a step moves a live row's state once each way (4.2 MB a
row and layer at 32 heads of 128 x 128) and nothing else of the arena.
``ops/kda_update.py``'s frame (and ``ops/ssd_update.py``'s before it); what
differs is the update: the decay is a head's constant, not a key channel's
gate, and the write is the plain outer product, with no ``S^T k`` first.

Grid ``(rows, head blocks)``; the slot indices, a row's flags (``LIVE``: the
row carries a token; ``FRESH``: it starts a sequence, so its state is zero
whatever the slot holds) and the layer's index are scalar-prefetched and
choose the block.  A row that carries no token reads and writes the scratch
slot 0's first block and changes nothing.

Inside a block a head's state is ``[K, V]`` with the values in the lanes.
``k`` and ``q`` arrive channel-major, ``[K, heads of the block]``, and a
head's column is broadcast along the lanes, as is the decay (one value a
head, repeated down the channels); ``v`` arrives as rows ``[heads of the
block, V]`` broadcast along the sublanes, and ``o`` leaves as such rows.
Five operations a state element: the decay's product, two for the write, two
for ``S^T q``.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import traced_for_tpu
from .kda_update import block_heads
from .ssd_update import FRESH, LIVE


def _lightning_update_kernel(slot_ref, flag_ref, layer_ref, s_ref, decay_ref, k_ref, q_ref, v_ref, so_ref, o_ref, *,
                             heads):
    del slot_ref, layer_ref
    flag = flag_ref[pl.program_id(0)]

    @pl.when((flag & LIVE) != 0)
    def _():
        fresh = (flag & FRESH) != 0
        for j in range(heads):
            state = decay_ref[0, :, j:j + 1] * jnp.where(fresh, 0.0, s_ref[j]) \
                + k_ref[0, 0, :, j:j + 1] * v_ref[0, 0, j:j + 1, :]                      # [K, V]
            so_ref[j] = state
            o_ref[0, 0, j:j + 1, :] = jnp.sum(q_ref[0, 0, :, j:j + 1] * state, axis=0, keepdims=True)

    @pl.when((flag & LIVE) == 0)
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def lightning_update(arena, layer, slot, flags, q, k, v, log_decay, *, interpret: Optional[bool] = None):
    """``arena`` [L, slots, H, K, V] float32; ``layer`` an index (traced in a
    scanned trunk); ``slot``, ``flags`` [B] int32 (``LIVE``, ``FRESH``); ``q``
    (scaled), ``k`` [B, H, K]; ``v`` [B, H, V]; ``log_decay`` [H] (``<= 0``);
    all float32.  Returns (``o`` [B, H, V] = ``S^T q`` of the new states,
    zeros for a row without ``LIVE``; the arena, the same buffer)."""
    if interpret is None:
        interpret = not traced_for_tpu()
    return _lightning_update(arena, jnp.asarray(layer, jnp.int32), slot, flags, q, k, v, log_decay,
                             block_heads(q.shape[1]), bool(interpret))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _lightning_update(arena, layer, slot, flags, q, k, v, log_decay, hb, interpret):
    """A jitted function of its own: a trunk that calls it a layer of a run
    traces and lowers the kernel (a body unrolled over the block's heads)
    once a program, not once a call."""
    f32 = jnp.float32
    b, h, dk = q.shape
    dv = arena.shape[-1]
    nblk = h // hb
    # channel-major tiles a head block: [B, blocks, K, heads of the block]
    cols = lambda t: jnp.swapaxes(t.astype(f32).reshape(b, nblk, hb, dk), 2, 3)  # noqa: E731
    decay = jnp.broadcast_to(jnp.exp(log_decay.astype(f32)).reshape(nblk, 1, hb), (nblk, dk, hb))

    def state_block(r, blk, slot_ref, flag_ref, layer_ref):
        live = flag_ref[r] & LIVE
        return layer_ref[0], slot_ref[r] * live, blk * live, 0, 0

    col = pl.BlockSpec((1, 1, dk, hb), lambda r, blk, *_: (r, blk, 0, 0))
    row = pl.BlockSpec((1, 1, hb, dv), lambda r, blk, *_: (r, blk, 0, 0))
    arena, o = pl.pallas_call(
        functools.partial(_lightning_update_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nblk),
            in_specs=[pl.BlockSpec((None, None, hb, dk, dv), state_block),
                      pl.BlockSpec((1, dk, hb), lambda r, blk, *_: (blk, 0, 0)), col, col, row],
            out_specs=[pl.BlockSpec((None, None, hb, dk, dv), state_block), row],
        ),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype), jax.ShapeDtypeStruct((b, nblk, hb, dv), f32)],
        input_output_aliases={3: 0},       # the arena, after the three prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ds_lightning_update",
    )(slot.astype(jnp.int32), flags.astype(jnp.int32), layer.reshape(1), arena,
      decay, cols(k), cols(q), v.astype(f32).reshape(b, nblk, hb, dv))
    return o.reshape(b, h, dv), arena
