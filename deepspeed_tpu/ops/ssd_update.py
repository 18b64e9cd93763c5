"""One position of the Mamba-2 recurrence on the slot arena, in place.

    S <- exp(dt A) S + (dt x) (x) B        y = S C

for every row of a decode step, the row's state ``[H, P, N]`` float32 read
out of the arena ``[layers, slots, H, P, N]`` by its slot index, updated and
written back where it lies: the arena is aliased input to output, so a step
moves a live row's state once each way and nothing else of the arena.
Written as gather, update and scatter the same step moves it about three
times each way.

Grid ``(rows, head blocks)`` (one block a row at the published sizes); the slot indices, a row's flags (bit 0: the row
carries a token; bit 1: it starts a sequence, so its state is zero whatever
the slot holds) and the layer's index are scalar-prefetched and choose the
block.  A row that carries no token reads and writes the scratch slot 0's
first block and changes nothing.

Inside a block a head's state is ``[P, N]`` with the state dimension in the
lanes, so what multiplies it a head and a channel (``dt x``, ``exp(dt A)``)
arrives channel-major, ``[P, heads of the block]``, and a head's column is
broadcast along the lanes; ``B`` and ``C`` are rows broadcast along the
sublanes; ``y`` leaves channel-major too.  The wrapper does those (tiny)
transposes.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import traced_for_tpu

#: heads a block holds at most: a row's whole state at the published 64 heads, 2 MiB at 64 x 64 x 128 float32
#: and 8 MiB of VMEM with both ways double-buffered (on a v5e 36 layers of 32 live rows took 11.5, 9.9, 9.3 and
#: 9.1 ms at 8, 16, 32 and 64 heads a block)
_BLOCK_HEADS = 64
LIVE, FRESH = 1, 2


def block_heads(n_heads: int) -> int:
    return next(h for h in range(min(n_heads, _BLOCK_HEADS), 0, -1) if n_heads % h == 0)


def _ssd_update_kernel(slot_ref, flag_ref, layer_ref, s_ref, xdt_ref, decay_ref, bc_ref, so_ref, y_ref, *, heads):
    del slot_ref, layer_ref
    flag = flag_ref[pl.program_id(0)]

    @pl.when((flag & LIVE) != 0)
    def _():
        b_row, c_row = bc_ref[0, 0:1, :], bc_ref[0, 1:2, :]                  # [1, N]
        fresh = (flag & FRESH) != 0
        for j in range(heads):
            state = jnp.where(fresh, 0.0, s_ref[j])                          # [P, N]
            state = decay_ref[0, 0, :, j:j + 1] * state + xdt_ref[0, 0, :, j:j + 1] * b_row
            so_ref[j] = state
            y_ref[0, 0, :, j:j + 1] = jnp.sum(state * c_row, axis=-1, keepdims=True)

    @pl.when((flag & LIVE) == 0)
    def _():
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def ssd_update(arena, layer, slot, flags, xdt, decay, b_mat, c_mat, *, interpret: Optional[bool] = None):
    """``arena`` [L, slots, H, P, N] float32; ``layer`` an index (traced in a
    scanned trunk); ``slot``, ``flags`` [B] int32 (``LIVE``, ``FRESH``);
    ``xdt`` [B, H, P] = ``dt x``; ``decay`` [B, H] = ``exp(dt A)``; ``b_mat``,
    ``c_mat`` [B, N]; all float32.  Returns (``y`` [B, H, P] = ``S C`` of the
    new states, zeros for a row without ``LIVE``; the arena, the same
    buffer)."""
    if interpret is None:
        interpret = not traced_for_tpu()
    return _ssd_update(arena, jnp.asarray(layer, jnp.int32), slot, flags, xdt, decay, b_mat, c_mat, block_heads(xdt.shape[1]),
                       bool(interpret))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _ssd_update(arena, layer, slot, flags, xdt, decay, b_mat, c_mat, hb, interpret):
    """A jitted function of its own: a trunk that calls it a layer of an
    unrolled period traces and lowers the kernel (a body unrolled over the
    block's heads) once a program, not once a call."""
    f32 = jnp.float32
    b, h, p = xdt.shape
    n = arena.shape[-1]
    nblk = h // hb
    # channel-major tiles a head block: [B, blocks, P, heads of the block]
    tiles = lambda t: jnp.swapaxes(t.astype(f32).reshape(b, nblk, hb, p), 2, 3)  # noqa: E731
    decay_t = tiles(jnp.broadcast_to(decay[:, :, None], (b, h, p)))
    bc = jnp.pad(jnp.stack([b_mat, c_mat], axis=1).astype(f32), ((0, 0), (0, 6), (0, 0)))   # a whole tile of sublanes

    def state_block(r, g, slot_ref, flag_ref, layer_ref):
        live = flag_ref[r] & LIVE
        return layer_ref[0], slot_ref[r] * live, g * live, 0, 0

    tile = pl.BlockSpec((1, 1, p, hb), lambda r, g, *_: (r, g, 0, 0))
    arena, y_t = pl.pallas_call(
        functools.partial(_ssd_update_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nblk),
            in_specs=[pl.BlockSpec((None, None, hb, p, n), state_block), tile, tile,
                      pl.BlockSpec((1, 8, n), lambda r, g, *_: (r, 0, 0))],
            out_specs=[pl.BlockSpec((None, None, hb, p, n), state_block), tile],
        ),
        out_shape=[jax.ShapeDtypeStruct(arena.shape, arena.dtype), jax.ShapeDtypeStruct((b, nblk, p, hb), f32)],
        input_output_aliases={3: 0},       # the arena, after the three prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ds_ssd_update",
    )(slot.astype(jnp.int32), flags.astype(jnp.int32), layer.reshape(1), arena,
      tiles(xdt), decay_t, bc)
    return jnp.swapaxes(y_t, 2, 3).reshape(b, h, p), arena
