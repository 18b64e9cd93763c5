"""Pallas TPU latent attention through pages: the *absorbed* form of
multi-head latent attention (``models/xing4.py``).

A token's cache in a layer is one row ``[c_kv | k_pe]`` that every head
shares (``kv_lora_rank + qk_rope_head_dim`` numbers, 512 + 64 at the
published sizes), padded with zeros to whole lanes (640): the arena is ``[L,
P, page, W]``, a page ``[page, W]`` whole tiles and one DMA.  The same row is
key and value: with the query carried into the latent space (``q_lat =
q_nope W_UK^T`` a head) a head's score is ``[q_lat | q_pe | 0] . row`` and its
output ``softmax(s) row[:kv_lora_rank]``, which ``W_UV`` takes to the head's
values afterwards.  So the kernel is one key head under ``H`` query heads
whose keys are ``W`` wide and whose values are the keys' first ``d_v`` lanes,
read once for both.

The walk is ``ops/paged_attention.py``'s, where the kernel copies the pages
itself: a row's queries are laid out position-major (row = position x H +
head) and cut into **query blocks** of up to ``_Q_BLOCK_POS`` positions (one
for every chunk the engine runs at 128; a wider chunk's blocks each walk
their own history, so the softmax state stays a block's); a block walks its
history in blocks of ``walk_block`` pages (512 key rows) up to its last
visible key through a double-buffered scratch, and per key block and tile of
``_TILE_ROWS`` query rows does one ``[tile, W] x [W, keys]`` and one ``[tile,
keys] x [keys, d_v]``, bfloat16 operands into the MXU, float32 scores,
online-softmax state and accumulator in VMEM.  A row with no token walks
nothing and writes zeros; a decode row is one tile of ``H`` query rows.

The device event is named ``ds_mla_absorbed``: the name carries the form, so
a reader of a trace counts this form's work against this form's minimum
(``benchmark/roofline_mla.py``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import get_trace_mesh, traced_for_tpu
from .paged_attention import DEFAULT_MASK_VALUE

_LANES = 128
#: key rows one step of the walk takes, the table allowing
_BLOCK_KEYS = 512
#: query rows a tile holds at most
_TILE_ROWS = 256
#: chunk positions a query block holds at most
_Q_BLOCK_POS = 128


def latent_lanes(latent_dim: int) -> int:
    """Lanes a cached row takes: ``latent_dim`` up to whole lanes."""
    return -(-latent_dim // _LANES) * _LANES


def walk_block(page_size: int, table_width: int) -> int:
    """Pages the kernel's walk takes at one step: 512 key rows, never more than the table holds."""
    return max(1, min(-(-_BLOCK_KEYS // page_size), table_width))


def write_latent(pages, rows, block_table, start_pos, page_size, chunk_lens=None, layer=None):
    """Scatter a chunk's latent rows into the pages.  pages: [P, page, W] or
    with ``layer`` the whole arena [L, P, page, W], updated in place where it
    is a loop's carry (``models/llama_cache._write_pages``); rows: [B, C, W];
    slots at and past a row's ``chunk_lens`` go to the null page 0 as zeros."""
    b, c = rows.shape[:2]
    positions = start_pos[:, None] + jnp.arange(c)[None, :]
    page_slot = jnp.minimum(positions // page_size, block_table.shape[1] - 1)
    page_idx = jnp.take_along_axis(block_table, page_slot, axis=1)
    if chunk_lens is not None:
        valid = jnp.arange(c)[None, :] < chunk_lens[:, None]
        page_idx = jnp.where(valid, page_idx, 0)
        rows = jnp.where(valid[:, :, None], rows, 0)
    where = (page_idx.reshape(-1), (positions % page_size).reshape(-1))
    return pages.at[where if layer is None else (layer, ) + where].set(rows.reshape((b * c, ) + rows.shape[2:]))


def mla_absorbed_reference(q, pages, block_table, start_pos, chunk_lens, page_size, *, d_v, scale):
    """The kernel's contract in jnp.  q: [B, C, H, W] (``[q_lat | q_pe | 0]``);
    pages: one layer's [P, page, W] with the chunk's rows written;
    block_table: [B, max_pages]; start_pos, chunk_lens: [B].  Returns [B, C,
    H, d_v] in q's dtype; query rows at and past ``chunk_lens`` are zero."""
    b, c, _, w = q.shape
    keys = pages[block_table.reshape(-1)].reshape(b, -1, w).astype(jnp.float32)      # [B, S_kv, W]
    scores = jnp.einsum("bchw,bkw->bhck", q.astype(jnp.float32), keys,
                        precision=jax.lax.Precision.HIGHEST) * jnp.float32(scale)
    qpos = start_pos[:, None] + jnp.arange(c)[None, :]
    seen = jnp.arange(keys.shape[1])[None, None, :] <= qpos[..., None]               # [B, C, S_kv]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhck,bkd->bchd", probs, keys[..., :d_v], precision=jax.lax.Precision.HIGHEST)
    if chunk_lens is not None:
        out = jnp.where((jnp.arange(c)[None, :] < chunk_lens[:, None])[..., None, None], out, 0)
    return out.astype(q.dtype)


def _mla_kernel(bt_ref, sp_ref, cl_ref, ly_ref, q_ref, arena_ref, o_ref, buf, sem, m_ref, l_ref, acc_ref, *,
                page_size, ppb, heads, tile, qpos, scale, d_v):
    """Grid step (b, g): query block ``g`` of row ``b`` walks its history."""
    b, g = pl.program_id(0), pl.program_id(1)
    qrows = q_ref.shape[1]
    block = ppb * page_size
    n_tiles = qrows // tile
    start = sp_ref[b] + g * qpos                      # context before this query block
    n_tok = jnp.clip(cl_ref[b] - g * qpos, 0, qpos)   # positions of it that carry a token
    last_page = jnp.clip((start + n_tok - 1) // page_size, 0, bt_ref.shape[1] - 1)
    n_blocks = jnp.where(n_tok > 0, last_page // ppb + 1, 0)
    live_tiles = (n_tok * heads + tile - 1) // tile

    def rows_of(t):
        return slice(None) if n_tiles == 1 else pl.ds(pl.multiple_of(t * tile, tile), tile)

    def init(t, _):
        r = rows_of(t)
        m_ref[r] = jnp.full((tile, 1), -jnp.inf, jnp.float32)
        l_ref[r] = jnp.zeros((tile, 1), jnp.float32)
        acc_ref[r] = jnp.zeros((tile, d_v), jnp.float32)

    jax.lax.fori_loop(0, live_tiles, init, None)

    def page_copy(blk, i, slot):
        # past the row's last page the block repeats it; the mask hides it
        page = bt_ref[b, jnp.minimum(blk * ppb + i, last_page)]
        return pltpu.make_async_copy(arena_ref.at[ly_ref[0], page], buf.at[slot, pl.ds(i * page_size, page_size)],
                                     sem.at[slot])

    def fetch(blk, slot):
        for i in range(ppb):
            page_copy(blk, i, slot).start()

    @pl.when(n_blocks > 0)
    def _first():
        fetch(0, 0)

    key = jax.lax.broadcasted_iota(jnp.int32, (tile, block), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)

    def walk(j, _):
        slot = j % 2

        @pl.when(j + 1 < n_blocks)
        def _next():
            fetch(j + 1, 1 - slot)

        for i in range(ppb):
            page_copy(j, i, slot).wait()

        def q_tile(t, _):
            r = rows_of(t)
            k = buf[slot]                                                  # [block, W]: keys, and values in its first d_v lanes
            s = jax.lax.dot_general(q_ref[0, r, :], k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale      # [tile, block]
            # row i of the position-major block is position i // heads; the last key it may see, from this block's first
            sees = start - j * block + (t * tile + row) // heads
            s = jnp.where(key <= sees, s, DEFAULT_MASK_VALUE)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot_general(
                p.astype(k.dtype), k[:, :d_v], (((1, ), (0, )), ((), ())), preferred_element_type=jnp.float32)
            m_ref[r] = m_new

        jax.lax.fori_loop(0, live_tiles, q_tile, None)

    jax.lax.fori_loop(0, n_blocks, walk, None)

    def finish(t, _):
        r = rows_of(t)

        @pl.when(t < live_tiles)
        def _live():
            carries = (t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, d_v), 0)) // heads < n_tok
            out = acc_ref[r] / jnp.maximum(l_ref[r], 1e-30)
            o_ref[0, r, :] = jnp.where(carries, out, 0).astype(o_ref.dtype)

        @pl.when(t >= live_tiles)
        def _dead():
            o_ref[0, r, :] = jnp.zeros((tile, d_v), o_ref.dtype)

    jax.lax.fori_loop(0, n_tiles, finish, None)


def mla_absorbed_pallas(q, pages, block_table, start_pos, chunk_lens, page_size, *, d_v: int, scale: float,
                        layer=None, interpret: Optional[bool] = None):
    """Drop-in twin of ``mla_absorbed_reference``.  With ``layer`` (an index,
    traced in a scanned trunk) ``pages`` is the whole arena [L, P, page, W]
    and the kernel reads that layer's pages where they lie.  One device: no
    head-sharded call is built, and the engine refuses tensor-parallel serving
    of latent pages before it gets here (``engine_v2._serving_shardings``)."""
    tm = get_trace_mesh()
    if tm is not None and tm.size > 1:
        raise NotImplementedError("ds_mla_absorbed under a mesh of several devices: no head-sharded call is built")
    if interpret is None:
        interpret = not traced_for_tpu()
    return _mla_call(q, pages, block_table, start_pos, chunk_lens, layer, page_size=page_size, d_v=int(d_v),
                     scale=float(scale), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "d_v", "scale", "interpret"))
def _mla_call(q, pages, block_table, start_pos, chunk_lens, layer, *, page_size, d_v, scale, interpret):
    """The kernel's call at one set of shapes (jitted for its trace's sake:
    ``paged_attention._paged_call``)."""
    b, c, h, w = q.shape
    if layer is None:
        pages, layer = pages[None], 0
    if chunk_lens is None:
        chunk_lens = jnp.full((b, ), c, jnp.int32)
    ppb = walk_block(page_size, block_table.shape[1])
    qpos = min(c, _Q_BLOCK_POS)
    n_qb = -(-c // qpos)
    rows = qpos * h
    tile = min(rows, _TILE_ROWS)
    qrows = -(-rows // tile) * tile

    # position-major query blocks: [B, n_qb, qpos * H (padded), W], row = position * H + head
    qg = jnp.pad(q, ((0, 0), (0, n_qb * qpos - c), (0, 0), (0, 0))).reshape(b, n_qb, rows, w)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, qrows - rows), (0, 0))).reshape(b, n_qb * qrows, w)

    itemsize = pages.dtype.itemsize
    vmem = (2 * qrows * w * q.dtype.itemsize + 2 * qrows * d_v * q.dtype.itemsize + qrows * (d_v + 2 * _LANES) * 4 +
            2 * ppb * page_size * w * itemsize)
    kernel = functools.partial(_mla_kernel, page_size=page_size, ppb=ppb, heads=h, tile=tile, qpos=qpos, scale=scale,
                               d_v=d_v)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, n_qb),
            in_specs=[pl.BlockSpec((1, qrows, w), lambda b, g, *_: (b, g, 0)), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, qrows, d_v), lambda b, g, *_: (b, g, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb * page_size, w), pages.dtype), pltpu.SemaphoreType.DMA((2, )),
                            pltpu.VMEM((qrows, 1), jnp.float32), pltpu.VMEM((qrows, 1), jnp.float32),
                            pltpu.VMEM((qrows, d_v), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_qb * qrows, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the query and output blocks twice (the pipeline's two buffers), the
            # key blocks' two slots, the softmax state, room for the body's temporaries
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        name="ds_mla_absorbed",
    )(block_table, start_pos.astype(jnp.int32), chunk_lens.astype(jnp.int32),
      jnp.reshape(layer, (1, )).astype(jnp.int32), qg, pages)
    out = out.reshape(b, n_qb, qrows, d_v)[:, :, :rows].reshape(b, n_qb * qpos, h, d_v)
    return out[:, :c]
