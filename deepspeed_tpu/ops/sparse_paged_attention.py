"""Paged attention over a chosen subset of a sequence's blocks (InfLLM-V2's
block-selected sparse attention through the pages).

``ops/paged_attention.py`` walks one contiguous range of a row's block table.
Here a query sees the key rows of a *list* of blocks alone, chosen a query
and key head by the selection over compressed keys
(``models/minicpm_sala.select_blocks``).  Two forms:

* ``sparse_paged_decode``, kernel **ds_sparse_paged_attention**: rows of one
  token.  A row and key head's list of page indices (the pages of its chosen
  blocks in order, the last one the page that holds the row's own token) and
  its length ride in scalar-prefetch SMEM in place of the block table: 512
  entries a row and key head at the published sizes, where the table has some
  thousand columns.  Grid ``(rows, key heads)``; a program walks its list
  ``pages a step`` at a time, copying whole pages ``[page, 2, n_kv, D]`` out of
  the arena in HBM into a double-buffered scratch (``paged_attention``'s copy:
  a page is one DMA), takes its own head's keys and values out of them (the
  strided load of ``paged_attention._head_rows``), and carries the
  online-softmax state of the head's ``rep`` queries.  Every listed page but
  the last is wholly visible; of the last the rows up to the token's own.
  **A page holds every key head and the lists differ by head, so a program
  moves its pages whole and uses half of each (at two key heads): up to twice
  the bytes the selection names.**  The other design, one walk over the union
  of the heads' lists under a mask a head, moves fewer bytes where the lists
  overlap and multiplies masked blocks instead; a layout with the key head
  outside the page would move what is used and no more (PERF.md "Left by PR
  49").
* ``sparse_paged_blocked``, ``jax.numpy`` under ``jax.named_scope("ds_sparse_prefill")``:
  rows of any width (a prefill group's chunks).  Key blocks of ``block_keys``
  rows in a loop that ends with the longest row, the block mask a query and
  key head applied, online softmax; never a gather of a whole history.  A
  tile's queries choose differently, so the loop reads every block some query
  sees: under random weights nearly every one, and prefill gets no cheaper by
  the selection here.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import traced_for_tpu
from .paged_attention import DEFAULT_MASK_VALUE, _copies_pages, _head_rows, _padded_heads

#: key rows one step of the list walk takes (``paged_attention._BLOCK_KEYS``)
_STEP_KEYS = 512


def block_lists(blocks, n_list):
    """The block mask of one-token rows ``blocks`` [B, G, nb] as lists: (the
    indices of the blocks seen in ascending order [B, G, n_list], entries past
    a list's length ``nb``; how many each list holds [B, G])."""
    nb = blocks.shape[-1]
    order = jnp.sort(jnp.where(blocks, jnp.arange(nb, dtype=jnp.int32), nb), axis=-1)[..., :n_list]
    if order.shape[-1] < n_list:
        order = jnp.pad(order, ((0, 0), (0, 0), (0, n_list - order.shape[-1])), constant_values=nb)
    return order, jnp.minimum(jnp.sum(blocks, axis=-1), n_list).astype(jnp.int32)


def page_lists(order, count, table, pos, live, page_size, block_size):
    """Lists of blocks as lists of pages: (page indices into the arena [B, G,
    n_list * m], ``m = block_size / page_size``; pages each row's lists hold
    [B]: the last listed block is the row's own (``pos // block_size``) and
    ends with the page that holds ``pos``; 0 for a row without ``live``)."""
    m = block_size // page_size
    b, g, n_list = order.shape
    # a block's pages lie side by side in the table: one fetch of ``m`` entries a listed block
    blocks = -(-table.shape[1] // m)
    by_block = jnp.pad(table, ((0, 0), (0, blocks * m - table.shape[1]))).reshape(b, blocks, m)
    at = jnp.minimum(order.reshape(b, g * n_list), blocks - 1)
    pages = jnp.take_along_axis(by_block, at[:, :, None], axis=1).reshape(b, g, n_list * m)
    n_pages = (count[:, 0] - 1) * m + (pos // page_size) % m + 1
    return pages, jnp.where(live, n_pages, 0).astype(jnp.int32)


def sparse_paged_decode_reference(q, pages, layer, page_list, n_pages, pos, page_size, scale=None):
    """The kernel's arithmetic in ``jax.numpy``: ``q`` [B, H, D] against the
    listed pages of layer ``layer`` of the arena ``pages`` [L, P, page, 2, G,
    D] -> [B, H, D]; zeros for a row whose list is empty."""
    b, h, d = q.shape
    g, n = page_list.shape[1:]
    scale = d**-0.5 if scale is None else scale
    rows = pages[layer][page_list]                                                # [B, G, n, page, 2, G, D]
    rows = jnp.take_along_axis(rows, jnp.arange(g).reshape(1, g, 1, 1, 1, 1, 1), axis=5)[:, :, :, :, :, 0]
    k, v = rows[..., 0, :].reshape(b, g, n * page_size, d), rows[..., 1, :].reshape(b, g, n * page_size, d)
    s = jnp.einsum("bgrd,bgkd->bgrk", q.reshape(b, g, h // g, d).astype(jnp.float32), k.astype(jnp.float32)) * scale
    entry, row = jnp.arange(n * page_size) // page_size, jnp.arange(n * page_size) % page_size
    seen = (entry[None, :] < n_pages[:, None] - 1) | \
        ((entry[None, :] == n_pages[:, None] - 1) & (row[None, :] <= (pos % page_size)[:, None]))
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, -1e30), axis=-1)
    o = jnp.einsum("bgrk,bgkd->bgrd", p, v.astype(jnp.float32)).reshape(b, h, d)
    return jnp.where((n_pages > 0)[:, None, None], o, 0.0).astype(q.dtype)


def _sparse_decode_kernel(list_ref, np_ref, last_ref, ly_ref, q_ref, arena_ref, o_ref, buf, sem, *, page_size, ppb,
                          n_list, scale):
    """Program ``(b, g)``: key head ``g``'s list of row ``b``, entries ``g n_list ..`` of the row's
    lists laid end to end (two axes pad less in SMEM than three).  ``buf``: [2,
    pages a step, page, 2, n_kv (padded), D] and its two DMA semaphores."""
    b, g = pl.program_id(0), pl.program_id(1)
    rep, d = q_ref.shape[2:]
    n_pad = buf.shape[4]
    n_kv = arena_ref.shape[-2]
    keys = ppb * page_size
    n_pages = np_ref[b]
    n_steps = (n_pages + ppb - 1) // ppb
    last_rows = last_ref[b]                       # rows of the list's last page the token sees

    def page_copy(step, i, slot):
        # past the list's end a step repeats its last page; the mask hides it
        page = list_ref[b, g * n_list + jnp.minimum(step * ppb + i, jnp.maximum(n_pages - 1, 0))]
        dst = buf.at[slot, i] if n_pad == n_kv else buf.at[slot, i, :, :, pl.ds(0, n_kv), :]
        return pltpu.make_async_copy(arena_ref.at[ly_ref[0], page], dst, sem.at[slot])

    def fetch(step, slot):
        for i in range(ppb):
            page_copy(step, i, slot).start()

    @pl.when(n_steps > 0)
    def _first():
        fetch(0, 0)

    key = jax.lax.broadcasted_iota(jnp.int32, (rep, keys), 1)

    def walk(j, carry):
        m_prev, l_prev, acc = carry
        slot = j % 2

        @pl.when(j + 1 < n_steps)
        def _next():
            fetch(j + 1, 1 - slot)

        for i in range(ppb):
            page_copy(j, i, slot).wait()
        k = _head_rows(buf.at[slot], 0, g)                                   # [keys, D]
        v = _head_rows(buf.at[slot], 1, g)
        s = jax.lax.dot_general(q_ref[0, 0], k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale   # [rep, keys]
        # rows of the list the token sees: every page before the last whole, the last up to its own row
        s = jnp.where(j * keys + key < (n_pages - 1) * page_size + last_rows, s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc = acc * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                                preferred_element_type=jnp.float32)
        return m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), acc

    init = (jnp.full((rep, 1), -jnp.inf, jnp.float32), jnp.zeros((rep, 1), jnp.float32),
            jnp.zeros((rep, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_steps, walk, init)
    o_ref[0, 0] = jnp.where(n_pages > 0, acc / jnp.maximum(l, 1e-30), 0.0).astype(o_ref.dtype)


def sparse_paged_decode(q, pages, layer, page_list, n_pages, pos, page_size, *, scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """One-token rows over their lists: ``q`` [B, H, D]; ``pages`` the whole
    arena [L, P, page, 2, G, D] (the token's keys and values already written);
    ``layer`` an index into it (traced in a scanned trunk); ``page_list`` [B,
    G, n] int32 page indices, of which row ``b`` walks the first ``n_pages[b]``
    a key head, the last of them the page that holds position ``pos[b]``
    (``page_lists``).  Returns [B, H, D]; zeros for a row whose list is empty."""
    if interpret is None:
        interpret = not traced_for_tpu()
    n_kv, d = pages.shape[-2:]
    if not interpret and not _copies_pages(n_kv, d, pages.dtype.itemsize):   # the chip's compiler refuses the DMA
        raise NotImplementedError(f"ds_sparse_paged_attention copies its pages itself: heads of 128 lanes in whole "
                                  f"tiles, not {n_kv} key heads of {d} in {pages.dtype}")
    return _sparse_decode(q, pages, jnp.asarray(layer, jnp.int32), page_list, n_pages, pos, page_size=page_size,
                          scale=None if scale is None else float(scale), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("page_size", "scale", "interpret"))
def _sparse_decode(q, pages, layer, page_list, n_pages, pos, *, page_size, scale, interpret):
    b, h, d = q.shape
    n_kv = pages.shape[-2]
    rep = h // n_kv
    n_list = page_list.shape[-1]
    ppb = max(1, min(_STEP_KEYS // page_size, n_list))
    n_pad = _padded_heads(n_kv, pages.dtype.itemsize)
    head = pl.BlockSpec((1, 1, rep, d), lambda b, g, *_: (b, g, 0, 0))
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, page_size=page_size, ppb=ppb, n_list=n_list,
                          scale=d**-0.5 if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, n_kv),
            in_specs=[head, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=head,
            scratch_shapes=[pltpu.VMEM((2, ppb, page_size, 2, n_pad, d), pages.dtype),
                            pltpu.SemaphoreType.DMA((2, ))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ds_sparse_paged_attention",
    )(page_list.astype(jnp.int32).reshape(b, n_kv * n_list), n_pages.astype(jnp.int32),
      (pos % page_size + 1).astype(jnp.int32), layer.reshape(1), q.reshape(b, n_kv, rep, d), pages)
    return out.reshape(b, h, d)


def _gather_kernel(ids_ref, ly_ref, arena_ref, o_ref, sem, *, n):
    b = pl.program_id(0)
    copies = [pltpu.make_async_copy(arena_ref.at[ly_ref[0], ids_ref[b, i]], o_ref.at[0, i], sem.at[0])
              for i in range(n)]
    for copy in copies:
        copy.start()
    for copy in copies:
        copy.wait()


def gather_pages(pages, layer, ids, *, interpret: Optional[bool] = None):
    """Pages ``ids`` [B, n] of layer ``layer`` of the arena ``pages`` [L, P,
    page, 2, G, D] -> [B, n, page, 2, G, D], a DMA a page out of the arena
    where it lies (kernel ``ds_gather_pages``); off the chip the plain gather."""
    if interpret is None:
        interpret = not traced_for_tpu()
    if interpret:
        return pages[layer, ids]
    return _gather_pages(pages, jnp.asarray(layer, jnp.int32), ids.astype(jnp.int32), False)


@functools.partial(jax.jit, static_argnums=3)
def _gather_pages(pages, layer, ids, interpret):
    b, n = ids.shape
    block = (1, n) + pages.shape[2:]
    return pl.pallas_call(
        functools.partial(_gather_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, ),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(block, lambda b, *_: (b, ) + (0, ) * (len(block) - 1)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1, ))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n) + pages.shape[2:], pages.dtype),
        interpret=interpret,
        name="ds_gather_pages",
    )(ids, layer.reshape(1), pages)


def sparse_paged_blocked(q, pages, layer, table, start_pos, chunk_lens, blocks, page_size, block_size, *,
                         scale: Optional[float] = None, block_keys: int = _STEP_KEYS):
    """Rows of any width under their block masks: ``q`` [B, C, H, D]; ``pages``
    the whole arena (the chunk's keys and values already written); ``table``
    [B, W]; ``blocks`` bool [B, C, G, nb] (``select_blocks``: a block a query
    does not see, or that lies past its position, is False).  Returns [B, C,
    H, D]; zeros for the positions at and past a row's ``chunk_lens``.

    A step's pages come through ``gather_pages``, not a ``jax.numpy`` gather:
    inside the loop the compiler is free to choose the arena's layout, chose
    the one the scores' product likes, and copied the whole arena into it
    before the loop, 4.4 GB a sparse layer and step at the cell's size (the
    offline compile for the chip showed it); a kernel's operand keeps the
    layout the arena has."""
    with jax.named_scope("ds_sparse_prefill"):
        f32 = jnp.float32
        b, c, h, d = q.shape
        g = pages.shape[-2]
        rep = h // g
        scale = d**-0.5 if scale is None else scale
        ppb = max(block_keys // page_size, 1)
        keys = ppb * page_size
        per = keys // block_size                                   # selection blocks a step holds
        width = table.shape[1]
        nb = -(-blocks.shape[-1] // per) * per
        blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, 0), (0, nb - blocks.shape[-1])))
        qpos = start_pos[:, None] + jnp.arange(c)[None, :]                                      # [B, C]
        live = jnp.arange(c)[None, :] < chunk_lens[:, None]
        qg = q.reshape(b, c, g, rep, d)
        n_steps = jnp.max(jnp.where(chunk_lens > 0, (start_pos + chunk_lens - 1) // keys + 1, 0))

        def step(j, carry):
            m_prev, l_prev, acc = carry
            column = jnp.minimum(j * ppb + jnp.arange(ppb), width - 1)
            rows = gather_pages(pages, layer, table[:, column])                          # [B, ppb, page, 2, G, D]
            k = rows[:, :, :, 0].reshape(b, keys, g, d)
            v = rows[:, :, :, 1].reshape(b, keys, g, d)
            s = jnp.einsum("bcgrd,bkgd->bcgrk", qg, k, preferred_element_type=f32) * scale
            seen = jnp.repeat(jax.lax.dynamic_slice_in_dim(blocks, j * per, per, axis=3), block_size, axis=3)
            seen = seen & ((j * keys + jnp.arange(keys))[None, None, None, :] <= qpos[:, :, None, None])
            s = jnp.where(seen[:, :, :, None, :], s, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(seen[:, :, :, None, :], jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            acc = acc * alpha + jnp.einsum("bcgrk,bkgd->bcgrd", p.astype(v.dtype), v, preferred_element_type=f32)
            return m_new, alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), acc

        init = (jnp.full((b, c, g, rep, 1), -jnp.inf, f32), jnp.zeros((b, c, g, rep, 1), f32),
                jnp.zeros((b, c, g, rep, d), f32))
        _, l, acc = jax.lax.fori_loop(0, n_steps, step, init)
        out = (acc / jnp.maximum(l, 1e-30)).reshape(b, c, h, d)
        return jnp.where(live[:, :, None, None], out, 0.0).astype(q.dtype)
