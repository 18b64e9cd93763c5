"""Pallas TPU paged (blocked-KV) attention.

TPU-native equivalent of the reference FastGen's blocked-flash/linear-KV
attention kernels (ref: deepspeed/inference/v2/kernels/ragged_ops —
``blocked_flash``, ``linear_blocked_kv_rotary``; KV geometry from
``inference/v2/ragged/kv_cache.py``).  The kernel attends a chunk of queries
per sequence against that sequence's paged KV history, gathering pages from
the shared arena through the block table.

The kernel has two forms of one algorithm (the same online softmax, bfloat16
operands into the MXU, float32 scores, state and accumulator; the same masks,
window and ``scale``; the arena's layout untouched, a page, ``[page, 2, n_kv,
D]``, still one DMA, the layer's index traced), chosen from the call's shape
alone (``takes_decode_form``): the **general form**, a grid step a row, for
rows that carry a chunk of query positions, and the **decode form**, the
call's rows one stream, for rows of one position each over pages the kernel
copies itself (every cell's decode group and fused rounds).  Both do what the
step carries, not what the table could hold.

The general form:
  * a row walks its own history in *blocks* of ``walk_block`` pages, up to
    the row's last visible key, ``start + chunk_len - 1``: a row with no
    token does nothing and writes zeros, and a decode row riding in a chunk
    program walks its own context once.  The block table, start positions,
    chunk lengths and the layer's index ride in scalar-prefetch SMEM.  How a
    block's pages reach VMEM follows from the page's shape
    (``_copies_pages``), because the chip's compiler allows a kernel's own
    DMA only out of whole tiles:
      - a page of whole tiles with heads of 128 lanes (every cell's): the
        arena stays in HBM (``pl.ANY``), grid = (batch, 1), and the kernel
        copies a block's pages (512 key rows where the table and a VMEM
        budget allow, 128 at least) into a double-buffered scratch, a loop
        of copies started and a loop of them awaited, a granule's pages a
        turn (``_start_copies``); the walk
        is a ``fori_loop`` that ends with the row, so the table's width costs
        nothing.  One head's keys (or values) are every ``2 * n_kv``-th row
        of the block's ``[keys * 2 * n_kv, D]`` view: a strided load, on
        32-bit words for bfloat16 (two heads share a sublane; a bfloat16 is
        the upper half of a float32), and the heads are a ``fori_loop``.
        Where a key's rows would put every sublane of such a load into one
        VMEM bank (a stride of an even number of 8-row tiles: 16, 32, 64 key
        heads) the scratch pads the heads to an odd number of tiles; the DMA
        writes the real ones.
      - a page the tiling pads (heads narrower than 128 lanes; 1, 3, 6, 12
        ... key heads) or heads wider than 128 lanes: the pipeline brings the
        block, one ``BlockSpec`` a page (a whole page is tile-legal in every
        layout), grid = (batch, blocks of 128 key rows the table holds); a
        step past the row's last block is skipped and its pages, clamped to
        the row's last one, are not fetched again.  A head's rows are a plain
        load a page, its index static: the heads are unrolled.
    Either way a block's tail past the row's last page repeats that page and
    is left to the position mask.
  * queries are laid out position-major ([B, n_kv, C*rep, D], row =
    c * rep + r), so the rows that carry a token are the first
    ``chunk_len * rep``; they are taken in tiles and a tile wholly past them
    is neither multiplied nor read: its output is zero.
  * per head, tile and block one ``[tile, D] x [D, keys]`` and one
    ``[tile, keys] x [keys, D]`` (``_attend``), the softmax state in VMEM
    scratch.

The decode form (``_decode_kernel``; PERF.md section 6, PR 55): a decode step
hands the kernel many short rows (160 in ``phi4flash_reason``: 32 slots x 5
groups of key pairs, a third of them dead), and a grid step a row pays each
row's first fetch with nothing to overlap it, whole blocks of 512 key rows
where a window layer's query sees 512 that straddle two, and a row's fixed
costs whether its slot lives or not.  Here all the call's rows are one grid
step (a group of them where their queries would pass ``_DECODE_ROWS_BYTES``
of VMEM), their queries and outputs ``[rows, n_kv, rep, D]`` in VMEM, and:
  * the rows that carry a token are listed from ``chunk_lens`` in SMEM, and
    only they are walked; the others are written as zeros and cost no fetch,
    no state and no walk;
  * the listed rows' blocks are one stream through the scratch's two slots:
    while a block is multiplied the stream's next is in flight, the row's
    next or the next listed row's first, so only the stream's first fetch is
    exposed;
  * a row's walk goes from the granule (128 key rows, ``walk_block(chunk=
    1)``) of its first visible key to that of its last, and only those
    granules are copied: a window of 512 costs 512 to 640 walked rows, not
    1,024.  How many granules a block takes is a value, not a shape: the
    copies started and the copies awaited are loops over them (a granule's
    pages unrolled, one wait for a granule's bytes);
  * the multiply takes a whole block of the scratch, ``_DECODE_BLOCK_KEYS``
    key rows where the table and the budget allow, whatever was copied into
    it: one ``[rep, D] x [D, keys]`` and one ``[rep, keys] x [keys, D]`` a
    head through the same ``_attend``.  A head's multiply costs a few hundred
    cycles whatever its width (the strided loads, two passes through the MXU
    and the softmax between them are one chain), so a row of one position
    wants few, long blocks; the granules not copied hold an earlier block's
    keys (zeros when the call starts), finite and past the query's last
    visible key, and the mask gives them no weight.
The body holds no loop of Python's over rows, blocks, heads or sizes (a
granule's pages alone are unrolled, in both forms): what a program pays to
trace and lower an instance (before the compile cache's key, so at every
start) is less than the parent's general form cost
(``tests/unit/ops/test_paged_attention_aot.py`` holds the lowered text's
length).  The engine's count of walked rows follows the form through
``walk_block`` (``inference/v2/engine_v2._walk_rows``).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..comm.mesh import TENSOR_AXIS, get_trace_mesh, in_manual_mesh, traced_for_tpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

#: key rows one step of the walk takes where the kernel copies the pages
#: itself, the table and the budget allowing (on the chip a step's fixed
#: costs, the softmax state read and written and the MXU filled and drained,
#: are half the time at 128 and an eighth at 512)
_BLOCK_KEYS = 512
#: ... and at least, and where the pipeline brings them: the MXU's width
_MIN_BLOCK_KEYS = 128
#: key rows a block of the decode form holds, the table and the budget
#: allowing: a head's multiply of a block costs a v5e some 340 cycles
#: whatever the block holds (a chain of strided loads, two passes through
#: the MXU and a softmax between them), so for a row of one query position
#: fewer and longer blocks win: at 1,024 a call is 28 to 44% shorter than at
#: 512 at every cell's decode shape (PERF.md section 6, PR 55)
_DECODE_BLOCK_KEYS = 1024
#: VMEM the two slots of the block's scratch may take (EvaByte's 32 heads,
#: padded to 40, in blocks of 512 rows: all of it)
_BLOCK_BYTES = 20 << 20
#: query rows a tile holds at most
_TILE_ROWS = 128
#: VMEM one of the decode form's query (or output) blocks may take: the rows
#: of one grid step
_DECODE_ROWS_BYTES = 4 << 20


def _copies_pages(n_kv: int, d: int, itemsize: int) -> bool:
    """Whether the kernel copies a block's pages out of the arena itself.  The
    chip's compiler allows a DMA only out of whole tiles, and tiles a page's
    ``[n_kv, D]`` rows by 128 lanes and by 8 sublanes, or by the power of two
    that holds fewer heads, or by one 32-bit word's worth of them (2 for
    bfloat16) at least; and a strided load takes rows of 128 lanes only."""
    tile_rows = min(8, max(4 // itemsize, 1 << (n_kv - 1).bit_length()))
    return d == 128 and n_kv % tile_rows == 0


def takes_decode_form(chunk: int, n_kv: int, d: int, itemsize: int) -> bool:
    """Whether a call whose rows are ``chunk`` query positions wide takes the
    kernel's decode form: one position a row, over pages the kernel copies."""
    return chunk == 1 and _copies_pages(n_kv, d, itemsize)


def _padded_heads(n_kv: int, itemsize: int) -> int:
    """Heads the scratch lays a key's rows out for.  A head's rows are taken
    with a sublane stride of one key's rows, ``2 * n_kv * itemsize / 4``
    32-bit sublanes; where that is an even number of 8-sublane tiles all
    eight sublanes of a load fall into one bank of VMEM (measured on a v5e:
    six cycles a load at a stride of 4 tiles, one at 1, 3 or 5).  One tile
    more makes it odd."""
    sublanes = 2 * n_kv * itemsize // 4
    return n_kv + (16 // itemsize if sublanes % 16 == 0 else 0)


def walk_block(page_size: int, table_width: int, n_kv: int, d: int, itemsize: int, chunk: int = 0) -> int:
    """Pages the kernel's walk takes at one step (a block), for a table and a
    page of these shapes: 512 key rows where it copies the pages itself,
    fewer where the two slots of its scratch would pass their budget (never
    fewer than 128 key rows); 128 key rows where the pipeline brings them;
    never more than the table holds.  With a ``chunk`` of 1 (a call whose
    rows carry one query position each) the granule of the decode form's
    walk: 128 key rows whoever brings them, so that the engine's count of
    walked rows (``engine_v2._walk_rows``) follows the form the call takes."""
    pages = -(-_MIN_BLOCK_KEYS // page_size)
    if _copies_pages(n_kv, d, itemsize) and not takes_decode_form(chunk, n_kv, d, itemsize):
        page_bytes = page_size * 2 * _padded_heads(n_kv, itemsize) * d * itemsize
        pages = max(min(-(-_BLOCK_KEYS // page_size), _BLOCK_BYTES // (2 * page_bytes)), pages)
    return max(1, min(pages, table_width))


def _decode_block(page_size: int, table_width: int, n_kv: int, d: int, itemsize: int) -> int:
    """Pages a block of the decode form's scratch holds: whole granules of
    its walk (``walk_block(chunk=1)``), ``_DECODE_BLOCK_KEYS`` key rows where
    the table and the scratch's budget allow, one granule at least."""
    granule = walk_block(page_size, table_width, n_kv, d, itemsize, chunk=1)
    page_bytes = page_size * 2 * _padded_heads(n_kv, itemsize) * d * itemsize
    pages = min(-(-_DECODE_BLOCK_KEYS // page_size), _BLOCK_BYTES // (2 * page_bytes), table_width)
    return max(pages // granule, 1) * granule


def _head_rows(block, kv, h):
    """One head's keys (``kv`` 0) or values (1) out of a block, as
    ``[pages * page, D]``.  Out of the scratch the kernel copied its pages
    into, ``[pages, page, 2, heads (padded), 128]``: every ``2 * heads``-th
    row of the block's ``[keys * 2 * heads, 128]`` view.  Out of the pages
    the pipeline brought, a list of ``[page, 2, heads, D]``: a load a page."""
    if isinstance(block, list):
        return jnp.concatenate([page[:, kv, h, :] for page in block], axis=0)
    ppb, page, _, n_pad, d = block.shape
    n_keys, e = ppb * page, kv * n_pad + h
    rows_ref = block.reshape(n_keys * 2 * n_pad, d)
    if rows_ref.dtype.itemsize == 4:
        return rows_ref[pl.ds(e, n_keys, stride=2 * n_pad), :]
    if rows_ref.dtype != jnp.bfloat16:
        raise NotImplementedError(f"paged kernel over a {rows_ref.dtype} arena")
    # two bfloat16 rows share a 32-bit sublane: load the pairs, keep the
    # half; a bfloat16 is the upper half of the float32 of the same value
    words = rows_ref.bitcast(jnp.uint32)[pl.ds(e // 2, n_keys, stride=n_pad), :]
    half = (words >> jnp.asarray(16 * (e % 2), jnp.uint32)) << 16
    return pltpu.bitcast(half, jnp.float32).astype(jnp.bfloat16)


def _last_page(start, n_tok, page_size, width):
    """The table column of a row's last visible key (0 for a row with no
    token, whose walk is empty)."""
    return jnp.clip((start + n_tok - 1) // page_size, 0, width - 1)


def _first_block(start, window, block):
    """The first block of a row's walk: with a ``window`` the one that holds
    the first key the row's first query may see, else block 0."""
    return jnp.maximum(start - window + 1, 0) // block if window else 0


def _attend(q, k, v, seen, m_prev, l_prev, acc_prev, scale):
    """One tile of queries against one block of a head's keys and values,
    into the online softmax: (m, l, acc) after it.  ``seen`` [tile, keys]:
    what each query row may see of the block."""
    # bf16 operands straight into the MXU, f32 accumulation
    s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())), preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    acc = acc_prev * alpha + jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                                 preferred_element_type=jnp.float32)
    return m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), acc


def _seen(sees, n_keys, window):
    """[rows, n_keys]: whether key ``i`` of a block is one a query row may
    see, whose last visible key, counted from the block's first, is ``sees``
    ([rows, 1] or a number); with a ``window`` its first too: a query sees
    ``window`` keys, its own among them.  A tile whose keys of this block all
    lie before it takes the finite mask value as its maximum, which the first
    real score wipes out (alpha = 0)."""
    key = jax.lax.broadcasted_iota(jnp.int32, (1, n_keys), 1)
    seen = key <= sees
    return seen & (key > sees - window) if window else seen


def _normalised(acc, l):
    """A row's output from its accumulator and its softmax sum."""
    return acc / jnp.maximum(l, 1e-30)


def _places(buf, slot, at, n_kv):
    """Places ``at`` (an index or a slice) of the scratch's ``slot`` as a
    copy's destination: the scratch may pad the heads, a DMA writes (and a
    wait counts) the real ones."""
    return buf.at[slot, at] if buf.shape[4] == n_kv else buf.at[slot, at, :, :, pl.ds(0, n_kv), :]


def _start_copies(arena_ref, ly_ref, page_of, buf, slot, n, per, sem):
    """Start the copies of the layer's pages ``page_of(i)`` into places ``i``
    = 0 .. ``n * per`` - 1 of the scratch's ``slot``: a loop of ``n`` turns,
    ``per`` copies unrolled in each (a copy a turn costs a v5e's scalar core
    some 20 cycles more, a page of 2 key heads a third of its time)."""
    n_kv = arena_ref.shape[-2]

    def turn(t, _):
        for i in range(per):
            at = t * per + i
            pltpu.make_async_copy(arena_ref.at[ly_ref[0], page_of(at)], _places(buf, slot, at, n_kv),
                                  sem.at[slot]).start()

    jax.lax.fori_loop(0, n, turn, None)


def _await_copies(buf, slot, n, per, n_kv, sem):
    """Wait for the ``n * per`` page copies ``_start_copies`` started into
    the scratch's ``slot``: a wait a turn for the bytes of its ``per`` pages
    (the semaphore counts bytes, and a wait reads its descriptor's size and
    semaphore alone)."""
    def turn(t, _):
        dst = _places(buf, slot, pl.ds(t * per, per), n_kv)
        pltpu.make_async_copy(dst, dst, sem.at[slot]).wait()

    jax.lax.fori_loop(0, n, turn, None)


def _paged_kernel(bt_ref, sp_ref, cl_ref, ly_ref, q_ref, *refs, page_size, ppb, copies, rep, tile, scale, window):
    """The general form: a grid step a row.  ``refs``: where the kernel
    ``copies`` its pages, the arena in HBM, the output, the scratch ``[2,
    pages a block, page, 2, n_kv (padded), D]`` and its two DMA semaphores
    (the row's one grid step walks all its blocks); else the block's ``ppb``
    pages as the pipeline brought them and the output (grid step ``g`` is
    block ``g``).  Then the softmax state."""
    b, g = pl.program_id(0), pl.program_id(1)
    if copies:
        arena_ref, o_ref, buf, sem, m_ref, l_ref, acc_ref = refs
    else:
        fed, (o_ref, m_ref, l_ref, acc_ref) = list(refs[:ppb]), refs[ppb:]
    _, n_kv, padded, d = q_ref.shape
    block = ppb * page_size
    n_tiles = padded // tile
    start, n_tok = sp_ref[b], cl_ref[b]
    last_page = _last_page(start, n_tok, page_size, bt_ref.shape[1])      # of the row's last visible key
    n_blocks = jnp.where(n_tok > 0, last_page // ppb + 1, 0)
    first_block = _first_block(start, window, block)
    live_tiles = (n_tok * rep + tile - 1) // tile      # tiles with a row that carries a token

    def rows_of(t):
        return slice(None) if n_tiles == 1 else pl.ds(pl.multiple_of(t * tile, tile), tile)

    @pl.when(g == 0)
    def _init():
        def init(t, _):
            r = rows_of(t)
            m_ref[:, r] = jnp.full((n_kv, tile, 1), -jnp.inf, jnp.float32)
            l_ref[:, r] = jnp.zeros((n_kv, tile, 1), jnp.float32)
            acc_ref[:, r] = jnp.zeros((n_kv, tile, d), jnp.float32)

        jax.lax.fori_loop(0, live_tiles, init, None)

    def block_step(j, pages):
        """Block ``j`` of the row, its pages in VMEM, into the softmax state."""
        row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)

        def head(h, _):
            k = _head_rows(pages, 0, h)                                  # [block, D]
            v = _head_rows(pages, 1, h)

            def q_tile(t, _):
                r = rows_of(t)
                # row i of the position-major q block is chunk position i // rep;
                # the last key it may see, counted from this block's first
                seen = _seen(start - j * block + (t * tile + row) // rep, block, window)
                m_ref[h, r], l_ref[h, r], acc_ref[h, r] = _attend(q_ref[0, h, r, :], k, v, seen, m_ref[h, r],
                                                                  l_ref[h, r], acc_ref[h, r], scale)

            jax.lax.fori_loop(0, live_tiles, q_tile, None)

        if copies:
            jax.lax.fori_loop(0, n_kv, head, None)
        else:
            # a plain load wants the head's index static
            for h in range(n_kv):
                head(h, None)

    if copies:
        per = math.gcd(ppb, -(-_MIN_BLOCK_KEYS // page_size))      # copies unrolled a turn of their loop

        def fetch(blk, slot):
            # past the row's last page the block repeats it; the mask hides it
            _start_copies(arena_ref, ly_ref, lambda i: bt_ref[b, jnp.minimum(blk * ppb + i, last_page)], buf, slot,
                          ppb // per, per, sem)

        @pl.when(n_blocks > 0)
        def _first():
            fetch(first_block, first_block % 2)

        def walk(j, _):
            slot = j % 2

            @pl.when(j + 1 < n_blocks)
            def _next():
                fetch(j + 1, 1 - slot)

            _await_copies(buf, slot, ppb // per, per, n_kv, sem)
            block_step(j, buf.at[slot])

        jax.lax.fori_loop(first_block, n_blocks, walk, None)
    else:
        @pl.when((g >= first_block) & (g < n_blocks))
        def _fed():
            block_step(g, fed)

    @pl.when(g == pl.num_programs(1) - 1)
    def _finish():
        def finish(t, _):
            r = rows_of(t)
            carries = (t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, d), 0)) // rep < n_tok

            @pl.when(t < live_tiles)
            def _live():
                def head(h, _):
                    o_ref[0, h, r, :] = jnp.where(carries, _normalised(acc_ref[h, r], l_ref[h, r]),
                                                  0).astype(o_ref.dtype)

                jax.lax.fori_loop(0, n_kv, head, None)

            @pl.when(t >= live_tiles)
            def _dead():
                o_ref[0, :, r, :] = jnp.zeros((n_kv, tile, d), o_ref.dtype)

        jax.lax.fori_loop(0, n_tiles, finish, None)


def _decode_kernel(bt_ref, sp_ref, cl_ref, ly_ref, q_ref, arena_ref, o_ref, buf, sem, live_ref, m_ref, l_ref, acc_ref,
                   *, page_size, ppb, sub, scale, window):
    """The decode form: a grid step a group of rows of one query position
    each (every cell's whole call), ``q_ref`` and ``o_ref`` ``[rows, n_kv,
    rep, D]``.  The rows that carry a token (``live_ref``, made here from
    ``chunk_lens``) are walked one behind the other as one stream of blocks
    through the scratch's two slots: while a block is multiplied the stream's
    next is in flight, be it the row's next or the next live row's first, so
    that only the stream's first fetch is exposed.  A block is ``ppb`` pages
    of the scratch, of which the granules of ``sub`` pages (128 key rows)
    from that of the row's first visible key to that of its last are copied:
    how many is a value, the copies loops.  The multiply takes the whole
    block, a fixed shape: what was not copied holds an earlier block's keys
    (zeros at first), finite and past the query's last visible key, so the
    mask gives them no weight."""
    g = pl.program_id(0)
    rows, n_kv, rep, d = q_ref.shape
    width = bt_ref.shape[1]
    sub_keys, subs, block = sub * page_size, ppb // sub, ppb * page_size
    base = g * rows

    @pl.when(g == 0)
    def _clear():
        def clear(i, _):
            buf[i // ppb, i % ppb] = jnp.zeros(buf.shape[2:], buf.dtype)

        jax.lax.fori_loop(0, 2 * ppb, clear, None)

    def find_live(r, n):
        live = cl_ref[base + r] > 0

        @pl.when(live)
        def _live():
            live_ref[n] = r

        @pl.when(jnp.logical_not(live))
        def _dead():
            o_ref[r] = jnp.zeros((n_kv, rep, d), o_ref.dtype)

        return n + live.astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, rows, find_live, 0)

    def span(r):
        """A row's position, the table column of its last visible key, the
        first granule of its walk and how many it takes."""
        start = sp_ref[base + r]
        last_page = _last_page(start, 1, page_size, width)
        first = jnp.minimum(_first_block(start, window, sub_keys), last_page // sub)
        return start, last_page, first, last_page // sub + 1 - first

    def taken(n_subs, j):
        """Granules block ``j`` of a walk of ``n_subs`` takes."""
        return jnp.minimum(subs, n_subs - j * subs)

    def fetch(r, j, slot):
        """Start the copies of block ``j`` of row ``r``'s walk."""
        _, last_page, first, n_subs = span(r)
        page0 = (first + j * subs) * sub
        # past the row's last page the granule repeats it; the mask hides it
        _start_copies(arena_ref, ly_ref, lambda i: bt_ref[base + r, jnp.minimum(page0 + i, last_page)], buf, slot,
                      taken(n_subs, j), sub, sem)

    @pl.when(n_live > 0)
    def _first():
        fetch(live_ref[0], 0, 0)

    def row(i, at):
        """Live row ``i``, whose first block is block ``at`` of the stream."""
        r = live_ref[i]
        start, _, first, n_subs = span(r)
        n_blocks = (n_subs + subs - 1) // subs

        def walk(j, _):
            slot = (at + j) % 2
            here = j + 1 < n_blocks

            @pl.when(here | (i + 1 < n_live))
            def _next():
                fetch(jnp.where(here, r, live_ref[jnp.minimum(i + 1, rows - 1)]), jnp.where(here, j + 1, 0), 1 - slot)

            _await_copies(buf, slot, taken(n_subs, j), sub, n_kv, sem)
            # the query's own key, its last visible one, counted from the block's first
            seen = _seen(start - (first + j * subs) * sub_keys, block, window)

            def head(h, _):
                fresh = j == 0
                m, l, acc = _attend(q_ref[r, h], _head_rows(buf.at[slot], 0, h), _head_rows(buf.at[slot], 1, h), seen,
                                    jnp.where(fresh, -jnp.inf, m_ref[h]), jnp.where(fresh, 0.0, l_ref[h]),
                                    jnp.where(fresh, 0.0, acc_ref[h]), scale)
                m_ref[h], l_ref[h], acc_ref[h] = m, l, acc

                @pl.when(j + 1 == n_blocks)
                def _finish():
                    o_ref[r, h] = _normalised(acc, l).astype(o_ref.dtype)

            jax.lax.fori_loop(0, n_kv, head, None)

        jax.lax.fori_loop(0, n_blocks, walk, None)
        return at + n_blocks

    jax.lax.fori_loop(0, n_live, row, 0)


def _paged_sharded(q, pages, block_table, start_pos, chunk_lens, layer, page_size, interpret, mesh, window, scale):
    """Run the paged kernel inside shard_map over the governing (trace) mesh.

    Mosaic custom calls cannot be auto-partitioned by GSPMD — the TP-sharded
    serving engine (inference/v2) traces this under a tensor-axis mesh, so the
    kernel wraps itself the way ``flash_attention._flash_sharded`` does.
    Attention is head-local: q shards on H, the arena on its n_kv dim, block
    tables, positions, chunk lengths and the layer's index replicate, and no
    collective is needed inside — the o_proj allreduce after it is GSPMD's to
    insert.  A tensor degree that does not divide n_kv replicates (correct,
    just not distributed)."""
    from jax.sharding import PartitionSpec as P

    h, n_kv = q.shape[2], pages.shape[-2]
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    head_axes = (TENSOR_AXIS, ) if tp > 1 and n_kv % tp == 0 and h % tp == 0 else ()
    qspec = P(None, None, head_axes or None, None)
    pspec = P(None, None, None, None, head_axes or None, None)

    def local(q_, pg_, bt_, sp_, cl_, ly_):
        return paged_attention_pallas(q_, pg_, bt_, sp_, cl_, page_size, layer=ly_, window=window, scale=scale,
                                      interpret=interpret)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, pspec, P(None, None), P(None), P(None), P()),
        out_specs=qspec,
        # pallas_call out_shapes carry no varying-mesh-axes annotation
        check_vma=False)
    return fn(q, pages, block_table, start_pos, chunk_lens, jnp.asarray(layer, jnp.int32))


def paged_attention_pallas(q, pages, block_table, start_pos, chunk_lens, page_size,
                           *, layer, window: int = 0, scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """The kernel's form of ``models/llama_cache.paged_attention`` (jnp
    golden) over one layer of the arena.

    q: [B, C, H, D]; pages: the whole arena [L, P, page, 2, n_kv, D] (chunk
    K/V already written); ``layer``: an index into it (traced in a scanned
    trunk), whose pages the kernel reads where they lie: no layer of the
    arena is sliced out first; block_table: [B, max_pages];
    start_pos/chunk_lens: [B] (``chunk_lens`` None: every row carries its
    whole chunk).  Query rows at and past a row's ``chunk_lens`` come out
    exactly zero.  With ``window`` (a static count) the query at position
    ``t`` sees keys ``t - window + 1 .. t`` and the walk starts at the block
    that holds the row's first visible key; ``scale`` multiplies the scores
    in place of ``1 / sqrt(D)``.
    """
    if interpret is None:
        interpret = not traced_for_tpu()
    if chunk_lens is None:
        chunk_lens = jnp.full(q.shape[:1], q.shape[1], jnp.int32)
    if isinstance(q, jax.core.Tracer) and not in_manual_mesh():
        mesh = get_trace_mesh()
        if mesh is not None and mesh.size > 1:
            return _paged_sharded(q, pages, block_table, start_pos, chunk_lens, layer, page_size, interpret, mesh,
                                  window, scale)
    return _paged_call(q, pages, block_table, start_pos, chunk_lens, layer, page_size=page_size,
                       window=int(window or 0), scale=None if scale is None else float(scale), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "window", "scale", "interpret"))
def _paged_call(q, pages, block_table, start_pos, chunk_lens, layer, *, page_size, window, scale, interpret):
    """The kernel's call at one set of shapes.  Jitted for its trace's sake:
    inside a step program's trace the jaxpr of a jitted function is looked up
    by its arguments' shapes, so the kernel's body (a walk of unrolled page
    copies: half a second of Python a trace) is traced once a shape and
    process, not twice a program that calls it (a scanned trunk traces its
    block twice), and a serving engine's programs share the decode shape."""
    b, c, h, d = q.shape
    n_kv = pages.shape[-2]
    rep = h // n_kv
    # the form, the block, how its pages arrive, the tile and the scratch
    # follow from the shapes: a chunk of up to _TILE_ROWS query rows a key
    # head is one tile; a longer one is cut into tiles of that many, padded
    # with rows no chunk position owns
    itemsize = pages.dtype.itemsize
    width = block_table.shape[1]
    copies = _copies_pages(n_kv, d, itemsize)
    decode = takes_decode_form(c, n_kv, d, itemsize)
    ppb = walk_block(page_size, width, n_kv, d, itemsize)
    rows = c * rep
    tile = min(rows, _TILE_ROWS)
    padded = -(-rows // tile) * tile

    # position-major query layout: [B, n_kv, C*rep, D], row = c*rep + r
    qg = q.reshape(b, c, n_kv, rep, d).transpose(0, 2, 1, 3, 4).reshape(b, n_kv, rows, d)
    if padded != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, padded - rows), (0, 0)))

    state = [pltpu.VMEM((n_kv, padded, 1), jnp.float32), pltpu.VMEM((n_kv, padded, 1), jnp.float32),
             pltpu.VMEM((n_kv, padded, d), jnp.float32)]
    lanes = lambda n: -(-n // 128) * 128  # noqa: E731
    q_block = n_kv * padded * lanes(d) * q.dtype.itemsize
    scale = 1.0 / (d**0.5) if scale is None else scale
    if decode:
        # the rows of a grid step: all of them where their queries and outputs
        # fit their share of VMEM, as every cell's do (160 rows of 2 key heads,
        # 16 of 32), else the most that divide the call; a row's ``rep``
        # query rows take whole sublane tiles there
        sub, ppb = walk_block(page_size, width, n_kv, d, itemsize, chunk=1), _decode_block(page_size, width, n_kv, d,
                                                                                            itemsize)
        q_block = n_kv * -(-rep * q.dtype.itemsize // 32) * 32 * lanes(d)
        group = max(g for g in range(1, b + 1) if b % g == 0 and (g == 1 or g * q_block <= _DECODE_ROWS_BYTES))
        q_block *= group
        row_block = pl.BlockSpec((group, n_kv, rep, d), lambda g, *_: (g, 0, 0, 0))
        grid, semantics = (b // group, ), ("arbitrary", )
        kernel = functools.partial(_decode_kernel, page_size=page_size, ppb=ppb, sub=sub, scale=scale, window=window)
        scratch = [pltpu.SMEM((group, ), jnp.int32)]
    else:
        row_block = pl.BlockSpec((1, n_kv, padded, d), lambda b, g, *_: (b, 0, 0, 0))
        # the row's one step walks the blocks it copies; the pipeline brings a block a step
        grid, semantics = (b, 1 if copies else -(-width // ppb)), ("parallel", "arbitrary")
        kernel = functools.partial(_paged_kernel, page_size=page_size, ppb=ppb, copies=copies, rep=rep, tile=tile,
                                   scale=scale, window=window)
        scratch = []
    if copies:
        n_pad = _padded_heads(n_kv, itemsize)
        arena_specs, arenas = [pl.BlockSpec(memory_space=pl.ANY)], [pages]
        scratch = [pltpu.VMEM((2, ppb, page_size, 2, n_pad, d), pages.dtype), pltpu.SemaphoreType.DMA((2, ))] + scratch
    else:
        def page_spec(i):
            def index(b, g, bt, sp, cl, ly):
                # clamped to the row's last page: past it the same page again,
                # which the pipeline does not fetch twice
                # ... and before its first block that block's, fetched once
                first = _first_block(sp[b], window, ppb * page_size)
                column = jnp.minimum(jnp.maximum(g, first) * ppb + i, _last_page(sp[b], cl[b], page_size, width))
                return ly[0], bt[b, column], 0, 0, 0, 0

            return pl.BlockSpec((None, None, page_size, 2, n_kv, d), index)

        n_pad = -(-n_kv // 8) * 8               # as the tiling lays a page out
        arena_specs, arenas = [page_spec(i) for i in range(ppb)], [pages] * ppb
    vmem = n_kv * padded * (lanes(d) + 2 * 128) * 4 + 2 * ppb * page_size * 2 * n_pad * lanes(d) * itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[row_block, *arena_specs],
            out_specs=row_block,
            scratch_shapes=scratch + state,
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, padded, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            # the query and output blocks twice (the pipeline's two buffers),
            # the block's pages twice (the scratch's two slots or the
            # pipeline's), the softmax state, and room for the body's
            # temporaries
            vmem_limit_bytes=4 * q_block + vmem + (16 << 20)),
        interpret=interpret,
        name="ds_paged_attention",
    )(block_table, start_pos.astype(jnp.int32), chunk_lens.astype(jnp.int32),
      jnp.reshape(layer, (1, )).astype(jnp.int32), qg, *arenas)

    return out[:, :, :rows].reshape(b, n_kv, c, rep, d).transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)
