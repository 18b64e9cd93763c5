"""Pallas paged decode attention vs the jnp golden (interpret mode on CPU),
mirroring the reference's kernel-vs-torch numeric tests (tests/unit/ops).
Tracing the interpreted kernel takes six seconds a case, so the cases run in
three files, a worker each: here those whose pages the kernel copies itself,
in ``test_paged_attention_pipelined.py`` those the pipeline brings, in
``test_paged_attention_window.py`` the window bound over cases of both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama_cache import _write_pages, paged_attention
from deepspeed_tpu.ops.paged_attention import paged_attention_pallas


def _setup(b=3, c=4, h=8, n_kv=4, d=32, page_size=8, max_pages=6, seed=0):
    """Build an arena of one layer with randomized per-sequence histories,
    then write the current chunk, exactly as LlamaAttentionCache does."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + b * max_pages
    pages = jnp.zeros((num_pages, page_size, 2, n_kv, d), jnp.float32)

    start_pos = np.array([0, 5, 13][:b] , np.int32)         # prefill, mid, deep
    chunk_lens = np.array([c, c - 1, 1][:b], np.int32)
    block_table = np.zeros((b, max_pages), np.int32)
    next_page = 1
    for i in range(b):
        needed = -(-(start_pos[i] + c) // page_size)
        for s in range(needed):
            block_table[i, s] = next_page
            next_page += 1

    # write history KV directly (positions < start_pos)
    hist_k = rng.normal(size=(b, int(start_pos.max()), n_kv, d)).astype(np.float32)
    hist_v = rng.normal(size=(b, int(start_pos.max()), n_kv, d)).astype(np.float32)
    pages_np = np.asarray(pages).copy()
    for i in range(b):
        for t in range(start_pos[i]):
            pg = block_table[i, t // page_size]
            pages_np[pg, t % page_size, 0] = hist_k[i, t]
            pages_np[pg, t % page_size, 1] = hist_v[i, t]
    pages = jnp.asarray(pages_np)

    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, c, n_kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, c, n_kv, d)), jnp.float32)
    bt = jnp.asarray(block_table)
    sp = jnp.asarray(start_pos)
    cl = jnp.asarray(chunk_lens)
    pages = _write_pages(pages[None], k_new, v_new, bt, sp, page_size, cl, layer=0)
    return q, pages, bt, sp, cl, page_size


def _rows(rows, c, h, n_kv, d=128, page_size=8, width=None, layers=1, seed=0):
    """An arena of ``layers`` layers, one of which holds the history of
    ``rows`` = [(start, chunk_len), ...] and each row's chunk, written as the
    twins write it; the table ``width`` columns wide.  Heads of 128 lanes, as
    every cell's, unless ``d`` says otherwise."""
    rng = np.random.default_rng(seed)
    b = len(rows)
    start = np.array([s for s, _ in rows], np.int32)
    lens = np.array([n for _, n in rows], np.int32)
    need = [-(-(s + c) // page_size) for s in start]
    width = width or max(need)
    table = np.zeros((b, width), np.int32)
    nxt = 1
    for i in range(b):
        table[i, :need[i]] = np.arange(nxt, nxt + need[i])
        nxt += need[i]
    pages = np.zeros((nxt, page_size, 2, n_kv, d), np.float32)
    for i in range(b):
        hist = rng.normal(size=(start[i], 2, n_kv, d)).astype(np.float32)
        for t in range(start[i]):
            pages[table[i, t // page_size], t % page_size] = hist[t]
    q = jnp.asarray(rng.normal(size=(b, c, h, d)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, c, n_kv, d)), jnp.float32) for _ in range(2))
    table, start, lens = jnp.asarray(table), jnp.asarray(start), jnp.asarray(lens)
    # the other layers hold other rows: a read of the wrong layer shows
    layer = max(layers - 2, 0)
    pages = jnp.stack([jnp.asarray(pages if i == layer else rng.normal(size=pages.shape), jnp.float32)
                       for i in range(layers)])
    pages = _write_pages(pages, k_new, v_new, table, start, page_size, lens, layer=layer)
    return q, pages, table, start, lens, page_size, layer


def _eva_view():
    """EvaByte's pages as the kernel sees them (``_kernel_view``): 32 key
    heads of 128 lanes, no grouping (the scratch pads such heads and takes a
    head's rows by strided load), rows whose chunk starts in the third window,
    one of them a decode row, the layer named in the whole arena."""
    from deepspeed_tpu.models.evabyte_cache import _kernel_view
    page, window = 8, 256
    ring = window // page                                   # ring pages; summary pages a window: ring // page
    rng = np.random.default_rng(3)
    start = np.array([2 * window + 40, 2 * window + 201, 2 * window], np.int32)
    lens = np.array([16, 1, 0], np.int32)
    width = ring + 3 * (ring // page)
    table = 1 + np.arange(3 * width, dtype=np.int32).reshape(3, width)
    arena = jnp.asarray(rng.normal(size=(2, 1 + 3 * width, page, 2, 32, 128)), jnp.float32)
    view, vstart = _kernel_view(jnp.asarray(table), jnp.asarray(start), page, ring, window, 4)
    q = jnp.asarray(rng.normal(size=(3, 16, 32, 128)), jnp.float32)
    return q, arena, view, vstart, jnp.asarray(lens), page, 1


COPIED = {
    # Heads of 128 lanes in whole tiles (every cell's): the kernel copies a block's pages itself, 64 of them (512 rows),
    # and takes a head's rows by strided load.
    # the three rows of old: a prefill from nothing, a chunk short of one token, a decode row deep in a chunk program
    "mha": lambda: _rows([(0, 4), (5, 3), (13, 1)], c=4, h=8, n_kv=8),
    "gqa": lambda: _rows([(0, 4), (5, 3), (13, 1)], c=4, h=8, n_kv=4),
    # a table of 150 columns under blocks of 64 pages: two whole blocks and a tail of 22, rows that end in each
    "table_no_multiple_of_the_block": lambda: _rows([(1100, 4), (520, 4), (250, 2), (513, 1)], c=4, h=4, n_kv=2, width=150),
    # a mixed step: a chunk of 32 next to a decode row deep in its context (its second block) and a row with nothing
    "decode_row_in_a_chunk_of_32": lambda: _rows([(64, 32), (700, 1), (0, 0), (37, 17)], c=32, h=8, n_kv=4),
    # query rows beyond one tile (32 positions x 8 heads a key head = 256 rows): a decode row multiplies the first only
    "more_than_one_query_tile": lambda: _rows([(10, 32), (90, 1), (40, 20), (0, 0)], c=32, h=16, n_kv=2),
    "two_key_heads_a_tensor_parallel_shard": lambda: _rows([(0, 8), (77, 1), (30, 5)], c=8, h=8, n_kv=2),
    "heads_of_128_lanes": lambda: _rows([(0, 4), (5, 3), (530, 1)], c=4, h=8, n_kv=4),
    "layer_named_in_the_whole_arena": lambda: _rows([(0, 4), (5, 3), (540, 1)], c=4, h=8, n_kv=4, layers=3),
    "evabyte_view_32_key_heads_third_window": _eva_view,
    # one key head: whole tiles in float32; in bfloat16 half a 32-bit sublane, a page the tiling pads (see below)
    "one_key_head": lambda: _rows([(3, 4), (21, 1), (300, 2)], c=4, h=4, n_kv=1),
}
#: a decode call's rows as (context, in keys; 0: a row with no token): dead rows first, last and in runs between
#: live ones, contexts round the granule's edges (128) and the block's (512)
_DECODE_ROWS = (0, 1, 127, 128, 0, 0, 129, 511, 512, 513, 0)


def _decode(contexts, h, n_kv, **kw):
    """A call of one query position a row (the kernel's decode form), a row a context."""
    return _rows([(max(n - 1, 0), int(n > 0)) for n in contexts], c=1, h=h, n_kv=n_kv, **kw)


DECODE = {
    # One query position a row over pages the kernel copies: the decode form, one stream of page copies over the
    # rows that carry a token.  Each cell's decode shape, fewer rows:
    # Phi-4-mini-flash [160, 2, 4, 128]: runs of dead rows, every edge of a granule and of a block
    "decode_two_key_heads_four_queries_each": lambda: _decode(_DECODE_ROWS, h=8, n_kv=2),
    # Mixtral [16, 8, 4, 128], the last row ending on the table's last page (65 pages of 8 hold 520 keys)
    "decode_eight_key_heads_ends_on_the_last_page": lambda: _decode((0, 300, 0, 520, 77), h=32, n_kv=8, width=65),
    # EvaByte [16, 32, 1, 128]: one query a key head, the scratch pads the heads
    "decode_32_key_heads_one_query_each": lambda: _decode((200, 0, 0, 515, 1), h=32, n_kv=32),
    # Granite [32, 4, 8, 128] and Solar-Open2 [32, 8, 8, 128]: eight queries a key head; a traced layer of three
    "decode_four_key_heads_eight_queries_each": lambda: _decode((640, 0, 130, 0), h=32, n_kv=4, layers=3),
    "decode_eight_key_heads_eight_queries_each": lambda: _decode((0, 0, 385, 1030), h=64, n_kv=8),
    # Trinity 48q/8kv: six queries a key head
    "decode_eight_key_heads_six_queries_each": lambda: _decode((257, 0, 512), h=48, n_kv=8),
    "decode_a_lone_live_row": lambda: _decode((0, 0, 0, 700, 0, 0), h=8, n_kv=2),
    "decode_no_live_row": lambda: _decode((0, 0, 0), h=8, n_kv=2),
    # more rows than one grid step holds (see ``test_decode_rows_beyond_one_grid_step``)
    "decode_every_row_live": lambda: _decode((5, 140, 260, 390), h=8, n_kv=2),
}
COPIED.update(DECODE)
PIPELINED = {
    # Pages the chip's tiling pads, or heads no strided load takes: the pipeline brings a block's pages, 16 of them
    # (128 rows), and a head's rows are a load a page.
    "heads_of_32_lanes": lambda: _rows([(0, 4), (5, 3), (13, 1)], c=4, h=8, n_kv=4, d=32),
    # 150 columns under blocks of 16 pages: nine whole blocks and a tail of 6; a row with nothing, the layer named
    "heads_of_32_lanes_table_no_multiple_of_the_block": lambda: _rows(
        [(1100, 4), (520, 4), (0, 0), (513, 1)], c=4, h=4, n_kv=2, d=32, width=150, layers=3),
    "heads_of_64_lanes_decode_row_in_a_chunk_of_32": lambda: _rows(
        [(64, 32), (700, 1), (0, 0), (37, 17)], c=32, h=16, n_kv=2, d=64),
    "three_key_heads": lambda: _rows([(0, 4), (5, 3), (140, 1)], c=4, h=6, n_kv=3),
    "heads_of_256_lanes": lambda: _rows([(0, 4), (130, 1)], c=4, h=4, n_kv=2, d=256),
}
CASES = {**COPIED, **PIPELINED}


def matches_jnp_golden(case, dtype):
    """The kernel in interpret mode against the jnp golden: same values where
    a row carries a token, exactly zero where it does not.  In bfloat16 (two
    heads a 32-bit sublane: the kernel's other way to take a head's rows out
    of a page) against the golden over the same rounded operands."""
    q, pages, table, start, lens, page_size, layer = CASES[case]()
    q, pages = q.astype(dtype), pages.astype(dtype)
    as32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    expected = paged_attention(as32(q), as32(pages[layer]), table, start, lens, page_size)
    got = jax.jit(lambda q, pages: paged_attention_pallas(q, pages, table, start, lens, page_size, layer=layer,
                                                          interpret=True))(q, pages)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(as32(got)), np.asarray(expected), atol=2e-5 if dtype == jnp.float32 else 3e-2)
    past = np.arange(q.shape[1])[None, :] >= np.asarray(lens)[:, None]
    np.testing.assert_array_equal(np.asarray(as32(got))[past], 0)
    if case in DECODE:
        # the same rows through the general form: the chunk of 1 padded into a chunk of 2
        wide = jax.jit(lambda q, pages: paged_attention_pallas(jnp.pad(q, ((0, 0), (0, 1), (0, 0), (0, 0))), pages, table,
                                                               start, lens, page_size, layer=layer, interpret=True))(q, pages)
        np.testing.assert_allclose(np.asarray(as32(wide[:, :1])), np.asarray(expected),
                                   atol=2e-5 if dtype == jnp.float32 else 3e-2)
        np.testing.assert_array_equal(np.asarray(as32(wide[:, 1:])), 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(COPIED))
def test_pallas_matches_jnp_golden(case, dtype):
    matches_jnp_golden(case, dtype)


def test_the_decode_form_is_taken_from_the_shape_alone():
    """One query position a row over pages the kernel copies takes the decode
    form, and the walk's granule the engine counts by follows it; a chunk, or
    pages the pipeline brings, the general one."""
    from deepspeed_tpu.ops.paged_attention import takes_decode_form, walk_block
    assert takes_decode_form(1, 2, 128, 2) and takes_decode_form(1, 32, 128, 2) and takes_decode_form(1, 2, 128, 4)
    assert not takes_decode_form(2, 2, 128, 2) and not takes_decode_form(128, 8, 128, 2)
    assert not takes_decode_form(1, 1, 128, 2) and not takes_decode_form(1, 4, 64, 2)        # pages the tiling pads
    assert walk_block(16, 257, 2, 128, 2, chunk=1) == 8 and walk_block(16, 257, 2, 128, 2, chunk=128) == 32
    assert walk_block(16, 257, 2, 128, 2) == 32 and walk_block(16, 5, 2, 128, 2, chunk=1) == 5
    assert walk_block(16, 257, 4, 64, 2, chunk=1) == walk_block(16, 257, 4, 64, 2) == 8


@pytest.mark.parametrize("case, fit", [("decode_two_key_heads_four_queries_each", 3), ("decode_every_row_live", 2)])
def test_decode_rows_beyond_one_grid_step(monkeypatch, case, fit):
    """A call whose queries pass the VMEM one grid step may give them is cut
    into groups of rows, a stream each, and gives the same values: 11 rows
    where 3 fit go one a step, 4 where 2 fit two a step."""
    from deepspeed_tpu.ops import paged_attention as kernel
    q, pages, table, start, lens, page_size, layer = DECODE[case]()
    monkeypatch.setattr(kernel, "_DECODE_ROWS_BYTES", fit * 2 * 8 * 128 * 4)     # a row: 2 key heads of 8 x 128 float32
    kernel._paged_call.clear_cache()                     # the budget is no part of the jitted call's key
    try:
        got = kernel.paged_attention_pallas(q, pages, table, start, lens, page_size, layer=layer, interpret=True)
    finally:
        kernel._paged_call.clear_cache()
    expected = paged_attention(q, pages[layer], table, start, lens, page_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)
