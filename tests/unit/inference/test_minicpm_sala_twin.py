"""MiniCPM-SALA's serving twin through pages, the indexer's cache and state
slots, against the plain reference's full forward (``test_minicpm_sala.py``
holds the small size, the weights and the reference's logits this file uses):
prefill in chunks then decode, logits of every position fed; slots other than
0 on interleaved pages, two sequences in one step, a row group of each width,
compressed keys that span two chunks and two pages."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.minicpm_sala_cache import MiniCPMSALAForCausalLMWithCache, init_cache

from test_minicpm_sala import CFG, LENGTH, TOL, draw, ref, ref_cfg, rel  # noqa: F401

PAGE = 8
KV = PagedKVConfig(num_pages=96, page_size=PAGE, max_pages_per_seq=34)


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 2 * LENGTH).reshape(2, LENGTH)


@pytest.fixture(scope="module")
def want(params, ids):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, ref_cfg(CFG))[0])
    return [np.asarray(fwd(params, jnp.asarray(row))) for row in ids]


def _twin_apply(attention_impl):
    twin = MiniCPMSALAForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    return jax.jit(lambda p, c, t, s, bt, n, groups: twin.apply(p, t, s, bt, c, n, False, groups),
                   static_argnames="groups")


#: one jitted function a way of reading the pages: a step's shape compiles once, whatever test feeds it
_APPLY = {impl: _twin_apply(impl) for impl in ("reference", "flash")}


def _tables(slots=(2, 3)):
    """Two rows on interleaved pages (row 0 the odd ones, row 1 the even ones from 2), each in its slot."""
    table = np.zeros((2, KV.max_pages_per_seq + 1), np.int32)
    table[0, :-1] = 1 + 2 * np.arange(KV.max_pages_per_seq)
    table[1, :-1] = 2 + 2 * np.arange(KV.max_pages_per_seq)
    table[:, -1] = slots
    return jnp.asarray(table)


def _feed(params, ids, steps, attention_impl="reference"):
    """``steps``: a list of steps, a step a list of groups, a group ``(width,
    [(row, tokens)...])``; a step of more than one group goes as row groups on
    one flat axis.  Per row the logits of every position fed."""
    cache = init_cache(CFG, KV, jnp.float32, 4, 32)
    tables = _tables()
    pos, out = [0, 0], [[], []]
    apply = functools.partial(_APPLY[attention_impl], params)
    with jax.default_matmul_precision("highest"):
        for step in steps:
            toks, start, lens, rows, groups = [], [], [], [], []
            for width, group in step:
                groups.append((len(group), width))
                for row, n in group:
                    t = np.zeros(width, np.int32)
                    t[:n] = ids[row, pos[row]:pos[row] + n]
                    toks.append(t), start.append(pos[row]), lens.append(n), rows.append(row)
            logits, cache = apply(cache, jnp.asarray(np.concatenate(toks)), jnp.asarray(start, jnp.int32),
                                  tables[np.asarray(rows)], jnp.asarray(lens, jnp.int32), groups=tuple(groups))
            at = 0
            for (width, group) in step:
                for row, n in group:
                    out[row].append(np.asarray(logits[at:at + n]))
                    pos[row] += n
                    at += width
    return [np.concatenate(o) if o else None for o in out], cache


def _chunks(width, upto, rows=(0, 1)):
    return [[(width, [(r, min(width, upto - s)) for r in rows])] for s in range(0, upto, width)]


@pytest.mark.parametrize("chunk,impl", [(32, "reference"), (128, "flash")])
def test_prefill_in_chunks_then_decode_equals_the_full_forward(params, ids, want, chunk, impl):
    """Two sequences on interleaved pages in slots 2 and 3: 232 prompt tokens
    in chunks (the last one ragged), then 8 decode steps through both kernels."""
    steps = _chunks(chunk, 232) + [[(1, [(0, 1), (1, 1)])] for _ in range(8)]
    got, cache = _feed(params, ids, steps, impl)
    for row in (0, 1):
        assert rel(got[row], want[row]) < TOL
    assert float(jnp.abs(cache["state"][:, 2:4]).min(axis=(2, 3, 4)).max()) >= 0 and \
        not np.asarray(cache["state"][:, 1]).any()


def test_compressed_keys_that_span_two_chunks_and_two_pages(params, ids, want):
    """Chunks of 20 and 27 tokens end inside pages of 8: every compressed key
    (two pages) is completed by a chunk that holds only its tail, and the
    indexer's cache holds what the whole sequence's keys give."""
    sizes = [20, 27] * 5
    steps, fed = [], 0
    for n in sizes:
        steps.append([(32, [(0, n)])])
        fed += n
    got, cache = _feed(params, ids, steps)
    assert rel(got[0], want[0][:fed]) < TOL
    held = np.asarray(cache["ckeys"][:, 2])                                    # the row's slot: [layers, columns + 1, G, d]
    assert np.abs(held[:, :fed // PAGE - 1]).min(axis=(2, 3)).min() > 0
    assert not held[:, fed // PAGE - 1:].any()          # the next one's second page is not whole yet; nothing was dumped
    assert not np.asarray(cache["ckeys"][:, [0, 1, 3]]).any()                  # and no other slot was written


def test_a_mixed_step_decodes_one_row_beside_the_others_prefill(params, ids, want):
    """Row 0 decodes through the two kernels in a group of one token while
    row 1 still prefills in a group of 32: two row groups on one flat axis."""
    steps = _chunks(32, 192, rows=(0, )) + \
        [[(1, [(0, 1)]), (32, [(1, 32)])] for _ in range(7)] + [[(1, [(0, 1), (1, 1)])] for _ in range(4)]
    got, _ = _feed(params, ids, steps, "flash")
    assert rel(got[0], want[0][:192 + 11]) < TOL and rel(got[1], want[1][:224 + 4]) < TOL
