"""Solar-Open2's serving twin through state slots and pages, against the
plain reference's full forward (``test_solar_open2.py`` holds the small size,
the weights and the reference's logits this file uses): prefill in chunks
then decode, logits of every position fed; slots other than 0 on scattered
pages, two sequences in one step, a row group of each width."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.solar_open2_cache import SolarOpen2ForCausalLMWithCache, init_cache

from test_solar_open2 import CFG, TOL, draw, ref, ref_cfg  # noqa: F401

PAGE, CHUNK = 16, 32
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 200).reshape(3, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    return [np.asarray(ref.forward(params, jnp.asarray(row[:150]), ref_cfg(CFG))[0]) for row in ids]


def _twin_apply(attention_impl):
    twin = SolarOpen2ForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    return jax.jit(lambda p, c, t, s, bt, n, groups: twin.apply(p, t, s, bt, c, n, False, groups),
                   static_argnames="groups")


#: one jitted function a way of reading the pages: a step's shape compiles once, whatever test feeds it
_APPLY = {impl: _twin_apply(impl) for impl in ("reference", "flash")}


def _feed(params, rows, steps, tables, attention_impl="reference", cache=None, start=None):
    """Feed ``rows`` through the twin.  A step is a list of groups, a group a
    list of ``(row, tokens)`` fed as one rectangle, ``CHUNK`` wide or, where
    no row carries more than a token, 1; a step of more than one group goes
    as row groups on one flat axis.  Per row the logits of every position
    fed, and the cache."""
    if cache is None:
        cache = init_cache(CFG, KV, jnp.float32, 6, CHUNK)
    pos, out = list(start or [0] * len(rows)), [[] for _ in rows]
    apply = functools.partial(_APPLY[attention_impl], params)
    with jax.default_matmul_precision("highest"):
        for step in steps:
            groups = tuple((len(g), 1 if max(n for _, n in g) <= 1 else CHUNK) for g in step)
            toks, order = [], [r for g in step for r, _ in g]
            for g, (_, width) in zip(step, groups):
                rect = np.zeros((len(g), width), np.int32)
                for j, (r, n) in enumerate(g):
                    rect[j, :n] = rows[r][pos[r]:pos[r] + n]
                toks.append(rect.reshape(-1))
            lens = [n for g in step for _, n in g]
            flat = jnp.asarray(np.concatenate(toks))
            logits, cache = apply(cache, flat if len(groups) > 1 else flat.reshape(groups[0]),
                                  jnp.asarray([pos[r] for r in order], jnp.int32), jnp.asarray(tables[order]),
                                  jnp.asarray(lens, jnp.int32), groups=groups if len(groups) > 1 else None)
            logits, t0 = np.asarray(logits).reshape(-1, logits.shape[-1]), 0
            for g, (_, width) in zip(step, groups):
                for r, n in g:
                    out[r].append(logits[t0:t0 + n])
                    pos[r] += n
                    t0 += width
    return [np.concatenate(o) if o else None for o in out], cache


def _table(pages, slot, width=14):
    """A block-table row: the pages, then zeros, the slot in the last column."""
    row = np.zeros(width, np.int32)
    row[:len(pages)] = pages
    row[-1] = slot
    return row


TABLES = np.stack([_table(np.arange(1, 40, 3), slot=4), _table(np.arange(3, 42, 3), slot=1),
                   _table(np.arange(2, 41, 3), slot=3)])
PLANS = {
    "chunks_that_start_and_end_inside_a_page": [[[(0, n)]] for n in (7, 32, 20, 12, 32, 5)] + [[[(0, 1)]]] * 12,
    "decode_from_the_second_token": [[[(0, 1)]]] * 40,
    "three_rows_in_scattered_slots_in_one_rectangle": [[[(0, 32), (1, 20), (2, 32)]], [[(0, 32), (1, 0), (2, 11)]]] +
    [[[(0, 1), (1, 1), (2, 1)]]] * 8,
}


@pytest.mark.parametrize("plan, attention_impl", [(p, "reference") for p in sorted(PLANS)] +
                         [("chunks_that_start_and_end_inside_a_page", "flash")])
def test_twin_chunks_then_decode_match_reference(params, ids, want, plan, attention_impl):
    got, _ = _feed(params, ids, PLANS[plan], TABLES, attention_impl)
    for i, g in enumerate(got):
        if g is not None:
            np.testing.assert_allclose(g, want[i][:len(g)], atol=TOL)


def test_a_mixed_step_in_two_row_groups_matches_reference(params, ids, want):
    """Rows 0 and 1 prefill, then decode one slot each (``ds_kda_update``)
    beside row 2's chunks (the chunked form): ``((2, 1), (1, 32))`` on one
    flat axis, a row group of each width in one step."""
    steps = [[[(0, 32), (1, 25)]], [[(0, 9), (1, 0)]]] + [[[(0, 1), (1, 1)], [(2, 32)]]] * 3 + \
        [[[(0, 1), (1, 1)], [(2, 13)]]] + [[[(0, 1), (1, 1), (2, 1)]]] * 4
    got, _ = _feed(params, ids, steps, TABLES)
    assert [len(g) for g in got] == [41 + 8, 25 + 8, 96 + 13 + 4]
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[i][:len(g)], atol=TOL)


def test_a_table_built_for_the_linear_layout_runs_in_the_scratch_slot(params, ids, want):
    """The benchmark's check builds its own table: consecutive pages from
    page 1, every other column 0 and no slot, so the row runs in slot 0."""
    table = np.zeros((1, 14), np.int32)
    table[0, :10] = 1 + np.arange(10)
    got, _ = _feed(params, ids[:1], [[[(0, 32)]]] * 4 + [[[(0, 8)]]] + [[[(0, 1)]]] * 10, table)
    np.testing.assert_allclose(got[0], want[0][:146], atol=TOL)


def test_a_padded_row_and_a_finished_row_leave_every_slot_but_scratch_untouched(params, ids):
    tables = np.stack([_table(np.arange(1, 14), slot=2), _table(np.arange(20, 33), slot=5), _table([], slot=0)])
    _, cache = _feed(params, ids, [[[(0, 32), (1, 32), (2, 0)]], [[(0, 32), (1, 7), (2, 0)]]], tables)
    for lens in ([32, 0, 0], [1, 0, 0]):
        _, after = _feed(params, ids, [[[(i, n) for i, n in enumerate(lens)]]], tables, cache=cache, start=[64, 39, 0])
        for name in ("kda", "conv"):
            keep = [s for s in range(6) if s not in (0, 2)]
            np.testing.assert_array_equal(np.asarray(after[name])[:, keep], np.asarray(cache[name])[:, keep])
            assert np.abs(np.asarray(after[name])[:, 2] - np.asarray(cache[name])[:, 2]).max() > 0
        others = np.setdiff1d(np.arange(1, KV.num_pages), np.asarray(tables[0][:13]))
        np.testing.assert_array_equal(np.asarray(after["pages"])[:, others], np.asarray(cache["pages"])[:, others])


def test_a_slot_used_before_gives_what_a_fresh_one_gives(params, ids, want):
    table = _table(1 + np.arange(13), slot=1)[None]
    _, cache = _feed(params, ids[1:2], [[[(0, 32)]]] * 5, table)            # another sequence, 160 tokens deep
    for plan in ([[[(0, 32)]]] * 2 + [[[(0, 1)]]] * 6, [[[(0, 1)]]] * 12):
        got, _ = _feed(params, ids[:1], plan, table, cache=cache)
        np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_state_that_is_not_carried_fails_the_comparison(params, ids, want):
    """The guard of the guard for the slots: the recurrent states zeroed 32
    positions in, the logits a hundred positions later are still far off."""
    table = _table(1 + np.arange(13), slot=1)[None]
    _, cache = _feed(params, ids[:1], [[[(0, 32)]]], table)
    cache = {**cache, "kda": jnp.zeros_like(cache["kda"])}
    got, _ = _feed(params, ids[:1], [[[(0, 32)]]] * 3 + [[[(0, 4)]]], table, cache=cache, start=[32])
    worst = [float(np.abs(got[0][lo:hi] - want[0][32 + lo:32 + hi]).max()) for lo, hi in ((0, 32), (96, 100))]
    assert worst[0] > 100 * TOL and worst[-1] > 10 * TOL, worst
