"""A run in a state-slot geometry: consecutive chunks of one prompt as rows of
one step, the slot's state and the convolution's last inputs handed from a row
to the row that continues it inside the program
(``models/solar_open2_cache.continuing_rows``; ``test_solar_open2.py`` holds
the small size and the weights this file uses, the rehearsal widths of
``solar-open2-250b-serve-1chip``).  The chunked form row after row against the
form that takes the rows side by side; steps of the twin that hold a run
against the plain reference and against the same chunks a step each; the
engine with runs against the engine at ``scheduler.run_rows = 1``; and which
geometries the scheduler plans a run under."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.solar_open2 import kda_chunk
from deepspeed_tpu.models.solar_open2_cache import continuing_rows, init_cache
from deepspeed_tpu.serving.kv_pressure import KVPressureManager

from test_chunk_runs import _Host
from test_minicpm_sala import CFG as SALA_CFG
from test_slot_twins_golden import FAMILIES
from test_solar_open2 import CFG, TOL, draw, ref, ref_cfg
from test_solar_open2_twin import _APPLY, CHUNK, KV, PAGE, TABLES

SLOTS = 6


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 200).reshape(3, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    return [np.asarray(ref.forward(params, jnp.asarray(row[:150]), ref_cfg(CFG))[0]) for row in ids]


# ---------------------------------------------------------------- (a) the chunked form, row after row


def _rows(seed, b=4, c=32, h=2, d=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k = (unit(jax.random.normal(key, (b, c, h, d))) for key in keys[:2])
    g = -jax.random.uniform(keys[3], (b, c, h, d), minval=0.001, maxval=0.3)
    return (q * d**-0.5, k, jax.random.normal(keys[2], (b, c, h, d)), g,
            2.0 * jax.random.uniform(keys[4], (b, c, h))), jax.random.normal(keys[5], (b, h, d, d))


@pytest.mark.parametrize("goes_on", [(0, 0, 0, 0), (0, 1, 1, 1), (0, 1, 0, 1), (0, 0, 1, 0)],
                         ids=["no_row", "one_run_of_four", "two_runs_of_two", "a_run_between_two_rows"])
def test_the_form_row_after_row_is_the_chunked_form_over_the_joined_rows(goes_on):
    """Rows that go on from the row before them give what ``kda_chunk`` gives
    over the rows joined end to end from the first row's state, and every row
    comes back with the state it leaves; a row that continues nothing gives
    what it gives side by side."""
    args, state = _rows(3)
    o, left = jax.jit(kda_chunk)(*args, state, continues=jnp.asarray(goes_on, bool))
    first = 0
    for i in range(1, 5):
        if i < 4 and goes_on[i]:
            continue
        joined = tuple(t[first:i].reshape((1, -1) + t.shape[2:]) for t in args)
        o_joined, _ = kda_chunk(*joined, state[first:first + 1])
        np.testing.assert_allclose(np.asarray(o[first:i]).reshape(o_joined.shape), np.asarray(o_joined), atol=2e-5)
        for j in range(first, i):       # row j's state: the chunked form from the run's start to its end
            upto = tuple(t[first:j + 1].reshape((1, -1) + t.shape[2:]) for t in args)
            np.testing.assert_allclose(np.asarray(left[j]), np.asarray(kda_chunk(*upto, state[first:first + 1])[1][0]),
                                       atol=2e-5)
        first = i


def test_which_rows_continue_is_read_from_slots_positions_and_lengths():
    """A row continues the row before it where it carries tokens, holds its
    slot and starts where that row, a full one, ends: not a padding row in the
    scratch slot behind another, not a row at its slot's own position, not the
    row behind a row that ended inside its chunk, not the rows of the
    benchmark's rectangle, all in slot 0 from one position."""
    cases = [
        ([3, 3, 3, 0], [64, 96, 128, 0], [32, 32, 7, 0], [0, 1, 1, 0]),        # a run of three and padding
        ([3, 3, 5, 5], [0, 32, 96, 128], [32, 32, 32, 32], [0, 1, 0, 1]),      # [A0, A1, B0, B1]
        ([0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0]),              # padding alone
        ([0, 0, 0, 0], [64, 64, 64, 64], [32, 32, 32, 32], [0, 0, 0, 0]),      # a rectangle built without slots
        ([2, 2, 4, 4], [32, 64, 0, 64], [20, 32, 32, 32], [0, 0, 0, 0]),       # behind a short row; a gap
        ([2, 4, 2, 2], [0, 32, 32, 64], [32, 32, 32, 0], [0, 0, 0, 0]),        # another's row between; no tokens
    ]
    for slot, start, lens, expect in cases:
        goes_on, handed_on = continuing_rows(*(jnp.asarray(a, jnp.int32) for a in (slot, start, lens)), 32)
        assert goes_on.tolist() == [bool(e) for e in expect], (slot, start, lens)
        assert handed_on.tolist() == [bool(e) for e in expect[1:] + [0]]


# ---------------------------------------------------------------- (b) steps of the twin that hold a run


def _step(params, cache, ids, rows, decode=(), tables=TABLES):
    """One step through the twin: ``rows`` (sequence, first position, tokens)
    are the rows of a prefill group ``CHUNK`` wide, ``decode`` (sequence,
    position) the rows of a group of one token before it.  -> (per row its
    tokens' logits, the cache)."""
    groups = ((len(decode), 1), ) * bool(decode) + ((len(rows), CHUNK), )
    rect = np.zeros((len(rows), CHUNK), np.int32)
    for j, (r, at, n) in enumerate(rows):
        rect[j, :n] = ids[r][at:at + n]
    toks = np.concatenate([np.asarray([ids[r][at] for r, at in decode], np.int32), rect.reshape(-1)])
    order = [r for r, _ in decode] + [r for r, _, _ in rows]
    tables = np.where(np.asarray(order)[:, None] >= 0, tables[np.maximum(order, 0)], 0)
    with jax.default_matmul_precision("highest"):
        logits, cache = _APPLY["reference"](
            params, cache, jnp.asarray(toks if decode else rect),
            jnp.asarray([at for _, at in decode] + [at for _, at, _ in rows], jnp.int32), jnp.asarray(tables),
            jnp.asarray([1] * len(decode) + [n for _, _, n in rows], jnp.int32), groups=groups if decode else None)
    logits = np.asarray(logits).reshape(-1, logits.shape[-1])
    at_row = len(decode) + CHUNK * np.arange(len(rows))
    return [logits[j] for j in range(len(decode))] + [logits[t0:t0 + n] for t0, (_, _, n) in zip(at_row, rows)], cache


#: per case: the steps fed a chunk a row before the step in question, that step's rows, its decode rows
RUN_STEPS = {
    "two_prompts_two_rows_each": ([[(0, 0, 32), (1, 0, 32)]], [(0, 32, 32), (0, 64, 32), (1, 32, 32), (1, 64, 32)], ()),
    "a_fresh_row_first": ([], [(0, 0, 32), (0, 32, 32), (0, 64, 32), (0, 96, 32)], ()),
    "a_run_that_ends_inside_its_last_chunk": ([[(0, 0, 32)]], [(0, 32, 32), (0, 64, 32), (0, 96, 13), (-1, 0, 0)], ()),
    "a_run_beside_a_prompts_one_row_and_two_decode_rows":
    ([[(0, 0, 32), (1, 0, 32), (2, 0, 32)], [(1, 32, 8), (2, 32, 20)]],
     [(0, 32, 32), (0, 64, 32), (0, 96, 32)], ((1, 40), (2, 52))),
}


@pytest.mark.parametrize("case", sorted(RUN_STEPS))
def test_a_step_that_holds_a_run_gives_the_reference_and_what_a_chunk_a_step_leaves(params, ids, want, case):
    """The logits of every token the step feeds against the plain reference's
    full forward, and the cache it leaves (states, convolution tails, pages)
    against the same chunks fed a step each; every slot held something
    before, which a fresh row must not see and a continuing row must not
    start from."""
    before, rows, decode = RUN_STEPS[case]
    cache = init_cache(CFG, KV, jnp.float32, SLOTS, CHUNK)
    cache = {**cache, "kda": cache["kda"] + 0.5, "conv": cache["conv"] - 0.25}
    for step in before:
        _, cache = _step(params, cache, ids, step)
    got, after = _step(params, cache, ids, rows, decode)
    for (r, at, n), g in zip([(r, at, 1) for r, at in decode] + list(rows), got):
        if n:
            np.testing.assert_allclose(g.reshape(n, -1), want[r][at:at + n], atol=TOL)
    a_step_each = cache
    for row in [(r, at, 1) for r, at in decode] + [row for row in rows if row[2]]:
        _, a_step_each = _step(params, a_step_each, ids, [row])
    held = sorted({int(TABLES[r][-1]) for r, _, n in rows if n} | {int(TABLES[r][-1]) for r, _ in decode})
    for name in ("kda", "conv"):
        np.testing.assert_allclose(np.asarray(after[name])[:, held], np.asarray(a_step_each[name])[:, held], atol=2e-5)
        others = [s for s in range(1, SLOTS) if s not in held]
        np.testing.assert_array_equal(np.asarray(after[name])[:, others], np.asarray(cache[name])[:, others])
    np.testing.assert_allclose(np.asarray(after["pages"])[:, 1:], np.asarray(a_step_each["pages"])[:, 1:], atol=2e-5)
    assert np.abs(np.asarray(after["kda"])[:, held] - np.asarray(cache["kda"])[:, held]).max() > 1e-3


def test_rows_that_each_start_from_the_slot_are_far_from_the_reference(params, ids, want):
    """The guard of the guard: the same four rows with the second row's slot
    column changed, so that no row continues it: its logits are far off."""
    cache = init_cache(CFG, KV, jnp.float32, SLOTS, CHUNK)
    rows = [(0, 0, 32), (0, 32, 32)]
    got, _ = _step(params, cache, ids, rows)
    np.testing.assert_allclose(got[1], want[0][32:64], atol=TOL)
    apart = np.stack([TABLES[0], np.concatenate([TABLES[0][:-1], [5]])])      # the same pages, another slot
    got, _ = _step(params, cache, ids[[0, 0]], [(0, 0, 32), (1, 32, 32)], tables=apart)
    assert np.abs(got[1] - want[0][32:64]).max() > 100 * TOL


# ---------------------------------------------------------------- (a) the engine, with runs and a chunk a step

LONG_KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=24)


def _serve(params, prompt, run_rows, new=16):
    """The prompt through an engine whose steps hold the decode bucket and a
    rung of four chunks (the cell's budget at the small size): (what the slot
    and the pages hold when the prompt is in, the greedy tokens, the steps'
    records)."""
    sched = SchedulerConfig(token_budget=8 + 4 * CHUNK, max_seqs=8, prefill_chunk=CHUNK, decode_bucket=8)
    eng = InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(
        kv=LONG_KV, scheduler=sched, max_new_tokens=new, decode_steps_per_dispatch=4, enable_prefix_cache=False,
        kv_dtype=jnp.float32))
    assert eng.scheduler.run_rows == 4 and eng.kv.geometry.chunk_runs
    eng.scheduler.run_rows = run_rows
    with jax.default_matmul_precision("highest"):
        eng.put([7], [prompt])
        seq = eng.state.seqs[7]
        while seq.in_prefill and not seq.in_decode:
            eng.step()
        slot, pages = seq.slot, list(seq.pages)
        held = {"kda": np.asarray(eng.cache["kda"])[:, slot], "conv": np.asarray(eng.cache["conv"])[:, slot],
                "pages": np.asarray(eng.cache["pages"])[:, pages]}
        while not seq.done:
            eng.step()
    return held, list(seq.generated), [s.to_row() for s in eng.anatomy.steps]


def test_a_prompt_served_with_runs_leaves_what_a_chunk_a_step_leaves_and_samples_the_same_tokens(params):
    """A prompt of 9 chunks and 11 tokens: with runs three steps of four rows
    (the last run ends inside its chunk), with ``run_rows`` 1 ten steps; the
    slot's states and convolution tails and the pages agree to the chunked
    form's rounding, and so do 16 greedy tokens."""
    prompt = np.random.default_rng(11).integers(1, CFG.vocab_size, 9 * CHUNK + 11).tolist()
    (held, tokens, rows), (held_1, tokens_1, rows_1) = _serve(params, prompt, 4), _serve(params, prompt, 1)
    prefill, prefill_1 = ([r for r in some if r["rows_prefill"]] for some in (rows, rows_1))
    assert [r["rows_prefill"] for r in prefill] == [4, 4, 2] and all(r["seqs_prefill"] == 1 for r in prefill)
    assert [r["rows_prefill"] for r in prefill_1] == [1] * 10
    assert {r["key"] for r in prefill} == {"step:b8:c1:b4:c32"}
    assert {r["key"] for r in prefill_1} == {"step:b8:c1:b1:c32"}
    for name in held:
        assert np.abs(held[name]).max() > 0.1
        np.testing.assert_allclose(held[name], held_1[name], atol=2e-5, rtol=1e-5)
    assert len(tokens) == 16 and tokens == tokens_1


# ---------------------------------------------------------------- (c) which geometries take a run

CELL = SchedulerConfig(token_budget=544, max_seqs=32, prefill_chunk=128, decode_bucket=32)
TWINS = {"solar_open2": (CFG, True), "phi4flash": (FAMILIES["phi4flash"][1], False),
         "granitehybrid": (FAMILIES["granitehybrid"][1], False), "minicpm_sala": (SALA_CFG, False)}


@pytest.mark.parametrize("twin", sorted(TWINS))
def test_the_scheduler_plans_a_run_where_the_twin_hands_the_state_on(twin):
    """Under the cell's scheduler (the decode bucket and four chunks of 128) a
    lone prompt of 1,000 tokens beside three decoding rows takes the rung of
    four under Solar-Open2's geometry and a chunk under the three others',
    whose twins start every row from its slot."""
    cfg, runs = TWINS[twin]
    geometry = cache_geometry(cfg, PAGE)
    assert geometry.state_slots and geometry.chunk_runs == runs
    host = _Host(geometry=geometry, sched=CELL)
    for uid in (1, 2, 3):
        host.decoding(uid, 40)
    host.prompt(0, 1000)
    assert host.fed() == {0: 512 if runs else 128}
    host.scheduler.run_rows = 1
    assert host.fed() == {0: 128}


def test_a_budget_of_one_chunk_beside_the_decode_bucket_holds_no_run():
    """MiniCPM-SALA's cell: ``token_budget`` 288 is the decode bucket of 32 and
    one chunk of 256, so beside a decoding row no run forms whatever the
    geometry says."""
    sched = SchedulerConfig(token_budget=288, max_seqs=32, prefill_chunk=256, decode_bucket=32)
    host = _Host(geometry=cache_geometry(CFG, PAGE), sched=sched)
    assert host.kv.geometry.chunk_runs
    host.decoding(1, 40)
    host.prompt(0, 5000)
    assert host.fed() == {0: 256}


@pytest.mark.parametrize("free, fed", [(64, 512), (3 + 8 + 17, 384), (3 + 8 + 8, 256), (3 + 8 + 7, 128), (3 + 8, 128),
                                       (3 + 4, 128)])
def test_with_no_prefix_cache_a_run_takes_the_free_pages_and_preempts_nobody(free, fed):
    """The first geometry that takes runs with the prefix cache off (a slot's
    state cannot be shared): ``_run_ahead`` reckons with the free pages alone,
    a run shrinks by whole rows to what they cover, and whom the pressure
    manager preempts and who decodes are as without runs."""
    outcomes = []
    for run_rows in (4, 1):
        host = _Host(geometry=cache_geometry(CFG, PAGE), num_pages=1 + 1024, run_rows=run_rows)
        assert host.kv.prefix_cache is None
        for uid in (1, 2, 3):
            host.decoding(uid, 16 * uid + 1)                 # the next token of each opens a page
        host.prompt(0, 1000, seen=256, first=5000)
        held = host.kv.allocator.allocate(host.kv.allocator.free_pages - free)    # nobody's the scheduler sees
        evicted, plan = KVPressureManager(host).resolve()
        assert host.single_step_page_demand(plan) <= host.kv.allocator.free_pages and held
        host.step(plan)                                      # and so it packs
        outcomes.append(([s.uid for s in evicted], sorted(s.uid for s in plan.decode)))
        assert {s.uid: n for s, n in plan.prefill} == {0: fed if run_rows == 4 else 128}
    assert outcomes[0] == outcomes[1]
