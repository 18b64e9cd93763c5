"""A run: consecutive chunks of one prompt as rows of one step.  The host's
half, without a device: what ``SplitFuseScheduler.plan`` hands out (every
prefilling sequence its chunk as before, then the spare rows of the rung of
four in scheduling order, as far as the geometry, the budget and the pages
go), how ``StateManager.pack_groups`` lays a run out, and that under KV
pressure a run shrinks rather than preempt anybody.  The twins' half (the same
tokens and logits with runs and without) is in ``test_row_groups_engine.py``,
``test_xing4_twin.py`` and ``test_kimi_vl_serving.py``."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.geometry import LinearGeometry, RingSummaryGeometry, SlotPagesGeometry
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, StateManager
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, SplitFuseScheduler
from deepspeed_tpu.models.xing4_cache import LatentPagesGeometry
from deepspeed_tpu.serving.kv_pressure import KVPressureManager

PAGE, CHUNK = 16, 128
SCHED = SchedulerConfig(token_budget=2048, max_seqs=16, prefill_chunk=CHUNK, decode_bucket=16)


class _Host:
    """What ``KVPressureManager`` and the tests need of an engine: the
    scheduler, the state, the pages, and a step that feeds what was planned."""

    def __init__(self, geometry=None, num_pages=4096, sched=SCHED, run_rows=4, prefix_cache=False):
        geometry = geometry or LinearGeometry(PAGE)
        self.kv = BlockedKVCache(num_pages, PAGE, 1024, enable_prefix_cache=prefix_cache, geometry=geometry,
                                 state_slots=sched.max_seqs + 1)
        self.state = StateManager(self.kv, max_batch=sched.max_seqs)
        self.scheduler = SplitFuseScheduler(sched)
        self.scheduler.run_rows = run_rows

    def prompt(self, uid, length, seen=0, first=0):
        seq = self.state.get_or_create(uid, list(range(first, first + length)))
        if seen:
            self.kv.ensure_capacity(seq, seen)
            seq.seen_tokens = seen
        return seq

    def decoding(self, uid, length):
        seq = self.prompt(uid, length, seen=length - 1)
        seq.generated = [seq.tokens[-1]]
        return seq

    def plan(self):
        return self.scheduler.plan(self.state)

    def fed(self):
        return {s.uid: n for s, n in self.plan().prefill}

    def single_step_page_demand(self, plan):
        return (sum(self.kv.pages_needed(s, 1) for s in plan.decode) +
                sum(self.kv.pages_needed(s, n) for s, n in plan.prefill))

    def preempt(self, uid):
        return self.state.preempt(uid)

    def step(self, plan):
        """The host's side of a step: pack (which allocates), then fold."""
        decode = [(s, 1) for s in plan.decode]
        rows = self.scheduler.run_rows if any(n > CHUNK for _, n in plan.prefill) else max(len(plan.prefill), 1)
        rb = self.state.pack_groups([(decode, SCHED.max_seqs, 1), (list(plan.prefill), rows, CHUNK)])
        for seq, n in decode + list(plan.prefill):
            seq.seen_tokens += n
            self.state.note_progress(seq)
            if not seq.in_prefill:
                seq.tokens.append(7)
                seq.generated.append(7)
        return rb


def _populate(host, case):
    for uid, (length, seen) in enumerate(case):
        host.prompt(uid, length, seen)


#: prompts as (length, tokens seen), in arrival order -> tokens a sequence is fed
PLANS = {
    "one_long_prompt_takes_the_rung_of_four": ([(1000, 0)], {0: 512}),
    "a_run_ends_with_its_prompt": ([(1000, 700)], {0: 300}),
    "two_prompts_the_spare_rows_go_to_the_first": ([(1000, 0), (1000, 128)], {0: 384, 1: 128}),
    "what_the_first_leaves_goes_to_the_next": ([(200, 0), (1000, 0), (900, 0)], {0: 200, 1: 128, 2: 128}),
    "two_prompts_the_first_nearly_done": ([(1000, 872), (1000, 0)], {0: 128, 1: 384}),
    "a_last_short_chunk_is_a_row_and_runs_nowhere": ([(1000, 900), (1000, 0)], {0: 100, 1: 384}),
    "four_prompts_are_todays_plan": ([(1000, 0)] * 4, {0: 128, 1: 128, 2: 128, 3: 128}),
    "five_prompts_are_todays_plan": ([(1000, 0)] * 5, {u: 128 for u in range(5)}),
    "a_prompts_last_token_alone": ([(257, 256)], {0: 1}),
}


@pytest.mark.parametrize("case", sorted(PLANS))
def test_plan_gives_every_prompt_its_chunk_and_the_spare_rows_in_order(case):
    prompts, want = PLANS[case]
    host = _Host()
    _populate(host, prompts)
    assert host.fed() == want
    assert sum(-(-n // CHUNK) for n in want.values()) <= max(4, len(prompts))
    # nobody gets less than without runs, and without them the plan is the old one
    host.scheduler.run_rows = 1
    assert host.fed() == {uid: min(n, CHUNK) for uid, n in want.items()}


def test_spare_rows_follow_the_scheduling_order_not_the_arrival_order():
    host = _Host()
    _populate(host, [(1000, 0), (1000, 0)])
    host.scheduler.order_key = lambda seq: -seq.uid
    plan = host.plan()
    assert [(s.uid, n) for s, n in plan.prefill] == [(1, 384), (0, 128)]


def test_the_token_budget_is_charged_for_a_run():
    host = _Host(sched=SchedulerConfig(token_budget=300, max_seqs=16, prefill_chunk=CHUNK, decode_bucket=16))
    host.decoding(9, 40)
    host.prompt(0, 1000)
    plan = host.plan()
    assert [(s.uid, n) for s, n in plan.prefill] == [(0, 300 - 16)] and plan.planned_tokens == 1 + 284


#: geometry -> tokens fed to a prompt of 1,000 from position 0, and from 128
GEOMETRIES = {
    "linear": (lambda: LinearGeometry(PAGE), 512, 512),
    "latent_pages": (lambda: LatentPagesGeometry(PAGE), 512, 512),
    # a row starts from the slot's state: one chunk a step
    "slot_pages": (lambda: SlotPagesGeometry(PAGE, window=64), 128, 128),
    # inside one window of 256: from its start two chunks, from its middle the one that ends it
    "ring_summary": (lambda: RingSummaryGeometry(PAGE, 256), 256, 128),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_the_geometry_says_whether_and_how_far_a_prompt_runs_ahead(name):
    make, from_zero, from_128 = GEOMETRIES[name]
    for seen, want in ((0, from_zero), (128, from_128)):
        host = _Host(geometry=make())
        host.prompt(0, 1000, seen)
        assert host.fed() == {0: want}
    assert make().chunk_runs == (from_zero > CHUNK)


def test_an_engine_without_row_groups_keeps_one_chunk():
    host = _Host(run_rows=1)
    host.prompt(0, 1000)
    assert host.fed() == {0: CHUNK}


def test_pack_groups_lays_a_run_out_as_rows_of_one_block_table_row():
    host = _Host()
    a, b = host.decoding(1, 50), host.decoding(2, 20)
    seq = host.prompt(0, 1000, seen=40)                       # from inside a page: rows share pages at their edges
    seq.mm_index = np.full((1000, ), -1, np.int32)
    seq.mm_index[160:300] = np.arange(140)                    # an image's rows over the first row's end and the second's
    other = host.prompt(3, 500)
    rb = host.state.pack_groups([([(a, 1), (b, 1)], 4, 1), ([(seq, 300), (other, CHUNK)], 4, CHUNK)], mm=True)
    assert rb.uids == [1, 2, -1, -1, 0, 0, 0, 3]
    assert rb.start_pos.tolist() == [49, 19, 0, 0, 40, 168, 296, 0]
    assert rb.chunk_lens.tolist() == [1, 1, 0, 0, 128, 128, 44, 128]
    assert len(seq.pages) == -(-340 // PAGE)                  # capacity once, for the run
    for row in (4, 5, 6):
        assert rb.block_tables[row, :len(seq.pages)].tolist() == seq.pages
        assert not rb.block_tables[row, len(seq.pages):].any()
    flat = rb.tokens[4:].reshape(4, CHUNK)                    # the prefill group's rows
    assert flat[:3].reshape(-1)[:300].tolist() == list(range(40, 340)) and not flat[2, 44:].any()
    assert flat[3].tolist() == list(range(CHUNK))
    mm = rb.mm_index[4:].reshape(4, CHUNK)
    assert mm[:3].reshape(-1)[:300].tolist() == seq.mm_index[40:340].tolist()
    assert (mm[0, 120:] >= 0).all() and (mm[1] >= 0).all() and mm[2, :4].tolist() == [136, 137, 138, 139]
    assert (mm[2, 4:] == -1).all() and (mm[3] == -1).all() and (rb.mm_index[:4] == -1).all()


def test_a_plan_without_a_run_packs_as_it_did():
    host = _Host()
    seq, dec = host.prompt(0, 1000, seen=256), host.decoding(1, 30)
    rb = host.state.pack_groups([([(dec, 1)], 4, 1), ([(seq, CHUNK)], 1, CHUNK)])
    assert rb.uids == [1, -1, -1, -1, 0] and rb.start_pos.tolist() == [29, 0, 0, 0, 256]
    assert rb.chunk_lens.tolist() == [1, 0, 0, 0, CHUNK] and rb.tokens[4:].tolist() == list(range(256, 384))
    assert rb.block_tables[4, :len(seq.pages)].tolist() == seq.pages and rb.mm_index is None


# ------------------------------------------------------------- under KV pressure


def _pressed(run_rows, free, cached=0):
    """Three requests decode, a prompt of 1,000 has two chunks in; the arena
    has ``free`` pages free and ``cached`` cold pages in the prefix cache."""
    host = _Host(num_pages=1 + 1024, run_rows=run_rows, prefix_cache=True)
    for uid in (1, 2, 3):
        host.decoding(uid, 16 * uid + 1)                     # the next token of each opens a page
    host.prompt(0, 1000, seen=256, first=5000)
    if cached:                                               # a finished request's pages, nobody's but the cache's
        gone = host.prompt(8, cached * PAGE + 1, seen=cached * PAGE, first=9000)
        host.state.note_progress(gone)
        host.state.flush(8)
    spare = host.kv.allocator.allocate(host.kv.allocator.free_pages - free)   # held by nobody the scheduler sees
    assert host.kv.allocator.free_pages == free and host.kv.prefix_cache.cached_pages >= cached
    return host, spare


@pytest.mark.parametrize("free, cached, fed", [
    (64, 0, 512),      # room for everything: 3 pages for the decode rows, 8 a chunk
    (3 + 8 + 17, 0, 384), (3 + 8 + 8, 0, 256), (3 + 8 + 7, 0, 128),   # the run takes the whole rows the pages cover
    (3 + 8, 24, 512), (3 + 8, 9, 256),                       # the prefix cache's cold pages count: evicted on demand
    (3 + 8, 0, 128),   # today's plan just fits: no run
    (3 + 4, 0, 128),   # today's plan does not fit: today's shortfall, today's victim
])
def test_a_run_shrinks_to_the_pages_there_are_and_preempts_nobody(free, cached, fed):
    outcomes = []
    for run_rows in (4, 1):
        host, _ = _pressed(run_rows, free, cached)
        evicted, plan = KVPressureManager(host).resolve()
        assert host.single_step_page_demand(plan) <= host.kv.allocator.free_pages
        host.step(plan)                                      # and so it packs
        outcomes.append(([s.uid for s in evicted], sorted(s.uid for s in plan.decode)))
        assert {s.uid: n for s, n in plan.prefill} == {0: fed if run_rows == 4 else CHUNK}
    assert outcomes[0] == outcomes[1]                        # whom it preempted, who decodes: as without runs


def test_the_prefix_cache_holds_the_same_pages_after_a_prompt_fed_in_runs():
    published = []
    for run_rows in (4, 1):
        host = _Host(run_rows=run_rows, prefix_cache=True)
        host.prompt(0, 1000)
        steps = 0
        while not host.state.seqs[0].generated:
            host.step(host.plan())
            steps += 1
        assert steps == (2 if run_rows == 4 else 8)
        seq = host.state.seqs[0]
        assert seq.pc_pages == 1000 // PAGE == host.kv.prefix_cache.cached_pages
        published.append((seq.pc_hash, host.kv.prefix_cache.held_digests(), list(seq.pages)))
        # and a second request with the same prompt finds them
        again = host.prompt(1, 1000)
        assert again.seen_tokens == (999 // PAGE) * PAGE and again.pages == seq.pages[:999 // PAGE]
    assert published[0] == published[1]
