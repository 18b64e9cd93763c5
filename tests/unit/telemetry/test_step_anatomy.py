"""Step-anatomy tests (telemetry/step_anatomy.py + the engine/serving
wiring + scripts/step_anatomy.py): the decomposition tiles wall time by
construction, host gaps measure inter-step loop tax and exclude idle,
the compile tracker tags warm-up vs steady-state recompiles (the AOT
regression guard), the disabled path allocates nothing, the report CLI
exits 1 on a planted tiling mismatch and prints byte-identical --json,
the ``host_gap``/``compile_wait`` phases fold in
``trace_report.py``/``why_slow.py`` instead of surfacing as
``unknown:<p>``, the counts of a step against numbers worked by hand,
``admit`` and ``deliver`` as segments of both ticks, and the profiler
ranges an ``annotate`` factory sees."""

import importlib.util
import json
import os
import subprocess
import sys
import tracemalloc

import pytest

from deepspeed_tpu.serving.clock import VirtualClock
from deepspeed_tpu.telemetry import (NULL_ANATOMY, FlightRecorder,
                                     MetricsRegistry, StepAnatomy, Tracer)
from deepspeed_tpu.telemetry.step_anatomy import HOST_SEGMENTS

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
SA_CLI = os.path.join(REPO_ROOT, "scripts", "step_anatomy.py")


def _load_script(name):
    path = os.path.join(REPO_ROOT, "scripts", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiles(row, tol=1e-9):
    return abs(row["wall_s"] - (row["host_gap_s"]
                                + sum(row["segments"].values())
                                + row["device_s"])) <= tol


# ------------------------------------------------------------- recorder


def test_segments_device_and_gap_tile_wall():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin()
    clock.advance(0.2)
    anat.mark("schedule")
    clock.advance(0.1)
    anat.mark("dispatch")
    clock.advance(0.5)
    anat.device_mark()
    clock.advance(0.05)
    anat.mark("sample_accept")
    anat.note_program("step:b4:c1", "decode")
    clock.advance(0.03)            # unmarked residual -> bookkeeping
    rec = anat.step_end()
    assert rec is not None
    row = rec.to_row()
    assert row["segments"]["schedule"] == pytest.approx(0.2)
    assert row["segments"]["dispatch"] == pytest.approx(0.1)
    assert row["device_s"] == pytest.approx(0.5)
    assert row["segments"]["sample_accept"] == pytest.approx(0.05)
    assert row["segments"]["bookkeeping"] == pytest.approx(0.03)
    assert row["host_gap_s"] == 0.0            # first step: no predecessor
    assert _tiles(row) and row["wall_s"] == pytest.approx(0.88)
    assert row["key"] == "step:b4:c1" and row["path"] == "decode"

    # second step: the inter-step window becomes its host gap
    clock.advance(0.3)
    anat.step_begin()
    clock.advance(0.4)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    rec2 = anat.step_end()
    row2 = rec2.to_row()
    assert row2["host_gap_s"] == pytest.approx(0.3)
    assert _tiles(row2) and row2["wall_s"] == pytest.approx(0.7)
    assert anat.host_gap_fraction() == pytest.approx(0.3 / (0.88 + 0.7))


def test_idle_excluded_and_flagged():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin()
    clock.advance(0.1)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    anat.step_end()
    clock.advance(5.0)             # arrival gap: the loop idled
    anat.note_idle()
    clock.advance(0.2)             # real pre-step host work after the idle
    anat.step_begin()
    clock.advance(0.1)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    row = anat.step_end().to_row()
    # the 5s idle is excluded; note_idle also reset the gap origin, so the
    # 0.2s of post-idle host work is excluded too (flagged instead)
    assert row["host_gap_s"] == 0.0 and row["after_idle"] is True
    assert _tiles(row)

    # mid-step idle (submit backoff): cursor snaps, no segment absorbs it
    anat.step_begin()
    clock.advance(1.0)
    anat.note_idle()
    clock.advance(0.3)
    anat.mark("schedule")
    clock.advance(0.1)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    row = anat.step_end().to_row()
    assert row["segments"]["schedule"] == pytest.approx(0.3)
    assert _tiles(row)


def test_empty_step_discarded_folds_into_next_gap():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin()
    clock.advance(0.1)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    anat.step_end()
    # a planned-but-empty step (no dispatch): discarded, not recorded
    anat.step_begin()
    clock.advance(0.25)
    assert anat.step_end() is None
    assert anat.total_steps == 1
    # its window lands in the NEXT real step's host gap
    anat.step_begin()
    clock.advance(0.05)
    anat.device_mark()
    anat.note_program("step:b4:c1", "decode")
    row = anat.step_end().to_row()
    assert row["host_gap_s"] == pytest.approx(0.25)
    assert _tiles(row)


def test_step_begin_idempotent_shared_between_frontend_and_engine():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin()              # the serving frontend opens the window
    clock.advance(0.2)
    anat.mark("schedule")
    anat.step_begin()              # the engine's own call must no-op
    clock.advance(0.1)
    anat.device_mark()
    anat.note_program("step:b8:c32", "prefill")
    row = anat.step_end().to_row()
    assert row["segments"]["schedule"] == pytest.approx(0.2)
    assert row["device_s"] == pytest.approx(0.1)
    assert _tiles(row)


def test_charge_last_step_virtual_clock_contract():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    anat.step_end()                # virtual: zero-width so far
    clock.advance(1.5)             # clock.on_step charged the cost
    rec = anat.charge_last_step(1.5)
    row = rec.to_row()
    assert row["device_s"] == pytest.approx(1.5)
    assert _tiles(row) and row["wall_s"] == pytest.approx(1.5)
    # the gap origin re-anchored at the charged clock: the next step
    # starts gap-free
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    anat.step_end()
    clock.advance(1.0)
    row2 = anat.charge_last_step(1.0).to_row()
    assert row2["host_gap_s"] == 0.0 and _tiles(row2)
    with pytest.raises(ValueError):
        anat.charge_last_step(-1.0)


def test_retention_bound_and_lifetime_totals():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock, max_steps=4)
    for _ in range(7):
        anat.step_begin()
        clock.advance(1.0)
        anat.device_mark()
        anat.note_program("step:b4:c1", "decode")
        anat.step_end()
    assert len(anat.steps) == 4 and anat.dropped_steps == 3
    assert anat.total_steps == 7
    assert anat.total_wall_s == pytest.approx(7.0)   # totals survive eviction
    assert anat.summary()["dropped_steps"] == 3


def test_compile_tracker_warmup_vs_steady_and_reset():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.note_compile("step:b4:c1")
    anat.note_compile("step:b8:c1")
    assert anat.steady_state_recompiles == 0
    anat.mark_steady()
    anat.reset_steps()             # the bench pattern: warm, seal, reset
    assert len(anat.compiles) == 2  # compile log survives the reset
    anat.step_begin()
    anat.note_compile("step:b8:c32")
    anat.note_program("step:b8:c32", "mixed")
    anat.step_end()
    assert anat.steady_state_recompiles == 1
    rows = [c.to_row() for c in anat.compiles]
    assert [c["steady"] for c in rows] == [False, False, True]
    assert rows[2]["step_index"] == 0  # the measured step that paid it


def test_null_anatomy_allocates_nothing():
    row, uids = {"uid": 0, "ttft_s": 1.0}, (0, )

    def loop(n):
        for _ in range(n):
            NULL_ANATOMY.step_begin()
            NULL_ANATOMY.mark("schedule")
            NULL_ANATOMY.note_program("step:b4:c1", "decode")
            NULL_ANATOMY.note_prefill_uids(uids)
            NULL_ANATOMY.device_mark()
            NULL_ANATOMY.note_compile("k")
            NULL_ANATOMY.step_end()
            NULL_ANATOMY.charge_last_step(1.0)
            NULL_ANATOMY.note_first_token(row)

    loop(10)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        loop(1000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    pkg = os.path.join("deepspeed_tpu", "telemetry")
    allocs = [d for d in after.compare_to(before, "lineno")
              if d.size_diff > 0 and any(pkg in (f.filename or "")
                                         for f in d.traceback)]
    assert sum(d.size_diff for d in allocs) < 8192, allocs
    assert NULL_ANATOMY.to_doc()["steps"] == [] and NULL_ANATOMY.to_doc()["first_tokens"] == []
    assert NULL_ANATOMY.first_tokens == ()


# ------------------------------------------------- report CLI + sabotage


def _sample_doc():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.note_compile("step:b4:c1")
    anat.mark_steady()
    for i in range(5):
        anat.step_begin()
        clock.advance(0.01 * (i + 1))
        anat.mark("schedule")
        clock.advance(0.02)
        anat.mark("dispatch")
        clock.advance(0.5)
        anat.device_mark()
        anat.note_program("step:b4:c1" if i % 2 else "step:b4:c32",
                          "decode" if i % 2 else "prefill")
        anat.step_end()
        clock.advance(0.05)        # inter-step loop tax -> next host gap
    return anat.to_doc()


def test_report_folds_and_verifies():
    sa = _load_script("step_anatomy")
    doc = _sample_doc()
    report = sa.fold(doc)
    assert report["verification"]["mismatches"] == 0
    assert report["n_steps"] == 5
    assert set(report["by_shape"]) == {"step:b4:c1", "step:b4:c32"}
    for agg in report["by_shape"].values():
        assert 0.0 <= agg["host_gap_fraction"] <= 1.0
    assert report["compiles"] == {"total": 1, "warmup": 1, "steady_state": 0,
                                  "steady_keys": []}


def test_cli_byte_identical_and_sabotage_exit1(tmp_path):
    doc = _sample_doc()
    p = tmp_path / "anat.json"
    p.write_text(json.dumps(doc))
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, SA_CLI, str(p), "--json"],
                           capture_output=True)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout)
    assert outs[0] == outs[1]      # byte-identical --json

    # sabotage 1: a planted tiling mismatch must exit 1
    bad = json.loads(json.dumps(doc))
    bad["steps"][2]["wall_s"] += 0.5
    pb = tmp_path / "bad.json"
    pb.write_text(json.dumps(bad))
    r = subprocess.run([sys.executable, SA_CLI, str(pb), "--json"],
                       capture_output=True)
    assert r.returncode == 1 and b"ANATOMY MISMATCH" in r.stderr

    # sabotage 2: a summary that denies a steady recompile the log records
    bad2 = json.loads(json.dumps(doc))
    bad2["compiles"][0]["steady"] = True
    pb2 = tmp_path / "bad2.json"
    pb2.write_text(json.dumps(bad2))
    r = subprocess.run([sys.executable, SA_CLI, str(pb2), "--json"],
                       capture_output=True)
    assert r.returncode == 1


# ------------------------------- anatomy phases in the report tooling


def _ev(name, ts, dur, args):
    return {"ph": "X", "pid": 1, "tid": 1, "name": name,
            "ts": ts * 1e6, "dur": dur * 1e6, "args": args}


def _request_trace_with_anatomy_phases():
    root_args = {"trace_id": 1, "span_id": 1, "state": "done", "ttft": 4.0,
                 "tpot": 1.0, "n_tokens": 7, "failovers": 0, "tenant": "t"}
    return {"traceEvents": [
        _ev("request", 0.0, 10.0, root_args),
        _ev("phase/pending", 0.0, 1.0,
            {"trace_id": 1, "span_id": 2, "parent_id": 1}),
        _ev("phase/prefill", 1.0, 2.0,
            {"trace_id": 1, "span_id": 3, "parent_id": 1}),
        _ev("phase/host_gap", 3.0, 0.5,
            {"trace_id": 1, "span_id": 4, "parent_id": 1}),
        _ev("phase/compile_wait", 3.5, 0.5,
            {"trace_id": 1, "span_id": 5, "parent_id": 1}),
        _ev("phase/decode", 4.0, 6.0,
            {"trace_id": 1, "span_id": 6, "parent_id": 1}),
    ], "otherData": {}}


def test_why_slow_knows_anatomy_phases():
    ws = _load_script("why_slow")
    report = ws.fold(_request_trace_with_anatomy_phases(), tol=1e-6)
    assert report["verification"]["mismatches"] == 0
    req = report["requests"][0]
    assert not any(c.startswith("unknown:") for c in req["causes"])
    assert req["causes"]["host_gap"] == pytest.approx(0.5)
    assert req["causes"]["compile_wait"] == pytest.approx(0.5)
    # both are named SLOWDOWN causes for the tail receipt
    assert "host_gap" in ws.SLOWDOWN_CAUSES
    assert "compile_wait" in ws.SLOWDOWN_CAUSES


def test_trace_report_knows_anatomy_phases():
    tr = _load_script("trace_report")
    report = tr.fold(_request_trace_with_anatomy_phases(), tol=1e-6)
    assert report["verification"]["mismatches"] == 0
    cp = report["critical_path"]
    assert cp["host_gap"]["total_s"] == pytest.approx(0.5)
    assert cp["compile_wait"]["total_s"] == pytest.approx(0.5)


class _FakeRange:
    """What ``utils/nvtx.py::profiler_range`` gives, recorded: open and
    close order, and the metadata set before the close."""

    def __init__(self, log, name):
        self.log, self.name, self.meta = log, name, {}

    def __enter__(self):
        self.log.append(("open", self.name, None))
        return self

    def __exit__(self, *exc):
        self.log.append(("close", self.name, dict(self.meta)))
        return False

    def set_metadata(self, **kw):
        self.meta.update(kw)


def test_annotate_factory_sees_step_and_marks_nested_with_counts():
    """With an ``annotate`` factory the recorder writes one ``ds.step``
    range a step, opened at step_begin and closed at step_end with the
    index, key and counts as metadata, and inside it one instant
    ``ds.mark.<segment>`` at every mark, in order (a reader rebuilds the
    segments as the intervals between marks).  A step that never
    dispatched closes its range without metadata."""
    clock = VirtualClock()
    log = []
    anat = StepAnatomy(clock=clock, annotate=lambda name: _FakeRange(log, name))
    anat.step_begin()
    clock.advance(0.2)
    anat.mark("admit")
    anat.mark("schedule")
    anat.note_program("multi:b4:k8", "multi_decode", rows_decode=3, tokens_real=24, slots=32)
    anat.note_counts(expert_rows=48, expert_rows_kernel=40)   # as the engine does: once the program is enqueued
    anat.mark("dispatch")
    clock.advance(0.8)
    anat.device_mark()
    anat.note_tokens(20, 4, expert_rows=2, expert_rows_kernel=2)
    anat.mark("sample_accept")
    anat.step_end()
    events = [(kind, name) for kind, name, _ in log]
    assert events == [
        ("open", "ds.step"),
        ("open", "ds.mark.admit"), ("close", "ds.mark.admit"),
        ("open", "ds.mark.schedule"), ("close", "ds.mark.schedule"),
        ("open", "ds.mark.dispatch"), ("close", "ds.mark.dispatch"),
        ("open", "ds.mark.device_wait"), ("close", "ds.mark.device_wait"),
        ("open", "ds.mark.sample_accept"), ("close", "ds.mark.sample_accept"),
        ("close", "ds.step")]
    assert log[-1][2] == {"index": 0, "key": "multi:b4:k8", "rows_decode": 3,
                          "rows_prefill": 0, "seqs_prefill": 0, "tokens_real": 24, "slots": 32,
                          "tokens_out": 20, "tokens_discarded": 4,
                          "expert_rows": 50, "expert_rows_kernel": 42,
                          "attn_rows_visible": 0, "attn_rows_walked": 0,
                          "ssm_rows": 0, "window_rows_visible": 0, "ssd_state_bytes": 0,
                          "mla_rows_read": 0, "mm_tokens": 0,
                          "sparse_decode_rows_read": 0, "lightning_state_bytes": 0,
                          "ring_rows_held": 0, "ring_rows_seen": 0, "full_rows_seen": 0,
                          "attn_decode_rows": 0, "attn_decode_rows_live": 0}
    # the counts ride the row and the per-program fold too
    row = anat.last_step.to_row()
    assert (row["tokens_real"], row["slots"], row["tokens_out"],
            row["tokens_discarded"]) == (24, 32, 20, 4)
    assert anat.by_shape()["multi:b4:k8"]["slots"] == 32
    # an empty step: a range, no metadata, no record
    del log[:]
    anat.step_begin()
    assert anat.step_end() is None
    assert log == [("open", "ds.step", None), ("close", "ds.step", {})]


def test_null_anatomy_never_calls_the_factory(tiny_serving):
    """The disabled path builds no range: an engine whose recorder was
    switched off serves with ``NULL_ANATOMY``, which has no factory to call
    (the tracemalloc test above pins that it allocates nothing either)."""
    eng = tiny_serving()
    assert eng.anatomy.enabled and eng.anatomy._annotate is not None    # the engine's own recorder draws ranges
    eng.set_anatomy(None)
    assert eng.anatomy is NULL_ANATOMY and not hasattr(NULL_ANATOMY, "_annotate")
    NULL_ANATOMY.step_begin(hold=True)
    NULL_ANATOMY.note_program("step:b4:c1", "decode", tokens_real=1, slots=4)
    NULL_ANATOMY.note_tokens(1, 0, real=1)
    assert NULL_ANATOMY.step_end(release=True) is None
    assert eng.generate([[1, 2, 3]], max_new_tokens=2) and NULL_ANATOMY.total_steps == 0


def test_held_window_closes_only_on_release():
    """The serial serving tick holds the window: the engine's own
    step_end no-ops, a clock charge lands on the OPEN step, and what the
    frontend marks afterwards (``deliver``) lies inside the step."""
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    anat.step_begin(hold=True)
    clock.advance(0.1)
    anat.mark("admit")
    anat.step_begin()                      # the engine's begin: no-op
    anat.note_program("step:b4:c1", "decode", rows_decode=1, tokens_real=1, slots=4)
    assert anat.step_end() is None         # the engine's end: held
    clock.advance(1.0)                     # clock.on_step charged the cost
    assert anat.charge_last_step(1.0).index == 0
    clock.advance(0.05)
    anat.mark("deliver")
    row = anat.step_end(release=True).to_row()
    assert row["segments"]["admit"] == pytest.approx(0.1)
    assert row["device_s"] == pytest.approx(1.0)
    assert row["segments"]["deliver"] == pytest.approx(0.05)
    assert _tiles(row) and row["wall_s"] == pytest.approx(1.15)
    assert row["end_ts"] == pytest.approx(1.15)


def test_flight_recorder_holds_no_copy_of_the_steps(tiny_serving):
    """One representation: the steps are tabled by ``to_doc()`` (and
    drawn in the profiler's trace); ``ServingEngine`` mirrors none of
    them onto the flight recorder or the tracer."""
    from deepspeed_tpu.serving import ServingEngine

    eng = tiny_serving()
    clock = VirtualClock()
    anat = eng.set_anatomy(StepAnatomy(clock=clock))
    tracer = Tracer(clock=clock)
    rec = FlightRecorder(clock=clock, max_per_track=8)
    serve = ServingEngine(eng, clock=clock, tracer=tracer, recorder=rec)
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": [1, 2, 3], "max_new_tokens": 3}])
    assert reqs[0].state.value == "done" and anat.total_steps > 0
    assert not [t for t in rec.summary()["tracks"] if t.startswith("anatomy/")]
    assert not [sp for sp in tracer.spans if sp.name.startswith("anatomy/")]
    assert not hasattr(anat, "emit_spans")
    assert anat.last_step.to_row()["key"].startswith("step:b")


# ----------------------------- serving-engine integration (tiny model)


def test_serving_anatomy_tiles_and_guards_recompiles(tiny_serving):
    from deepspeed_tpu.serving import (AdmissionConfig, ServingConfig,
                                       ServingEngine, VirtualClock)

    eng = tiny_serving()
    clock = VirtualClock()
    anat = eng.set_anatomy(StepAnatomy(clock=clock))
    eng.generate([[1, 2, 3]], max_new_tokens=2)       # warms b2 only
    warm_compiles = len(anat.compiles)
    assert warm_compiles >= 2 and anat.steady_state_recompiles == 0
    anat.mark_steady()
    anat.reset_steps()
    metrics = MetricsRegistry()
    recorder = FlightRecorder(clock=clock, max_per_track=64)
    serve = ServingEngine(eng, clock=clock,
                          config=ServingConfig(admission=AdmissionConfig(
                              max_queue_depth=8)),
                          metrics=metrics, recorder=recorder)
    reqs = serve.run([{"arrival_ts": 0.5 * i, "prompt": [1 + i, 2, 3, 4, 5],
                       "max_new_tokens": 4} for i in range(5)])
    assert all(r.state.value == "done" for r in reqs)

    doc = anat.to_doc()
    sa = _load_script("step_anatomy")
    report = sa.fold(doc)
    assert report["verification"]["mismatches"] == 0   # tiling holds live
    assert report["n_steps"] == anat.total_steps > 0
    # the 4-batch bucket was never warmed: its compile is a steady-state
    # recompile — counted on the recorder, the metrics, and per-step rows
    assert anat.steady_state_recompiles >= 1
    assert metrics.counter("engine/recompile_steady_state").value == \
        anat.steady_state_recompiles
    assert metrics.counter("engine/recompiles").value == \
        len(anat.compiles) - warm_compiles
    assert sum(r["compiles"] for r in doc["steps"]) >= 1
    # one representation: no copy of the steps on the flight recorder
    assert recorder.track("anatomy/serving") == []
    # kv gauges export
    serve.export_kv_gauges()
    assert 0.0 <= metrics.gauge("kv/page_occupancy").value <= 1.0
    occ = eng.kv.arena_stats()
    assert occ["in_use"] + occ["free"] == occ["usable"]


def test_engine_records_by_default_and_none_switches_it_off(tiny_serving):
    eng = tiny_serving()
    own = eng.anatomy
    assert isinstance(own, StepAnatomy) and own.steps.maxlen == 8192 and eng.anatomy_is_default
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    assert own.total_steps >= 2 and all(_tiles(r.to_row()) for r in own.steps)
    assert eng.set_anatomy(None) is NULL_ANATOMY and eng.anatomy is NULL_ANATOMY and not eng.anatomy_is_default
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    assert NULL_ANATOMY.total_steps == 0 and own.total_steps == len(own.steps)   # the old recorder saw no more


def _counts(rec):
    return (rec.key, rec.path, rec.rows_decode, rec.rows_prefill,
            rec.tokens_real, rec.slots, rec.tokens_out, rec.tokens_discarded)


def test_counts_of_single_and_mixed_steps_by_hand(tiny_serving):
    """Prompts of 3 and 12 tokens, chunk 8, batch bucket 2, prefill rungs
    of 1 and 4 rows, 3 tokens each.  Step 0 prefills 3 + 8 positions in a
    prefill group of 4 x 8 slots beside the decode bucket's 2 dead slots
    and the short prompt's first token comes out; step 1 is mixed (one
    decode row of the bucket's 2 slots, the long prompt's last 4 positions
    in a group of 1 x 8) and gives two tokens; steps 2 and 3 decode the
    two rows, one of which ends after step 2."""
    eng = tiny_serving()
    anat = eng.set_anatomy(StepAnatomy(clock=VirtualClock()))
    outs = eng.generate([[1, 2, 3], list(range(1, 13))], max_new_tokens=3)
    got = [_counts(r) for r in anat.steps]
    assert got == [
        ("step:b2:c1:b4:c8", "prefill", 0, 2, 11, 34, 1, 0),
        ("step:b2:c1:b1:c8", "mixed", 1, 1, 5, 10, 2, 0),
        ("step:b2:c1", "decode", 2, 0, 2, 2, 2, 0),
        ("step:b2:c1", "decode", 1, 0, 1, 2, 1, 0)]
    assert sum(r.tokens_out for r in anat.steps) == sum(len(o) for o in outs) == 6
    fold = anat.by_shape()
    assert fold["step:b2:c1:b1:c8"]["tokens_real"] == 5 and fold["step:b2:c1:b1:c8"]["slots"] == 10
    assert all(r.tokens_real <= r.slots for r in anat.steps)
    # a model with no expert layer sends no row through experts, or their kernel
    assert all(r.expert_rows == 0 and r.expert_rows_kernel == 0 for r in anat.steps)


def test_counts_of_a_fused_dispatch_with_overshoot(tiny_serving):
    """k = 4 fused decode steps, 6 tokens asked: the prefill gives the
    first, one fused dispatch four more, and the last dispatch runs the
    full rung of 4 for the one token still missing: 3 discarded."""
    eng = tiny_serving(k=4)
    anat = eng.set_anatomy(StepAnatomy(clock=VirtualClock()))
    outs = eng.generate([[1, 2, 3]], max_new_tokens=6)
    got = [_counts(r) for r in anat.steps]
    assert got == [
        ("step:b2:c1:b1:c8", "prefill", 0, 1, 3, 10, 1, 0),
        ("multi:b2:k4", "multi_decode", 1, 0, 4, 8, 4, 0),
        ("multi:b2:k4", "multi_decode", 1, 0, 4, 8, 1, 3)]
    assert sum(r.tokens_out for r in anat.steps) == len(outs[0]) == 6
    assert sum(r.tokens_discarded for r in anat.steps) == 3


class _TickingClock:
    """A real-clock stand-in that is deterministic: every reading is one
    millisecond after the last, ``on_step`` charges nothing (as
    ``WallClock``), so every host segment between two readings is > 0."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        self.t += 1e-3
        return self.t

    def wait_until(self, ts):
        self.t = max(self.t, ts)

    def on_step(self, cost):
        return None


@pytest.mark.parametrize("async_dispatch", [False, True], ids=["serial", "pipelined"])
def test_admit_and_deliver_are_segments_in_both_ticks(tiny_serving, async_dispatch):
    """The tick's expiry + admission and its delivery are named segments
    of the step in the serial and in the pipelined tick, the tiling stays
    exact, and ``overlap`` (the caller's loop under a dispatch in flight)
    exists only in the pipelined one."""
    from deepspeed_tpu.serving import ServingConfig, ServingEngine

    eng = tiny_serving()
    clock = _TickingClock()
    anat = eng.set_anatomy(StepAnatomy(clock=clock))
    serve = ServingEngine(eng, clock=clock,
                          config=ServingConfig(async_dispatch=async_dispatch))
    reqs = serve.run([{"arrival_ts": 0.0, "prompt": [1 + i, 2, 3, 4, 5],
                       "max_new_tokens": 4} for i in range(3)])
    assert all(r.state.value == "done" for r in reqs)
    rows = [r.to_row() for r in anat.steps]
    assert rows and all(_tiles(r, tol=1e-8) for r in rows)
    assert sum(r["segments"]["admit"] for r in rows) > 0
    assert sum(r["segments"]["deliver"] for r in rows) > 0
    assert (sum(r["segments"]["overlap"] for r in rows) > 0) == async_dispatch
    if not async_dispatch:
        # the whole tick lies in the step: admit first, deliver last, so
        # the gap is one clock reading of the caller's loop per tick
        assert all(r["segments"]["admit"] > 0 and r["segments"]["deliver"] > 0
                   for r in rows)
        assert max(r["host_gap_s"] for r in rows[1:]) < 0.01
    assert sum(r["tokens_out"] for r in rows) == sum(len(r.tokens) for r in reqs)


# ------------------------------- names the device trace reads (XLA Modules, kernels)


def test_step_programs_carry_their_key_as_a_name(tiny_serving):
    """Each builder names its function after the program key before
    ``jax.jit``, so the lowered module (what the device trace's ``XLA
    Modules`` line shows) is ``jit_ds_step_b2_c1_b1_c8`` and not ``jit_step``."""
    from deepspeed_tpu.inference.v2.engine_v2 import _named

    eng = tiny_serving(k=4)
    assert {eng._key_label(k) for k in eng.step_shape_set()} >= {
        "step:b2:c1", "step:b2:c1:b1:c8", "step:b2:c1:b4:c8", "multi:b2:k4"}
    assert "jit_ds_step_b2_c1_b1_c8" in eng._aot_lower(((2, 1), (1, 8))).as_text()[:400]
    assert "jit_ds_step_b2_c1" in eng._aot_lower(((2, 1), )).as_text()[:400]
    assert "jit_ds_multi_b2_k4" in eng._aot_lower(("multi", 2, 4)).as_text()[:400]
    assert eng._build_verify_jit(2, 5).__name__ == "ds_verify_b2_w5"
    assert eng._compiled_step(((4, 8), )).__name__ == "ds_step_b4_c8"
    assert _named(lambda x: x, "multi:b16:k8").__name__ == "ds_multi_b16_k8"


def test_training_step_functions_carry_ds_names():
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import PRESETS, LlamaForCausalLM

    eng, _, _, _ = ds.initialize(model=LlamaForCausalLM(PRESETS["tiny"]), config={
        "train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}})
    ids = np.random.default_rng(0).integers(0, 256, (8, 16), dtype=np.int32)
    batch = {"input_ids": ids, "labels": ids}
    eng._ensure_ready(batch)
    assert eng._train_step_fn.__name__ == "ds_train_step"
    assert eng._accum_fn.__name__ == "ds_accum"
    assert eng._apply_step_fn.__name__ == "ds_apply"
    assert "jit_ds_train_step" in eng._train_step_fn.lower(eng.state, batch).as_text()[:400]
    # train_batch runs under ds.train_step / ds.dispatch
    # ranges: inactive TraceMes without a profile, and the loss comes out
    assert np.isfinite(float(jax.device_get(eng.train_batch(batch=batch))))


def test_pallas_kernels_carry_ds_names():
    """Every ``pl.pallas_call`` in the paged and flash kernels passes
    ``name=``: read from the jaxpr (the CPU traces what the TPU lowers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.flash_attention import flash_attention
    from deepspeed_tpu.ops.paged_attention import paged_attention_pallas

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 64)), jnp.float32)
    loss = lambda q: flash_attention(q, q, q, causal=True, block_q=32, block_k=32,
                                     interpret=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss))(q))
    assert all(n in text for n in ("ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv"))

    pages = jnp.zeros((1, 5, 8, 2, 2, 32), jnp.float32)
    qd = jnp.asarray(rng.normal(size=(2, 1, 4, 32)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    sp, cl = jnp.asarray([3, 9], jnp.int32), jnp.asarray([1, 1], jnp.int32)
    text = str(jax.make_jaxpr(lambda q, p: paged_attention_pallas(
        q, p, bt, sp, cl, 8, layer=0, interpret=True))(qd, pages))
    assert "ds_paged_attention" in text


def test_the_rings_counts_ride_the_step_record_by_name():
    """What a geometry with rings counts (``SlotPagesGeometry(ring_rows=)``:
    the rows a window layer's ring holds a call, those of them and of a layer
    with pages the call's last query sees) reaches the record, its row and the
    per-program fold by name, as every named count does; a step that notes
    none reads 0."""
    from deepspeed_tpu.inference.v2.geometry import SlotPagesGeometry
    from deepspeed_tpu.telemetry.step_anatomy import COUNTS
    assert {"ring_rows_held", "ring_rows_seen", "full_rows_seen"} <= set(COUNTS)
    geometry = SlotPagesGeometry(16, window=4096, chunk_runs=True, run_tokens=1024, ring_rows=5136)
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    for start, n, calls in ((8192, 128, 1), (100, 8, 8)):      # a chunk past two windows; eight fused rounds at 100
        anat.step_begin()
        key, path = ("step:b32:c1:b1:c128", "mixed") if calls == 1 else ("multi:b32:k8", "multi_decode")
        anat.note_program(key, path, rows_decode=int(calls > 1), rows_prefill=int(calls == 1), tokens_real=n, slots=n)
        anat.note_counts(state_counts=geometry.state_counts(start, n, calls))
        clock.advance(0.01)
        anat.step_end()
    chunk, fused = (r.to_row() for r in anat.steps)
    assert (chunk["ring_rows_held"], chunk["ring_rows_seen"], chunk["full_rows_seen"]) == (5136, 4096, 8320)
    assert chunk["window_rows_visible"] == 128 * 4096
    assert (fused["ring_rows_held"], fused["ring_rows_seen"]) == (8 * 5136, sum(range(101, 109)))
    assert fused["full_rows_seen"] == fused["ring_rows_seen"] == fused["window_rows_visible"]
    assert anat.by_shape()["multi:b32:k8"]["ring_rows_held"] == 8 * 5136
    anat.step_begin()
    anat.note_program("step:b32:c1", "decode", rows_decode=1, tokens_real=1, slots=32)
    clock.advance(0.01)
    anat.step_end()
    assert anat.last_step.ring_rows_held == 0 and anat.last_step.to_row()["full_rows_seen"] == 0
