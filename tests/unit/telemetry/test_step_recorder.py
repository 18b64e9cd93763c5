"""The engine's own step recorder (PR 34): every engine records its steps, a
serving frontend moves the recorder onto its clock, a step says what CPU and
collector time it took on a real clock (and 0.0 on a virtual one), a step that
falls behind the median of its program key logs one ``ds.slow_step`` line, and
``telemetry.recorders()`` reaches every live recorder of the process."""

import gc
import logging
import time

import pytest

from deepspeed_tpu.serving.clock import VirtualClock, WallClock
from deepspeed_tpu.telemetry import NULL_ANATOMY, PerfClock, StepAnatomy, recorders
from deepspeed_tpu.utils.logging import logger


def _tiles(row, tol=1e-9):
    return abs(row["wall_s"] - (row["host_gap_s"] + sum(row["segments"].values()) + row["device_s"])) <= tol


def _step(anat, clock, dispatch_s=0.005, device_s=0.015, key="step:b4:c1", gap_s=0.0):
    """One scripted step: ``gap_s`` to the caller, ``dispatch_s`` in the
    dispatch segment, ``device_s`` at the readback."""
    clock.advance(gap_s)
    anat.step_begin()
    anat.note_program(key, "decode", rows_decode=3, tokens_real=3, slots=4)
    clock.advance(dispatch_s)
    anat.mark("dispatch")
    clock.advance(device_s)
    anat.device_mark()
    return anat.step_end()


@pytest.fixture
def slow_lines():
    """The ``ds.slow_step`` lines the program's logger is handed."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            if record.getMessage().startswith("ds.slow_step"):
                lines.append(record.getMessage())

    handler = Keep()
    logger.addHandler(handler)
    yield lines
    logger.removeHandler(handler)


# ------------------------------------------------ the engine's own recorder


def test_serving_engine_binds_the_engines_own_recorder_to_its_clock(tiny_serving):
    from deepspeed_tpu.serving import ServingEngine
    eng = tiny_serving()
    own = eng.anatomy
    assert isinstance(own.clock, PerfClock)
    clock = VirtualClock()
    clock.advance(100.0)
    serve = ServingEngine(eng, clock=clock)
    assert eng.anatomy is own and own.clock is clock
    req = serve.submit([1, 2, 3], max_new_tokens=3)
    serve.drain()
    assert req.state.terminal and own.total_steps >= 3
    assert all(100.0 < r.end_ts <= clock.now() and _tiles(r.to_row()) for r in own.steps)
    assert all(r.cpu_s == 0.0 and r.gc_s == 0.0 for r in own.steps)    # a virtual clock: no reading of real time


def test_serving_engine_leaves_a_brought_recorder_on_its_clock(tiny_serving):
    from deepspeed_tpu.serving import ServingEngine
    eng = tiny_serving()
    mine = eng.set_anatomy(StepAnatomy(clock=(theirs := VirtualClock())))
    ServingEngine(eng, clock=VirtualClock())
    assert eng.anatomy is mine and mine.clock is theirs
    eng.set_anatomy(None)
    ServingEngine(eng, clock=VirtualClock())          # nothing to bind, nothing raised
    assert eng.anatomy is NULL_ANATOMY


def test_rebind_refuses_an_open_step_and_resets_the_gap_origin():
    a, b = VirtualClock(), VirtualClock()
    anat = StepAnatomy(clock=a)
    _step(anat, a)
    anat.step_begin()
    with pytest.raises(RuntimeError, match="a step is open"):
        anat.rebind(b)
    anat.note_program("step:b4:c1", "decode")
    anat.step_end()
    b.advance(500.0)                                   # the two clocks share no zero
    anat.rebind(b)
    rec = _step(anat, b, gap_s=0.3)
    assert anat.clock is b and rec.host_gap_s == 0.0 and rec.end_ts == pytest.approx(500.32)
    assert _step(anat, b, gap_s=0.3).host_gap_s == pytest.approx(0.3)


def test_warm_all_ends_the_warm_up(tiny_serving):
    eng = tiny_serving()
    assert not eng.anatomy.steady
    warm = eng.warm_all()
    assert warm["compiled"] and eng.anatomy.steady and eng.anatomy.steady_state_recompiles == 0
    assert all(c.aot for c in eng.anatomy.compiles)
    eng.generate([[1, 2, 3]], max_new_tokens=2)        # every program was compiled ahead: no recompile to flag
    assert eng.anatomy.steady_state_recompiles == 0


def test_the_ring_of_8192_counts_what_it_drops():
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock, max_steps=8192)
    for _ in range(8200):
        _step(anat, clock)
    assert (len(anat.steps), anat.dropped_steps, anat.total_steps) == (8192, 8, 8200)
    assert anat.steps[0].index == 8 and anat.summary()["dropped_steps"] == 8


# ------------------------------------------------------ cpu_s and gc_s


def test_the_tiling_holds_with_cpu_and_collector_seconds_present(tiny_serving):
    eng = tiny_serving()
    eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=4)
    rows = [r.to_row() for r in eng.anatomy.steps]
    assert rows and all(_tiles(r) for r in rows)
    assert all({"cpu_s", "gc_s"} <= set(r) and "cpu_s" not in r["segments"] for r in rows)
    # CPU the thread burned inside the steps: some, and no more than they took (a thread's clock may move in ticks of
    # 10 ms, so the two are compared over the run and with a tick a step to spare)
    own = sum(r["wall_s"] - r["host_gap_s"] for r in rows)
    assert all(r["cpu_s"] >= 0.0 and r["gc_s"] >= 0.0 for r in rows)
    assert 0.0 < sum(r["cpu_s"] for r in rows) <= own + 0.01 * len(rows)
    assert sum(r["gc_s"] for r in rows) <= own + 1e-3


@pytest.mark.parametrize("clock_type", [PerfClock, WallClock, VirtualClock])
def test_a_collection_inside_a_step_shows_in_gc_s_on_a_real_clock_only(clock_type):
    anat = StepAnatomy(clock=clock_type())
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    t0 = time.perf_counter()
    junk = [[i] for i in range(20000)]                 # allocating may collect a young generation too
    gc.collect()
    took = time.perf_counter() - t0
    rec = anat.step_end()
    del junk
    if clock_type is VirtualClock:
        assert rec.gc_s == 0.0 and rec.cpu_s == 0.0
    else:
        assert 0.0 < rec.gc_s <= took and rec.cpu_s > 0.0      # on a busy machine the collector waits for the CPU too
    assert _tiles(rec.to_row())                        # neither is a part of the tiling
    # outside a step a collection is nobody's
    gc.collect()
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    assert anat.step_end().gc_s < took


def test_cpu_s_is_the_threads_own_time_not_its_sleep():
    anat = StepAnatomy()                               # PerfClock
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    time.sleep(0.05)                                   # blocked: wall time, no CPU
    rec = anat.step_end()
    assert rec.wall_s >= 0.05 and rec.cpu_s < 0.02
    anat.step_begin()
    anat.note_program("step:b4:c1", "decode")
    t0 = time.thread_time()
    while time.thread_time() - t0 < 0.03:              # busy: CPU
        pass
    assert anat.step_end().cpu_s >= 0.03


# ------------------------------------------------------- the slow-step rule


def test_one_stalled_step_gives_one_line_that_names_its_segment(slow_lines):
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    for _ in range(16):
        _step(anat, clock, gap_s=0.01)
    assert not slow_lines and not anat.slow_steps
    _step(anat, clock, dispatch_s=3.0, gap_s=0.7)
    for _ in range(16):
        _step(anat, clock, gap_s=0.01)
    assert len(slow_lines) == 1 and len(anat.slow_steps) == 1
    fields = dict(f.split("=", 1) for f in slow_lines[0].split()[1:])
    assert fields["index"] == "16" and fields["key"] == "step:b4:c1"
    assert float(fields["own_s"]) == pytest.approx(3.015) and float(fields["median_s"]) == pytest.approx(0.02)
    assert float(fields["dispatch"]) == pytest.approx(3.0)          # the largest host segment, by name
    assert float(fields["host_gap_s"]) == pytest.approx(0.7)        # printed, and no part of the rule
    assert float(fields["device_wait_s"]) == pytest.approx(0.015)
    assert (fields["cpu_s"], fields["gc_s"], fields["compiles"]) == ("0.000000", "0.000000", "0")
    assert (fields["rows_decode"], fields["rows_prefill"], fields["tokens_real"], fields["slots"]) == ("3", "0", "3", "4")
    row = anat.slow_steps[0]
    assert row["index"] == 16 and row["own_s"] == pytest.approx(3.015) and row["median_s"] == pytest.approx(0.02)
    assert anat.summary()["slow_steps"] == 1


def test_a_steady_run_and_a_long_gap_give_no_line(slow_lines):
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    for i in range(200):
        # two programs, each steady about its own median; the caller idles 5 s now and then
        _step(anat, clock, device_s=0.015 + 0.001 * (i % 5), gap_s=5.0 if i % 50 == 49 else 0.002)
        _step(anat, clock, device_s=0.9, key="step:b4:c128")
    assert not slow_lines and not anat.slow_steps


def test_no_line_within_the_first_16_steps_of_a_key(slow_lines):
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    for _ in range(15):
        _step(anat, clock)
    _step(anat, clock, dispatch_s=3.0)                 # the 16th: 15 steps are no median to hold it to
    assert not slow_lines
    for _ in range(40):
        _step(anat, clock, key="multi:b4:k8")          # another key's steps do not count for this one
    _step(anat, clock, dispatch_s=3.0, key="step:b4:c32")
    assert not slow_lines
    _step(anat, clock, dispatch_s=3.0)                 # the 17th of its key
    assert len(slow_lines) == 1


def test_at_most_one_line_a_second_and_every_row_kept(slow_lines):
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock)
    for _ in range(20):
        _step(anat, clock)
    _step(anat, clock, device_s=0.4)                   # slow: 0.405 s against 0.02
    _step(anat, clock, device_s=0.4)                   # slow again, 0.4 s after the line
    assert len(slow_lines) == 1 and len(anat.slow_steps) == 2
    clock.advance(1.0)
    _step(anat, clock, device_s=0.4)
    assert len(slow_lines) == 2 and len(anat.slow_steps) == 3
    assert "device_wait_s=0.400000" in slow_lines[1]
    # a step over 4 x the median and under a quarter of a second over it is not slow
    _step(anat, clock, device_s=0.2)
    assert len(anat.slow_steps) == 3


# ------------------------------------------------------------ recorders()


def test_recorders_are_the_live_ones_oldest_first():
    first, second = StepAnatomy(clock=VirtualClock()), StepAnatomy(clock=VirtualClock())
    live = recorders()
    assert live.index(first) < live.index(second)
    del live, first
    gc.collect()
    assert second in recorders() and len([r for r in recorders() if r is second]) == 1


def test_recorders_forget_a_collected_engine(tiny_serving):
    eng = tiny_serving()
    own = eng.anatomy
    assert own in recorders()
    marker = id(own)
    del eng, own
    gc.collect()
    assert marker not in [id(r) for r in recorders()]


@pytest.mark.parametrize("collections", [1, 3])
def test_the_recorder_of_the_newest_step_outlives_its_engine(tiny_serving, collections):
    """The benchmark's readers come when the run's engine is unreferenced: what
    closed the newest step is there however often the collector has run, and
    goes when another recorder closes a step."""
    eng = tiny_serving()
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    marker, steps = id(eng.anatomy), eng.anatomy.total_steps
    assert steps > 0
    del eng
    for _ in range(collections):
        gc.collect()
    kept = [r for r in recorders() if id(r) == marker]
    assert len(kept) == 1 and kept[0].total_steps == steps
    del kept
    clock = VirtualClock()
    other = StepAnatomy(clock=clock)
    _step(other, clock)
    gc.collect()
    assert marker not in [id(r) for r in recorders()] and other in recorders()
