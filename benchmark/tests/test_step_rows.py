"""``step_rows.window_rows`` and the six readers of the step records, on
made-up rows and on a recorder filled by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_step_rows.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import run as bench_run  # noqa: E402
import step_rows  # noqa: E402
from deepspeed_tpu.serving.clock import VirtualClock  # noqa: E402
from deepspeed_tpu.telemetry import StepAnatomy  # noqa: E402

READERS = ("slot_fill_share", "step_host_p50_ms", "step_device_wait_p50_ms", "step_excess_share",
           "expert_kernel_share", "attn_walk_tightness")


def _row(key, host_gap_s, host_s, device_s, **counts):
    row = {"key": key, "host_gap_s": host_gap_s, "device_s": device_s, "wall_s": host_gap_s + host_s + device_s,
           "tokens_real": 0, "slots": 0, "expert_rows": 0, "expert_rows_kernel": 0,
           "attn_rows_visible": 0, "attn_rows_walked": 0}
    row.update(counts)
    return row


ROWS = [
    _row("step:b16:c128", 0.001, 0.004, 0.040, tokens_real=140, slots=2048, expert_rows=280, expert_rows_kernel=280,
         attn_rows_visible=9000, attn_rows_walked=10240),
    _row("multi:b16:k8", 0.002, 0.003, 0.020, tokens_real=24, slots=128, expert_rows=48,
         attn_rows_visible=3000, attn_rows_walked=4096),
    _row("multi:b16:k8", 0.001, 0.003, 0.021, tokens_real=24, slots=128, expert_rows=48,
         attn_rows_visible=3024, attn_rows_walked=4096),
    _row("multi:b16:k8", 0.001, 0.503, 0.020, tokens_real=24, slots=128, expert_rows=48,
         attn_rows_visible=3048, attn_rows_walked=4096),   # half a second in a host segment
]


def _step(anat, clock, key, host_s, device_s, **counts):
    anat.step_begin()
    clock.advance(host_s)
    anat.note_program(key, "decode", **counts)
    anat.mark("dispatch")
    clock.advance(device_s)
    anat.device_mark()
    anat.step_end()


@pytest.fixture
def recorded():
    """A recorder with ten steps of 30 ms that end at 0.03, 0.06, ..., and a
    run whose ticks cover the steps that end from 0.09 to 0.24."""
    clock = VirtualClock()
    anat = StepAnatomy(clock=clock, max_steps=16)
    for _ in range(10):
        _step(anat, clock, "step:b4:c1", 0.01, 0.02, tokens_real=3, slots=4)
    run = {"ticks": [(0.065, 0.09, 3, 0), (0.125, 0.15, 3, 0), (0.215, 0.24, 3, 0)]}
    return anat, clock, run


def test_window_rows_are_the_steps_between_the_first_and_the_last_tick(recorded):
    anat, _, run = recorded
    rows = step_rows.window_rows(run)
    assert [r["index"] for r in rows] == [2, 3, 4, 5, 6, 7]
    assert rows[0] == anat.steps[2].to_row()
    assert step_rows.window_span(run) == (0.065, 0.24)


def test_window_rows_come_from_the_recorder_that_holds_most_of_them(recorded):
    anat, _, run = recorded
    other_clock = VirtualClock()
    other = StepAnatomy(clock=other_clock)     # e.g. the recorder of an engine that only warmed up
    other_clock.advance(0.07)
    _step(other, other_clock, "step:b8:c1", 0.01, 0.02)
    assert [r["key"] for r in step_rows.window_rows(run)] == ["step:b4:c1"] * 6
    del anat


def test_window_rows_none_without_ticks_or_steps_or_after_a_drop(recorded):
    anat, clock, run = recorded
    assert step_rows.window_rows({"ticks": []}) is None and step_rows.window_rows({}) is None
    assert step_rows.window_rows({"ticks": [(50.0, 50.1, 1, 0)]}) is None      # no step ended in there
    for _ in range(7):      # the ring of 16 drops the first step: the one before the window's first is still there
        _step(anat, clock, "step:b4:c1", 0.01, 0.02)
    assert anat.dropped_steps == 1 and len(step_rows.window_rows(run)) == 6
    _step(anat, clock, "step:b4:c1", 0.01, 0.02)   # now the window's first is the ring's oldest: was it the first?
    assert anat.steps[0].index == 2 and step_rows.window_rows(run) is None


def test_a_program_without_recorders_reads_none(monkeypatch):
    """A parent of PR 34: ``deepspeed_tpu.telemetry`` has no ``recorders``."""
    import deepspeed_tpu.telemetry as telemetry
    monkeypatch.delattr(telemetry, "recorders")
    run = {"ticks": [(0.0, 1.0, 1, 0)]}
    assert step_rows.window_rows(run) is None
    assert {name: bench_run.reader("layer_metrics", name)(run) for name in READERS} == dict.fromkeys(READERS)


@pytest.mark.parametrize("name, want", [
    ("slot_fill_share", 212 / 2432),
    ("step_host_p50_ms", 5.0),           # wall_s - device_s: 5, 5, 4, 504 ms
    ("step_device_wait_p50_ms", 20.5),
    # own_s by key: 44 ms alone; 23, 24, 523 ms with a median of 24: 499 ms over it, of a window of 2 s
    ("step_excess_share", 0.499 / 2.0),
    ("expert_kernel_share", 280 / 424),
    ("attn_walk_tightness", 18072 / 22528),
])
def test_the_readers_on_made_up_rows(monkeypatch, name, want):
    monkeypatch.setattr(step_rows, "window_rows", lambda run: ROWS)
    run = {"ticks": [(10.0, 10.1, 8, 0), (11.9, 12.0, 8, 0)]}
    assert bench_run.reader("layer_metrics", name)(run) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_the_readers_leave_their_metric_out_where_nothing_is_to_read(monkeypatch, name):
    read = bench_run.reader("layer_metrics", name)
    monkeypatch.setattr(step_rows, "window_rows", lambda run: None)
    assert read({"ticks": [(0.0, 1.0, 1, 0)]}) is None
    # a dense model has no expert rows, a twin on the jnp form walks no kernel
    bare = [_row("step:b4:c1", 0.0, 0.01, 0.02, tokens_real=3, slots=4)]
    monkeypatch.setattr(step_rows, "window_rows", lambda run: bare)
    got = read({"ticks": [(0.0, 1.0, 1, 0)]})
    assert (got is None) == (name in ("expert_kernel_share", "attn_walk_tightness"))
