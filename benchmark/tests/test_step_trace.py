"""``step_trace`` on a hand-made trace with nested spans and a modules line
(``fixtures/nested.trace.json``, its numbers worked by hand in
``nested.expected.json``) and on hand-made step rows:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import step_trace  # noqa: E402
import trace_reduce  # noqa: E402


def _fixture(name):
    with open(os.path.join(HERE, "fixtures", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def trace():
    raw = _fixture("nested.trace.json")
    as_events = lambda evs: [(n, s, e, st) for n, s, e, st in evs]
    return {"devices": {int(k): as_events(v) for k, v in raw["devices"].items()}, "host": as_events(raw["host"]),
            "program": as_events(raw["program"]), "modules": as_events(raw["modules"])}


@pytest.fixture(scope="module")
def expected():
    return _fixture("nested.expected.json")


def test_segments_are_the_intervals_between_marks(trace, expected):
    spans = step_trace.segments(trace["program"])
    first = [s for s in spans if s[0] != "ds.step" and 0.9 <= s[1] and s[2] <= 2.44]
    assert [(n, pytest.approx(a), pytest.approx(b)) for n, a, b, _ in first] == \
        [tuple(x) for x in expected["segments_of_step_0"]]
    steps = [s for s in spans if s[0] == "ds.step"]
    assert [s[3]["key"] for s in steps] == ["step:b16:c128", "multi:b16:k8", "multi:b16:k8"]
    assert not [s for s in spans if s[0].startswith("ds.mark.")]
    # the last step's tail, after its last mark, is bookkeeping
    assert [(n, pytest.approx(a), pytest.approx(b)) for n, a, b, _ in spans if a >= 4.0] == \
        [("ds.bookkeeping", 4.03, 4.04)]


def test_idle_gaps_go_to_the_innermost_span(trace, expected):
    gaps = dict(step_trace.idle_gaps(trace, step_trace.segments(trace["program"])))
    assert gaps == {k: pytest.approx(v) for k, v in expected["idle_gaps_innermost"].items()}
    # the outer view (``trace_reduce.reduce``, the benchmark's own four spans) owns every gap by ``tick``
    outer = dict(trace_reduce.reduce({"devices": trace["devices"], "host": trace["host"]})["idle_gaps"])
    assert outer == {k: pytest.approx(v) for k, v in expected["idle_gaps_outer_only"].items()}
    assert sum(gaps.values()) == pytest.approx(sum(outer.values()))
    # with no program span in the trace the two views agree
    assert dict(step_trace.idle_gaps(trace, [])) == {k: pytest.approx(v) for k, v in outer.items()}


def test_modules_reduction_and_program_keys(trace, expected):
    got = step_trace.modules(trace)
    assert got == {k: {"runs": v["runs"], "device_s": pytest.approx(v["device_s"])}
                   for k, v in expected["modules"].items()}
    assert step_trace.program_key("jit_ds_verify_b8_w5(77)") == "verify:b8:w5"
    assert step_trace.program_key("jit__lambda(9)") == "jit__lambda"


ROWS = [  # three steps as StepRecord.to_row gives them; the recorder's clock runs 100 s behind the trace's
    {"index": 0, "key": "step:b16:c128", "tokens_real": 300, "slots": 2048, "tokens_out": 5, "tokens_discarded": 0,
     "wall_s": 1.54, "host_gap_s": 0.0, "device_s": 1.0, "end_ts": -97.56},
    {"index": 1, "key": "multi:b16:k8", "tokens_real": 96, "slots": 128, "tokens_out": 90, "tokens_discarded": 6,
     "wall_s": 0.90, "host_gap_s": 0.02, "device_s": 0.5, "end_ts": -96.66},
    {"index": 2, "key": "multi:b16:k8", "tokens_real": 96, "slots": 128, "tokens_out": 96, "tokens_discarded": 0,
     "wall_s": 0.70, "host_gap_s": 0.02, "device_s": 0.63, "end_ts": -95.96002},
]


def test_the_four_metrics_on_hand_made_rows():
    assert step_trace.slot_fill_share(ROWS) == pytest.approx(492 / 2304)
    assert step_trace.mixed_step_share(ROWS) == pytest.approx(1 / 3)
    assert step_trace.step_host_p50_ms(ROWS) == pytest.approx(400.0)
    assert step_trace.step_device_wait_p50_ms(ROWS) == pytest.approx(630.0)
    assert step_trace.slot_fill_share([]) is None and step_trace.mixed_step_share([]) is None
    assert step_trace.step_host_p50_ms([]) is None and step_trace.step_device_wait_p50_ms([]) is None


def test_programs_table_joins_rows_and_modules(trace):
    table = step_trace.programs(ROWS, step_trace.modules(trace))
    assert list(table) == ["multi:b16:k8", "step:b16:c128"]
    assert table["multi:b16:k8"] == {"steps": 2, "wall_s": pytest.approx(1.6), "tokens_real": 192, "slots": 256,
                                     "tokens_out": 186, "tokens_discarded": 6, "runs": 2,
                                     "device_s": pytest.approx(1.13)}
    assert table["step:b16:c128"]["runs"] == table["step:b16:c128"]["steps"] == 1


def test_clock_error_is_the_distance_between_the_two_sets_of_edges(trace):
    err = step_trace.clock_error(ROWS, step_trace.segments(trace["program"]))
    assert err["steps"] == 3
    assert err["step_range_max_ms"] == pytest.approx(0.0, abs=1e-6)
    assert err["device_wait_max_ms"] == pytest.approx(0.0, abs=1e-6)
    # two ends sit 100 s apart, the third 100.00002 s: 0.02 ms off the median
    assert err["end_offset_spread_max_ms"] == pytest.approx(0.02, abs=1e-6)


def test_slow_ticks_are_those_over_three_medians():
    ticks = [(10.0, 10.1, 8, 0), (10.1, 10.2, 8, 0), (10.2, 10.6, 1, 300), (10.6, 10.7, 8, 0)]
    assert step_trace.slow_ticks(ticks, 10.0) == [(0.2, 0.4, 1, 300)]
    assert step_trace.slow_ticks([], 0.0) == []
