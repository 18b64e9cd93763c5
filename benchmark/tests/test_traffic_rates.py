"""A serving mix's rate, knee and lead-in as data, and the sweep that reads them:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_traffic_rates.py -q

A mix that states its knee (``knee_per_s``) is held to ``rate_per_s`` = 0.8 of
it and to a lead-in of a median request's stay under the readings the sweep
took at that rate (``at_rate``), so a rate that no longer follows from its
knee is a failing test.  The sweep itself runs once, on a cell's rehearsal
sizes on the CPU: control flow and arithmetic, never a device reading.
"""

import glob
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import run as bench_run  # noqa: E402
import traffic_gen  # noqa: E402

MIXES = {os.path.basename(p)[:-len(".json")]: bench_run.load_json("traffic", os.path.basename(p))
         for p in sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")))}
SERVING = {name: mix for name, mix in MIXES.items() if mix["kind"] == "serve_open_loop"}
SWEPT = sorted(name for name, mix in SERVING.items() if "knee_per_s" in mix)


def test_the_three_reswept_mixes_state_their_knees():
    assert {"chat_heavy_tail", "reason_short_in_long_out", "sessions_short_in_long_out"} <= set(SWEPT)


@pytest.mark.parametrize("name", SWEPT)
def test_rate_is_four_fifths_of_the_stated_knee(name):
    mix = SERVING[name]
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"], abs=0.005), \
        f"{name}: rate_per_s {mix['rate_per_s']} is not 0.8 x knee_per_s {mix['knee_per_s']}: sweep again or reset it"


@pytest.mark.parametrize("name", SWEPT)
def test_lead_in_is_a_median_requests_stay_at_the_rate(name):
    mix = SERVING[name]
    assert set(mix["at_rate"]) == {"ttft_mean_ms", "tpot_p50_ms"}
    stay = traffic_gen.median_stay_s(mix, **mix["at_rate"])
    assert mix["lead_in_s"] == traffic_gen.lead_in_rule(mix) == max(1, round(stay)), \
        f"{name}: lead_in_s {mix['lead_in_s']} is not the {stay:.1f} s a median request stays under at_rate"
    # a request in the window must be able to end inside the drain
    assert mix["drain_cap_s"] > stay


def test_median_stay_counts_the_first_token_once():
    mix = {"output": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 0, "hi": 200}], "clip": [1, 200]}}
    assert traffic_gen.quantile(mix["output"], 0.5) == 100
    assert traffic_gen.median_stay_s(mix, ttft_mean_ms=150.0, tpot_p50_ms=20.0) == pytest.approx(0.150 + 99 * 0.020)
    assert traffic_gen.lead_in_rule(mix, {"ttft_mean_ms": 150.0, "tpot_p50_ms": 20.0}) == 2
    assert traffic_gen.lead_in_rule(mix, {"ttft_mean_ms": 1.0, "tpot_p50_ms": 0.1}) == 1  # never no lead-in


@pytest.mark.parametrize("name", sorted(SERVING))
def test_a_given_lead_in_takes_the_files_place(name):
    mix = SERVING[name]
    by_file = traffic_gen.serving_schedule(mix, 20.0, 7, 1000)
    again = traffic_gen.serving_schedule(mix, 20.0, 7, 1000, lead_in_s=mix["lead_in_s"])
    assert by_file == again
    longer = traffic_gen.serving_schedule(mix, 20.0, 7, 1000, rate_per_s=2 * mix["rate_per_s"],
                                          lead_in_s=2 * mix["lead_in_s"])
    lead = [r for r in longer if not r["measured"]]
    assert len(lead) == round(2 * mix["rate_per_s"] * 2 * mix["lead_in_s"])
    assert all(-2 * mix["lead_in_s"] <= r["due"] < 0 for r in lead)
    assert sum(r["measured"] for r in longer) == round(2 * mix["rate_per_s"] * 20.0)


def test_assign_replaces_one_value_of_the_files():
    files = {"config": {"engine": {"scheduler": {"max_seqs": 32}, "decode_steps_per_dispatch": 8}},
             "traffic": {"rate_per_s": 0.4}}
    bench_run.assign(files, ["config.engine.scheduler.max_seqs=64", "traffic.rate_per_s=1.2"])
    assert files["config"]["engine"] == {"scheduler": {"max_seqs": 64}, "decode_steps_per_dispatch": 8}
    assert files["traffic"]["rate_per_s"] == 1.2
    with pytest.raises(KeyError):
        bench_run.assign(files, ["config.no_such_group.x=1"])


def _fields(line):
    return {k: v for k, v in (item.split("=", 1) for item in line.split(": ", 1)[1].split(" "))}


def test_sweep_on_rehearsal_sizes_prints_the_mean_and_takes_its_lead_in_from_the_rate():
    """Two windows at one rate after one set-up; the second window's lead-in
    is a median request's stay under the first window's readings."""
    cell = "mixtral_chat"
    cmd = [sys.executable, os.path.join(HERE, "selfcheck.py"), "--rehearse", cell, "--seed", str(2 ** 31 + 40),
           "--sweep", "4,4", "--set", "traffic.at_rate={\"ttft_mean_ms\": 2900.0, \"tpot_p50_ms\": 10.0}"]
    out = subprocess.run(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("sweep: ")]
    assert len(lines) == 2 and '"metrics"' not in out.stdout, out.stdout[-2000:]  # a sweep prints no result
    first, second = (_fields(ln) for ln in lines)
    bench = json.load(open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")))
    traffic_name = next(w["traffic"] for w in bench["workloads"] if w["name"] == cell)
    mix = bench_run.merge(SERVING[traffic_name], SERVING[traffic_name]["rehearsal"])
    # the first window stands behind the stay the given at_rate readings make: 2.9 s and a few tokens of 10 ms
    assert float(first["lead_in_s"]) == 3
    for row in (first, second):
        assert int(row["failed"]) == 0 and int(row["attempted"]) == round(4 * 4.0)
        assert float(row["ttft_p50_ms"]) > 0 and float(row["queue_wait_max_ms"]) >= 0
        assert int(row["in_system_most"]) >= max(int(row["in_system_at_open"]), int(row["in_system_at_close"]))
        # a mean lies inside its sample: at or over the smallest wait, under the largest
        assert 0 < float(row["ttft_mean_ms"]) and float(row["ttft_mean_ms"]) < 10 * float(row["ttft_p90_ms"])
    readings = {"ttft_mean_ms": float(first["ttft_mean_ms"]), "tpot_p50_ms": float(first["tpot_p50_ms"])}
    assert float(second["lead_in_s"]) == traffic_gen.lead_in_rule(mix, readings)
