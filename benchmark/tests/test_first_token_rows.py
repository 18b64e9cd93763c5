"""``first_token_rows.window_rows`` and the six readers of the program's
first-token rows (PR 51), on made-up rows, on a recorder filled by hand, and
in the CPU rehearsal of one ``_p50`` and one ``_mean`` cell:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_first_token_rows.py -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import first_token_rows  # noqa: E402
import run as bench_run  # noqa: E402
from deepspeed_tpu.serving.clock import VirtualClock  # noqa: E402
from deepspeed_tpu.telemetry import StepAnatomy  # noqa: E402

P50 = ("prefill_ms_per_ktok_p50", "ttft_bypassed_p50_ms", "ttft_wait_p50_ms")
MEAN = ("prefill_ms_per_ktok_mean", "ttft_bypassed_mean_ms", "ttft_wait_mean_ms")


def _row(uid, first_token_ts, prefill_tokens, late_s=0.0, queued_s=0.0, carried_s=0.0, bypassed_s=0.0,
         vision_encode_s=0.0, wait_s=0.0, other_s=0.0):
    parts = dict(late_s=late_s, queued_s=queued_s, carried_s=carried_s, bypassed_s=bypassed_s,
                 vision_encode_s=vision_encode_s, wait_s=wait_s, other_s=other_s)
    return {"uid": uid, "first_token_ts": first_token_ts, "ttft_s": sum(parts.values()), **parts,
            "prompt_tokens": prefill_tokens, "prefill_tokens": prefill_tokens, "prefill_steps": 1, "preemptions": 0}


# per thousand tokens 100, 125 and 200 ms; passed by for 0, 30 and 300 ms; no step running for 5, 12 and 40 ms
ROWS = [
    _row(0, 10.5, 2000, late_s=0.002, queued_s=0.001, carried_s=0.200, wait_s=0.002),
    _row(1, 11.0, 8000, late_s=0.004, carried_s=1.000, bypassed_s=0.030, wait_s=0.006, other_s=0.002),
    _row(2, 11.5, 4000, queued_s=0.030, carried_s=0.800, bypassed_s=0.300, vision_encode_s=0.050, wait_s=0.010),
]
RUN = {"ticks": [(10.0, 10.1, 8, 0), (11.9, 12.0, 8, 0)]}


@pytest.mark.parametrize("name, want", [
    ("prefill_ms_per_ktok_p50", 125.0), ("prefill_ms_per_ktok_mean", 425.0 / 3),
    ("ttft_bypassed_p50_ms", 30.0), ("ttft_bypassed_mean_ms", 110.0),
    ("ttft_wait_p50_ms", 12.0), ("ttft_wait_mean_ms", 19.0),
])
def test_the_readers_on_made_up_rows(monkeypatch, name, want):
    monkeypatch.setattr(first_token_rows, "window_rows", lambda run: ROWS)
    assert bench_run.reader("layer_metrics", name)(RUN) == pytest.approx(want)


def test_the_means_and_the_towers_wait_sum_to_the_mean_ttft(monkeypatch):
    monkeypatch.setattr(first_token_rows, "window_rows", lambda run: ROWS)
    carried = first_token_rows.mean(RUN, lambda r: 1e3 * r["carried_s"])
    tower = first_token_rows.mean(RUN, lambda r: 1e3 * r["vision_encode_s"])
    parts = [bench_run.reader("layer_metrics", n)(RUN) for n in ("ttft_bypassed_mean_ms", "ttft_wait_mean_ms")]
    assert carried + tower + sum(parts) == pytest.approx(first_token_rows.mean(RUN, lambda r: 1e3 * r["ttft_s"]))


@pytest.mark.parametrize("name", P50 + MEAN)
def test_a_reader_leaves_its_metric_out_where_the_program_keeps_no_rows(monkeypatch, name):
    read = bench_run.reader("layer_metrics", name)
    monkeypatch.setattr(first_token_rows, "window_rows", lambda run: None)
    assert read(RUN) is None
    monkeypatch.setattr(first_token_rows, "window_rows", lambda run: [])
    assert read(RUN) is None


def test_window_rows_are_the_first_tokens_between_the_first_and_the_last_tick():
    anat = StepAnatomy(clock=VirtualClock())
    other = StepAnatomy(clock=VirtualClock())       # e.g. the recorder of an engine that only warmed up
    for row in (_row(7, 9.9, 100), *ROWS, _row(8, 12.1, 100)):
        anat.note_first_token(row)
    other.note_first_token(_row(9, 11.0, 100))
    assert [r["uid"] for r in first_token_rows.window_rows(RUN)] == [0, 1, 2]
    assert first_token_rows.window_rows({"ticks": []}) is None and first_token_rows.window_rows({}) is None
    assert first_token_rows.window_rows({"ticks": [(50.0, 50.1, 1, 0)]}) == []
    del anat, other


def test_a_program_without_the_ring_reads_none(monkeypatch):
    """A parent of PR 51: its recorders keep steps and no first tokens."""
    anat = StepAnatomy(clock=VirtualClock())
    import deepspeed_tpu.telemetry as telemetry

    class Old:
        steps = ()

    monkeypatch.setattr(telemetry, "recorders", lambda: [Old()])
    assert first_token_rows.window_rows(RUN) is None
    assert {n: bench_run.reader("layer_metrics", n)(RUN) for n in P50 + MEAN} == dict.fromkeys(P50 + MEAN)
    monkeypatch.delattr(telemetry, "recorders")     # a parent of PR 34
    assert first_token_rows.window_rows(RUN) is None
    del anat


@pytest.mark.parametrize("cell, names", [("mixtral_doc", P50), ("mixtral_chat", MEAN)])
def test_the_rehearsal_reads_the_new_metrics(cell, names):
    cmd = [sys.executable, os.path.join(HERE, "selfcheck.py"), "--rehearse", cell, "--seed", str(2 ** 31 + 51),
           "--trace", "1"]
    out = subprocess.run(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] and set(names) <= set(result["metrics_read"]), result
