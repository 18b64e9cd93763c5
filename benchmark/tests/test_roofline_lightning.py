"""``roofline_lightning`` on hand-worked shapes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_lightning.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_lightning  # noqa: E402
import run as bench  # noqa: E402

STATE = 32 * 128 * 128          # elements of one layer's state a sequence, the published sizes


def test_shape_comes_from_the_configuration():
    cfg = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    assert roofline_lightning.shape_of(cfg) == (6, 32, 128, 128)
    assert roofline_lightning.shape_of(bench.merge(cfg, cfg["rehearsal"])) == (2, 4, 32, 32)


def test_one_position_reads_and_writes_the_state_once():
    f, b = roofline_lightning.lightning_update_call(32, 128, 128)
    assert f == 5 * STATE
    assert b == 4 * (2 * STATE + 4 * 32 * 128) == 4_259_840                      # 2 x 2 MB and 64 KB
    import peaks
    peak = peaks.match_device_kind("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > f / peak["bf16_flops"]


def test_traced_work_counts_the_calls_from_the_state_bytes(monkeypatch):
    import step_rows
    cfg = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    a_row = 2 * 6 * STATE * 4                                                      # a row's six states in and out
    rows = [{"end_ts": 5.0, "lightning_state_bytes": 10**12},                     # before the stretch
            {"end_ts": 7.0, "lightning_state_bytes": 8 * 3 * a_row},              # 8 rounds of 3 rows
            {"end_ts": 9.0, "lightning_state_bytes": 0}]                           # a prefill step: the chunked form
    run = {"config": cfg, "ticks": [(0.0, 10.0, 1, 0)], "reduced": {"window_s": 4.0}}
    monkeypatch.setattr(step_rows, "window_rows", lambda _: rows)
    f, b = roofline_lightning.lightning_update_call(32, 128, 128)
    work = roofline_lightning.traced_work(run)
    assert work["flops"] == pytest.approx(8 * 3 * 6 * f) and work["bytes"] == pytest.approx(8 * 3 * 6 * b)
    monkeypatch.setattr(step_rows, "window_rows", lambda _: [{"end_ts": 7.0}])
    assert roofline_lightning.traced_work(run) is None


def test_kernel_seconds_sums_the_kernels_events():
    events = [("ds_lightning_update", 0.0, 0.5, {}), ("ds_lightning_update.2", 1.0, 1.25, {}),
              ("ds_kda_update", 2.0, 3.0, {})]
    assert roofline_lightning.kernel_seconds({"events": events}) == pytest.approx(0.75)
