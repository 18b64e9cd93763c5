"""``roofline_sparse`` on hand-worked shapes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_sparse.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_sparse  # noqa: E402
import run as bench  # noqa: E402


def test_shape_comes_from_the_configuration():
    cfg = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    assert roofline_sparse.shape_of(cfg) == (4, 16, 128)            # two sparse layers of two key heads
    assert roofline_sparse.shape_of(bench.merge(cfg, cfg["rehearsal"])) == (4, 2, 32)


def test_a_named_row_is_read_once_and_met_by_its_key_heads_queries():
    # one decode row past dense_len, one layer and key head: 97 blocks of 64 rows
    f, b = roofline_sparse.decode_rows_call(6208, 16, 128)
    assert f == 4 * 128 * 16 * 6208 == 50_855_936
    assert b == 2 * 2 * 128 * 6208 == 3_178_496                       # a key and a value of 256 B a row
    import peaks
    peak = peaks.match_device_kind("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > f / peak["bf16_flops"]       # 16 operations a byte: the bytes bound it


def _run(rows):
    cfg = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    import peaks
    return {"config": cfg, "ticks": [(0.0, 10.0, 1, 0)], "reduced": {"window_s": 4.0},
            "peak": peaks.match_device_kind("TPU v5 lite")}, rows


def test_traced_work_adds_the_steps_of_the_traced_stretch(monkeypatch):
    import roofline
    import step_rows
    rows = [{"end_ts": 5.0, "sparse_decode_rows_read": 10**9},                      # before the stretch
            {"end_ts": 7.0, "sparse_decode_rows_read": 4 * 6208 * 8},             # a fused dispatch of 8 rounds, one row
            {"end_ts": 9.0, "sparse_decode_rows_read": 0}]                          # a prefill step
    run, rows = _run(rows)
    monkeypatch.setattr(step_rows, "window_rows", lambda _: rows)
    f, b = roofline_sparse.decode_rows_call(4 * 6208 * 8, 16, 128)
    assert roofline_sparse.traced_work(run) == pytest.approx(roofline.least_time_s(f, b, run["peak"]))
    monkeypatch.setattr(step_rows, "window_rows", lambda _: [{"end_ts": 7.0}])      # a program without the counts
    assert roofline_sparse.traced_work(run) is None


def test_kernel_seconds_sums_the_kernels_events():
    events = [("ds_sparse_paged_attention", 0.0, 0.5, {}), ("ds_sparse_paged_attention.1", 1.0, 1.25, {}),
              ("ds_paged_attention", 2.0, 3.0, {}), ("fusion.3", 3.0, 4.0, {})]
    assert roofline_sparse.kernel_seconds({"events": events}) == pytest.approx(0.75)
