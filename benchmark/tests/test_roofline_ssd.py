"""``roofline_ssd`` on hand-worked shapes (``selfcheck.py`` (d) does this for
``roofline.py``; that file is not this PR's to edit):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_ssd.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_ssd  # noqa: E402
import run as bench  # noqa: E402

STATE = 64 * 64 * 128          # elements of one layer's state a sequence, the published sizes


def test_shape_comes_from_the_configuration():
    cfg = bench.load_json("configs", "granite-4.0-h-micro-serve-1chip.json")
    assert roofline_ssd.shape_of(cfg) == (36, 64, 64, 128)
    assert roofline_ssd.shape_of({**cfg, **cfg["rehearsal"]}) == (6, 16, 32, 32)


def test_one_position_reads_and_writes_the_state_once():
    f, b = roofline_ssd.ssd_update_call(64, 64, 128)
    assert f == 5 * STATE
    assert b == 4 * (2 * STATE + 2 * 64 * 64 + 2 * 128 + 64) == 4_228_352        # 2 x 2 MB and 34 KB
    # bytes bound it on every chip the benchmark knows: 5 operations against 8 bytes a state element
    import peaks
    peak = peaks.match_device_kind("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > f / peak["bf16_flops"]


def _run(seconds=8.0):
    import traffic_gen
    traffic = {"rate_per_s": 1.0, "block_s": 2, "lead_in_s": 0, "mix_seed": 3,
               "prompt": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 100, "hi": 200}], "clip": [100, 200]},
               "output": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 4, "hi": 8}], "clip": [4, 8]}}
    cfg = {"vocab_size": 512, "layer_types": ["mamba", "attention", "mamba", "mamba"], "mamba_n_heads": 4,
           "mamba_d_head": 8, "mamba_d_state": 16}
    sched = traffic_gen.serving_schedule(traffic, seconds, 1, 512)
    n = len(sched)
    # every request's first token 0.5 s after it was due, 0.1 s a token after that
    samples = {"gen_late_ms": [0.0] * n, "queue_wait_ms": [0.0] * n, "ttft_ms": [500.0] * n, "tpot_ms": [100.0] * n}
    return {"config": cfg, "traffic": traffic, "seconds": seconds, "seed": 1, "samples": samples, "failed": 0}, sched


def test_traced_work_counts_the_decode_tokens_inside_the_last_stretch():
    run, sched = _run()
    tokens = 0.0
    for r in sched:      # the stretch is the window's last 4 s
        first, n = r["due"] + 0.5, r["max_new_tokens"] - 1
        tokens += max(0.0, min(first + 0.1 * n, 8.0) - max(first, 4.0)) / (0.1 * n) * n
    f, b = roofline_ssd.ssd_update_call(4, 8, 16)
    work = roofline_ssd.traced_work(run)
    assert tokens > 0 and work["flops"] == pytest.approx(3 * tokens * f) and work["bytes"] == pytest.approx(3 * tokens * b)
    assert roofline_ssd.traced_work({**run, "failed": 1}) is None


def test_the_reader_finds_nothing_where_the_program_has_no_such_kernel():
    """A parent of the PR that brought the kernel: no event of that name, no metric, no error."""
    reader = bench.reader("layer_metrics", "ssd_update_roofline")
    run, _ = _run()
    hlo = '%ds_paged_attention.3 = bf16[32,4,8,128]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call"'
    reduced = {"events": [(hlo, 0.0, 0.002)]}
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert reader({**run, "reduced": reduced, "peak": peak}) is None
    assert reader({**run, "reduced": None, "peak": peak}) is None
    mine = hlo.replace("ds_paged_attention.3", "ds_ssd_update.7")
    share = reader({**run, "reduced": {"events": [(mine, 0.0, 0.002), (hlo, 0.002, 0.5)]}, "peak": peak})
    work = roofline_ssd.traced_work(run)
    assert share == pytest.approx(100.0 * work["bytes"] / 819e9 / 0.002)
