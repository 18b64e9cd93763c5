"""``roofline_swa`` on hand-worked shapes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_swa.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_swa  # noqa: E402
import run as bench  # noqa: E402

CFG = bench.load_json("configs", "trinity-large-preview-serve-1chip.json")


def test_shape_comes_from_the_configuration():
    assert roofline_swa.layer_kinds(CFG) == ["sliding_attention"] * 4 + ["full_attention"]
    assert roofline_swa.shape_of(CFG) == (48, 8, 128, 4, 1)
    assert roofline_swa.shape_of(bench.merge(CFG, CFG["rehearsal"])) == (4, 2, 32, 4, 1)


def test_a_decode_row_at_16k_reads_32k_key_rows_and_is_bound_by_its_bytes():
    # one query at position 16,383: 4,096 rows in each of four window layers, 16,384 in the full one
    f, b = roofline_swa.step_work(4096, 16384, 4096, 16384, 1, 48, 8, 128, 4, 1)
    assert f == 4 * 128 * 48 * (4 * 4096 + 16384) == 805_306_368
    assert b == 2 * 2 * 8 * 128 * 32768 + 2 * 2 * 48 * 128 * 5 == 134_217_728 + 122_880
    import peaks
    peak = peaks.match_device_kind("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > f / peak["bf16_flops"]       # 6 operations a byte


def test_a_chunk_of_128_at_8k_is_bound_by_its_operations():
    # positions 8,192 .. 8,319: every query sees 4,096 window rows; the full layer's pairs by hand
    pairs = sum(t + 1 for t in range(8192, 8320))
    f, b = roofline_swa.step_work(128 * 4096, pairs, 4096, 8320, 128, 48, 8, 128, 4, 1)
    assert f == 4 * 128 * 48 * (4 * 128 * 4096 + pairs)
    import peaks
    peak = peaks.match_device_kind("TPU v5 lite")
    assert f / peak["bf16_flops"] > b / peak["hbm_bytes_per_s"]


def _run():
    import peaks
    return {"config": CFG, "ticks": [(0.0, 10.0, 1, 0)], "reduced": {"window_s": 4.0},
            "peak": peaks.match_device_kind("TPU v5 lite")}


def test_traced_work_adds_the_steps_of_the_traced_stretch(monkeypatch):
    import roofline
    import step_rows
    zero = dict.fromkeys(roofline_swa.COUNTS, 0)
    rows = [{**zero, "end_ts": 5.0, "attn_rows_visible": 10**12},                       # before the stretch
            {**zero, "end_ts": 7.0, "window_rows_visible": 4096, "attn_rows_visible": 16384, "ring_rows_seen": 4096,
             "full_rows_seen": 16384, "tokens_real": 1},
            {**zero, "end_ts": 9.0, "window_rows_visible": 100, "attn_rows_visible": 100, "ring_rows_seen": 100,
             "full_rows_seen": 100, "tokens_real": 1}]
    run = _run()
    monkeypatch.setattr(step_rows, "window_rows", lambda _: rows)
    want = sum(roofline.least_time_s(*roofline_swa.step_work(*(r[c] for c in roofline_swa.COUNTS), 48, 8, 128, 4, 1),
                                     run["peak"]) for r in rows[1:])
    assert roofline_swa.traced_work(run) == pytest.approx(want)
    monkeypatch.setattr(step_rows, "window_rows", lambda _: [{"end_ts": 7.0, "attn_rows_visible": 5}])
    assert roofline_swa.traced_work(run) is None                                        # a program without the counts


def _call(name, arena, t0, t1):
    text = (f"%{name} = bf16[32,8,8,128]{{3,2,1,0}} custom-call(s32[32,2082]{{1,0}} %t, s32[32]{{0}} %s, "
            f"bf16[32,8,8,128]{{3,2,1,0}} %q, {arena}{{5,4,3,2,1,0}} %arena), custom_call_target=\"tpu_custom_call\"")
    return (text, t0, t1, {})


def test_kernel_seconds_tells_the_two_kinds_by_the_arena_they_are_handed():
    events = [_call("ds_paged_attention.1", "bf16[4,9538,16,2,8,128]", 0.0, 0.5),
              _call("ds_paged_attention.2", "bf16[1,40000,16,2,8,128]", 1.0, 1.25),
              _call("ds_paged_attention", "bf16[4,9538,16,2,8,128]", 2.0, 2.125),
              ("%fusion.3 = bf16[4,9538,16,2,8,128]{5,4,3,2,1,0} fusion(bf16[8]{0} %x), kind=kLoop", 3.0, 4.0, {})]
    got = roofline_swa.kernel_seconds({"events": events}, CFG)
    assert got == {"all": pytest.approx(0.875), "window": pytest.approx(0.625), "full": pytest.approx(0.25)}
    assert roofline_swa.kernel_seconds({"events": events})["all"] == pytest.approx(0.875)
    assert roofline_swa.kernel_seconds({"events": events[3:]}, CFG) == {"all": 0.0, "window": 0.0, "full": 0.0}
