"""``roofline_experts`` on hand-worked shapes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_experts.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import peaks  # noqa: E402
import roofline  # noqa: E402
import roofline_experts as rx  # noqa: E402
import run as bench  # noqa: E402

EXPERT = 3 * 4096 * 768          # elements of one expert's three matrices, the published sizes


def test_shape_comes_from_the_configuration():
    cfg = bench.load_json("configs", "granite-4.0-h-small-serve-1chip.json")
    assert rx.shape_of(cfg) == (10, 36, 72, 10, 4096, 768)
    assert rx.shape_of({**cfg, **cfg["rehearsal"]}) == (10, 4, 8, 3, 128, 64)
    assert rx.shape_of({**cfg, "router_experts": None})[2] == 36         # a bank that holds its whole router


def test_a_pass_reads_each_touched_expert_once_and_multiplies_the_chosen_rows():
    # a full decode bucket: 320 choices touch 0.989 of the bank, half of them fall on a held expert
    f, b = rx.call_work(320, 36, 72, 4096, 768)
    touched = 36 * (1 - (71 / 72) ** 320)
    assert touched == pytest.approx(35.59, abs=0.01)
    assert f == pytest.approx(160 * 2 * EXPERT) and b == pytest.approx(2 * EXPERT * touched + 2 * 2 * 4096 * 160)
    assert 2 * EXPERT == 18_874_368                                       # 18.9 MB an expert
    peak = peaks.match_device_kind("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > 10 * f / peak["bf16_flops"]      # a decode step is the bank's read
    # one row: ten choices, five held, under five experts touched
    f1, b1 = rx.call_work(10, 36, 72, 4096, 768)
    assert f1 == pytest.approx(5 * 2 * EXPERT) and 4.6 * 2 * EXPERT < b1 < 5.0 * 2 * EXPERT
    # four chunks of a prompt beside the bucket: the operations reach a third of the time of the bytes
    f4, b4 = rx.call_work(5440, 36, 72, 4096, 768)
    assert 0.2 < (f4 / peak["bf16_flops"]) / (b4 / peak["hbm_bytes_per_s"]) < 1.0      # still the bank's read


def test_a_fused_dispatch_is_its_rounds_and_a_step_without_experts_is_nothing():
    multi = {"path": "multi_decode", "rows_decode": 20, "tokens_real": 160, "expert_rows": 1600}
    assert rx.step_calls(multi) == [200.0] * 8
    mixed = {"path": "mixed", "rows_decode": 20, "tokens_real": 148, "expert_rows": 1480}
    assert rx.step_calls(mixed) == [1480]
    assert rx.step_calls({**mixed, "expert_rows": 0}) == []


def _run(rows, cfg):
    ticks = [(0.0, 10.0, 1, 0)]
    return {"config": cfg, "peak": peaks.match_device_kind("TPU v5 lite"), "ticks": ticks,
            "reduced": {"window_s": 4.0, "events": []}}, rows


def test_traced_work_adds_the_least_time_of_every_pass_of_every_layer(monkeypatch):
    cfg = bench.load_json("configs", "granite-4.0-h-small-serve-1chip.json")
    rows = [{"path": "multi_decode", "rows_decode": 32, "tokens_real": 256, "expert_rows": 2560, "end_ts": 9.0},
            {"path": "mixed", "rows_decode": 30, "tokens_real": 158, "expert_rows": 1580, "end_ts": 9.5}]
    run, _ = _run(rows, cfg)
    import roofline_mla
    monkeypatch.setattr(roofline_mla, "traced_rows", lambda run: rows)
    peak = run["peak"]
    want = 10 * (8 * roofline.least_time_s(*rx.call_work(320, 36, 72, 4096, 768), peak)
                 + roofline.least_time_s(*rx.call_work(1580, 36, 72, 4096, 768), peak))
    assert rx.traced_work(run) == pytest.approx(want)
    assert 0.05 < want < 0.12                         # nine passes of ten layers at 0.84 ms of bank a layer
    monkeypatch.setattr(roofline_mla, "traced_rows", lambda run: None)
    assert rx.traced_work(run) is None
    assert rx.traced_work({**run, "config": {**cfg, "num_local_experts": 0}}) is None


def test_kernel_seconds_finds_both_forms_and_nothing_else():
    cfg = bench.load_json("configs", "granite-4.0-h-small-serve-1chip.json")
    text = "%{} = {} fusion({} %p), kind=kOutput, calls=%c"
    events = [
        ("%ds_gmm.3 = bf16[320,768]{1,0} custom-call(bf16[320,4096]{1,0} %a), custom_call_target=\"tpu_custom_call\"",
         0.0, 1.0, {}),
        (text.format("fusion.1", "bf16[36,32,768]{2,1,0}", "bf16[32,4096]{1,0}"), 1.0, 3.0, {}),
        (text.format("fusion.2", "f32[32,4096]{1,0}", "f32[36,32,4096]{2,1,0}"), 3.0, 3.5, {}),
        (text.format("fusion.3", "bf16[32,8192]{1,0}", "bf16[32,4096]{1,0}"), 3.5, 9.0, {}),
        (text.format("fusion.4", "bf16[32,4096]{1,0}", "bf16[32,4096]{1,0}"), 9.0, 9.25, {"tf_op": "jit/ds_experts_dense/dot"}),
        ("%while.1 = (bf16[36,32,768]{2,1,0}) while((bf16[36,32,768]{2,1,0}) %t), body=%b", 0.0, 9.0, {}),
    ]
    got = rx.kernel_seconds({"events": events}, cfg)
    assert got == {"grouped": 1.0, "dense": 2.75, "all": 3.75}
