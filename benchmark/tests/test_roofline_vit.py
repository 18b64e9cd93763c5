"""``roofline_vit`` on hand-worked shapes, and its alignment of encode
records with the runs of the tower's programs in a trace:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_vit.py -q
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import peaks  # noqa: E402
import roofline  # noqa: E402
import roofline_vit  # noqa: E402
import run as bench  # noqa: E402

PEAK = peaks.match_device_kind("TPU v5 lite")
CFG = bench.load_json("configs", "kimi-vl-a3b-serve-1chip.json")
VC = CFG["vision_config"]


def test_weights_are_the_parameter_trees():
    # tests/unit/inference/test_kimi_vl.py holds the tree to 416,866,032 + 30,679,808
    assert roofline_vit.weights(VC, 2048) == 416_866_032 + 30_679_808


def test_an_image_of_4096_patches():
    f, b = roofline_vit.encode_call(4096, VC, 2048)
    a_layer = 2 * 1152 * 3456 + 2 * 1152 * 1152 + 4 * 1152 * 4304          # 30,449,664 a patch and layer
    assert a_layer == 30_449_664
    products = 4096 * (2 * 588 * 1152 + 27 * a_layer)
    pairs = 27 * 4 * 4096 ** 2 * 72 * 16
    projector = 1024 * (2 * 4608 ** 2 + 2 * 4608 * 2048)
    assert f == products + pairs + projector
    assert 5.4e12 < f < 5.6e12 and 0.37 < pairs / f < 0.39                   # 5.5 TFLOP, 38% of them the pairs
    assert b == 2 * (447_545_840 + 4096 * 588 + 1024 * 2048)
    # 6,100 operations a byte: bound by its operations, 28 ms at the chip's peak
    assert roofline.least_time_s(f, b, PEAK) == f / PEAK["bf16_flops"]
    # an image of 1,024 patches: a sixteenth of the pairs, a quarter of the products; still bound by operations
    f1, b1 = roofline_vit.encode_call(1024, VC, 2048)
    assert f1 == 1024 * (2 * 588 * 1152 + 27 * a_layer) + pairs // 16 + 256 * (2 * 4608 ** 2 + 2 * 4608 * 2048)
    assert roofline.least_time_s(f1, b1, PEAK) == f1 / PEAK["bf16_flops"]
    # the rehearsal's tower: the same function of other shapes
    small = {**VC, **CFG["rehearsal"]["vision_config"]}
    assert roofline_vit.encode_call(16, small, 128)[0] == \
        16 * (2 * 588 * 64 + 2 * (2 * 64 * 192 + 2 * 64 * 64 + 4 * 64 * 96)) + 2 * 4 * 16 ** 2 * 64 + 4 * (2 * 256 ** 2 + 2 * 256 * 128)


def test_records_are_aligned_with_the_traces_runs_by_their_buckets():
    from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

    class Clock:
        t = 0.0

        def now(self):
            return self.t

    clock = Clock()
    rec = StepAnatomy(clock=clock)
    for i, (real, bucket) in enumerate([(1024, 1024), (4048, 4096), (2016, 2048), (4096, 4096), (4048, 4096), (1024, 1024)]):
        clock.t = 10.0 + i
        rec.note_encode(f"vit:p{bucket}", real, bucket)
    # the trace holds the runs of records 1..4; record 5 was dispatched before its end and had not run yet
    modules = [(f"jit_ds_vit_p{b}(123)", 11.0 + i, 11.0 + i + 0.05, {}) for i, b in enumerate([4096, 2048, 4096, 4096])]
    modules.insert(2, ("jit_ds_step_b16_c1_b1_c128(9)", 12.5, 12.52, {}))
    run = {"reduced": {"modules": modules, "busy_s": 1.0}, "ticks": [(10.0, 15.5, 1, 0)], "peak": PEAK, "config": CFG}
    assert [b for b, _ in roofline_vit.program_events(run["reduced"])] == [4096, 2048, 4096, 4096]
    rows = roofline_vit.traced_encodes(run)
    assert [r["vit_patches_real"] for r in rows] == [4048, 2016, 4096, 4048]
    least = sum(roofline.least_time_s(*roofline_vit.encode_call(n, VC, 2048), PEAK) for n in (4048, 2016, 4096, 4048))
    assert abs(roofline_vit.traced_work(run) - least) < 1e-12
    assert abs(roofline_vit.program_seconds(run["reduced"]) - 0.2) < 1e-9
    # a parent's reduced trace (no modules line) and a trace without a tower's run: nothing to read
    assert roofline_vit.traced_encodes({**run, "reduced": {"busy_s": 1.0}}) is None
    assert roofline_vit.traced_work({**run, "reduced": {"modules": modules[2:3]}}) is None
    del rec
