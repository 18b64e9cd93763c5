"""``roofline_mla`` on hand-worked shapes (``selfcheck.py`` (d) does this for
``roofline.py``; that file is not this PR's to edit):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_mla.py -q
"""

import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import peaks  # noqa: E402
import roofline  # noqa: E402
import roofline_mla  # noqa: E402
import run as bench  # noqa: E402

PEAK = peaks.match_device_kind("TPU v5 lite")
SHAPE = dict(heads=32, rank=512, rope=64)


def test_shape_comes_from_the_configuration():
    cfg = bench.load_json("configs", "xing4.0-29b-a4b-serve-1chip.json")
    assert roofline_mla.shape_of(cfg) == (7, 32, 512, 64)
    assert roofline_mla.shape_of({**cfg, **cfg["rehearsal"]}) == (3, 4, 64, 16)


def test_a_pair_costs_what_the_form_needs():
    # one decode row over a context that then holds 1000 tokens: 1000 pairs
    f, b = roofline_mla.absorbed_call(1, 999, **SHAPE)
    assert f == 1000 * 32 * (576 + 512) * 2 == 1000 * 69_632
    assert b == 2 * (1000 * 576 + 32 * (576 + 512)) == 1_221_632      # the latents once, 32 queries in, 32 outputs out
    # 57 operations a byte: under the chip's 240, so the decode row is bound by the read of the latents
    assert 50 < f / b < 60 and roofline.least_time_s(f, b, PEAK) == b / PEAK["hbm_bytes_per_s"]
    # a chunk of 128 at the start of a prompt: 128 * 129 / 2 pairs; behind 16k of context: bound by the operations
    assert roofline_mla.absorbed_call(128, 0, **SHAPE)[0] == 8256 * 69_632
    f, b = roofline_mla.absorbed_call(128, 16384, **SHAPE)
    assert f == (128 * 16384 + 8256) * 69_632 and b == 2 * (16512 * 576 + 128 * 32 * 1088)
    assert roofline.least_time_s(f, b, PEAK) == f / PEAK["bf16_flops"]
    # the expanded form: 20,480 a pair and 8,388,608 for every cached row a call rebuilds keys and values from
    fe, be = roofline_mla.expanded_call(128, 16384, **SHAPE, nope=128, v=128)
    assert fe == (128 * 16384 + 8256) * 32 * (192 + 128) * 2 + 16512 * 512 * 32 * 256 * 2
    assert be == 2 * (16512 * 576 + 128 * 32 * (192 + 128))


def test_the_forms_cross_at_a_chunk_of_about_171_queries():
    cross = roofline_mla.crossover_chunk(**SHAPE, nope=128, v=128)
    assert abs(cross - 8_388_608 / (69_632 - 20_480)) < 1e-9 and 170 < cross < 171
    for chunk, cheaper in ((128, "absorbed"), (512, "expanded")):   # over a long context; the chunk's own pairs are few
        fa = roofline_mla.absorbed_call(chunk, 32768, **SHAPE)[0]
        fe = roofline_mla.expanded_call(chunk, 32768, **SHAPE, nope=128, v=128)[0]
        assert (fa < fe) == (cheaper == "absorbed"), (chunk, fa, fe)
    # at a chunk of 512 the expanded form does about half the operations
    assert 0.5 < fe / fa < 0.56


def _run(rows, end, window_s):
    cfg = bench.load_json("configs", "xing4.0-29b-a4b-serve-1chip.json")
    return {"config": cfg, "peak": PEAK, "ticks": [(0.0, end, 1, 0)], "reduced": {"window_s": window_s, "events": []},
            "_rows": rows}


def test_traced_work_adds_the_steps_least_times(monkeypatch):
    import step_rows
    rows = [{"end_ts": 1.0, "attn_rows_visible": 10**9, "mla_rows_read": 10**6, "tokens_real": 10**3},   # before the stretch
            {"end_ts": 7.0, "attn_rows_visible": 128 * 16384 + 8256, "mla_rows_read": 16512, "tokens_real": 128},
            {"end_ts": 9.0, "attn_rows_visible": 16 * 1000, "mla_rows_read": 16 * 1000, "tokens_real": 16}]
    run = _run(rows, end=10.0, window_s=4.0)
    monkeypatch.setattr(step_rows, "window_rows", lambda r: r["_rows"])
    assert [r["end_ts"] for r in roofline_mla.traced_rows(run)] == [7.0, 9.0]
    prefill = roofline.least_time_s(*roofline_mla.absorbed_call(128, 16384, **SHAPE), PEAK)
    decode = 16 * roofline.least_time_s(*roofline_mla.absorbed_call(1, 999, **SHAPE), PEAK)
    assert abs(roofline_mla.traced_work(run) - 7 * (prefill + decode)) < 1e-12
    # records without the count (a program without the latent twin) give nothing to read
    monkeypatch.setattr(step_rows, "window_rows", lambda r: [{"end_ts": 9.0, "attn_rows_visible": 1, "tokens_real": 1}])
    assert roofline_mla.traced_work(run) is None
    monkeypatch.setattr(step_rows, "window_rows", lambda r: None)
    assert roofline_mla.traced_work(run) is None


def test_kernel_seconds_sums_the_events_named_ds_mla():
    ev = lambda name, t0, t1: (f"%{name} = bf16[16,32,512]{{2,1,0}} custom-call(s32[16,2066]{{1,0}} %a), "  # noqa: E731
                               'custom_call_target="tpu_custom_call"', t0, t1, {})
    reduced = {"events": [ev("ds_mla_absorbed.3", 0.0, 1.0), ev("ds_mla_absorbed.4", 2.0, 2.5),
                          ev("ds_paged_attention.1", 3.0, 4.0), ("fusion.7", 4.0, 5.0, {})]}
    assert roofline_mla.kernel_seconds(reduced) == 1.5
    assert roofline_mla.kernel_seconds({"events": reduced["events"][2:]}) == 0
