"""``roofline_eva`` on hand-worked shapes (``selfcheck.py`` (d) does this for
``roofline.py``; that file is not this PR's to edit):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_eva.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_eva  # noqa: E402

SHAPE = (32, 128, 2048, 16)   # heads, head size, window, chunk: the published sizes


@pytest.mark.parametrize("t, rows", [(0, 1), (2047, 2048), (2048, 1 + 128), (6143, 2048 + 256), (6144, 1 + 384),
                                     (24967, 24967 - 12 * 2048 + 1 + 12 * 128)])
def test_visible_rows_are_the_window_so_far_plus_a_summary_a_chunk_before_it(t, rows):
    assert roofline_eva.visible_rows(t, 2048, 16) == rows


def test_one_decode_query_deep_in_the_fourth_window():
    # position 7000 = window 3, offset 856: 857 exact rows + 3 x 128 summaries = 1,241 visible rows
    f, b = roofline_eva.eva_attention_call(1, 7000, *SHAPE)
    assert f == 4 * 128 * 32 * 1241 and b == 2 * 128 * 32 * (2 * 1241 + 2)


def test_a_prefill_chunk_at_the_start_of_the_second_window():
    # 128 queries from 2048: the first sees 1 + 128 rows, the last 128 + 128; 128 x 129 + 128 x 127 / 2 pairs
    f, b = roofline_eva.eva_attention_call(128, 2048, *SHAPE)
    assert f == 4 * 128 * 32 * (128 * 129 + 8128) and b == 2 * 128 * 32 * (2 * 256 + 2 * 128)


def test_a_call_across_a_window_is_refused():
    with pytest.raises(ValueError):
        roofline_eva.eva_attention_call(128, 2000, *SHAPE)


def test_prefill_and_decode_add_their_calls_up():
    small = (2, 4, 64, 8)   # windows of 64, chunks of 8: 8 summaries a window
    by_hand = sum(roofline_eva.visible_rows(t, 64, 8) for t in range(150))
    f, _ = roofline_eva.eva_prefill(150, 32, *small)
    assert f == 4 * 4 * 2 * by_hand
    f, b = roofline_eva.eva_decode(150, 4, *small)   # feeds positions 150, 151, 152
    rows = [roofline_eva.visible_rows(t, 64, 8) for t in (150, 151, 152)]
    assert f == 4 * 4 * 2 * sum(rows) and b == 2 * 4 * 2 * sum(2 * r + 2 for r in rows)


def test_traced_work_counts_the_overlap_with_the_last_stretch():
    traffic = {"rate_per_s": 1.0, "block_s": 2, "lead_in_s": 0, "mix_seed": 3,
               "prompt": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 100, "hi": 200}], "clip": [100, 200]},
               "output": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 4, "hi": 8}], "clip": [4, 8]}}
    cfg = {"vocab_size": 320, "num_attention_heads": 2, "hidden_size": 8, "window_size": 64, "chunk_size": 8,
           "num_hidden_layers": 3, "engine": {"scheduler": {"prefill_chunk": 32}}}
    import traffic_gen
    sched = traffic_gen.serving_schedule(traffic, 8.0, 1, 320)
    n = len(sched)
    # every request: admitted when due, first token 1 s later, done 1 s after that
    samples = {"gen_late_ms": [0.0] * n, "queue_wait_ms": [0.0] * n, "ttft_ms": [1000.0] * n,
               "tpot_ms": [1000.0 / (r["max_new_tokens"] - 1) for r in sched]}
    run = {"config": cfg, "traffic": traffic, "seconds": 8.0, "seed": 1, "samples": samples, "failed": 0}
    got = roofline_eva.traced_work(run)
    want = 0.0
    for r in sched:   # the stretch is [4, 8]
        for a, work in ((r["due"], roofline_eva.eva_prefill(len(r["prompt"]), 32, 2, 4, 64, 8)),
                        (r["due"] + 1.0, roofline_eva.eva_decode(len(r["prompt"]), r["max_new_tokens"], 2, 4, 64, 8))):
            want += max(0.0, min(a + 1.0, 8.0) - max(a, 4.0)) * work[0]
    assert got["flops"] == pytest.approx(3 * want) and got["flops"] > 0
    assert roofline_eva.traced_work({**run, "failed": 1}) is None
