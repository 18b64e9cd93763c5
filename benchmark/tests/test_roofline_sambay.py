"""``roofline_sambay`` on hand-worked shapes (``selfcheck.py`` (d) does this
for ``roofline.py``; that file is not this PR's to edit):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_roofline_sambay.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import roofline_sambay  # noqa: E402

# query heads, key heads, head size, window, window layers, layers that read the shared pages: the published sizes
SHAPE = (40, 20, 64, 512, 8, 8)
PAIR = 4 * 128 * 20           # operations a visible row: 20 query pairs, two products over 128 lanes
ROW = 2 * 128 * 10            # bytes of one row's keys (or values): 10 key pairs of 128 lanes, bfloat16
Q_IO = 2 * 2 * 128 * 40       # bytes a query in and its output out: 40 heads of 128 lanes


@pytest.mark.parametrize("t, rows", [(0, 1), (510, 511), (511, 512), (512, 512), (3079, 512)])
def test_a_window_layer_sees_the_last_512_rows(t, rows):
    assert roofline_sambay.window_rows(t, 512) == rows


def test_shape_comes_from_the_configuration():
    cfg = {"num_attention_heads": 40, "num_key_value_heads": 20, "hidden_size": 2560, "sliding_window": 512,
           "num_hidden_layers": 32}
    assert roofline_sambay.shape_of(cfg) == SHAPE


def test_one_decode_query_2500_tokens_deep():
    # the window layers see 512 rows, the shared pages' readers 2,501
    f, b = roofline_sambay.attention_call(1, 2500, *SHAPE)
    assert f == PAIR * (8 * 512 + 8 * 2501)
    assert b == 8 * (2 * ROW * 512 + Q_IO) + 8 * (2 * ROW * 2501 + Q_IO)


def test_a_prefill_chunk_across_the_window_s_edge():
    # 128 queries from 448: positions 448..510 see t + 1 rows in a window layer (63 of them), 511..575 see 512 (65)
    f, b = roofline_sambay.attention_call(128, 448, *SHAPE)
    pairs_w = sum(range(449, 512)) + 65 * 512
    pairs_s = sum(range(449, 577))
    assert f == PAIR * 8 * (pairs_w + pairs_s)
    assert b == 8 * (2 * ROW * 512 + 128 * Q_IO) + 8 * (2 * ROW * 576 + 128 * Q_IO)


def test_prefill_and_decode_add_their_calls_up():
    small = (4, 2, 32, 32, 2, 2)      # one key pair, two query pairs, heads of 32, window 32
    f, _ = roofline_sambay.prefill(150, 32, *small)
    assert f == 4 * 64 * 2 * 2 * (sum(min(t + 1, 32) for t in range(150)) + sum(t + 1 for t in range(150)))
    f, b = roofline_sambay.decode(150, 4, *small)      # feeds positions 150, 151, 152
    assert f == 4 * 64 * 2 * 2 * (3 * 32 + 151 + 152 + 153)
    row, q_io = 2 * 64 * 1, 2 * 2 * 64 * 4
    assert b == sum(2 * (2 * row * 32 + q_io) + 2 * (2 * row * (t + 1) + q_io) for t in (150, 151, 152))


def test_the_scan_reads_and_writes_its_state_once_a_call():
    f, b = roofline_sambay.ssm_scan_call(128, 5120, 16)
    assert f == 7 * 128 * 5120 * 16
    assert b == 4 * (2 * 5120 * 16 + 3 * 128 * 5120 + 2 * 128 * 16)


def test_traced_work_counts_the_overlap_with_the_last_stretch():
    traffic = {"rate_per_s": 1.0, "block_s": 2, "lead_in_s": 0, "mix_seed": 3,
               "prompt": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 100, "hi": 200}], "clip": [100, 200]},
               "output": {"mixture": [{"weight": 1.0, "dist": "uniform", "lo": 4, "hi": 8}], "clip": [4, 8]}}
    cfg = {"vocab_size": 512, "num_attention_heads": 4, "num_key_value_heads": 2, "hidden_size": 128,
           "sliding_window": 32, "num_hidden_layers": 8, "engine": {"scheduler": {"prefill_chunk": 32}}}
    import traffic_gen
    sched = traffic_gen.serving_schedule(traffic, 8.0, 1, 512)
    n = len(sched)
    # every request admitted when due, its first token 0.5 s later, 0.1 s a token after that
    samples = {"gen_late_ms": [0.0] * n, "queue_wait_ms": [0.0] * n, "ttft_ms": [500.0] * n, "tpot_ms": [100.0] * n}
    run = {"config": cfg, "traffic": traffic, "seconds": 8.0, "seed": 1, "samples": samples, "failed": 0}
    work = roofline_sambay.traced_work(run)
    shape = roofline_sambay.shape_of(cfg)
    want = 0.0
    for r in sched:      # the stretch is the window's last 4 s
        p, o = len(r["prompt"]), r["max_new_tokens"]
        first, end = r["due"] + 0.5, r["due"] + 0.5 + 0.1 * (o - 1)
        for a, b, w in ((r["due"], first, roofline_sambay.prefill(p, 32, *shape)), (first, end, roofline_sambay.decode(p, o, *shape))):
            want += max(0.0, min(b, 8.0) - max(a, 4.0)) / (b - a) * w[0]
    assert work["flops"] == pytest.approx(want) and 0 < work["flops"] and 0 < work["bytes"]
    assert roofline_sambay.traced_work({**run, "failed": 1}) is None
