"""``tests/tpu/solar_open2_check.py`` is what the chip runs at the cell's
size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered pages,
the published KDA initialisation, the reference without the state term,
without the KDA mixers, without the GQA mixer and with all but one of the
held experts."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def test_check_in_real_slots_under_the_published_initialisation_at_the_rehearsal_size():
    import run as bench
    import solar_open2_check
    config = bench.load_json("configs", "solar-open2-250b-serve-1chip.json")
    traffic = bench.load_json("traffic", "ctx_8k_32k_long_answer.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = solar_open2_check.readings(config, traffic, 3000046603, rows)
    per_row = solar_open2_check.report(out, rows)
    assert out["steps"] == 7 + 8 and out["kernel_steps"] == 8
    # with 8 of 16 experts held and 4 a token a choice that bfloat16 flips moves a position by 0.1-0.3, and one row in
    # three has more than a tenth of such positions: the miniature holds the median, the chip's run the 90th percentile
    assert all(p90 < 0.2 and all(change > 3 * median for change in zeroed.values()) for p90, zeroed, median in per_row), \
        per_row
