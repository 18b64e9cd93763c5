"""``tests/tpu/xing4_check.py`` is what the chip runs at the cell's size; here
its control flow at the configuration file's rehearsal size, bfloat16 as
served: two sequences on scattered pages, decode rows beside a prefilling row
as two row groups, the initialisation under which the stream mix, the
selection bias and the rotary part of the score show, and the three mutilated
references."""

import os
import sys

import pytest

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


@pytest.mark.slow   # a minute: four passes of the reference at 512 positions; the chip's run is the builder's
def test_check_on_scattered_pages_in_two_row_groups_at_the_rehearsal_size():
    import run as bench
    import xing4_check
    config = bench.load_json("configs", "xing4.0-29b-a4b-serve-1chip.json")
    traffic = bench.load_json("traffic", "doc_8k_32k_short_answer.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 136), (40, 8, 0)]
    out = xing4_check.readings(config, traffic, 3000037001, rows)
    per_row = xing4_check.report(out, rows, 0.01)
    assert out["steps"] == 7 + 8 and out["mixed_steps"] == 5
    assert all(clear >= 10 and program < 0.05 and all(change > 3 * program for change in changed.values())
               for program, clear, changed in per_row), per_row
