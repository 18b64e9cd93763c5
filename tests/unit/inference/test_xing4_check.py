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
@pytest.mark.parametrize("runs", [False, True], ids=["a_chunk_a_step", "runs"])
def test_check_on_scattered_pages_in_two_row_groups_at_the_rehearsal_size(runs):
    import run as bench
    import xing4_check
    config = bench.load_json("configs", "xing4.0-29b-a4b-serve-1chip.json")
    traffic = bench.load_json("traffic", "doc_8k_32k_short_answer.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 136), (40, 8, 0)]
    if runs:   # a rung of four prefill rows between one and max_seqs, as the cell's scheduler has
        config["engine"]["scheduler"].update(max_seqs=8, decode_bucket=8)
    out = xing4_check.readings(config, traffic, 3000037001, rows, runs=runs)
    per_row = xing4_check.report(out, rows, 0.01)
    # a chunk a step: 7 steps of the long prompt, the short one's 2 beside them and 5 of its decode steps; with runs
    # 96 + 96 + 8 tokens of the long prompt in three steps, the short one decoding beside the third
    assert (out["steps"], out["mixed_steps"], out["run_steps"]) == ((3 + 8, 1, 2) if runs else (7 + 8, 5, 0))
    assert all(clear >= 10 and program < 0.05 and all(change > 3 * program for change in changed.values())
               for program, clear, changed in per_row), per_row
