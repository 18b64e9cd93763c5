"""``tests/tpu/solar_open2_check.py`` is what the chip runs at the cell's
size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered pages,
the published KDA initialisation, the reference without the state term,
without the KDA mixers, without the GQA mixer and with all but one of the
held experts; and one sequence fed as runs of four rows against the same
sequence a chunk a step."""

import os
import sys

import numpy as np

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def _rehearsal_files():
    import run as bench
    config = bench.load_json("configs", "solar-open2-250b-serve-1chip.json")
    traffic = bench.load_json("traffic", "ctx_8k_32k_long_answer.json")
    return bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])


def test_check_in_real_slots_under_the_published_initialisation_at_the_rehearsal_size():
    import solar_open2_check
    config, traffic = _rehearsal_files()
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = solar_open2_check.readings(config, traffic, 3000046603, rows)
    per_row = solar_open2_check.report(out, rows)
    assert out["steps"] == 7 + 8 and out["kernel_steps"] == 8
    # with 8 of 16 experts held and 4 a token a choice that bfloat16 flips moves a position by 0.1-0.3, and one row in
    # three has more than a tenth of such positions: the miniature holds the median, the chip's run the 90th percentile
    assert all(p90 < 0.2 and all(change > 3 * median for change in zeroed.values()) for p90, zeroed, median in per_row), \
        per_row


def test_a_prompt_fed_as_runs_is_the_prompt_fed_a_chunk_a_step_under_the_published_initialisation():
    """200 tokens in chunks of 32: two rectangles of four rows (the second's
    run ends inside its chunk and leaves a padding row) against seven steps
    of a chunk, then 8 decode steps each way."""
    import solar_open2_check
    config, traffic = _rehearsal_files()
    out = solar_open2_check.run_readings(config, traffic, 3000050603, 200, 8, 0, slots=(4, 2))
    read = solar_open2_check.report_run(out)
    assert (out["run_steps"], out["a_chunk_a_step_steps"]) == (2 + 8, 7 + 8) and len(out["run"]) == 208
    # the two ways differ by bfloat16's rounding through other programs, no more than either differs from float32,
    # and far less than a state that is not handed on would show
    assert read["between"] < 0.2 and read["kda"] < 0.05 and read["conv"] < 0.05, read
    assert read["without_state"] > 3 * float(np.median(out["run"])), read
