"""``tests/tpu/phi4flash_check.py`` is what the chip runs at the cell's
size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered
pages, the head over the sampled rows against the all-position logits at
both batches.  At a width of 128 the matrices' 0.02 gives every product a
gain of a quarter, so of the mixer kinds only those the residual is made
of show here; the chip's run holds all six."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def test_check_in_real_slots_under_weights_for_every_mixer_at_the_rehearsal_size():
    import phi4flash_check
    import run as bench
    config = bench.load_json("configs", "phi4-mini-flash-serve-1chip.json")
    traffic = bench.load_json("traffic", "reason_short_in_long_out.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = phi4flash_check.readings(config, traffic, 3000003601, rows)
    per_row = phi4flash_check.report(out, rows)
    assert out["steps"] == 7 + 8 and out["last_only"] < 1e-5 and out["last_only_exact"] < 1e-5 and out["bucket"] < 0.03
    assert all(program < 0.02 and all(zeroed[kind] > 3 * program for kind in ("mamba", "window", "full", "cross"))
               for program, zeroed in per_row)
