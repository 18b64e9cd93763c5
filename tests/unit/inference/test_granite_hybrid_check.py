"""``tests/tpu/granite_hybrid_check.py`` is what the chip runs at the cell's
size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered pages,
the published Mamba-2 initialisation, the reference without the state term,
without the Mamba mixers and without the attention mixers."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def test_check_in_real_slots_under_the_published_initialisation_at_the_rehearsal_size():
    import granite_hybrid_check
    import run as bench
    config = bench.load_json("configs", "granite-4.0-h-micro-serve-1chip.json")
    traffic = bench.load_json("traffic", "sessions_short_in_long_out.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = granite_hybrid_check.readings(config, traffic, 3000032601, rows)
    per_row = granite_hybrid_check.report(out, rows)
    assert out["steps"] == 7 + 8 and out["kernel_steps"] == 8
    assert all(program < 0.03 and all(change > 3 * program for change in zeroed.values()) for program, zeroed in per_row), per_row
    # the same engine's two-group programs against the rectangle (tests/tpu/row_groups_check.py), two rows decoding
    # beside one and two that prefill
    import row_groups_check
    out = row_groups_check.readings(config, traffic, 3000032601,
                                    lambda abstract: granite_hybrid_check.check_init(abstract, 3000032601, "bfloat16"),
                                    granite_hybrid_check.REAL_FROM, rungs=(1, 2), decode_rows=2)
    assert row_groups_check.report("granite_hybrid_check", out) < 0.03, out
