"""``tests/tpu/granite_moe_hybrid_check.py`` is what the chip runs at the
cell's size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered pages
beside a padding row, the published Mamba-2 initialisation, the reference
without the routed experts, without the shared MLP and without the state."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def test_check_in_real_slots_under_the_published_initialisation_at_the_rehearsal_size():
    import granite_moe_hybrid_check as check
    import run as bench
    config = bench.load_json("configs", "granite-4.0-h-small-serve-1chip.json")
    traffic = bench.load_json("traffic", "agent_turns_mid_in_short_out.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = check.readings(config, traffic, 3000056601, rows)
    per_row = check.report(out, rows, margin_min=0.02)
    assert out["steps"] == 7 + 8 and out["kernel_steps"] == 8
    assert all(program < 0.05 and all(change > 3 * program for change in gone.values()) for program, gone in per_row), \
        per_row
