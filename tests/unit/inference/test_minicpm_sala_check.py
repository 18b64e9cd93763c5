"""``tests/tpu/minicpm_sala_check.py`` is what the chip runs at the cell's
size; here its control flow at the configuration file's rehearsal size,
bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered pages,
weights at which the mixers show, the reference without the state term, with
a dense walk in the sparse layers' place and with the selection one block
further on; and ``cell_readings``, the cell's own check read five ways, whose
sparse faults show at the cell's context and not at the rehearsal's 440
tokens: here its control flow, and that the missing state term fails."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def test_check_in_real_slots_under_weights_that_show_the_mixers_at_the_rehearsal_size():
    import run as bench
    import minicpm_sala_check
    config = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    traffic = bench.load_json("traffic", "ctx_16k_64k_mid_answer.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(440, 8, 4, 376), (70, 8, 1, 0), (420, 8, 3, 390)]
    out = minicpm_sala_check.readings(config, traffic, 3000049603, rows)
    per_row = minicpm_sala_check.report(out, rows)
    assert out["steps"] == 14 + 8 and out["kernel_steps"] == 8
    for i, (p90, zeroed, median) in enumerate(per_row):
        assert p90 < 0.15 and zeroed["state"] > 3 * median, per_row
        if i != 1:          # the rows past the rehearsal's dense_len of 256
            assert zeroed["sparse"] > 3 * median and zeroed["shift"] > 3 * median, per_row


def test_the_cells_own_check_read_five_ways_at_the_rehearsal_size():
    import run as bench
    import minicpm_sala_check
    config = bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json")
    traffic = bench.load_json("traffic", "ctx_16k_64k_mid_answer.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    out = minicpm_sala_check.cell_readings(config, traffic, [3000049604])[3000049604]
    assert set(out) == {"program", "control", "state", "sparse", "shift"}
    for group, limit in config["check"]["limits"].items():
        assert out["program"][group] < out["control"][group] < limit < out["state"][group], (group, out)
        assert out["program"][group] <= out["sparse"][group] and out["program"][group] <= out["shift"][group], out
