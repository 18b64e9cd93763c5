"""``tests/tpu/trinity_check.py`` is what the chip runs at the cell's size;
here its control flow at the configuration file's rehearsal size, bfloat16 as
served: three sequences in slots 3, 1 and 2 on scattered pages, one past two
laps of its rings, weights of a trained model's sizes, the reference with
each of its controls; and ``cell_readings``, the cell's own check read once
for each control, whose window shows at the cell's context and hardly at the
rehearsal's 200 tokens: here its control flow."""

import os
import sys

HERE = os.path.dirname(__file__)
sys.path[:0] = [os.path.join(HERE, "..", "..", "tpu"), os.path.join(HERE, "..", "..", "..", "benchmark")]


def _files():
    import run as bench
    config = bench.load_json("configs", "trinity-large-preview-serve-1chip.json")
    traffic = bench.load_json("traffic", "short_long_one_queue.json")
    return bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])


def test_check_in_real_slots_with_every_control_at_the_rehearsal_size():
    import trinity_check
    config, traffic = _files()
    traffic["prompt"]["clip"] = [8, 352]         # room for a row past two laps of a ring of 144 rows
    rows = [(330, 8, 3, 266), (70, 8, 1, 0), (200, 8, 2, 136)]
    out = trinity_check.readings(config, traffic, 3000054603, rows)
    per_row = trinity_check.report(out, rows)
    assert out["steps"] == 11 + 8 and out["kernel_steps"] == 8
    for i, (p90, changed, median) in enumerate(per_row):
        assert p90 < 0.15, per_row
        for control, moved in changed.items():
            if control == "window" and i == 1:
                assert moved < 1e-6              # nothing of a row of 78 tokens lies behind a window of 64... by much
                continue
            assert moved > 3 * median, (i, control, per_row)


def test_the_cells_own_check_read_once_a_control_at_the_rehearsal_size():
    import trinity_check
    config, traffic = _files()
    out = trinity_check.cell_readings(config, traffic, [3000054604], controls=("window", "gate"))[3000054604]
    assert set(out) == {"program", "control", "window", "gate"}
    for group, limit in config["check"]["limits"].items():
        assert out["program"][group] < limit, (group, out)
        assert out["program"][group] < out["gate"][group] and out["program"][group] < out["window"][group], out
