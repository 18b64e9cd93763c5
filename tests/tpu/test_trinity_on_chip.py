"""On the chip, at the size of the cell ``trinity_mixed_queue``
(``benchmark/configs/trinity-large-preview-serve-1chip.json``: published
layers 0 and 8-11 at every published width, bfloat16, 33 state slots of four
rings of 321 pages, 40,000 pages under the one full layer): what the
benchmark's ``correct`` does not look at (slots other than 0, scattered
pages, several rows in a batch), and each of the reference's controls read
beside the program, by ``trinity_check.py``.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_trinity_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import trinity_check  # noqa: E402

#: (prompt, decode steps, state slot, first position compared): a row past two windows and a ring's first lap (5,136
#: rows) in the last slot, 36 chunks then 64 steps; a row that has just passed its window; a row under it, that ends
#: its prompt inside a chunk and decodes beside the others' prefill
ROWS = [(9152, 64, 32, 8896), (4600, 64, 1, 4344), (700, 64, 17, 444)]
PAST_A_WINDOW = (0, 1)          # the rows compared behind a window: the window's control shows there
LIMIT = 0.1


def _load(folder, name):
    with open(os.path.join(trinity_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_window_rotary_split_gate_norms_expert_and_scale_are_held_in_real_slots():
    config, traffic = _load("configs", "trinity-large-preview-serve-1chip"), _load("traffic", "short_long_one_queue")
    out = trinity_check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000054701)), ROWS)
    out["router_margin_min"] = config["check"]["router_margin_min"]
    per_row = trinity_check.report(out, ROWS)
    assert out["kernel_steps"] >= 64
    assert max(program for program, _, _ in per_row) < LIMIT, per_row
    # a limit set as the benchmark sets its own, three times the program's reading, calls every control
    for i, (program, changed, _) in enumerate(per_row):
        for control, moved in changed.items():
            if control == "window" and i not in PAST_A_WINDOW:
                continue
            assert moved > 3 * program, (i, control, per_row)


def test_the_cells_own_check_fails_each_control():
    """``correct`` as the cell decides it (the harness's row, ``benchmark/weights.py``, the file's limits): the
    program passes, the int8 control does not, and nor does the program against the reference with any of its
    controls but the absent expert, which moves too few positions for a 90th percentile to see."""
    config, traffic = _load("configs", "trinity-large-preview-serve-1chip"), _load("traffic", "short_long_one_queue")
    seed = int(os.environ.get("DS_CHECK_SEED", 3000054702))
    out = trinity_check.cell_readings(config, traffic, [seed])[seed]
    for group, limit in config["check"]["limits"].items():
        assert out["program"][group] < limit < out["control"][group], (group, out)
        for control in trinity_check.PERCENTILE:
            if control != "expert":
                assert out[control][group] > limit, (group, control, out)
