"""On the chip, at the size of the cell ``granite4h_sessions``
(``benchmark/configs/granite-4.0-h-micro-serve-1chip.json``: 40 layers, every
width, the whole vocabulary, bfloat16, 33 state slots of 36 states of 2 MB):
what the benchmark's ``correct`` cannot hold (PERF.md section 2), held here
by ``granite_hybrid_check.py``.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_granite_hybrid_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import granite_hybrid_check  # noqa: E402
import row_groups_check  # noqa: E402

#: (prompt, decode steps, state slot, first position compared): the cell's own check row in the last slot (22
#: chunks of the block form, then 64 steps of the kernel), and two shorter sequences that end their prompts
#: inside a chunk and decode beside the long one's prefill
ROWS = [(2816, 64, 32, 2560), (1500, 64, 1, 1280), (700, 64, 17, 512)]


def _load(folder, name):
    with open(os.path.join(granite_hybrid_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_state_mamba_and_attention_are_held_in_real_slots_under_the_published_initialisation():
    config, traffic = _load("configs", "granite-4.0-h-micro-serve-1chip"), _load("traffic", "sessions_short_in_long_out")
    out = granite_hybrid_check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000032701)), ROWS)
    per_row = granite_hybrid_check.report(out, ROWS)
    assert out["kernel_steps"] >= 64
    assert max(program for program, _ in per_row) < 0.1, per_row
    # a limit set as the benchmark sets its own, three times the program's reading, calls every kind's absence in every row
    assert all(change > 3 * program for program, zeroed in per_row for change in zeroed.values()), per_row


def test_the_cells_two_group_programs_give_what_the_rectangle_gives_in_real_slots():
    """``step:b32:c1:b1:c128`` and ``step:b32:c1:b4:c128``, the programs of the
    cell's mixed steps (the decode group through ``ds_ssd_update``, the prefill
    group through the block form), against the rectangle of the same rows,
    which takes the block form for all of them: logits and every array of the
    cache (``row_groups_check.py``)."""
    config = _load("configs", "granite-4.0-h-micro-serve-1chip")
    traffic = _load("traffic", "sessions_short_in_long_out")
    seed = int(os.environ.get("DS_CHECK_SEED", 3000032801))
    out = row_groups_check.readings(config, traffic, seed,
                                    lambda abstract: granite_hybrid_check.check_init(abstract, seed, "bfloat16"),
                                    granite_hybrid_check.REAL_FROM)
    # two batches of one step differ by what either differs from the reference: held to the limit the rows above are
    assert row_groups_check.report("granite_hybrid_check", out) < 0.1, out
