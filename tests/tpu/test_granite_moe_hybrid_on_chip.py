"""On the chip, at the size of the cell ``granite4hs_agent_turns``
(``benchmark/configs/granite-4.0-h-small-serve-1chip.json``: one period of ten
layers at every published width, 36 of 72 experts held, half the vocabulary,
bfloat16, 33 state slots of 9 states of 4 MB): what the benchmark's ``correct``
cannot hold (PERF.md section 2), held here by ``granite_moe_hybrid_check.py``.

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_granite_moe_hybrid_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import granite_moe_hybrid_check as check  # noqa: E402
import row_groups_check  # noqa: E402

#: (prompt, decode steps, state slot, first position compared): the cell's own check row in the last slot (31
#: chunks of the block form, then 64 steps of the kernel), and two shorter sequences that end their prompts
#: inside a chunk and decode beside the long one's prefill
ROWS = [(3968, 64, 32, 3456), (1500, 64, 1, 1280), (700, 64, 17, 512)]
#: the router margin under which a position is left out, as the cell's file has it for its own check
CONFIG, TRAFFIC = "granite-4.0-h-small-serve-1chip", "agent_turns_mid_in_short_out"


def _load(folder, name):
    with open(os.path.join(check.granite_hybrid_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_routed_shared_and_state_are_held_in_real_slots_under_the_published_initialisation():
    config, traffic = _load("configs", CONFIG), _load("traffic", TRAFFIC)
    out = check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000056701)), ROWS)
    per_row = check.report(out, ROWS, config["check"]["router_margin_min"])
    assert out["kernel_steps"] >= 64
    assert max(program for program, _ in per_row) < 0.1, per_row
    # a limit set as the benchmark sets its own, three times the program's reading, calls every term's absence in every row
    assert all(change > 3 * program for program, gone in per_row for change in gone.values()), per_row


def test_the_cells_two_group_programs_give_what_the_rectangle_gives_in_real_slots():
    """``step:b32:c1:b1:c128`` and ``step:b32:c1:b4:c128``, the programs of the
    cell's mixed steps (the router over one flat axis of 160 and of 544 slots:
    the dense form and the sorted one), against the rectangle of the same rows:
    logits and every array of the cache (``row_groups_check.py``)."""
    config, traffic = _load("configs", CONFIG), _load("traffic", TRAFFIC)
    seed = int(os.environ.get("DS_CHECK_SEED", 3000056801))
    out = row_groups_check.readings(config, traffic, seed, lambda abstract: check.check_init(abstract, seed, "bfloat16"),
                                    check.REAL_FROM)
    assert row_groups_check.report("granite_moe_hybrid_check", out) < 0.1, out
