"""On the chip, at the size of the cell ``phi4flash_reason``
(``benchmark/configs/phi4-mini-flash-serve-1chip.json``: 32 layers, every
width, the whole vocabulary, bfloat16, 33 state slots): what the benchmark's
``correct`` cannot hold (PERF.md section 2), held here by
``phi4flash_check.py``.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_phi4flash_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import phi4flash_check  # noqa: E402

#: (prompt, decode steps, state slot, first position compared): the cell's own check row in the last slot (its
#: rings wrap five times, the recurrence runs through 22 chunks, the shared pages are 2.8k rows deep), and two
#: shorter sequences that end their prompts inside a chunk and decode beside the long one's prefill.  Compared
#: behind 512 tokens and more, as the benchmark's rows are: the first positions of a row read 0.08-0.10 in
#: bfloat16, three times the deep ones (PERF.md section 2)
ROWS = [(2816, 64, 32, 2560), (1500, 64, 1, 1280), (700, 64, 17, 512)]


def _load(folder, name):
    with open(os.path.join(phi4flash_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_every_mixer_kind_is_held_in_real_slots_and_the_sampled_rows_head_agrees():
    config, traffic = _load("configs", "phi4-mini-flash-serve-1chip"), _load("traffic", "reason_short_in_long_out")
    out = phi4flash_check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000003701)), ROWS)
    per_row = phi4flash_check.report(out, ROWS)
    worst = max(program for program, _ in per_row)
    assert worst < 0.1, per_row
    # a limit set as the benchmark sets its own, three times the program's reading, calls every kind's absence in every row
    assert all(change > 3 * program for program, zeroed in per_row for change in zeroed.values()), per_row
    # the engine's step programs take the head over the sampled rows alone; the benchmark's check does not
    assert out["last_only"] < 1e-3 and out["bucket"] < 3 * worst, (out["last_only"], out["bucket"], worst)
