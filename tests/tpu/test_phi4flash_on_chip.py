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
import row_groups_check  # noqa: E402

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
    # the engine's step programs take the head over the sampled rows alone; the benchmark's check does not.  The two are
    # two programs of one step: compiled with the compiler's excess precision off they agree bit for bit (232 rows of
    # 232, PR 38), which holds the rows taken and the head; as served, 228 rows agree bit for bit and in four the
    # trunk's output differs in every channel, by 1.2e-3 to 1.03e-2 (a wrong row reads over 1): held to three times that
    # reading, in at most one row of ten (PERF.md section 6, PR 38, "After the review"; under 1e-3 before the flat axis)
    apart = [d for d in out["last_only_rows"] if d > 1e-3]
    assert out["last_only_exact"] < 1e-3 and out["last_only"] < 3e-2 and len(apart) <= len(out["last_only_rows"]) // 10, (
        out["last_only_exact"], out["last_only"], len(apart), len(out["last_only_rows"]))
    assert out["bucket"] < 3 * worst, (out["bucket"], worst)


def test_the_cells_two_group_programs_give_what_the_rectangle_gives_in_real_slots():
    """``step:b32:c1:b1:c128`` and ``step:b32:c1:b4:c128``, the programs of the
    cell's mixed steps, against the rectangle of the same rows: logits and
    every array of the cache (``row_groups_check.py``)."""
    config, traffic = _load("configs", "phi4-mini-flash-serve-1chip"), _load("traffic", "reason_short_in_long_out")
    seed = int(os.environ.get("DS_CHECK_SEED", 3000003801))
    out = row_groups_check.readings(
        config, traffic, seed, lambda abstract: phi4flash_check.check_init(abstract, seed, "bfloat16",
                                                                         config["num_hidden_layers"]),
        phi4flash_check.real_from(config))
    # two batches of one step differ by what either differs from the reference: held to the limit the rows above are
    assert row_groups_check.report("phi4flash_check", out) < 0.1, out
