"""On the chip, at the size of the cell ``xing4_longdoc``
(``benchmark/configs/xing4.0-29b-a4b-serve-1chip.json``: one dense and six
expert layers at every published width, the whole vocabulary, bfloat16): what
the benchmark's ``correct`` cannot hold (PERF.md section 2), held here by
``xing4_check.py``.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_xing4_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import xing4_check  # noqa: E402

#: (prompt, decode steps, first position compared): the cell's own check row (67 chunks over up to 536 scattered
#: pages, then 128 decode steps at 8.6k of context), and a shorter sequence that ends its prompt inside a chunk and
#: decodes, one slot a step, beside the long one's prefill
ROWS = [(8576, 128, 8320), (700, 64, 512)]


#: the least router margin (in ``s + bias``, the reference's) of a position that is compared: under these weights too a
#: bfloat16 error picks another expert near a tie, in one position in three of the long row
MARGIN_MIN = 0.01


def _load(folder, name):
    with open(os.path.join(xing4_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("runs", [True, False], ids=["runs_of_four_chunks", "a_chunk_a_step"])
def test_the_stream_mix_the_selection_bias_and_the_rotary_score_are_held_at_8k_on_scattered_pages(runs):
    """``runs``: the long prompt's consecutive chunks in the spare rows of the
    rung of four (three beside the short prompt's row, four once that
    decodes), as the engine feeds a prompt since PR 42; else a chunk a step."""
    config, traffic = _load("configs", "xing4.0-29b-a4b-serve-1chip"), _load("traffic", "doc_8k_32k_short_answer")
    out = xing4_check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000037001)), ROWS, runs=runs)
    per_row = xing4_check.report(out, ROWS, MARGIN_MIN)
    limit = min(config["check"]["limits"].values())
    # a chunk a step: the short row decodes beside 61 of the long prompt's 67 chunks; with runs the long prompt takes
    # three rows a step beside the short prompt's six chunks and four a step beside its decode steps: 6 + 13 steps
    assert out["mixed_steps"] >= (12 if runs else 60) and all(clear >= 10 for _, clear, _ in per_row), per_row
    assert (out["run_steps"] >= 18) == runs, out["run_steps"]
    # at 8k of context the program is inside the limit the cell holds it to, and that limit calls every mutilated reference
    program, _, changed = per_row[0]
    assert program < limit and all(change > limit for change in changed.values()), per_row
    # a short context reads higher, program and control alike (PERF.md section 2, "Why no short context is compared"): there
    # every mutilated reference reads over three times the program, which is what a limit between them needs
    assert all(change > 3 * program for program, _, changed in per_row for change in changed.values()), per_row
