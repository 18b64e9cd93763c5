"""On the chip, at the size of the cell ``solaropen2_longctx``
(``benchmark/configs/solar-open2-250b-serve-1chip.json``: one period of four
layers at every published width, 40 of 320 experts held, bfloat16, 33 state
slots of 3 states of 4 MB): what the benchmark's ``correct`` cannot hold
(PERF.md section 2), held here by ``solar_open2_check.py``.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_solar_open2_on_chip.py -q -s

``DS_CHECK_SEED`` draws other weights and tokens.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
import row_groups_check  # noqa: E402
import solar_open2_check  # noqa: E402

#: (prompt, decode steps, state slot, first position compared): the cell's own check row in the last slot (67
#: chunks of the chunked form, then 64 steps of the kernel), and two shorter sequences that end their prompts
#: inside a chunk and decode beside the long one's prefill
ROWS = [(8576, 64, 32, 8320), (3000, 64, 1, 2752), (1100, 64, 17, 896)]


def _load(folder, name):
    with open(os.path.join(solar_open2_check.ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_state_mixers_and_a_held_expert_are_held_in_real_slots_under_the_published_initialisation():
    config, traffic = _load("configs", "solar-open2-250b-serve-1chip"), _load("traffic", "ctx_8k_32k_long_answer")
    out = solar_open2_check.readings(config, traffic, int(os.environ.get("DS_CHECK_SEED", 3000046701)), ROWS)
    per_row = solar_open2_check.report(out, ROWS)
    assert out["kernel_steps"] >= 64
    assert max(program for program, _, _ in per_row) < config["check"]["limits"]["long"], per_row
    # a limit set as the benchmark sets its own, three times the program's reading, calls every absence in every row
    assert all(change > 3 * program for program, zeroed, _ in per_row for change in zeroed.values()), per_row


def test_the_cells_two_group_programs_give_what_the_rectangle_gives_in_real_slots():
    """``step:b32:c1:b1:c128`` and ``step:b32:c1:b4:c128``, the programs of the
    cell's mixed steps (the decode group through ``ds_kda_update``, the prefill
    group through the chunked form), against the rectangle of the same rows,
    which takes the chunked form for all of them: logits and every array of
    the cache (``row_groups_check.py``)."""
    config, traffic = _load("configs", "solar-open2-250b-serve-1chip"), _load("traffic", "ctx_8k_32k_long_answer")
    # the check keeps the cache on the host between its programs, several copies of it: with the cell's arena of
    # 4.57 GB a one-chip machine's 40 GiB of host memory ran out (my chip run, PR 46); 9,000 pages hold the rows
    config["engine"]["kv"]["num_pages"] = 9000
    seed = int(os.environ.get("DS_CHECK_SEED", 3000046801))
    out = row_groups_check.readings(config, traffic, seed,
                                    lambda abstract: solar_open2_check.check_init(abstract, seed, "bfloat16", config),
                                    solar_open2_check.REAL_FROM)
    assert row_groups_check.report("solar_open2_check", out) < config["check"]["limits"]["long"], out


def test_a_prompt_fed_as_runs_of_four_rows_is_the_prompt_fed_a_chunk_a_step():
    """A run in the state slots (PR 50): 8,576 tokens as 16 rectangles of four
    consecutive chunks and one of three, every row behind a rectangle's first
    starting from the state and the convolution's tail the row before it
    leaves, against the same tokens a chunk a step in another slot: the last
    256 prompt positions (two continuing rows) and 64 decode steps against the
    float32 reference, the two ways against one another, and what the two
    slots hold at the end."""
    config, traffic = _load("configs", "solar-open2-250b-serve-1chip"), _load("traffic", "ctx_8k_32k_long_answer")
    seed = int(os.environ.get("DS_CHECK_SEED", 3000050701))
    out = solar_open2_check.run_readings(config, traffic, seed, 8576, 64, 8320)
    read = solar_open2_check.report_run(out)
    assert (out["run_steps"], out["a_chunk_a_step_steps"]) == (17 + 64, 67 + 64)
    limit = config["check"]["limits"]["long"]
    assert read["run"] < limit and read["a_chunk_a_step"] < limit and read["between"] < limit, read
    # a state that is not handed on reads as the reference without its state term does: far over the program's error
    assert read["without_state"] > 3 * read["run"] and read["kda"] < limit and read["conv"] < limit, read
