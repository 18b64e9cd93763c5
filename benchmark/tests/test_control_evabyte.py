"""The control of ``correct`` for ``evabyte_doc``, as ``test_control.py`` keeps
it for the other cells (that file is not this PR's to edit): at the
configuration's ``rehearsal`` size on the CPU the program's logits pass and
the int8 control fails one limit, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_evabyte.py -q

The ``long`` group lies in the third window (positions 700-763 of windows of
256), behind 32 summary rows; the 8 decode steps cross position 768, where
the ring wraps and 16 more summaries become visible at once.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

# between the two readings at the rehearsal size (program 0.0063 to 0.0072, control 0.028 to 0.031)
SMALL_LIMIT = 0.014
SEEDS = (0, 1, 2 ** 31 + 5)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_logits_separate_program_from_int8_control(seed):
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    import harness
    import run
    from kinds import serve_open_loop as kind
    cfg = run.load_json("configs", "evabyte-6.5b-serve-1chip.json")
    cfg = run.merge(cfg, cfg["rehearsal"])
    traffic = run.load_json("traffic", "bytes_doc.json")
    traffic = run.merge(traffic, traffic["rehearsal"])
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    ref = kind.reference_logits(cfg, params, rows)
    control = [logits for logits, _ in kind.reference_logits(cfg, params, rows, mode="int8")]
    readings = [kind.group_readings(cfg, *kind.position_errors(rows, got, ref))
                for got in (kind.program_logits(eng, rows), control)]
    assert set(cfg["check"]["limits"]) == {"long", "decode"}
    for group in cfg["check"]["limits"]:  # every group of positions separates the two by itself
        assert readings[0][group][0] <= SMALL_LIMIT < readings[1][group][0], (group, readings)
