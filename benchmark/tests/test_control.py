"""The control of ``correct``, kept as a test at a size a test run holds
(the configuration files' ``rehearsal`` sizes, on the CPU):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

The control is the plain reference put in the program's place and computed in
int8, the step below the bfloat16 the configurations state.  It has to come
out as not correct while the program comes out as correct, under one limit.
On the chip, at the cells' own sizes, the same readings come from
``selfcheck.py --limits`` and are in PERF.md section 2 beside the limits chosen.
A benchmark run never runs this.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

# between the two readings at the rehearsal sizes (program about 0.005, control 0.013 to 0.017)
SMALL_LIMIT = 0.009
SEEDS = (0, 1, 2 ** 31 + 5)


def _config(name):
    import run
    cfg = run.load_json("configs", name + ".json")
    return run.merge(cfg, cfg["rehearsal"])


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_logits_separate_program_from_int8_control(seed):
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    import harness
    import run
    from kinds import serve_open_loop as kind
    cfg = _config("mixtral-8x7b-serve-1chip")
    traffic = run.load_json("traffic", "chat_heavy_tail.json")
    traffic = run.merge(traffic, traffic["rehearsal"])
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    ref = kind.reference_logits(cfg, params, rows)
    control = [logits for logits, _ in kind.reference_logits(cfg, params, rows, mode="int8")]
    readings = [kind.group_readings(cfg, *kind.position_errors(rows, got, ref))
                for got in (kind.program_logits(eng, rows), control)]
    for group in cfg["check"]["limits"]:  # every group of positions separates the two by itself
        assert readings[0][group][0] <= SMALL_LIMIT < readings[1][group][0], (group, readings)


@pytest.mark.parametrize("seed", SEEDS)
def test_training_logits_separate_program_from_int8_control(seed):
    import jax
    import jax.numpy as jnp

    import harness
    from kinds import train_job as kind
    cfg = _config("qwen15-moe-a2.7b-zero3-4chip")
    pcfg = harness.program_config(cfg)
    model, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    ids = kind.logit_rows(cfg, None, seed, 2)
    want = kind.reference_logits(cfg, params, ids)
    program = kind.logit_error(model.apply(params, jnp.asarray(ids)), want)
    control = kind.logit_error(kind.reference_logits(cfg, params, ids, "int8"), want)
    assert program <= SMALL_LIMIT < control, (program, control)


def test_limits_in_the_configuration_files_are_numbers_between_zero_and_one():
    for name in os.listdir(os.path.join(HERE, "configs")):
        with open(os.path.join(HERE, "configs", name)) as f:
            check = json.load(f)["check"]
        limits = {**{k: v for k, v in check.items() if k.endswith("_limit")}, **check.get("limits", {})}
        assert limits and all(0.0 < v < 1.0 for v in limits.values()), (name, limits)
