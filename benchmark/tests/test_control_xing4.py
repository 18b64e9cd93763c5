"""The control of ``correct`` for ``xing4_longdoc``, as ``test_control.py``
keeps it for the Mixtral cells (that file is not this PR's to edit): at the
configuration's ``rehearsal`` size on the CPU the program's logits pass and
the int8 control fails the limit, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_xing4.py -q

The row's 200 prompt tokens go in six chunks of 32 and one of 8 through the
absorbed latent kernel (interpreted here) on the latent pages, then 8 decode
steps of one token; the reference computes the expanded form a head at a time.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

SEEDS = (0, 1, 2 ** 31 + 5)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_logits_separate_program_from_int8_control(seed):
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    import harness
    import run
    from kinds import serve_open_loop as kind
    cfg = run.load_json("configs", "xing4.0-29b-a4b-serve-1chip.json")
    cfg = run.merge(cfg, cfg["rehearsal"])
    traffic = run.load_json("traffic", "doc_8k_32k_short_answer.json")
    traffic = run.merge(traffic, traffic["rehearsal"])
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    ref = kind.reference_logits(cfg, params, rows)
    control = [logits for logits, _ in kind.reference_logits(cfg, params, rows, mode="int8")]
    readings = [kind.group_readings(cfg, *kind.position_errors(rows, got, ref))
                for got in (kind.program_logits(eng, rows), control)]
    print("readings", seed, readings)
    limits = cfg["check"]["limits"]
    assert set(limits) == {"long", "decode"}
    for group, limit in limits.items():  # every group of positions separates the two by itself
        assert readings[0][group][0] <= limit < readings[1][group][0], (group, readings)
