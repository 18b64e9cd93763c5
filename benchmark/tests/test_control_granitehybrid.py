"""The control of ``correct`` for ``granite4h_sessions``, as ``test_control.py``
keeps it for the Mixtral cells (that file is not this PR's to edit): at the
configuration's ``rehearsal`` size on the CPU the program's logits pass and
the int8 control fails the limit, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_granitehybrid.py -q

The row is built as ``program_logits`` builds it, for the linear layout and
with no slot: it runs in the scratch slot 0.  Its 200 prompt tokens go in
six chunks of 32 (the block form of the recurrence, the state gathered from
the slot arena and scattered back) and one of 8 (whose padding must leave
the state alone), then 8 decode steps through ``ds_ssd_update`` (interpreted
here), which carry the state through the arena in place.
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
    cfg = run.load_json("configs", "granite-4.0-h-micro-serve-1chip.json")
    cfg = run.merge(cfg, cfg["rehearsal"])
    traffic = run.load_json("traffic", "sessions_short_in_long_out.json")
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
