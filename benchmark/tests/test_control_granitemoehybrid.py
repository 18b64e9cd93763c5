"""The control of ``correct`` for ``granite4hs_agent_turns``, as
``test_control_granitehybrid.py`` keeps it for the dense sibling: at the
configuration's ``rehearsal`` size on the CPU the program's logits pass and
the int8 control fails the limit, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_granitemoehybrid.py -q

The row runs in the scratch slot 0 as ``program_logits`` builds it: 200 prompt
tokens in six chunks of 32 and one of 8 (whose 24 padded slots must reach no
expert and leave the state alone), then 8 decode steps of one token through
``ds_ssd_update`` (interpreted here); every step routes 3 of 8 with experts
0-3 held, beside the shared MLP.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

SEEDS = (0, 1, 2 ** 31 + 5)


def _cell_files():
    import run
    cfg = run.load_json("configs", "granite-4.0-h-small-serve-1chip.json")
    traffic = run.load_json("traffic", "agent_turns_mid_in_short_out.json")
    return run.merge(cfg, cfg["rehearsal"]), run.merge(traffic, traffic["rehearsal"])


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_logits_separate_program_from_int8_control(seed):
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    import harness
    from kinds import serve_open_loop as kind
    cfg, traffic = _cell_files()
    pcfg = harness.program_config(cfg)
    assert pcfg.held == (0, 4) and pcfg.router_width == 8 and pcfg.num_experts_per_tok == 3
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


def test_each_control_of_the_reference_moves_the_logits():
    """The family's controls at the rehearsal size under the benchmark's
    weights: the reference without the shared MLP, the routed experts, one
    held expert or the recurrent state each gives other logits, in that order
    of size (N(0, 0.02^2) makes the routed sum a few times smaller than the
    shared MLP's output and a state that forgets in a few positions: what the
    cell's own check can and cannot see is in the configuration's
    ``assumed.check``; the on-chip test draws weights under which each shows)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    from refs import granitemoehybrid as ref
    from refs import plain
    cfg, _ = _cell_files()
    _, params = harness.seeded_params(cfg, harness.program_config(cfg), 3, jax.devices()[:1])
    ids = jnp.asarray(np.random.default_rng(3).integers(1, cfg["vocab_size"], 96))
    want, margin = ref.forward(params, ids, cfg)
    assert want.shape == (96, cfg["vocab_size"]) and margin.shape == (96, ) and float(margin.min()) >= 0.0
    moved = {control: float(np.median(np.asarray(plain.rel_l2(ref.forward(params, ids, cfg, without=(control, ))[0],
                                                              want)))) for control in ref.CONTROLS}
    assert moved["shared"] > moved["routed"] > moved["expert"] > moved["state"] > 1e-5, moved
