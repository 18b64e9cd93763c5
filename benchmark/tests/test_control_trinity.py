"""The control of ``correct`` for ``trinity_mixed_queue``, as ``test_control.py``
keeps it for the Mixtral cells (that file is not this PR's to edit): at the
configuration's ``rehearsal`` size on the CPU the program's logits and the
int8 control's separate, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_trinity.py -q

At the rehearsal's widths (128, heads of 32) the program reads 0.015-0.02 and
the control 0.04-0.22 (CPU, counts of error, not a device metric; at the
cell's widths the chip's ``--limits`` reads them, PERF.md section 2).  The
rehearsal's own limit in the configuration file (0.15) is loose on purpose: a
rehearsal shows control flow.  So this test holds the line between the two
itself: the program's reading under 0.6 of the control's over the 64 prompt
positions; the 8 decode positions are too few for a line (one position whose
router changes its mind is their 90th percentile) and are held to lie under
the control's.

The row is built as ``program_logits`` builds it, for the linear layout and
with no slot: it runs in the scratch slot 0.  Its 200 prompt tokens go in
seven chunks of 32 (past the rehearsal's window of 64 and its ring's first
lap of 144 rows), then 8 decode steps, the rings and the pages both through
``ds_paged_attention`` (interpreted here).
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
    cfg = run.load_json("configs", "trinity-large-preview-serve-1chip.json")
    cfg = run.merge(cfg, cfg["rehearsal"])
    traffic = run.load_json("traffic", "short_long_one_queue.json")
    traffic = run.merge(traffic, traffic["rehearsal"])
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    ref = kind.reference_logits(cfg, params, rows)
    control = [logits for logits, _ in kind.reference_logits(cfg, params, rows, mode="int8")]
    program, control = (kind.group_readings(cfg, *kind.position_errors(rows, got, ref))
                        for got in (kind.program_logits(eng, rows), control))
    print("readings", seed, program, control)
    assert set(cfg["check"]["limits"]) == {"long", "decode"}
    assert program["long"][0] < 0.6 * control["long"][0], (program, control)
    assert program["long"][0] <= cfg["check"]["limits"]["long"]
    assert program["decode"][0] < control["decode"][0], (program, control)
