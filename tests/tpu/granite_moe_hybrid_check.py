"""The Granite 4.0-H twin with routed experts against
``benchmark/refs/granitemoehybrid.py`` where the benchmark's own check cannot
look (PERF.md section 2): under the **published Mamba-2 initialisation** with
matrices at ``1 / sqrt(fan_in)`` (``granite_hybrid_check.check_init``: a state
a hundred positions back still counts, router logits of deviation 1, the
routed sum a fifth of the shared MLP's output and both a share of the logits
that a comparison in bfloat16 sees), in **state slots other than the scratch
one**, several sequences of different lengths in one batch on scattered
pages.  The reference without the routed term, without the shared MLP and
without the recurrent state (``refs.granitemoehybrid.forward(without=)``) must
each lie far from the whole reference.

``cell_readings`` reads the cell's own ``correct`` under ``benchmark/weights.py``
once a control: what the check of the measured run would and would not see.

Used at the cell's own size on the chip (``test_granite_moe_hybrid_on_chip.py``)
and at the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_granite_moe_hybrid_check.py``).
"""

import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import granite_hybrid_check  # noqa: E402  (puts the benchmark and the checkout on the path)
from granite_hybrid_check import REAL_FROM, check_init, fed_in_slots  # noqa: E402,F401

#: the reference's controls read here: each must move the logits by over three times the program's distance
CONTROLS = ("routed", "shared", "state")


def _reference(config: dict):
    """``forward(params, ids, first, without)`` of the family's reference, jitted."""
    import jax
    from refs import granitemoehybrid as ref
    return jax.jit(lambda p, ids, first, without: ref.forward(p, ids, config, "f32", first, without),
                   static_argnums=(2, 3))


def reference_logits(fwd, params, rows, without=()):
    """Per row (token ids, first position compared): (logits, router margins),
    the row padded to whole blocks of 512 as the harness pads it."""
    import jax.numpy as jnp
    out = []
    for toks, first in rows:
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        logits, margin = fwd(params, jnp.asarray(ids), first, tuple(without))
        out.append((logits[:len(toks) - first], margin[:len(toks) - first]))
    return out


def readings(config: dict, traffic: dict, seed: int, rows: list) -> dict:
    """``granite_hybrid_check.fed_in_slots`` (every row through the engine's own
    twin, weights and cache in one batch, each in its slot on pages drawn at
    random, a padding row behind them whose slots of every step must reach no
    expert) against the float32 reference on the same weights.  Returns
    ``program`` (per row ``||logits - ref|| / ||ref||`` of the positions
    compared), ``margins`` (the reference's router margins of those
    positions), ``without`` (per control and row, the same distance between
    the reference without that term and the whole reference) and the steps'
    counts."""
    from refs import plain

    eng, toks, got, out = fed_in_slots(config, traffic, seed, rows)
    fwd = _reference(config)
    ref_rows = [(toks[i], first) for i, (_, _, _, first) in enumerate(rows)]
    ref = reference_logits(fwd, eng.params, ref_rows)
    out["program"] = [np.asarray(plain.rel_l2(g, r)) for g, (r, _) in zip(got, ref)]
    out["margins"] = [np.asarray(m) for _, m in ref]
    del got
    out["without"] = {}
    for control in CONTROLS:
        changed = reference_logits(fwd, eng.params, ref_rows, (control, ))
        out["without"][control] = [np.asarray(plain.rel_l2(c, r)) for (c, _), (r, _) in zip(changed, ref)]
        del changed
    return out


def report(out: dict, rows: list, margin_min: float = 0.0) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors over the positions whose router margin is at least ``margin_min``,
    per control the 10th percentile of the reference's change)."""
    per_row = []
    for i, ((p, d, slot, first), errs, margins) in enumerate(zip(rows, out["program"], out["margins"])):
        clear = margins >= margin_min
        print(f"granite_moe_hybrid_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"clear={int(clear.sum())} p50={np.median(errs):.6f} p90={np.percentile(errs, 90):.6f} "
              f"p90_clear={np.percentile(errs[clear], 90):.6f} max={errs.max():.6f} "
              f"margin_p10={np.percentile(margins, 10):.5f} margin_p50={np.median(margins):.5f}", flush=True)
        per_row.append((float(np.percentile(errs[clear], 90)),
                        {c: float(np.percentile(of[i], 10)) for c, of in out["without"].items()}))
    for control, of in out["without"].items():
        print(f"granite_moe_hybrid_check: without={control} " + " ".join(
            f"slot{slot}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for (_, _, slot, _), e in zip(rows, of)),
              flush=True)
    print(f"granite_moe_hybrid_check: steps={out['steps']} kernel_steps={out['kernel_steps']}", flush=True)
    return per_row


def cell_readings(config: dict, traffic: dict, seeds: list, controls=CONTROLS) -> dict:
    """The cell's own check (``kinds/serve_open_loop``: its row, weights by
    ``benchmark/weights.py``, the 90th percentile of the clear positions a
    group) read a seed: ``program`` and ``control`` (the reference in int8 in
    the program's place) as ``selfcheck.py --limits`` reads them, and the
    program against the reference without each term: what ``correct`` would
    compare were the program to leave the routed experts, the shared MLP or
    the carried state out."""
    import jax

    import harness
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from kinds import serve_open_loop

    pcfg = harness.program_config(config)
    fwd = _reference(config)
    out = {}
    for seed in seeds:
        _, params = harness.seeded_params(config, pcfg, seed, jax.devices()[:1])
        eng = InferenceEngineV2(pcfg, params, serve_open_loop.engine_config(config, traffic))
        del params
        rows = serve_open_loop.check_rows(config, seed)
        got = serve_open_loop.program_logits(eng, rows)
        eng.cache = None
        true = serve_open_loop.reference_logits(config, eng.params, rows)
        out[seed] = {}
        for who in ("program", "control") + tuple(controls):
            if who == "program":
                a, b = got, true
            elif who == "control":
                a, b = [lg for lg, _ in serve_open_loop.reference_logits(config, eng.params, rows, mode="int8")], true
            else:       # the margins stay the true reference's: the same positions are clear
                faulty = reference_logits(fwd, eng.params, [(toks, first) for toks, _, first in rows], (who, ))
                a, b = got, [(lg, margin) for (lg, _), (_, margin) in zip(faulty, true)]
            errs, margins, groups = serve_open_loop.position_errors(rows, a, b)
            out[seed][who] = {g: v for g, (v, _, _) in
                              serve_open_loop.group_readings(config, errs, margins, groups).items()}
            print(f"granite_moe_hybrid_check: cell seed={seed} who={who} " + " ".join(
                f"{g}:p90_clear={v:.6f},p50={np.median(errs[groups == g]):.6f},max={errs[groups == g].max():.6f}"
                for g, v in out[seed][who].items()), flush=True)
            del a, b
        del eng, got, true
    return out


if __name__ == "__main__":      # on the chip: python3 tests/tpu/granite_moe_hybrid_check.py <seed>[,<seed>...]
    import harness
    import run as bench
    harness.open_device(1, rehearse=False)
    cell_readings(bench.load_json("configs", "granite-4.0-h-small-serve-1chip.json"),
                  bench.load_json("traffic", "agent_turns_mid_in_short_out.json"),
                  [int(n) for n in sys.argv[1].split(",")])
