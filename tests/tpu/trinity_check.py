"""The Trinity twin against ``benchmark/refs/trinity.py`` where the
benchmark's own check cannot look (PERF.md section 2): in **state slots other
than the scratch one**, several sequences of different lengths in one batch on
scattered pages, rows past a window and past a ring's first lap, and with
every one of the reference's controls (``refs/trinity.CONTROLS``: no window,
rotary on the full layer too, no output gate, no head norms, 31 of the 32 held
experts, ``route_scale`` 1) read beside the program.

Two sets of weights.  ``readings`` draws matrices N(0, 1 / fan_in), the
embedding N(0, 1 / hidden_size) (unit rows behind the muP multiplier), norm
weights 1 and a selection bias N(0, 0.1^2) of the size of the score gaps: the
sizes a trained model has.  ``cell_readings`` draws ``benchmark/weights.py``'s
(every matrix N(0, 0.02^2)), the cell's own check read once for each control:
under the sandwich norms every branch leaves at unit size whatever its
weights' scale, so the controls show there too.

A control that changes every position behind it (the window past 4,096
positions, the rotary split, the gate, the head norms, ``route_scale``) is
read by the 10th percentile of the change it makes to the reference; the
absent expert changes the one position in sixteen that is routed to it in one
of four layers, and is read by the 98th.

Used at the cell's own size on the chip (``test_trinity_on_chip.py``) and at
the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_trinity_check.py``).
"""

import math
import os
import sys
import zlib

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the percentile of a control's change to the reference that is held against the program's reading
PERCENTILE = {"window": 10, "rope_split": 10, "gate": 10, "head_norms": 10, "route_scale": 10, "expert": 98}


def check_init(abstract, seed: int, dtype, config: dict):
    """Weights for the check, a leaf keyed by its path."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)

    def fill(key):
        out = []
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if "norm" in name:
                x = jnp.ones(leaf.shape)
            elif "embedding" in name:
                x = jax.random.normal(k, leaf.shape) / math.sqrt(config["hidden_size"])
            elif "expert_bias" in name:
                x = 0.1 * jax.random.normal(k, leaf.shape)
            else:                  # [fan_in, fan_out] matrices; the experts' [E, fan_in, fan_out]
                x = jax.random.normal(k, leaf.shape) / math.sqrt(leaf.shape[-2])
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def _reference(config):
    import jax

    from refs import trinity as ref
    return jax.jit(lambda p, ids, first, without: ref.forward(p, ids, config, "f32", first, without),
                   static_argnums=(2, 3))


def reference_logits(fwd, params, rows, without=()):
    """Per row (token ids, first position compared) the reference's (logits,
    router margins) from ``first`` on, the row padded to a multiple of 512
    tokens as the harness pads it (``kinds/serve_open_loop.reference_logits``)."""
    import jax.numpy as jnp
    out = []
    for toks, first in rows:
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        logits, margin = fwd(params, jnp.asarray(ids), first, tuple(without))
        out.append((logits[:len(toks) - first], margin[:len(toks) - first]))
    return out


def readings(config: dict, traffic: dict, seed: int, rows: list, controls=None) -> dict:
    """``rows``: (prompt tokens, decode tokens, state slot, first position
    compared) a sequence.  Every row goes through the engine's own twin,
    weights and cache in one batch, each in its slot and on pages drawn at
    random: SplitFuse chunks, then one token a step beside the rows still in
    their prompts, the window layers through their rings and the full layer
    through its pages (``ds_paged_attention`` both).  Returns ``program``:
    per row ``||logits - ref|| / ||ref||`` of the positions compared, against
    the float32 reference on the same weights; ``changed``: per control and
    row, the same distance between the reference with that control and the
    reference; ``margins``: per row the reference's router margins."""
    import jax
    import jax.numpy as jnp

    import harness
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from flax import linen as nn
    from kinds import serve_open_loop
    from refs import plain

    pcfg = harness.program_config(config)
    model = harness.load_symbol(config["program"]["model"])(pcfg)
    abstract = nn.meta.unbox(jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    params = check_init(abstract, seed, jnp.bfloat16, config)
    eng = InferenceEngineV2(pcfg, params, serve_open_loop.engine_config(config, traffic))
    del params                                                               # the engine's are the ones compared
    kv, sched = eng.kv, eng.econfig.scheduler
    chunk, page = sched.prefill_chunk, kv.page_size

    rng = np.random.default_rng(int(seed) + 1)
    toks = [rng.integers(1, config["vocab_size"], p + d).tolist() for p, d, _, _ in rows]
    free = rng.permutation(np.arange(1, eng.econfig.kv.num_pages)).tolist()   # page 0 is the null page
    size = len(rows) + 1                                                      # one padding row behind them
    tables = np.zeros((size, kv.table_width), np.int32)
    for i, (p, d, slot, _) in enumerate(rows):
        n_pages = math.ceil((p + d) / page)
        assert n_pages < kv.table_width and 0 < slot <= sched.max_seqs, (n_pages, slot)
        tables[i, :n_pages] = [free.pop() for _ in range(n_pages)]
        tables[i, -1] = slot
    step = jax.jit(lambda p, c, t, s, b, ln: eng.model.apply(p, t, s, b, c, ln), donate_argnums=1)

    pos, got = [0] * len(rows), [[] for _ in rows]
    out = {"steps": 0, "kernel_steps": 0}
    while any(pos[i] < len(toks[i]) for i in range(len(rows))):
        lens = [min(chunk, p - pos[i]) if pos[i] < p else int(pos[i] < p + d) for i, (p, d, _, _) in enumerate(rows)]
        width = chunk if max(lens) > 1 else 1
        t, s, n = np.zeros((size, width), np.int32), np.zeros(size, np.int32), np.zeros(size, np.int32)
        for i, ln in enumerate(lens):
            t[i, :ln], s[i], n[i] = toks[i][pos[i]:pos[i] + ln], pos[i], ln
        logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(t), jnp.asarray(s), jnp.asarray(tables),
                                 jnp.asarray(n))
        for i, ln in enumerate(lens):
            skip = max(rows[i][3] - pos[i], 0)
            if skip < ln:
                got[i].append(logits[i, skip:ln].astype(jnp.float32))
            pos[i] += ln
        out["steps"] += 1
        out["kernel_steps"] += width == 1
        del logits
    eng.cache = None

    fwd = _reference(config)
    ref_rows = [(toks[i], first) for i, (_, _, _, first) in enumerate(rows)]
    ref = reference_logits(fwd, eng.params, ref_rows)
    out["program"] = [np.asarray(plain.rel_l2(jnp.concatenate(g), r)) for g, (r, _) in zip(got, ref)]
    out["margins"] = [np.asarray(m) for _, m in ref]
    del got
    out["changed"] = {}
    for control in controls or PERCENTILE:
        other = reference_logits(fwd, eng.params, ref_rows, without=(control, ))
        out["changed"][control] = [np.asarray(plain.rel_l2(c, r)) for (c, _), (r, _) in zip(other, ref)]
        del other
    return out


def cell_readings(config: dict, traffic: dict, seeds: list, controls=None) -> dict:
    """The cell's own check (``kinds/serve_open_loop``: its rows, weights by
    ``benchmark/weights.py``, the 90th percentile of the clear positions a
    group) read a seed: ``program`` and ``control`` (the reference in int8 in
    the program's place) as ``selfcheck.py --limits`` reads them, and the
    program against the reference with each of the reference's controls: what
    ``correct`` would compare were the program to leave the window out, turn
    the full layer's heads too, drop the gate or the head norms, lose an
    expert or the route's scale.  Per seed, reading and group the number
    compared."""
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
        for who in ("program", "control") + tuple(controls or PERCENTILE):
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
            print(f"trinity_check: cell seed={seed} who={who} " + " ".join(
                f"{g}:p90_clear={v:.6f},p10={np.percentile(errs[groups == g], 10):.6f},"
                f"p98={np.percentile(errs[groups == g], 98):.6f},max={errs[groups == g].max():.6f}"
                for g, v in out[seed][who].items()), flush=True)
            del a, b
        del eng, got, true
    return out


def report(out: dict, rows: list) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors over the positions clear of a router tie, per control the held
    percentile of the reference's change, the program's median error)."""
    margin_min = out.get("router_margin_min", 0.0)
    per_row = []
    for i, ((p, d, slot, first), errs) in enumerate(zip(rows, out["program"])):
        clear = errs[out["margins"][i] >= margin_min]
        print(f"trinity_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"clear={len(clear)} p50={np.median(errs):.6f} p90_clear={np.percentile(clear, 90):.6f} "
              f"p90={np.percentile(errs, 90):.6f} max={errs.max():.6f}", flush=True)
        per_row.append((float(np.percentile(clear, 90)),
                        {c: float(np.percentile(ch[i], PERCENTILE[c])) for c, ch in out["changed"].items()},
                        float(np.median(errs))))
    for control, changed in out["changed"].items():
        print(f"trinity_check: without={control} p{PERCENTILE[control]} " + " ".join(
            f"slot{slot}:{np.percentile(e, PERCENTILE[control]):.6f}(p50={np.median(e):.6f})"
            for (_, _, slot, _), e in zip(rows, changed)), flush=True)
    print(f"trinity_check: steps={out['steps']} kernel_steps={out['kernel_steps']}", flush=True)
    return per_row


if __name__ == "__main__":      # on the chip: python3 tests/tpu/trinity_check.py <seed>[,<seed>...]
    import harness
    import run as bench
    harness.open_device(1, rehearse=False)
    cell_readings(bench.load_json("configs", "trinity-large-preview-serve-1chip.json"),
                  bench.load_json("traffic", "short_long_one_queue.json"), [int(n) for n in sys.argv[1].split(",")])
