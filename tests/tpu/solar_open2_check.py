"""The Solar-Open2 twin against ``benchmark/refs/solar_open2.py`` where the
benchmark's own check cannot look (PERF.md section 2): under the **published
KDA initialisation** (``exp(A_log)`` log-uniform in [1, 16],
``softplus(dt_bias)`` log-uniform in [0.001, 0.1], so a state a hundred
positions back still counts) with matrices at ``1 / sqrt(fan_in)`` and an
embedding of unit rows, so that every mixer kind and the held experts carry a
share of the logits that a comparison in bfloat16 can see (the attention
layer's queries and keys twice that: under scores of unit spread a softmax
over 8k keys is an average, and the mixer's part of the logits falls with the
root of the context), and in **state
slots other than the scratch one**, several sequences of different lengths in
one batch on scattered pages.  ``benchmark/weights.py`` draws ``A_log`` and
``dt_bias`` N(0, 0.02^2) (``g`` about -0.69 a position: a state forgets in a
few positions), and the harness's ``program_logits`` passes no slot, so its
one row runs in slot 0.

The controls are the reference's own (``forward(without=)``): without the
state term (the delta rule reads an empty state), without the KDA mixers,
without the GQA mixer, with all but the last of the held experts.  That
last expert's selection bias is 1 here, so every token chooses it (the bias
decides the choice alone; its weight is its unbiased score among the eight),
so the absence of one held expert shows in every position.

Used at the cell's own size on the chip (``test_solar_open2_on_chip.py``)
and at the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_solar_open2_check.py``).
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

#: per array of the cache, the first index of its second axis past the null page or the scratch slot
#: (what ``row_groups_check.readings`` compares from)
REAL_FROM = {"pages": 1, "kda": 1, "conv": 1}
#: what the reference leaves out (``refs/solar_open2.forward(without=)``)
KINDS = ("state", "kda", "gqa", "expert")


def check_init(abstract, seed: int, dtype, config: dict):
    """Weights for the check, a leaf keyed by its path: the KDA parameters as
    published, matrices N(0, 1 / fan_in), the embedding N(0, 1), norm weights 1, the selection bias
    N(0, 0.1^2) and 1 for the last held expert."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)
    last_held = config.get("first_expert", 0) + config["n_routed_experts"] - 1
    gqa_layer = "['layer_0']"      # the period's attention layer (``gqa_layers`` starts at 0)

    def fill(key):
        out = []
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if "norm" in name:
                x = jnp.ones(leaf.shape)
            elif "A_log" in name:
                x = jax.random.uniform(k, leaf.shape, minval=0.0, maxval=math.log(16.0))
            elif "dt_bias" in name:
                dt = jnp.exp(jax.random.uniform(k, leaf.shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
                x = jnp.log(jnp.expm1(dt))
            elif "e_score_correction_bias" in name:
                x = (0.1 * jax.random.normal(k, leaf.shape)).at[..., last_held].set(1.0)
            elif "embedding" in name:
                x = jax.random.normal(k, leaf.shape)
            else:                  # [periods, (experts,) fan_in, fan_out] matrices, [periods, conv, channels] convolutions
                x = jax.random.normal(k, leaf.shape) / math.sqrt(leaf.shape[-2])
                if gqa_layer in name and ("['q_proj']" in name or "['k_proj']" in name):
                    x = 2.0 * x    # scores of spread 4: a softmax over thousands of keys that still picks some
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def reference_logits(config: dict, params, rows, without=()):
    """Per row (token ids, first position compared) the reference's logits
    from ``first`` on, the row padded to a multiple of 512 tokens as the
    harness pads it (``kinds/serve_open_loop.reference_logits``)."""
    import jax
    import jax.numpy as jnp

    from refs import solar_open2 as ref
    fwd = jax.jit(lambda p, ids, first: ref.forward(p, ids, config, "f32", first, without)[0], static_argnums=2)
    out = []
    for toks, first in rows:
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        out.append(fwd(params, jnp.asarray(ids), first)[:len(toks) - first])
    return out


def _engine(config: dict, traffic: dict, seed: int):
    """The cell's engine under ``check_init``'s weights: its twin, weights and cache are what is compared."""
    import jax
    import jax.numpy as jnp

    import harness
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from flax import linen as nn
    from kinds import serve_open_loop

    pcfg = harness.program_config(config)
    model = harness.load_symbol(config["program"]["model"])(pcfg)
    abstract = nn.meta.unbox(jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    return InferenceEngineV2(pcfg, check_init(abstract, seed, jnp.bfloat16, config),
                             serve_open_loop.engine_config(config, traffic))


def readings(config: dict, traffic: dict, seed: int, rows: list) -> dict:
    """``rows``: (prompt tokens, decode tokens, state slot, first position
    compared) a sequence.  Every row goes through the engine's own twin,
    weights and cache in one batch, each in its slot and on pages drawn at
    random: SplitFuse chunks (the chunked form), then one token a step
    (``ds_kda_update``) beside the rows still in their prompts.  Returns
    ``program``: per row ``||logits - ref|| / ||ref||`` of the positions
    compared, against the float32 reference on the same weights; ``zeroed``:
    per kind and row, the same distance between the reference without that
    kind and the whole reference; ``kernel_steps``: the steps whose every row
    carried one token at most, which went through the kernel."""
    import jax
    import jax.numpy as jnp

    from refs import plain

    eng = _engine(config, traffic, seed)
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
        logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(t), jnp.asarray(s), jnp.asarray(tables), jnp.asarray(n))
        for i, ln in enumerate(lens):
            skip = max(rows[i][3] - pos[i], 0)
            if skip < ln:
                got[i].append(logits[i, skip:ln].astype(jnp.float32))
            pos[i] += ln
        out["steps"] += 1
        out["kernel_steps"] += width == 1
        del logits
    assert float(jnp.max(jnp.abs(eng.cache["kda"][:, [r[2] for r in rows]]))) > 0   # the rows' slots hold their states
    eng.cache = None

    ref_rows = [(toks[i], first) for i, (_, _, _, first) in enumerate(rows)]
    ref = reference_logits(config, eng.params, ref_rows)
    out["program"] = [np.asarray(plain.rel_l2(jnp.concatenate(g), r)) for g, r in zip(got, ref)]
    del got
    out["zeroed"] = {}
    for kind in KINDS:
        changed = reference_logits(config, eng.params, ref_rows, without=(kind, ))
        out["zeroed"][kind] = [np.asarray(plain.rel_l2(c, r)) for c, r in zip(changed, ref)]
        del changed
    return out


def run_readings(config: dict, traffic: dict, seed: int, prompt: int, decode: int, first: int, slots=(5, 9),
                 run_rows: int = 4) -> dict:
    """One sequence of ``prompt`` tokens twice through the engine's own twin,
    weights and cache, on scattered pages: in ``slots[0]`` as **runs**, its
    consecutive chunks the rows of one rectangle of ``run_rows`` rows (each
    row behind the first continues the row before it: the state and the
    convolution's tail are handed on inside the program), and in ``slots[1]``
    a chunk a step; then ``decode`` steps of one token each way.  Returns, of
    the positions from ``first`` on: ``run`` and ``a_chunk_a_step``, each
    ``||logits - ref|| / ||ref||`` against the float32 reference;
    ``between``, the same distance of the two from one another;
    ``without_state``, of the reference that reads an empty state from the
    whole one (what a state that is not handed on would look like); and
    ``kda`` and ``conv``, the relative distance of what the two slots hold at
    the end."""
    import jax
    import jax.numpy as jnp

    from refs import plain

    eng = _engine(config, traffic, seed)
    kv, chunk = eng.kv, eng.econfig.scheduler.prefill_chunk
    rng = np.random.default_rng(int(seed) + 1)
    toks = rng.integers(1, config["vocab_size"], prompt + decode).tolist()
    free = rng.permutation(np.arange(1, eng.econfig.kv.num_pages)).tolist()
    n_pages = math.ceil(len(toks) / kv.page_size)
    assert n_pages < kv.table_width
    step = jax.jit(lambda p, c, t, s, b, ln: eng.model.apply(p, t, s, b, c, ln), donate_argnums=1)

    def serve(slot, rows):
        """The sequence in ``slot``, ``rows`` consecutive chunks a step: the logits from ``first`` on."""
        table = np.zeros((rows, kv.table_width), np.int32)
        table[:, :n_pages] = [free.pop() for _ in range(n_pages)]
        table[:, -1] = slot
        pos, got, steps = 0, [], 0
        while pos < len(toks):
            width = chunk if pos < prompt else 1
            end = prompt if pos < prompt else len(toks)
            starts = [min(pos + r * width, end) for r in range(rows if width > 1 else 1)]
            lens = [min(width, end - at) for at in starts]
            t, s, n = np.zeros((rows, width), np.int32), np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            for r, (at, ln) in enumerate(zip(starts, lens)):
                t[r, :ln], s[r], n[r] = toks[at:at + ln], at, ln
            # a row that carries nothing is a padding row: no pages, the scratch slot
            tables = np.where((n > 0)[:, None], table, 0)
            logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(t), jnp.asarray(s), jnp.asarray(tables),
                                     jnp.asarray(n))
            for r, (at, ln) in enumerate(zip(starts, lens)):
                skip = max(first - at, 0)
                if skip < ln:
                    got.append(logits[r, skip:ln].astype(jnp.float32))
            pos += sum(lens)
            steps += 1
            jax.block_until_ready(logits)       # a step at a time, as the engine reads its tokens back
            del logits
        return jnp.concatenate(got), steps

    out = {}
    run, out["run_steps"] = serve(slots[0], run_rows)
    each, out["a_chunk_a_step_steps"] = serve(slots[1], 1)
    for name in ("kda", "conv"):
        a, b = (np.asarray(eng.cache[name][:, slot], np.float32) for slot in slots)
        out[name] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    eng.cache = None
    ref, = reference_logits(config, eng.params, [(toks, first)])
    out["run"], out["a_chunk_a_step"] = (np.asarray(plain.rel_l2(g, ref)) for g in (run, each))
    out["between"] = np.asarray(plain.rel_l2(run, each))
    del run, each
    changed, = reference_logits(config, eng.params, [(toks, first)], without=("state", ))
    out["without_state"] = np.asarray(plain.rel_l2(changed, ref))
    return out


def report_run(out: dict) -> dict:
    """Print ``run_readings``; the 90th percentiles (the 10th of ``without_state``), and the states' distances."""
    read = {name: float(np.percentile(out[name], 10 if name == "without_state" else 90))
            for name in ("run", "a_chunk_a_step", "between", "without_state")}
    read.update(kda=out["kda"], conv=out["conv"])
    print(f"solar_open2_check: run steps={out['run_steps']} a_chunk_a_step_steps={out['a_chunk_a_step_steps']} "
          f"positions={len(out['run'])} " + " ".join(f"{k}={v:.6f}" for k, v in read.items()) +
          f" run_p50={np.median(out['run']):.6f} a_chunk_a_step_p50={np.median(out['a_chunk_a_step']):.6f} "
          f"between_p50={np.median(out['between']):.6f}", flush=True)
    return read


def report(out: dict, rows: list) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors, per kind the 10th percentile of the reference's change, the
    program's median error)."""
    for (p, d, slot, first), errs in zip(rows, out["program"]):
        print(f"solar_open2_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"p50={np.median(errs):.6f} p90={np.percentile(errs, 90):.6f} max={errs.max():.6f}", flush=True)
    for kind, per_row in out["zeroed"].items():
        print(f"solar_open2_check: without={kind} " + " ".join(
            f"slot{slot}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for (_, _, slot, _), e in zip(rows, per_row)),
              flush=True)
    print(f"solar_open2_check: steps={out['steps']} kernel_steps={out['kernel_steps']}", flush=True)
    return [(float(np.percentile(errs, 90)), {kind: float(np.percentile(per_row[i], 10)) for kind, per_row in out["zeroed"].items()},
             float(np.median(errs))) for i, errs in enumerate(out["program"])]
