"""The Granite 4.0-H twin against ``benchmark/refs/granitehybrid.py`` where the
benchmark's own check cannot look (PERF.md section 2): under the **published
Mamba-2 initialisation** (``A`` uniform in [1, 16], ``softplus(dt_bias)``
log-uniform in [0.001, 0.1], ``D`` = 1, so a state a hundred positions back
still counts) with matrices at ``1 / sqrt(fan_in)``, so that every mixer kind
carries a share of the logits that a comparison in bfloat16 can see, and in
**state slots other than the scratch one**, several sequences of different
lengths in one batch on scattered pages.  ``benchmark/weights.py`` draws
``A_log``, ``dt_bias`` and ``D`` N(0, 0.02^2) (``A`` about -1, ``dt`` about 0.7:
a state forgets in a few positions, ``D`` about 0), and the harness's
``program_logits`` passes no slot, so its one row runs in slot 0.

Used at the cell's own size on the chip (``test_granite_hybrid_on_chip.py``)
and at the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_granite_hybrid_check.py``).
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
REAL_FROM = {"pages": 1, "ssm": 1, "conv": 1}

#: what is taken out of the reference's forward pass -> what marks, in a parameter's path, the leaves it zeroes
KINDS = {
    "state": "['in_proj']",        # the columns of B and C alone (see ``without``): y = D x, no recurrence
    "mamba": "['out_proj']",       # every Mamba mixer
    "attention": "['o_proj']",     # every attention mixer
}


def check_init(abstract, seed: int, dtype):
    """Weights for the check, a leaf keyed by its path: the Mamba parameters
    as published, matrices N(0, 1 / fan_in), the embedding N(0, 0.02^2) (what
    the multiplier of 12 was made for), norm weights 1, the convolution's
    bias 0."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)

    def fill(key):
        out = []
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if "norm" in name or name.endswith("['D']"):
                x = jnp.ones(leaf.shape)
            elif "A_log" in name:
                x = jnp.log(jax.random.uniform(k, leaf.shape, minval=1.0, maxval=16.0))
            elif "dt_bias" in name:
                dt = jnp.exp(jax.random.uniform(k, leaf.shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
                x = jnp.log(jnp.expm1(dt))
            elif "conv_bias" in name:
                x = jnp.zeros(leaf.shape)
            elif "embedding" in name:
                x = 0.02 * jax.random.normal(k, leaf.shape)
            else:                  # [periods, fan_in, fan_out] matrices, [periods, d_conv, channels] convolutions
                x = jax.random.normal(k, leaf.shape) / math.sqrt(leaf.shape[-2])
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def without(params, kind: str, config: dict):
    """``params`` with the leaves of ``KINDS[kind]`` zeroed (of ``in_proj``
    the columns of ``B`` and ``C`` alone: with no convolution bias both are
    then ``silu(0)`` = 0 and the state stays empty)."""
    import jax
    import jax.numpy as jnp
    d, n = config["mamba_n_heads"] * config["mamba_d_head"], config["mamba_d_state"]

    def zero(path, x):
        if KINDS[kind] not in jax.tree_util.keystr(path):
            return x
        return x.at[..., 2 * d:2 * d + 2 * n].set(0) if kind == "state" else jnp.zeros_like(x)

    return jax.tree_util.tree_map_with_path(zero, params)


def fed_in_slots(config: dict, traffic: dict, seed: int, rows: list):
    """``rows``: (prompt tokens, decode tokens, state slot, first position
    compared) a sequence.  Every row goes through the engine's own twin,
    weights (``check_init``) and cache in one batch, each in its slot and on
    pages drawn at random, a padding row behind them: SplitFuse chunks (the
    block form), then one token a step (``ds_ssd_update``) beside the rows
    still in their prompts, which a mixed step carries as chunks of one token.
    Returns (the engine, its cache dropped; the rows' token ids; per row the
    logits of the positions compared; ``steps`` and ``kernel_steps``, the
    steps whose every row carried one token at most, which went through the
    kernel)."""
    import jax
    import jax.numpy as jnp

    import harness
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from flax import linen as nn
    from kinds import serve_open_loop

    pcfg = harness.program_config(config)
    model = harness.load_symbol(config["program"]["model"])(pcfg)
    abstract = nn.meta.unbox(jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    params = check_init(abstract, seed, jnp.bfloat16)
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
    counts = {"steps": 0, "kernel_steps": 0}
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
        counts["steps"] += 1
        counts["kernel_steps"] += width == 1
        del logits
    scratch = float(jnp.max(jnp.abs(eng.cache["ssm"][:, [r[2] for r in rows]])))
    assert scratch > 0                                                        # the rows' slots hold their states
    eng.cache = None
    return eng, toks, [jnp.concatenate(g) for g in got], counts


def readings(config: dict, traffic: dict, seed: int, rows: list) -> dict:
    """``fed_in_slots`` against the float32 reference on the same weights.
    Returns ``program``: per row ``||logits - ref|| / ||ref||`` of the
    positions compared; ``zeroed``: per kind and row, the same distance
    between the reference without that kind and the whole reference;
    ``steps`` and ``kernel_steps``."""
    from kinds import serve_open_loop
    from refs import plain

    eng, toks, got, out = fed_in_slots(config, traffic, seed, rows)
    ref_rows = [(toks[i], p, first) for i, (p, _, _, first) in enumerate(rows)]
    ref = [logits for logits, _ in serve_open_loop.reference_logits(config, eng.params, ref_rows)]
    out["program"] = [np.asarray(plain.rel_l2(g, r)) for g, r in zip(got, ref)]
    del got
    out["zeroed"] = {}
    for kind in KINDS:
        changed = serve_open_loop.reference_logits(config, without(eng.params, kind, config), ref_rows)
        out["zeroed"][kind] = [np.asarray(plain.rel_l2(c, r)) for (c, _), r in zip(changed, ref)]
        del changed
    return out


def report(out: dict, rows: list) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors, per kind the 10th percentile of the reference's change)."""
    for (p, d, slot, first), errs in zip(rows, out["program"]):
        print(f"granite_hybrid_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"p50={np.median(errs):.6f} p90={np.percentile(errs, 90):.6f} max={errs.max():.6f}", flush=True)
    for kind, per_row in out["zeroed"].items():
        print(f"granite_hybrid_check: zeroed={kind} " + " ".join(
            f"slot{slot}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for (_, _, slot, _), e in zip(rows, per_row)),
              flush=True)
    print(f"granite_hybrid_check: steps={out['steps']} kernel_steps={out['kernel_steps']}", flush=True)
    return [(float(np.percentile(errs, 90)), {kind: float(np.percentile(per_row[i], 10)) for kind, per_row in out["zeroed"].items()})
            for i, errs in enumerate(out["program"])]
