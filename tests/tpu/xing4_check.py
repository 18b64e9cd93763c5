"""The Xing4.0 twin against ``benchmark/refs/xing4.py`` where the benchmark's
own check cannot look (PERF.md section 2): under an **initialisation with
which the new mathematics is visible** and on **scattered pages, several
sequences in one batch, decode rows beside a prefilling row as two row
groups**.  ``benchmark/weights.py`` draws every vector N(0, 0.02^2), so the
hyper-connection's ``a`` and ``b`` are about 0.02 (``Hres`` nearly uniform,
``Hpre`` 0.5, ``Hpost`` 1) and the router's bias is of the size of the scores'
differences; here ``b_res`` has a strong diagonal, ``a`` is of order 1, ``phi``
at ``1 / sqrt(n C)``, the bias 0.2 against scores that spread by 0.2, matrices
at ``1 / sqrt(fan_in)`` and the embedding N(0, 1), so that every part carries
a share of the logits that a comparison in bfloat16 can see.

Three mutilated references, each a change of weights that takes one piece of
the mathematics out of the plain forward pass, must differ from the whole
reference by more than the limit the program is held to.

Used at the cell's own size on the chip (``test_xing4_on_chip.py``) and at the
configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_xing4_check.py``).
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

#: what is taken out of the reference's forward pass
KINDS = ("res_mix", "select_bias", "rope_score")


def check_init(abstract, seed: int, dtype):
    """Weights for the check, a leaf keyed by its path."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)

    def fill(key):
        out = []
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            noise = jax.random.normal(jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF), leaf.shape)
            if name.endswith("['a']"):
                x = 1.0 + 0.3 * noise
            elif name.endswith("['b']"):
                n = math.isqrt(leaf.shape[-1] + 1) - 1                       # 2 n + n^2 entries
                x = noise + jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])
            elif "e_score_correction_bias" in name:
                x = 0.2 * noise
            elif "norm" in name:
                x = jnp.ones(leaf.shape)
            elif "embedding" in name:
                x = noise
            else:                                                            # matrices, N(0, 1 / fan_in)
                fan_in = (leaf.shape[-3] if "q_b_proj" in name or "kv_b_proj" in name else
                          leaf.shape[-3] * leaf.shape[-2] if "o_proj" in name else leaf.shape[-2])
                x = noise / math.sqrt(fan_in)
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def without(params, kind: str, config: dict):
    """``params`` changed so that the plain forward pass lacks ``kind``:
    ``res_mix``: ``a_res`` 0 and ``b_res`` 30 on the diagonal, -30 off it, so
    ``Hres = I`` (the streams are not mixed); ``select_bias``: the router's
    bias 0 (the choice by the scores alone); ``rope_score``: the columns of
    ``W_qb`` that make ``q_pe`` 0 (the score without its rotary part)."""
    import jax
    import jax.numpy as jnp
    n, nope = config["hc_mult"], config["qk_nope_head_dim"]

    def change(path, x):
        name = jax.tree_util.keystr(path)
        if kind == "res_mix" and name.endswith("['a']"):
            return x.at[..., 2].set(0)
        if kind == "res_mix" and name.endswith("['b']"):
            return x.at[..., 2 * n:].set((60.0 * jnp.eye(n) - 30.0).reshape(-1).astype(x.dtype))
        if kind == "select_bias" and "e_score_correction_bias" in name:
            return jnp.zeros_like(x)
        if kind == "rope_score" and "q_b_proj" in name:
            return x.at[..., nope:].set(0)
        return x

    return jax.tree_util.tree_map_with_path(change, params)


def readings(config: dict, traffic: dict, seed: int, rows: list, runs: bool = False) -> dict:
    """``rows``: (prompt tokens, decode tokens, first position compared) a
    sequence.  Every row goes through the engine's own twin, weights and arena
    in one batch on pages drawn at random: SplitFuse chunks, then one token a
    step; a step that carries both is two row groups on one flat axis, the
    rows of one token at most at one slot each beside the chunks, as the
    engine lays a mixed step out; with ``runs``, a prompt's consecutive chunks
    in the spare rows of the rung of four, as the scheduler hands them out
    (``run_steps`` counts the steps that held one).  Returns ``program``: per row ``||logits -
    ref|| / ||ref||`` of the positions compared, against the float32 reference
    on the same weights; ``changed``: per kind and row, the same distance
    between the mutilated reference and the whole one; ``mixed_steps``."""
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
    params = check_init(abstract, seed, jnp.bfloat16)
    eng = InferenceEngineV2(pcfg, params, serve_open_loop.engine_config(config, traffic))
    del params                                                               # the engine's are the ones compared
    kv, chunk = eng.kv, eng.econfig.scheduler.prefill_chunk

    rng = np.random.default_rng(int(seed) + 1)
    toks = [rng.integers(1, config["vocab_size"], p + d).tolist() for p, d, _ in rows]
    free = rng.permutation(np.arange(1, eng.econfig.kv.num_pages)).tolist()   # page 0 is the null page
    tables = np.zeros((len(rows), kv.table_width), np.int32)
    for i, (p, d, _) in enumerate(rows):
        n_pages = math.ceil((p + d) / kv.page_size)
        assert n_pages <= kv.table_width, n_pages
        tables[i, :n_pages] = [free.pop() for _ in range(n_pages)]
    step = jax.jit(lambda p, c, t, s, b, ln, groups: eng.model.apply(p, t, s, b, c, ln, False, groups), donate_argnums=1,
                   static_argnames="groups")

    pos, got = [0] * len(rows), [[] for _ in rows]
    out = {"steps": 0, "mixed_steps": 0, "run_steps": 0}
    run_rows = eng.scheduler.run_rows if runs else 1
    while any(pos[i] < len(toks[i]) for i in range(len(rows))):
        # a chunk a prefilling row, then (``runs``) the spare rows of the rung in order, as the scheduler plans
        fed = [min(chunk, p - pos[i]) if pos[i] < p else int(pos[i] < p + d) for i, (p, d, _) in enumerate(rows)]
        spare = run_rows - sum(n > 1 for n in fed)
        for i, (p, _, _) in enumerate(rows):
            if fed[i] == chunk and spare > 0:
                fed[i] = min(p - pos[i], (1 + spare) * chunk)
                spare -= -(-fed[i] // chunk) - 1
        # (row, first position, tokens) of each row of the step: a run is a row a chunk through the same pages
        wide = [(i, pos[i] + at, min(chunk, fed[i] - at)) for i in range(len(rows)) if fed[i] > 1
                for at in range(0, fed[i], chunk)]
        one = [(i, pos[i], fed[i]) for i in range(len(rows)) if fed[i] <= 1]
        if len(wide) > len({i for i, _, _ in wide}):
            wide += [(None, 0, 0)] * (run_rows - len(wide))       # the rung's padding rows
            out["run_steps"] += 1
        layout = [(one, 1), (wide, chunk)] if wide and one else [(wide or one, chunk if wide else 1)]
        flat, order = [], [entry for members, _ in layout for entry in members]
        for members, width in layout:
            rect = np.zeros((len(members), width), np.int32)
            for j, (i, start, n) in enumerate(members):
                if n:
                    rect[j, :n] = toks[i][start:start + n]
            flat.append(rect.reshape(-1))
        groups = tuple((len(members), width) for members, width in layout)
        logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(np.concatenate(flat)),
                                 jnp.asarray([start for _, start, _ in order], jnp.int32),
                                 jnp.asarray(np.stack([tables[i] if i is not None else 0 * tables[0] for i, _, _ in order])),
                                 jnp.asarray([n for _, _, n in order], jnp.int32), groups=groups)
        t0 = 0
        for members, width in layout:
            for i, start, n in members:
                if i is not None:
                    skip = max(rows[i][2] - start, 0)
                    if skip < n:
                        got[i].append(logits[t0 + skip:t0 + n].astype(jnp.float32))
                    pos[i] += n
                t0 += width
        out["steps"] += 1
        out["mixed_steps"] += len(layout) == 2
        del logits
    eng.cache = None

    ref_rows = [(toks[i], p, first) for i, (p, _, first) in enumerate(rows)]
    whole = serve_open_loop.reference_logits(config, eng.params, ref_rows)
    ref, out["margins"] = [logits for logits, _ in whole], [np.asarray(margin) for _, margin in whole]
    out["program"] = [np.asarray(plain.rel_l2(jnp.concatenate(g), r)) for g, r in zip(got, ref)]
    del got, whole
    out["changed"] = {}
    for kind in KINDS:
        changed = serve_open_loop.reference_logits(config, without(eng.params, kind, config), ref_rows)
        out["changed"][kind] = [np.asarray(plain.rel_l2(c, r)) for (c, _), r in zip(changed, ref)]
        del changed
    return out


def report(out: dict, rows: list, margin_min: float) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors over the positions whose router margin in the reference is at
    least ``margin_min``: nearer a tie a rounding error of any size picks
    another expert, as in the benchmark's own check; per kind the 10th
    percentile of the reference's change over all positions)."""
    per_row = []
    for i, ((p, d, first), errs, margin) in enumerate(zip(rows, out["program"], out["margins"])):
        by_margin = " ".join(f"m>={m}:{int((margin >= m).sum())}:{np.percentile(errs[margin >= m], 90):.4f}"
                             for m in (0.0, 0.002, 0.005, 0.01, 0.02, 0.04) if (margin >= m).sum() >= 5)
        print(f"xing4_check: program prompt={p} decode={d} from={first} positions={len(errs)} "
              f"p50={np.median(errs):.6f} max={errs.max():.6f} p90 of the clear by margin (count:p90): {by_margin}", flush=True)
        clear = margin >= margin_min
        per_row.append((float(np.percentile(errs[clear], 90)), int(clear.sum()),
                        {kind: float(np.percentile(changed[i], 10)) for kind, changed in out["changed"].items()}))
    for kind, changed in out["changed"].items():
        print(f"xing4_check: without={kind} " + " ".join(
            f"row{i}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for i, e in enumerate(changed)), flush=True)
    print(f"xing4_check: steps={out['steps']} mixed_steps={out['mixed_steps']} run_steps={out.get('run_steps', 0)}",
          flush=True)
    return per_row
