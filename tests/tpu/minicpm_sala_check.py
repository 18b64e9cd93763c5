"""The MiniCPM-SALA twin against ``benchmark/refs/minicpm_sala.py`` where the
benchmark's own check cannot look (PERF.md section 2): under **weights at
which every mixer carries a share of the logits that a comparison in bfloat16
can see**, and in **state slots other than the scratch one**, several
sequences of different lengths in one batch on scattered pages.

Under ``benchmark/weights.py`` (every matrix N(0, 0.02^2), every norm weight
1) a sparse layer's ``o`` is a mean over thousands of value rows: a dense walk
in the sparse layers' place moves the cell's logits by 0.055 and a selection
one block further on by 0.062, three times what bfloat16 does (0.018), which
the cell's limit of 0.035 holds and ``cell_readings`` below reads; and the
harness's ``program_logits`` passes no slot, so its one row runs in slot 0.
In ``readings``, where the same faults read five to twenty times the program: matrices N(0, 1 / fan_in); the embedding
N(0, 1 / scale_emb^2), so the stream starts at unit rows; norm weights 1 but
the sparse layers' ``q_norm``, which is 4 (``q`` and ``k`` are normalised a
head, so the scores' spread is the norm weights' to set: at 4 a softmax over
thousands of keys still picks some, and the compressed-key scores spread by
0.7 instead of 0.18, so the selection prefers some blocks); the sparse and
the Lightning layers' ``o_proj`` times 4, against the residual scale of
0.2475 and the mean a long softmax takes.

The controls are the reference's own (``forward(without=)``): without the
state term (the Lightning layers read an empty state), with a dense walk in
the sparse layers' place, with the selection taken one block further on.  The
last two change nothing for a row under ``dense_len``.

Used at the cell's own size on the chip (``test_minicpm_sala_on_chip.py``)
and at the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_minicpm_sala_check.py``).
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

#: what the reference leaves out or does differently (``refs/minicpm_sala.forward(without=)``)
KINDS = ("state", "sparse", "shift")
Q_NORM, O_PROJ = 4.0, 4.0


def check_init(abstract, seed: int, dtype, config: dict):
    """Weights for the check, a leaf keyed by its path."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)
    kinds = config["mixer_types"]
    sparse_runs = [f"['run_{j}']" for j, kind in
                   enumerate(k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k) if kind == "minicpm4"]

    def fill(key):
        out = []
        for path, leaf in leaves:
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if "norm" in name:
                x = jnp.ones(leaf.shape)
                if "['q_norm']" in name and any(run in name for run in sparse_runs):
                    x = Q_NORM * x
            elif "embedding" in name:
                x = jax.random.normal(k, leaf.shape) / config["scale_emb"]
            else:                  # [layers of the run, fan_in, fan_out] matrices; the head [fan_in, fan_out]
                x = jax.random.normal(k, leaf.shape) / math.sqrt(leaf.shape[-2])
                if "['mixer']['o_proj']" in name:
                    x = O_PROJ * x
            out.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def reference_logits(config: dict, params, rows, without=()):
    """Per row (token ids, first position compared) the reference's logits
    from ``first`` on, the row padded to a multiple of 512 tokens as the
    harness pads it (``kinds/serve_open_loop.reference_logits``)."""
    import jax
    import jax.numpy as jnp

    from refs import minicpm_sala as ref
    fwd = jax.jit(lambda p, ids, first: ref.forward(p, ids, config, "f32", first, without)[0], static_argnums=2)
    out = []
    for toks, first in rows:
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        out.append(fwd(params, jnp.asarray(ids), first)[:len(toks) - first])
    return out


def readings(config: dict, traffic: dict, seed: int, rows: list) -> dict:
    """``rows``: (prompt tokens, decode tokens, state slot, first position
    compared) a sequence.  Every row goes through the engine's own twin,
    weights and cache in one batch, each in its slot and on pages drawn at
    random: SplitFuse chunks (the blocked walk under the block mask, the
    chunked form), then one token a step (``ds_sparse_paged_attention``,
    ``ds_lightning_update``) beside the rows still in their prompts.  Returns
    ``program``: per row ``||logits - ref|| / ||ref||`` of the positions
    compared, against the float32 reference on the same weights; ``zeroed``:
    per kind and row, the same distance between the reference without that
    kind and the whole reference; ``kernel_steps``: the steps whose every row
    carried one token at most, which went through the kernels."""
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
        logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(t), jnp.asarray(s), jnp.asarray(tables), jnp.asarray(n))
        for i, ln in enumerate(lens):
            skip = max(rows[i][3] - pos[i], 0)
            if skip < ln:
                got[i].append(logits[i, skip:ln].astype(jnp.float32))
            pos[i] += ln
        out["steps"] += 1
        out["kernel_steps"] += width == 1
        del logits
    assert float(jnp.min(jnp.max(jnp.abs(eng.cache["state"][:, [r[2] for r in rows]]), axis=(2, 3, 4)))) > 0   # the slots hold states
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


def cell_readings(config: dict, traffic: dict, seeds: list) -> dict:
    """The cell's own check (``kinds/serve_open_loop``: its rows, weights by
    ``benchmark/weights.py``, the 90th percentile of the clear positions a
    group) read five ways a seed: ``program`` and ``control`` (the reference
    in int8 in the program's place) as ``selfcheck.py --limits`` reads them,
    and the program against the reference with each of ``KINDS``: what
    ``correct`` would compare were the program to leave the state term out,
    walk the sparse layers densely or take the selection one block further
    on.  Per seed, reading and group the number compared; the last three
    must lie over the file's limits and the first under them."""
    import jax
    import jax.numpy as jnp

    import harness
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from kinds import serve_open_loop
    from refs import minicpm_sala as ref

    pcfg = harness.program_config(config)
    faulty = {kind: jax.jit(lambda p, ids, first, w=kind: ref.forward(p, ids, config, "f32", first, (w, ))[0],
                            static_argnums=2) for kind in KINDS}
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
        for who in ("program", "control") + KINDS:
            if who == "program":
                a, b = got, true
            elif who == "control":
                a, b = [lg for lg, _ in serve_open_loop.reference_logits(config, eng.params, rows, mode="int8")], true
            else:
                a, b = got, []
                for (toks, _, first), (_, margin) in zip(rows, true):
                    ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
                    ids[:len(toks)] = toks
                    b.append((faulty[who](eng.params, jnp.asarray(ids), first)[:len(toks) - first], margin))
            errs, margins, groups = serve_open_loop.position_errors(rows, a, b)
            out[seed][who] = {g: v for g, (v, _, _) in
                              serve_open_loop.group_readings(config, errs, margins, groups).items()}
            print(f"minicpm_sala_check: cell seed={seed} who={who} " + " ".join(
                f"{g}:p90_clear={v:.6f},p10={np.percentile(errs[groups == g], 10):.6f},max={errs[groups == g].max():.6f}"
                for g, v in out[seed][who].items()), flush=True)
            del a, b
        del eng, got, true
    return out


def report(out: dict, rows: list) -> list:
    """Print the readings; per row (the 90th percentile of the program's
    errors, per kind the 10th percentile of the reference's change, the
    program's median error)."""
    for (p, d, slot, first), errs in zip(rows, out["program"]):
        print(f"minicpm_sala_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"p50={np.median(errs):.6f} p90={np.percentile(errs, 90):.6f} max={errs.max():.6f}", flush=True)
    for kind, per_row in out["zeroed"].items():
        print(f"minicpm_sala_check: without={kind} " + " ".join(
            f"slot{slot}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for (_, _, slot, _), e in zip(rows, per_row)),
              flush=True)
    print(f"minicpm_sala_check: steps={out['steps']} kernel_steps={out['kernel_steps']}", flush=True)
    return [(float(np.percentile(errs, 90)), {kind: float(np.percentile(per_row[i], 10)) for kind, per_row in out["zeroed"].items()},
             float(np.median(errs))) for i, errs in enumerate(out["program"])]


if __name__ == "__main__":      # on the chip: python3 tests/tpu/minicpm_sala_check.py <seed>[,<seed>...]
    import harness
    import run as bench
    harness.open_device(1, rehearse=False)
    cell_readings(bench.load_json("configs", "minicpm-sala-9b-serve-1chip.json"),
                  bench.load_json("traffic", "ctx_16k_64k_mid_answer.json"), [int(n) for n in sys.argv[1].split(",")])
