"""The Phi-4-mini-flash twin against ``benchmark/refs/phi4flash.py`` where the
benchmark's own check cannot look (PERF.md section 2): under **weights with
which every mixer kind carries a quarter of the logits or more**, which a
comparison in bfloat16 can see, and in **state slots other than the scratch
one**, several sequences of different lengths in one batch on scattered
pages.  ``benchmark/weights.py`` draws every leaf but the norms' weights
N(0, 0.02^2): that mutes the Mamba layers (``D`` and the convolution at 0.02,
``A`` = -1 and ``dt`` = 0.69, a state that forgets in two positions) and with
them the gated memory units, the factor ``1 - l0`` = 0.2 leaves the layers
that read the shared pages a twentieth to a tenth of the logits, and the
harness's ``program_logits`` passes no slot, so its one row runs in slot 0.

Used at the cell's own size on the chip (``test_phi4flash_on_chip.py``) and at
the configuration file's rehearsal size on the CPU
(``tests/unit/inference/test_phi4flash_check.py``).  Both also hold the branch that
the engine's step programs take and the harness's check does not: the head
over each row's last real token alone (``last_only``), at the batch the
scheduler's decode bucket gives the timed programs.
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

#: a mixer kind -> what marks, in a parameter's path, the leaves whose zeroing takes it out of the forward pass
KINDS = {
    "scan_state": "['x_proj']",                              # B = C = 0: the scan gives D u alone
    "mamba": "mamba']['mixer']['out_proj']",                 # the self-decoder's and the middle one
    "gmu": "['gmu']['mixer']['out_proj']",
    "window": "['self_decoder']['attn']['mixer']['o_proj']",
    "full": "['mid_attn']['mixer']['o_proj']",
    "cross": "['cross']['mixer']['o_proj']",
}


def real_from(config: dict) -> dict:
    """Per array of the cache, the first index of its second axis that is
    past the null page (a page of tokens is ``page_groups`` device pages) or
    the scratch slot: what ``row_groups_check.readings`` compares from."""
    import harness
    from deepspeed_tpu.models.phi4flash_cache import page_groups
    n = page_groups(harness.program_config(config))
    return {"pages": n, "ring": n, "ssm": 1, "conv": 1}


def _l0(layer):
    return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(layer, np.float64))


def check_init(abstract, seed: int, dtype, n_layers: int):
    """Fill ``abstract`` so that every mixer kind matters at the published
    widths (its absence moves the logits by a quarter or more of their norm:
    ``readings`` measures it).  Matrices and biases N(0, 0.02^2)
    (``initializer_range``; the biases so that a dropped one shows, as
    ``benchmark/weights.py`` has it).  Mamba as the family's published code
    sets it: ``A = -(1..d_state)``, ``D = 1``, ``softplus(dt bias)``
    log-uniform in [1e-3, 1e-1], the depthwise convolution uniform in
    +-1/sqrt(d_conv).  Norm weights 1 + N(0, 0.1^2) so that a dropped one
    shows; the inner norm of differential attention 1.5 / (1 - l0) times
    that, so that a layer's heads come out 1.5 in size whatever its index
    (``1 - l0`` falls to 0.2 from layer 10 on); the gated memory unit's
    ``W_2`` N(0, 0.06^2)."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_leaves_with_path(abstract)
    treedef = jax.tree.structure(abstract)
    half = n_layers // 2

    def attention_layers(name, shape):
        """The layer indices of a stacked inner norm's leading axis (the middle layer's has none)."""
        if "['mid_attn']" in name:
            return half + 1
        first = 1 if "['self_decoder']" in name else half + 3
        return (first + 2 * np.arange(shape[0]))[:, None]

    def fill(key):
        leaves = []
        for path, leaf in flat:
            name, shape = jax.tree_util.keystr(path), leaf.shape
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if name.endswith("['A_log']"):
                x = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
            elif name.endswith("['D']"):
                x = jnp.ones(shape, jnp.float32)
            elif "['dt_proj']['bias']" in name:
                x = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(k, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))))
            elif name.endswith("['conv_kernel']"):
                bound = shape[-2]**-0.5
                x = jax.random.uniform(k, shape, minval=-bound, maxval=bound)
            elif "norm" in name and name.endswith("['weight']"):
                x = 1.0 + 0.1 * jax.random.normal(k, shape)
                if "['sub_norm']" in name:
                    x = x * jnp.asarray(1.5 / (1.0 - _l0(attention_layers(name, shape))), jnp.float32)
            elif KINDS["gmu"] in name:
                x = 0.06 * jax.random.normal(k, shape)
            else:
                x = 0.02 * jax.random.normal(k, shape)
            leaves.append(x.astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(fill)(jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31))


def without(params, kind: str):
    """``params`` with the leaves of ``KINDS[kind]`` zeroed."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if KINDS[kind] in jax.tree_util.keystr(path) else x, params)


def readings(config: dict, traffic: dict, seed: int, rows: list) -> dict:
    """``rows``: (prompt tokens, decode tokens, state slot, first position
    compared) a sequence.  Every row goes through the engine's own twin,
    weights and cache in one batch, each in its slot and on pages drawn at
    random: SplitFuse chunks, then one token a step beside the rows still in
    their prompts.  Returns

    * ``program``: per row ``||logits - ref|| / ||ref||`` of the positions
      compared, against the float32 reference on the same weights;
    * ``zeroed``: per mixer kind and row, the same distance between the
      reference without that kind and the whole reference;
    * ``last_only``: the largest such distance between the head over each
      row's last real token alone and the all-position logits there, every
      step, at the batch of this check; ``last_only_rows`` holds it row by
      row, and ``last_only_exact`` is the same reading with both programs
      compiled under ``xla_allow_excess_precision=false``: the two heads are
      two programs of one step, the compiler is free to leave out a rounding
      to bfloat16 in one and not in the other, and a row whose trunk then
      differs in the last bit of one element differs, layers later, in all
      of them (PERF.md section 6, PR 38, "After the review");
    * ``bucket``: the largest distance from the reference of the same head
      with the rows spread over a batch of the scheduler's decode bucket, the
      shape of the engine's step programs, at every step that ends on a
      position compared (another batch rounds otherwise: on the chip two
      batches differ by what either differs from the reference)."""
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
    params = check_init(abstract, seed, jnp.bfloat16, config["num_hidden_layers"])
    eng = InferenceEngineV2(pcfg, params, serve_open_loop.engine_config(config, traffic))
    del params                                                               # the engine's are the ones compared
    kv, sched = eng.kv, eng.econfig.scheduler
    chunk, page = sched.prefill_chunk, kv.page_size

    rng = np.random.default_rng(int(seed) + 1)
    toks = [rng.integers(1, config["vocab_size"], p + d).tolist() for p, d, _, _ in rows]
    free = rng.permutation(np.arange(1, eng.econfig.kv.num_pages)).tolist()   # page 0 is the null page
    tables = np.zeros((len(rows), kv.table_width), np.int32)
    for i, (p, d, slot, _) in enumerate(rows):
        n_pages = math.ceil((p + d) / page)
        assert n_pages < kv.table_width and 0 < slot <= sched.max_seqs, (n_pages, slot)
        tables[i, :n_pages] = [free.pop() for _ in range(n_pages)]
        tables[i, -1] = slot

    def batch_of(size, at):
        """Per-step arrays of a batch of ``size`` rows with the sequences at rows ``at``; the rest is padding."""
        def arrays(width, pos, lens):
            t, s, n, b = (np.zeros(shape, np.int32) for shape in ((size, width), (size, ), (size, ), (size, kv.table_width)))
            for i, r in enumerate(at):
                t[r, :lens[i]] = toks[i][pos[i]:pos[i] + lens[i]]
                s[r], n[r], b[r] = pos[i], lens[i], tables[i]
            return jnp.asarray(t), jnp.asarray(s), jnp.asarray(b), jnp.asarray(n)
        return arrays

    n = len(rows)
    small = batch_of(n + 1, list(range(n)))                                   # one padding row behind them
    bucket_rows = [round(i * (sched.decode_bucket - 1) / max(n - 1, 1)) for i in range(n)]
    bucket = batch_of(sched.decode_bucket, bucket_rows)

    def apply(p, c, t, s, b, ln, last):
        return eng.model.apply(p, t, s, b, c, ln, last)

    step = jax.jit(apply, static_argnums=6, donate_argnums=1)
    peek = jax.jit(apply, static_argnums=6)                                   # the cache stays as it was
    exact_programs = {}

    def exact(last, *args):
        """``peek`` compiled with the compiler's excess precision off, a program a (width, ``last``)."""
        key = (args[0].shape, last)
        if key not in exact_programs:
            exact_programs[key] = peek.lower(eng.params, eng.cache, *args, last).compile(
                compiler_options={"xla_allow_excess_precision": False})
        return exact_programs[key](eng.params, eng.cache, *args)[0]

    def worst(a, b):
        return float(jnp.max(plain.rel_l2(a, b)))

    pos, got = [0] * n, [[] for _ in rows]
    out = {"last_only_rows": [], "last_only_exact": 0.0, "steps": 0}
    wide_rows = []
    while any(pos[i] < len(toks[i]) for i in range(n)):
        lens = [min(chunk, p - pos[i]) if pos[i] < p else int(pos[i] < p + d) for i, (p, d, _, _) in enumerate(rows)]
        width = chunk if max(lens) > 1 else 1
        live = [i for i in range(n) if lens[i]]
        args = small(width, pos, lens)
        last = peek(eng.params, eng.cache, *args, True)[0][jnp.asarray(live), 0]
        ends = [i for i in live if pos[i] + lens[i] > rows[i][3]]              # rows whose last token is compared
        if ends:                                                              # the timed programs' batch
            wide = peek(eng.params, eng.cache, *bucket(width, pos, lens), True)[0]
            wide_rows += [(i, pos[i] + lens[i] - 1, wide[bucket_rows[i], 0]) for i in ends]
            del wide
        pick = lambda every: jnp.stack([every[i, lens[i] - 1] for i in live])      # noqa: E731
        out["last_only_exact"] = max(out["last_only_exact"],
                                     worst(exact(True, *args)[jnp.asarray(live), 0], pick(exact(False, *args))))
        logits, eng.cache = step(eng.params, eng.cache, *args, False)
        out["last_only_rows"] += np.asarray(plain.rel_l2(last, pick(logits))).tolist()
        for i in live:
            skip = max(rows[i][3] - pos[i], 0)
            if skip < lens[i]:
                got[i].append(logits[i, skip:lens[i]])
            pos[i] += lens[i]
        out["steps"] += 1
        del logits, last
    eng.cache = None
    out["last_only"] = max(out["last_only_rows"])

    ref_rows = [(toks[i], p, first) for i, (p, _, _, first) in enumerate(rows)]
    ref = [logits for logits, _ in serve_open_loop.reference_logits(config, eng.params, ref_rows)]
    out["program"] = [np.asarray(plain.rel_l2(jnp.concatenate(g), r)) for g, r in zip(got, ref)]
    out["bucket"] = max(worst(logits, ref[i][at - rows[i][3]]) for i, at, logits in wide_rows)
    del got
    out["zeroed"] = {}
    for kind in KINDS:
        changed = serve_open_loop.reference_logits(config, without(eng.params, kind), ref_rows)
        out["zeroed"][kind] = [np.asarray(plain.rel_l2(c, r)) for (c, _), r in zip(changed, ref)]
        del changed
    return out


def report(out: dict, rows: list) -> tuple:
    """Print the readings; per row (the 90th percentile of the program's
    errors, per kind the 10th percentile of the reference's change)."""
    for (p, d, slot, first), errs in zip(rows, out["program"]):
        print(f"phi4flash_check: program prompt={p} decode={d} slot={slot} from={first} positions={len(errs)} "
              f"p50={np.median(errs):.6f} p90={np.percentile(errs, 90):.6f} max={errs.max():.6f}", flush=True)
    for kind, per_row in out["zeroed"].items():
        print(f"phi4flash_check: zeroed={kind} " + " ".join(
            f"slot{slot}:p10={np.percentile(e, 10):.6f},p50={np.median(e):.6f}" for (_, _, slot, _), e in zip(rows, per_row)),
              flush=True)
    apart = [d for d in out["last_only_rows"] if d > 0]
    print(f"phi4flash_check: last_only={out['last_only']:.3e} rows={len(out['last_only_rows'])} rows_apart={len(apart)} "
          f"last_only_exact={out['last_only_exact']:.3e} bucket={out['bucket']:.3e} steps={out['steps']}", flush=True)
    return [(float(np.percentile(errs, 90)), {kind: float(np.percentile(per_row[i], 10)) for kind, per_row in out["zeroed"].items()})
            for i, errs in enumerate(out["program"])]
