"""A slot-holding twin's two-group step programs against the same work as a
rectangle, at the cell's size and in real slots: what the benchmark's
``correct`` cannot see (it feeds rectangles, in the scratch slot) and what
the engine runs in every mixed step since the twins take row groups
(``models/llama_cache.py`` "Row groups"): the decode bucket at one slot a row
beside a prefill group of a rung of rows at the chunk.

The work of one mixed step: of the bucket's rows about half decode, each
behind a context of its own depth in a slot and on pages drawn at random, the
rest are padding; the prefill rows are a continuation that ends inside its
chunk, a prompt's first chunk (a slot that starts from zero), a continuation
that fills its chunk and a short prompt.  Once as the groups ``((bucket, 1),
(rung, chunk))`` on the flat axis and once as the rectangle ``[bucket + rung,
chunk]``, both from one cache (kept on the host between them: a device holds
one), both with the head over each row's last real token as the engine's
programs take it.  Compared: those logits a live row, and every array of the
cache but its null page and scratch slot, as ``||two groups - rectangle||``
over ``||rectangle - before||``, the size of what the step wrote.

Used by ``test_phi4flash_on_chip.py`` and ``test_granite_hybrid_on_chip.py``
at the cells' sizes and by their CPU miniatures at the rehearsal sizes.
"""

import math
import os
import sys

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def readings(config: dict, traffic: dict, seed: int, fill, real_from: dict, rungs=(1, 4), decode_rows=None) -> dict:
    """``fill(abstract parameters) -> parameters`` (a check module's
    ``check_init``); ``real_from``: per array of the cache the first index of
    its second axis that is no null page and no scratch slot.  Returns per
    rung ``{"logits": the largest relative distance of a live row's logits,
    "cache": {array: distance over the step's update}, "rows": (decoding,
    prefilling)}``."""
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
    eng = InferenceEngineV2(pcfg, fill(abstract), serve_open_loop.engine_config(config, traffic))
    kv, sched = eng.kv, eng.econfig.scheduler
    chunk, page, bucket = sched.prefill_chunk, kv.page_size, sched.decode_bucket
    n_decode = decode_rows or bucket // 2 + 1
    assert n_decode + max(rungs) <= sched.max_seqs and n_decode <= bucket

    rng = np.random.default_rng(int(seed) + 2)
    # (context before the step, tokens in the step) a sequence: the decoding rows, then the prefilling ones
    work = [(int(rng.integers(chunk + 2, 5 * chunk)), 1) for _ in range(n_decode)]
    work += [(2 * chunk, chunk - chunk // 4), (0, chunk), (chunk, chunk), (0, chunk // 3 + 1)][:max(rungs)]
    toks = [rng.integers(1, config["vocab_size"], before + n) for before, n in work]
    slots = rng.permutation(np.arange(1, sched.max_seqs + 1))[:len(work)]
    free = rng.permutation(np.arange(1, eng.econfig.kv.num_pages)).tolist()   # page 0 is the null page
    tables = np.zeros((len(work), kv.table_width), np.int32)
    for i, (before, n) in enumerate(work):
        n_pages = math.ceil((before + n) / page)
        tables[i, :n_pages] = [free.pop() for _ in range(n_pages)]
        tables[i, -1] = slots[i]

    def apply(p, c, t, s, b, ln, groups):
        return eng.model.apply(p, t, s, b, c, ln, True, groups)

    step = jax.jit(apply, static_argnums=6, donate_argnums=1)

    # every sequence's context, in rectangles of a chunk
    for at in range(0, max(before for before, _ in work), chunk):
        lens = np.clip([before - at for before, _ in work], 0, chunk).astype(np.int32)
        start = np.minimum(at, [before for before, _ in work]).astype(np.int32)
        t = np.zeros((len(work), chunk), np.int32)
        for i, n in enumerate(lens):
            t[i, :n] = toks[i][start[i]:start[i] + n]
        _, eng.cache = step(eng.params, eng.cache, jnp.asarray(t), jnp.asarray(start), jnp.asarray(tables),
                            jnp.asarray(lens), None)
    before_step = jax.device_get(eng.cache)
    eng.cache = None

    def norm(x):
        return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))

    out = {}
    decode_at = np.sort(rng.permutation(bucket)[:n_decode])                   # the decoding rows' places in the bucket
    for rung in rungs:
        rows = bucket + rung
        place = list(decode_at) + [bucket + j for j in range(rung)]          # row of the step a sequence
        seqs = list(range(n_decode)) + [n_decode + j for j in range(rung)]
        start, lens = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
        table, rect = np.zeros((rows, kv.table_width), np.int32), np.zeros((rows, chunk), np.int32)
        for r, i in zip(place, seqs):
            start[r], lens[r], table[r] = work[i][0], work[i][1], tables[i]
            rect[r, :lens[r]] = toks[i][start[r]:start[r] + lens[r]]
        groups = ((bucket, 1), (rung, chunk))
        flat = np.concatenate([rect[:bucket, 0], rect[bucket:].reshape(-1)])
        got = {}
        for name, tokens, g in (("groups", flat, groups), ("rectangle", rect, None)):
            logits, cache = step(eng.params, jax.device_put(before_step), jnp.asarray(tokens), jnp.asarray(start),
                                 jnp.asarray(table), jnp.asarray(lens), g)
            got[name] = (np.asarray(logits[jnp.asarray(place), 0], np.float32), jax.device_get(cache))
            del logits, cache
        (l_groups, c_groups), (l_rect, c_rect) = got["groups"], got["rectangle"]
        reading = {"rows": (n_decode, rung), "cache": {},
                   "logits": float(np.max(plain.rel_l2(jnp.asarray(l_groups), jnp.asarray(l_rect))))}
        for name, first in real_from.items():
            f32 = lambda cache: np.asarray(cache[name][:, first:], np.float32)  # noqa: E731
            wrote = norm(f32(c_rect) - f32(before_step))
            assert wrote > 0, f"the step wrote nothing to {name}"
            reading["cache"][name] = norm(f32(c_groups) - f32(c_rect)) / wrote
        out[rung] = reading
        del got
    return out


def report(tag: str, out: dict) -> float:
    """Print the readings; the largest of them."""
    worst = 0.0
    for rung, r in out.items():
        print(f"{tag}: two_groups rung={rung} rows_decode={r['rows'][0]} rows_prefill={r['rows'][1]} "
              f"logits={r['logits']:.6f} " + " ".join(f"{k}={v:.6f}" for k, v in r["cache"].items()), flush=True)
        worst = max(worst, r["logits"], *r["cache"].values())
    return worst
