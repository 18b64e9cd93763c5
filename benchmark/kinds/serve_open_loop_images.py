"""Traffic kind ``serve_open_loop_images``: ``serve_open_loop`` whose requests
carry images.  A request is a prompt of token ids in which each image stands
as a run of ``h w / 4`` placeholder ids, and the images themselves (pixels
``[h w, 3, p, p]`` from the seed, bfloat16 host arrays, and their grids).

Path under test: ``InferenceEngineV2 -> warm_all -> ServingEngine(WallClock)``,
``serve.submit(prompt, images=...)`` and ``tick()``: the vision tower runs
inside the tick as dispatches of its own, the copy of the pixels to the device
included, so both lie inside TTFT (timed from the time a request was due).

Taken from ``serve_open_loop`` as it is, since none of it touches a request's
payload: the engine configuration, ``build`` (weights, engine, arena,
``warm_all``), ``drive`` (through a frontend that hands ``submit`` the images
a prompt carries), ``summarise``, ``in_system``, the comparison of logits
position by position.  **Repeated here**, because the payload
goes through them: the schedule (``traffic_gen.serving_schedule`` draws token
ids alone; the arrival process is its ``_stretch``, called as it is), the
check's rows, ``program_logits`` and ``reference_logits`` (the rows carry
images: the engine's own encode programs and row buffer, then chunks with
``mm_index``), ``check``, ``limits``, ``sweep`` and ``run`` (they call the
above); ``group_readings`` (the percentile compared is the configuration's).  ``TraceWindow`` is the harness's with one more thing kept: the
trace's ``XLA Modules`` line (``step_trace.load``), by which the tower's
programs are told from the step programs.

The mix's file: ``text`` (text tokens a request, a ``traffic_gen`` mixture),
``images`` (``count``: a mixture over the number of images; ``grids``: grid
and weight), ``output``; ``prompt.clip`` states the shortest and the longest
prompt the mix can make (the harness sizes a sequence's pages from it).
Count, lengths, grids, pairing and due times come from the file's
``mix_seed``, the same in every seed; ``--seed`` draws token ids and pixels.
"""

import importlib
import math
import time

import numpy as np

import harness
import traffic_gen
from harness import say
from kinds import serve_open_loop as base
from kinds.serve_open_loop import build, drive, engine_config, in_system, in_system_most, position_errors, summarise
from percentiles import percentile

__all__ = ["run", "limits", "engine_config"]


# ------------------------------------------------------------------- payload


class Prompt(list):
    """Token ids, with the images their placeholder runs stand for."""
    images = None


class Frontend:
    """The serving frontend as ``drive`` knows it: ``submit`` hands on the
    images a prompt carries."""

    def __init__(self, serve):
        self._serve = serve

    def submit(self, prompt, **kwargs):
        return self._serve.submit(prompt, images=getattr(prompt, "images", None), **kwargs)

    def __getattr__(self, name):
        return getattr(self._serve, name)


def merge_of(cfg) -> int:
    kh, kw = cfg["vision_config"]["merge_kernel_size"]
    return kh * kw


def make_pixels(rng, grid, cfg):
    """[h w, 3, p, p] bfloat16 in [-1, 1): 256 levels, as a scaled 8-bit image has."""
    import ml_dtypes
    p = cfg["vision_config"]["patch_size"]
    levels = rng.integers(-128, 128, (grid[0] * grid[1], 3, p, p), dtype=np.int8)
    return (levels.astype(np.float32) / 128.0).astype(ml_dtypes.bfloat16)


def make_prompt(rng, cfg, text_len: int, grids) -> Prompt:
    """Text (a quarter of it in front, two tokens between images, the rest
    behind, so the last token is text) around one placeholder run an image."""
    ph, merge = cfg["media_placeholder_token_id"], merge_of(cfg)
    text = rng.integers(1, min(ph, cfg["vocab_size"]), text_len).tolist()
    between = 2 * (len(grids) - 1)
    front = max(1, (text_len - between) // 4)
    ids, at = text[:front], front
    for i, (h, w) in enumerate(grids):
        if i:
            ids += text[at:at + 2]
            at += 2
        ids += [ph] * (h * w // merge)
    prompt = Prompt(ids + text[at:])
    prompt.images = [(make_pixels(rng, g, cfg), tuple(g)) for g in grids]
    return prompt


def _stratified(values, weights, n: int, mix) -> list:
    """``n`` draws of ``values`` in the shares ``weights``, shuffled by the mix."""
    cum = np.cumsum(weights) / np.sum(weights)
    picks = [values[int(np.searchsorted(cum, (i + 0.5) / n))] for i in range(n)]
    return [picks[j] for j in mix.permutation(n)]


def serving_schedule(traffic, seconds, seed, cfg, rate_per_s=None, lead_in_s=None) -> list:
    """``traffic_gen.serving_schedule`` for requests with images: the same
    requests (text length, image count and grids, output length) at the same
    times in every seed; the seed draws token ids and pixels."""
    rate = traffic["rate_per_s"] if rate_per_s is None else rate_per_s
    rng = np.random.default_rng(int(seed))
    mix = np.random.default_rng(int(traffic["mix_seed"]))
    lead = float(traffic["lead_in_s"] if lead_in_s is None else lead_in_s)
    text_mix = {**traffic, "prompt": traffic["text"]}
    grids = [tuple(g["grid"]) for g in traffic["images"]["grids"]]
    weights = [g["weight"] for g in traffic["images"]["grids"]]
    out = []
    for measured, t0, length in ((False, -lead, lead), (True, 0.0, float(seconds))):
        rows = traffic_gen._stretch(mix, text_mix, rate, t0, length)
        counts = traffic_gen.stratified_lengths(traffic["images"]["count"], len(rows))
        counts = [counts[j] for j in mix.permutation(len(rows))]
        drawn = iter(_stratified(grids, weights, sum(counts), mix))
        for (due, text_len, out_len), n_images in zip(rows, counts):
            prompt = make_prompt(rng, cfg, int(text_len), [next(drawn) for _ in range(n_images)])
            out.append({"due": float(due), "measured": measured, "max_new_tokens": int(out_len), "prompt": prompt})
    return out


# ---------------------------------------------------------------- correctness
#
# As ``serve_open_loop``'s, with rows that carry images (``check.rows``: text
# tokens and grids).  A row's images go through the engine's own encode
# programs into units of its row buffer, its prompt through the engine's model,
# weights and arena in SplitFuse chunks with ``mm_index`` (chunks cross text /
# image borders, an image spans chunks), then ``decode_tokens`` one-token steps
# through the latent pages; the logits of every position are compared with the
# plain float32 reference's full forward pass, tower included.


def check_rows(cfg, seed):
    """The seeded sample: per row (prompt with images + decode ids, prompt length, first position compared)."""
    chk = cfg["check"]
    rng = np.random.default_rng(int(seed) + 1)
    rows = []
    for r in chk["rows"]:
        prompt = make_prompt(rng, cfg, r["text"], [tuple(g) for g in r["grids"]])
        ids = Prompt(list(prompt) + rng.integers(1, cfg["media_placeholder_token_id"], chk["decode_tokens"]).tolist())
        ids.images = prompt.images
        rows.append((ids, len(prompt), r.get("from", 0)))
    return rows


def program_logits(eng, rows):
    """Logits the program gives for ``rows``, one row at a time (an image is
    a dispatch of its own and a row's units are given back after it), through
    the engine's model, weights, arena, encode programs and row buffer."""
    import jax
    import jax.numpy as jnp

    kv, chunk = eng.econfig.kv, eng.econfig.scheduler.prefill_chunk
    step = jax.jit(lambda p, c, t, s, bt, l, mi, mr: eng.model.apply(p, t, s, bt, c, l, False, None, mi, mr),
                   donate_argnums=(1, ))
    out = []
    for toks, prompt_len, first in rows:
        pages = math.ceil(len(toks) / kv.page_size)
        if pages > kv.max_pages_per_seq or 1 + pages > kv.num_pages:
            raise RuntimeError("the check's rows do not fit the engine's arena")
        table = np.zeros((1, kv.max_pages_per_seq), np.int32)
        table[0, :pages] = 1 + np.arange(pages)
        table = jnp.asarray(table)
        mm_index, held = np.full(len(toks), -1, np.int32), []
        for img in eng._sequence_images(list(toks[:prompt_len]), toks.images):
            img.units = eng.mm_alloc.allocate(eng._image_units(img))
            held += img.units
            eng.dispatch_encode(img)
            mm_index[img.start:img.end] = eng.image_row_index(img)
        got = []

        def feed(width, start, n):
            ids, mi = np.zeros((1, width), np.int32), np.full((1, width), -1, np.int32)
            ids[0, :n], mi[0, :n] = toks[start:start + n], mm_index[start:start + n]
            logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(ids), jnp.asarray([start], jnp.int32), table,
                                     jnp.asarray([n], jnp.int32), jnp.asarray(mi), eng.mm_rows)
            skip = max(first - start, 0)
            if skip < n:
                got.append(logits[0, skip:n].astype(jnp.float32))

        for s in range(0, prompt_len, chunk):
            feed(chunk, s, min(chunk, prompt_len - s))
        for s in range(prompt_len, len(toks)):
            feed(1, s, 1)
        out.append(jnp.concatenate(got))
        eng.mm_alloc.free(held)
    return out


def reference_logits(cfg, params, rows, mode="f32", ablate=()):
    """Per row, the plain reference in ``mode``: (logits [len - from, vocab],
    router margins); ``ablate`` is the reference's (the builder's chip test).  A row is padded to a multiple of 512 tokens (id 0, not
    the placeholder's); attention is causal, so the padding changes nothing before it."""
    import jax
    import jax.numpy as jnp
    ref_mod = importlib.import_module("refs." + cfg["family"])
    out = []
    for toks, _, first in rows:
        grids = [g for _, g in toks.images]
        fwd = jax.jit(lambda p, ids, pixels: ref_mod.forward(p, ids, cfg, mode, first, images=list(zip(pixels, grids)),
                                                                ablate=ablate))
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        pixels = [jnp.asarray(px).reshape(px.shape[0], -1) for px, _ in toks.images]
        logits, margin = fwd(params, jnp.asarray(ids), pixels)
        out.append((logits[:len(toks) - first], margin[:len(toks) - first]))
    return out


def group_readings(cfg, errs, margins, groups):
    """``serve_open_loop.group_readings`` at the percentile the configuration
    states (``check.percentile``; 90 where it states none): per group the
    number ``check`` compares, how many positions are clear of router ties,
    and how many the group has.  Six of 64 sigmoid scores leave so few
    positions clear (one in eight at 0.005) that two or three of them whose
    expert flipped all the same, or that attend to one that did, move a 90th
    percentile from 0.013 to 0.07-0.15 in one seed of eight (PERF.md section
    2); the configuration states the median."""
    clear = margins >= cfg["check"]["router_margin_min"]
    q = cfg["check"].get("percentile", 90)
    return {g: (float(np.percentile(errs[clear & (groups == g)], q)),
                int((clear & (groups == g)).sum()), int((groups == g).sum()))
            for g in cfg["check"]["limits"]}


def check(ctx, eng):
    """Decides ``correct`` for the numerics."""
    cfg = ctx["config"]
    rows = check_rows(cfg, ctx["seed"])
    got = program_logits(eng, rows)
    ctx["parts"].mark("check_program")
    errs, margins, groups = position_errors(rows, got, reference_logits(cfg, eng.params, rows))
    ok = True
    for g, (value, n_clear, n) in group_readings(cfg, errs, margins, groups).items():
        limit = cfg["check"]["limits"][g]
        say("check", group=g, percentile=cfg["check"].get("percentile", 90), logit_rel_err=f"{value:.6f}", limit=limit,
            positions=n, clear_of_router_ties=n_clear,
            p50_of_all=f"{np.median(errs[groups == g]):.6f}", max_of_all=f"{errs[groups == g].max():.6f}")
        ok = ok and value <= limit
    ctx["parts"].mark("check_reference")
    return ok


def limits(ctx, seeds, dump=None):
    """Builder's mode (``selfcheck.py --limits``): for each seed the numbers
    ``check`` compares, for the program and for the control (the reference
    in int8, the tower's products too), at the cell's own size."""
    import os

    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    cfg = ctx["config"]
    pcfg = harness.program_config(cfg)
    for seed in seeds:
        _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
        eng = InferenceEngineV2(pcfg, params, engine_config(cfg, ctx["traffic"]))
        rows = check_rows(cfg, seed)
        ref = reference_logits(cfg, params, rows)
        raw = {}
        for who in ("program", "control"):
            got = program_logits(eng, rows) if who == "program" else \
                [logits for logits, _ in reference_logits(cfg, params, rows, mode="int8")]
            errs, margins, groups = position_errors(rows, got, ref)
            del got
            raw.update({who: errs, "margins": margins, "groups": groups})
            for g, (value, n_clear, n) in group_readings(cfg, errs, margins, groups).items():
                clear = (groups == g) & (margins >= cfg["check"]["router_margin_min"])
                say("limits", seed=seed, who=who, group=g, compared=f"{value:.6f}", clear=n_clear, positions=n,
                    p75_clear=f"{np.percentile(errs[clear], 75):.6f}", p90_clear=f"{np.percentile(errs[clear], 90):.6f}",
                    p50_all=f"{np.median(errs[groups == g]):.6f}", p90_all=f"{np.percentile(errs[groups == g], 90):.6f}")
        if dump:
            os.makedirs(dump, exist_ok=True)
            np.savez(os.path.join(dump, f"limits_{ctx['cell']['name']}_{seed}.npz"), **raw)
        say("limits_device", seed=seed, hbm_peak_bytes=max(harness.hbm_bytes(jax.devices()[:1])))
        del eng, params, ref


# --------------------------------------------------------------------- window


class TraceWindow(harness.TraceWindow):
    """The harness's window over the profiler; the reduced trace also keeps
    the first device's ``XLA Modules`` line (``modules``: one event a program run)."""

    def stop(self, now: float) -> None:
        import shutil

        import jax

        import step_trace
        import trace_reduce
        if not self.enabled or self.started is None or self.stopped is not None:
            return
        self.stopped = now
        self.window = (self.started, now)
        jax.profiler.stop_trace()
        try:
            if not self.rehearse:  # a CPU rehearsal has no device plane to reduce
                trace = step_trace.load(trace_reduce.find_xplane(self.dir))
                self.reduced = {**trace_reduce.reduce(trace), "modules": trace["modules"]}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def sweep(ctx, serve, clock, devices):
    """``serve_open_loop.sweep`` over this kind's schedule: several rates in
    one process after one set-up, a line per rate."""
    traffic, seconds = ctx["traffic"], ctx["seconds"]
    readings = traffic.get("at_rate")
    for n, rate in enumerate(ctx["sweep"]):
        lead_in_s = traffic_gen.lead_in_rule(traffic, readings) if readings else float(traffic["lead_in_s"])
        schedule = serving_schedule(traffic, seconds, ctx["seed"] + n, ctx["config"], rate_per_s=rate, lead_in_s=lead_in_s)
        t_open = clock.now() + lead_in_s
        records, _ = drive(Frontend(serve), clock, schedule, t_open, seconds, traffic["drain_cap_s"])
        attempted, failed, samples = summarise(records)
        row = {"rate_per_s": rate, "lead_in_s": lead_in_s, "attempted": attempted, "finished": attempted - failed,
               "failed": failed, "in_system_at_open": in_system(records, t_open),
               "in_system_at_close": in_system(records, t_open + seconds), "in_system_most": in_system_most(records),
               "drained_s": round(clock.now() - t_open - seconds, 2)}
        if samples["ttft_ms"] and samples["tpot_ms"]:
            readings = {"ttft_mean_ms": sum(samples["ttft_ms"]) / len(samples["ttft_ms"]),
                        "tpot_p50_ms": percentile(samples["tpot_ms"], 50)}
            row.update(ttft_mean_ms=round(readings["ttft_mean_ms"], 2),
                       ttft_p50_ms=round(percentile(samples["ttft_ms"], 50), 2),
                       ttft_p90_ms=round(percentile(samples["ttft_ms"], 90), 2),
                       tpot_p50_ms=round(readings["tpot_p50_ms"], 3),
                       queue_wait_max_ms=round(max(samples["queue_wait_ms"]), 2))
        say("sweep", **row)
        t_cap = clock.now() + traffic["drain_cap_s"]
        while clock.now() < t_cap and (serve.load_stats()["active"] or serve.load_stats()["queue_depth"]):
            serve.tick()
        prefix_cache = serve.engine.kv.prefix_cache
        if prefix_cache is not None:
            prefix_cache.evict(prefix_cache.cached_pages)
        if failed or row.get("queue_wait_max_ms", 0.0) > base.SLOT_WAIT_MS:
            say("sweep_ends", at_rate_per_s=rate, why="a request failed or waited for a slot")
            break
    say("sweep_device", hbm_peak_bytes=max(harness.hbm_bytes(devices)))
    return None


def run(ctx):
    from deepspeed_tpu.serving import ServingEngine, WallClock

    parts, traffic, seconds = ctx["parts"], ctx["traffic"], ctx["seconds"]
    eng, devices = build(ctx)
    numerics_ok = check(ctx, eng)
    compiles = harness.CompileListener()
    clock = WallClock()
    mono = time.monotonic() - clock.now()  # clock time + mono = time.monotonic()
    serve = ServingEngine(eng, clock=clock)
    if ctx["sweep"]:
        return sweep(ctx, serve, clock, devices)

    schedule = serving_schedule(traffic, seconds, ctx["seed"], ctx["config"])
    parts.mark("schedule")
    t_open = clock.now() + traffic["lead_in_s"]
    setup_s = parts.report(t_open + mono, lead_in=float(traffic["lead_in_s"]))
    tracer = TraceWindow(ctx, t_open, seconds)
    records, ticks = drive(Frontend(serve), clock, schedule, t_open, seconds, traffic["drain_cap_s"], tracer)
    tracer.stop(clock.now())

    attempted, failed, samples = summarise(records)
    n_compiles = compiles.since(t_open + mono)
    images = [len(r["prompt"].images) for r in schedule if r["measured"]]
    say("window", attempted=attempted, failed=failed, images=sum(images),
        in_system_at_open=in_system(records, t_open), in_system_at_close=in_system(records, t_open + seconds),
        in_system_most=in_system_most(records), drained_s=round(clock.now() - t_open - seconds, 3),
        **{f"{name}_{k}": round(v, 2) for name in ("ttft_ms", "tpot_ms", "queue_wait_ms", "gen_late_ms")
           if samples[name] for k, v in (("mean", sum(samples[name]) / len(samples[name])),
                                         *((f"p{q}", percentile(samples[name], q)) for q in (50, 90)))})
    say("check", compiles_in_window=n_compiles, limit=0)
    return {
        "correct": bool(numerics_ok and n_compiles == 0),
        "attempted": attempted, "failed": failed, "setup_s": setup_s,
        "samples": samples, "compiles_in_window": n_compiles,
        "ticks": [t for t in ticks if t_open <= t[0] < t_open + seconds],
        "reduced": tracer.reduced, "chips": 1,
        "hbm_peak_bytes": harness.hbm_bytes(devices),
        "attention": None,   # the paged kernel's work: no reader of this cell asks for it
    }
