"""Traffic kind ``serve_open_loop``: requests offered on a schedule to one
``ServingEngine`` replica, whatever the system does with them.

Path under test: ``InferenceEngineV2 -> warm_all -> ServingEngine(WallClock)``,
driven from one thread: due requests are submitted, then one ``tick()`` runs.
TTFT and TPOT are timed from the time a request was due, so a late generator
is charged to the system.
"""

import dataclasses
import importlib
import math
import time

import numpy as np

import harness
import traffic_gen
from harness import say, span
from percentiles import percentile


# --------------------------------------------------------------------- set-up


def engine_config(cfg, traffic):
    """The program's engine configuration for this configuration and mix:
    every slot holds the mix's longest request plus one fused dispatch of
    overshoot, so ``max_pages_per_seq`` follows from the traffic file."""
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama_cache import PagedKVConfig
    e = cfg["engine"]
    max_prompt, max_out = traffic_gen.longest_request(traffic)
    page, k = e["kv"]["page_size"], e["decode_steps_per_dispatch"]
    kv = PagedKVConfig(num_pages=e["kv"]["num_pages"], page_size=page,
                       max_pages_per_seq=math.ceil((max_prompt + max_out + k) / page) + 1)
    return RaggedInferenceEngineConfig(
        kv=kv, scheduler=SchedulerConfig(**e["scheduler"]), max_new_tokens=max_out,
        decode_steps_per_dispatch=k, enable_prefix_cache=e["enable_prefix_cache"])


def build(ctx):
    """Weights, engine, arena, warm-up.  Returns (engine, its devices)."""
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    cfg, traffic, parts = ctx["config"], ctx["traffic"], ctx["parts"]
    devices = jax.devices()[:1]
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, ctx["seed"], devices)
    jax.block_until_ready(params)
    parts.mark("weights")

    eng = InferenceEngineV2(pcfg, params, engine_config(cfg, traffic))
    del params
    jax.block_until_ready(eng.cache)
    parts.mark("engine_and_arena")

    warm = eng.warm_all()
    jax.random.split(eng.rng)  # the serving loop's only eager operation: compile it now
    say("warm_all", compiled=warm["compiled"], cached=warm["cached"], fallback=warm["fallback"],
        programs=len(warm["keys"]))
    if warm["fallback"]:
        raise RuntimeError(f"warm_all fell back to lazy compilation for {warm['fallback']} programs")
    parts.mark("warm_up")
    return eng, devices


# ---------------------------------------------------------------- correctness
#
# The sample (``check`` in the configuration file) is a few rows of token ids
# from the seed.  Each row's prompt goes through the engine's own model,
# weights and KV arena in SplitFuse chunks and then ``decode_tokens`` steps of
# one token, the paged kernel reading the pages written before; the logits of
# the positions from the row's ``from`` on are compared with the plain float32
# reference's full forward pass, position by position, as
# ||logits - ref|| / ||ref|| over the vocabulary.  Positions fall into groups,
# each held to its own limit: ``prefill`` (prompt positions of the short
# rows), ``long`` (prompt positions behind ``from`` tokens of context: the
# paged kernel over hundreds of pages) and ``decode`` (every row's decode
# steps).  A position whose router margin in the reference is under
# ``router_margin_min`` is left out: there a rounding error of any size picks
# another expert and the position reads near 1 in a sound run too.  Of the
# rest, the 90th percentile has to sit under the group's limit, so nine
# positions in ten of every group are held.
#
# A family's reference gives ``forward(params, ids, cfg, mode, first) ->
# (logits of the positions from first on, their router margins)``.


def check_rows(cfg, seed):
    """The seeded sample: per row (token ids, prompt length, first position compared)."""
    chk = cfg["check"]
    rng = np.random.default_rng(int(seed) + 1)
    return [(rng.integers(1, cfg["vocab_size"], r["prompt"] + chk["decode_tokens"]).tolist(),
             r["prompt"], r.get("from", 0)) for r in chk["rows"]]


def program_logits(eng, rows):
    """Logits the program gives for ``rows``, all rows in one batch, through
    the engine's model, weights and arena (pages 1.. of it, before any
    request holds them).  Returns per row a device array [len - from, vocab]."""
    import jax
    import jax.numpy as jnp

    kv, chunk = eng.econfig.kv, eng.econfig.scheduler.prefill_chunk
    b = len(rows)
    prompt_lens = [p for _, p, _ in rows]
    pages_each = math.ceil(max(len(t) for t, _, _ in rows) / kv.page_size)
    if pages_each > kv.max_pages_per_seq or 1 + b * pages_each > kv.num_pages:
        raise RuntimeError("the check's rows do not fit the engine's arena")
    table = np.zeros((b, kv.max_pages_per_seq), np.int32)
    for i in range(b):
        table[i, :pages_each] = 1 + i * pages_each + np.arange(pages_each)  # page 0 is the null page
    table = jnp.asarray(table)
    step = jax.jit(lambda p, c, t, s, bt, l: eng.model.apply(p, t, s, bt, c, l), donate_argnums=(1, ))
    out = [[] for _ in rows]

    def feed(width, starts, lens):
        toks = np.zeros((b, width), np.int32)
        for i, (s, n) in enumerate(zip(starts, lens)):
            toks[i, :n] = rows[i][0][s:s + n]
        logits, eng.cache = step(eng.params, eng.cache, jnp.asarray(toks), jnp.asarray(starts, jnp.int32),
                                 table, jnp.asarray(lens, jnp.int32))
        for i, (s, n) in enumerate(zip(starts, lens)):
            skip = max(rows[i][2] - s, 0)
            if skip < n:
                out[i].append(logits[i, skip:n].astype(jnp.float32))

    for s in range(0, max(prompt_lens), chunk):
        feed(chunk, [min(s, p) for p in prompt_lens], [min(max(p - s, 0), chunk) for p in prompt_lens])
    for j in range(len(rows[0][0]) - prompt_lens[0]):
        feed(1, [p + j for p in prompt_lens], [1] * b)
    return [jnp.concatenate(o) for o in out]


def reference_logits(cfg, params, rows, mode="f32"):
    """Per row, the plain reference in ``mode``: (logits [len - from, vocab],
    router margins [len - from]).  A row is padded to a multiple of 512
    tokens, so rows of like length share a program; attention is causal, so
    the padding behind a row changes nothing before it."""
    import jax
    import jax.numpy as jnp
    ref_mod = importlib.import_module("refs." + cfg["family"])
    fwd = jax.jit(lambda p, ids, first: ref_mod.forward(p, ids, cfg, mode, first), static_argnums=2)
    out = []
    for toks, _, first in rows:
        ids = np.zeros(512 * math.ceil(len(toks) / 512), np.int32)
        ids[:len(toks)] = toks
        logits, margin = fwd(params, jnp.asarray(ids), first)
        out.append((logits[:len(toks) - first], margin[:len(toks) - first]))
    return out


def position_errors(rows, got, ref):
    """Per position compared: (||got - ref|| / ||ref|| over the vocabulary,
    the reference's router margin, the position's group)."""
    import jax.numpy as jnp

    from refs import plain
    errs, margins, groups = [], [], []
    for (toks, prompt, first), g, (want, margin) in zip(rows, got, ref):
        errs.append(np.asarray(plain.rel_l2(jnp.asarray(g), want)))
        margins.append(np.asarray(margin))
        index = np.arange(first, len(toks))
        groups.append(np.where(index >= prompt, "decode", "long" if first else "prefill"))
    return np.concatenate(errs), np.concatenate(margins), np.concatenate(groups)


def group_readings(cfg, errs, margins, groups):
    """Per group: the number ``check`` compares (90th percentile of the
    errors of the positions whose router margin is clear), how many
    positions that is, and how many the group has."""
    clear = margins >= cfg["check"]["router_margin_min"]
    return {g: (float(np.percentile(errs[clear & (groups == g)], 90)),
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
        say("check", group=g, logit_rel_err_p90=f"{value:.6f}", limit=limit, positions=n, clear_of_router_ties=n_clear,
            p50_of_all=f"{np.median(errs[groups == g]):.6f}", max_of_all=f"{errs[groups == g].max():.6f}")
        ok = ok and value <= limit
    ctx["parts"].mark("check_reference")
    return ok


def limits(ctx, seeds, dump=None):
    """Builder's mode (``selfcheck.py --limits``): for each seed the numbers
    ``check`` compares, read for the program and for the control (the
    reference computed in int8 in the program's place), at the cell's own
    size, in one process.  ``dump`` is a directory for the per-position
    errors, margins and groups, from which the limits were chosen."""
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
                say("limits", seed=seed, who=who, group=g, p90_clear=f"{value:.6f}", clear=n_clear, positions=n,
                    p50_all=f"{np.median(errs[groups == g]):.6f}", p90_all=f"{np.percentile(errs[groups == g], 90):.6f}")
        if dump:
            os.makedirs(dump, exist_ok=True)
            np.savez(os.path.join(dump, f"limits_{ctx['cell']['name']}_{seed}.npz"), **raw)
        del eng, params, ref


# --------------------------------------------------------------------- window


@dataclasses.dataclass
class Record:
    due: float
    measured: bool
    prompt_len: int
    submit_ts: float = None
    req: object = None


def drive(serve, clock, schedule, t_open, seconds, drain_cap_s, tracer=None):
    """Offer ``schedule`` (due times relative to ``t_open``, on ``clock``) and
    tick until every measured request has ended or the drain cap runs out.
    Returns (records, ticks); a tick is (start, end, tokens out, prompt tokens
    whose prefill ended in it)."""
    records = [Record(t_open + r["due"], r["measured"], len(r["prompt"])) for r in schedule]
    live, ticks, i = [], [], 0
    t_end = t_open + seconds + drain_cap_s
    while True:
        now = clock.now()
        if tracer is not None:
            tracer.poll(now)
        while i < len(records) and records[i].due <= now:
            rec, item = records[i], schedule[i]
            rec.submit_ts = clock.now()
            with span("submit"):
                rec.req = serve.submit(item["prompt"], max_new_tokens=item["max_new_tokens"],
                                       arrival_ts=rec.due)
            live.append(rec)
            i += 1
        live = [r for r in live if not r.req.state.terminal]
        if i == len(records) and not any(r.measured for r in live):
            break
        if now > t_end:
            break
        if not live:
            clock.wait_until(min(records[i].due, now + 0.05))
            continue
        waiting = {id(r): r for r in live if r.req.first_token_ts is None}
        t0 = clock.now()
        with span("tick"):
            out = serve.tick()
        t1 = clock.now()
        n_out = sum(len(v) for v in out.values())
        n_prompt = sum(r.prompt_len for r in waiting.values() if r.req.first_token_ts is not None)
        if n_out or n_prompt:
            ticks.append((t0, t1, n_out, n_prompt))
    return records, ticks


def in_system(records, t):
    """Requests due by ``t`` and not finished by ``t``."""
    n = 0
    for r in records:
        if r.due <= t and r.req is not None:
            fin = r.req.finish_ts
            n += fin is None or fin > t
        elif r.due <= t:
            n += 1
    return n


def in_system_most(records):
    """The most requests in the system at any request's due time."""
    return max(in_system(records, r.due) for r in records)


def summarise(records):
    """(attempted, failed, per-request samples in ms) of the measured requests."""
    from deepspeed_tpu.serving.request import RequestState
    measured = [r for r in records if r.measured]
    done = [r for r in measured if r.req is not None and r.req.state is RequestState.DONE
            and len(r.req.tokens) == r.req.max_new_tokens]
    samples = {
        "ttft_ms": [1e3 * (r.req.first_token_ts - r.due) for r in done],
        "tpot_ms": [1e3 * (r.req.finish_ts - r.req.first_token_ts) / (len(r.req.tokens) - 1)
                    for r in done if len(r.req.tokens) > 1],
        # the two tile the wait from due to admitted: neither holds the other's interval
        "gen_late_ms": [1e3 * (r.submit_ts - r.due) for r in done],
        "queue_wait_ms": [1e3 * (r.req.admitted_ts - r.submit_ts) for r in done],
    }
    return len(measured), len(measured) - len(done), samples


def attention_work(ctx, records, tracer, chunk):
    """(FLOPs, bytes) the paged attention of every request needed inside the
    traced stretch, all layers: a request's prefill work is spread evenly
    from its admission to its first token and its decode work from there to
    its end, and the part of each that overlaps the trace is counted.
    Unfinished stretches are left out, so the count errs low."""
    import roofline
    if tracer.window is None:
        return None
    cfg = ctx["config"]
    n_q, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // n_q
    w0, w1 = tracer.window
    flops = nbytes = 0.0

    def add(a, b, work):
        nonlocal flops, nbytes
        if a is None or b is None or b <= a:
            return
        share = max(0.0, min(b, w1) - max(a, w0)) / (b - a)
        flops, nbytes = flops + share * work[0], nbytes + share * work[1]

    for r in records:
        if r.req is None:
            continue
        add(r.req.admitted_ts, r.req.first_token_ts, roofline.paged_prefill(r.prompt_len, chunk, n_q, n_kv, d))
        add(r.req.first_token_ts, r.req.finish_ts,
            roofline.paged_decode(r.prompt_len, len(r.req.tokens), n_q, n_kv, d))
    layers = cfg["num_hidden_layers"]
    return {"flops": flops * layers, "bytes": nbytes * layers}


#: a request that waited this long to be admitted waited for a slot (an
#: admission with a slot free takes a fraction of a millisecond)
SLOT_WAIT_MS = 1000.0


def sweep(ctx, serve, clock, devices):
    """Several rates in one process after one set-up: a line per rate with
    the requests in the system when the window opens and closes, finished
    and failed, the longest wait for admission and the cell's latencies.
    Each window opens behind a lead-in taken from its rate: the stay of a
    median request under the readings of the window before it (the file's
    ``at_rate`` for the first), so that a rate under the knee does not grow
    inside its window for want of a lead-in.  A rate at which a request
    waited for a slot ends the sweep: the rates above it queue too.  Used to
    find a mix's knee and to read a rate's spread; never a measured run."""
    traffic, seconds = ctx["traffic"], ctx["seconds"]
    readings = traffic.get("at_rate")
    for n, rate in enumerate(ctx["sweep"]):
        lead_in_s = traffic_gen.lead_in_rule(traffic, readings) if readings else float(traffic["lead_in_s"])
        schedule = traffic_gen.serving_schedule(traffic, seconds, ctx["seed"] + n, ctx["config"]["vocab_size"],
                                                rate_per_s=rate, lead_in_s=lead_in_s)
        t_open = clock.now() + lead_in_s
        records, ticks = drive(serve, clock, schedule, t_open, seconds, traffic["drain_cap_s"])
        attempted, failed, samples = summarise(records)
        row = {"rate_per_s": rate, "lead_in_s": lead_in_s, "attempted": attempted, "finished": attempted - failed,
               "failed": failed, "in_system_at_open": in_system(records, t_open),
               "in_system_at_close": in_system(records, t_open + seconds),
               "in_system_most": in_system_most(records),
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
        # a window opens on the arena a run's would: none of the windows' before it in the prefix cache
        prefix_cache = serve.engine.kv.prefix_cache
        if prefix_cache is not None:
            prefix_cache.evict(prefix_cache.cached_pages)
        if failed or row.get("queue_wait_max_ms", 0.0) > SLOT_WAIT_MS:
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

    schedule = traffic_gen.serving_schedule(traffic, seconds, ctx["seed"], ctx["config"]["vocab_size"])
    parts.mark("schedule")
    # the lead-in is set-up the traffic needs: ``drive`` runs it and the window
    # in one loop, so the window opens on an engine in steady state
    t_open = clock.now() + traffic["lead_in_s"]
    setup_s = parts.report(t_open + mono, lead_in=float(traffic["lead_in_s"]))
    tracer = harness.TraceWindow(ctx, t_open, seconds)
    records, ticks = drive(serve, clock, schedule, t_open, seconds, traffic["drain_cap_s"], tracer)
    tracer.stop(clock.now())

    attempted, failed, samples = summarise(records)
    n_compiles = compiles.since(t_open + mono)
    say("window", attempted=attempted, failed=failed,
        in_system_at_open=in_system(records, t_open), in_system_at_close=in_system(records, t_open + seconds),
        in_system_most=in_system_most(records),
        drained_s=round(clock.now() - t_open - seconds, 3),
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
        "attention": attention_work(ctx, records, tracer, eng.econfig.scheduler.prefill_chunk),
    }
