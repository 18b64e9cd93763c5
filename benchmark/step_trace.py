#!/usr/bin/env python3
"""The program's own step records, read beside the device trace (PR 24).

With a recorder attached (``StepAnatomy(clock, annotate=profiler_range)``)
the serving program writes into the profiler's host plane, on the device
planes' clock, one ``ds.step`` range a step, whose metadata are the step's
program key and counts, and one instant ``ds.mark.<segment>`` where each host
segment ends; every step program and kernel carries a name on the device
side (``XLA Modules``: ``jit_ds_step_b16_c128``; ``XLA Ops``:
``ds_paged_attention``).  This module is what reads them:

* ``load`` keeps, beside what ``trace_reduce.load`` keeps, the host events
  named ``ds.*`` with their metadata and the first device's ``XLA Modules``
  line; ``segments`` rebuilds ``ds.<segment>`` as the interval between two
  marks of one step;
* ``idle_gaps`` gives each idle gap of the first device to the INNERMOST
  span over its midpoint, the benchmark's own four (``tick``, ...) staying
  the outer ones; ``modules`` counts runs and device seconds by program;
* ``programs`` is the table a traced run prints, one line a program key;
* ``slot_fill_share``, ``mixed_step_share``, ``step_host_p50_ms`` and
  ``step_device_wait_p50_ms`` read the step rows; ``clock_error`` says how
  far the recorder's own edges lie from the trace's.

No cell reads it yet: ``kinds/serve_open_loop.py`` attaches no recorder and
``run.py`` prints no ``programs:`` line, and a PR of another kind may not edit
either (PERF.md section 7 lists the edits).  Until then this file is also
the builder's command that runs one serving cell with the recorder attached:

    python3 benchmark/step_trace.py --workload mixtral_chat --seed 7 --seconds 20

It prints ``steps:``, ``slow_ticks:``, ``clock:``, ``programs:``, ``spans:``,
``idle_gaps:``, ``kernels:`` and ``modules:`` lines and, last, one JSON
object.  For a cell of another kind (the training cell, whose spans are
always there) it runs the kind's own ``run`` and reads the trace it kept.  It
is never a measured run: the driver does not call it.
"""

import re
import statistics
import time

T_START = time.monotonic()

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from harness import say  # noqa: E402

MODULES_LINE = "XLA Modules"
MARK = "ds.mark."
STEP = "ds.step"


# ----------------------------------------------------------------- the trace


def load(path: str) -> dict:
    """``trace_reduce.load(path)`` plus ``program`` (host events named
    ``ds.*``, with their metadata as the event's statistics) and ``modules``
    (the first device's ``XLA Modules`` line: one event an executed program)."""
    from jax.profiler import ProfileData
    trace = trace_reduce.load(path)
    program, modules = [], {}
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = [_event(ev, {}) for ev in line.events]
            elif not m and plane.name.startswith("/host:"):
                program.extend(_event(ev, dict(ev.stats)) for ev in line.events if ev.name.startswith("ds."))
    trace["program"] = sorted(program, key=lambda e: e[1])
    trace["modules"] = modules[min(modules)] if modules else []
    return trace


def _event(ev, stats):
    start = ev.start_ns * 1e-9
    return (ev.name, start, start + ev.duration_ns * 1e-9, stats)


def segments(program: list) -> list:
    """The spans the program drew, as (name, start, end, stats): every
    ``ds.*`` range as it is, and in place of the instants ``ds.mark.<s>``
    the segments they end: ``ds.<s>`` from the previous mark of the same
    ``ds.step`` (or the step's begin) to the mark, and ``ds.bookkeeping``
    from the last mark to the step's end."""
    marks = sorted((e for e in program if e[0].startswith(MARK)), key=lambda e: e[1])
    out = [e for e in program if not e[0].startswith(MARK)]
    i = 0
    for step in sorted((e for e in program if e[0] == STEP), key=lambda e: e[1]):
        cursor = step[1]
        while i < len(marks) and marks[i][1] < step[1]:
            i += 1  # a mark outside every step: the recorder ignored it too
        while i < len(marks) and marks[i][1] <= step[2]:
            out.append(("ds." + marks[i][0][len(MARK):], cursor, marks[i][1], {}))
            cursor = marks[i][1]
            i += 1
        if step[2] > cursor:
            out.append(("ds.bookkeeping", cursor, step[2], {}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def owned_gaps(trace: dict, spans: list) -> list:
    """[(owner, start, end)]: every interval in which the first device runs
    nothing, inside the traced stretch, with the innermost span over its
    midpoint (the shortest of those that cover it; ``ds.step`` only where
    none of its segments does), else ``no_span``."""
    devices = trace["devices"]
    first = min(devices)
    every = [e for evs in devices.values() for e in evs] + list(trace["host"])
    t0, t1 = min(e[1] for e in every), max(e[2] for e in every)
    busy = trace_reduce.union((e[1], e[2]) for e in devices[first])
    cover = sorted(((e[1], e[2], e[0]) for e in list(trace["host"]) + spans), key=lambda s: s[1] - s[0])
    out = []
    for s, e in trace_reduce.subtract([[t0, t1]], busy):
        mid = 0.5 * (s + e)
        out.append((next((n for a, b, n in cover if a <= mid <= b), "no_span"), s, e))
    return out


def idle_gaps(trace: dict, spans: list) -> list:
    """[(owner, seconds)], largest first: ``owned_gaps`` summed by owner."""
    gaps = {}
    for owner, s, e in owned_gaps(trace, spans):
        gaps[owner] = gaps.get(owner, 0.0) + e - s
    return sorted(gaps.items(), key=lambda kv: -kv[1])


MODULE_NAME = re.compile(r"^(jit_[A-Za-z0-9_]+)")


def program_key(module: str) -> str:
    """``jit_ds_step_b16_c128(1234...)`` -> ``step:b16:c128``, the key the
    step records carry; any other program keeps its name (``jit__lambda``)."""
    m = MODULE_NAME.match(module)
    name = m.group(1) if m else module
    return name[len("jit_ds_"):].replace("_", ":") if name.startswith("jit_ds_") else name


def modules(trace: dict) -> dict:
    """{program: {"runs", "device_s"}} over the ``XLA Modules`` line."""
    out = {}
    for name, start, end, _ in trace["modules"]:
        row = out.setdefault(program_key(name), {"runs": 0, "device_s": 0.0})
        row["runs"] += 1
        row["device_s"] += end - start
    return out


# ------------------------------------------------------------- the step rows


def programs(rows: list, by_module: dict = None) -> dict:
    """Per program key over step rows (``StepRecord.to_row``): steps, host
    wall seconds, real tokens and slots, and, with ``modules()`` of the same
    stretch, the runs and device seconds the device trace counted."""
    out = {}
    for r in rows:
        p = out.setdefault(r["key"], {"steps": 0, "wall_s": 0.0, "tokens_real": 0, "slots": 0,
                                      "tokens_out": 0, "tokens_discarded": 0})
        p["steps"] += 1
        p["wall_s"] += r["wall_s"]
        for c in ("tokens_real", "slots", "tokens_out", "tokens_discarded"):
            p[c] += r[c]
    for key, m in (by_module or {}).items():
        if key in out:
            out[key].update(runs=m["runs"], device_s=m["device_s"])
    return {k: out[k] for k in sorted(out)}


def slot_fill_share(rows):
    """Real token positions over the positions the programs computed."""
    slots = sum(r["slots"] for r in rows)
    return sum(r["tokens_real"] for r in rows) / slots if slots else None


def mixed_step_share(rows):
    """Steps of a single-step program wider than one token (``step:*:c128``:
    a tick in which a decoding request gets one token, not k) over all steps."""
    if not rows:
        return None
    return sum(1 for r in rows if r["key"].startswith("step:") and not r["key"].endswith(":c1")) / len(rows)


def step_host_p50_ms(rows):
    """Per step, everything but the wait for the device: host segments and host gap."""
    return 1e3 * statistics.median(r["wall_s"] - r["device_s"] for r in rows) if rows else None


def step_device_wait_p50_ms(rows):
    """Per step, the host's wait at the readback (the ``ds.device_wait`` segment)."""
    return 1e3 * statistics.median(r["device_s"] for r in rows) if rows else None


def clock_error(rows: list, spans: list) -> dict:
    """How far the trace's edges lie from the recorder's own, in ms, over the
    steps both hold (matched by index): the ``ds.step`` range against
    ``wall_s - host_gap_s``, the ``ds.device_wait`` segment against
    ``device_s`` (two edges each), and the spread of ``trace end - end_ts``
    about its median (the two clocks differ by a constant)."""
    by_index = {r["index"]: r for r in rows}
    steps = [s for s in spans if s[0] == STEP and s[3].get("index") in by_index]
    waits = sorted((s for s in spans if s[0] == "ds.device_wait"), key=lambda s: s[1])
    d_step, d_wait, offsets = [], [], []
    for s in steps:
        r = by_index[s[3]["index"]]
        d_step.append(abs((s[2] - s[1]) - (r["wall_s"] - r["host_gap_s"])))
        offsets.append(s[2] - r["end_ts"])
        inside = [w[2] - w[1] for w in waits if s[1] <= w[1] and w[2] <= s[2]]
        if inside:
            d_wait.append(abs(sum(inside) - r["device_s"]))
    if not steps:
        return {"steps": 0}
    off = statistics.median(offsets)
    return {"steps": len(steps), "step_range_max_ms": 1e3 * max(d_step),
            "device_wait_max_ms": 1e3 * max(d_wait) if d_wait else None,
            "end_offset_spread_max_ms": 1e3 * max(abs(o - off) for o in offsets)}


def slow_ticks(ticks: list, t_open: float, factor: float = 3.0) -> list:
    """(offset from the window's opening, duration, tokens out, prompt tokens
    ended) of each tick longer than ``factor`` times the median tick."""
    if not ticks:
        return []
    med = statistics.median(t[1] - t[0] for t in ticks)
    return [(round(t[0] - t_open, 3), round(t[1] - t[0], 4), t[2], t[3]) for t in ticks if t[1] - t[0] > factor * med]


# --------------------------------------------- the builder's command (no cell)


class KeptTrace(harness.TraceWindow):
    """The harness's profiler window, keeping what ``load`` reads of the trace."""
    last = None
    describe = False   # also print what the trace holds (``trace_reduce.describe``), for looking at it by hand

    def stop(self, now):
        import shutil

        import jax
        if not self.enabled or self.started is None or self.stopped is not None:
            return
        self.stopped, self.window, self.trace = now, (self.started, now), None
        jax.profiler.stop_trace()
        try:
            path = trace_reduce.find_xplane(self.dir)
            if self.describe:
                trace_reduce.describe(path, top=8)
            self.trace = load(path)
            if not self.rehearse:
                self.reduced = trace_reduce.reduce(self.trace)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        KeptTrace.last = self


def run_recorded(ctx: dict) -> dict:
    """``kinds.serve_open_loop.run`` with a recorder attached before the
    lead-in and the trace kept for this module's readers."""
    import traffic_gen
    from deepspeed_tpu.serving import ServingEngine, WallClock
    from deepspeed_tpu.telemetry import StepAnatomy
    from deepspeed_tpu.utils.nvtx import profiler_range
    from kinds import serve_open_loop as sol

    parts, traffic, seconds = ctx["parts"], ctx["traffic"], ctx["seconds"]
    eng, devices = sol.build(ctx)
    numerics_ok = sol.check(ctx, eng)
    compiles = harness.CompileListener()
    clock = WallClock()
    mono = time.monotonic() - clock.now()
    anat = eng.set_anatomy(StepAnatomy(clock=clock, max_steps=1 << 16,
                                       annotate=profiler_range if ctx["trace"] else None))
    anat.mark_steady()  # warm_all is done: a compile from here on is a steady-state recompile
    serve = ServingEngine(eng, clock=clock)
    schedule = traffic_gen.serving_schedule(traffic, seconds, ctx["seed"], ctx["config"]["vocab_size"])
    t_open = clock.now() + traffic["lead_in_s"]
    setup_s = parts.report(t_open + mono, lead_in=float(traffic["lead_in_s"]))
    tracer = KeptTrace(ctx, t_open, seconds)
    records, ticks = sol.drive(serve, clock, schedule, t_open, seconds, traffic["drain_cap_s"], tracer)
    tracer.stop(clock.now())
    attempted, failed, samples = sol.summarise(records)
    n_compiles = compiles.since(t_open + mono)
    return {
        "correct": bool(numerics_ok and n_compiles == 0 and anat.steady_state_recompiles == 0),
        "attempted": attempted, "failed": failed, "setup_s": setup_s, "samples": samples,
        "compiles_in_window": n_compiles, "t_open": t_open,
        "ticks": [t for t in ticks if t_open <= t[0] < t_open + seconds],
        "steps": [r.to_row() for r in anat.steps if t_open <= r.end_ts < t_open + seconds],
        "reduced": tracer.reduced, "hbm_peak_bytes": harness.hbm_bytes(devices),
    }


def report_steps(run: dict, tracer, rehearse: bool, reader) -> dict:
    """The lines that read the step rows: ``steps:``, ``slow_ticks:``,
    ``clock:``, ``programs:``.  ``reader`` is ``run.reader``: the outside
    view of the same window comes from the cells' own reader files."""
    rows = run["steps"]
    out = {"steps": len(rows), "slot_fill_share": slot_fill_share(rows), "mixed_step_share": mixed_step_share(rows),
           "tokens_per_tick": reader("layer_metrics", "tokens_per_tick")(run)}
    if not rehearse:  # a CPU run reports counts and shares of counts, never a time
        out.update(setup_s=run["setup_s"], step_host_p50_ms=step_host_p50_ms(rows),
                   step_device_wait_p50_ms=step_device_wait_p50_ms(rows),
                   tick_p50_ms=reader("layer_metrics", "tick_p50_ms")(run),
                   tpot_p50_ms=reader("end_to_end", "tpot_p50_ms")(run))
    say("steps", **out)
    for tick in slow_ticks(run["ticks"], run["t_open"]):
        say("slow_ticks", offset_s=tick[0], seconds=tick[1], tokens_out=tick[2], prompt_tokens=tick[3])
    table = programs(rows)
    if tracer is not None and tracer.trace is not None:
        spans = segments(tracer.trace["program"])
        w0, w1 = tracer.window
        traced = [r for r in rows if w0 <= r["end_ts"] - r["wall_s"] + r["host_gap_s"] and r["end_ts"] <= w1]
        if tracer.trace["modules"]:  # the steps of the traced stretch beside the device's count of them
            table = programs(traced, modules(tracer.trace))
        if not rehearse:
            out["clock"] = clock_error(rows, spans)
            say("clock", **out["clock"])
    for key, p in table.items():
        say("programs", key=key, **{k: (round(v, 6) if isinstance(v, float) else v) for k, v in p.items()})
    out["programs"] = table
    return out


KERNELS = ("ds_paged_attention", "ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv")


def report_trace(tracer) -> dict:
    """The lines that read the kept trace: ``spans:``, ``idle_gaps:``, ``kernels:``, ``modules:``."""
    trace = tracer.trace
    spans = segments(trace["program"])
    seen = {}
    for s in spans:
        seen[s[0]] = seen.get(s[0], 0) + 1
    say("spans", **seen)
    out = {"spans_seen": seen}
    if not trace["devices"]:  # a CPU rehearsal has no device plane
        return out
    gaps = idle_gaps(trace, spans)
    idle = sum(sec for _, sec in gaps)
    out["idle_gaps"] = [list(g) for g in gaps]
    out["idle_owned_by_ds"] = sum(sec for n, sec in gaps if n.startswith("ds.")) / idle if idle else None
    say("idle_gaps", **{n: round(sec, 6) for n, sec in gaps}, owned_by_ds=out["idle_owned_by_ds"])
    # a gap between two programs is milliseconds long, one inside a running program microseconds
    sizes = {}
    for owner, a, b in owned_gaps(trace, spans):
        n, over, long_s = sizes.get(owner, (0, 0, 0.0))
        sizes[owner] = (n + 1, over + (b - a > 1e-4), long_s + (b - a if b - a > 1e-4 else 0.0))
    out["idle_gap_sizes"] = {k: {"gaps": n, "over_0.1ms": over, "seconds_in_those": sec} for k, (n, over, sec) in sizes.items()}
    for owner, v in out["idle_gap_sizes"].items():
        say("idle_gap_sizes", owner=owner, **{k: (round(x, 6) if isinstance(x, float) else x) for k, x in v.items()})
    ops = trace["devices"][min(trace["devices"])]
    # by the operation's own name (its HLO text also names its operands)
    out["kernels"] = {n: sum(e[2] - e[1] for e in ops if trace_reduce.parse(e)[0].startswith(n)) for n in KERNELS}
    out["kernels"]["tpu_custom_call"] = sum(e[2] - e[1] for e in ops if trace_reduce.PALLAS_CALL in e[0])
    say("kernels", **out["kernels"])
    named = next((e[0] for e in ops if trace_reduce.parse(e)[0].startswith(KERNELS)), None)
    say("kernel_event", name=repr(named[:160]) if named else None)
    out["modules"] = modules(trace)
    for key, m in out["modules"].items():
        say("modules", key=key, runs=m["runs"], device_s=round(m["device_s"], 6))
    reduced = tracer.reduced
    out.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"],
               device_ops=[list(kv) for kv in reduced["device_ops"][:10]],
               idle_gaps_outer=[list(kv) for kv in reduced["idle_gaps"]])
    return out


def main():
    import argparse
    import importlib
    import json
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the program under test
    import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: the recorder alone, nothing written into a profile")
    ap.add_argument("--rehearse", action="store_true", help="on the CPU at the files' rehearsal sizes: counts only")
    ap.add_argument("--describe", action="store_true", help="print planes, lines and the longest operations of the trace")
    args = ap.parse_args()
    KeptTrace.describe = args.describe
    bench_run.T_START = T_START
    _, ctx, device = bench_run.open_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                         rehearse=args.rehearse)
    kind = ctx["traffic"]["kind"]
    if kind == "serve_open_loop":
        run = run_recorded(ctx)
    else:  # a cell whose program attaches no recorder: its own run, with the trace kept
        harness.TraceWindow = KeptTrace
        run = importlib.import_module("kinds." + kind).run(ctx)
    tracer = KeptTrace.last
    result = {"correct": run["correct"], "attempted": run["attempted"], "failed": run["failed"], "device": device}
    if kind == "serve_open_loop":
        result.update(report_steps(run, tracer, args.rehearse, bench_run.reader))
    if tracer is not None and tracer.trace is not None:
        result.update(report_trace(tracer))
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
