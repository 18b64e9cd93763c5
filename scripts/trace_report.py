#!/usr/bin/env python
"""Fold a telemetry Chrome trace into a critical-path breakdown.

Input: a trace exported by ``deepspeed_tpu.telemetry.write_chrome_trace``.
For every request trace (root span named
``request``) the phase child spans — ``pending`` (router queue /
failover re-dispatch wait), ``queued`` (replica admission queue, incl.
preemption requeue and submit backoff), ``prefill``, ``decode``,
``migrating`` (paused for chunked KV export — the per-request
cost of a disaggregated prefill→decode handoff), ``evicted``,
``fenced`` (the open tail of an attempt the router displaced without
observing its end: a lease expiry, or an in-lease restart detected by
the heartbeat's generation bump — either way the fencing discipline
discarded that work rather than crediting it to a served phase) — are
summed into a per-request breakdown, then aggregated
into the fleet-level critical path: where does a request's latency
actually go — queueing, prompt processing, token generation, or
retry/backoff after preemption and failover?

Cross-check (the acceptance receipt): phase spans are derived from the
request's state history and must TILE [arrival, terminal] exactly, so
for every completed request

    sum(phase spans)  ==  ttft + tpot * (n_tokens - 1)  ==  e2e

within ``--tol`` (default 1e-6; the trace stores µs with 1e-3 µs
resolution, so the reconstruction error floor is ~1e-9 s).  A mismatch
means an instrumentation gap (a phase nobody attributed) and the report
exits non-zero — traces that lie are worse than no traces.

Output: one JSON document on stdout (and ``--out`` if given):
``critical_path`` totals/fractions per phase, per-phase p50/p95 across
requests, failover/preemption counts, and the verification record.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from deepspeed_tpu.serving.metrics import percentile_summary  # noqa: E402

#: ``host_gap`` / ``compile_wait`` are the step-anatomy phases
#: (telemetry/step_anatomy.py): per-step host-side loop tax and JIT
#: compile pauses — named here so a trace that carries them as
#: ``phase/<name>`` spans folds instead of breaking the tiling
#: ``parked``/``promote`` are the kv-tier phases (serving/kvtier):
#: host-demoted idle windows and the unhidden slice of the h2d promote
#: transfer a resume pays (telemetry/spans.py carves them out of
#: parked/queued so the tiling still holds exactly)
#: ``tool_stall`` is a PARKED interval relabeled by its session park
#: phase (serving/sessions): a mid-generation wait for an agentic tool
#: result; ``think_time`` is the session-level between-turn gap (only in
#: session-root traces, which fold() skips — named for completeness);
#: ``vision_encode`` is the part of a request's prefill before its images
#: were through the vision tower (telemetry/spans.py carves it out of prefill);
#: ``prefill`` is the part of PREFILL in which a step that carried the request
#: ran, ``prefill_bypassed`` the part in which a step ran and carried none of
#: it, ``prefill_wait`` the part in which no step ran (carved by the windows
#: the serving frontend notes a step)
PHASES = ("pending", "queued", "prefill", "decode", "migrating", "evicted",
          "fenced", "host_gap", "compile_wait", "parked", "tool_stall",
          "think_time", "promote", "vision_encode", "prefill_bypassed",
          "prefill_wait")
#: the phases a request is in while its state is PREFILL
_PREFILLING = ("prefill", "prefill_bypassed", "prefill_wait", "vision_encode")
_US = 1e6


def fold(doc: dict, tol: float = 1e-6) -> dict:
    """Pure-function core (unit-tested; main() is the CLI shell)."""
    spans = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    by_trace = {}
    for e in spans:
        by_trace.setdefault(e["args"].get("trace_id"), []).append(e)

    requests = []
    mismatches = []
    for trace_id, evs in sorted(by_trace.items(), key=lambda kv: str(kv[0])):
        roots = [e for e in evs if e["name"] == "request"]
        if not roots:
            continue  # engine-step traces etc. — not a request trace
        root = roots[0]
        phases = {p: 0.0 for p in PHASES}
        by_parent = {}
        for e in evs:
            if e["name"].startswith("phase/"):
                p = e["name"][len("phase/"):]
                phases[p] = phases.get(p, 0.0) + e["dur"] / _US
                by_parent.setdefault(e["args"].get("parent_id"), []).append((e["ts"], p))
        # preemption/requeue is visible in the phase STRUCTURE: within one
        # attempt, a queued (or re-prefill) segment following an earlier
        # decode/prefill segment means the request was evicted and requeued
        # (the eviction instant itself is zero-length, so no evicted span)
        preemptions = 0
        for segs in by_parent.values():
            segs.sort()
            for prev, cur in zip(segs, segs[1:]):
                if cur[1] == "queued" and prev[1] in _PREFILLING + ("decode", ):
                    preemptions += 1
        attempts = [e for e in evs if e["name"] == "attempt"]
        span_sum = sum(phases.values())
        rec = {
            "trace_id": trace_id,
            "state": root["args"].get("state"),
            "n_tokens": root["args"].get("n_tokens"),
            "failovers": root["args"].get("failovers", 0),
            "preemptions": preemptions,
            "attempts": len(attempts),
            "e2e": root["dur"] / _US,
            "ttft": root["args"].get("ttft"),
            "tpot": root["args"].get("tpot"),
            "span_sum": round(span_sum, 9),
            "phases": {p: round(v, 9) for p, v in phases.items()},
        }
        # the receipt: spans must account for every second the latency
        # accounting recorded.  DONE requests with >= 2 tokens have the
        # full ttft/tpot decomposition; otherwise fall back to e2e.
        if rec["state"] == "done" and rec["ttft"] is not None \
                and rec["tpot"] is not None and (rec["n_tokens"] or 0) >= 2:
            accounted = rec["ttft"] + rec["tpot"] * (rec["n_tokens"] - 1)
        else:
            accounted = rec["e2e"]
        rec["accounted"] = round(accounted, 9)
        rec["residual"] = round(span_sum - accounted, 9)
        if abs(rec["residual"]) > tol:
            mismatches.append(rec)
        requests.append(rec)

    total = sum(r["span_sum"] for r in requests)
    breakdown = {}
    for p in PHASES:
        tp = sum(r["phases"].get(p, 0.0) for r in requests)
        breakdown[p] = {
            "total_s": round(tp, 9),
            "fraction": round(tp / total, 6) if total else None,
            # same method as serving/metrics.py's percentile fields —
            # the two must agree
            "per_request": percentile_summary(
                [r["phases"].get(p, 0.0) for r in requests]),
        }
    # retry/backoff time: what failover + preemption recovery actually
    # cost — queue-class phases on requests that were displaced/preempted
    retry_s = sum(r["phases"].get("pending", 0.0) + r["phases"].get("queued", 0.0)
                  for r in requests if r["failovers"] or r["preemptions"])
    return {
        "n_traces": len(by_trace),
        "n_requests": len(requests),
        "states": {s: sum(1 for r in requests if r["state"] == s)
                   for s in sorted({r["state"] for r in requests})},
        "failovers": sum(r["failovers"] or 0 for r in requests),
        "preemptions": sum(r["preemptions"] for r in requests),
        "critical_path": breakdown,
        "retry_queue_s": round(retry_s, 9),
        "total_span_s": round(total, 9),
        "verification": {
            "tol": tol,
            "checked": len(requests),
            "mismatches": len(mismatches),
            "worst_residual": max((abs(r["residual"]) for r in requests),
                                  default=0.0),
            "failing_traces": [r["trace_id"] for r in mismatches][:10],
        },
        "requests": requests,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome-trace JSON (write_chrome_trace output)")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="max |span_sum - (ttft + tpot*(n-1))| per request")
    ap.add_argument("--out", default=None, help="also write the report here")
    ap.add_argument("--full", action="store_true",
                    help="include the per-request table in stdout output")
    args = ap.parse_args()

    with open(args.trace) as f:
        doc = json.load(f)
    report = fold(doc, tol=args.tol)
    printable = report if args.full else {k: v for k, v in report.items()
                                          if k != "requests"}
    print(json.dumps(printable, indent=1, sort_keys=True))
    if args.out:
        from deepspeed_tpu.resilience.atomic_io import atomic_write_json
        atomic_write_json(args.out, report, indent=1)
    if report["verification"]["mismatches"]:
        print(f"TRACE MISMATCH: {report['verification']['mismatches']} request(s) "
              f"whose spans do not account for their recorded latency "
              f"(worst residual {report['verification']['worst_residual']:g}s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
