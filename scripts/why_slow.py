#!/usr/bin/env python
"""why_slow — per-request slowdown attribution over a telemetry trace.

``trace_report.py`` answers "where does latency go on average";
``why_slow`` answers the question an operator actually asks: **"why was
THIS request slow — and what explains the p99?"**  It folds every request
trace in a Chrome trace (``deepspeed_tpu.telemetry.write_chrome_trace``
output — a tracer's export or a flight-recorder dump) into a
named-cause breakdown of its end-to-end latency:

    queue_wait       router-queue (``phase/pending``) + replica admission
                     queue (``phase/queued``) time with no degradation
                     active
    partition_delay  pending/queued time overlapping a declared
                     degradation window (a control-plane partition, a
                     flash crowd) — the trace's ``otherData`` carries
                     ``degradation_t0``/``degradation_t1`` (the exporter's
                     ``meta``; ``--window t0:t1`` overrides)
    prefill          prompt processing (incl. recompute-on-resume): a step
                     that carried a chunk of the request was running
    prefill_bypassed a step ran and carried none of it: the token budget
                     went to another prompt, a dispatch was in flight when
                     it was admitted (``phase/prefill_bypassed``)
    prefill_wait     admitted, and no step of the engine running: planning
                     before a dispatch, delivery after it, the caller's loop
                     between ticks (``phase/prefill_wait``)
    decode           token generation
    migration_pause  paused for chunked KV export (``phase/migrating``)
    lease_expiry     re-home wait after a lease-expiry/fencing
                     displacement (the ``phase/pending`` stretch that
                     follows a fenced attempt)
    fenced           work served outside the replica's lease and
                     discarded by the fence (``phase/fenced``)
    eviction         KV-pressure eviction windows (``phase/evicted``)

Every second of every phase span lands in EXACTLY one cause, so per
request ``sum(causes) == e2e`` within ``--tol`` (default 1e-6) — the same
tiling discipline ``trace_report.py`` enforces; a mismatch means an
attribution gap and the report **exits 1** (sabotage-tested).  One
exception: a trace that DECLARES dropped spans (``otherData.
dropped_spans > 0`` — a flight-recorder dump whose bounded ring evicted
old phase spans, or a tracer past its retention cap) cannot distinguish
an attribution gap from eviction, so its mismatches are reported as
``possibly_truncated`` with a stderr warning and exit 0 — the black box
stays analyzable after a long incident.  Requests
additionally carry their ``tenant`` and ``brownout_capped`` flags from
the root span, so a brownout-truncated request is identifiable even
though the cap costs tokens, not seconds.

The tail receipt: ``ttft_gap`` compares the p99 TTFT request against the
p50 one (nearest-rank over DONE requests, TTFT-clipped causes) and
reports what fraction of the p99−p50 gap the SLOWDOWN causes (everything
except baseline prefill/decode compute) explain.

Output is one deterministic JSON document (sorted keys, no timestamps):
``--json`` prints compact bytes that are identical across repeat runs on
the same trace.

Deliberately stdlib-only (no package import): the CLI starts in
milliseconds and runs anywhere the trace file does.
"""

import argparse
import json
import math
import sys

_US = 1e6

#: the attribution taxonomy; every phase second maps to exactly one cause.
#: ``host_gap`` / ``compile_wait`` are the step-anatomy phases
#: (telemetry/step_anatomy.py): per-step host loop tax and JIT compile
#: pauses — named slowdowns, not baseline compute
CAUSES = ("queue_wait", "partition_delay", "prefill", "decode",
          "migration_pause", "lease_expiry", "fenced", "eviction",
          "host_gap", "compile_wait", "parked", "tool_stall", "promote",
          "prefill_bypassed", "prefill_wait")

#: causes that are NOT baseline compute — the named slowdowns the tail
#: receipt attributes the p99-p50 gap to.  ``parked`` is deliberate idle
#: (the session slept between turns with its KV host-side),
#: ``tool_stall`` is the mid-generation wait for an agentic session's
#: tool result (serving/sessions — the agent's latency, parked through
#: the same host tier), and ``promote`` is the h2d transfer a resume
#: could not hide — the receipt separates resume-TTFT paid to the tier
#: from recompute it avoided.  ``prefill_bypassed`` and ``prefill_wait``
#: are the parts of a prompt's processing in which no step worked on it: a
#: prompt that waited behind another is not baseline compute
SLOWDOWN_CAUSES = ("queue_wait", "partition_delay", "migration_pause",
                   "lease_expiry", "fenced", "eviction", "host_gap",
                   "compile_wait", "parked", "tool_stall", "promote",
                   "prefill_bypassed", "prefill_wait")

#: phase -> cause for the phases that map 1:1
_DIRECT = {"prefill": "prefill", "decode": "decode",
           "migrating": "migration_pause", "fenced": "fenced",
           "evicted": "eviction", "host_gap": "host_gap",
           "compile_wait": "compile_wait", "parked": "parked",
           "tool_stall": "tool_stall", "promote": "promote",
           "prefill_bypassed": "prefill_bypassed",
           "prefill_wait": "prefill_wait",
           # the wait for the vision tower is the prompt's processing: prefill
           "vision_encode": "prefill"}


def _overlap(t0, t1, w0, w1):
    lo, hi = max(t0, w0), min(t1, w1)
    return max(0.0, hi - lo)


def _split_wait(t0, t1, windows, base_cause, causes):
    """Split one wait-class interval between ``base_cause`` and
    partition_delay by overlap with the degradation windows."""
    total = t1 - t0
    delayed = sum(_overlap(t0, t1, w0, w1) for w0, w1 in windows)
    delayed = min(total, delayed)
    causes["partition_delay"] += delayed
    causes[base_cause] += total - delayed


def _percentile_request(recs, q):
    """Nearest-rank pick (ceil(q*n)th order statistic): the CONCRETE
    request at quantile ``q`` of the TTFT order — so the p99 of a 90-
    request run IS the slowest request, not the second-slowest
    (deterministic; ties broken by trace id)."""
    if not recs:
        return None
    ordered = sorted(recs, key=lambda r: (r["ttft"], str(r["trace_id"])))
    idx = max(0, math.ceil(q * len(ordered)) - 1)
    return ordered[idx]


def fold(doc, tol=1e-6, windows=None):
    """Pure-function core (unit-tested; main() is the CLI shell).

    ``windows``: list of (t0, t1) degradation windows in trace-clock
    seconds; defaults to the single window the trace's ``otherData``
    declares via ``degradation_t0``/``degradation_t1`` (none = no
    partition_delay attribution)."""
    other = doc.get("otherData", {}) if isinstance(doc, dict) else {}
    if windows is None:
        t0, t1 = other.get("degradation_t0"), other.get("degradation_t1")
        windows = [(float(t0), float(t1))] \
            if isinstance(t0, (int, float)) and isinstance(t1, (int, float)) \
            else []
    windows = [(float(a), float(b)) for a, b in windows]

    spans = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    by_trace = {}
    for e in spans:
        by_trace.setdefault(e["args"].get("trace_id"), []).append(e)

    requests, mismatches = [], []
    for trace_id, evs in sorted(by_trace.items(), key=lambda kv: str(kv[0])):
        roots = [e for e in evs if e["name"] == "request"]
        if not roots:
            continue  # engine-step / control-plane traces: not a request
        root = roots[0]
        rargs = root["args"]
        causes = {c: 0.0 for c in CAUSES}
        # attempts that ended in a fencing displacement: pending time from
        # the first such displacement onward is lease-expiry re-home wait
        fenced_children = [e for e in evs if e["name"] == "phase/fenced"]
        first_fence = min((e["ts"] / _US for e in fenced_children),
                          default=None)
        phase_list = []
        for e in evs:
            if not e["name"].startswith("phase/"):
                continue
            p = e["name"][len("phase/"):]
            t0 = e["ts"] / _US
            t1 = t0 + e["dur"] / _US
            phase_list.append((p, t0, t1))
            if p in _DIRECT:
                causes[_DIRECT[p]] += t1 - t0
            elif p == "pending":
                if first_fence is not None and t0 >= first_fence:
                    # the router queue wait AFTER a fencing displacement is
                    # the cost of the lease expiry itself, not of load
                    causes["lease_expiry"] += t1 - t0
                else:
                    _split_wait(t0, t1, windows, "queue_wait", causes)
            elif p == "queued":
                _split_wait(t0, t1, windows, "queue_wait", causes)
            else:
                # an unknown phase would silently break the tiling receipt
                # below — name it in the report instead of absorbing it
                causes.setdefault(f"unknown:{p}", 0.0)
                causes[f"unknown:{p}"] += t1 - t0
        cause_sum = sum(causes.values())
        e2e = root["dur"] / _US
        rec = {
            "trace_id": trace_id,
            "state": rargs.get("state"),
            "tenant": rargs.get("tenant"),
            "brownout_capped": bool(rargs.get("brownout_capped")),
            "failovers": rargs.get("failovers", 0),
            "n_tokens": rargs.get("n_tokens"),
            "ttft": rargs.get("ttft"),
            "e2e": round(e2e, 9),
            "causes": {c: round(v, 9) for c, v in sorted(causes.items())},
            "residual": round(cause_sum - e2e, 9),
        }
        # TTFT-clipped causes: the share of each cause BEFORE the first
        # token — what the tail receipt decomposes the TTFT gap with
        if rec["state"] == "done" and rec["ttft"] is not None:
            arrival = root["ts"] / _US
            ft = arrival + rec["ttft"]
            tc = {c: 0.0 for c in causes}
            for p, t0, t1 in phase_list:
                seg = _overlap(t0, t1, arrival, ft)
                if seg <= 0:
                    continue
                if p in _DIRECT:
                    tc[_DIRECT[p]] += seg
                elif p == "pending" and first_fence is not None \
                        and t0 >= first_fence:
                    tc["lease_expiry"] += seg
                elif p in ("pending", "queued"):
                    _split_wait(t0, min(t1, ft), windows, "queue_wait", tc)
                else:
                    tc[f"unknown:{p}"] += seg
            rec["ttft_causes"] = {c: round(v, 9) for c, v in sorted(tc.items())}
        if abs(rec["residual"]) > tol:
            mismatches.append(rec)
        requests.append(rec)

    total = sum(r["e2e"] for r in requests)
    agg = {}
    for c in sorted({c for r in requests for c in r["causes"]}):
        tc = sum(r["causes"].get(c, 0.0) for r in requests)
        agg[c] = {"total_s": round(tc, 9),
                  "fraction": round(tc / total, 6) if total else None}

    # the tail receipt: p99 vs p50 TTFT, gap decomposed by slowdown causes
    done = [r for r in requests if r["state"] == "done"
            and r["ttft"] is not None and "ttft_causes" in r]
    gap_rec = None
    if len(done) >= 2:
        p50 = _percentile_request(done, 0.50)
        p99 = _percentile_request(done, 0.99)
        gap = p99["ttft"] - p50["ttft"]
        per_cause = {
            c: round(p99["ttft_causes"].get(c, 0.0)
                     - p50["ttft_causes"].get(c, 0.0), 9)
            for c in SLOWDOWN_CAUSES}
        attributed = sum(per_cause.values())
        gap_rec = {
            "ttft_p50": round(p50["ttft"], 9),
            "ttft_p99": round(p99["ttft"], 9),
            "gap": round(gap, 9),
            "p50_trace_id": p50["trace_id"],
            "p99_trace_id": p99["trace_id"],
            "attributed_s": round(attributed, 9),
            "attributed_fraction": round(attributed / gap, 6) if gap > 0 else None,
            "by_cause": per_cause,
        }

    return {
        "n_requests": len(requests),
        "states": {s: sum(1 for r in requests if r["state"] == s)
                   for s in sorted({r["state"] for r in requests})},
        "tenants": {t: sum(1 for r in requests if r["tenant"] == t)
                    for t in sorted({str(r["tenant"]) for r in requests})},
        "brownout_capped": sum(1 for r in requests if r["brownout_capped"]),
        "degradation_windows": [[round(a, 9), round(b, 9)]
                                for a, b in windows],
        "causes": agg,
        "ttft_gap": gap_rec,
        "verification": {
            "tol": tol,
            "checked": len(requests),
            # a trace that DECLARES span eviction cannot tell attribution
            # gaps from truncation: its residuals are downgraded from
            # mismatch (exit 1) to possibly_truncated (warn, exit 0)
            "partial_trace": bool(other.get("dropped_spans")),
            "mismatches": 0 if other.get("dropped_spans") else len(mismatches),
            "possibly_truncated": len(mismatches)
            if other.get("dropped_spans") else 0,
            "worst_residual": max((abs(r["residual"]) for r in requests),
                                  default=0.0),
            "failing_traces": [r["trace_id"] for r in mismatches][:10],
        },
        "requests": requests,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="Chrome-trace JSON (write_chrome_trace "
                                  "output or a flight-recorder dump)")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="max |sum(causes) - e2e| per request")
    ap.add_argument("--window", action="append", default=None,
                    metavar="T0:T1",
                    help="degradation window (repeatable); overrides the "
                         "trace's otherData declaration")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="compact deterministic JSON on stdout (byte-"
                         "identical across repeat runs on the same trace)")
    ap.add_argument("--out", default=None, help="also write the report here")
    ap.add_argument("--full", action="store_true",
                    help="include the per-request table in stdout output")
    args = ap.parse_args()

    with open(args.trace) as f:
        doc = json.load(f)
    windows = None
    if args.window:
        windows = []
        for w in args.window:
            a, b = w.split(":")
            windows.append((float(a), float(b)))
    report = fold(doc, tol=args.tol, windows=windows)
    printable = report if (args.full or args.as_json) \
        else {k: v for k, v in report.items() if k != "requests"}
    if args.as_json:
        sys.stdout.write(json.dumps(printable, sort_keys=True,
                                    separators=(",", ":")) + "\n")
    else:
        print(json.dumps(printable, indent=1, sort_keys=True))
    if args.out:
        # stdlib-only CLI: write via temp+rename so a partial report can
        # never be observed (the atomic_io stance without the import)
        import os
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:  # atomic-ok: temp file, renamed below
            json.dump(report, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, args.out)
    ver = report["verification"]
    if ver["mismatches"]:
        print(f"ATTRIBUTION MISMATCH: {ver['mismatches']} "
              f"request(s) whose causes do not tile their e2e (worst "
              f"residual {ver['worst_residual']:g}s)",
              file=sys.stderr)
        return 1
    if ver["possibly_truncated"]:
        print(f"WARNING: {ver['possibly_truncated']} request(s) do not tile "
              f"but the trace declares dropped spans — residuals may be "
              f"ring eviction, not attribution gaps", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
