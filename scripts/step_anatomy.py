#!/usr/bin/env python
"""step_anatomy — verify and fold a per-step engine anatomy table.

Input: a step-anatomy document — the ``StepAnatomy.to_doc()`` export
(``{"schema": 3, "steps": [...], "compiles": [...]}``).

The report does two things, in this order:

1. **Verify the tiling.**  For every step,

       wall_s == host_gap_s + sum(segments) + device_s

   must hold within ``--tol`` (default 1e-6, padded by the 9-decimal
   rounding bound of the committed values).  The recorder produces this
   by construction, so a mismatch means the artifact was edited, a
   different producer drifted, or the recorder broke — **exit 1**, the
   same traces-that-lie-are-worse-than-no-traces stance as
   ``trace_report.py``.  The compile log is cross-checked too: the
   declared ``steady_state_recompiles`` must equal the number of
   ``steady`` entries in the committed compile list.

2. **Fold the anatomy.**  Per program key (``step:b16:c128``,
   ``multi:b16:k8``): step count, wall/host/device/host-gap seconds, the
   host-gap fraction (what the caller did between two ticks), what the
   steps carried (rows, real tokens, slots, tokens out and discarded),
   and per-segment totals; plus the overall fractions (``overlap_fraction``:
   the share of wall time the pipelined tick hid under a dispatch in
   flight, 0 in the serial loop) and the compile summary (warm-up vs
   steady-state).

Output: one deterministic JSON document (sorted keys, no timestamps);
``--json`` prints compact bytes byte-identical across repeat runs on the
same input.  Deliberately stdlib-only: no package import, starts in
milliseconds, runs anywhere the artifact does.
"""

import argparse
import json
import sys

#: must mirror telemetry/step_anatomy.py HOST_SEGMENTS — the fixed
#: per-step segment vocabulary (a committed row missing one is drift)
HOST_SEGMENTS = ("admit", "schedule", "draft_plan", "verify_plan",
                 "aot_compile", "compile_wait", "dispatch", "sample_accept",
                 "deliver", "overlap", "bookkeeping", "promote_wait")

#: must mirror telemetry/step_anatomy.py COUNTS — what a step carried
COUNTS = ("rows_decode", "rows_prefill", "tokens_real", "slots", "tokens_out",
          "tokens_discarded", "expert_rows", "expert_rows_kernel", "attn_rows_visible", "attn_rows_walked",
          "ssm_rows", "window_rows_visible", "ssd_state_bytes", "mla_rows_read")


def fold(anatomy, tol=1e-6):
    """Pure-function core (unit-tested; main() is the CLI shell)."""
    steps = anatomy.get("steps")
    if not isinstance(steps, list):
        raise ValueError("not a step-anatomy document: no 'steps' table")
    compiles = anatomy.get("compiles") or []

    mismatches = []
    by_shape = {}
    tot = {"wall_s": 0.0, "host_s": 0.0, "device_s": 0.0, "host_gap_s": 0.0,
           **{c: 0 for c in COUNTS}}
    seg_tot = {s: 0.0 for s in HOST_SEGMENTS}
    for i, row in enumerate(steps):
        segs = row.get("segments") or {}
        missing = [s for s in HOST_SEGMENTS if s not in segs]
        if missing:
            mismatches.append({"index": row.get("index", i),
                               "error": f"missing segments {missing}"})
            continue
        host = sum(segs[s] for s in HOST_SEGMENTS)
        wall = row.get("wall_s", 0.0)
        gap = row.get("host_gap_s", 0.0)
        dev = row.get("device_s", 0.0)
        residual = wall - (gap + host + dev)
        # the committed values are independently rounded to 9 decimals:
        # each component contributes up to 0.5e-9 of rounding noise —
        # a legitimately-tiled artifact must not fail on rounding alone
        pad = 0.5e-9 * (len(HOST_SEGMENTS) + 3)
        if abs(residual) > tol + pad:
            mismatches.append({"index": row.get("index", i),
                               "key": row.get("key"),
                               "residual": round(residual, 12)})
            continue
        agg = by_shape.setdefault(row.get("key"), {
            "steps": 0, "wall_s": 0.0, "host_s": 0.0, "device_s": 0.0,
            "host_gap_s": 0.0, "compiles": 0, **{c: 0 for c in COUNTS},
            "segments": {s: 0.0 for s in HOST_SEGMENTS}})
        agg["steps"] += 1
        agg["wall_s"] += wall
        agg["host_s"] += host
        agg["device_s"] += dev
        agg["host_gap_s"] += gap
        agg["compiles"] += row.get("compiles", 0)
        for c in COUNTS:
            agg[c] += row.get(c, 0)
            tot[c] += row.get(c, 0)
        for s in HOST_SEGMENTS:
            agg["segments"][s] += segs[s]
        tot["wall_s"] += wall
        tot["host_s"] += host
        tot["device_s"] += dev
        tot["host_gap_s"] += gap
        for s in HOST_SEGMENTS:
            seg_tot[s] += segs[s]

    shapes = {}
    for key in sorted(by_shape):
        agg = by_shape[key]
        wall = agg["wall_s"]
        shapes[key] = {
            "steps": agg["steps"],
            "wall_s": round(wall, 9),
            "host_s": round(agg["host_s"], 9),
            "device_s": round(agg["device_s"], 9),
            "host_gap_s": round(agg["host_gap_s"], 9),
            "host_gap_fraction": round(agg["host_gap_s"] / wall, 6)
            if wall > 0 else None,
            "compiles": agg["compiles"],
            **{c: agg[c] for c in COUNTS},
            "segments": {s: round(agg["segments"][s], 9)
                         for s in HOST_SEGMENTS},
        }

    steady = [c for c in compiles if c.get("steady")]
    declared = (anatomy.get("summary") or {}).get("steady_state_recompiles")
    compile_drift = (declared is not None and declared != len(steady))
    if compile_drift:
        mismatches.append({
            "error": f"summary declares {declared} steady-state "
                     f"recompile(s) but the compile log records "
                     f"{len(steady)} — the document disagrees with itself"})

    wall = tot["wall_s"]
    return {
        "n_steps": len(steps),
        "n_shapes": len(shapes),
        "totals": {
            "wall_s": round(wall, 9),
            "host_s": round(tot["host_s"], 9),
            "device_s": round(tot["device_s"], 9),
            "host_gap_s": round(tot["host_gap_s"], 9),
            "host_gap_fraction": round(tot["host_gap_s"] / wall, 6)
            if wall > 0 else None,
            "device_fraction": round(tot["device_s"] / wall, 6)
            if wall > 0 else None,
            "overlap_fraction": round(seg_tot["overlap"] / wall, 6)
            if wall > 0 else None,
            **{c: tot[c] for c in COUNTS},
            "segments": {s: round(seg_tot[s], 9) for s in HOST_SEGMENTS},
        },
        "by_shape": shapes,
        "compiles": {
            "total": len(compiles),
            "warmup": len(compiles) - len(steady),
            "steady_state": len(steady),
            "steady_keys": sorted({c.get("key") for c in steady}),
        },
        "after_idle_steps": sum(1 for r in steps if r.get("after_idle")),
        "dropped_steps": (anatomy.get("summary") or {}).get(
            "dropped_steps", 0),
        "verification": {
            "tol": tol,
            "checked": len(steps),
            "mismatches": len(mismatches),
            "failing": mismatches[:10],
        },
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("doc", help="StepAnatomy.to_doc() export")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="max |wall - (gap + segments + device)| per step")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="compact deterministic JSON on stdout (byte-"
                         "identical across repeat runs on the same input)")
    ap.add_argument("--out", default=None, help="also write the report here")
    args = ap.parse_args()

    with open(args.doc) as f:
        doc = json.load(f)
    report = fold(doc, tol=args.tol)
    if args.as_json:
        sys.stdout.write(json.dumps(report, sort_keys=True,
                                    separators=(",", ":")) + "\n")
    else:
        print(json.dumps(report, indent=1, sort_keys=True))
    if args.out:
        # stdlib-only CLI: temp+fsync+rename so a partial report can
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
        print(f"ANATOMY MISMATCH: {ver['mismatches']} step(s)/record(s) "
              f"whose components do not tile their wall time (first: "
              f"{ver['failing'][0]})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
