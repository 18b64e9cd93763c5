"""Trace exporters: Chrome-trace/Perfetto JSON and JSONL, atomically written.

``to_chrome_trace`` renders finished spans as the Trace Event Format
(``ph:"X"`` complete events, µs timestamps) that chrome://tracing and
Perfetto load directly; span events become ``ph:"i"`` instants and each
track gets a ``thread_name`` metadata record.  Everything about the
output is deterministic: tracks are numbered in sorted-name order,
events are sorted by (track, ts, span_id), keys are sorted, and
timestamps are exact float µs of the clock readings — so a VirtualClock
trace serializes byte-identically across runs (the property the fleet
determinism test pins).  ``validate_chrome_trace`` holds a document to the
structure the renderer promises.

Writers go through ``resilience.atomic_io`` — a trace must never be
observable half-written.
"""

import json
from typing import Dict, Iterable, List, Optional

from ..resilience.atomic_io import atomic_write_bytes
from .trace import Span

__all__ = ["to_chrome_trace", "write_chrome_trace", "spans_to_jsonl",
           "write_jsonl", "load_chrome_trace", "validate_chrome_trace"]

_US = 1e6  # clock seconds (or virtual steps) -> Chrome µs


def _clean(attrs: Optional[dict]) -> dict:
    """JSON-safe attribute dict (deterministic: sorted at dump time)."""
    if not attrs:
        return {}
    out = {}
    for k, v in attrs.items():
        if v is None or isinstance(v, (bool, int, float, str)):
            out[str(k)] = v
        elif isinstance(v, (list, tuple)):
            out[str(k)] = [x if isinstance(x, (bool, int, float, str)) else str(x)
                           for x in v]
        else:
            out[str(k)] = str(v)
    return out


def to_chrome_trace(spans: Iterable[Span], dropped_spans: int = 0,
                    meta: Optional[dict] = None) -> dict:
    """Render finished spans as a Chrome-trace document (dict)."""
    spans = [s for s in spans if s.end_ts is not None]
    tracks = sorted({s.track for s in spans})
    tids = {t: i for i, t in enumerate(tracks)}
    events: List[dict] = []
    for t in tracks:
        events.append({"ph": "M", "pid": 0, "tid": tids[t], "ts": 0,
                       "name": "thread_name", "args": {"name": t}})
    # deterministic render order; within a track, X events sorted by start
    # ts (then id) — validate_chrome_trace's per-track monotonicity invariant
    for s in sorted(spans, key=lambda s: (tids[s.track], s.start_ts, s.span_id)):
        args = _clean(s.attrs)
        args["trace_id"] = s.trace_id
        args["span_id"] = s.span_id
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        events.append({"ph": "X", "pid": 0, "tid": tids[s.track],
                       "ts": round(s.start_ts * _US, 3),
                       "dur": round((s.end_ts - s.start_ts) * _US, 3),
                       "name": s.name, "args": args})
        for ename, ets, eattrs in s.events:
            ea = _clean(eattrs)
            ea["trace_id"] = s.trace_id
            ea["span_id"] = s.span_id
            events.append({"ph": "i", "pid": 0, "tid": tids[s.track],
                           "ts": round(ets * _US, 3), "s": "t",
                           "name": ename, "args": ea})
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "format": "deepspeed_tpu.telemetry", "version": 1,
            "clock_unit_us": _US, "n_spans": len(spans),
            "dropped_spans": int(dropped_spans),
            "tracks": tracks,
        },
    }
    if meta:
        doc["otherData"].update(_clean(meta))
    return doc


def _dump(doc) -> bytes:
    return json.dumps(doc, sort_keys=True, separators=(",", ":")).encode("utf-8")


def write_chrome_trace(path: str, spans: Iterable[Span], dropped_spans: int = 0,
                       meta: Optional[dict] = None, site: Optional[str] = None) -> str:
    """Atomically write the Chrome-trace JSON; byte-identical for
    identical span streams."""
    return atomic_write_bytes(path, _dump(to_chrome_trace(
        spans, dropped_spans=dropped_spans, meta=meta)), site=site)


def span_to_record(s: Span) -> dict:
    return {
        "name": s.name, "trace_id": s.trace_id, "span_id": s.span_id,
        "parent_id": s.parent_id, "track": s.track,
        "start_ts": s.start_ts, "end_ts": s.end_ts,
        "attrs": _clean(s.attrs),
        "events": [{"name": n, "ts": t, "attrs": _clean(a)} for n, t, a in s.events],
    }


def spans_to_jsonl(spans: Iterable[Span]) -> str:
    """One JSON object per finished span, materialization order (the
    stream shape log pipelines ingest)."""
    lines = [json.dumps(span_to_record(s), sort_keys=True, separators=(",", ":"))
             for s in spans if s.end_ts is not None]
    return "\n".join(lines) + ("\n" if lines else "")


def write_jsonl(path: str, spans: Iterable[Span], site: Optional[str] = None) -> str:
    return atomic_write_bytes(path, spans_to_jsonl(spans).encode("utf-8"), site=site)


def load_chrome_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


_TERMINAL_STATES = {"done", "timed_out", "rejected"}


def validate_chrome_trace(doc) -> Optional[str]:
    """Check a document ``to_chrome_trace`` rendered against the invariants
    a trace consumer (Perfetto, scripts/trace_report.py) relies on:
    well-formed events, per-track monotonic timestamps, every span's parent
    existing in the same trace, and serving request spans closing in a
    terminal state.  Returns None, or what is wrong."""
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        return "expected a Chrome-trace object with a traceEvents list"
    errors = []
    last_ts = {}                      # (pid, tid) -> last X-event start ts
    span_ids = {}                     # trace_id -> set of span ids
    parents = []                      # (trace_id, parent_id, name)
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict) or ev.get("ph") not in ("M", "X", "i"):
            errors.append(f"traceEvents[{i}]: unknown/missing ph "
                          f"{ev.get('ph') if isinstance(ev, dict) else ev!r}")
            continue
        if "pid" not in ev or "tid" not in ev or "name" not in ev:
            errors.append(f"traceEvents[{i}]: missing pid/tid/name")
            continue
        if ev["ph"] == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            errors.append(f"traceEvents[{i}]: non-numeric ts {ts!r}")
            continue
        args = ev.get("args") or {}
        if ev["ph"] == "X":
            if not (isinstance(ev.get("dur"), (int, float)) and ev["dur"] >= 0):
                errors.append(f"traceEvents[{i}] ({ev['name']}): bad dur "
                              f"{ev.get('dur')!r}")
            track = (ev["pid"], ev["tid"])
            if ts < last_ts.get(track, float("-inf")):
                errors.append(f"traceEvents[{i}] ({ev['name']}): ts {ts} goes "
                              f"BACKWARDS on track {track} (monotonic per-track "
                              "order violated)")
            last_ts[track] = ts
            if "trace_id" not in args or "span_id" not in args:
                errors.append(f"traceEvents[{i}] ({ev['name']}): span without "
                              "trace_id/span_id args")
                continue
            span_ids.setdefault(args["trace_id"], set()).add(args["span_id"])
            if args.get("parent_id") is not None:
                parents.append((args["trace_id"], args["parent_id"], ev["name"]))
            if ev["name"] == "request" and \
                    args.get("state") not in _TERMINAL_STATES:
                errors.append(f"traceEvents[{i}]: request span closed in "
                              f"non-terminal state {args.get('state')!r}")
    for trace_id, parent_id, name in parents:
        if parent_id not in span_ids.get(trace_id, ()):
            errors.append(f"span {name!r} (trace {trace_id}): parent "
                          f"{parent_id} does not exist in its trace")
    if errors:
        return "; ".join(errors[:8]) + \
            (f"; ... {len(errors) - 8} more" if len(errors) > 8 else "")
    return None
