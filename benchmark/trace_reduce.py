"""From the profiler's trace (``.xplane.pb``, read with
``jax.profiler.ProfileData`` alone) to the numbers the benchmark reports:
device busy and idle time, time by operation, a kernel's summed time, the
time a collective runs alone, and idle gaps by what the host was doing.

A device plane is one named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
one event an executed operation.  The benchmark's own host spans (``tick``,
``submit``, ``train_batch``, ``input``) are ``TraceAnnotation`` events on the
host plane's thread lines and share the device's clock.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
OWN_SPANS = ("tick", "submit", "train_batch", "input")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
                        r"collective-broadcast|ragged-all-to-all)")


def load(path: str) -> dict:
    """{"devices": {n: [event]}, "host": [event]}; an event is
    (name, start_s, end_s, stats) with times in seconds on one clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(_events(line, with_stats=True))
            elif not m and plane.name.startswith("/host:"):
                host.extend(e for e in _events(line, with_stats=False) if e[0] in OWN_SPANS)
    return {"devices": devices, "host": host}


def _events(line, with_stats):
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        stats = {k: v for k, v in ev.stats} if with_stats else {}
        out.append((ev.name, start, start + ev.duration_ns * 1e-9, stats))
    return out


HLO_LINE = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?.*?[}\])] ([a-z\-]+)\(")
CONTAINERS = ("while", "conditional", "call")  # their time is their children's, which the line also holds


def parse(event) -> tuple:
    """(operation name, opcode, dtype[shape] of the result or "") of an event
    whose name is the operation's HLO text, as the TPU profiler writes it;
    any other name is kept whole with no opcode."""
    m = HLO_LINE.match(event[0])
    if not m:
        return event[0].lstrip("%").split(" ")[0], "", ""
    return m.group(1), m.group(3), m.group(2) or ""


def display_name(event) -> str:
    """Operation, opcode, dtype and shape as one word of at most 64 characters."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", "_".join(p for p in parse(event) if p)).strip("_")[:64]


PALLAS_CALL = 'custom_call_target="tpu_custom_call"'  # how a Pallas kernel shows in an event's HLO text


def operand_count(event) -> int:
    """Operands of the operation whose HLO text is the event's name."""
    m = re.search(r" [a-z\-]+\((.*?)\), ", event[0])
    return m.group(1).count("%") if m else 0


def union(intervals) -> list:
    """Disjoint sorted intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Points of the disjoint sorted intervals ``a`` that no interval of ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def reduce(trace: dict) -> dict:
    """The reduced trace: see the keys below."""
    devices, host = trace["devices"], trace["host"]
    if not devices or not any(devices.values()):
        raise RuntimeError("the trace holds no device operation: nothing ran on a TPU in the traced window")
    every = [e for evs in devices.values() for e in evs] + host
    t0, t1 = min(e[1] for e in every), max(e[2] for e in every)
    busy = {n: union((e[1], e[2]) for e in evs) for n, evs in devices.items()}
    first = min(devices)
    by_op, count = {}, {}
    for e in devices[first]:
        if parse(e)[1] in CONTAINERS:
            continue
        key = display_name(e)
        by_op[key] = by_op.get(key, 0.0) + e[2] - e[1]
        count[key] = count.get(key, 0) + 1
    leaves = [e for e in devices[first] if parse(e)[1] not in CONTAINERS]
    coll = union((e[1], e[2]) for e in leaves if COLLECTIVE.match(parse(e)[1] or parse(e)[0]))
    rest = union((e[1], e[2]) for e in leaves if not COLLECTIVE.match(parse(e)[1] or parse(e)[0]))
    gaps = {}
    spans = sorted((e[1], e[2], e[0]) for e in host)
    for s, e in subtract([[t0, t1]], busy[first]):
        mid = 0.5 * (s + e)
        owner = next((n for a, b, n in spans if a <= mid <= b), "no_span")
        gaps[owner] = gaps.get(owner, 0.0) + e - s
    return {
        "window_s": t1 - t0,
        "busy_s": sum(length(b) for b in busy.values()) / len(busy),
        "busy_s_by_device": {n: length(b) for n, b in busy.items()},
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1]),
        "op_counts": count,
        "collective_s": length(coll),
        "collective_exposed_s": length(subtract(coll, rest)),
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
        "events": devices[first],
    }


def kernel_events(reduced: dict, needle: str) -> list:
    """Durations in seconds of the first device's events whose name (the
    operation's HLO text) holds ``needle``."""
    return [e[2] - e[1] for e in reduced["events"] if needle in e[0]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def describe(path: str, top: int = 12) -> None:
    """Print what a trace holds: planes, lines, the longest operations that
    are not containers, one event of every custom call with its statistics,
    and the benchmark's own host spans.  For looking at a trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            if DEVICE_PLANE.match(plane.name) and line.name == OPS_LINE:
                evs = [(ev.name, 0.0, 0.0, {k: v for k, v in ev.stats}, ev.duration_ns) for ev in events]
                leaves = [e for e in evs if parse(e)[1] not in CONTAINERS]
                for e in sorted(leaves, key=lambda e: -e[4])[:top]:
                    print(f"    {e[4] * 1e-6:9.3f} ms  {display_name(e)}  | {e[0][:200]!r}")
                seen = set()
                for e in leaves:
                    if parse(e)[1] == "custom-call" and parse(e)[0] not in seen and len(seen) < 6:
                        seen.add(parse(e)[0])
                        stats = {k: str(v)[:300] for k, v in e[3].items()}
                        print(f"    custom call {e[4] * 1e-6:.3f} ms  {e[0][:600]!r}\n      stats {stats}")
            elif plane.name.startswith("/host:"):
                own = [ev for ev in events if ev.name in OWN_SPANS]
                for ev in own[:3]:
                    print(f"    own span {ev.name!r} {ev.duration_ns * 1e-6:.3f} ms")
