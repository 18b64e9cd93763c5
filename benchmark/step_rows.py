"""The step records of a serving run's window (PR 34).

``InferenceEngineV2`` records every step it runs (``telemetry/step_anatomy.py``:
host segments, the wait at the readback, the gap to the caller, the program's
key and what the step carried), and ``ServingEngine`` binds the engine's
recorder to the clock the run's ticks are timed on.  The readers of the step
records under ``layer_metrics/`` call ``window_rows``; a program that has no
such recorder (a parent of PR 34) gives ``None`` and its line leaves their
metrics out.
"""

import statistics


def window_span(run):
    """(start of the window's first tick, end of its last), or None."""
    ticks = run.get("ticks")
    return (ticks[0][0], ticks[-1][1]) if ticks else None


def window_rows(run):
    """``StepRecord.to_row()`` of the steps that ended between the first
    tick's start and the last tick's end of ``run["ticks"]``, from the live
    recorder that holds most of them.  None where the program has no
    recorder, the run no tick, or the ring has dropped the window's first steps."""
    try:
        from deepspeed_tpu.telemetry import recorders
    except ImportError:
        return None
    span = window_span(run)
    if span is None:
        return None
    best, rows = None, []
    for rec in recorders():
        inside = [r for r in rec.steps if span[0] <= r.end_ts <= span[1]]
        if len(inside) > len(rows):
            best, rows = rec, inside
    if best is None or (best.dropped_steps and rows[0] is best.steps[0]):
        return None
    return [r.to_row() for r in rows]


def share(rows, over: str, under: str):
    """Sum of count ``over`` by sum of count ``under`` over the rows; None where that is 0."""
    total = sum(r[under] for r in rows) if rows else 0
    return sum(r[over] for r in rows) / total if total else None


def excess_share(rows, seconds: float):
    """What the steps took beyond the median step of their own program key,
    summed, over ``seconds``: small and positive in a steady run, large in
    one that stalled inside a step."""
    if not rows or not seconds > 0:
        return None
    own = {}
    for r in rows:
        own.setdefault(r["key"], []).append(r["wall_s"] - r["host_gap_s"])
    return sum(max(0.0, s - statistics.median(of_key)) for of_key in own.values() for s in of_key) / seconds
