"""The first tokens of a serving run's window, as the program recorded them (PR 51).

``ServingEngine`` folds every request's way to its first token into one row
of its engine's step recorder (``StepAnatomy.first_tokens``,
``telemetry/spans.py`` ``first_token_row``): the request's timestamps and
counts and the parts of its TTFT, which sum to it: ``late_s`` (due, and the
caller had not submitted it), ``queued_s``, ``carried_s`` (a step that
carried a chunk of it ran), ``bypassed_s`` (a step ran and carried none of
it), ``vision_encode_s``, ``wait_s`` (admitted, no step running) and
``other_s``.  The six readers under ``layer_metrics/`` that start with
``prefill_ms_per_ktok`` or ``ttft_`` call ``median`` or ``mean`` here; a
program that keeps no such rows (a parent of PR 51) gives ``None`` and its
line leaves their metrics out.
"""

import step_rows
from percentiles import percentile


def window_rows(run):
    """The rows whose first token was delivered between the first tick's
    start and the last tick's end of ``run["ticks"]``, from the live recorder
    that holds most of them.  None where the program keeps no such rows or
    the run has no tick."""
    try:
        from deepspeed_tpu.telemetry import recorders
    except ImportError:
        return None
    span = step_rows.window_span(run)
    if span is None:
        return None
    rings = [rec.first_tokens for rec in recorders() if hasattr(rec, "first_tokens")]
    if not rings:
        return None
    return max(([r for r in ring if span[0] <= r["first_token_ts"] <= span[1]] for ring in rings), key=len)


def per_ktok_ms(row):
    """What a thousand prompt tokens cost in the request's own steps, whatever it waited for."""
    return 1e3 * row["carried_s"] / (row["prefill_tokens"] / 1000)


def bypassed_ms(row):
    """The steps that ran in its PREFILL and carried none of it: the scheduler's and the dispatch ladder's share."""
    return 1e3 * row["bypassed_s"]


def wait_ms(row):
    """Everything in which no step of the engine ran for anybody: the caller, the queue, the ticks' own host work."""
    return 1e3 * (row["late_s"] + row["queued_s"] + row["wait_s"] + row["other_s"])


def _values(run, of):
    rows = window_rows(run)
    return [of(r) for r in rows if r["prefill_tokens"]] if rows else None


def median(run, of):
    values = _values(run, of)
    return percentile(values, 50) if values else None


def mean(run, of):
    values = _values(run, of)
    return sum(values) / len(values) if values else None
