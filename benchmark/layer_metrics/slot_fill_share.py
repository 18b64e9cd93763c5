"""slot_fill_share -- layer: Inference engine; unit share; moves tpot_p50_ms.
Token positions computed for a live sequence over the positions the step
programs computed, padding included, over the window's step records."""
import step_rows
import step_trace


def read(run):
    rows = step_rows.window_rows(run)
    return step_trace.slot_fill_share(rows) if rows else None
