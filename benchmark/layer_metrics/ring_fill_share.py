"""ring_fill_share -- layer: Inference engine; unit share; moves tpot_p50_ms.
Ring rows the live sequences' newest queries can see (``ring_rows_seen``: a
call's last query sees ``min(t + 1, window)``) over the ring rows their slots
hold (``ring_rows_held``: the window, the step's slack and a page, whatever
the sequence's length), over the window's step records: what pages drawn on
demand behind a window would free (ROADMAP A1 (c)), before anybody builds
them.  A short prompt's ring is mostly empty; a ring is never wholly seen, for
its slack."""
import step_rows


def read(run):
    rows = step_rows.window_rows(run)
    if not rows or "ring_rows_held" not in rows[0]:
        return None
    return step_rows.share(rows, "ring_rows_seen", "ring_rows_held")
