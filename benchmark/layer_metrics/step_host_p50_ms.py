"""step_host_p50_ms -- layer: Serving frontend; unit ms; moves tpot_p50_ms.
Median over the window's step records of ``wall_s - device_s``: the host
segments ``ds.admit`` ... ``ds.bookkeeping`` and the gap to the caller, all
the time the device may wait for the host."""
import step_rows
import step_trace


def read(run):
    rows = step_rows.window_rows(run)
    return step_trace.step_host_p50_ms(rows) if rows else None
