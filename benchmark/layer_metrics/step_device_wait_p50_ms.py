"""step_device_wait_p50_ms -- layer: Inference engine; unit ms; moves
tpot_p50_ms.  Median over the window's step records of ``device_s``, the
host's wait at the readback (segment ``ds.device_wait``)."""
import step_rows
import step_trace


def read(run):
    rows = step_rows.window_rows(run)
    return step_trace.step_device_wait_p50_ms(rows) if rows else None
