"""swa_attn_busy_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Summed device time of the events named ``ds_paged_attention`` (the window
layers' calls over their rings and the full layer's over its pages) over the
trace's busy time: how much of the device's work in the cell is attention; it
falls when the kernel gets faster (``better`` is ``lower``).  Read only where
the program keeps rings (its step records count ``ring_rows_held``): on a
program without the twin there is nothing to read."""
import roofline_swa
import step_rows


def read(run):
    trace = run.get("reduced")
    rows = step_rows.window_rows(run)
    if not trace or not trace["busy_s"] or not rows or "ring_rows_held" not in rows[0]:
        return None
    spent = roofline_swa.kernel_seconds(trace)["all"]
    return spent / trace["busy_s"] if spent > 0 else None
