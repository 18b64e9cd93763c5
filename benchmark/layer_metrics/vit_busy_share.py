"""vit_busy_share -- layer: Kernels; unit share; moves ttft_mean_ms.  Summed
device time of the vision tower's programs (``vit:p<bucket>`` on the trace's
modules line) over the trace's busy time: how much of the device's work in
the cell the tower is; it falls when the tower gets faster (``better`` is
``lower``)."""
import roofline_vit


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"]:
        return None
    spent = roofline_vit.program_seconds(trace)
    return spent / trace["busy_s"] if spent > 0 else None
