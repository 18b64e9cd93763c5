"""ssd_update_busy_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Summed device time of the events named ``ds_ssd_update`` over the trace's
busy time: how much of the device's work in the cell is carrying the Mamba-2
states of the decode rows through the slot arena; it falls when the kernel
gets faster (``better`` is ``lower``)."""
import roofline_ssd


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"]:
        return None
    spent = roofline_ssd.kernel_seconds(trace)
    return spent / trace["busy_s"] if spent > 0 else None
