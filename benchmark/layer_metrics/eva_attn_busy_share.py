"""eva_attn_busy_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Summed device time of the events named ``ds_paged_attention`` over the
trace's busy time: whether the attention over ring and summary pages is most
of the device's work in the cell."""
import roofline_eva


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"]:
        return None
    spent = roofline_eva.paged_kernel_seconds(trace)
    return spent / trace["busy_s"] if spent > 0 else None
