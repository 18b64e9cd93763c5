"""sparse_attn_busy_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Summed device time of the events named ``ds_sparse_paged_attention`` over the
trace's busy time: how much of the device's work in the cell is the decode
rows' list walks over their chosen blocks; it falls when the kernel gets
faster (``better`` is ``lower``)."""
import roofline_sparse


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"]:
        return None
    spent = roofline_sparse.kernel_seconds(trace)
    return spent / trace["busy_s"] if spent > 0 else None
