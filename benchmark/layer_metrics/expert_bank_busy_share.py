"""expert_bank_busy_share -- layer: Kernels; unit share; moves tpot_p50_ms.
Summed device time of the events that are the routed experts' products, in
both forms (``roofline_experts.kernel_seconds``: the grouped kernel ``ds_gmm``
and the dense form ``ds_experts_dense``), over the trace's busy time: how much
of the device's work in the cell is the experts' bank; it falls when the
products get faster (``better`` is ``lower``).  Where the configuration routes
nothing or no such event ran there is nothing to read."""
import roofline_experts


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"] or not run["config"].get("num_local_experts"):
        return None
    spent = roofline_experts.kernel_seconds(trace, run["config"])["all"]
    return spent / trace["busy_s"] if spent > 0 else None
