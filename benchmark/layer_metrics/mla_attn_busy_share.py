"""mla_attn_busy_share -- layer: Kernels; unit share; moves ttft_p50_ms.
Summed device time of the events named ``ds_mla_*`` over the trace's busy
time: whether latent attention is most of the device's work in the cell; it
falls when the kernel gets faster (``better`` is ``lower``)."""
import roofline_mla


def read(run):
    trace = run.get("reduced")
    if not trace or not trace["busy_s"]:
        return None
    spent = roofline_mla.kernel_seconds(trace)
    return spent / trace["busy_s"] if spent > 0 else None
