"""sambay_attn_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the attention the traced stretch needed
(``roofline_sambay.traced_work``: the window layers' last 512 rows and the
shared pages' every row, read by the layer that writes them and the
cross-attention layers) over the summed device time of the events named
``ds_paged_attention``, the kernel that computes all of it."""
import roofline
import roofline_sambay


def read(run):
    trace = run.get("reduced")
    if not trace or run.get("peak") is None:
        return None
    spent = roofline_sambay.paged_kernel_seconds(trace)
    work = roofline_sambay.traced_work(run)
    if spent <= 0 or work is None:
        return None
    return 100.0 * roofline.least_time_s(work["flops"], work["bytes"], run["peak"]) / spent
