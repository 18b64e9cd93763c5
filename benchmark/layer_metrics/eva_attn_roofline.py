"""eva_attn_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the EVA attention the traced stretch needed
(``roofline_eva.traced_work``: exact rows of the query's window and summary
rows of the windows before it, all layers) over the summed device time of
the events named ``ds_paged_attention``, the kernel that computes it."""
import roofline
import roofline_eva


def read(run):
    trace = run.get("reduced")
    if not trace or run.get("peak") is None:
        return None
    spent = roofline_eva.paged_kernel_seconds(trace)
    work = roofline_eva.traced_work(run)
    if spent <= 0 or work is None:
        return None
    return 100.0 * roofline.least_time_s(work["flops"], work["bytes"], run["peak"]) / spent
