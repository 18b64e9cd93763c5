"""lightning_update_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.
Least time by the roofline for the one-position Lightning updates the traced
stretch's steps made (``roofline_lightning.traced_work``: a state read and
written once a token and layer, from the step records) over the summed device
time of the events named ``ds_lightning_update``, the kernel that makes them.
Where the program has no such kernel (a parent of the PR that brought it)
there is nothing to read."""
import roofline
import roofline_lightning


def read(run):
    trace = run.get("reduced")
    if not trace or run.get("peak") is None:
        return None
    spent = roofline_lightning.kernel_seconds(trace)
    if spent <= 0:
        return None
    work = roofline_lightning.traced_work(run)
    if work is None:
        return None
    return 100.0 * roofline.least_time_s(work["flops"], work["bytes"], run["peak"]) / spent
