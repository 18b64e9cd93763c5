"""mla_attn_roofline -- layer: Kernels; unit %; moves ttft_p50_ms.  Least time
by the roofline for the latent attention the traced stretch's steps needed
(``roofline_mla.traced_work``: the absorbed form's operations a visible
query-key pair, the cached rows once a call, from the step records) over the
summed device time of the events named ``ds_mla_*``.  Where the program has
no such kernel or no such counts (a parent of the PR that brought them) there
is nothing to read."""
import roofline_mla


def read(run):
    trace = run.get("reduced")
    if not trace:
        return None
    spent = roofline_mla.kernel_seconds(trace)
    if spent <= 0:
        return None
    least = roofline_mla.traced_work(run)
    return None if least is None else 100.0 * least / spent
