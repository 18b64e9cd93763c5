"""swa_attn_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the attention the traced stretch's steps needed
(``roofline_swa.traced_work``: the window layers' last 4,096 rows and the full
layer's every row, from the step records) over the summed device time of the
events named ``ds_paged_attention``, the kernel both kinds of layer go
through (the program's scopes ``ds_swa_window`` and ``ds_swa_full``).  Where
the program has no such counts (a parent of the PR that brought them) there
is nothing to read."""
import roofline_swa


def read(run):
    trace = run.get("reduced")
    if not trace:
        return None
    spent = roofline_swa.kernel_seconds(trace)["all"]
    if spent <= 0:
        return None
    least = roofline_swa.traced_work(run)
    return None if least is None else 100.0 * least / spent
