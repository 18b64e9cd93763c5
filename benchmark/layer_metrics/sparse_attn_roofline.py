"""sparse_attn_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the key rows the selection names for the traced
stretch's one-token rows (``roofline_sparse.traced_work``: a named row's key
and value once, one key head's, from the step records) over the summed device
time of the events named ``ds_sparse_paged_attention``, the list walk that
reads them.  The walk moves whole pages of both key heads to use one head's
rows, so at best it reads half of the bytes' bound.  Where the program has no
such kernel or no such counts (a parent of the PR that brought them) there is
nothing to read."""
import roofline_sparse


def read(run):
    trace = run.get("reduced")
    if not trace:
        return None
    spent = roofline_sparse.kernel_seconds(trace)
    if spent <= 0:
        return None
    least = roofline_sparse.traced_work(run)
    return None if least is None else 100.0 * least / spent
