"""vit_encode_roofline -- layer: Kernels; unit %; moves ttft_mean_ms.  Least
time by the roofline for the images the traced stretch's encodes carried
(``roofline_vit.traced_work``: the tower's products a patch, ``4 N^2 d H`` a
layer for the pairs, the projector, weights once a dispatch; from the encode
records) over the summed device time of the tower's programs (``vit:p<bucket>``
on the trace's modules line).  Where the program has no tower or keeps no
encode records (a parent of the PR that brought them) there is nothing to read."""
import roofline_vit


def read(run):
    trace = run.get("reduced")
    if not trace:
        return None
    spent = roofline_vit.program_seconds(trace)
    if spent <= 0:
        return None
    least = roofline_vit.traced_work(run)
    return None if least is None else 100.0 * least / spent
