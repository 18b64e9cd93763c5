"""paged_attn_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the paged attention the traced requests needed
(``roofline.paged_*`` over the requests' own lengths, all layers) over the
summed device time of the paged kernel's events in the trace.  The program
gives its Pallas kernels no name, so the events are the serving step's
``tpu_custom_call`` operations; ``_paged_kernel`` is the only one there."""
import roofline
import trace_reduce


def read(run):
    trace, work = run.get("reduced"), run.get("attention")
    if not trace or not work or run.get("peak") is None:
        return None
    spent = sum(trace_reduce.kernel_events(trace, trace_reduce.PALLAS_CALL))
    if spent <= 0:
        return None
    return 100.0 * roofline.least_time_s(work["flops"], work["bytes"], run["peak"]) / spent
