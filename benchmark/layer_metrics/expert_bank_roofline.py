"""expert_bank_roofline -- layer: Kernels; unit %; moves tpot_p50_ms.  Least
time by the roofline for the routed experts' products of the traced stretch's
steps (``roofline_experts.traced_work``: an expert the step's rows touch read
once, the chosen rows' operations; from the step records' ``expert_rows``)
over the summed device time of the events that are those products, in both
forms (``roofline_experts.kernel_seconds``).  Where the program keeps no step
records, routes nothing or ran no such event there is nothing to read."""
import roofline_experts


def read(run):
    trace = run.get("reduced")
    if not trace or run.get("peak") is None or not run["config"].get("num_local_experts"):
        return None
    spent = roofline_experts.kernel_seconds(trace, run["config"])["all"]
    if spent <= 0:
        return None
    least = roofline_experts.traced_work(run)
    return None if least is None else 100.0 * least / spent
