"""queue_wait_p90_ms -- layer: Serving frontend; unit ms; moves ttft_mean_ms.
90th percentile of ``admitted_ts`` minus the time of the ``submit()`` call:
how long the frontend held a request before the engine took it (no free
slot, no free pages).  The generator's own lateness is ``gen_late_p90_ms``;
the two intervals lie end to end and share nothing."""
from percentiles import percentile


def read(run):
    samples = run.get("samples", {}).get("queue_wait_ms")
    return percentile(samples, 90) if samples else None
