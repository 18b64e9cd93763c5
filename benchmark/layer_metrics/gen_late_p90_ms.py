"""gen_late_p90_ms -- layer: Serving frontend; unit ms; moves ttft_mean_ms.
90th percentile of the ``submit()`` call's time minus the time the request
was due: how long a due request waited for the tick in progress to end,
because the generator shares its thread with ``tick()``."""
from percentiles import percentile


def read(run):
    samples = run.get("samples", {}).get("gen_late_ms")
    return percentile(samples, 90) if samples else None
