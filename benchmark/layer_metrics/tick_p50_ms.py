"""tick_p50_ms -- layer: Inference engine; unit ms; moves tpot_p50_ms.
Median host-clock time of ``tick()``, ticks with work only."""
from percentiles import percentile


def read(run):
    ticks = run.get("ticks")
    return percentile([1e3 * (t[1] - t[0]) for t in ticks], 50) if ticks else None
