"""tpot_p50_ms (ms, lower is better; host clock).  Median over the measured
requests that finished of (finish - first token) / (tokens - 1)."""
from percentiles import percentile


def read(run):
    samples = run.get("samples", {}).get("tpot_ms")
    return percentile(samples, 50) if samples else None
