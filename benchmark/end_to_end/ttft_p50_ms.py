"""ttft_p50_ms (ms, lower is better; host clock).  Median over the measured
requests that finished of: first token's time minus the time the request was
due."""
from percentiles import percentile


def read(run):
    samples = run.get("samples", {}).get("ttft_ms")
    return percentile(samples, 50) if samples else None
