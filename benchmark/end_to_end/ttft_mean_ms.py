"""ttft_mean_ms (ms, lower is better; host clock).  Mean over the measured
requests that finished of: first token's time minus the time the request was
due.  Every request's wait counts in it, the long prompts' most."""


def read(run):
    samples = run.get("samples", {}).get("ttft_ms")
    return sum(samples) / len(samples) if samples else None
