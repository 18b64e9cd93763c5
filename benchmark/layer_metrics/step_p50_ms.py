"""step_p50_ms -- layer: Training engine; unit ms; moves train_tok_s_chip.
Median host-clock time of input + ``train_batch`` + ``block_until_ready``."""
from percentiles import percentile


def read(run):
    steps = run.get("steps")
    return percentile([1e3 * (b - a) for a, b in steps], 50) if steps else None
