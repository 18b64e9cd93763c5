"""The one percentile function of the benchmark: every sample is kept and
the percentile is read by linear interpolation between order statistics
(numpy's default, "linear"), written out so that nothing depends on numpy's
version."""


def percentile(samples, q: float) -> float:
    """``q`` in [0, 100] over all of ``samples``; raises on an empty sample,
    so a metric with nothing to read is left out by its caller, not zeroed."""
    xs = sorted(float(x) for x in samples)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
