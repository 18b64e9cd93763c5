"""hbm_peak_gb.serve -- layer: Device; unit GB; moves tpot_p50_ms.
``memory_stats()["peak_bytes_in_use"]`` after the window, in 1e9 bytes."""


def read(run):
    return max(run["hbm_peak_bytes"]) / 1e9 if "samples" in run else None
