"""hbm_peak_gb.train -- layer: Device; unit GB; moves train_tok_s_chip.
``memory_stats()["peak_bytes_in_use"]`` after the window, the fullest of the
cell's chips, in 1e9 bytes."""


def read(run):
    return max(run["hbm_peak_bytes"]) / 1e9 if "steps" in run else None
