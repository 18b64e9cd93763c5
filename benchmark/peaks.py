"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect (ICI) a chip.  Copied from ``bench.PEAK_BF16_FLOPS``
of the program, which a later PR may delete.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def match_device_kind(kind: str) -> dict:
    """The peaks of ``kind``; raises for a kind the table does not hold."""
    if kind not in PEAKS:
        raise KeyError(f"device_kind {kind!r} is not in benchmark/peaks.py "
                       f"(known: {sorted(PEAKS)}); add its published peaks with their source")
    return PEAKS[kind]
