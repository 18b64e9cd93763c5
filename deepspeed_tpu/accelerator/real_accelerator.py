"""Accelerator auto-detection (ref: accelerator/real_accelerator.py:51
get_accelerator; DS_ACCELERATOR env override honored as DS_TPU_ACCELERATOR
or the reference's own DS_ACCELERATOR)."""

import os

ds_accelerator = None


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    override = os.environ.get("DS_ACCELERATOR") or os.environ.get("DS_TPU_ACCELERATOR")
    if override == "cpu":
        from .cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
        return ds_accelerator
    if override == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
        return ds_accelerator

    # a busy or broken chip must surface here: jax.devices() raising is NOT
    # evidence of a CPU-only host, so the error propagates
    import jax
    if jax.devices()[0].platform == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
    else:
        from .cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
    return ds_accelerator


def set_accelerator(accel):
    global ds_accelerator
    ds_accelerator = accel
