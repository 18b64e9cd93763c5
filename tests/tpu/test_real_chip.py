"""Real-TPU integration tests (SURVEY §4: marker-gated TPU leg of the
harness; the CPU-mesh conftest forces these to skip under the default
suite).  Run directly on a TPU host with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu -q
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def test_train_throughput_floor():
    """Llama-125M bf16 must clear a conservative throughput floor (catches
    per-step sync regressions like the ThroughputTimer issue)."""
    import time
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import LlamaForCausalLM, PRESETS

    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(PRESETS["125m"]), config={
        "train_batch_size": 8, "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2}, "bf16": {"enabled": True}, "steps_per_print": 0})
    ids = np.random.default_rng(0).integers(0, 32000, (8, 1024), dtype=np.int32)
    b = {"input_ids": ids, "labels": ids}
    for _ in range(3):
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(5):
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    tps = 8 * 1024 * 5 / (time.time() - t0)
    assert tps > 30_000, f"throughput regression: {tps:,.0f} tokens/s (expect >50k on v5e)"


def test_generate_on_chip():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import LlamaForCausalLM, PRESETS

    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(PRESETS["tiny"]), config={
        "train_batch_size": 8, "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "hybrid_engine": {"enabled": True, "max_out_tokens": 8}, "steps_per_print": 0})
    out = engine.generate(np.ones((2, 4), np.int32), max_new_tokens=4)
    assert out.shape == (2, 8)
