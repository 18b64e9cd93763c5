#!/usr/bin/env python
"""Round-4 features, end to end on a CPU mesh (no TPU needed):

1. OneBitAdam with the REAL compressed wire (``comm_backend_name``):
   sign-packed momentum allreduce after an fp32-warmup phase
   (ref: deepspeed/runtime/fp16/onebit/adam.py + runtime/comm/nccl.py).
2. ZeRO++ qgZ gradient transport (``zero_quantized_gradients``): int8
   quantized all-to-all reduce-scatter + quantized all-gather
   (ref: deepspeed/runtime/comm/coalesced_collectives.py).
3. Pipelined NVMe optimizer offload (``offload_optimizer: nvme``): fp32
   master + Adam moments live on disk in double-buffered sub-groups
   (ref: deepspeed/runtime/swap_tensor/pipelined_optimizer_swapper.py).

Run:  python examples/compressed_and_offload.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

import deepspeed_tpu as ds
from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                  max_position_embeddings=64, rope_theta=1e4,
                  dtype=jax.numpy.float32, param_dtype=jax.numpy.float32)


def train(tag, config, mesh_devices=8, steps=6):
    mesh = create_mesh(MeshSpec(data=mesh_devices), devices=jax.devices()[:mesh_devices])
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(CFG), mesh=mesh,
                                    dist_init_required=False, config=config)
    ids = np.random.default_rng(0).integers(0, 256, (8, 32)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(steps)]
    print(f"{tag:>28}: loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return engine


def main():
    dist.configure(enabled=True)

    # 1. 1-bit Adam on the compressed wire (freeze_step=2 so the momentum
    #    wire engages within this demo)
    train("OneBitAdam compressed wire", {
        "train_batch_size": 8,
        "optimizer": {"type": "OneBitAdam",
                      "params": {"lr": 1e-3, "freeze_step": 2, "comm_backend_name": "nccl"}},
        "zero_optimization": {"stage": 0}, "steps_per_print": 0})

    # 2. qgZ: int8 gradient transport
    train("qgZ int8 grad transport", {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0, "zero_quantized_gradients": True},
        "steps_per_print": 0})

    dist.log_summary()  # wire bytes per step for both transports

    # 3. pipelined NVMe optimizer offload (single-device mesh)
    with tempfile.TemporaryDirectory() as swap:
        train("pipelined NVMe offload", {
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0,
                                  "offload_optimizer": {"device": "nvme", "nvme_path": swap}},
            "steps_per_print": 0}, mesh_devices=1)


if __name__ == "__main__":
    main()
