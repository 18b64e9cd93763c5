#!/usr/bin/env python
"""Long-context bench: S=32k causal-LM training on the local chip.

The Ulysses-32k artifact (BASELINE config 4, r3 verdict item 9): trains a
125M Llama at 32,768-token context on one chip — flash kernels (the
triangular-table grid never touches above-diagonal blocks, which at 32k
is ~50% of the square), flash_only remat — and records tokens/s + MFU.
The distributed leg (Llama-3-8B, seq-parallel 8 × data 2 @ 32k) is
compile-proven on a v5p-16 topology in MEMBUDGET.json
(llama3_8b_ulysses32k).

Also records the FPDT q-chunked path (deepspeed_tpu.sequence.fpdt_layer)
at the same shape — the O(chunk^2) live-state profile the reference
streams by hand (ref: deepspeed/sequence/fpdt_layer.py:971).

Writes BENCH_LONGCTX.json at the repo root and prints one JSON line.
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import numpy as np


def run(attention_impl, seq, batch, steps=3, windows=3):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                      num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=seq, rope_theta=5e5, scan_layers=False,
                      remat=True,
                      remat_policy="flash_only" if attention_impl == "flash" else "nothing_saveable",
                      attention_impl=attention_impl)
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(cfg), config={
        "train_batch_size": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
    })
    ids = np.random.default_rng(0).integers(0, 32000, (batch, seq), dtype=np.int32)
    b = {"input_ids": ids, "labels": ids}
    loss = None
    for _ in range(2):
        loss = engine.train_batch(batch=b)
    final = float(loss)
    tps = []
    for _ in range(windows):
        t0 = time.time()  # dslint-ok(determinism): benchmark measures real step wall time
        for _ in range(steps):
            loss = engine.train_batch(batch=b)
        final = float(loss)
        tps.append(batch * seq * steps / (time.time() - t0))  # dslint-ok(determinism): benchmark measures real step wall time
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(engine.state.params))
    return statistics.median(tps), n_params, cfg, final


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench import peak_flops_per_chip, require_tpu
    from deepspeed_tpu.utils import compile_cache
    require_tpu()
    compile_cache.enable()

    seq, batch = 32768, 1
    tps, n_params, cfg, loss = run("flash", seq, batch)
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = tps * flops_per_token / peak_flops_per_chip() / jax.device_count()

    tps_fpdt, _, _, loss_fpdt = run("fpdt", seq, batch, steps=2, windows=2)
    tps_64k, _, _, loss_64k = run("flash", 65536, 1, steps=2, windows=2)

    out = {
        "metric": "longctx_train_tokens_per_sec_per_chip",
        "value": round(tps / jax.device_count(), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "seq": seq, "batch": batch, "mfu": round(mfu, 4),
            "n_params": n_params,
            "loss_finite": bool(np.isfinite(loss) and np.isfinite(loss_fpdt) and np.isfinite(loss_64k)),
            "fpdt_tokens_per_sec_per_chip": round(tps_fpdt / jax.device_count(), 1),
            "flash_64k_tokens_per_sec_per_chip": round(tps_64k / jax.device_count(), 1),
            "flash_over_fpdt": round(tps / tps_fpdt, 2),
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
            "distributed_32k_compile_proof": "MEMBUDGET.json:llama3_8b_ulysses32k",
        },
    }

    # FPDT-only deep-context leg (r5): a context flash CANNOT reach on this
    # chip.  flash at S=131072 OOMs at compile (flash_only remat still keeps
    # every layer's kernel out+lse residuals: ~S*H*(D+128)*2B*L); FPDT's
    # staged groups are jax.checkpoint'd so only group OUTPUTS survive to
    # the backward — it trains where flash cannot.
    s131 = 131072
    try:
        run("flash", s131, 1, steps=1, windows=1)
        flash_131k = "unexpectedly fit"
    except Exception as e:
        flash_131k = f"OOM ({str(e)[:80]})"
    tps_131k, _, _, loss_131k = run("fpdt", s131, 1, steps=1, windows=1)
    out["extra"]["fpdt_only_131k"] = {
        "seq": s131,
        "fpdt_tokens_per_sec_per_chip": round(tps_131k / jax.device_count(), 1),
        "loss_finite": bool(np.isfinite(loss_131k)),
        "flash_at_131k": flash_131k,
    }
    from deepspeed_tpu.resilience.atomic_io import atomic_write_json
    atomic_write_json(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_LONGCTX.json"),
                      out, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
