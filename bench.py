#!/usr/bin/env python
"""Headline benchmark: causal-LM training throughput on the local chip(s).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric: training tokens/sec/chip on a GPT-scale model (Llama-architecture
125M, bf16, remat, flash kernels), plus MFU against the chip's peak bf16
FLOPS.  ``vs_baseline`` is measured MFU / 0.45 — the reference north-star
acceptance bar (BASELINE.json: "ZeRO-3 ... at >=45% MFU").

Robustness (round 4 — a single timing window once recorded a silent 23x
environment degradation as truth):
  * timing = median over >=3 independent windows, spread reported; extra
    windows are run until two agree within 10% (or the window budget is
    exhausted, in which case the output says so via ``unstable: true``);
  * the traced program is ASSERTED to contain the Pallas flash custom-call
    (``tpu_custom_call``) — a silent fallback to the naive path can't
    masquerade as a kernel regression or vice versa;
  * the median is compared against the committed per-device landmark in
    ``bench_landmarks.json``; >2x below emits ``degraded_env: true`` and a
    loud stderr warning instead of silently recording garbage.
"""

import json
import os
import statistics
import sys
import time

import jax
import numpy as np


def match_device_kind(table):
    """Look the local device kind up in ``table`` by case-insensitive
    substring (runtimes report e.g. "TPU v5 lite" or "TPU v5e" for the
    same chip — tables list every alias)."""
    kind = getattr(jax.devices()[0], "device_kind", "").lower()
    for k, v in table.items():
        if k.lower() in kind:
            return v
    return None


# Peak dense bf16 FLOP/s per chip, keyed by ``device_kind`` substring.
# Source: Google Cloud TPU documentation, the "System architecture" page of
# each generation (v4: 275, v5e: 197, v5p: 459, v6e "Trillium": 918 TFLOP/s).
PEAK_BF16_FLOPS = {
    "tpu v5 lite": 197e12,  # v5e
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v4": 275e12,
    "tpu v6": 918e12,
}


def peak_flops_per_chip():
    """Peak bf16 FLOP/s of the local accelerator.  The table is the only
    source: a device it does not list is an error, not a default."""
    peak = match_device_kind(PEAK_BF16_FLOPS)
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s entry for device_kind "
            f"{getattr(jax.devices()[0], 'device_kind', None)!r}; add it to "
            f"bench.PEAK_BF16_FLOPS with its source")
    return peak


def require_tpu():
    """The on-chip benches time the Pallas kernels; with no chip they fail
    instead of timing a different program on the CPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"{os.path.basename(sys.argv[0])}: no TPU chip found "
                         f"(jax.devices()[0].platform == {dev.platform!r}); "
                         f"this benchmark runs on the chip only")


def load_landmark(metric):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_landmarks.json")
    try:
        with open(path) as f:
            table = json.load(f).get(metric, {})
    except (OSError, ValueError):
        return None
    v = match_device_kind(table)
    return float(v) if v is not None else None


def main():
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    require_tpu()
    from deepspeed_tpu.utils import compile_cache
    compile_cache.enable()
    n_dev = jax.device_count()
    batch, seq = 24 * n_dev, 1024  # B=24/chip measured best on v5e (B=8: 119k,
    # B=16: 123k, B=24: 125k, B=32: 119k tok/s — spills past 24)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048,
                      num_hidden_layers=12, num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=seq, rope_theta=1e4, scan_layers=False, remat=True,
                      remat_policy="flash_saveable",
                      attention_impl="flash")
    model = LlamaForCausalLM(cfg)
    config = {
        "train_batch_size": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq), dtype=np.int32)
    b = {"input_ids": ids, "labels": ids}

    for _ in range(3):  # warmup + compile
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)

    # --- program integrity: the flash kernel must actually be in the step.
    # StableHLO of the traced step contains the Pallas custom-call; a config
    # regression that silently routes attention through the naive path would
    # otherwise be indistinguishable from an environment problem.
    hlo_text = engine._train_step_fn.lower(engine.state, b).as_text()
    # all three flash kernels must be present: fwd alone with a naive
    # backward (a remat/VJP regression) would halve perf while still
    # containing a tpu_custom_call
    missing = [k for k in ("_fwd2_kernel", "_dq2_kernel", "_dkv2_kernel") if k not in hlo_text]
    flash_in_hlo = not missing
    assert flash_in_hlo, (
        f"bench integrity: flash kernels missing from the compiled train "
        f"step ({missing}) — attention (partially) fell back to the naive path")

    # --- timing: median over independent windows; keep adding windows until
    # two consecutive ones agree within 10% (a single window proved foolable
    # in r3).
    steps_per_window = 6
    max_windows = 8
    window_tps = []
    stable = False
    for _ in range(max_windows):
        t0 = time.time()
        for _ in range(steps_per_window):
            loss = engine.train_batch(batch=b)
        jax.block_until_ready(loss)
        dt = time.time() - t0
        window_tps.append(batch * seq * steps_per_window / dt / n_dev)
        if len(window_tps) >= 3 and abs(window_tps[-1] - window_tps[-2]) <= 0.1 * window_tps[-1]:
            stable = True
            break
    if stable:
        # a transient slowdown in early windows must not drag the median
        # (e.g. [5k, 5k, 122k, 122k] medians to 63k and passes every check):
        # once two consecutive windows agree, report only the windows that
        # agree with the final one
        agreed = [w for w in window_tps if abs(w - window_tps[-1]) <= 0.1 * window_tps[-1]]
    else:
        agreed = window_tps
    tokens_per_sec_per_chip = statistics.median(agreed)
    spread = (max(agreed) - min(agreed)) / tokens_per_sec_per_chip

    # --- landmark comparison: a >2x shortfall vs the committed best-known-good
    # for this device kind is an environment problem, not a code regression —
    # say so loudly instead of recording it as truth.
    landmark = load_landmark("train_tokens_per_sec_per_chip")
    degraded_env = bool(landmark and tokens_per_sec_per_chip < 0.5 * landmark)
    if degraded_env:
        print(f"WARNING: bench measured {tokens_per_sec_per_chip:.0f} tok/s/chip, "
              f">2x below the committed landmark {landmark:.0f} for this device "
              f"kind — environment degradation likely; do not treat this number "
              f"as a code regression. Windows: {[round(w) for w in window_tps]}",
              file=sys.stderr)

    # params (excluding embeddings doesn't match convention; use all) → 6N per token
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(engine.state.params))
    model_flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq  # attn term
    mfu = tokens_per_sec_per_chip * model_flops_per_token / peak_flops_per_chip()

    out = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "n_params": n_params,
            "batch": batch,
            "seq": seq,
            "n_devices": n_dev,
            "step_time_s": round(batch * seq / (tokens_per_sec_per_chip * n_dev), 4),
            "windows_tok_s_chip": [round(w, 1) for w in window_tps],
            "spread": round(spread, 4),
            "unstable": not stable,
            "flash_in_hlo": flash_in_hlo,
            "landmark": landmark,
            "degraded_env": degraded_env,
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
