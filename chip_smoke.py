#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of a model the repo supports (depth cut, random seeded
weights), and checks what comes out by the repo's own means:

  serve@1  Llama-3-8B width, 8 layers, bf16 weights:
           InferenceEngineV2 -> warm_all (fallback == 0) ->
           ServingEngine(clock=WallClock()) -> 16 seeded requests -> drain.
           The paged Pallas kernel must be in every lowered step program,
           nothing may compile after warm-up, and the served tokens must be
           (near-)argmax of a plain-attention forward on the same weights.
  train@1  Llama-2-7B width, 2 layers, on-device Adam:
           ds.initialize -> 5 x train_batch on a fixed batch.  Loss finite
           and strictly decreasing; the three flash kernels in the step.
  train@4  Llama-3-8B width, 4 layers, ZeRO-3 over data=4 (>= 4 chips only):
           every device holds about a quarter of params + optimizer state;
           all-gather and reduce-scatter in the compiled step.
  serve@4  the serve@1 model at tensor_parallel=4 (>= 4 chips only): arena
           and weights sharded four ways, same requests, same token check.

The parent imports the standard library only and runs each leg as a child
process, one after another: a chip belongs to one process at a time, and an
engine dropped in-process does not return its HBM.  Every child first
asserts that JAX sees a TPU.  Each leg prints one JSON line; the times in
it (seconds to first step, compile included) are set-up information, not
results — this script is not the benchmark.

Last line of stdout on success, exit code 0:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Anything else — no TPU, a failed check, a child that dies — exits non-zero
and prints no such line.

``--rehearse`` (never passed by the driver) runs the same code at toy sizes
on four virtual CPU devices with the kernels interpreted.  Every line says
so, and the pass line is not printed; nor is it for a ``--legs`` subset.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

ONE_CHIP_LEGS = ("serve@1", "train@1")
FOUR_CHIP_LEGS = ("train@4", "serve@4")
TOTAL_BUDGET_S = 1150  # the contract allows 1200 s for everything

# Tolerance of the serving checks, in logits.  The weights are random, so the
# logits are ~N(0, 1) over a 128k vocabulary: the largest are 4 to 8, where
# bf16 resolves 2^-5.  Two bf16 pipelines that differ only in the order of
# attention's sums land a few ulp apart after 8 layers; 4 ulp is allowed.
#  - every logit of the paged path is within it of plain attention's;
#  - a served token is within it of the reference's maximum (a near-tie may
#    flip the argmax; a wrong page, mask or head mapping is off by ~5).
LOGIT_TOL = 0.125


# --------------------------------------------------------------------------
# parent: standard library only


def _run_child(leg, passthrough, timeout_s):
    """Run one leg in its own process group, echo its output, and return
    (exit code, the leg's JSON record or None, its checked tokens or None).
    The group is killed at the time limit, so nothing the child started
    outlives this call."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg] + passthrough
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, start_new_session=True)

    def _kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(timeout_s, _kill)
    timer.start()
    record = tokens = None
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith("{"):
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if obj.get("leg") == leg and "checks" in obj:
                    record = obj
                elif obj.get("leg") == leg and obj.get("phase") == "tokens":
                    tokens = obj["tokens"]
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill()
    return rc, record, tokens


def parent_main(args):
    t_start = time.monotonic()
    passthrough = ["--rehearse"] if args.rehearse else []
    wanted = args.legs.split(",") if args.legs else None
    records, tokens = {}, {}

    def run(leg, extra=()):
        left = TOTAL_BUDGET_S - (time.monotonic() - t_start)
        if left <= 0:
            sys.exit(f"chip_smoke: out of time before leg {leg}")
        rc, rec, tokens[leg] = _run_child(leg, passthrough + list(extra), left)
        if rc != 0 or rec is None or not rec.get("ok"):
            sys.exit(f"chip_smoke: leg {leg} FAILED (exit code {rc})")
        records[leg] = rec

    for leg in ONE_CHIP_LEGS:
        if wanted is None or leg in wanted:
            run(leg)
    count = max((r["device"]["count"] for r in records.values()), default=0)
    four = [leg for leg in FOUR_CHIP_LEGS
            if (leg in wanted if wanted is not None else count >= 4)]
    for leg in four:
        extra = []
        if leg == "serve@4" and tokens.get("serve@1"):
            # serve@4 also checks serve@1's tokens where the two first part
            extra = ["--serve1-tokens", json.dumps(tokens["serve@1"])]
        run(leg, extra)
    if "serve@1" in records and "serve@4" in records:
        one = records["serve@1"]["kv_arena_bytes_per_device"][0]
        for b in records["serve@4"]["kv_arena_bytes_per_device"]:
            if b * 4 != one:
                sys.exit(f"chip_smoke: serve@4 holds {b} arena bytes on a device, "
                         f"not a quarter of serve@1's {one}")
    if not records:
        sys.exit("chip_smoke: no leg ran")

    summary = {"legs": {k: v["devices"] for k, v in records.items()},
               "skipped": [leg for leg in FOUR_CHIP_LEGS if leg not in records],
               "wall_s": round(time.monotonic() - t_start, 1)}
    if args.rehearse or wanted is not None:
        # a rehearsal or a chosen subset proves less than the pass line says
        print(json.dumps({"rehearsal": args.rehearse, "subset": wanted, **summary}))
        return
    print(json.dumps(summary))
    device = next(iter(records.values()))["device"]
    print(json.dumps({"ok": True, "device": device}))


# --------------------------------------------------------------------------
# children: each owns the chip for its lifetime


def _say(rehearse, obj):
    if rehearse:
        obj = {"rehearsal": True, **obj}
    print(json.dumps(obj), flush=True)


def _open_device(leg, n_devices, rehearse):
    """First thing every child does: find the chip(s) or fail.  With
    JAX_PLATFORMS unset JAX drops to the CPU with a warning when libtpu
    cannot take the chip; this check is what turns that into a failure."""
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if not rehearse and dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU chip found — JAX {jax.__version__} reports {device}; "
                 f"nothing was built")
    if device["count"] < n_devices:
        sys.exit(f"chip_smoke: leg {leg} needs {n_devices} devices, JAX sees {device['count']}")
    _say(rehearse, {"leg": leg, "phase": "device", "device": device, "jax": jax.__version__})
    if not rehearse:
        from deepspeed_tpu.utils import compile_cache
        _say(rehearse, {"leg": leg, "phase": "compile_cache", "dir": compile_cache.enable()})
    return device


def _peak_hbm(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _per_device_bytes(tree, devices):
    """Bytes each device really holds of ``tree``, from addressable_shards."""
    import jax
    held = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            for sh in leaf.addressable_shards:
                held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return [held[d.id] for d in devices]


def _n_params(tree):
    import jax
    import numpy as np
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def _model_record(cfg, preset):
    """Widths as run next to the preset they come from; only depth is cut."""
    from deepspeed_tpu.models.llama import PRESETS
    return {"preset": preset, "hidden": cfg.hidden_size,
            "heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
            "head_dim": cfg.hidden_size // cfg.num_attention_heads,
            "mlp": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "layers": cfg.num_hidden_layers,
            "layers_published": PRESETS[preset].num_hidden_layers if preset in PRESETS else None}


def _finish(rehearse, record, checks):
    record["checks"] = checks
    record["ok"] = all(checks.values())
    _say(rehearse, record)
    if not record["ok"]:
        failed = [k for k, v in checks.items() if not v]
        sys.exit(f"chip_smoke: leg {record['leg']} failed checks: {failed}")


# ------------------------------------------------------------------- serve


def serve_setup(rehearse):
    """(model config, preset name, KV and scheduler config, request shape)."""
    import dataclasses

    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama import PRESETS, LlamaConfig
    from deepspeed_tpu.models.llama_cache import PagedKVConfig

    if rehearse:
        cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                          num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=512, rope_theta=1e4,
                          param_dtype=jnp.bfloat16, attention_impl="flash", scan_layers=True)
        kv = PagedKVConfig(num_pages=96, page_size=16, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=128, max_seqs=4, prefill_chunk=32, decode_bucket=4)
        shape = dict(n_requests=4, prompt_lo=20, prompt_hi=90, new_tokens=8)
        preset = "toy"
    else:
        # widths uncut (hidden 4096, 32q/8kv heads at d=128, MLP 14336, vocab
        # 128256); depth cut 32 -> 8: 2.8B parameters, 5.6 GB in bf16.
        # param_dtype defaults to float32, which at this width is 11 GB
        cfg = dataclasses.replace(PRESETS["llama3-8b"], num_hidden_layers=8,
                                  param_dtype=jnp.bfloat16, attention_impl="flash",
                                  scan_layers=True)
        # 4096 pages x 16 tokens x 32 KB a token (8 layers) = 2 GB arena;
        # 104 pages a sequence hold the longest prompt plus its 64 new tokens
        kv = PagedKVConfig(num_pages=4096, page_size=16, max_pages_per_seq=104)
        # one batch bucket, so warm_all compiles 5 programs, not 5 a bucket
        sched = SchedulerConfig(token_budget=2048, max_seqs=16, prefill_chunk=128,
                                decode_bucket=16)
        shape = dict(n_requests=16, prompt_lo=200, prompt_hi=1500, new_tokens=64)
        preset = "llama3-8b"
    return cfg, preset, kv, sched, shape


def _seeded_prompts(shape, chunk, vocab, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = []
    for _ in range(shape["n_requests"]):
        n = int(rng.integers(shape["prompt_lo"], shape["prompt_hi"] + 1))
        if n % chunk == 0:
            n += 1  # ragged on purpose: the last prefill chunk is partial
        prompts.append([int(t) for t in rng.integers(1, vocab, n)])
    return prompts


def _reference_rows_fn(cfg, params, n, pad_to):
    """rows(prompt, generated) -> the ``n`` logit rows [n, vocab] that predict
    the generated tokens, from a plain-attention forward of the training
    model on the same weights: no paged cache, no Pallas kernel.
    Teacher-forced, so one flipped near-tie does not cascade.  One compiled
    program serves every call."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(dataclasses.replace(cfg, attention_impl="reference"))

    @jax.jit
    def fwd(params, ids, start):
        logits = model.apply(params, ids)[0]
        return jax.lax.dynamic_slice_in_dim(logits, start, n, axis=0).astype(jnp.float32)

    def rows(prompt, generated):
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(prompt) + len(generated)] = prompt + generated
        return np.asarray(fwd(params, jnp.asarray(ids), len(prompt) - 1))

    return rows


def _paged_rows(eng, seq, n):
    """The last ``n`` logit rows of ``seq`` from the serving twin — paged
    cache and Pallas kernel, the engine's own weights and sharding — fed in
    prefill chunks through a scratch arena of one sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models.llama_cache import PagedKVConfig, init_kv_cache

    kvc, chunk = eng.econfig.kv, eng.econfig.scheduler.prefill_chunk
    n_pages = -(-len(seq) // kvc.page_size)
    cache = init_kv_cache(eng.cfg, PagedKVConfig(n_pages + 1, kvc.page_size, kvc.max_pages_per_seq),
                          dtype=eng.econfig.kv_dtype)
    if eng.mesh is not None:
        cache = jax.device_put(cache, eng._cache_sh)
    table = np.zeros((1, kvc.max_pages_per_seq), np.int32)
    table[0, :n_pages] = 1 + np.arange(n_pages)  # page 0 is the null page
    step = jax.jit(lambda p, c, t, s, b, l: eng.model.apply(p, t, s, b, c, l), donate_argnums=(1, ))
    out = []
    for s in range(0, len(seq), chunk):
        part = seq[s:s + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(part)] = part
        logits, cache = eng._invoke(step, eng.params, cache, jnp.asarray(toks),
                                    jnp.asarray([s], jnp.int32), jnp.asarray(table),
                                    jnp.asarray([len(part)], jnp.int32))
        if s + chunk > len(seq) - n:
            out.append(np.asarray(logits[0, :len(part)].astype(jnp.float32)))
    return np.concatenate(out)[-n:]


def leg_serve(leg, tp, rehearse, serve1_tokens):
    device = _open_device(leg, tp, rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.serving import ServingEngine, WallClock
    from deepspeed_tpu.serving.request import RequestState

    cfg, preset, kv, sched, shape = serve_setup(rehearse)
    t0 = time.monotonic()
    # a 128-token dummy: a length that is not a multiple of 128 would send
    # "flash" down the chunked -> reference fallback chain inside init
    params = jax.jit(LlamaForCausalLM(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    n_params = _n_params(params)

    # tensor_parallel=1 builds no mesh and lands on device 0, also on a
    # four-chip host: no environment variable hides the other chips
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched, max_new_tokens=shape["new_tokens"], tensor_parallel=tp))
    devices = list(eng.mesh.devices.flat) if eng.mesh is not None else jax.devices()[:1]
    arena = _per_device_bytes(eng.cache, devices)
    weights = _per_device_bytes(eng.params, devices)

    warm = eng.warm_all()
    keys = eng.step_shape_set()
    warm_s = time.monotonic() - t0
    lowered_missing = [eng._key_label(k) for k in keys
                       if "ds_paged_attention" not in eng._aot_lower(k).as_text()]
    mosaic_missing = [eng._key_label(k) for k in keys
                      if "tpu_custom_call" not in eng._step_fns[k].as_text()]
    _, _ = jax.random.split(eng.rng)  # the serving loop's only eager ops: compile them now

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name) if name.endswith("backend_compile_duration") else None)
    programs_before = set(eng._step_fns)

    serve = ServingEngine(eng, clock=WallClock())
    prompts = _seeded_prompts(shape, sched.prefill_chunk, cfg.vocab_size, seed=0)
    reqs = [serve.submit(p, max_new_tokens=shape["new_tokens"]) for p in prompts]
    t1 = time.monotonic()
    serve.drain()
    serve_s = time.monotonic() - t1
    compiles_in_window = len(compiles)
    done = [r for r in reqs if r.state == RequestState.DONE
            and len(r.tokens) == shape["new_tokens"]]

    # shortest and longest prompt against plain attention on the same weights
    order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    picked = [order[0], order[-1]]
    n = shape["new_tokens"]
    pad_to = -(-(len(prompts[order[-1]]) + n) // 128) * 128
    ref_rows = _reference_rows_fn(cfg, params, n, pad_to)
    tokens = [list(reqs[i].tokens) for i in picked]
    gaps, logit_err = [], []
    for i, toks in zip(picked, tokens):
        ref = ref_rows(prompts[i], toks)
        gaps.append(ref.max(axis=-1) - ref[np.arange(n), toks])
        logit_err.append(float(np.abs(_paged_rows(eng, prompts[i] + toks[:-1], n) - ref).max()))
    max_gap = float(max(g.max() for g in gaps))
    exact = int(sum((g == 0).sum() for g in gaps))
    checks = {
        "warm_all_fallback_0": warm["fallback"] == 0 and warm["compiled"] == len(keys),
        "all_requests_done": len(done) == len(reqs),
        "no_compile_after_warm_up": compiles_in_window == 0
        and set(eng._step_fns) == programs_before,
        "paged_logits_within_tol_of_reference": max(logit_err) <= LOGIT_TOL,
        "served_tokens_within_tol_of_reference_max": max_gap <= LOGIT_TOL,
        "arena_sharded_evenly": len(set(arena)) == 1 and arena[0] * tp == eng.cache.nbytes,
    }
    if not rehearse:  # interpreted kernels leave no custom call to find
        checks["paged_kernel_in_every_lowered_step"] = not lowered_missing
        checks["mosaic_call_in_every_compiled_step"] = not mosaic_missing
    _say(rehearse, {"leg": leg, "phase": "tokens", "requests": picked, "tokens": tokens})
    first_split = None
    if serve1_tokens is not None:
        # up to the first position where the two legs part, both saw the same
        # context there; serve@1's token must be a near-tie here as well
        first_split = []
        for i, (mine, theirs) in enumerate(zip(tokens, serve1_tokens)):
            j = next((k for k, (a, b) in enumerate(zip(mine, theirs)) if a != b), None)
            first_split.append(j)
            if j is not None:
                row = ref_rows(prompts[picked[i]], mine[:j] + [theirs[j]])[j]
                checks[f"serve1_token_near_tie_at_split_{i}"] = \
                    float(row.max() - row[theirs[j]]) <= LOGIT_TOL
    _finish(rehearse, {
        "leg": leg, "device": device, "devices": tp,
        "model": {**_model_record(cfg, preset), "param_dtype": "bfloat16",
                  "n_params": n_params},
        "kv": {"pages": kv.num_pages, "page_size": kv.page_size,
               "max_pages_per_seq": kv.max_pages_per_seq},
        "setup_s_to_warm_compile_included": round(warm_s, 1),
        "setup_s_serving_window": round(serve_s, 1),
        "warm_all": {k: warm[k] for k in ("compiled", "cached", "fallback", "keys")},
        "requests": {"submitted": len(reqs), "done": len(done),
                     "prompt_tokens": [len(p) for p in prompts],
                     "new_tokens_each": shape["new_tokens"]},
        "compiles_in_window": compiles_in_window,
        "kernels_in_hlo": {"ds_paged_attention": not lowered_missing,
                           "tpu_custom_call": not mosaic_missing},
        "check": {"requests": picked, "positions": int(sum(len(g) for g in gaps)),
                  "max_abs_logit_err_paged_vs_reference": [round(e, 4) for e in logit_err],
                  "argmax_exact": exact, "max_logit_gap_of_served_token": round(max_gap, 4),
                  "tol": LOGIT_TOL, "first_split_vs_serve1": first_split},
        "kv_arena_bytes_per_device": arena,
        "weight_bytes_per_device": weights,
        "peak_hbm_bytes_per_device": _peak_hbm(devices),
    }, checks)


# ------------------------------------------------------------------- train


def train_setup(leg, rehearse):
    """(model config, preset name, published depth, batch per device, seq)."""
    import dataclasses

    from deepspeed_tpu.models.llama import PRESETS, LlamaConfig

    if rehearse:
        cfg = LlamaConfig(vocab_size=512, hidden_size=256, intermediate_size=512,
                          num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=512, rope_theta=1e4)
        preset, batch_per_device, seq = "toy", 1, 128
    elif leg == "train@1":
        # Llama-3's 128k vocabulary puts 1.05B parameters into the two
        # embedding tables alone, which no 16 GB chip trains with on-device
        # Adam; Llama-2-7B's width at 2 layers is 0.67B parameters
        cfg, preset, batch_per_device, seq = PRESETS["llama2-7b"], "llama2-7b", 2, 2048
        cfg = dataclasses.replace(cfg, num_hidden_layers=2)
    else:
        # 4 layers at Llama-3-8B width: 1.9B parameters, ZeRO-3 over 4 chips
        cfg, preset, batch_per_device, seq = PRESETS["llama3-8b"], "llama3-8b", 1, 2048
        cfg = dataclasses.replace(cfg, num_hidden_layers=4)
    cfg = dataclasses.replace(cfg, attention_impl="flash", remat=True,
                              remat_policy="flash_saveable", scan_layers=True)
    return cfg, preset, batch_per_device, seq


def leg_train(leg, n_dev, rehearse):
    device = _open_device(leg, n_dev, rehearse)
    import jax
    import numpy as np
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    cfg, preset, batch_per_device, seq = train_setup(leg, rehearse)
    # the engine's default mesh spans every device JAX sees; the one-chip leg
    # on a four-chip host says so with an explicit one-device mesh
    devices = jax.devices()[:n_dev]
    mesh = None if n_dev == len(jax.devices()) else \
        mesh_lib.create_mesh(mesh_lib.MeshSpec(), devices=devices)
    batch = batch_per_device * n_dev
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(cfg), mesh=mesh, config={
        "train_batch_size": batch,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
    })
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    b = {"input_ids": ids, "labels": ids}

    t0 = time.monotonic()
    losses = [float(engine.train_batch(batch=b))]
    first_step_s = time.monotonic() - t0
    losses += [float(engine.train_batch(batch=b)) for _ in range(4)]

    t1 = time.monotonic()
    with mesh_lib.trace_mesh(engine.mesh):
        lowered = engine._train_step_fn.lower(engine.state, b)
    lowered_text = lowered.as_text()
    compiled_text = lowered.compile().as_text()
    hlo_check_s = time.monotonic() - t1
    kernels = {k: k in lowered_text for k in ("ds_flash_fwd", "ds_flash_dq", "ds_flash_dkv")}

    state = engine.state
    n_params = _n_params(state.params)
    sharded = (state.params, state.master, state.opt_state)
    held = _per_device_bytes(sharded, devices)
    total = sum(l.nbytes for l in jax.tree.leaves(sharded) if isinstance(l, jax.Array))
    checks = {
        "losses_finite": bool(np.isfinite(losses).all()),
        "losses_strictly_decreasing": all(b_ < a for a, b_ in zip(losses, losses[1:])),
    }
    if not rehearse:  # interpreted kernels leave no custom call to find
        checks["flash_kernels_in_lowered_step"] = all(kernels.values())
        checks["mosaic_call_in_compiled_step"] = "tpu_custom_call" in compiled_text
    collectives = None
    if n_dev > 1:
        collectives = {c: c in compiled_text for c in ("all-gather", "reduce-scatter", "all-reduce")}
        checks["no_device_holds_over_30pct_of_state"] = max(held) <= 0.30 * total
        checks["all_gather_in_compiled_step"] = collectives["all-gather"]
        # the CPU backend lowers the gradient reduction to all-reduce +
        # slice; on the chip ZeRO-3 must show as a real reduce-scatter
        checks["reduce_scatter_in_compiled_step"] = collectives["reduce-scatter"] or (
            rehearse and collectives["all-reduce"])
    _finish(rehearse, {
        "leg": leg, "device": device, "devices": n_dev,
        "model": {**_model_record(cfg, preset), "n_params": n_params},
        "job": {"zero_stage": 3, "mesh": {k: v for k, v in engine.mesh.shape.items() if v > 1},
                "batch": batch, "seq": seq, "compute_dtype": "bfloat16",
                "remat_policy": cfg.remat_policy, "optimizer": "AdamW on device"},
        "setup_s_to_first_step_compile_included": round(first_step_s, 1),
        "setup_s_hlo_check_recompile": round(hlo_check_s, 1),
        "steps": len(losses), "losses": [round(x, 4) for x in losses],
        "kernels_in_hlo": kernels, "collectives_in_compiled_step": collectives,
        "state_bytes_total": total, "state_bytes_per_device": held,
        "peak_hbm_bytes_per_device": _peak_hbm(devices),
    }, checks)


# --------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leg", choices=ONE_CHIP_LEGS + FOUR_CHIP_LEGS,
                    help="(internal) run one leg in this process")
    ap.add_argument("--legs", help="comma-separated subset to run, e.g. serve@1,train@4; "
                                   "cannot print the pass line (default: the one-chip legs, "
                                   "plus the four-chip legs where JAX sees >= 4 devices)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on 4 virtual CPU devices; cannot print the pass line")
    ap.add_argument("--serve1-tokens", help="(internal) serve@1's checked tokens, as JSON")
    args = ap.parse_args()
    if args.leg is None:
        return parent_main(args)
    if args.leg.startswith("serve"):
        tokens = json.loads(args.serve1_tokens) if args.serve1_tokens else None
        return leg_serve(args.leg, 4 if args.leg.endswith("@4") else 1, args.rehearse, tokens)
    return leg_train(args.leg, 4 if args.leg.endswith("@4") else 1, args.rehearse)


if __name__ == "__main__":
    main()
