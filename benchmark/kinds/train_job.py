"""Traffic kind ``train_job``: whole training steps on packed sequences until
the window closes.

Path under test: ``deepspeed_tpu.initialize`` (the ZeRO stage and mesh the
configuration file states) -> ``train_batch``, each step ended by
``block_until_ready``; the input of each step is made on the host inside
the loop, as a loader would hand it over.
"""

import importlib
import time

import numpy as np

import harness
import traffic_gen
from harness import say, span


def check_batch(cfg, traffic, seed, global_batch):
    """The batch of the first, unmeasured step: the program trains on it at
    its full shape, but only ``check.positions`` leading positions of the
    rows ``check.rows`` count in the loss.  Attention is causal, so the
    reference needs those prefixes alone, and the rows lie on the first and
    the last chip, so the gradient crosses the partition."""
    chk = cfg["check"]
    batch = next(traffic_gen.train_batches(traffic, int(seed) + 1, cfg["vocab_size"], global_batch))
    mask = np.zeros_like(batch["loss_mask"])
    rows = [r % global_batch for r in chk["rows"]]
    mask[rows, :chk["positions"]] = 1.0
    batch["loss_mask"] = mask
    prefix = {k: v[rows, :chk["positions"]] for k, v in batch.items()}
    return batch, prefix


def logit_rows(cfg, traffic, seed, chips):
    """Token ids [chips, check.logit_positions] for the forward comparison:
    one row a chip, so the program's forward runs on its own mesh."""
    n = cfg["check"]["logit_positions"]
    return np.random.default_rng(int(seed) + 2).integers(0, cfg["vocab_size"], (chips, n), dtype=np.int32)


def reference_logits(cfg, params, ids, mode="f32"):
    import jax.numpy as jnp
    return importlib.import_module("refs." + cfg["family"]).forward(params, jnp.asarray(ids), cfg, mode)


def logit_error(got, want):
    """Median over positions of ||got - want|| / ||want|| over the vocabulary."""
    import jax.numpy as jnp

    from refs import plain
    return float(jnp.median(plain.rel_l2(got.astype(jnp.float32), want)))


def reference(cfg, params, prefix, mode="f32"):
    """(loss, gradient norm) of the plain reference on the check's prefixes."""
    import jax.numpy as jnp
    ref_mod = importlib.import_module("refs." + cfg["family"])
    loss, gnorm = ref_mod.loss_and_grad_norm(params, jnp.asarray(prefix["input_ids"]),
                                             jnp.asarray(prefix["labels"]),
                                             jnp.asarray(prefix["loss_mask"]), cfg, mode)
    return float(loss), float(gnorm)


def compare(got, want, logit_err, cfg):
    """The forward pass's logits, then loss and gradient norm of the check
    step, each beside its limit.  The logits carry the precision: a norm
    moves only with the square of a random error, so the last two guard the
    backward pass against gross faults (a term left out, a wrong scale)."""
    chk = cfg["check"]
    say("check", logit_rel_err_p50=f"{logit_err:.6f}", limit=chk["logit_rel_err_p50_limit"])
    d_loss = abs(got[0] - want[0]) / abs(want[0])
    d_norm = abs(got[1] - want[1]) / abs(want[1])
    say("check", loss_rel_diff=f"{d_loss:.3e}", limit=chk["loss_rel_diff_limit"],
        program=f"{got[0]:.6f}", reference=f"{want[0]:.6f}")
    say("check", grad_norm_rel_diff=f"{d_norm:.3e}", limit=chk["grad_norm_rel_diff_limit"],
        program=f"{got[1]:.6f}", reference=f"{want[1]:.6f}")
    return (logit_err <= chk["logit_rel_err_p50_limit"] and d_loss <= chk["loss_rel_diff_limit"]
            and d_norm <= chk["grad_norm_rel_diff_limit"])


def limits(ctx, seeds, dump=None):
    """Builder's mode (``selfcheck.py --limits``; nothing to ``dump`` here): for each seed, the reference's loss and gradient norm
    and the control's (the reference computed in int8 in the program's
    place), at the cell's own size.  The program's own numbers are on the
    ``check`` lines of its runs."""
    import jax
    cfg, traffic = ctx["config"], ctx["traffic"]
    pcfg = harness.program_config(cfg)
    for seed in seeds:
        _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
        _, prefix = check_batch(cfg, traffic, seed, traffic["micro_batch_per_chip"] * ctx["chips"])
        want, low = reference(cfg, params, prefix), reference(cfg, params, prefix, mode="int8")
        ids = logit_rows(cfg, traffic, seed, ctx["chips"])
        ctrl = logit_error(reference_logits(cfg, params, ids, "int8"), reference_logits(cfg, params, ids))
        say("limits", seed=seed, control_logit_rel_err_p50=f"{ctrl:.6f}", reference_loss=f"{want[0]:.6f}", control_loss=f"{low[0]:.6f}",
            control_loss_rel_diff=f"{abs(low[0] - want[0]) / want[0]:.3e}",
            reference_grad_norm=f"{want[1]:.6f}", control_grad_norm=f"{low[1]:.6f}",
            control_grad_norm_rel_diff=f"{abs(low[1] - want[1]) / want[1]:.3e}")
        del params


def run(ctx):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm import mesh as mesh_lib

    cfg, traffic, parts, seconds, chips = ctx["config"], ctx["traffic"], ctx["parts"], ctx["seconds"], ctx["chips"]
    if ctx["sweep"]:
        raise SystemExit("benchmark: --sweep is for serving mixes")
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    from deepspeed_tpu.module_inject.tp_rules import param_shardings

    devices = jax.devices()[:chips]
    pcfg = harness.program_config(cfg)
    # the weights are made in the layout the engine keeps them in (its own rule)
    mesh = create_mesh(MeshSpec(), devices=devices)
    stage = cfg["engine"]["deepspeed"]["zero_optimization"]["stage"]
    model, params = harness.seeded_params(cfg, pcfg, ctx["seed"], devices,
                                          shardings=lambda boxed: param_shardings(boxed, mesh, stage))
    jax.block_until_ready(params)
    parts.mark("weights")

    global_batch = traffic["micro_batch_per_chip"] * chips
    first, prefix = check_batch(cfg, traffic, ctx["seed"], global_batch)
    want = reference(cfg, params, prefix)
    ids = logit_rows(cfg, traffic, ctx["seed"], chips)
    want_logits = np.asarray(reference_logits(cfg, params, ids))  # to the host: the chips are about to fill
    jax.clear_caches()  # the reference's programs are done with; their device memory goes back
    parts.mark("reference_check")

    # ``initialize(params=...)`` drops the partition metadata before it derives
    # shardings and so replicates given weights on every chip (out of memory at
    # this size).  The engine therefore builds its own state, and the seed's
    # weights then take the place of its parameters and master copy.
    engine, _, _, _ = ds.initialize(model=model, mesh=mesh, config={
        **cfg["engine"]["deepspeed"], "train_batch_size": global_batch, "steps_per_print": 0})
    engine._ensure_ready(first)
    to_master = jax.jit(lambda p: jax.tree.map(lambda x: x.astype(jnp.float32), p),
                        out_shardings=engine.state_shardings.master)
    engine.state = engine.state._replace(params=jax.device_put(params["params"], engine.state_shardings.params),
                                         master=to_master(params["params"]))
    count = harness.n_params(params)
    del params
    parts.mark("engine_and_state")
    say("memory", after="engine_state", gb_in_use=[round(b / 1e9, 2) for b in harness.hbm_bytes(devices, "bytes_in_use")])

    compiles = harness.CompileListener()
    batches = traffic_gen.train_batches(traffic, ctx["seed"], cfg["vocab_size"], global_batch)
    losses = []
    # the check step is the engine's own compiled step, called where
    # ``train_batch`` calls it, because the engine keeps no gradient norm
    with mesh_lib.trace_mesh(engine.mesh):
        got_logits = jax.jit(lambda p, ids: model.apply({"params": p}, ids))(engine.state.params, ids)
        logit_err = logit_error(got_logits, jnp.asarray(want_logits))
        del got_logits, want_logits
        engine.state, m = engine._train_step_fn(engine.state, first)
    got = (float(m.loss), float(m.grad_norm))
    numerics_ok = compare(got, want, logit_err, cfg)
    for _ in range(traffic["warmup_steps"]):
        losses.append(float(engine.train_batch(batch=next(batches))))
    parts.mark("warm_up_steps")

    t_open = time.monotonic()
    setup_s = parts.report(t_open)
    tracer = harness.TraceWindow(ctx, t_open, seconds)
    steps = []
    tokens_per_step = global_batch * traffic["seq_len"]
    while True:
        t0 = time.monotonic()
        if t0 >= t_open + seconds:
            break
        tracer.poll(t0)
        with span("input"):
            batch = next(batches)
        with span("train_batch"):
            loss = engine.train_batch(batch=batch)
            jax.block_until_ready(loss)
        steps.append((t0, time.monotonic()))
        losses.append(float(loss))
    t_close = time.monotonic()
    tracer.stop(t_close)

    n_compiles = compiles.since(t_open)
    finite = bool(np.isfinite(losses).all())
    fell = losses[-1] < losses[0]
    say("window", steps=len(steps), tokens_per_step=tokens_per_step, first_loss=f"{losses[0]:.4f}",
        last_loss=f"{losses[-1]:.4f}", params=count)
    say("check", compiles_in_window=n_compiles, limit=0)
    say("check", losses_finite=finite, loss_fell=fell)
    # the window's time is that of its whole steps, first start to last end
    elapsed = steps[-1][1] - steps[0][0]
    return {
        "correct": bool(numerics_ok and n_compiles == 0 and finite and fell),
        "attempted": len(steps), "failed": 0, "setup_s": setup_s,
        "steps": steps, "tokens_per_step": tokens_per_step, "elapsed_s": elapsed,
        "compiles_in_window": n_compiles, "reduced": tracer.reduced, "chips": chips,
        "hbm_peak_bytes": harness.hbm_bytes(devices),
        "micro_batch_per_chip": traffic["micro_batch_per_chip"], "seq_len": traffic["seq_len"],
    }
