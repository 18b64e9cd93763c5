"""DeepSpeedEngine — the training engine.

TPU-native analog of ``deepspeed/runtime/engine.py:189 DeepSpeedEngine``
(3,990 LoC).  The reference wraps an eager torch ``nn.Module`` and
orchestrates fwd/bwd/step with hook-driven ZeRO machinery; here the whole
train step — gradient accumulation scan, loss scaling, grad sharding
constraints (reduce-scatter), clipping, optimizer update, master-weight
recast — is ONE jitted program whose in/out shardings realise the configured
ZeRO stage (see runtime/zero/partition.py).  What the reference does with
streams, hooks and buckets, XLA's scheduler does from the program structure.

API parity map (reference → here):
  engine.forward(batch)            → forward()            (engine.py:2041)
  engine.backward(loss)            → backward()           (engine.py:2204)
  engine.step()                    → step()               (engine.py:2338)
  engine.train_batch(...)          → train_batch()        (pipe/engine.py:338;
        promoted here to the primary fused path for all configs)
  engine.eval_batch                → eval_batch
  engine.save_checkpoint/load_...  → save_checkpoint/load_checkpoint
        (engine.py:3274/2928; implemented over orbax in checkpoint/engine.py)
  engine.no_sync                   → no_sync (engine.py:2184; no-op — grad
        sync placement is compiled, accumulation already local)
"""

import os
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm import mesh as mesh_lib
from ..comm.mesh import BATCH_AXES, SEQ_AXIS, MeshSpec, create_mesh, set_global_mesh
from ..ops import optimizer as opt_lib
from ..ops.adam import adam, adamw, fused_adam
from ..ops.adagrad import adagrad, sgd
from ..ops.lamb import fused_lamb
from ..ops.lion import fused_lion
from ..ops.onebit import onebit_adam, onebit_lamb, zero_one_adam
from ..utils.logging import log_dist, logger
from ..utils.nvtx import profiler_range
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER,
                           NoopTimer, SynchronizedWallClockTimer, ThroughputTimer)
from .config import DeepSpeedConfig
from .constants import (ADAGRAD_OPTIMIZER, ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM_OPTIMIZER,
                        FUSED_LAMB_OPTIMIZER, LAMB_OPTIMIZER, LION_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                        ONEBIT_LAMB_OPTIMIZER, SGD_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER)
from .fp16.loss_scaler import DynamicLossScaler, LossScalerState, create_loss_scaler, found_inf_or_nan
from .lr_schedules import LRSchedulerShim, get_lr_schedule
from .zero.partition import grad_shardings as make_grad_shardings
from .zero.partition import master_and_optstate_shardings

OPTIMIZER_FACTORIES = {
    ADAM_OPTIMIZER: adam,
    ADAMW_OPTIMIZER: adamw,
    FUSED_ADAM_OPTIMIZER: fused_adam,
    "cpuadam": fused_adam,  # offload handled by sharding/memory-kind, same math
    LAMB_OPTIMIZER: fused_lamb,
    FUSED_LAMB_OPTIMIZER: fused_lamb,
    LION_OPTIMIZER: fused_lion,
    ADAGRAD_OPTIMIZER: adagrad,
    SGD_OPTIMIZER: sgd,
    ONEBIT_ADAM_OPTIMIZER: onebit_adam,
    ONEBIT_LAMB_OPTIMIZER: onebit_lamb,
    ZERO_ONE_ADAM_OPTIMIZER: zero_one_adam,
}


class TrainState(NamedTuple):
    """Everything the compiled step reads+writes.  ``master`` is the fp32
    copy (ref: runtime/bf16_optimizer.py fp32 groups); when training in fp32
    it is aliased conceptually to params (stored once, params is the master).
    """
    step: jnp.ndarray
    params: Any  # compute dtype
    master: Any  # fp32 master (or () when compute dtype is fp32)
    opt_state: Any
    scaler: LossScalerState
    skipped_steps: jnp.ndarray


class StepMetrics(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    found_inf: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray


def _default_model_inputs(batch):
    kw = {}
    for k in ("positions", "segment_ids"):
        if k in batch:
            kw[k] = batch[k]
    return (batch["input_ids"], ), kw


def _default_loss_fn(outputs, batch):
    from ..models.llama import causal_lm_loss
    if "labels" not in batch:
        raise KeyError("batch must contain 'labels' for the default causal-LM loss; "
                       "pass loss_fn= to initialize() for custom losses")
    return causal_lm_loss(outputs, batch["labels"], batch.get("loss_mask"))


class DeepSpeedEngine:

    def __init__(self,
                 model,
                 config: DeepSpeedConfig,
                 optimizer=None,
                 lr_scheduler=None,
                 loss_fn: Optional[Callable] = None,
                 model_inputs_fn: Optional[Callable] = None,
                 mesh=None,
                 params=None,
                 init_rng=None,
                 dont_change_device=False):
        self.module = model
        self._config = config
        self.loss_fn = loss_fn or _default_loss_fn
        self.model_inputs_fn = model_inputs_fn or _default_model_inputs
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.init_rng = init_rng if init_rng is not None else jax.random.PRNGKey(0)

        # ---- mesh (ref: groups.py group creation + initialize_mesh_device)
        if mesh is None:
            spec = MeshSpec(pipe=config.pipeline.stages,
                            data=-1,
                            expert=config.moe.expert_parallel_size,
                            seq=config.sequence_parallel_size,
                            tensor=config.tensor_parallel_config.autotp_size)
            mesh = create_mesh(spec)
        self.mesh = mesh
        set_global_mesh(mesh)

        self.compute_dtype = config.precision_dtype
        self.zero_stage = config.zero_optimization_stage
        self.gas = config.gradient_accumulation_steps

        # ---- loss scaling (ref: runtime/fp16/loss_scaler.py)
        self.loss_scaler = create_loss_scaler(config.fp16_config, self.compute_dtype)

        # ---- optimizer transform + lr schedule
        self.lr_base, self._base_lr_schedule = self._build_lr_schedule()
        # variable-batch LR scaling (ref: data_sampling/variable_batch_size_
        # and_lr.py scale_lr): _lr_scale is a python float read at TRACE time
        # — each batch-size bucket compiles its own step with its own scale
        # (the jit cache is keyed on it via _ensure_ready)
        self._lr_scale = 1.0
        self._vblr = None  # (ref_batch_size, method) when enabled
        self.lr_schedule = lambda step: self._base_lr_schedule(step) * self._lr_scale
        self.opt = self._build_optimizer_transform()
        if lr_scheduler is None or callable(lr_scheduler) and not hasattr(lr_scheduler, "step"):
            self.lr_scheduler = LRSchedulerShim(self.lr_schedule)
        else:
            self.lr_scheduler = lr_scheduler

        # ---- timers/monitor (ref: engine.py:154 EngineTimers, monitor hookup)
        self.timers = SynchronizedWallClockTimer() if config.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(batch_size=config.train_batch_size,
                                          steps_per_output=config.steps_per_print)
        self.monitor = self._build_monitor()

        # ---- flops profiler (ref: engine.py:300-304 construction,
        # :2411-2424 step trigger)
        self.flops_profiler = None
        if config.flops_profiler_config.enabled:
            from ..profiling.flops_profiler import FlopsProfiler
            self.flops_profiler = FlopsProfiler(model=self.module, ds_engine=self,
                                                recompute_fwd_factor=config.flops_profiler_config.recompute_fwd_factor)

        # ---- telemetry (deepspeed_tpu/telemetry, docs/OBSERVABILITY.md):
        # per-step traces (engine/step -> fwd_bwd/optim, plus the streamed
        # optimizer's upload/compute/download child spans) and a metrics
        # registry; disabled (null, allocation-free) until set_telemetry()
        from ..telemetry.trace import NULL_TRACER
        self.tracer = NULL_TRACER
        self.metrics_registry = None

        # ---- compression-aware training (ref: compression/compress.py
        # init_compression; applied as a param transform inside the loss)
        self._compression_fn = None
        self._compression_requested = bool(config._param_dict.get("compression_training"))

        # ---- progressive layer drop (ref: engine.py progressive_layer_drop
        # config + runtime/progressive_layer_drop.py)
        self.progressive_layer_drop = None
        pld_cfg = config._param_dict.get("progressive_layer_drop", {})
        if pld_cfg.get("enabled", False):
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(theta=pld_cfg.get("theta", 0.5),
                                                               gamma=pld_cfg.get("gamma", 0.001))

        # ---- state (lazy until first batch unless params given)
        self.state: Optional[TrainState] = None
        self.state_shardings = None
        self._grad_shardings = None
        self._train_step_fn = None
        self._eval_fn = None
        self._accum_fn = None
        self._apply_fn = None
        self._pending_grads = None
        self._pending_loss = None
        self._micro_step_count = 0
        self.global_steps = 0
        self.global_samples = 0
        if params is not None:
            self._materialize_state(params=params)

        log_dist(f"DeepSpeedEngine: mesh={dict(self.mesh.shape)} zero_stage={self.zero_stage} "
                 f"dtype={self.compute_dtype.__name__} gas={self.gas}", ranks=[0])

    # ------------------------------------------------------------------ build

    def _build_lr_schedule(self):
        base_lr = 1e-3
        if self._config.optimizer_config is not None:
            base_lr = self._config.optimizer_config.params.get("lr", 1e-3)
        if self.client_lr_scheduler is not None and callable(self.client_lr_scheduler):
            return base_lr, self.client_lr_scheduler
        if self._config.scheduler_config is not None and self._config.scheduler_config.type:
            fn = get_lr_schedule(self._config.scheduler_config.type, self._config.scheduler_config.params, base_lr)
            return base_lr, fn
        return base_lr, (lambda step: jnp.asarray(base_lr, jnp.float32))

    def _build_optimizer_transform(self):
        if self.client_optimizer is not None:
            opt = self.client_optimizer
            if hasattr(opt, "init") and hasattr(opt, "update"):
                return opt
            raise TypeError("client optimizer must be an optax-style GradientTransformation")
        cfg = self._config.optimizer_config
        if cfg is None or cfg.type is None:
            return self._maybe_loco_wrap(adamw(lr=self.lr_schedule))
        name = cfg.type.lower()
        if name not in OPTIMIZER_FACTORIES:
            raise ValueError(f"Unknown optimizer {cfg.type}; known: {sorted(OPTIMIZER_FACTORIES)}")
        params = dict(cfg.params)
        params.pop("lr", None)
        params.pop("torch_adam", None)
        # 1-bit family: "comm_backend_name" (ref: runtime/fp16/onebit/adam.py
        # comm_backend_name nccl/mpi/compressed) routes the momentum exchange
        # through the REAL compressed wire (runtime/comm/compressed.py) inside
        # a shard_map training step — see _build_compressed_train_step
        backend = params.pop("comm_backend_name", None)
        if name in (ONEBIT_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER):
            self._onebit_comm_backend = backend
            if name == ZERO_ONE_ADAM_OPTIMIZER:
                # 0/1 Adam has NO warmup — the momentum rides the compressed
                # wire from step 0 (ref: zoadam.py), and on var-interval
                # steps exp_avg_sq updates from the UNCOMPRESSED allreduced
                # grad like the reference (var_allreduce_fn below, cond-gated
                # to the rare due steps; see ops/onebit.zero_one_adam)
                self._onebit_freeze_step = 0
            else:
                self._onebit_freeze_step = int(params.get("freeze_step", 100))
            if self._compressed_transport_active():
                from .comm.compressed import compressed_allreduce
                from ..comm.mesh import DATA_AXIS

                def exchange(tensor, error):
                    avg, e_new = compressed_allreduce(tensor, error, DATA_AXIS)
                    # single-stage error feedback on the AVERAGED tensor:
                    # pmean(local - compressed) == global momentum minus the
                    # transmitted average — the server-side EF of the
                    # reference's two-stage scheme (nccl.py:16 steps 3-4);
                    # keeping per-worker error would make the opt state
                    # worker-varying, which the replicated TrainState can't
                    # represent
                    return avg, jax.lax.pmean(e_new, DATA_AXIS)

                params["compress_fn"] = exchange
                if name == ZERO_ONE_ADAM_OPTIMIZER:
                    # reference variance source (zoadam.py): var-due steps
                    # exchange the raw fp32 grad; lax.cond in the optimizer
                    # keeps it off the wire on every other step
                    params["var_allreduce_fn"] = \
                        lambda g: jax.lax.pmean(g, DATA_AXIS)
                # warmup-phase twin WITHOUT the exchange: its compressed
                # result is discarded anyway (frozen=False selects the exact
                # momentum), so tracing the collectives into the warmup
                # program would be pure wasted wire every pre-freeze step
                self._opt_warmup = OPTIMIZER_FACTORIES[name](
                    lr=self.lr_schedule, **{k: v for k, v in params.items()
                                            if k not in ("compress_fn",
                                                         "var_allreduce_fn")})
        if name in (ADAM_OPTIMIZER, FUSED_ADAM_OPTIMIZER, "cpuadam"):
            # the reference's adam_w_mode flag (ops/adam/fused_adam.py)
            adam_w = params.pop("adam_w_mode", True)
            opt = fused_adam(lr=self.lr_schedule, adam_w_mode=adam_w, **params)
        else:
            opt = OPTIMIZER_FACTORIES[name](lr=self.lr_schedule, **params)
        return self._maybe_loco_wrap(opt)

    def _maybe_loco_wrap(self, opt):
        """ZeRO++ LoCo (``zeropp_loco_param`` + ``zero_quantized_gradients``):
        the qgZ gradient wire WITH error feedback — the previous round's
        quantization error folds back into the gradient before quantizing
        (ref: runtime/comm/coalesced_collectives.py:81
        all_to_all_loco_quant_reduce; config key zero/config.py:315).

        Implemented as a state-carrying GradientTransformation so the error
        tree rides opt_state (sharded/checkpointed like any moment); the
        update runs INSIDE the manual-DDP shard_map step.  The error is
        server-side (pmean'd) — replicated state cannot hold per-worker
        residuals."""
        loco_cfg = getattr(self._config.zero_config, "zeropp_loco_param", None)
        qgz_flag = getattr(self._config.zero_config, "zero_quantized_gradients", False)
        # the 1-bit transport owns the wire (and its unwrapped warmup twin
        # could not carry the (inner, err) state) — LoCo stands down
        self._loco_active = bool(loco_cfg is not None and qgz_flag
                                 and not getattr(self, "_onebit_comm_backend", None)
                                 and self._manual_ddp_eligible())
        if not self._loco_active:
            if loco_cfg is not None:
                logger.warning("zeropp_loco_param set but LoCo transport needs "
                               "zero_quantized_gradients plus the manual-DDP "
                               "requirements (pure-DP mesh, stage 0, gas=1, "
                               "non-fp16) — ignored")
            return opt

        from ..comm.mesh import DATA_AXIS
        from ..ops.optimizer import GradientTransformation, tree_zeros_like
        from .comm.compressed import padded_quant_allreduce
        beta = float((loco_cfg or {}).get("err_beta", 0.8))
        world = self.mesh.shape[DATA_AXIS]
        clip = self._config.gradient_clipping

        def red(g, e):
            full, new_err = padded_quant_allreduce(g, DATA_AXIS, world, error=e,
                                                   err_beta=beta)
            return full, jax.lax.pmean(new_err, DATA_AXIS)

        def init(params):
            return (opt.init(params), tree_zeros_like(params, jnp.float32))

        def update(grads, state, params=None):
            inner, err = state
            pairs = jax.tree.map(red, grads, err)
            reduced = jax.tree.map(lambda t: t[0], pairs,
                                   is_leaf=lambda x: isinstance(x, tuple))
            new_err = jax.tree.map(lambda t: t[1], pairs,
                                   is_leaf=lambda x: isinstance(x, tuple))
            if clip and clip > 0:
                # clipping belongs to the REDUCED gradient — the engine's
                # pre-update clip is skipped in loco mode (its local-grad
                # norm would over-clip by up to sqrt(world) on noisy grads)
                norm = opt_lib.global_norm(reduced)
                cs = jnp.minimum(1.0, clip / (norm + 1e-6))
                reduced = jax.tree.map(lambda g: g * cs, reduced)
            updates, new_inner = opt.update(reduced, inner, params)
            return updates, (new_inner, new_err)

        log_dist(f"ZeRO++ LoCo gradient transport active (err_beta={beta})", ranks=[0])
        return GradientTransformation(init, update)

    def _streamed_offload_ok(self, what: str) -> bool:
        """Shared eligibility for the DISPATCH-streamed offload tiers
        (NVMe swap / host grouped): single-device mesh, Adam-family
        optimizer, non-fp16 static-unity scaling — the per-group update
        orchestration owns the step; the sharded multi-chip answer is ZeRO."""
        from .fp16.loss_scaler import StaticLossScaler
        name = (self._config.optimizer_config.type or "").lower() \
            if self._config.optimizer_config else "adamw"
        ok = (self.mesh.size == 1
              and name in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM_OPTIMIZER, "cpuadam")
              and isinstance(self.loss_scaler, StaticLossScaler)
              and float(self.loss_scaler.init_scale) == 1.0
              and self.compute_dtype != jnp.float16)
        if not ok:
            logger.warning(f"offload_optimizer {what}: the streamed update needs a "
                           "single-device mesh, Adam-family optimizer and non-fp16 "
                           "static-unity scaling — falling back to host memory-kind "
                           "offload")
        return ok

    def _nvme_pipelined_active(self) -> bool:
        """True when optimizer states should live on NVMe with the pipelined
        double-buffered swap (ref: swap_tensor/pipelined_optimizer_swapper.py):
        offload_optimizer device=nvme + nvme_path."""
        off = self._config.zero_config.offload_optimizer
        if off is None or str(getattr(off, "device", "")) != "nvme" \
                or not getattr(off, "nvme_path", None):
            return False
        return self._streamed_offload_ok("device=nvme")

    def _host_streamed_active(self) -> bool:
        """True when optimizer states should live in TPU-host pinned memory
        with the GROUPED multi-dispatch update (swap_tensor/
        host_streamed_optimizer.py).  Selected by device=cpu +
        pipeline_read/pipeline_write (the reference's pipelined-offload
        knobs, ref: runtime/zero/offload_config.py:78) — the plain
        device=cpu path keeps the single-program compute_on update, whose
        HBM staging XLA does not bound (docs/PERF.md r4 receipts)."""
        off = self._config.zero_config.offload_optimizer
        if off is None or str(getattr(off, "device", "")) != "cpu" \
                or not (getattr(off, "pipeline_read", False)
                        or getattr(off, "pipeline_write", False)):
            return False
        return self._streamed_offload_ok("device=cpu pipelined")

    def _compressed_transport_active(self) -> bool:
        """True when the 1-bit momentum exchange should ride the compressed
        wire: a comm backend was requested, there is a >1 data axis to
        exchange over, and the state layout is the replicated one the
        manual-collective step requires (ref constraint: the 1-bit
        optimizers require ZeRO stage <= 1; here stage 0 + gas 1)."""
        if getattr(self, "_onebit_comm_backend", None) is None:
            return False
        ok = self._manual_ddp_eligible()
        if not ok:
            logger.warning(
                "onebit comm_backend_name set but compressed transport needs a pure-DP "
                "mesh (>1 'data' axis, all others 1 — the manual step reduces over "
                "'data' only), zero stage 0, gas=1 and non-fp16 compute — falling "
                "back to local compression numerics (no wire exchange)")
            self._onebit_comm_backend = None
        return ok

    def _build_monitor(self):
        try:
            from ..monitor.monitor import MonitorMaster
            monitor = MonitorMaster(self._config.monitor_config)
            if monitor.enabled:
                # resilience/* events (injected faults, retries, checkpoint
                # fallbacks, watchdog trips) ride the same writer surface
                from ..resilience import events as res_events
                res_events.attach_monitor(monitor)
            return monitor
        except Exception as e:  # monitor must never break training
            logger.debug(f"monitor disabled: {e}")
            return None

    # ---------------------------------------------------------- state init

    def _materialize_state(self, batch=None, params=None, abstract=False):
        """Create the sharded TrainState.

        Params are initialised directly into their partitioned layout
        (jit with out_shardings) — the analog of ``zero.Init``'s
        partition-at-construction (ref: runtime/zero/partition_parameters.py:825):
        no device ever holds the unsharded model.

        ``abstract=True`` builds only shapes + shardings (ShapeDtypeStructs,
        nothing allocated) — the AOT compile-only path behind
        ``compile_aot`` for memory-budget analysis of models far larger
        than the local host could hold.
        """
        from flax import linen as nn

        from ..module_inject.tp_rules import param_shardings as make_param_shardings
        from .zero.mics import resolve_partition_axes

        # MiCS / ZeRO++ hpZ: restrict which DP mesh axes the ZeRO partition
        # uses (ref: runtime/zero/mics.py, partition_parameters.py hpZ)
        param_axes, state_axes = resolve_partition_axes(self.mesh, self._config.zero_config, self.zero_stage)

        if params is None:
            args, kwargs = self.model_inputs_fn(batch)
            abs_args, abs_kwargs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype), (args, kwargs))

            def boxed_init(rng):
                return self.module.init(rng, *abs_args, **abs_kwargs)

            abs_boxed = jax.eval_shape(boxed_init, self.init_rng)
            var_shardings = make_param_shardings(abs_boxed, self.mesh, self.zero_stage, fsdp_axes=param_axes)

            def unboxed_init(rng):
                return nn.meta.unbox(boxed_init(rng))

            if abstract:
                variables = nn.meta.unbox(abs_boxed)
            else:
                with self.mesh:
                    variables = jax.jit(unboxed_init, out_shardings=var_shardings)(self.init_rng)
        else:
            variables = params if isinstance(params, dict) and "params" in params else {"params": params}
            variables = nn.meta.unbox(variables)
            abs_vars = jax.eval_shape(lambda: variables)
            var_shardings = make_param_shardings(abs_vars, self.mesh, self.zero_stage, fsdp_axes=param_axes)
            variables = jax.device_put(variables, var_shardings)

        raw_params = variables["params"]
        param_sh = var_shardings["params"]

        # cast params to compute dtype; master keeps fp32
        use_master = self.compute_dtype != jnp.float32
        cast = partial(jax.tree.map, lambda x: x.astype(self.compute_dtype)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x)

        abs_params = jax.eval_shape(lambda: raw_params)
        master_sh = master_and_optstate_shardings(param_sh, abs_params, self.mesh, self.zero_stage,
                                                  zero_axes=state_axes)
        self._grad_shardings = make_grad_shardings(param_sh, abs_params, self.mesh, self.zero_stage,
                                                   zero_axes=state_axes)

        nvme_pipe_early = self._nvme_pipelined_active()
        host_stream_early = self._host_streamed_active() and not nvme_pipe_early

        @partial(jax.jit, out_shardings=None)
        def build_state(p):
            if nvme_pipe_early or host_stream_early:
                # dispatch-streamed offload: master + moments live on DISK
                # (PipelinedNVMeOptimizer) or in host pinned memory
                # (HostStreamedOptimizer); the device state is params-only
                master, opt_state = (), ()
            else:
                master = jax.tree.map(lambda x: x.astype(jnp.float32), p) if use_master else ()
                opt_state = self.opt.init(master if use_master else p)
            return TrainState(step=jnp.zeros((), jnp.int32),
                              params=cast(p),
                              master=master,
                              opt_state=opt_state,
                              scaler=self.loss_scaler.init_state(),
                              skipped_steps=jnp.zeros((), jnp.int32))

        # compute output shardings for the state
        abs_state = jax.eval_shape(build_state, abs_params)
        opt_sh = self._optstate_shardings(abs_state.opt_state, param_sh, master_sh)
        repl = NamedSharding(self.mesh, P())
        # offload_optimizer device=cpu → optimizer/master live in host memory
        # (memory_kind pinned_host); XLA streams them through the update
        # (ref: runtime/zero/offload_config.py + cpu_adam — same math, the
        # host residency is a sharding property, not a different optimizer)
        host_kind_ok = [None]  # probe result shared by both offload blocks

        def try_host_offload(name, *sharding_trees):
            """Move shardings to host memory kind if the backend supports it
            (one probe-compile, cached); returns the trees (possibly unchanged)."""
            to_host = lambda s: s.with_memory_kind("pinned_host") \
                if isinstance(s, NamedSharding) else s
            if host_kind_ok[0] is None:
                try:
                    probe = NamedSharding(self.mesh, P())  # rank-agnostic probe
                    jax.jit(lambda x: x, out_shardings=to_host(probe)) \
                        .lower(jax.ShapeDtypeStruct((1, ), jnp.float32)).compile()
                    host_kind_ok[0] = True
                except Exception as e:
                    host_kind_ok[0] = False
                    logger.warning(f"host memory kinds unsupported on this backend; "
                                   f"offload stays on device ({e})")
            if not host_kind_ok[0]:
                return sharding_trees
            out = tuple(jax.tree.map(to_host, t) for t in sharding_trees)
            log_dist(f"{name}: resident in host memory (streamed through HBM)", ranks=[0])
            return out

        offload = self._config.zero_config.offload_optimizer
        nvme_pipe = nvme_pipe_early  # computed once above (warns on fallback)
        streamed = nvme_pipe or host_stream_early
        if offload is not None and offload.device in ("cpu", "nvme") and not streamed:
            if use_master:
                master_sh, opt_sh = try_host_offload("offload_optimizer", master_sh, opt_sh)
            else:
                (opt_sh, ) = try_host_offload("offload_optimizer", opt_sh)
        # offload_param (ZeRO-Infinity): compute-dtype params themselves live
        # in host memory and stream through HBM per use — with scan-over-
        # layers XLA prefetches one layer's slab at a time (the analog of the
        # reference's AsyncPartitionedParameterSwapper double buffering,
        # ref: runtime/zero/partition_parameters.py remote_device="cpu")
        p_offload = self._config.zero_config.offload_param
        if p_offload is not None and getattr(p_offload, "device", None) in ("cpu", "nvme"):
            (param_sh, ) = try_host_offload("offload_param", param_sh)
        self.state_shardings = TrainState(
            step=repl,
            params=param_sh,
            master=master_sh if use_master and not streamed else (),
            opt_state=opt_sh,
            scaler=jax.tree.map(lambda _: repl, abs_state.scaler),
            skipped_steps=repl,
        )
        if abstract:
            # shape+sharding skeleton only: leaves are ShapeDtypeStructs
            # carrying their NamedSharding — exactly what jit.lower accepts
            self.state = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
                if isinstance(s, NamedSharding) else a, abs_state, self.state_shardings)
        else:
            with self.mesh:
                self.state = jax.jit(build_state, out_shardings=self.state_shardings)(raw_params)
        if nvme_pipe and not abstract and getattr(self, "_nvme_opt", None) is None:
            from .swap_tensor.pipelined_optimizer_swapper import PipelinedNVMeOptimizer
            self._nvme_opt = PipelinedNVMeOptimizer(
                self.opt, jax.tree.leaves(self.state.params),
                self._config.zero_config.offload_optimizer.nvme_path,
                compute_dtype=self.compute_dtype)
        elif host_stream_early and not abstract and getattr(self, "_nvme_opt", None) is None:
            # same orchestration (_nvme_train_step), host-memory storage tier;
            # buffer_count sizes the partition exactly as it does for the
            # NVMe tier (ref: offload_config.py buffer_count) — more groups
            # = smaller HBM staging per dispatch
            from .swap_tensor.host_streamed_optimizer import HostStreamedOptimizer
            self._nvme_opt = HostStreamedOptimizer(
                self.opt, jax.tree.leaves(self.state.params),
                n_groups=max(1, self._config.zero_config.offload_optimizer.buffer_count),
                compute_dtype=self.compute_dtype, mesh=self.mesh)
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(abs_params))
        log_dist(f"Initialized TrainState: {n_params/1e6:.1f}M params, zero_stage={self.zero_stage}"
                 f"{' (abstract)' if abstract else ''}", ranks=[0])

    def _optstate_shardings(self, abs_opt_state, param_sh, master_sh):
        """Match each per-param moment tree inside opt_state to the master
        sharding; scalars replicated."""
        repl = NamedSharding(self.mesh, P())
        param_leaves = jax.tree.structure(master_sh if master_sh != () else param_sh)

        def assign(subtree):
            # if subtree matches the param tree structure, use master shardings
            # — but only for leaves whose rank fits the spec (e.g. OnebitLamb
            # keeps per-param SCALAR trust ratios in a param-shaped tree)
            try:
                if jax.tree.structure(subtree) == param_leaves:
                    sh_tree = master_sh if master_sh != () else param_sh

                    def fit(aval, sh):
                        ok = isinstance(sh, NamedSharding) and \
                            getattr(aval, "ndim", 0) >= len(sh.spec)
                        return sh if ok else repl

                    return jax.tree.map(fit, subtree, sh_tree)
            except Exception:
                pass
            return None

        def walk(node):
            matched = assign(node)
            if matched is not None:
                return matched
            if hasattr(node, "_fields"):  # NamedTuple
                return type(node)(*[walk(getattr(node, f)) for f in node._fields])
            if isinstance(node, tuple):
                return tuple(walk(x) for x in node)
            if isinstance(node, list):
                return [walk(x) for x in node]
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            return repl

        return walk(abs_opt_state)

    # ---------------------------------------------------------- step builder

    def _batch_sharding_tree(self, batch):
        seq_ax = SEQ_AXIS if self.mesh.shape.get(SEQ_AXIS, 1) > 1 else None

        def one(x):
            nd = np.ndim(x)
            if nd == 0:
                return NamedSharding(self.mesh, P())
            spec = [BATCH_AXES] + ([seq_ax] if nd > 1 else []) + [None] * (nd - 2)
            return NamedSharding(self.mesh, P(*spec))

        return jax.tree.map(one, batch)

    def _microbatch_loss(self, params, mb, step=None, training=False):
        if self._compression_fn is not None and step is not None:
            params = self._compression_fn(params, step)
        args, kwargs = self.model_inputs_fn(mb)
        if training and step is not None and self.progressive_layer_drop is not None \
                and getattr(self.module, "supports_pld", False):
            # traced PLD schedule: theta(t) = (1-p)·e^{-γt} + p, per-layer
            # keep mask drawn from a step-derived key (ref:
            # runtime/progressive_layer_drop.py; one compiled program, the
            # schedule advances via the step input)
            from .progressive_layer_drop import pld_layer_mask
            pld = self.progressive_layer_drop
            theta = (1.0 - pld.theta) * jnp.exp(-pld.gamma * step.astype(jnp.float32)) + pld.theta
            rng = jax.random.fold_in(jax.random.PRNGKey(17), step)
            mask, inv = pld_layer_mask(rng, self.module.cfg.num_hidden_layers, theta)
            kwargs["pld_scale"] = mask * inv
        # TRUE-1F1B pipeline modules compute the loss INSIDE the schedule
        # (post-stack per microbatch, interleaved backward); the engine's
        # jax.grad then consumes the custom-VJP grads
        if getattr(self.module, "schedule", None) == "1f1b":
            if kwargs:
                from .pipe.module import PipelineError
                raise PipelineError(
                    f"PipelineModule does not accept keyword model inputs {sorted(kwargs)} "
                    "(same contract as the gpipe schedule)")
            return self.module.apply_loss_1f1b({"params": params}, self.loss_fn, mb, *args)
        outputs = self.module.apply({"params": params}, *args, **kwargs)
        return self.loss_fn(outputs, mb)

    def enable_compression(self):
        """Build the compression transform from config (ref:
        compression/compress.py:100 init_compression)."""
        self._compression_requested = True
        self._step_key = None  # force step rebuild
        self._step_cache = {}  # cached programs were traced without the transform
        if self.state is not None:
            self._build_compression()

    def _build_compression(self):
        from ..compression.compress import build_compression_fn
        comp_dict = self._config._param_dict.get("compression_training", {})
        abs_params = jax.eval_shape(lambda: self.state.params)
        self._compression_fn = build_compression_fn(comp_dict, abs_params)

    def _grads_for_batch(self, state, batch):
        """Accumulated (summed) scaled grads + mean loss over the GAS axis.

        Gradient accumulation = lax.scan over microbatches (ref: the
        micro-step loop around engine.backward, engine.py:2204), computed in
        grad_accum_dtype fp32 (ref: runtime/config.py data_types).
        """
        params = state.params
        scale = state.scaler.cur_scale

        def scaled_loss(p, mb):
            loss = self._microbatch_loss(p, mb, step=state.step, training=True)
            return (loss * scale).astype(jnp.float32), loss

        grad_fn = jax.grad(scaled_loss, has_aux=True)

        if self.gas == 1:
            grads, loss = grad_fn(params, batch)
            return grads, loss

        def reshape_gas(x):
            if np.ndim(x) == 0:
                return x
            b = x.shape[0]
            return x.reshape((self.gas, b // self.gas) + x.shape[1:])

        batch_g = jax.tree.map(reshape_gas, batch)

        def body(carry, mb):
            acc, loss_acc = carry
            g, loss = grad_fn(params, mb)
            acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32), acc, g)
            return (acc, loss_acc + loss), None

        zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = jax.lax.scan(body, (zero_grads, jnp.zeros((), jnp.float32)),
                                            batch_g, length=self.gas)
        return grads, loss_sum / self.gas

    def _apply_grads(self, state: TrainState, grads, loss):
        """Unscale, constrain sharding, clip, update, recast — with on-device
        overflow skip (ref: stage3.py:2082 step + loss-scaler adjust).

        bf16/fp32 fast path: with a static unity scaler there is nothing to
        unscale and no overflow-skip (ref: bf16_optimizer.py has no scaler),
        so the finite-check reduction and the 3× whole-tree ``where`` passes
        are elided from the compiled step entirely.
        """
        cfg = self._config
        from .fp16.loss_scaler import StaticLossScaler
        # fp16 is excluded from the fast path even at loss_scale=1: non-finite
        # grads are real in half precision and the step must still be skipped
        # on overflow (ref: fused_optimizer.py keeps the overflow check for
        # static scales)
        static_unity = isinstance(self.loss_scaler, StaticLossScaler) and \
            float(self.loss_scaler.init_scale) == 1.0 and \
            self.compute_dtype != jnp.float16
        inv = (1.0 / self.gas) if static_unity else 1.0 / (state.scaler.cur_scale * self.gas)
        if cfg.gradient_predivide_factor != 1.0:
            inv = inv / cfg.gradient_predivide_factor

        use_master = self.compute_dtype != jnp.float32
        from ..ops.adam import AdamState
        # use_master required: the fp32-compute variant would feed
        # device-resident params into the host-compute region
        stream_offload = (static_unity and use_master and self._host_offloaded_opt()
                          and isinstance(state.opt_state, AdamState))
        if stream_offload:
            # leaf-streamed path: never materialize the fp32 grad tree — the
            # norm reduces each leaf with an f32 accumulator (XLA fuses the
            # cast into the reduction) and the cast happens per leaf inside
            # the sequenced update
            norm2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32) * inv))
                        for g in jax.tree.leaves(grads))
            grad_norm = jnp.sqrt(norm2)
            found_inf = jnp.asarray(False)
            clip_scale = jnp.asarray(1.0, jnp.float32)
            if cfg.gradient_clipping and cfg.gradient_clipping > 0:
                clip_scale = jnp.minimum(1.0, cfg.gradient_clipping / (grad_norm + 1e-6))
            new_params, new_master, new_opt_state = self._offload_streamed_update(
                grads, state, inv, clip_scale)
        else:
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
            from ..comm.mesh import DATA_AXIS, in_manual_mesh
            manual = in_manual_mesh()
            if not manual:  # inside shard_map (compressed transport path)
                # the grads are per-device values; GSPMD constraints don't
                # apply
                grads = jax.lax.with_sharding_constraint(grads, self._grad_shardings)

            found_inf = jnp.asarray(False) if static_unity else found_inf_or_nan(grads)
            grad_norm = opt_lib.global_norm(grads)
            if manual:
                # per-device grads: reduce so every worker clips with the
                # same scale and the metrics are well-defined under the
                # replicated out-spec
                grad_norm = jnp.sqrt(jax.lax.pmean(jnp.square(grad_norm), DATA_AXIS))
                if not static_unity:
                    found_inf = jax.lax.pmax(found_inf.astype(jnp.int32),
                                             DATA_AXIS).astype(jnp.bool_)
            if cfg.gradient_clipping and cfg.gradient_clipping > 0 \
                    and not (manual and getattr(self, "_loco_active", False)):
                # LoCo clips inside its optimizer wrapper on the REDUCED
                # grads; clipping the local grads here against the (noise-
                # inflated) local norm would over-clip
                clip_scale = jnp.minimum(1.0, cfg.gradient_clipping / (grad_norm + 1e-6))
                grads = jax.tree.map(lambda g: g * clip_scale, grads)

            master = state.master if use_master else state.params
            # host-offloaded (pinned_host) states: memory-space typing
            # requires the update's compute operands in device space —
            # explicit transfers in; out_shardings stream the results back
            master = self._from_host(master,
                                     self.state_shardings.master if use_master
                                     else self.state_shardings.params)
            opt_in = self._from_host(state.opt_state, self.state_shardings.opt_state)
            updates, new_opt_state = self.opt.update(grads, opt_in, master)
            new_master = opt_lib.apply_updates(master, updates)

            if not static_unity:
                # skip the update entirely on overflow (ref: fused_optimizer.py)
                def pick(new, old):
                    return jax.tree.map(lambda n, o: jnp.where(found_inf, o, n), new, old)

                new_master = pick(new_master, master)
                # compare against the device-pulled opt_in, not the (possibly
                # pinned_host) state.opt_state — mixing memory spaces in the
                # where() fails to lower (advisor r4)
                new_opt_state = pick(new_opt_state, opt_in)
            new_params = jax.tree.map(lambda m: m.astype(self.compute_dtype),
                                      new_master) if use_master else new_master
        new_scaler = self.loss_scaler.update(state.scaler, found_inf)
        lr_val = jnp.asarray(self.lr_schedule(state.step + 1), jnp.float32)

        new_state = TrainState(step=state.step + 1,
                               params=new_params,
                               master=new_master if use_master else (),
                               opt_state=new_opt_state,
                               scaler=new_scaler,
                               skipped_steps=state.skipped_steps + found_inf.astype(jnp.int32))
        metrics = StepMetrics(loss=loss.astype(jnp.float32),
                              grad_norm=grad_norm,
                              found_inf=found_inf,
                              lr=lr_val,
                              loss_scale=state.scaler.cur_scale)
        return new_state, metrics

    def _host_offloaded_opt(self):
        """True when master/optimizer shardings live in pinned_host."""
        sh = (self.state_shardings.master, self.state_shardings.opt_state)
        return any(isinstance(s, NamedSharding) and s.memory_kind == "pinned_host"
                   for s in jax.tree.leaves(sh))

    def _offload_streamed_update(self, grads, state, inv, clip_scale):
        """CPU-Adam: the optimizer step executes as XLA HOST compute, on the
        TPU host where the offloaded fp32 master/moments live.

        Same division of labor as the reference (ref:
        csrc/adam/cpu_adam_impl.cpp + runtime/zero/stage_1_and_2.py CPU
        offload): device does fwd/bwd, the host applies Adam.  Grads cross
        to the host; fresh compute-dtype params cross back.  Verified on
        chip: loss parity with the on-device update to ~1e-3.

        Honest limits (measured): a device-side whole-tree update hoists
        every host→HBM pull to the program top (whole fp32 state on device
        at once); this host-execute path still stages its I/O buffers
        through HBM for layout conversion, so the single-chip capacity win
        over no-offload is partial — at true 7B+ scale the answer is ZeRO
        sharding across chips (see MEMBUDGET.json), not single-chip
        offload.
        """
        from jax.experimental.compute_on import compute_on

        use_master = self.compute_dtype != jnp.float32
        master = state.master if use_master else state.params
        opt_state = state.opt_state
        host = NamedSharding(self.mesh, P()).with_memory_kind("pinned_host")

        # grads keep their ZeRO sharding, only the memory kind changes — a
        # replicated host spec would all-gather every leaf into each host
        g_host = jax.tree.map(
            lambda g, s: jax.device_put(
                g, s.with_memory_kind("pinned_host") if isinstance(s, NamedSharding) else host),
            grads, self._grad_shardings)
        scal = jax.device_put(clip_scale * inv, host)
        with compute_on("device_host"):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32) * scal, g_host)
            updates, new_opt_state = self.opt.update(g32, opt_state, master)
            new_master = jax.tree.map(lambda m, u: m + u, master, updates)
            new_params_h = jax.tree.map(lambda m: m.astype(self.compute_dtype),
                                        new_master) if use_master else new_master
        param_sh = self.state_shardings.params
        new_params = jax.tree.map(
            lambda x, s: jax.device_put(x, s if isinstance(s, NamedSharding) else None),
            new_params_h, param_sh)
        return new_params, new_master, new_opt_state

    def _from_host(self, tree, sh_tree):
        """Pull host-offloaded (pinned_host) state into device space for the
        update (ZeRO-Infinity streaming: XLA schedules the transfers leaf by
        leaf, so only the leaves currently being updated occupy HBM)."""
        leaves = [s for s in jax.tree.leaves(sh_tree) if isinstance(s, NamedSharding)]
        if not any(s.memory_kind == "pinned_host" for s in leaves):
            return tree

        def pull(x, s):
            if isinstance(s, NamedSharding) and s.memory_kind == "pinned_host":
                return jax.device_put(x, s.with_memory_kind("device"))
            return x

        return jax.tree.map(pull, tree, sh_tree)

    def _manual_ddp_eligible(self) -> bool:
        """Shared eligibility for the manual-DDP compressed transports
        (1-bit momentum wire, qgZ gradient wire): a >1 pure-DP data axis,
        replicated state (stage 0), gas=1 and non-fp16 compute."""
        from ..comm.mesh import DATA_AXIS
        pure_dp = all(size == 1 for ax, size in self.mesh.shape.items() if ax != DATA_AXIS)
        return (self.mesh.shape.get(DATA_AXIS, 1) > 1 and pure_dp and self.zero_stage == 0
                and self.gas == 1 and self.compute_dtype != jnp.float16)

    def _qgz_active(self) -> bool:
        """ZeRO++ qgZ gradient transport (zero_quantized_gradients): the
        step's grad reduction rides int8 — quantized all-to-all
        reduce-scatter + quantized all-gather (ref:
        runtime/comm/coalesced_collectives.py:31).  Decision latched (and
        the fallback warned) ONCE — step-program rebuilds must not re-warn,
        and a 1-bit run with the flag also set must not claim the fp32
        wire is in use."""
        if getattr(self, "_qgz_decided", None) is None:
            if not getattr(self._config.zero_config, "zero_quantized_gradients", False) \
                    or getattr(self, "_onebit_comm_backend", None):
                self._qgz_decided = False
            else:
                self._qgz_decided = self._manual_ddp_eligible()
                if not self._qgz_decided:
                    logger.warning("zero_quantized_gradients needs a pure-DP mesh, zero "
                                   "stage 0, gas=1 and non-fp16 compute — gradients stay "
                                   "on the fp32 wire")
        return self._qgz_decided

    def _build_compressed_train_step(self, batch, warmup: bool):
        """Manual-DDP step with the grad/momentum exchange on the
        COMPRESSED wire (r3 verdict item 2: the pieces existed but no
        config path routed the training step through them).

        Per-device gradients are computed WITHOUT a GSPMD mean — each
        worker differentiates only its batch shard.  Two transports:

        * 1-bit family (comm_backend_name): the reference flow
          (fp16/onebit/adam.py — local momentum update, then
          compressed_allreduce of the momentum): n/8 sign bytes + one
          fp32 scale per tensor instead of 4n
          (ref: runtime/comm/nccl.py:16).
        * qgZ (zero_quantized_gradients): int8 quantized all-to-all
          reduce-scatter + quantized all-gather of the GRADS before a
          normal optimizer update
          (ref: runtime/comm/coalesced_collectives.py:31).
        """
        from ..comm.mesh import DATA_AXIS
        qgz = self._qgz_active()
        batch_sh = self._batch_sharding_tree(batch)
        repl = NamedSharding(self.mesh, P())
        metrics_sh = StepMetrics(*([repl] * 5))
        state_specs = jax.tree.map(lambda _: P(), self.state)
        batch_specs = jax.tree.map(lambda s: s.spec, batch_sh)
        metric_specs = StepMetrics(*([P()] * 5))

        opt_for_phase = self._opt_warmup if warmup else self.opt

        def sharded_step(state, b):
            scale = state.scaler.cur_scale

            def scaled_loss(p, mb):
                loss = self._microbatch_loss(p, mb, step=state.step, training=True)
                return (loss * scale).astype(jnp.float32), loss

            grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params, b)
            if qgz and not getattr(self, "_loco_active", False):
                # (LoCo reduces inside the optimizer update — the error
                # state rides opt_state)
                from .comm.compressed import padded_quant_allreduce
                world = self.mesh.shape[DATA_AXIS]
                grads = jax.tree.map(
                    lambda g: padded_quant_allreduce(g, DATA_AXIS, world), grads)
            elif warmup:
                # warmup stage: full-precision gradient allreduce, exactly
                # the reference backend pre-freeze (fp16/onebit/adam.py) —
                # without it worker params fork (local grads, no exchange
                # until the momentum compression kicks in)
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, DATA_AXIS), grads)
            loss = jax.lax.pmean(loss, DATA_AXIS)
            # phase-bound optimizer (tracing happens on the first call,
            # synchronously after this build — the swap is trace-local)
            prev, self.opt = self.opt, opt_for_phase
            try:
                return self._apply_grads(state, grads, loss)
            finally:
                self.opt = prev

        step_fn = jax.shard_map(sharded_step, mesh=self.mesh,
                                in_specs=(state_specs, batch_specs),
                                out_specs=(state_specs, metric_specs),
                                check_vma=False)

        def ds_train_step(state, b):   # the name the device trace's XLA Modules line shows
            return step_fn(state, b)

        self._train_step_fn = jax.jit(ds_train_step,
                                      in_shardings=(self.state_shardings, batch_sh),
                                      out_shardings=(self.state_shardings, metrics_sh),
                                      donate_argnums=(0, ))
        self._batch_shardings = batch_sh

        # wire accounting for CommsLogger, vs 4n fp32 transport:
        # 1-bit → signs (n/8) + one fp32 scale per tensor; qgZ → int8 both
        # directions (n + n/256 scale bytes each way)
        if qgz:
            # per direction: padded int8 payload + one fp32 scale per 256-block
            # (the padding to world*256 is real wire traffic)
            unit = self.mesh.shape[DATA_AXIS] * 256

            def leaf_bytes(n):
                padded = -(-n // unit) * unit
                return 2 * (padded + 4 * (padded // 256))

            self._compressed_wire_bytes = sum(
                leaf_bytes(int(np.prod(l.shape))) for l in jax.tree.leaves(self.state.params))
        else:
            self._compressed_wire_bytes = sum(
                (int(np.prod(l.shape)) + 7) // 8 + 4 for l in jax.tree.leaves(self.state.params))
        self._compressed_wire_name = "all_to_all_quant_reduce" if qgz else "compressed_allreduce"

        def unsupported(*a, **k):
            raise RuntimeError("the imperative forward/backward/step path does not support "
                               "compressed gradient/momentum transport; use train_batch()")

        self._accum_fn = unsupported
        self._apply_step_fn = unsupported

    def _build_nvme_train_step(self, batch):
        """Device program for the pipelined-NVMe mode: fwd/bwd only — grads,
        loss and the grad norm come OUT; the optimizer update runs per
        sub-group against disk-resident states (PipelinedNVMeOptimizer)."""
        batch_sh = self._batch_sharding_tree(batch)
        repl = NamedSharding(self.mesh, P())
        inv = 1.0 / self.gas
        if self._config.gradient_predivide_factor != 1.0:
            inv = inv / self._config.gradient_predivide_factor
        if getattr(self, "_nvme_opt", None) is not None:
            # lr/phase inputs are baked at trace time (e.g. variable-batch
            # _lr_scale rides self.lr_schedule): a step rebuild must retrace
            # the per-group update programs too
            self._nvme_opt._update_fns.clear()

        def ds_grad_step(state, b):
            grads, loss = self._grads_for_batch(state, b)
            norm2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32) * inv))
                        for g in jax.tree.leaves(grads))
            return grads, loss, jnp.sqrt(norm2)

        self._train_step_fn = jax.jit(ds_grad_step, in_shardings=(self.state_shardings, batch_sh))
        self._batch_shardings = batch_sh

        def unsupported(*a, **k):
            raise RuntimeError("the imperative forward/backward/step path does not support "
                               "pipelined NVMe optimizer offload; use train_batch()")

        self._accum_fn = unsupported
        self._apply_step_fn = unsupported

    def _nvme_train_step(self, batch):
        """Host-orchestrated step: device fwd/bwd (async), then the
        double-buffered per-group update.  Step N's tail write-backs drain
        while step N+1's fwd/bwd dispatches (the overlap the reference gets
        from its swap pipeline), and the FIRST groups' state uploads/reads
        are issued here, right after the fwd/bwd dispatch, so they ride the
        transfer engine (or aio threads) under the backward itself."""
        nv = self._nvme_opt
        nv.events.append(("step_entry_pending_writes", nv.pending_writes()))
        state = self.state
        step_span = getattr(self, "_step_span", None)
        with self.tracer.span("engine/fwd_bwd", parent=step_span, track="engine"):
            # span covers the DISPATCH; the async program keeps running —
            # the wait for grads shows up inside the optim span (bwd_wait)
            grads, loss, gnorm = self._train_step_fn(state, batch)
        # backward-phase prefetch: fwd/bwd is dispatched but (async) still
        # running — stage the first groups now instead of at step boundary
        mode = getattr(self, "_nvme_step_mode", None)
        if mode != "serialize":
            nv.prefetch(0)
            nv.prefetch(1)
        inv = 1.0 / self.gas
        cfg = self._config
        if cfg.gradient_predivide_factor != 1.0:
            inv = inv / cfg.gradient_predivide_factor
        scale = jnp.asarray(inv, jnp.float32)
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            scale = scale * jnp.minimum(1.0, cfg.gradient_clipping / (gnorm + 1e-6))
        self.timers(STEP_GLOBAL_TIMER).start()
        opt_span = self.tracer.start_span("engine/optim", parent=step_span,
                                          track="engine")
        if self.tracer.enabled:
            # clock-domain anchor: instrumentation timestamps are absolute
            # perf_counter; map them into the tracer's clock by offset
            from ..runtime.swap_tensor.overlap_instrumentation import now as _perf_now
            anchor_perf, anchor_trace = _perf_now(), self.tracer.now()
        try:
            new_leaves = nv.step(jax.tree.leaves(grads), jnp.asarray(self.global_steps, jnp.int32),
                                 scale, serialize=(mode == "serialize"),
                                 flush=(mode == "flush"))
        except Exception as e:
            # the failed steps are exactly the ones an operator reads the
            # trace for — tag and close instead of dropping the open span
            opt_span.set(error=f"{type(e).__name__}: {e}")
            raise
        finally:
            if self.tracer.enabled and getattr(nv, "instrumentation", None) is not None:
                # lift this step's upload/compute/download pipeline events
                # into real child spans of the optim span (paired
                # issue->done become spans; unpaired issues — async tails
                # left in flight — become span events on the optim span)
                nv.instrumentation.lift_spans(
                    self.tracer, opt_span, track="stream",
                    since_ts=anchor_perf, offset=anchor_trace - anchor_perf)
            self.tracer.end(opt_span)
        self.timers(STEP_GLOBAL_TIMER).stop()
        tdef = jax.tree.structure(state.params)
        new_state = state._replace(params=jax.tree.unflatten(tdef, new_leaves),
                                   step=state.step + 1)
        metrics = StepMetrics(loss=loss.astype(jnp.float32),
                              grad_norm=gnorm,
                              found_inf=jnp.asarray(False),
                              lr=jnp.asarray(self.lr_schedule(state.step + 1), jnp.float32),
                              loss_scale=jnp.asarray(1.0, jnp.float32))
        return new_state, metrics

    def _build_train_step(self, batch):
        if getattr(self, "_nvme_opt", None) is not None or \
                (getattr(self, "_abstract_state", False)
                 and (self._nvme_pipelined_active() or self._host_streamed_active())):
            # abstract (compile_aot) engines build the nvme grad-step program
            # too: the normal path would feed the () opt_state to opt.update
            return self._build_nvme_train_step(batch)
        if getattr(self, "_onebit_comm_backend", None):
            return self._build_compressed_train_step(
                batch, warmup=self.global_steps < self._onebit_freeze_step)
        if self._qgz_active():
            return self._build_compressed_train_step(batch, warmup=False)
        batch_sh = self._batch_sharding_tree(batch)
        repl = NamedSharding(self.mesh, P())

        # the step functions carry the names the device trace's ``XLA
        # Modules`` line shows: jit_ds_train_step, jit_ds_accum, jit_ds_apply
        def ds_train_step(state, b):
            grads, loss = self._grads_for_batch(state, b)
            return self._apply_grads(state, grads, loss)

        metrics_sh = StepMetrics(*([repl] * 5))
        self._train_step_fn = jax.jit(ds_train_step,
                                      in_shardings=(self.state_shardings, batch_sh),
                                      out_shardings=(self.state_shardings, metrics_sh),
                                      donate_argnums=(0, ))
        self._batch_shardings = batch_sh

        def ds_accum(state, b):
            # one micro-batch per call — NO gas re-split here: the imperative
            # forward/backward/step path calls backward() once per micro-batch
            # and step() divides the summed grads by gas
            scale = state.scaler.cur_scale

            def scaled_loss(p, mb):
                loss = self._microbatch_loss(p, mb, step=state.step, training=True)
                return (loss * scale).astype(jnp.float32), loss

            grads, loss = jax.grad(scaled_loss, has_aux=True)(state.params, b)
            return grads, loss

        micro_batch_sh = self._batch_sharding_tree(batch)
        def ds_apply(state, grads, loss):
            return self._apply_grads(state, grads, loss)

        self._accum_fn = jax.jit(ds_accum, in_shardings=(self.state_shardings, micro_batch_sh))
        self._apply_step_fn = jax.jit(ds_apply,
                                      in_shardings=(self.state_shardings, None, repl),
                                      out_shardings=(self.state_shardings, metrics_sh),
                                      donate_argnums=(0, ))

    @staticmethod
    def _batch_key(batch):
        import numpy as _np
        leaves, treedef = jax.tree.flatten(batch)
        return (str(treedef),
                tuple((_np.shape(l), str(getattr(l, "dtype", type(l)))) for l in leaves))

    def _ensure_ready(self, batch):
        if getattr(self, "_abstract_state", False):
            raise RuntimeError(
                "this engine was AOT-compiled abstractly (compile_aot) and holds "
                "no real state; create a fresh engine to train")
        if self.state is None:
            self._materialize_state(batch=batch)
        if self._compression_requested and self._compression_fn is None:
            self._build_compression()
            self._compression_requested = False
        if self._vblr is not None:
            from .data_pipeline.data_sampling.variable_batch_size_and_lr import scale_lr
            ref_bs, method = self._vblr
            if isinstance(batch, dict) and batch.get("loss_mask") is not None:
                # bucketed loaders pad with all-masked rows; the EFFECTIVE
                # batch size (real sequences) drives the LR scale
                bs = int(np.asarray(batch["loss_mask"]).any(axis=-1).sum())
            else:
                bs = int(np.shape(jax.tree.leaves(batch)[0])[0])
            self._lr_scale = scale_lr(ref_bs, bs, method=method)
        # compiled fns are keyed by batch structure: a malformed batch fails
        # cleanly without poisoning the cache, and changing batch shapes
        # (e.g. curriculum seq-len growth) triggers a fresh compile
        key = self._batch_key(batch) + (self._lr_scale, )
        self._rebuilt_this_step = False
        if getattr(self, "_onebit_comm_backend", None):
            # compressed transport compiles distinct warmup (fp32 grad
            # allreduce) and compression (momentum-wire) phase programs,
            # switched host-side at freeze_step like the reference backend
            key = key + (self.global_steps < self._onebit_freeze_step, )
        if getattr(self, "_step_key", None) != key:
            # memoize built programs per key: alternating batch buckets
            # (variable batch size, curriculum flips) must NOT retrace on
            # every switch — steady state reuses the compiled set
            cache = getattr(self, "_step_cache", None)
            if cache is None:
                cache = self._step_cache = {}
            if key in cache:
                (self._train_step_fn, self._accum_fn, self._apply_step_fn,
                 self._batch_shardings, self._eval_fn) = cache[key]
            else:
                self._build_train_step(batch)
                self._rebuilt_this_step = True  # first call pays compilation
                self._eval_fn = None
                cache[key] = (self._train_step_fn, self._accum_fn, self._apply_step_fn,
                              self._batch_shardings, self._eval_fn)
            self._step_key = key

    # ------------------------------------------------------------- public API

    def set_variable_batch_lr(self, ref_batch_size: int, method: str = "linear"):
        """Enable variable-batch LR scaling (ref: data_sampling/
        variable_batch_size_and_lr.py lr_scheduler_for_variable_batch_size):
        every train_batch's LR is multiplied by scale_lr(ref_batch_size,
        actual_batch_size, method).  Pairs with VariableBatchDataLoader."""
        self._vblr = (int(ref_batch_size), method)

    def compile_aot(self, batch):
        """AOT-compile the full train step WITHOUT allocating any state.

        The TPU-native answer to the reference's ZeRO memory estimators
        (ref: runtime/zero/stage3.py estimate_zero3_model_states_mem_needs_
        all_live and the autotuner's memory model): instead of closed-form
        approximations, the REAL compiled program's memory analysis — exact
        per-device bytes for arguments (state), outputs, and XLA temp/peak
        (activations, collectives) — at full model scale on any mesh,
        including a virtual CPU mesh standing in for a pod slice.

        Returns the ``jax`` Compiled object: ``.memory_analysis()`` for the
        HBM budget, ``.cost_analysis()`` for FLOPs.  The engine holds only
        ShapeDtypeStructs afterwards — training on it raises; build a fresh
        engine to actually train.
        """
        assert self.state is None, (
            "compile_aot requires a fresh engine: this one already holds real "
            "training state, which abstract materialization would destroy")
        self._materialize_state(batch=batch, abstract=True)
        self._abstract_state = True
        self._build_train_step(batch)
        abs_batch = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=s),
            batch, self._batch_shardings)
        with mesh_lib.trace_mesh(self.mesh):
            return self._train_step_fn.lower(self.state, abs_batch).compile()

    def train_batch(self, data_iter=None, batch=None):
        """Run one full training step = gas micro-batches (ref:
        pipe/engine.py:338 train_batch; for non-pipeline configs this fuses
        what forward/backward/step do imperatively)."""
        if batch is None:
            assert data_iter is not None, "provide data_iter or batch"
            micro = [next(data_iter) for _ in range(self.gas)]
            batch = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *micro) if self.gas > 1 else micro[0]
        # shape donor for elastic re-materialization after a membership change
        # (elasticity/elastic_agent.py) — host arrays, one batch, cheap
        self.last_batch = batch
        self._ensure_ready(batch)
        # named chaos site around the step dispatch: injects device loss
        # (drives DSElasticAgent recovery), stragglers (drives the step
        # watchdog) or transient errors; a single `is None` test when unarmed
        from ..resilience import fault_injection as _fi
        _fi.check("engine.step")
        prof_cfg = self._config.flops_profiler_config
        profiling_now = (self.flops_profiler is not None and self.global_steps == prof_cfg.profile_step)
        if profiling_now:
            self.flops_profiler.start_profile(example_batch=batch)
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        import time as _time
        _step_t0 = _time.time()  # dslint-ok(determinism): 1-bit wire latency proxy is real dispatch wall time (see comment below)
        # one trace per training step; phases land as child spans (the
        # null tracer makes this whole block allocation-free when off)
        self._step_span = self.tracer.start_span(
            "engine/step", track="engine",
            attrs={"global_step": self.global_steps} if self.tracer.enabled else None)
        try:
            # in a running jax.profiler trace: one ds.train_step range a step
            # on the host plane (an inactive TraceMe otherwise)
            with jax.profiler.StepTraceAnnotation("ds.train_step", step_num=self.global_steps), \
                    mesh_lib.trace_mesh(self.mesh):  # first call traces model code
                if getattr(self, "_nvme_opt", None) is not None:
                    self.state, metrics = self._nvme_train_step(batch)
                else:
                    with self.tracer.span("engine/fused_step",
                                          parent=self._step_span, track="engine"):
                        # the jitted call places the batch itself: a device_put of
                        # its own, to have a span for it, cost 0.1% of the step
                        with profiler_range("ds.dispatch"):
                            self.state, metrics = self._train_step_fn(self.state, batch)
        finally:
            self.tracer.end(self._step_span)
            self._step_span = None
        if getattr(self, "_compressed_wire_bytes", None) \
                and self.global_steps >= getattr(self, "_onebit_freeze_step", 0) \
                and not self._rebuilt_this_step:
            # only compression-phase steps carry the 1-bit wire (warmup's
            # traffic is the fp32 grad pmean); latency = dispatch wall time,
            # the closest host-side proxy for the async step.  Steps that
            # just (re)built the program are skipped — their wall time is
            # dominated by compilation, not the wire
            from ..comm import comm as dist
            dist._record(self._compressed_wire_name, _step_t0, self._compressed_wire_bytes)
        self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(global_step=True)
        if profiling_now:
            jax.block_until_ready(metrics.loss)
            self.flops_profiler.stop_profile()
            self.flops_profiler.print_model_profile(profile_step=self.global_steps,
                                                    module_depth=prof_cfg.module_depth,
                                                    top_modules=prof_cfg.top_modules,
                                                    detailed=prof_cfg.detailed,
                                                    output_file=prof_cfg.output_file)
            self.flops_profiler.end_profile()
        self.global_steps += 1
        self.global_samples += self._config.train_batch_size
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        self._write_monitor(metrics)
        self._maybe_print(metrics)
        return metrics.loss

    def measure_stream_overlap(self, batch, pipelined_steps: int = 1):
        """Measure the streamed-optimizer pipeline's transfer/compute
        overlap on real steps and return the artifact dict (see
        overlap_instrumentation.report): per-group upload/compute/download
        seconds, the aggregate overlap fraction, and the transfer-/compute-
        bound floor.  Runs ``pipelined_steps`` normal (flushed) steps plus
        one serialized probe step — these are REAL training steps (state
        advances).  Requires an active streamed offload tier."""
        assert getattr(self, "_nvme_opt", None) is not None, (
            "measure_stream_overlap needs an active streamed optimizer tier "
            "(offload_optimizer device=cpu+pipeline_read or device=nvme)")
        try:
            self._nvme_step_mode = "flush"
            for _ in range(max(1, pipelined_steps)):
                self.train_batch(batch=batch)
            self._nvme_step_mode = "serialize"
            self.train_batch(batch=batch)
        finally:
            self._nvme_step_mode = None
        return self._nvme_opt.overlap_report()

    def _build_eval_fn(self):
        if self._eval_fn is None:
            def eval_loss(state, b):
                return self._microbatch_loss(state.params, b, step=state.step)
            self._eval_fn = jax.jit(eval_loss, in_shardings=(self.state_shardings, self._batch_shardings))
            # refresh the per-bucket step cache: its entry was created with
            # _eval_fn=None at train-step build time, and restoring that
            # stale None on a bucket switch-and-back would force an eval
            # retrace (advisor r2)
            cache = getattr(self, "_step_cache", None)
            key = getattr(self, "_step_key", None)
            if cache is not None and key in cache:
                cache[key] = (self._train_step_fn, self._accum_fn, self._apply_step_fn,
                              self._batch_shardings, self._eval_fn)
        return self._eval_fn

    def forward(self, batch):
        """Compute loss for a micro-batch (eval path shares the jitted fn)."""
        self._ensure_ready(batch)
        self._last_batch = batch
        fn = self._build_eval_fn()
        self.timers(FORWARD_GLOBAL_TIMER).start()
        with mesh_lib.trace_mesh(self.mesh):
            loss = fn(self.state, batch)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss=None, batch=None):
        """Accumulate gradients for the last forwarded batch (ref:
        engine.py:2204 backward).  The ``loss`` argument is accepted for API
        parity; gradients are recomputed functionally."""
        batch = batch if batch is not None else getattr(self, "_last_batch", None)
        assert batch is not None, "call forward(batch) first or pass batch="
        self._ensure_ready(batch)
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        with mesh_lib.trace_mesh(self.mesh):
            grads, loss_v = self._accum_fn(self.state, batch)
        if self._pending_grads is None:
            self._pending_grads, self._pending_loss = grads, loss_v
        else:
            self._pending_grads = jax.tree.map(jnp.add, self._pending_grads, grads)
            self._pending_loss = self._pending_loss + loss_v
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        self._micro_step_count += 1
        return loss_v

    def is_gradient_accumulation_boundary(self):
        """ref: engine.py:2124."""
        return self._micro_step_count % self.gas == 0

    def step(self):
        """Apply the optimizer once per GAS boundary (ref: engine.py:2338)."""
        assert self._pending_grads is not None, "backward() must run before step()"
        if not self.is_gradient_accumulation_boundary():
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        # note: _apply_grads divides by gas via the scaler path; pending grads
        # are summed over backward() calls which matches
        loss = self._pending_loss / self._micro_step_count
        with mesh_lib.trace_mesh(self.mesh):
            self.state, metrics = self._apply_step_fn(self.state, self._pending_grads, loss)
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._pending_grads, self._pending_loss = None, None
        self._micro_step_count = 0
        self.global_steps += 1
        self.global_samples += self._config.train_batch_size
        self._write_monitor(metrics)
        self._maybe_print(metrics)
        self.lr_scheduler.step()
        return metrics

    def eval_batch(self, data_iter=None, batch=None):
        if batch is None:
            batch = next(data_iter)
        return self.forward(batch)

    def no_sync(self):
        """Grad-sync control is compiled into the step on TPU; context kept
        for API parity (ref: engine.py:2184)."""
        import contextlib
        return contextlib.nullcontext()

    def zero_grad(self):
        self._pending_grads, self._pending_loss = None, None
        self._micro_step_count = 0

    # ------------------------------------------------------------- monitoring

    def set_telemetry(self, tracer=None, metrics=None):
        """Attach a telemetry ``Tracer`` and/or ``MetricsRegistry``
        (deepspeed_tpu/telemetry).  Every subsequent ``train_batch`` emits
        one ``engine/step`` trace with ``fwd_bwd``/``optim`` child spans
        (streamed-optimizer tiers additionally lift their per-group
        upload/compute/download phases into child spans), and the flops
        profiler — when enabled — publishes its per-step flops/params
        gauges into the registry."""
        from ..telemetry.trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics_registry = metrics
        if self.flops_profiler is not None:
            # always propagate — set_telemetry() with no registry must
            # DETACH a previously attached one, or the profiler keeps
            # publishing into (and pinning) a registry the caller dropped
            self.flops_profiler.attach_metrics(metrics)
        return self

    def _write_monitor(self, metrics):
        if self.monitor is not None and self.monitor.enabled:
            events = [
                ("Train/Samples/train_loss", float(metrics.loss), self.global_samples),
                ("Train/Samples/lr", float(metrics.lr), self.global_samples),
                ("Train/Samples/loss_scale", float(metrics.loss_scale), self.global_samples),
            ]
            nv = getattr(self, "_nvme_opt", None)
            ver = getattr(getattr(nv, "instrumentation", None), "version", 0)
            if nv is not None and hasattr(nv, "overlap_report") \
                    and ver != getattr(self, "_stream_report_ver", 0):
                # streamed-optimizer overlap metrics: emitted once per FRESH
                # measurement (probe/flushed step), not re-sent every step
                rep = nv.overlap_report()
                if rep is not None:
                    for key in ("upload_s", "compute_s", "download_s",
                                "overlap_fraction", "pipelined_wall_s"):
                        if rep.get(key) is not None:
                            events.append((f"Train/Samples/stream_{key}",
                                           float(rep[key]), self.global_samples))
                self._stream_report_ver = ver
            self.monitor.write_events(events)

    def _maybe_print(self, metrics):
        spp = self._config.steps_per_print
        if spp and self.global_steps % spp == 0:
            log_dist(
                f"step={self.global_steps} loss={float(metrics.loss):.4f} "
                f"lr={float(metrics.lr):.3e} gnorm={float(metrics.grad_norm):.3f} "
                f"scale={float(metrics.loss_scale):.0f} skipped={int(self.state.skipped_steps)}",
                ranks=[0])

    # ------------------------------------------------------------ checkpoints

    # --------------------------------------------------------- state offload

    def offload_states(self, include=None, device: str = "cpu", nvme_path=None,
                       pin_memory: bool = True, non_blocking: bool = False):
        """Evict optimizer state / fp32 master weights from device memory
        (ref: runtime/zero/offload_states.py + engine.offload_states — used
        e.g. between RLHF train and generate phases).

        device='cpu'  → host numpy copies (HBM freed; ``reload_states``
                        or the next train_batch re-uploads them).
        device='nvme' → streamed to ``nvme_path`` via the native aio engine
                        (ops/aio); ``reload_states`` REQUIRED before training.
        """
        assert self.state is not None, "no state materialized yet"
        include = set(include or ("optimizer_states", "master_weights"))
        self._offloaded = getattr(self, "_offloaded", {})

        def take(name, tree):
            if name not in include or tree == ():
                return tree
            if device == "nvme":
                from .swap_tensor import AioSwapConfig, TensorSwapper
                if getattr(self, "_nvme_swapper", None) is None:
                    assert nvme_path is not None, "offload_states(device='nvme') needs nvme_path"
                    self._nvme_swapper = TensorSwapper(nvme_path, AioSwapConfig())
                self._nvme_swapper.swap_out(name, tree)
                self._offloaded[name] = "nvme"
                # zero-length host placeholders keep the pytree structure
                return jax.tree.map(lambda x: np.empty((0, ), np.dtype(x.dtype)), tree)
            self._offloaded[name] = "cpu"
            return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)

        new_opt = take("optimizer_states", self.state.opt_state)
        new_master = take("master_weights", self.state.master)
        self.state = self.state._replace(opt_state=new_opt, master=new_master)
        log_dist(f"offload_states: {sorted(include)} → {device}", ranks=[0])

    def reload_states(self, non_blocking: bool = False):
        """Restore previously offloaded states to their device shardings
        (ref: engine.reload_states)."""
        offloaded = getattr(self, "_offloaded", {})
        if not offloaded:
            return

        def put(name, tree, shardings):
            if name not in offloaded or tree == ():
                return tree
            if offloaded[name] == "nvme":
                tree = self._nvme_swapper.swap_in(name)
            return jax.device_put(tree, shardings)

        self.state = self.state._replace(
            opt_state=put("optimizer_states", self.state.opt_state, self.state_shardings.opt_state),
            master=put("master_weights", self.state.master, self.state_shardings.master))
        self._offloaded = {}

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True, exclude_frozen_parameters=False):
        from .swap_tensor.host_streamed_optimizer import HostStreamedOptimizer
        nv = getattr(self, "_nvme_opt", None)
        if nv is not None and not isinstance(nv, HostStreamedOptimizer):
            # NVMe tier: optimizer state lives on disk already; the
            # checkpoint captures params + step, and resume re-reads the
            # swap files at nvme_path (they are flushed durable here)
            nv.swapper.flush_writes()
            logger.warning("save_checkpoint with pipelined NVMe offload: optimizer "
                           "moments stay in the nvme_path swap files — keep that "
                           "directory alongside the checkpoint to resume exactly")
        from ..checkpoint.engine import save_checkpoint as _save
        # host tier: state is process RAM — persist it INTO the tag dir
        # (unlike NVMe swap files, nothing else makes it durable).  Passed
        # as the extra-state callback so the npz files land INSIDE the
        # durability fence: covered by the tag manifest and written before
        # `latest` is published (a crash mid-npz leaves the previous
        # checkpoint published, not a half-restorable new one)
        extra = nv.save_state if isinstance(nv, HostStreamedOptimizer) else None
        return _save(self, save_dir, tag=tag, client_state=client_state or {},
                     save_latest=save_latest, extra_state_cb=extra)

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        from ..checkpoint.engine import load_checkpoint as _load
        out = _load(self, load_dir, tag=tag, load_optimizer_states=load_optimizer_states,
                    load_module_only=load_module_only)
        if getattr(self, "_nvme_opt", None) is not None and self.state is not None:
            from .swap_tensor.host_streamed_optimizer import HostStreamedOptimizer
            nv = self._nvme_opt
            loaded_path = out[0] if isinstance(out, tuple) else None
            if isinstance(nv, HostStreamedOptimizer) and load_optimizer_states \
                    and loaded_path is not None:
                # host tier: restore the group state persisted into the tag
                # dir by save_checkpoint.  The tag dir is the PATH THE LOAD
                # RESOLVED (returned above) — re-reading `latest` here would
                # point at the corrupt tag the loader just fell back FROM
                tag_dir = loaded_path
                if nv.load_state(tag_dir):
                    # a same-shaped host_opt_group*.npz from a DIFFERENT run
                    # loads cleanly but its master would silently revert the
                    # restored params on the first step — probe one leaf per
                    # group and resync (moments reset, warned) on mismatch
                    leaves = jax.tree.leaves(self.state.params)
                    if not nv.master_matches_params(leaves, self.compute_dtype):
                        logger.warning(
                            "host-streamed offload: restored host_opt_group*.npz "
                            "state does not correspond to the loaded checkpoint's "
                            "params (same shapes, different run?) — reinitializing "
                            "master from the restored weights (Adam moments reset)")
                        nv.resync_master_from_params(leaves)
                    return out
            # the offloaded fp32 master must correspond to the restored
            # params — otherwise the first step would silently revert the
            # loaded weights to whatever the store held (e.g. the random
            # init written at materialization)
            leaves = jax.tree.leaves(self.state.params)
            if not nv.master_matches_params(leaves, self.compute_dtype):
                logger.warning("streamed optimizer offload: stored state does not match "
                               "the loaded checkpoint — reinitializing master from the "
                               "restored weights (Adam moments reset to zero)")
                nv.resync_master_from_params(leaves)
        return out

    # ------------------------------------------------------------- properties

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def train_batch_size(self):
        return self._config.train_batch_size

    def gradient_accumulation_steps(self):
        return self.gas

    def get_global_grad_norm(self):
        return None  # populated in metrics per step

    def zero_optimization(self):
        return self.zero_stage > 0

    def zero_optimization_stage(self):
        return self.zero_stage

    @property
    def loss_scale(self):
        return float(self.state.scaler.cur_scale) if self.state is not None else None

    @property
    def skipped_steps(self):
        return int(self.state.skipped_steps) if self.state is not None else 0

    def get_lr(self):
        return [float(self.lr_schedule(self.state.step if self.state is not None else 0))]

    def module_state_dict(self):
        return self.state.params if self.state is not None else None
