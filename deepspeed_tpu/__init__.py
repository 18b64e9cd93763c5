"""deepspeed_tpu — a TPU-native training/inference framework with the
capabilities of DeepSpeed, built on JAX/XLA/Pallas.

Public API parity with the reference (``deepspeed/__init__.py``):
  initialize()        — ref: deepspeed/__init__.py:69
  init_distributed()  — ref: deepspeed/__init__.py:233 → comm/comm.py:636
  init_inference()    — ref: deepspeed/__init__.py:291 (inference engine)
  add_config_arguments— ref: deepspeed/__init__.py:268
"""

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401
from .runtime import zero  # noqa: F401  (ds.zero.Init / GatheredParameters parity)
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader  # noqa: F401
from .utils.logging import logger  # noqa: F401
from . import resilience  # noqa: F401  (fault injection / crash-safe I/O surface)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=29500,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               mesh_param=None,
               config_params=None,
               loss_fn=None,
               model_inputs_fn=None,
               mesh=None,
               params=None,
               init_rng=None):
    """Create a training engine (ref: deepspeed/__init__.py:69 initialize).

    Returns the same 4-tuple as the reference:
        (engine, optimizer, training_dataloader, lr_scheduler)

    ``model`` is a flax module (see deepspeed_tpu.models); ``config`` is the
    DeepSpeed-style JSON dict/path.  ``params`` may carry pre-initialised
    weights; otherwise params are initialised lazily, directly into their
    ZeRO-partitioned layout on first batch (zero.Init semantics,
    ref: runtime/zero/partition_parameters.py:825).
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config
    assert config is not None, "deepspeed_tpu.initialize: config is required"

    init_distributed(distributed_port=distributed_port, dist_init_required=dist_init_required)

    if mpu is not None and mesh is None:
        # External Megatron-style mpu honored end-to-end (ref:
        # deepspeed/runtime/engine.py reads mpu.get_model_parallel_world_size
        # etc. to build its process groups; module_inject/containers/
        # megatron_gpt.py:14 consumes the mp group).  Here the grid maps onto
        # mesh axes: TP -> 'tensor', PP -> 'pipe', DP -> 'data'; the sharding
        # rules then place params exactly where the mpu's groups would.
        from .comm.mesh import mesh_from_mpu
        mesh = mesh_from_mpu(mpu)

    if isinstance(config, DeepSpeedConfig):
        ds_config = config
    else:
        # an explicit mesh decides the data-parallel degree the batch triad is
        # checked against — not jax.device_count(): a one-device mesh on a
        # four-chip host trains with dp=1
        dp = None
        if mesh is not None:
            from .comm.mesh import BATCH_AXES, axis_size
            dp = axis_size(mesh, *BATCH_AXES)
        ds_config = DeepSpeedConfig(config, mpu=mpu, dp_world_size=dp)
    from .runtime.pipe.engine import PipelineEngine
    from .runtime.pipe.module import PipelineModule
    if isinstance(model, PipelineModule):
        engine_cls = PipelineEngine
    elif ds_config.hybrid_engine.enabled:
        # RLHF train+generate engine (ref: deepspeed/__init__.py:119 picks
        # DeepSpeedHybridEngine when config.hybrid_engine.enabled)
        from .runtime.hybrid_engine import DeepSpeedHybridEngine
        engine_cls = DeepSpeedHybridEngine
    else:
        engine_cls = DeepSpeedEngine
    engine = engine_cls(model=model,
                        config=ds_config,
                        optimizer=optimizer,
                        lr_scheduler=lr_scheduler,
                        loss_fn=loss_fn,
                        model_inputs_fn=model_inputs_fn,
                        mesh=mesh,
                        params=params,
                        init_rng=init_rng)

    dataloader = None
    if training_data is not None:
        # loader yields MICRO-batches (global micro = micro_per_device × dp);
        # engine.train_batch pulls gradient_accumulation_steps of them per
        # optimizer step (ref: deepspeed_io engine.py:1854 semantics)
        micro_global = ds_config.train_batch_size // ds_config.gradient_accumulation_steps
        dataloader = DeepSpeedDataLoader(training_data,
                                         batch_size=micro_global,
                                         collate_fn=collate_fn)
    return engine, engine.opt, dataloader, engine.lr_scheduler


def add_config_arguments(parser):
    """ref: deepspeed/__init__.py:268 — attach --deepspeed flags to argparse."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale", default=False, action="store_true")
    group.add_argument("--deepscale_config", default=None, type=str)
    return parser


def init_inference(model=None, config=None, **kwargs):
    """ref: deepspeed/__init__.py:291 — build an inference engine."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model=model, config=config or {}, **kwargs)


def tp_model_init(model=None, tp_size: int = 1, dtype=None, config=None):
    """ref: deepspeed/__init__.py:369 tp_model_init — prepare a model for
    tensor-parallel training.  Returns (model, TpTrainingManager); pass the
    manager's shardings (or just set tensor_parallel.autotp_size in the
    engine config — the engine's logical-rules path covers flax models with
    logical axis names; the manager covers converted HF trees)."""
    from .runtime.tensor_parallel import TpTrainingManager, TPTrainingConfig
    if isinstance(config, TPTrainingConfig):
        cfg = config
    elif isinstance(config, dict):
        cfg = TPTrainingConfig(**{**config, "autotp_size": config.get("autotp_size", tp_size)})
    elif config is None:
        cfg = TPTrainingConfig(autotp_size=tp_size)
    else:
        raise TypeError(f"config must be TPTrainingConfig or dict, got {type(config)}")
    return model, TpTrainingManager(model=model, tp_size=tp_size, dtype=dtype, config=cfg)
