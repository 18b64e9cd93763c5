"""Build a FastGen-v2 engine from a HuggingFace checkpoint directory.

ref: deepspeed/inference/v2/engine_factory.py:69 build_hf_engine — reads the
HF config, picks the per-arch policy, maps the checkpoint into the engine's
parameter containers, returns an InferenceEngineV2.

Loading uses transformers' local machinery only (no hub download): the
checkpoint directory must contain config.json + weights
(model.safetensors / pytorch_model.bin shards).
"""

import os
from typing import Optional

from ...utils.logging import logger
from .engine_v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from .model_implementations import convert_hf_state_dict


def _load_state_dict(path: str):
    """Collect the full torch state dict from a local HF checkpoint dir."""
    import glob
    import torch

    sts = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if sts:
        from safetensors.torch import load_file
        sd = {}
        for f in sts:
            sd.update(load_file(f))
        return sd
    bins = sorted(glob.glob(os.path.join(path, "pytorch_model*.bin"))) or \
        sorted(glob.glob(os.path.join(path, "*.bin")))
    if bins:
        sd = {}
        for f in bins:
            sd.update(torch.load(f, map_location="cpu", weights_only=True))
        return sd
    raise FileNotFoundError(f"no weight files (*.safetensors / pytorch_model*.bin) under {path}")


def build_hf_engine(path: str,
                    engine_config: Optional[RaggedInferenceEngineConfig] = None,
                    debug_level: int = 0,
                    quantization_mode: Optional[str] = None) -> InferenceEngineV2:
    """ref: engine_factory.py:69.  ``quantization_mode``: None | 'wf6af16'
    -style strings accepted; any non-None value enables int8 weight-only
    quantization of the loaded checkpoint (inference/quantization)."""
    from transformers import AutoConfig

    hf_cfg = AutoConfig.from_pretrained(path, local_files_only=True)
    sd = _load_state_dict(path)
    cfg, params = convert_hf_state_dict(sd, hf_cfg)
    logger.info(f"build_hf_engine: model_type={hf_cfg.model_type} "
                f"{sum(p.size for p in _leaves(params))/1e6:.1f}M params")

    # v1-era archs (bloom / gpt-neox / gptj / gpt-neo) have conversion
    # policies but no paged cache twin — the reference serves them through
    # v1 kernel injection (module_inject/containers); here they route to the
    # v1 jitted-forward engine behind a generate()-compatible surface
    from ...models.cache_zoo import CACHE_MODEL_REGISTRY
    if not isinstance(cfg, tuple(CACHE_MODEL_REGISTRY)):
        import deepspeed_tpu as ds
        from .model_implementations.policies import policy_for
        if quantization_mode is not None:
            raise NotImplementedError(
                f"quantization_mode={quantization_mode!r} requires the paged v2 engine; "
                f"{hf_cfg.model_type} has no paged cache twin and serves via the v1 path")
        if engine_config is not None:
            logger.warning(f"build_hf_engine: engine_config is ignored for {hf_cfg.model_type} "
                           "(v1 fallback path — no ragged scheduler/KV arena)")
        model = policy_for(hf_cfg.model_type).build_model(cfg)
        logger.info(f"build_hf_engine: {hf_cfg.model_type} has no paged twin — "
                    "serving through the v1 engine (ref: v1 kernel-injection containers)")
        return ds.init_inference(model=model, config={"dtype": "fp32"},
                                 params={"params": params})

    if quantization_mode is not None:
        from ..quantization import quantize_inference_params
        return InferenceEngineV2(cfg, quantize_inference_params(params), engine_config=engine_config)

    return InferenceEngineV2(cfg, {"params": params}, engine_config=engine_config)


def _leaves(tree):
    import jax
    return jax.tree.leaves(tree)
