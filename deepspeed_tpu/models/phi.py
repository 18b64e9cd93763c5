"""Phi-1 / Phi-2 — parallel attention+MLP decoder with partial rotary.

ref: deepspeed/inference/v2/model_implementations/phi/ — LN(+bias) into
parallel attention and MLP branches sharing one residual, biases on every
projection, rotary applied only to the first ``rotary_dim`` of each head
(partial_rotary_factor), gelu MLP, final LN and a biased lm_head.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .llama import (EMBED, HEADS, HEAD_DIM, KV_HEADS, LAYERS, MLP, VOCAB, _logical,
                    get_attention_impl, rotary_embedding)


@dataclass(frozen=True)
class PhiConfig:
    vocab_size: int = 51200
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    partial_rotary_factor: float = 0.4
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 2048
    tie_word_embeddings: bool = False
    qk_layernorm: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = True
    attention_impl: str = "reference"

    @staticmethod
    def from_hf(hf_cfg, **overrides):
        fields = dict(vocab_size=hf_cfg.vocab_size,
                      hidden_size=hf_cfg.hidden_size,
                      intermediate_size=hf_cfg.intermediate_size,
                      num_hidden_layers=hf_cfg.num_hidden_layers,
                      num_attention_heads=hf_cfg.num_attention_heads,
                      num_key_value_heads=getattr(hf_cfg, "num_key_value_heads", None)
                      or hf_cfg.num_attention_heads,
                      partial_rotary_factor=getattr(hf_cfg, "partial_rotary_factor", 0.5),
                      rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
                      layer_norm_eps=getattr(hf_cfg, "layer_norm_eps", 1e-5),
                      max_position_embeddings=hf_cfg.max_position_embeddings,
                      tie_word_embeddings=getattr(hf_cfg, "tie_word_embeddings", False),
                      qk_layernorm=getattr(hf_cfg, "qk_layernorm", False))
        fields.update(overrides)
        return PhiConfig(**fields)


def apply_partial_rope(x, cos, sin, rotary_dim):
    """Rotate only the first ``rotary_dim`` of each head (HF phi
    rotate_half convention), pass the rest through.
    x: [B, S, N, D]; cos/sin: [B, S, rotary_dim/2] (or [T, N, D] with
    the tables of flat positions [T, rotary_dim/2])."""
    rot, keep = x[..., :rotary_dim].astype(jnp.float32), x[..., rotary_dim:]
    half = rotary_dim // 2
    r1, r2 = rot[..., :half], rot[..., half:]
    c, s = cos[..., None, :], sin[..., None, :]
    rotated = jnp.concatenate([r1 * c - r2 * s, r2 * c + r1 * s], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), keep], axis=-1)


class PhiAttention(nn.Module):
    cfg: PhiConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
        D = cfg.hidden_size // H
        rot_dim = int(D * cfg.partial_rotary_factor)
        dense = partial(nn.DenseGeneral, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = dense(features=(H, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)),
                  name="q_proj")(x)
        k = dense(features=(KV, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="k_proj")(x)
        v = dense(features=(KV, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="v_proj")(x)
        if cfg.qk_layernorm:
            # per-head LayerNorm over head_dim BEFORE rope (ref: HF PhiAttention
            # q_layernorm/k_layernorm, phi-1/phi-1.5 checkpoints)
            q = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="q_layernorm")(q)
            k = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="k_layernorm")(k)
        cos, sin = rotary_embedding(positions, rot_dim, cfg.rope_theta)
        q = apply_partial_rope(q, cos, sin, rot_dim)
        k = apply_partial_rope(k, cos, sin, rot_dim)
        out = get_attention_impl(cfg.attention_impl)(q, k, v, causal=True, segment_ids=segment_ids)
        return nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=True,
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                               name="dense")(out)


class PhiBlock(nn.Module):
    cfg: PhiConfig
    scanned: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="input_layernorm")(x)
        attn_out = PhiAttention(cfg, name="self_attn")(h, positions, segment_ids)
        m = nn.Dense(cfg.intermediate_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)), name="fc1")(h)
        m = jax.nn.gelu(m, approximate=True)  # HF phi: gelu_new
        mlp_out = nn.Dense(cfg.hidden_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EMBED)), name="fc2")(m)
        out = x + attn_out + mlp_out  # parallel residual
        if self.scanned:
            return out, None
        return out


class PhiForCausalLM(nn.Module):
    cfg: PhiConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None):
        cfg = self.cfg
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(input_ids)
        block_cls = PhiBlock
        if cfg.remat:
            block_cls = nn.remat(PhiBlock, prevent_cse=not cfg.scan_layers)
        if cfg.scan_layers:
            blocks = nn.scan(block_cls, variable_axes={"params": 0}, split_rngs={"params": True},
                             in_axes=(nn.broadcast, nn.broadcast), length=cfg.num_hidden_layers,
                             metadata_params={nn.PARTITION_NAME: LAYERS})
            x, _ = blocks(cfg, scanned=True, name="layers")(x, positions, segment_ids)
        else:
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"layers_{i}")(x, positions, segment_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="final_layernorm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                        name="lm_head")(x)
