"""EvaByte: a byte-level decoder whose attention is EVA, chunked linear
attention (ref: https://huggingface.co/EvaByte/EvaByte, ``config.json``:
``model_type`` ``evabyte``, ``attention_class`` ``eva``).

One layer, with ``x`` the residual stream, kept in float32 (``fp32_skip_add``):

* ``h = RMSNorm(x)`` with weight ``1 + g`` (``norm_add_unit_offset``);
  ``q, k, v = h Wq, h Wk, h Wv`` without bias; RoPE on ``q`` and ``k`` by
  absolute position.
* Token ``t`` lies in chunk ``t // chunk_size`` and window ``t //
  window_size``.  Every head has two learned vectors, ``adaptive_phi`` and
  ``adaptive_mu_k``.  A complete chunk is summarised to one key and one value:
  ``a_j = softmax_j((k_j . phi) / sqrt(d))`` over the chunk's tokens,
  ``k~ = sum_j a_j k_j + mu``, ``v~ = sum_j a_j v_j`` (:func:`summarise_chunks`).
* Query ``t`` attends, under ONE softmax of ``q . key / sqrt(d)`` in float32,
  to the exact keys and values of its own window up to itself and to the
  summaries of every chunk of the windows before it; never to a summary of
  its own window.  ``x = x + o Wo``.
* ``x = x + SwiGLU(RMSNorm(x))``.

After the last layer: RMSNorm, then float32 logits (``fp32_logits``) from one
head matrix ``hidden x (num_pred_heads x vocab_size)``; head ``i`` predicts
byte ``t + 1 + i``.  Embedding and head are untied.

This file is the full-sequence model (training, parity tests); the serving
twin through the paged arena is ``models/evabyte_cache.py``.  ``mixedp_attn``
needs no switch here: both attention paths take their softmax in float32.
"""

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .llama import (EMBED, HEAD_DIM, HEADS, LAYERS, VOCAB, LlamaMLP, RMSNorm, _logical, apply_rope,
                    rotary_embedding)


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """Fields carry the published key names.  The three switches are part
    of the published config and have one supported value each: the model is
    written for it, and any other is refused rather than served wrongly."""
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    num_pred_heads: int = 8
    chunk_size: int = 16
    window_size: int = 2048
    norm_add_unit_offset: bool = True
    fp32_skip_add: bool = True
    fp32_logits: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"   # reference | flash (the serving twin's paged kernel)

    def __post_init__(self):
        if not (self.norm_add_unit_offset and self.fp32_skip_add and self.fp32_logits):
            raise ValueError("EvaByte is implemented for norm_add_unit_offset, fp32_skip_add and fp32_logits all true")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("EvaByte groups no heads: num_key_value_heads must equal num_attention_heads")
        if self.window_size % (self.chunk_size * self.chunk_size):
            raise ValueError("window_size must be a multiple of chunk_size^2 (a window's summaries fill whole pages)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads



def eva_norm(cfg: EvaByteConfig, name: str, dtype=None):
    """RMSNorm with weight ``1 + g``, handing on ``dtype`` (the compute dtype
    inside a layer; float32 before the head)."""
    return RMSNorm(cfg.rms_norm_eps, dtype or cfg.dtype, cfg.param_dtype, unit_offset=True, name=name)


def summarise_chunks(k, v, phi, mu):
    """Chunk summaries.  k, v: [..., chunk, H, D] (keys after RoPE); phi, mu:
    [H, D].  Returns (k~, v~) [..., H, D] in float32."""
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    scores = jnp.einsum("...jhd,hd->...jh", k, phi.astype(jnp.float32)) / jnp.sqrt(jnp.float32(k.shape[-1]))
    a = jax.nn.softmax(scores, axis=-2)[..., None]
    return jnp.sum(a * k, axis=-3) + mu.astype(jnp.float32), jnp.sum(a * v, axis=-3)


class EvaProjections(nn.Module):
    """q, k, v after RoPE, the two summary vectors and the output projection:
    what the full-sequence layer and the serving twin share, under one
    parameter tree."""
    cfg: EvaByteConfig

    def setup(self):
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, features=(cfg.num_attention_heads, cfg.head_dim), use_bias=False,
                        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)))
        self.q_proj, self.k_proj, self.v_proj = dense(name="q_proj"), dense(name="k_proj"), dense(name="v_proj")
        vec = _logical(nn.initializers.normal(0.02), (HEADS, HEAD_DIM))
        shape = (cfg.num_attention_heads, cfg.head_dim)
        self.adaptive_phi = self.param("adaptive_phi", vec, shape, cfg.param_dtype)
        self.adaptive_mu_k = self.param("adaptive_mu_k", vec, shape, cfg.param_dtype)
        self.o_proj = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
                                      param_dtype=cfg.param_dtype, name="o_proj",
                                      kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)))

    def qkv(self, h, positions):
        cos, sin = rotary_embedding(positions, self.cfg.head_dim, self.cfg.rope_theta)
        return apply_rope(self.q_proj(h), cos, sin), apply_rope(self.k_proj(h), cos, sin), self.v_proj(h)


def eva_attention(q, k, v, phi, mu, chunk: int, window: int):
    """Full-sequence EVA, window by window.  q, k, v: [B, S, H, D] of
    positions 0..S-1, any S.  Returns [B, S, H, D] in q's dtype."""
    b, s, n, d = q.shape
    n_chunks = s // chunk   # complete chunks; a trailing partial one is summarised by nobody
    k_sum, v_sum = summarise_chunks(k[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, n, d),
                                    v[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, n, d), phi, mu)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    per_window = window // chunk
    out = []
    for w in range(-(-s // window)):
        lo, hi = w * window, min((w + 1) * window, s)
        qw = q[:, lo:hi].astype(jnp.float32)
        keys = jnp.concatenate([k_sum[:, :w * per_window], k[:, lo:hi].astype(jnp.float32)], axis=1)
        vals = jnp.concatenate([v_sum[:, :w * per_window], v[:, lo:hi].astype(jnp.float32)], axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qw, keys) * scale
        # every summary of an earlier window is visible; exact rows up to the query itself
        kpos = jnp.arange(keys.shape[1]) - w * per_window
        visible = kpos[None, :] <= jnp.arange(hi - lo)[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", probs, vals))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


class EvaByteBlock(nn.Module):
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        attn = EvaProjections(cfg, name="self_attn")
        q, k, v = attn.qkv(eva_norm(cfg, "input_layernorm")(x), positions)
        o = eva_attention(q, k, v, attn.adaptive_phi, attn.adaptive_mu_k, cfg.chunk_size, cfg.window_size)
        x = x + attn.o_proj(o).astype(x.dtype)
        x = x + LlamaMLP(cfg, name="mlp")(eva_norm(cfg, "post_attention_layernorm")(x)).astype(x.dtype)
        return x, None


def eva_embed(cfg: EvaByteConfig):
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)), name="embed_tokens")


class EvaByteHead(nn.Module):
    """The ``num_pred_heads x vocab_size`` head matrix; ``heads`` is how many
    of the prediction heads to compute, from head 0."""
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, x, heads: int):
        cfg = self.cfg
        kernel = self.param("kernel", _logical(nn.initializers.lecun_normal(), (EMBED, None, VOCAB)),
                            (cfg.hidden_size, cfg.num_pred_heads, cfg.vocab_size), cfg.param_dtype)
        return jnp.einsum("...e,epv->...pv", x.astype(jnp.float32), kernel[:, :heads].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)   # fp32_logits


class EvaByteForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, num_pred_heads,
    vocab_size]``: every prediction head, any S."""
    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1])[None], input_ids.shape)
        x = eva_embed(cfg)(input_ids).astype(jnp.float32)
        blocks = nn.scan(EvaByteBlock, variable_axes={"params": 0}, split_rngs={"params": True},
                         in_axes=nn.broadcast, length=cfg.num_hidden_layers,
                         metadata_params={nn.PARTITION_NAME: LAYERS})
        x, _ = blocks(cfg, name="layers")(x, positions)
        return EvaByteHead(cfg, name="lm_head")(eva_norm(cfg, "norm", jnp.float32)(x), cfg.num_pred_heads)
