"""Xing4.0 (ref: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B
``config.json``, ``model_type`` ``xing4_0``): multi-head latent attention, a
hyper-connected residual of ``hc_mult`` streams, sigmoid-routed experts beside
a shared one after ``first_k_dense_replace`` dense layers.

A token carries ``n = hc_mult`` streams of width ``C``, ``X [n, C]``; ``X_0``
is ``n`` copies of ``E[id]``.  A layer is two **hyper-connected sublayers**
(manifold-constrained hyper-connections, arXiv:2512.24880), one around the
attention and one around the MLP or the expert block ``F``:

  x~ = RMSNorm_g(vec(X))                      over all n C
  [H~pre | H~post | H~res] = a * (x~ phi) + b  phi [n C, n + n + n^2], a one scalar each
  Hpre = sigmoid(H~pre)   Hpost = 2 sigmoid(H~post)
  Hres = SK(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
  u = Hpre X   y = F(RMSNorm(u))   X' = Hres X + Hpost^T y

``SK`` is ``exp`` and then ``hc_sinkhorn_iters`` times rows over their sums,
columns over their sums (each sum plus ``hc_eps``): a doubly stochastic
matrix.  The coefficients are computed in float32, the streams kept in the
compute dtype.  After the last layer the streams are added, normed, and go
to the head.

**Latent attention.**  ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_pe] = c_q
W_qb`` a head (``q_lora_rank`` None: ``[q_nope | q_pe] = x W_q``, no
bottleneck and no query norm, as ``models/kimi_vl.py`` has it); ``[c_kv | k_pe] = x W_kva``, ``c_kv <- RMSNorm(c_kv)``; rotary
(YaRN, interleaved pairs) on ``q_pe`` and on the one ``k_pe`` all heads
share; ``[k_nope | v] = c_kv W_kvb`` a head; ``s = (q_nope . k_nope + q_pe .
k_pe) * softmax_scale``.  What a cache has to hold of a token is ``[c_kv |
k_pe]``.  ``Xing4Attention`` computes the projections and hands them to
``attend``: this file's is the *expanded* form (keys and values rebuilt from
the latents), the serving twin's the *absorbed* one (``models/xing4_cache.py``).

**Experts.**  ``s = sigmoid(h W_g)`` in float32; the ``num_experts_per_tok``
largest of ``s + e_score_correction_bias``; their ``s`` renormalised
(``norm_topk_prob``) and times ``routed_scaling_factor``; the weighted sum of
the chosen SwiGLU experts plus the ungated shared expert's output.  No token
is dropped (``moe/sharded_moe.dropless_dispatch``).

This file is the full-sequence model (parity tests, the parameter tree the
benchmark fills): ``dense_layers_<i>`` unrolled, the expert layers under one
scan, ``layers``.  The multi-token-prediction module
(``num_nextn_predict_layers``) is not built: the key is held and unused.
"""

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED, HEAD_DIM, HEADS, LAYERS, MLP, VOCAB
from ..moe.experts import ExpertsFFN
from ..moe.sharded_moe import dropless_dispatch
from .llama import RMSNorm, _logical

HIGHEST = jax.lax.Precision.HIGHEST


def _hashable(value):
    return tuple(sorted(value.items())) if isinstance(value, dict) else value


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """Fields carry the published key names."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = 768          # None: no query bottleneck, one ``q_proj`` and no query norm
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rope_theta: float = 10000.0
    #: the published dict (``type`` yarn), kept as sorted items so the config hashes; None: plain rotary
    rope_scaling: Any = None
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 1       # published; the module is not built
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"       # reference (jnp) | flash (ops/mla_attention.py), the twin's

    def __post_init__(self):
        object.__setattr__(self, "rope_scaling", _hashable(self.rope_scaling))
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError("group-limited routing (n_group > 1) is not built")
        if self.attention_bias or self.tie_word_embeddings:
            raise NotImplementedError("attention_bias and tie_word_embeddings are not built for this family")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace lies outside the layers")

    @property
    def num_sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Numbers a token's cache holds a layer: ``[c_kv | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def yarn(self) -> Optional[dict]:
        return None if self.rope_scaling is None else dict(self.rope_scaling)

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-1/2``, times ``yarn_mscale(factor, mscale_all_dim)^2`` under YaRN."""
        scale = self.qk_head_dim**-0.5
        yarn = self.yarn
        if yarn and yarn.get("mscale_all_dim"):
            scale *= _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])**2
        return scale


# ------------------------------------------------------------------- rotary


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: Xing4Config):
    """[qk_rope_head_dim / 2] float32.  Under YaRN the published blend: the
    plain frequencies where at least ``beta_fast`` rotations fit into the
    original context, the same over ``factor`` where fewer than ``beta_slow``
    do, a linear ramp between the two dimensions."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = 1.0 / (base**(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    yarn = cfg.yarn
    if not yarn:
        return plain
    original = yarn["original_max_position_embeddings"]

    def dim_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def rope_tables(cfg: Xing4Config, positions):
    """(cos, sin) [..., qk_rope_head_dim / 2] float32, times
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``
    (1 at the published values)."""
    angles = positions.astype(jnp.float32)[..., None] * rope_inv_freq(cfg)
    yarn = cfg.yarn
    m = 1.0 if not yarn else (_yarn_mscale(yarn["factor"], yarn.get("mscale", 1)) /
                              _yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0) or 0))
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def apply_rope_interleaved(x, cos, sin):
    """Pairs ``(2i, 2i + 1)`` rotated by angle ``i``.  x [..., d]; cos, sin broadcast to [..., d / 2]."""
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------- hyper-connection


def sinkhorn(logits, iters: int, eps: float):
    """``SK``: [..., n, n] float32 -> doubly stochastic [..., n, n]."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_coefficients(cfg: Xing4Config, normed, phi, a, b):
    """(Hpre [..., n], Hpost [..., n], Hres [..., n, n]) in float32 from
    ``normed`` = ``x~`` [..., n C] float32 and the sublayer's maps: ``phi``
    [n C, 2 n + n^2], ``a`` [3], ``b`` [2 n + n^2]."""
    n = cfg.hc_mult
    proj = jnp.matmul(normed, phi.astype(jnp.float32), precision=HIGHEST)
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * proj[..., 2 * n:] + b[2 * n:]).reshape(proj.shape[:-1] + (n, n))
    res = sinkhorn(jnp.clip(res, cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max), cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def hc_mix(x, pre):
    """``u = Hpre X``: [..., n, C] -> [..., C] in the streams' dtype."""
    return jnp.einsum("...n,...nc->...c", pre, x.astype(jnp.float32)).astype(x.dtype)


def hc_write(x, y, post, res):
    """``X' = Hres X + Hpost^T y`` in the streams' dtype."""
    mixed = jnp.einsum("...ij,...jc->...ic", res, x.astype(jnp.float32))
    return (mixed + post[..., :, None] * y.astype(jnp.float32)[..., None, :]).astype(x.dtype)


class HyperConnection(nn.Module):
    """One hyper-connected sublayer around ``fn(u) -> (y, aux)``: returns (X', aux)."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, fn):
        cfg = self.cfg
        n, width = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
        phi = self.param("phi", nn.initializers.normal(0.02), (width, 2 * n + n * n), cfg.param_dtype)
        a = self.param("a", nn.initializers.normal(0.02), (3, ), cfg.param_dtype)
        b = self.param("b", nn.initializers.normal(0.02), (2 * n + n * n, ), cfg.param_dtype)
        with jax.named_scope("ds_mhc"):
            flat = x.astype(jnp.float32).reshape(x.shape[:-2] + (width, ))
            normed = RMSNorm(cfg.rms_norm_eps, jnp.float32, cfg.param_dtype, name="hc_norm")(flat)
            pre, post, res = hc_coefficients(cfg, normed, phi, a, b)
            u = hc_mix(x, pre)
        y, aux = fn(u)
        with jax.named_scope("ds_mhc"):
            return hc_write(x, y, post, res), aux


# ---------------------------------------------------------------- attention


def expanded_attention(cfg: Xing4Config, q_nope, q_pe, c_kv, k_pe, w_kvb):
    """The expanded form over whole sequences: q_nope [B, S, H, nope], q_pe
    [B, S, H, rope], c_kv [B, S, rank], k_pe [B, S, rope], w_kvb [rank, H,
    nope + v] -> [B, S, H, v].  Causal; scores and softmax in float32."""
    kv = jnp.einsum("bsl,lhd->bshd", c_kv, w_kvb.astype(c_kv.dtype))
    k_nope, v = kv[..., :cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]
    f32 = jnp.float32
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(f32), k_nope.astype(f32), precision=HIGHEST) +
              jnp.einsum("bqhd,bkd->bhqk", q_pe.astype(f32), k_pe.astype(f32), precision=HIGHEST)) * cfg.softmax_scale
    s = scores.shape[-1]
    scores = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(f32), precision=HIGHEST).astype(q_nope.dtype)


class Xing4Attention(nn.Module):
    """The projections of latent attention around ``attend(q_nope [..., H,
    nope], q_pe [..., H, rope], c_kv [..., rank], k_pe [..., rope], w_kvb
    [rank, H, nope + v]) -> (o [..., H, v], aux)``; ``q_pe`` and ``k_pe``
    arrive rotated, ``c_kv`` normed.  Returns (out [..., C], aux)."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, positions, attend):
        cfg = self.cfg
        heads, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim

        def dense(features, names, name, axis=-1):
            return nn.DenseGeneral(features=features, axis=axis, use_bias=False, dtype=cfg.dtype,
                                   param_dtype=cfg.param_dtype,
                                   kernel_init=_logical(nn.initializers.lecun_normal(), names), name=name)

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)

        if cfg.q_lora_rank:
            c_q = norm("q_a_layernorm")(dense(cfg.q_lora_rank, (EMBED, None), "q_a_proj")(x))
            q = dense((heads, nope + rope), (None, HEADS, HEAD_DIM), "q_b_proj")(c_q)
        else:
            q = dense((heads, nope + rope), (EMBED, HEADS, HEAD_DIM), "q_proj")(x)
        kv_a = dense(cfg.kv_lora_rank + rope, (EMBED, None), "kv_a_proj_with_mqa")(x)
        c_kv = norm("kv_a_layernorm")(kv_a[..., :cfg.kv_lora_rank])
        w_kvb = self.param("kv_b_proj", _logical(nn.initializers.lecun_normal(), (None, HEADS, HEAD_DIM)),
                           (cfg.kv_lora_rank, heads, nope + cfg.v_head_dim), cfg.param_dtype)
        cos, sin = rope_tables(cfg, positions)
        q_pe = apply_rope_interleaved(q[..., nope:], cos[..., None, :], sin[..., None, :])
        k_pe = apply_rope_interleaved(kv_a[..., cfg.kv_lora_rank:], cos, sin)
        with jax.named_scope("ds_mla"):
            o, aux = attend(q[..., :nope], q_pe, c_kv, k_pe, w_kvb.astype(cfg.dtype))
        return dense(cfg.hidden_size, (HEADS, HEAD_DIM, EMBED), "o_proj", axis=(-2, -1))(o), aux


# --------------------------------------------------------------------- MLPs


class Xing4MLP(nn.Module):
    """SwiGLU of ``width``: the dense layers' MLP and the shared expert."""
    cfg: Xing4Config
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg

        def dense(features, names, name):
            return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            kernel_init=_logical(nn.initializers.lecun_normal(), names), name=name)

        h = nn.silu(dense(self.width, (EMBED, MLP), "gate_proj")(x)) * dense(self.width, (EMBED, MLP), "up_proj")(x)
        return dense(cfg.hidden_size, (MLP, EMBED), "down_proj")(h)


class Xing4MoE(nn.Module):
    """The expert block over a batch ``x`` [B, S, C]: sigmoid router with a
    selection bias, the routed experts through the dropless dispatch, the
    shared expert beside them.  ``token_mask`` [B, S]: slots that carry no
    token go to no routed expert.  ``stacked_banks``: as ``moe.layer.MoE``'s."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, token_mask=None, stacked_banks=None):
        cfg = self.cfg
        with jax.named_scope("ds_moe_router"):
            logits = nn.Dense(cfg.n_routed_experts, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, "experts_gate")),
                              name="gate")(x.astype(jnp.float32))
            bias = None
            if cfg.topk_method == "noaux_tc":
                bias = self.param("e_score_correction_bias", nn.initializers.zeros_init(), (cfg.n_routed_experts, ),
                                  cfg.param_dtype)
        experts = ExpertsFFN(num_experts=cfg.n_routed_experts, hidden_size=cfg.hidden_size,
                             intermediate_size=cfg.moe_intermediate_size, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="experts")
        bank, layer = (experts.bank(), None) if stacked_banks is None else stacked_banks
        with jax.named_scope("ds_moe_grouped"):
            out, _, exp_counts = dropless_dispatch(x.astype(cfg.dtype), logits, bank, cfg.num_experts_per_tok,
                                                   token_mask, None, layer, cfg.norm_topk_prob, cfg.scoring_func,
                                                   bias, float(cfg.routed_scaling_factor))
        self.sow("intermediates", "exp_counts", exp_counts)
        if cfg.n_shared_experts:
            out = out + Xing4MLP(cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
                                 name="shared_experts")(x).astype(jnp.float32)
        return out.astype(x.dtype)


# ---------------------------------------------------------------- the layer


def layer_forward(cfg: Xing4Config, sparse: bool, x, positions, attend, token_mask=None, stacked_banks=None):
    """One layer on the streams ``x`` [..., n, C], built in the calling
    module's scope so that the full-sequence model and the serving twin name
    the same parameters: ``attn_hc``, ``input_layernorm``, ``self_attn``,
    ``mlp_hc``, ``post_attention_layernorm``, ``mlp``.  Returns (x, what
    ``attend`` handed back)."""

    def norm(name):
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)

    # built here, in the layer's scope, and called inside the sublayers'
    attn, attn_norm, mlp_norm = Xing4Attention(cfg, name="self_attn"), norm("input_layernorm"), \
        norm("post_attention_layernorm")
    block = Xing4MoE(cfg, name="mlp") if sparse else Xing4MLP(cfg, cfg.intermediate_size, name="mlp")
    x, aux = HyperConnection(cfg, name="attn_hc")(x, lambda u: attn(attn_norm(u), positions, attend))

    def mlp(u):
        h = mlp_norm(u)
        if not sparse:
            return block(h), None
        h3 = h if h.ndim == 3 else h.reshape((1, -1, h.shape[-1]))
        mask = None if token_mask is None else token_mask.reshape(h3.shape[:2])
        return block(h3, mask, stacked_banks).reshape(h.shape), None

    x, _ = HyperConnection(cfg, name="mlp_hc")(x, mlp)
    return x, aux


def embed_streams(cfg: Xing4Config, input_ids):
    """``X_0``: ``hc_mult`` copies of the token's embedding, [..., n, C]."""
    embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)), name="embed_tokens")
    x = embed(input_ids)
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (cfg.hc_mult, cfg.hidden_size))


def head_logits(cfg: Xing4Config, x):
    """The streams added, the final norm, the head: [..., n, C] -> [..., vocab]."""
    x = jnp.sum(x.astype(jnp.float32), axis=-2).astype(cfg.dtype)
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


class _DenseLayer(nn.Module):
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, positions, attend):
        return layer_forward(self.cfg, False, x, positions, attend)[0]


class _SparseLayer(nn.Module):
    """A scan's body: ``(x, None) -> (x, None)``."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, _, positions):
        cfg = self.cfg
        return layer_forward(cfg, True, x, positions, lambda *a: (expanded_attention(cfg, *a), None))[0], None


class Xing4ForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab]``: the
    full-sequence model, the expanded attention in jnp."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = embed_streams(cfg, input_ids)
        attend = lambda *a: (expanded_attention(cfg, *a), None)  # noqa: E731
        for i in range(cfg.first_k_dense_replace):
            x = _DenseLayer(cfg, name=f"dense_layers_{i}")(x, positions, attend)
        if cfg.num_sparse_layers:
            blocks = nn.scan(_SparseLayer, variable_axes={"params": 0, "intermediates": 0},
                             split_rngs={"params": True}, in_axes=(0, nn.broadcast), length=cfg.num_sparse_layers,
                             metadata_params={nn.PARTITION_NAME: LAYERS})
            x, _ = blocks(cfg, name="layers")(x, jnp.arange(cfg.num_sparse_layers), positions)
        return head_logits(cfg, x)
