"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder (ref:
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning ``config.json``,
``model_type`` ``phi4flash``).  No positional encoding anywhere.

``L`` layers (``L % 4 == 0``, ``L >= 8``) of five kinds:

* ``i < L/2``: even ``i`` **Mamba**, odd ``i`` **window attention** (the
  self-decoder);
* ``i = L/2``: Mamba, whose scan output before the gate is kept as the
  **memory** ``m`` (one vector of ``d_inner`` a position);
* ``i = L/2 + 1``: **full attention**, whose keys and values are the
  **shared cache**;
* ``i >= L/2 + 2``: even ``i`` **gated memory unit**, odd ``i``
  **cross-attention** to the shared cache (the cross-decoder).

Every layer: ``h = x + mixer(LN1(x))``, ``out = h + W_down(silu(g) * u)``
with ``[g, u] = W_gate_up LN2(h)``; LayerNorm with weight and bias; a final
LayerNorm; logits ``x E^T`` with the embedding ``E``.

* Mamba-1: ``[u, z] = W_in x``; ``u <- silu(conv1d_causal(u, k=4) + b)``;
  ``[dt_r, B, C] = W_x u``; ``dt = softplus(W_dt dt_r + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(dt_t A) s_{t-1} + dt_t B_t u_t``, ``y_t = C_t .
  s_t + D u_t``; output ``W_out (y * silu(z))``.
* Gated memory unit: ``W_2 (m_t * silu(W_1 x_t))``.
* Differential attention (window, full and cross alike; ``d`` = 64): query
  heads ``2i, 2i+1`` are ``q1_i, q2_i``; key heads ``2j, 2j+1`` are ``k1_j,
  k2_j``, value heads ``v1_j, v2_j``; ``a1 = softmax(q1 k1^T / sqrt(d)) [v1 |
  v2]``, ``a2`` likewise; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0``
  with ``l0 = 0.8 - 0.6 exp(-0.3 i)`` for layer ``i``; a head pair gives
  ``(1 - l0) RMSNorm_2d(a1 - lambda a2)``.  A window layer's query ``t`` sees
  keys ``t - window < j <= t``.  A cross layer projects queries only.

Keys and values are kept **packed**: a key pair is one head of ``2d`` = 128
lanes ``[k1 | k2]``, a value pair ``[v1 | v2]``, and a query is ``[q1 | 0]``
or ``[0 | q2]``, so that a dot over 128 lanes is ``q1 . k1`` or ``q2 . k2``
and an attention output is already ``[v1 | v2]`` wide: the serving twin's
pages (``models/phi4flash_cache.py``) are then whole tiles of the paged
kernel.  This file is the full-sequence model (parity tests, the parameter
tree the benchmark fills); every parameter is shared with the twin.
"""

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from .llama import EMBED, LAYERS, VOCAB, _logical


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """Fields carry the published key names; the Mamba sizes are the
    family's convention, which the published config does not state."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    hidden_act: str = "silu"
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None       # None: ceil(hidden_size / 16)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"   # reference | flash (the serving twin's paged kernel)

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError("the layer pattern needs num_hidden_layers % 4 == 0 and at least 8")
        if not self.tie_word_embeddings or self.mlp_bias or self.lm_head_bias or self.hidden_act != "silu" \
                or self.mb_per_layer != 2:
            raise ValueError("Phi4Flash is implemented for tied embeddings, no MLP or head bias, silu, mb_per_layer 2")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 or \
                (self.num_attention_heads // 2) % (self.num_key_value_heads // 2):
            raise ValueError("differential attention pairs the heads: even counts, query pairs a multiple of key pairs")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @property
    def n_self_pairs(self) -> int:
        """[Mamba, window attention] pairs before the middle."""
        return self.num_hidden_layers // 4

    @property
    def n_cross_pairs(self) -> int:
        """[gated memory unit, cross-attention] pairs after the middle."""
        return self.num_hidden_layers // 4 - 1


def lambda_init(layer):
    """``l0`` of the layer with index ``layer`` (traced in a scanned trunk)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


class LayerNorm(nn.Module):
    """LayerNorm in float32; parameters ``weight`` and ``bias``."""
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", _logical(nn.initializers.ones_init(), (EMBED, )), (x.shape[-1], ), self.param_dtype)
        b = self.param("bias", _logical(nn.initializers.zeros_init(), (EMBED, )), (x.shape[-1], ), self.param_dtype)
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(self.dtype)


class _Weight(nn.Module):
    """A norm's ``weight`` alone (the inner RMSNorm of differential attention)."""
    width: int
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("weight", nn.initializers.ones_init(), (self.width, ), self.param_dtype)


def _norm(cfg, name):
    return LayerNorm(cfg.layer_norm_eps, cfg.dtype, cfg.param_dtype, name=name)


def _dense(cfg, features, name, use_bias=False):
    return nn.Dense(features, use_bias=use_bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.lecun_normal(), name=name)


class Phi4FlashMLP(nn.Module):
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        g, u = jnp.split(_dense(cfg, 2 * cfg.intermediate_size, "gate_up_proj")(x), 2, axis=-1)
        return _dense(cfg, cfg.hidden_size, "down_proj")(nn.silu(g) * u)


# -------------------------------------------------------------------- Mamba


def ssm_scan(u, dt, a, b_mat, c_mat, d_skip, state, valid):
    """The selective scan over a chunk, state in and state out: one code path
    for a prefill chunk and for a decode token (a chunk of one).

    ``u``, ``dt``: [B, C, D] float32;  ``a``: [D, N];  ``b_mat``, ``c_mat``:
    [B, C, N];  ``d_skip``: [D];  ``state``: [B, N, D] float32 (the channel
    axis last, in the lanes);  ``valid``: [B, C], a position that carries no
    token leaves the state as it is.  Returns (``y`` [B, C, D] float32, the
    state after the row's last valid position).  A ``lax.scan`` over the
    positions: the carry is the one [B, N, D] state, never [B, C, D, N]."""
    a_t = a.T.astype(jnp.float32)[None]                               # [1, N, D]
    dt = jnp.where(valid[..., None], dt, 0.0)                         # exp(0 A) = 1 and 0 B u = 0

    def step(s, at):
        u_t, dt_t, b_t, c_t = at                                      # [B, D], [B, D], [B, N], [B, N]
        s = jnp.exp(dt_t[:, None, :] * a_t) * s + b_t[:, :, None] * (dt_t * u_t)[:, None, :]
        return s, jnp.sum(c_t[:, :, None] * s, axis=1)

    with jax.named_scope("ds_ssm_scan"):
        seq = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)    # noqa: E731
        state, y = jax.lax.scan(step, state, (seq(u), seq(dt), seq(b_mat), seq(c_mat)))
        return jnp.swapaxes(y, 0, 1) + d_skip.astype(jnp.float32) * u, state


class MambaMixer(nn.Module):
    """``__call__(x, state, tail, chunk_lens) -> (out, y, state, tail)``:
    ``state`` [B, N, D] float32 and ``tail`` [B, d_conv - 1, D], the last
    inputs of the convolution, are what a sequence carries between calls
    (zeros at its start); ``y`` is the scan's output before the gate, the
    memory of the middle layer.  The call is five parts in a row, of which
    ``in_project``, ``scan_inputs`` and ``gate_out`` are functions of a token
    alone and ``convolve`` and ``scan`` need a row's sequence: the serving
    twin runs the former on its flat token axis and the latter a row group at
    a time."""
    cfg: Phi4FlashConfig

    def setup(self):
        cfg = self.cfg
        d, n, k = cfg.d_inner, cfg.d_state, cfg.d_conv
        self.in_proj = _dense(cfg, 2 * d, "in_proj")
        self.conv_kernel = self.param("conv_kernel", nn.initializers.lecun_normal(), (k, d), cfg.param_dtype)
        self.conv_bias = self.param("conv_bias", nn.initializers.zeros_init(), (d, ), cfg.param_dtype)
        self.A_log = self.param("A_log", lambda *_: jnp.log(jnp.broadcast_to(jnp.arange(1.0, n + 1), (d, n))
                                                            ).astype(cfg.param_dtype))
        self.D = self.param("D", nn.initializers.ones_init(), (d, ), cfg.param_dtype)
        self.x_proj = _dense(cfg, cfg.rank + 2 * n, "x_proj")
        self.dt_proj = _dense(cfg, d, "dt_proj", use_bias=True)
        self.out_proj = _dense(cfg, cfg.hidden_size, "out_proj")

    def in_project(self, x):
        """``x`` [..., hidden] -> (the convolution's input ``u``, the gate ``z``), each [..., D]."""
        return jnp.split(self.in_proj(x), 2, axis=-1)

    def convolve(self, u, tail, chunk_lens):
        """``u`` [B, C, D], ``tail`` [B, d_conv - 1, D] -> (the convolved and activated ``u``, the new tail)."""
        c, k = u.shape[1], self.cfg.d_conv
        seen = jnp.concatenate([tail.astype(u.dtype), u], axis=1)                        # [B, k-1+C, D]
        conv = sum(seen[:, j:j + c].astype(jnp.float32) * self.conv_kernel[j].astype(jnp.float32) for j in range(k))
        # the inputs before the row's next position: rows n .. n + k - 2 of ``seen``
        tail = jnp.take_along_axis(seen, (chunk_lens[:, None] + jnp.arange(k - 1)[None, :])[:, :, None], axis=1)
        return nn.silu(conv + self.conv_bias.astype(jnp.float32)).astype(self.cfg.dtype), tail

    def scan_inputs(self, u):
        """The convolved ``u`` [..., D] -> (``dt`` [..., D] float32, ``B``, ``C`` [..., N])."""
        cfg = self.cfg
        dt_r, b_mat, c_mat = jnp.split(self.x_proj(u), [cfg.rank, cfg.rank + cfg.d_state], axis=-1)
        return jax.nn.softplus(self.dt_proj(dt_r).astype(jnp.float32)), b_mat, c_mat

    def scan(self, u, dt, b_mat, c_mat, state, chunk_lens):
        """``ssm_scan`` of a rectangle [B, C, ...] from ``state``: (``y`` [B, C, D] float32, the new state)."""
        valid = jnp.arange(u.shape[1])[None, :] < chunk_lens[:, None]
        return ssm_scan(u.astype(jnp.float32), dt, -jnp.exp(self.A_log.astype(jnp.float32)), b_mat, c_mat, self.D,
                        state, valid)

    def gate_out(self, y, z):
        """``y`` [..., D] float32 under the gate ``z`` -> [..., hidden]."""
        return self.out_proj((y * nn.silu(z.astype(jnp.float32))).astype(self.cfg.dtype))

    def __call__(self, x, state, tail, chunk_lens):
        u, z = self.in_project(x)
        u, tail = self.convolve(u, tail, chunk_lens)
        y, state = self.scan(u, *self.scan_inputs(u), state, chunk_lens)
        return self.gate_out(y, z), y, state, tail

    def fresh(self, batch):
        """(state, tail) of a sequence's start."""
        cfg = self.cfg
        return (jnp.zeros((batch, cfg.d_state, cfg.d_inner), jnp.float32),
                jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), cfg.dtype))


class GatedMemoryUnit(nn.Module):
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.cfg
        with jax.named_scope("ds_gmu"):
            gate = nn.silu(_dense(cfg, cfg.d_inner, "in_proj")(x).astype(jnp.float32))
            return _dense(cfg, cfg.hidden_size, "out_proj")((memory.astype(jnp.float32) * gate).astype(cfg.dtype))


# ---------------------------------------------------- differential attention


def dense_attention(q, k, v, scale, window=0):
    """Packed heads, whole sequence: ``q`` [B, S, H, D2], ``k``, ``v`` [B, S,
    H_kv, D2] -> [B, S, H, D2]; float32 softmax; ``window`` 0: causal only."""
    b, s, h, d2 = q.shape
    rep = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], rep, d2).astype(jnp.float32)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32)).reshape(b, s, h, d2)


class DiffAttention(nn.Module):
    """The projections and the combination of differential attention; how the
    packed queries meet the packed keys and values (a dense product here, the
    pages in the serving twin) is the caller's.  ``cross``: queries only."""
    cfg: Phi4FlashConfig
    cross: bool = False

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * d, "q_proj", use_bias=True)
        if not self.cross:
            self.k_proj = _dense(cfg, cfg.num_key_value_heads * d, "k_proj", use_bias=True)
            self.v_proj = _dense(cfg, cfg.num_key_value_heads * d, "v_proj", use_bias=True)
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj", use_bias=True)
        vec = lambda name: self.param(name, nn.initializers.normal(0.1), (d, ), cfg.param_dtype)  # noqa: E731
        self.lambda_q1, self.lambda_k1 = vec("lambda_q1"), vec("lambda_k1")
        self.lambda_q2, self.lambda_k2 = vec("lambda_q2"), vec("lambda_k2")
        self.sub_norm = _Weight(2 * d, cfg.param_dtype, name="sub_norm")

    @property
    def scale(self) -> float:
        return 1.0 / (self.cfg.head_dim**0.5)

    def queries(self, x):
        """``x`` [..., hidden] -> [..., H, 2d]: head ``2i`` is ``[q1_i | 0]``, head ``2i + 1`` is ``[0 | q2_i]``."""
        cfg = self.cfg
        d = cfg.head_dim
        q = self.q_proj(x).reshape(x.shape[:-1] + (cfg.num_attention_heads // 2, 2, d))
        zero = jnp.zeros_like(q[..., 0, :])
        q = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1), jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2)
        return q.reshape(x.shape[:-1] + (cfg.num_attention_heads, 2 * d))

    def keys_values(self, x):
        """Each [..., H_kv / 2, 2d]: pair ``j`` is ``[k1_j | k2_j]``, ``[v1_j | v2_j]``."""
        cfg = self.cfg
        shape = x.shape[:-1] + (cfg.num_key_value_heads // 2, 2 * cfg.head_dim)
        return self.k_proj(x).reshape(shape), self.v_proj(x).reshape(shape)

    def combine(self, a, layer):
        """``a`` [..., H, 2d], heads ``2i`` and ``2i + 1`` the two softmaxes'
        outputs of pair ``i`` -> the layer's output [..., hidden]."""
        cfg = self.cfg
        with jax.named_scope("ds_diff_combine"):
            f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
            l0 = lambda_init(layer)
            lam = jnp.exp(jnp.sum(f32(self.lambda_q1) * f32(self.lambda_k1))) - \
                jnp.exp(jnp.sum(f32(self.lambda_q2) * f32(self.lambda_k2))) + l0
            a = f32(a).reshape(a.shape[:-2] + (cfg.num_attention_heads // 2, 2, a.shape[-1]))
            diff = a[..., 0, :] - lam * a[..., 1, :]
            diff = diff * jax.lax.rsqrt(jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + cfg.layer_norm_eps)
            out = (1.0 - l0) * diff * f32(self.sub_norm())
            return self.o_proj(out.reshape(a.shape[:-3] + (-1, )).astype(cfg.dtype))


# -------------------------------------------------------------------- layers


class Phi4FlashLayer(nn.Module):
    """One layer around its mixer: ``layer(x, mix) -> (out, aux)`` where
    ``mix(mixer, LN1(x)) -> (mixed, aux)`` runs the mixer as the caller's
    trunk needs it (whole sequence, or through the serving twin's cache)."""
    cfg: Phi4FlashConfig
    kind: str   # mamba | attn | cross | gmu

    def setup(self):
        cfg = self.cfg
        self.input_layernorm = _norm(cfg, "input_layernorm")
        self.post_attention_layernorm = _norm(cfg, "post_attention_layernorm")
        self.mlp = Phi4FlashMLP(cfg, name="mlp")
        self.mixer = {"mamba": MambaMixer, "attn": DiffAttention, "cross": partial(DiffAttention, cross=True),
                      "gmu": GatedMemoryUnit}[self.kind](cfg, name="mixer")

    def __call__(self, x, mix):
        mixed, aux = mix(self.mixer, self.input_layernorm(x))
        h = x + mixed.astype(x.dtype)
        return h + self.mlp(self.post_attention_layernorm(h)).astype(x.dtype), aux


def _whole_mamba(mixer, h):
    out, y, _, _ = mixer(h, *mixer.fresh(h.shape[0]), jnp.full((h.shape[0], ), h.shape[1], jnp.int32))
    return out, y


class SelfPair(nn.Module):
    """Layers ``2j`` (Mamba) and ``2j + 1`` (window attention), a scan's body."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, j):
        cfg = self.cfg

        def window_attention(mixer, h):
            k, v = mixer.keys_values(h)
            return mixer.combine(dense_attention(mixer.queries(h), k, v, mixer.scale, cfg.sliding_window), 2 * j + 1), None

        x, _ = Phi4FlashLayer(cfg, "mamba", name="mamba")(x, _whole_mamba)
        x, _ = Phi4FlashLayer(cfg, "attn", name="attn")(x, window_attention)
        return x, None


class CrossPair(nn.Module):
    """Layers ``L/2 + 2 + 2j`` (gated memory unit) and ``+ 1`` (cross-attention)."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, j, memory, k, v):
        cfg = self.cfg
        first = cfg.num_hidden_layers // 2 + 2

        def cross_attention(mixer, h):
            return mixer.combine(dense_attention(mixer.queries(h), k, v, mixer.scale), first + 2 * j + 1), None

        x, _ = Phi4FlashLayer(cfg, "gmu", name="gmu")(x, lambda mixer, h: (mixer(h, memory), None))
        x, _ = Phi4FlashLayer(cfg, "cross", name="cross")(x, cross_attention)
        return x, None


def scan_pairs(pair_cls, length, n_broadcast=0):
    """``nn.scan`` of a pair of layers over the pairs' indices."""
    return nn.scan(pair_cls, variable_axes={"params": 0}, split_rngs={"params": True},
                   in_axes=(0, ) + (nn.broadcast, ) * n_broadcast, length=length,
                   metadata_params={nn.PARTITION_NAME: LAYERS})


def embed_tokens(cfg):
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)), name="embed_tokens")


def tied_logits(embed, x):
    """``x E^T`` in float32; ``x`` [..., hidden]."""
    return jnp.einsum("...h,vh->...v", x, embed.embedding.astype(x.dtype), preferred_element_type=jnp.float32)


class Phi4FlashForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab]`` (float32)."""
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        half = cfg.num_hidden_layers // 2
        embed = embed_tokens(cfg)
        x = embed(input_ids)
        x, _ = scan_pairs(SelfPair, cfg.n_self_pairs)(cfg, name="self_decoder")(x, jnp.arange(cfg.n_self_pairs))
        x, memory = Phi4FlashLayer(cfg, "mamba", name="mid_mamba")(x, _whole_mamba)

        def full_attention(mixer, h):
            k, v = mixer.keys_values(h)
            return mixer.combine(dense_attention(mixer.queries(h), k, v, mixer.scale), half + 1), (k, v)

        x, (k, v) = Phi4FlashLayer(cfg, "attn", name="mid_attn")(x, full_attention)
        x, _ = scan_pairs(CrossPair, cfg.n_cross_pairs, 3)(cfg, name="cross_decoder")(
            x, jnp.arange(cfg.n_cross_pairs), memory, k, v)
        return tied_logits(embed, _norm(cfg, "final_layernorm")(x))
