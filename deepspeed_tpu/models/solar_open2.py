"""Solar-Open2 (ref: https://huggingface.co/upstage/Solar-Open2-250B
``config.json``, ``model_type`` ``solar_open2``): every layer a mixer and an
expert block; the layers in ``gqa_layers`` (0, 4, 8, ...) are gated softmax
grouped-query attention with no positional encoding, the others Kimi Delta
Attention (KDA, as published with Kimi Linear: ``fla/layers/kda.py``), a
gated delta-rule linear attention whose state is a matrix a head.

  x += Mixer(RMSNorm(x));  x += MoE(RMSNorm(x));  final RMSNorm, an untied head.

* **KDA** (``kda_use_full_proj`` false: low-rank gate projections):
  ``q, k, v = silu(conv(W x))`` (causal depthwise convolution of
  ``short_conv_kernel_size``, no bias), heads ``[H, d]``; ``q, k <- l2norm`` a
  head, ``q <- q / sqrt(d)``; a channel's log-decay ``g = -exp(A_log[h]) *
  softplus(W_fb (W_fa x) + dt_bias)``; ``beta = sigmoid(W_b x)`` a head, times
  2 with ``kda_allow_neg_eigval``; a head's state ``S`` [keys, values]:

    S <- diag(exp(g_t)) S;   S <- S + beta_t k_t (v_t - S^T k_t)^T;   o_t = S^T q_t

  ``o <- RMSNorm_head(o) * sigmoid(W_gb (W_ga x))``; out ``W_o o``.
* **GQA**: ``softmax(q k^T / sqrt(d)) v`` causal, no rotary (``use_rope``
  false), no q/k norm; with ``use_gqa_gate`` ``o <- o * sigmoid(W_g x)``
  element-wise over ``[H x d]`` before ``W_o``.
* **MoE**: scores ``sigmoid(W_r x)``, a selection bias added for the choice
  alone, the ``num_experts_per_tok`` largest, their unbiased scores
  renormalised (``norm_topk_prob``) and times ``routed_scaling_factor``;
  SwiGLU experts of ``moe_intermediate_size``, ``n_shared_experts`` shared
  ones beside them (``moe/sharded_moe.dropless_dispatch``).

The recurrence has three forms that ``tests/unit/inference/test_solar_open2.py``
ties together: position by position (``kda_recurrent``), a chunk of positions
at a time with the state touched once a sub-block (``kda_chunk``, the WY / UT
transform; with ``continues``, a row of the batch going on from the row
before it) and one position on the slot arena in place
(``ops/kda_update.py``, the serving twin's decode rows).

**A chip's share.**  ``n_routed_experts`` is what the bank holds; where
``router_experts`` (the published count) is larger the layer holds experts
``first_expert .. first_expert + n_routed_experts - 1`` of a router that wide
(``dropless_dispatch(held=)``): the other chips of the group that shares the
layer hold the rest, the shared expert is computed here in full, and the
layer's output is this chip's part of the sum.  ``vocab_size`` is the rows of
the vocabulary held: a sliced vocabulary is a smaller vocabulary.

The layer pattern has a period (``[GQA, KDA, KDA, KDA]``); the trunk scans the
periods and compiles one, layer ``j`` of a period under ``periods/layer_<j>``
(``models/granite_hybrid.py``).  This file is the full-sequence model; every
parameter is shared with the serving twin (``models/solar_open2_cache.py``).
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED, VOCAB
from ..moe.experts import ExpertsFFN
from ..moe.sharded_moe import dropless_dispatch
from .granite_hybrid import _mamba_dt_bias, layer_name
from .llama import RMSNorm, _logical
from .llama_cache import scan_blocks
from .phi4flash import _Weight, dense_attention, embed_tokens
from .xing4 import HIGHEST, Xing4MLP, _hashable

#: positions a sub-block of the chunked form holds: inside one the decays are
#: taken pair by pair (``exp(G_t - G_s)``, never positive), and the state is
#: touched once a sub-block
KDA_SUB = 16


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """Fields carry the published key names."""
    vocab_size: int = 196608                    # rows of the vocabulary held
    hidden_size: int = 4096
    intermediate_size: int = 10240              # published; unused (first_k_dense_replace 0)
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: the published dict (short_conv_kernel_size, head_dim, num_heads, num_kv_heads), kept as sorted items
    linear_attn_config: Any = None
    gqa_interval: int = 3
    gqa_layers: Optional[Tuple[int, ...]] = None   # None: every (gqa_interval + 1)-th layer from 0
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320                 # experts the bank holds
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    #: the router's width where the bank holds a share of it, and the first expert held
    router_experts: Optional[int] = None
    first_expert: int = 0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0                 # published; unused (use_rope false)
    partial_rotary_factor: float = 1.0
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"           # reference | flash (the serving twin's paged kernel)

    def __post_init__(self):
        lin = dict(self.linear_attn_config or {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                                               "num_kv_heads": None})
        object.__setattr__(self, "linear_attn_config", _hashable(lin))
        step = self.gqa_interval + 1
        layers = range(0, max(self.num_hidden_layers, 1), step) if self.gqa_layers is None else self.gqa_layers
        object.__setattr__(self, "gqa_layers", tuple(int(i) for i in layers))
        if self.kda_use_full_proj:
            raise NotImplementedError("kda_use_full_proj: the full-rank gate projections are not built, only the "
                                      "low-rank ones (W_fb W_fa, W_gb W_ga) the published model uses")
        if self.use_rope:
            raise NotImplementedError("use_rope: the family's attention layers carry no positional encoding; a "
                                      "rotary variant is not built")
        if self.first_k_dense_replace:
            raise NotImplementedError("first_k_dense_replace > 0: leading dense layers are not built (the "
                                      "published model has none, and intermediate_size is unused)")
        if self.tie_word_embeddings:
            raise NotImplementedError("tie_word_embeddings is not built for this family")
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise NotImplementedError("linear_attn_config.num_kv_heads: grouped keys in the linear mixer are not "
                                      "built (published: null, as many as num_heads)")
        if self.router_width % self.n_routed_experts or \
                not 0 <= self.first_expert <= self.router_width - self.n_routed_experts:
            raise ValueError("the experts held, first_expert .. first_expert + n_routed_experts - 1, must lie inside "
                             "the router's router_experts and divide them")

    @property
    def linear(self) -> dict:
        return dict(self.linear_attn_config)

    @property
    def kda_heads(self) -> int:
        return self.linear["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        return self.linear["head_dim"]

    @property
    def kda_width(self) -> int:
        """Width of each of the linear mixer's ``q``, ``k``, ``v``."""
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_size(self) -> int:
        return self.linear["short_conv_kernel_size"]

    @property
    def router_width(self) -> int:
        return self.router_experts or self.n_routed_experts

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """``dropless_dispatch``'s ``held``: None where the bank holds every expert."""
        return None if self.router_width == self.n_routed_experts else (self.first_expert, self.n_routed_experts)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("gqa" if i in self.gqa_layers else "kda" for i in range(self.num_hidden_layers))

    @property
    def period(self) -> int:
        """The shortest period of the layer pattern."""
        kinds, n = self.layer_types, self.num_hidden_layers
        return next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))

    def per_period(self, kind: str, before: Optional[int] = None) -> int:
        """Layers of ``kind`` in a period (among its first ``before`` layers)."""
        return self.layer_types[:self.period if before is None else before].count(kind)

    def count(self, kind: str) -> int:
        return self.per_period(kind) * (self.num_hidden_layers // self.period)


def _norm(cfg, name):
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.lecun_normal(), name=name)


# ---------------------------------------------------------------- delta rule


def kda_recurrent(q, k, v, g, beta, state):
    """The recurrence position by position: ``q``, ``k``, ``g`` [B, C, H, K],
    ``v`` [B, C, H, V], ``beta`` [B, C, H], ``state`` [B, H, K, V], all
    float32 (``q`` scaled, ``q`` and ``k`` normalised, ``g <= 0``) -> (``o``
    [B, C, H, V], the state after the last position)."""

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        o_t, state = kda_update_reference(q_t, k_t, v_t, g_t, beta_t, state)
        return state, o_t

    state, o = jax.lax.scan(step, state, tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), state


def kda_update_reference(q, k, v, g, beta, state):
    """One position in ``jax.numpy`` (what ``ops/kda_update.kda_update``
    computes on the slot arena): ``q``, ``k``, ``g`` [B, H, K], ``v`` [B, H,
    V], ``beta`` [B, H], ``state`` [B, H, K, V] -> (``o`` [B, H, V], the new
    state)."""
    state = jnp.exp(g)[..., None] * state
    u = beta[..., None] * (v - jnp.sum(k[..., None] * state, axis=-2))
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * state, axis=-2), state


def _kda_sub_block_terms(q, k, v, g, beta):
    """What of a sub-block of ``c`` positions does not ask for the state it
    starts from, heads leading: ``q``, ``k``, ``g`` [B, H, c, K], ``v`` [B, H,
    c, V], ``beta`` [B, H, c].  With ``G`` the running sum of ``g`` inside the
    sub-block (the WY / UT transform of the delta rule):

      A = strict_lower(beta_t (k_t * exp(G_t - G_s)) . k_s);   (I + A) [W | U] = diag(beta) [k * exp(G) | v]

    -> (``W``, ``U``, ``q * exp(G)``, ``lower((q_t * exp(G_t - G_s)) . k_s)``,
    ``k * exp(G_c - G)``, ``exp(G_c)``).  Decays enter as differences ``G_t -
    G_s <= 0`` and as ``exp(G) <= 1`` alone, so nothing overflows however
    fast a channel forgets."""
    c, dk = q.shape[-2], q.shape[-1]
    cum = jnp.cumsum(g, axis=-2)                                            # G  [B, H, c, K]
    seen = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    pair = jnp.exp(jnp.where(seen[:, :, None], cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf))
    kk = jnp.sum(k[..., :, None, :] * pair * k[..., None, :, :], axis=-1)   # [B, H, t, s], 0 where s > t
    qk = jnp.sum(q[..., :, None, :] * pair * k[..., None, :, :], axis=-1)
    a = jnp.where(jnp.arange(c)[:, None] > jnp.arange(c)[None, :], beta[..., None] * kk, 0.0)
    into = jnp.exp(cum)                                                     # exp(G_t): the start's state seen from t
    rhs = beta[..., None] * jnp.concatenate([k * into, v], axis=-1)
    wu = jax.scipy.linalg.solve_triangular(a + jnp.eye(c, dtype=a.dtype), rhs, lower=True, unit_diagonal=True)
    to_end = jnp.exp(cum[..., -1:, :] - cum)                                # exp(G_c - G_s)
    return wu[..., :dk], wu[..., dk:], q * into, qk, k * to_end, jnp.exp(cum[..., -1, :])


def _kda_sub_block_from(state, w, u, q_into, qk, k_to_end, decay):
    """A sub-block from the ``state`` [B, H, K, V] it starts from, of what
    ``_kda_sub_block_terms`` gave: (the state after it, ``o`` [B, H, c, V]).

      U' = U - W S_0;   o = (q * exp(G)) S_0 + lower((q_t * exp(G_t - G_s)) . k_s) U'
      S_c = diag(exp(G_c)) S_0 + (k * exp(G_c - G))^T U'"""
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=HIGHEST, preferred_element_type=jnp.float32)  # noqa: E731
    u = u - mm("bhck,bhkv->bhcv", w, state)
    o = mm("bhck,bhkv->bhcv", q_into, state) + mm("bhts,bhsv->bhtv", qk, u)
    return decay[..., None] * state + mm("bhck,bhcv->bhkv", k_to_end, u), o


def _kda_sub_block(state, q, k, v, g, beta):
    """One sub-block of ``c`` positions, heads leading: (the state after it, ``o``)."""
    return _kda_sub_block_from(state, *_kda_sub_block_terms(q, k, v, g, beta))


def _kda_blocks(t, sub):
    """[B, C, H, ...] -> [n, B, H, sub, ...] float32, ``C`` padded to ``n`` whole sub-blocks."""
    b, c = t.shape[:2]
    n = -(-c // sub)
    t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, n * sub - c)) + ((0, 0), ) * (t.ndim - 2))
    t = t.reshape((b, n, sub) + t.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)


def _kda_unblock(o, c):
    """``o`` [n, B, H, sub, V] of the sub-blocks -> [B, C, H, V]."""
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)                           # [B, n, sub, H, V]
    return o.reshape((o.shape[0], -1) + o.shape[3:])[:, :c]


def _kda_rows_in_turn(blocks, state, continues):
    """The sub-blocks of a batch whose row ``i`` starts from the state row ``i
    - 1`` leaves where ``continues[i]`` (its ``state[i]`` is not read):
    ``blocks`` of ``[n, B, H, sub, ...]`` each, ``state`` [B, H, K, V] ->
    (the state every row leaves, ``o`` [n, B, H, sub, V]).  The rows go one
    after another; of a row, what its sub-blocks do not ask the state for is
    taken for all of them at once, and the state then passes through them in
    order."""

    def row(handed, at):
        start, goes_on, blocks = at
        terms = tuple(t[:, None] for t in _kda_sub_block_terms(*blocks))                     # [n, 1, H, ...]
        first = jnp.where(goes_on, handed, start)[None]
        last, o = jax.lax.scan(lambda s, at: _kda_sub_block_from(s, *at), first, terms)
        return last[0], (last[0], o[:, 0])

    by_row = tuple(jnp.moveaxis(t, 1, 0) for t in blocks)                                    # [B, n, H, sub, ...]
    _, (state, o) = jax.lax.scan(row, jnp.zeros_like(state[0]), (state, continues, by_row))
    return state, jnp.moveaxis(o, 0, 1)


def kda_chunk(q, k, v, g, beta, state, sub=KDA_SUB, continues=None):
    """A chunk of positions (of any length) with no loop over positions:
    ``kda_recurrent``'s arguments and results.  A position that carries no
    token has ``g`` 0, ``beta`` 0 and ``k`` 0 and leaves the state alone.
    The chunk goes ``sub`` positions at a time (``_kda_sub_block``), the state
    carried from one sub-block to the next, the rows of the batch side by
    side.

    ``continues`` [B] bool: row ``i`` of the batch goes on from row ``i - 1``
    where it is set, its ``state[i]`` not read; ``state`` comes back a row
    each, a continued row's being the one it handed on.  The rows then go one
    after another (``_kda_rows_in_turn``: the same sub-blocks, solves and
    order along a sequence), whether any continues another or none does: at
    four rows of 128 that costs 0.4 ms of a step of 34 where none does
    (PERF.md section 6, PR 50), less than a ``cond`` between the two forms."""
    with jax.named_scope("ds_kda_chunk"):
        blocks = tuple(_kda_blocks(t, sub) for t in (q, k, v, g, beta))
        if continues is None:
            state, o = jax.lax.scan(lambda s, at: _kda_sub_block(s, *at), state, blocks)
        else:
            state, o = _kda_rows_in_turn(blocks, state, continues)
        return _kda_unblock(o, q.shape[1]), state


def _a_log(key, shape, dtype):
    """The published initialisation: ``exp(A_log)`` log-uniform in [1, 16]
    (and ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], Mamba-2's: ``_mamba_dt_bias``)."""
    return jax.random.uniform(key, shape, jnp.float32, 0.0, math.log(16.0)).astype(dtype)


def l2norm(x):
    """``x / sqrt(sum x^2 + 1e-6)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


class KDAMixer(nn.Module):
    """The projections, the convolution, the gates and the gated norm of a
    KDA layer; how the recurrence runs between ``heads`` and ``finish``
    (whole sequence here, through the slot arena in the serving twin) is the
    caller's.  ``in_project`` is a function of a token alone; ``convolve``
    needs a row's sequence."""
    cfg: SolarOpen2Config

    def setup(self):
        cfg = self.cfg
        h, d, w = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_width
        self.q_proj, self.k_proj, self.v_proj = (_dense(cfg, w, n) for n in ("q_proj", "k_proj", "v_proj"))
        self.conv_kernel = self.param("conv_kernel", nn.initializers.lecun_normal(), (cfg.conv_size, 3 * w),
                                      cfg.param_dtype)
        self.f_a_proj, self.f_b_proj = _dense(cfg, d, "f_a_proj"), _dense(cfg, w, "f_b_proj")
        self.g_a_proj, self.g_b_proj = _dense(cfg, d, "g_a_proj"), _dense(cfg, w, "g_b_proj")
        self.b_proj = _dense(cfg, h, "b_proj")
        self.A_log = self.param("A_log", _a_log, (h, ), cfg.param_dtype)
        self.dt_bias = self.param("dt_bias", _mamba_dt_bias, (w, ), cfg.param_dtype)
        self.o_norm = _Weight(d, cfg.param_dtype, name="o_norm")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")

    def in_project(self, x, live):
        """``x`` [..., hidden], ``live`` [...] (whether the position carries a
        token) -> (the convolution's input ``[q | k | v]`` [..., 3 W], the
        log-decay ``g`` [..., H, K] float32, ``beta`` [..., H] float32, both 0
        where not ``live``, and the output gate's input [..., W])."""
        cfg = self.cfg
        f32 = jnp.float32
        qkv = jnp.concatenate([self.q_proj(x), self.k_proj(x), self.v_proj(x)], axis=-1)
        with jax.named_scope("ds_kda_gate"):
            f = self.f_b_proj(self.f_a_proj(x)).astype(f32) + self.dt_bias.astype(f32)
            f = f.reshape(f.shape[:-1] + (cfg.kda_heads, cfg.kda_head_dim))
            g = -jnp.exp(self.A_log.astype(f32))[:, None] * jax.nn.softplus(f)
            beta = jax.nn.sigmoid(self.b_proj(x).astype(f32)) * (2.0 if cfg.kda_allow_neg_eigval else 1.0)
            g, beta = jnp.where(live[..., None, None], g, 0.0), jnp.where(live[..., None], beta, 0.0)
        return qkv, g, beta, self.g_b_proj(self.g_a_proj(x))

    def convolve(self, qkv, tail, chunk_lens):
        """``qkv`` [B, C, 3 W] as ``in_project`` gave it, ``tail`` [B, conv_size
        - 1, 3 W] (the convolution's last inputs, zeros at a sequence's start)
        -> (the convolved and activated ``qkv``, the new tail)."""
        c, k = qkv.shape[1], self.cfg.conv_size
        seen = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)                    # [B, k-1+C, 3 W]
        conv = sum(seen[:, j:j + c].astype(jnp.float32) * self.conv_kernel[j].astype(jnp.float32) for j in range(k))
        # the inputs before the row's next position: rows n .. n + k - 2 of ``seen``
        tail = jnp.take_along_axis(seen, (chunk_lens[:, None] + jnp.arange(k - 1)[None, :])[:, :, None], axis=1)
        return nn.silu(conv).astype(self.cfg.dtype), tail

    def heads(self, qkv):
        """The convolved ``qkv`` [..., 3 W] -> ``q`` (normalised and scaled),
        ``k`` (normalised) [..., H, K] and ``v`` [..., H, V], float32."""
        cfg = self.cfg
        q, k, v = (t.reshape(t.shape[:-1] + (cfg.kda_heads, cfg.kda_head_dim)) for t in jnp.split(qkv, 3, axis=-1))
        return l2norm(q) * cfg.kda_head_dim**-0.5, l2norm(k), v.astype(jnp.float32)

    def finish(self, o, gate):
        """``o`` [..., H, V] float32 (the recurrence's output), ``gate`` as
        ``in_project`` gave it -> [..., hidden]."""
        cfg = self.cfg
        with jax.named_scope("ds_kda_gate"):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            o = o * self.o_norm().astype(jnp.float32)
            o = o.reshape(o.shape[:-2] + (cfg.kda_width, )) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return self.o_proj(o.astype(cfg.dtype))

    def fresh(self, batch):
        """(state, tail) of a sequence's start."""
        cfg = self.cfg
        return (jnp.zeros((batch, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32),
                jnp.zeros((batch, cfg.conv_size - 1, 3 * cfg.kda_width), cfg.dtype))


# ---------------------------------------------------------------- attention


class GatedAttention(nn.Module):
    """The projections of a grouped-query attention layer and its output
    gate; how queries meet keys and values (a dense product here, the pages
    in the serving twin) is the caller's.  No rotary, no q/k norm."""
    cfg: SolarOpen2Config

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * d, "q_proj")
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * d, "k_proj")
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * d, "v_proj")
        if cfg.use_gqa_gate:
            self.g_proj = _dense(cfg, cfg.num_attention_heads * d, "g_proj")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")

    def qkv(self, x):
        """``x`` [..., hidden] -> [..., H, d], [..., H_kv, d], [..., H_kv, d]."""
        cfg = self.cfg
        heads = lambda t, n: t.reshape(t.shape[:-1] + (n, cfg.head_dim))  # noqa: E731
        return (heads(self.q_proj(x), cfg.num_attention_heads), heads(self.k_proj(x), cfg.num_key_value_heads),
                heads(self.v_proj(x), cfg.num_key_value_heads))

    def out(self, a, x):
        """The attended values ``a`` [..., H, d] and the mixer's input ``x``
        (the gate's) -> [..., hidden]."""
        a = a.reshape(a.shape[:-2] + (-1, )).astype(self.cfg.dtype)
        if self.cfg.use_gqa_gate:
            a = a * jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32)).astype(a.dtype)
        return self.o_proj(a)


# ------------------------------------------------------------------ experts


class SolarOpen2MoE(nn.Module):
    """The expert block over a batch ``x`` [B, S, C]: a sigmoid router of
    ``router_width`` outputs with a selection bias, the experts held here
    through the dropless dispatch, the shared expert in full beside them.
    ``token_mask`` [B, S]: slots that carry no token go to no routed expert.
    ``stacked_banks``: (the banks of a scanned trunk [L, E, ...], the layer's
    index), read in place (``moe.layer.MoE``'s)."""
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x, token_mask=None, stacked_banks=None):
        cfg = self.cfg
        with jax.named_scope("ds_moe_router"):
            logits = nn.Dense(cfg.router_width, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, "experts_gate")),
                              name="gate")(x.astype(jnp.float32))
            bias = self.param("e_score_correction_bias", nn.initializers.zeros_init(), (cfg.router_width, ),
                              cfg.param_dtype)
        experts = ExpertsFFN(num_experts=cfg.n_routed_experts, hidden_size=cfg.hidden_size,
                             intermediate_size=cfg.moe_intermediate_size, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="experts")
        bank, layer = (experts.bank(), None) if stacked_banks is None else stacked_banks
        with jax.named_scope("ds_moe_grouped"):
            out, _, exp_counts = dropless_dispatch(x.astype(cfg.dtype), logits, bank, cfg.num_experts_per_tok,
                                                   token_mask, None, layer, cfg.norm_topk_prob, "sigmoid", bias,
                                                   float(cfg.routed_scaling_factor), cfg.held)
        self.sow("intermediates", "exp_counts", exp_counts)
        if cfg.n_shared_experts:
            out = out + Xing4MLP(cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
                                 name="shared_experts")(x).astype(jnp.float32)
        return out.astype(x.dtype)


# -------------------------------------------------------------------- layers


class SolarOpen2Layer(nn.Module):
    """One layer around its mixer: ``layer(x, mix, token_mask, stacked_banks)
    -> (out, aux)`` where ``mix(mixer, RMSNorm(x)) -> (mixed, aux)`` runs the
    mixer as the caller's trunk needs it.  ``x`` [B, S, C] or the flat axis
    [T, C] of a serving step (one group to the router)."""
    cfg: SolarOpen2Config
    kind: str   # kda | gqa

    def setup(self):
        cfg = self.cfg
        self.input_layernorm = _norm(cfg, "input_layernorm")
        self.post_attention_layernorm = _norm(cfg, "post_attention_layernorm")
        self.mixer = {"kda": KDAMixer, "gqa": GatedAttention}[self.kind](cfg, name="mixer")
        self.mlp = SolarOpen2MoE(cfg, name="mlp")

    def __call__(self, x, mix, token_mask=None, stacked_banks=None):
        mixed, aux = mix(self.mixer, self.input_layernorm(x))
        h = x + mixed.astype(x.dtype)
        u = self.post_attention_layernorm(h)
        u3 = u if u.ndim == 3 else u[None]
        mask = None if token_mask is None else token_mask.reshape(u3.shape[:2])
        return h + self.mlp(u3, mask, stacked_banks).reshape(u.shape).astype(x.dtype), aux


def _whole_kda(mixer, h):
    b, s = h.shape[:2]
    state, tail = mixer.fresh(b)
    qkv, g, beta, gate = mixer.in_project(h, jnp.ones((b, s), bool))
    qkv, _ = mixer.convolve(qkv, tail, jnp.full((b, ), s, jnp.int32))
    o, _ = kda_chunk(*mixer.heads(qkv), g, beta, state)
    return mixer.finish(o, gate), None


def _whole_gqa(mixer, h):
    q, k, v = mixer.qkv(h)
    return mixer.out(dense_attention(q, k, v, mixer.cfg.head_dim**-0.5), h), None


class _WholePeriod(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x, _):
        for j, kind in enumerate(self.cfg.layer_types[:self.cfg.period]):
            x, _ = SolarOpen2Layer(self.cfg, kind, name=layer_name(j))(x, _whole_kda if kind == "kda" else _whole_gqa)
        return x, None


def head_logits(cfg, x):
    """The final norm and the untied head over the vocabulary rows held."""
    x = _norm(cfg, "norm")(x)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


class SolarOpen2ForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab_size]``."""
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        n_periods = cfg.num_hidden_layers // cfg.period
        x = embed_tokens(cfg)(input_ids)
        x, _ = scan_blocks(_WholePeriod, n_periods, 0)(cfg, name="periods")(x, jnp.arange(n_periods))
        return head_logits(cfg, x)
