"""Granite 4.0-H (ref: https://huggingface.co/ibm-granite/granite-4.0-h-micro
``config.json``, ``model_type`` ``granitemoehybrid``): Mamba-2 layers with a
few grouped-query attention layers between them, no positional encoding
(``position_embedding_type: "nope"``), muP multipliers; the dense siblings
(``num_local_experts`` 0: the shared MLP is the layer's MLP, granite-4.0-h-micro)
and the siblings with routed experts beside the shared MLP (granite-4.0-h-small:
72 experts, ten a token; transformers ``modeling_granitemoehybrid.py``).

``x = embedding_multiplier * E[ids]``.  Layer ``i``, with ``u = RMSNorm(h)``:

  h = x + residual_multiplier * mixer_i(RMSNorm(x))
  shared = W_out(silu(a) * b),   [a | b] = W_in u          (2 x shared_intermediate_size)
  x = h + residual_multiplier * (routed + shared)          (routed = 0 in a dense sibling)

and logits ``RMSNorm(x) E^T / logits_scaling`` with the tied embedding.

* routed experts (``num_local_experts`` > 0): ``l = W_r u`` in float32, no
  bias; the ``num_experts_per_tok`` largest of ``l``; ``g`` = the softmax over
  those logits alone (= the softmax over all, the chosen renormalised, which
  is what ``moe/sharded_moe.dropless_dispatch`` computes); ``routed = sum_e
  g_e W2_e(silu(a_e) * b_e)``, ``[a_e | b_e] = W1_e u`` of ``2 x
  intermediate_size``.  Departures from the published code, in layout alone:
  it holds ``W1_e`` as one matrix ``[2 f, hidden]`` (``input_linear``) whose
  **first** half goes through the activation; here the halves are the bank's
  ``w_gate`` (the first, through ``silu``) and ``w_up`` (the second), ``[E,
  hidden, f]`` each, and ``W2_e`` is ``w_down`` ``[E, f, hidden]``
  (``moe/experts.ExpertsFFN``); the router is ``block_sparse_moe/router``.
  The shared MLP keeps the published fused ``input_linear``, first half
  through the activation.

* attention (``layer_types[i] == "attention"``): no bias, no rotary, causal
  ``softmax(attention_multiplier * q k^T) v``, grouped heads.
* Mamba-2 (``d_inner = mamba_n_heads * mamba_d_head``, one group): ``[z | xBC
  | dt] = W_in u``; ``xBC <- silu(conv1d_causal_depthwise(xBC, k) + b)``; ``[x
  | B | C]``; ``dt = softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)`` a
  head; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` a head (``[d_head,
  d_state]``, ``B`` and ``C`` shared by the heads); ``y_t = S_t C_t + D x_t``;
  ``y <- RMSNorm(y * silu(z))`` over all of ``d_inner`` with a weight; out
  ``W_out y``.

The recurrence is computed a block of positions at a time (``ssd_chunk``, the
state-space-duality form: inside a block matrix products, the state touched
once) or one position at a time (``ssd_update_reference``; on the serving
path the kernel ``ops/ssd_update.py``).

**A chip's share** (as ``models/solar_open2.py`` and ``models/trinity.py``).
``num_local_experts`` is what the bank holds; where ``router_experts`` (the
published count) is larger the layer holds experts ``first_expert ..
first_expert + num_local_experts - 1`` of a router that wide
(``dropless_dispatch(held=)``): the router keeps its outputs and its choices
a token, the weights are over all the chosen, the choices that fall on an
expert held elsewhere reach no expert, the shared MLP is computed here in
full, and the layer's output is this chip's part of the sum.  ``vocab_size``
is the rows of the tied vocabulary held: a sliced vocabulary is a smaller one.

The layer pattern has a period (10 at the published sizes: five Mamba
layers, attention, four Mamba layers), so the trunk scans the periods and
compiles one: layer ``j`` of a period lies under ``periods/layer_<j>``, its
parameters stacked [periods, ...].  (A scan inside the period's, over a run
of like layers, would have the outer loop slice each run's stack of weights
out of the periods' stack, a copy of every weight a step.)  This file is the
full-sequence model (parity tests, the parameter tree the benchmark fills);
every parameter is shared with the serving twin
(``models/granite_hybrid_cache.py``).
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED
from ..moe.experts import ExpertsFFN
from ..moe.sharded_moe import dropless_dispatch
from .llama import RMSNorm, _logical
from .llama_cache import scan_blocks
from .phi4flash import _Weight, dense_attention, embed_tokens

#: positions the recurrence takes at one step of its block form
SSD_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Fields carry the published key names."""
    vocab_size: int = 100352                          # rows of the tied vocabulary held
    hidden_size: int = 2048
    intermediate_size: int = 8192                     # a routed expert's width; a dense sibling's MLP
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Optional[Tuple[str, ...]] = None     # None: attention where i % 10 == 5
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256                       # the published kernel's block; changes no result
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    position_embedding_type: str = "nope"
    normalization_function: str = "rmsnorm"
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    num_local_experts: int = 0                        # experts the bank holds; 0: a dense sibling
    num_experts_per_tok: int = 0
    #: the router's width where the bank holds a share of it, and the first expert held
    router_experts: Optional[int] = None
    first_expert: int = 0
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"   # reference | flash (the serving twin's paged kernel)

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is None:
            kinds = ["attention" if i % 10 == 5 else "mamba" for i in range(self.num_hidden_layers)]
        object.__setattr__(self, "layer_types", tuple(kinds))
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError("layer_types names 'mamba' or 'attention' for each of num_hidden_layers layers")
        if self.num_local_experts:
            if not 0 < self.num_experts_per_tok <= self.router_width:
                raise ValueError("num_experts_per_tok must lie in 1 .. the router's width")
            if self.router_width % self.num_local_experts or \
                    not 0 <= self.first_expert <= self.router_width - self.num_local_experts:
                raise ValueError("the experts held, first_expert .. first_expert + num_local_experts - 1, must lie "
                                 "inside the router's router_experts and divide them")
        elif self.num_experts_per_tok or self.router_experts:
            raise ValueError("num_experts_per_tok and router_experts belong to routed experts: num_local_experts "
                             "is 0")
        if self.position_embedding_type != "nope" or self.normalization_function != "rmsnorm" or \
                self.hidden_act != "silu" or not self.tie_word_embeddings or self.attention_bias or \
                self.mamba_proj_bias or self.mamba_n_groups != 1:
            raise NotImplementedError("GraniteHybrid is implemented as published for granite-4.0-h-micro: no "
                                      "positional encoding, RMSNorm, silu, a tied embedding, no bias on the "
                                      "attention's or the Mamba projections, one group")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")
        if not self.num_local_experts and self.shared_intermediate_size != self.intermediate_size:
            raise ValueError("a dense GraniteHybrid's MLP is the shared MLP: shared_intermediate_size must equal "
                             "intermediate_size")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_local_experts

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """``dropless_dispatch``'s ``held``: None where the bank holds every expert."""
        return None if self.router_width == self.num_local_experts else (self.first_expert, self.num_local_experts)

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


class GraniteMLP(nn.Module):
    """The shared MLP (a dense sibling's only one)."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        a, b = jnp.split(_dense(cfg, 2 * cfg.shared_intermediate_size, "input_linear")(x), 2, axis=-1)
        return _dense(cfg, cfg.hidden_size, "output_linear")(nn.silu(a) * b)


class GraniteMoE(nn.Module):
    """The routed experts over a batch ``x`` [B, S, C]: a softmax router of
    ``router_width`` outputs, the experts held here through the dropless
    dispatch -> [B, S, C] float32, this share's part of the routed sum.
    ``token_mask`` [B, S]: slots that carry no token go to no expert.
    ``stacked_banks``: (the banks of a scanned trunk [L, E, ...], the layer's
    index), read in place (``moe.layer.MoE``'s)."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, token_mask=None, stacked_banks=None):
        cfg = self.cfg
        with jax.named_scope("ds_moe_router"):
            logits = nn.Dense(cfg.router_width, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, "experts_gate")),
                              name="router")(x.astype(jnp.float32))
        experts = ExpertsFFN(num_experts=cfg.num_local_experts, hidden_size=cfg.hidden_size,
                             intermediate_size=cfg.intermediate_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                             name="experts")
        bank, layer = (experts.bank(), None) if stacked_banks is None else stacked_banks
        with jax.named_scope("ds_moe_grouped"):
            out, _, exp_counts = dropless_dispatch(x.astype(cfg.dtype), logits, bank, cfg.num_experts_per_tok,
                                                   token_mask, None, layer, True, "softmax", None, 1.0, cfg.held)
        self.sow("intermediates", "exp_counts", exp_counts)
        return out


# ------------------------------------------------------------------ Mamba-2


def ssd_chunk(x, dt, a, b_mat, c_mat, state):
    """One block of positions ``1..Q`` of the recurrence, state in and out,
    with no loop over positions.  With ``L_t = sum_{s<=t} dt_s A``:

      y_t = exp(L_t) C_t S_0 + sum_{s<=t} exp(L_t - L_s) dt_s (C_t . B_s) x_s
      S_Q = exp(L_Q) S_0 + sum_s exp(L_Q - L_s) dt_s x_s (x) B_s

    ``x`` [B, Q, H, P];  ``dt`` [B, Q, H] float32, 0 at a position that
    carries no token (which then leaves the state alone);  ``a`` [H];
    ``b_mat``, ``c_mat`` [B, Q, N];  ``state`` [B, H, P, N] float32.  Returns
    (``y`` [B, Q, H, P] float32 without the ``D x`` term, the state after the
    block).  ``C B^T`` is one product for all heads; decay differences, which
    are never positive, and the state stay in float32."""
    with jax.named_scope("ds_ssd_chunk"):
        q = x.shape[1]
        f32 = jnp.float32
        cum = jnp.cumsum(dt * a.astype(f32), axis=1)                       # L_t  [B, Q, H]
        cum_h = jnp.swapaxes(cum, 1, 2)                                    # [B, H, Q]
        diff = cum_h[:, :, :, None] - cum_h[:, :, None, :]                 # L_t - L_s  [B, H, t, s]
        seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
        decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
        scores = jnp.einsum("btn,bsn->bts", c_mat, b_mat, preferred_element_type=f32)
        w = scores[:, None] * decay * jnp.swapaxes(dt, 1, 2)[:, :, None, :]
        y = jnp.einsum("bhts,bshp->bthp", w.astype(x.dtype), x, preferred_element_type=f32)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum("btn,bhpn->bthp", c_mat, state.astype(c_mat.dtype),
                                                     preferred_element_type=f32)
        to_end = (jnp.exp(cum[:, -1:] - cum) * dt)[..., None]              # exp(L_Q - L_s) dt_s  [B, Q, H, 1]
        state = jnp.exp(cum[:, -1])[:, :, None, None] * state + \
            jnp.einsum("bshp,bsn->bhpn", (x.astype(f32) * to_end).astype(x.dtype), b_mat, preferred_element_type=f32)
        return y, state


def ssd_blocks(x, dt, a, b_mat, c_mat, state, block=SSD_BLOCK):
    """``ssd_chunk`` over a sequence of any length, ``block`` positions at a time."""
    s = x.shape[1]
    if s <= block:
        return ssd_chunk(x, dt, a, b_mat, c_mat, state)
    n = -(-s // block)
    blocks = lambda t: jnp.swapaxes(  # noqa: E731
        jnp.pad(t, ((0, 0), (0, n * block - s)) + ((0, 0), ) * (t.ndim - 2)).reshape(
            (t.shape[0], n, block) + t.shape[2:]), 0, 1)

    def step(state, at):
        y, state = ssd_chunk(*at[:2], a, *at[2:], state)
        return state, y

    state, y = jax.lax.scan(step, state, (blocks(x), blocks(dt), blocks(b_mat), blocks(c_mat)))
    return jnp.swapaxes(y, 0, 1).reshape((x.shape[0], n * block) + x.shape[2:])[:, :s], state


def ssd_update_reference(xdt, decay, b_mat, c_mat, state):
    """One position of the recurrence in ``jax.numpy`` (what the kernel
    ``ops/ssd_update.ssd_update`` computes on the slot arena): ``xdt`` [B, H,
    P] = ``dt x``, ``decay`` [B, H] = ``exp(dt A)``, ``b_mat``, ``c_mat`` [B,
    N], ``state`` [B, H, P, N], all float32 -> (``y`` [B, H, P] without the
    ``D x`` term, the new state)."""
    state = decay[:, :, None, None] * state + xdt[..., None] * b_mat[:, None, None, :]
    return jnp.sum(state * c_mat[:, None, None, :], axis=-1), state


def _mamba_a_log(key, shape, dtype):
    """The published initialisation: ``A`` uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _mamba_dt_bias(key, shape, dtype):
    """... and ``softplus(dt_bias)`` log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    return jnp.log(jnp.expm1(dt)).astype(dtype)


class Mamba2Mixer(nn.Module):
    """The projections, the convolution and the gated norm of a Mamba-2
    layer; how the recurrence runs between ``project`` and ``finish`` (whole
    sequence here, through the slot arena in the serving twin) is the
    caller's.  ``project`` is ``in_project``, a function of a token alone,
    and ``convolve``, which needs a row's sequence: the serving twin runs the
    first on its flat token axis and the second a row group at a time."""
    cfg: GraniteHybridConfig

    def setup(self):
        cfg = self.cfg
        h = cfg.mamba_n_heads
        self.in_proj = _dense(cfg, 2 * cfg.d_inner + 2 * cfg.mamba_d_state + h, "in_proj")
        self.conv_kernel = self.param("conv_kernel", nn.initializers.lecun_normal(), (cfg.mamba_d_conv, cfg.conv_dim),
                                      cfg.param_dtype)
        if cfg.mamba_conv_bias:
            self.conv_bias = self.param("conv_bias", nn.initializers.zeros_init(), (cfg.conv_dim, ), cfg.param_dtype)
        self.dt_bias = self.param("dt_bias", _mamba_dt_bias, (h, ), cfg.param_dtype)
        self.A_log = self.param("A_log", _mamba_a_log, (h, ), cfg.param_dtype)
        self.D = self.param("D", nn.initializers.ones_init(), (h, ), cfg.param_dtype)
        self.norm = _Weight(cfg.d_inner, cfg.param_dtype, name="norm")
        self.out_proj = _dense(cfg, cfg.hidden_size, "out_proj")

    def in_project(self, u, live):
        """``u`` [..., hidden], ``live`` [...] (whether the position carries a
        token) -> (the gate ``z`` [..., d_inner], the convolution's input
        [..., conv_dim], ``dt`` [..., H] float32 and 0 where not ``live``)."""
        cfg = self.cfg
        z, xbc, dt = jnp.split(self.in_proj(u), [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias.astype(jnp.float32))
        return z, xbc, jnp.where(live[..., None], dt, 0.0)

    def convolve(self, xbc, tail, chunk_lens):
        """``xbc`` [B, C, conv_dim] as ``in_project`` gave it, ``tail`` [B,
        d_conv - 1, conv_dim] (the last inputs of the convolution, zeros at a
        sequence's start) -> (the convolved and activated ``xbc``, the new
        tail)."""
        cfg = self.cfg
        c, k = xbc.shape[1], cfg.mamba_d_conv
        seen = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)                    # [B, k-1+C, conv_dim]
        conv = sum(seen[:, j:j + c].astype(jnp.float32) * self.conv_kernel[j].astype(jnp.float32) for j in range(k))
        if cfg.mamba_conv_bias:
            conv = conv + self.conv_bias.astype(jnp.float32)
        # the inputs before the row's next position: rows n .. n + k - 2 of ``seen``
        tail = jnp.take_along_axis(seen, (chunk_lens[:, None] + jnp.arange(k - 1)[None, :])[:, :, None], axis=1)
        return nn.silu(conv).astype(cfg.dtype), tail

    def x_b_c(self, xbc):
        """The convolved ``xbc`` [..., conv_dim] -> ``x`` [..., H, P], ``B``, ``C`` [..., N]."""
        cfg = self.cfg
        x, b_mat, c_mat = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + cfg.mamba_d_state], axis=-1)
        return x.reshape(x.shape[:-1] + (cfg.mamba_n_heads, cfg.mamba_d_head)), b_mat, c_mat

    def project(self, u, tail, chunk_lens):
        """A rectangle through all three: ``u`` [B, C, hidden], ``tail`` [B,
        d_conv - 1, conv_dim] -> (``z`` [B, C, d_inner], ``x`` [B, C, H, P],
        ``B``, ``C`` [B, C, N], ``dt`` [B, C, H] float32 and 0 past
        ``chunk_lens``, the new tail)."""
        z, xbc, dt = self.in_project(u, jnp.arange(u.shape[1])[None, :] < chunk_lens[:, None])
        xbc, tail = self.convolve(xbc, tail, chunk_lens)
        return (z, ) + self.x_b_c(xbc) + (dt, tail)

    def neg_a(self):
        return -jnp.exp(self.A_log.astype(jnp.float32))

    def finish(self, y, x, z):
        """``y`` [..., H, P] float32 (the recurrence's output without the skip
        term), ``x`` as ``x_b_c`` gave it, ``z`` the gate -> [..., hidden]."""
        cfg = self.cfg
        with jax.named_scope("ds_gated_norm"):
            y = y + self.D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
            y = y.reshape(y.shape[:-2] + (cfg.d_inner, )) * nn.silu(z.astype(jnp.float32))
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.rms_norm_eps)
            y = (y * self.norm().astype(jnp.float32)).astype(cfg.dtype)
        return self.out_proj(y)

    def fresh(self, batch):
        """(state, tail) of a sequence's start."""
        cfg = self.cfg
        return (jnp.zeros((batch, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32),
                jnp.zeros((batch, cfg.mamba_d_conv - 1, cfg.conv_dim), cfg.dtype))


# ---------------------------------------------------------------- attention


class GraniteAttention(nn.Module):
    """The projections of an attention layer; how queries meet keys and
    values (a dense product here, the pages in the serving twin) is the
    caller's.  No rotary: ``position_embedding_type`` is ``nope``."""
    cfg: GraniteHybridConfig

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * d, "q_proj")
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * d, "k_proj")
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * d, "v_proj")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")

    def qkv(self, x):
        """``x`` [..., hidden] -> [..., H, d], [..., H_kv, d], [..., H_kv, d]."""
        cfg = self.cfg
        heads = lambda t, n: t.reshape(t.shape[:-1] + (n, cfg.head_dim))  # noqa: E731
        return (heads(self.q_proj(x), cfg.num_attention_heads), heads(self.k_proj(x), cfg.num_key_value_heads),
                heads(self.v_proj(x), cfg.num_key_value_heads))

    def out(self, a):
        return self.o_proj(a.reshape(a.shape[:-2] + (-1, )).astype(self.cfg.dtype))


# -------------------------------------------------------------------- layers


class GraniteHybridLayer(nn.Module):
    """One layer around its mixer: ``layer(x, mix, token_mask, stacked_banks)
    -> (out, aux)`` where ``mix(mixer, RMSNorm(x)) -> (mixed, aux)`` runs the
    mixer as the caller's trunk needs it.  ``x`` [B, S, C] or the flat axis
    [T, C] of a serving step (one group to the router); the last two
    arguments are the routed experts' (``GraniteMoE``)."""
    cfg: GraniteHybridConfig
    kind: str   # mamba | attention

    def setup(self):
        cfg = self.cfg
        self.input_layernorm = _norm(cfg, "input_layernorm")
        self.post_attention_layernorm = _norm(cfg, "post_attention_layernorm")
        self.shared_mlp = GraniteMLP(cfg, name="shared_mlp")
        if cfg.num_local_experts:
            self.block_sparse_moe = GraniteMoE(cfg, name="block_sparse_moe")
        self.mixer = {"mamba": Mamba2Mixer, "attention": GraniteAttention}[self.kind](cfg, name="mixer")

    def __call__(self, x, mix, token_mask=None, stacked_banks=None):
        cfg = self.cfg
        mixed, aux = mix(self.mixer, self.input_layernorm(x))
        h = x + (cfg.residual_multiplier * mixed).astype(x.dtype)
        u = self.post_attention_layernorm(h)
        if not cfg.num_local_experts:
            return h + (cfg.residual_multiplier * self.shared_mlp(u)).astype(x.dtype), aux
        u3 = u if u.ndim == 3 else u[None]
        mask = None if token_mask is None else token_mask.reshape(u3.shape[:2])
        m = self.block_sparse_moe(u3, mask, stacked_banks).reshape(u.shape) + self.shared_mlp(u).astype(jnp.float32)
        return h + (cfg.residual_multiplier * m).astype(x.dtype), aux


def _whole_mamba(mixer, h):
    state, tail = mixer.fresh(h.shape[0])
    z, x, b_mat, c_mat, dt, _ = mixer.project(h, tail, jnp.full((h.shape[0], ), h.shape[1], jnp.int32))
    y, _ = ssd_blocks(x, dt, mixer.neg_a(), b_mat, c_mat, state)
    return mixer.finish(y, x, z), None


def _whole_attention(mixer, h):
    q, k, v = mixer.qkv(h)
    return mixer.out(dense_attention(q, k, v, mixer.cfg.attention_multiplier)), None


def layer_name(j: int) -> str:
    return f"layer_{j}"


class _WholePeriod(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x, _):
        for j, kind in enumerate(self.cfg.layer_types[:self.cfg.period]):
            mix = _whole_mamba if kind == "mamba" else _whole_attention
            x, _ = GraniteHybridLayer(self.cfg, kind, name=layer_name(j))(x, mix)
        return x, None


def scaled_logits(cfg, embed, x):
    """``x E^T / logits_scaling`` in float32; ``x`` [..., hidden]."""
    logits = jnp.einsum("...h,vh->...v", x, embed.embedding.astype(x.dtype), preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


class GraniteHybridForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab]`` (float32)."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        n_periods = cfg.num_hidden_layers // cfg.period
        embed = embed_tokens(cfg)
        x = (cfg.embedding_multiplier * embed(input_ids)).astype(cfg.dtype)
        x, _ = scan_blocks(_WholePeriod, n_periods, 0)(cfg, name="periods")(x, jnp.arange(n_periods))
        return scaled_logits(cfg, embed, _norm(cfg, "norm")(x))
