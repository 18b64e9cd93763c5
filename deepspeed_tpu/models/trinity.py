"""Trinity (ref: https://huggingface.co/arcee-ai/Trinity-Large-Preview
``config.json``, ``model_type`` ``afmoe``; transformers
``models/afmoe/modeling_afmoe.py``): gated grouped-query softmax attention of
two kinds that alternate by ``layer_types``, a sandwich of four RMSNorms a
layer, leading dense layers and then expert layers.

  x0 = E[ids] * sqrt(hidden_size)                                    (mup_enabled)
  a  = Attn_i(RMSNorm_in(x));      x = x + RMSNorm_post_attn(a)
  m  = FFN_i(RMSNorm_pre_mlp(x));  x = x + RMSNorm_post_mlp(m)
  logits = W_head RMSNorm_final(x)                                   (an untied head)

* **Attention**: ``q, k, v = W_q u, W_k u, W_v u`` (no bias), heads of
  ``head_dim``, grouped; ``q, k <- RMSNorm_head`` (one weight of ``head_dim``
  each); a ``sliding_attention`` layer turns ``q`` and ``k`` by their
  positions (rotary, ``rope_theta``, the whole head, the half-split form) and
  a query at ``t`` sees keys ``t - sliding_window + 1 .. t``; a
  ``full_attention`` layer carries no position term at all and sees keys ``0
  .. t``; ``o = softmax(q k^T / sqrt(head_dim)) v``; ``o <- o * sigmoid(W_g
  u)`` element-wise over ``[heads x head_dim]``; out ``W_o o``.
* **FFN**, layer ``i < num_dense_layers``: SwiGLU of ``intermediate_size``.
* **FFN**, else: scores ``sigmoid(W_r u)`` in float32, a selection bias
  ``expert_bias`` added for the choice alone, the ``num_experts_per_tok``
  largest, their unbiased scores renormalised (``route_norm``) and times
  ``route_scale``; SwiGLU experts of ``moe_intermediate_size`` and
  ``num_shared_experts`` shared ones beside them
  (``moe/sharded_moe.dropless_dispatch``).

**A chip's share** (as ``models/solar_open2.py``).  ``num_experts`` is what
the bank holds; where ``router_experts`` (the published count) is larger the
layer holds experts ``first_expert .. first_expert + num_experts - 1`` of a
router that wide (``dropless_dispatch(held=)``): the other chips of the group
that shares the layer hold the rest, the shared expert is computed here in
full, and the layer's output is this chip's part of the sum.  ``vocab_size``
is the rows of the vocabulary held.  **A cut in depth**: the layers run are
the ``num_dense_layers`` first of ``layer_types`` and then ``num_hidden_layers
- num_dense_layers`` from ``expert_layers_from`` on (None: straight on), so a
pipeline stage that holds published layers 0 and 8-11 keeps ``layer_types`` as
published.

The layers are unrolled under ``layers_<i>`` (the leading dense layers differ
from the rest and the published expert layers, 54 behind 6, are no whole
number of periods of four); a serving program traces a layer once a kind
(``models/phi4flash_cache.layer_traced_once``'s way).  This file is the
full-sequence model; every parameter is shared with the serving twin
(``models/trinity_cache.py``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED, VOCAB
from ..moe.experts import ExpertsFFN
from ..moe.sharded_moe import dropless_dispatch
from .llama import RMSNorm, _logical, apply_rope, rotary_embedding
from .phi4flash import _Weight, dense_attention, embed_tokens
from .xing4 import Xing4MLP

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """Fields carry the published key names."""
    vocab_size: int = 200192                    # rows of the vocabulary held
    hidden_size: int = 3072
    intermediate_size: int = 12288              # the dense layers' SwiGLU
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    #: a kind a published layer; None: every ``global_attn_every_n_layers``-th full, the others sliding
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 4096
    num_experts: int = 256                      # experts the bank holds
    num_shared_experts: int = 1
    num_experts_per_tok: int = 4
    route_norm: bool = True
    route_scale: float = 2.448
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    mup_enabled: bool = True
    hidden_act: str = "silu"
    load_balance_coeff: float = 5e-5            # published; training's
    use_grouped_mm: bool = True                 # published; how the published code multiplies its experts
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Any = None
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    #: the router's width where the bank holds a share of it, and the first expert held
    router_experts: Optional[int] = None
    first_expert: int = 0
    #: the published index of the first expert layer run here; None: ``num_dense_layers``
    expert_layers_from: Optional[int] = None
    #: serving: the most tokens one sequence feeds in a step (a run of chunks): the slack of a window layer's
    #: ring behind its window, and where ``SlotPagesGeometry.chunk_limit`` ends a run
    run_tokens: int = 512
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"           # reference | flash (the serving twin's paged kernel)

    def __post_init__(self):
        types = self.layer_types
        if types is None:
            every = self.global_attn_every_n_layers
            n = max(self.num_hidden_layers, (self.expert_layers_from or 0) + self.num_hidden_layers)
            types = [FULL if (i + 1) % every == 0 else SLIDING for i in range(n)]
        object.__setattr__(self, "layer_types", tuple(str(t) for t in types))
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise NotImplementedError(f"layer_types {sorted(unknown)}: only {SLIDING} and {FULL} are built")
        if self.score_func != "sigmoid":
            raise NotImplementedError(f"score_func {self.score_func!r}: the family's router scores by sigmoid; no "
                                      "other is built")
        for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups"):
            if getattr(self, key) != 1:
                raise NotImplementedError(f"{key} = {getattr(self, key)}: a group-limited choice of experts is not "
                                          "built (published: 1, every expert in one group)")
        if self.rope_scaling is not None:
            raise NotImplementedError("rope_scaling: scaled rotary frequencies are not built (published: null)")
        if self.hidden_act != "silu":
            raise NotImplementedError(f"hidden_act {self.hidden_act!r}: the MLPs and experts are SwiGLU")
        if self.tie_word_embeddings:
            raise NotImplementedError("tie_word_embeddings is not built for this family")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers must lie inside num_hidden_layers")
        if len(self.kinds) != self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers: too few for {self.num_dense_layers} "
                             f"dense layers and {self.num_hidden_layers - self.num_dense_layers} expert layers from "
                             f"layer {self._experts_from} on")
        if self.router_width % self.num_experts or \
                not 0 <= self.first_expert <= self.router_width - self.num_experts:
            raise ValueError("the experts held, first_expert .. first_expert + num_experts - 1, must lie inside "
                             "the router's router_experts and divide them")

    @property
    def _experts_from(self) -> int:
        return self.num_dense_layers if self.expert_layers_from is None else self.expert_layers_from

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each layer run here: the dense layers, then the expert layers."""
        first = self._experts_from
        return self.layer_types[:self.num_dense_layers] + \
            self.layer_types[first:first + self.num_hidden_layers - self.num_dense_layers]

    def count(self, kind: str) -> int:
        return self.kinds.count(kind)

    def index(self, layer: int) -> int:
        """Layer ``layer``'s place among the layers of its kind (its layer in the cache's arena of that kind)."""
        return self.kinds[:layer].count(self.kinds[layer])

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """``dropless_dispatch``'s ``held``: None where the bank holds every expert."""
        return None if self.router_width == self.num_experts else (self.first_expert, self.num_experts)


def _norm(cfg, name):
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.lecun_normal(), name=name)


def head_norm(x, weight, eps):
    """RMSNorm over a head's ``head_dim`` in float32; ``x`` [..., heads, head_dim]."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * weight.astype(jnp.float32)).astype(x.dtype)


class GatedAttention(nn.Module):
    """The projections, the head norms, the rotary turn and the output gate of
    an attention layer; how queries meet keys and values (a dense product
    here, the rings and the pages in the serving twin) is the caller's."""
    cfg: TrinityConfig

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * d, "q_proj")
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * d, "k_proj")
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * d, "v_proj")
        self.gate_proj = _dense(cfg, cfg.num_attention_heads * d, "gate_proj")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")
        self.q_norm = _Weight(d, cfg.param_dtype, name="q_norm")
        self.k_norm = _Weight(d, cfg.param_dtype, name="k_norm")

    def qkv(self, x, positions=None):
        """``x`` [..., hidden] -> [..., H, d], [..., H_kv, d], [..., H_kv, d],
        ``q`` and ``k`` normalised a head and, with ``positions`` [...] (a
        sliding layer's), turned by them."""
        cfg = self.cfg
        heads = lambda t, n: t.reshape(t.shape[:-1] + (n, cfg.head_dim))  # noqa: E731
        q = head_norm(heads(self.q_proj(x), cfg.num_attention_heads), self.q_norm(), cfg.rms_norm_eps)
        k = head_norm(heads(self.k_proj(x), cfg.num_key_value_heads), self.k_norm(), cfg.rms_norm_eps)
        if positions is not None:
            cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        return q, k, heads(self.v_proj(x), cfg.num_key_value_heads)

    def out(self, a, x):
        """The attended values ``a`` [..., H, d] and the mixer's input ``x``
        (the gate's) -> [..., hidden]."""
        a = a.reshape(a.shape[:-2] + (-1, )).astype(self.cfg.dtype)
        a = a * jax.nn.sigmoid(self.gate_proj(x).astype(jnp.float32)).astype(a.dtype)
        return self.o_proj(a)


class TrinityMoE(nn.Module):
    """The expert block over a batch ``x`` [B, S, C]: a sigmoid router of
    ``router_width`` outputs with a selection bias, the experts held here
    through the dropless dispatch, the shared expert in full beside them.
    ``token_mask`` [B, S]: slots that carry no token go to no routed expert."""
    cfg: TrinityConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.cfg
        with jax.named_scope("ds_moe_router"):
            logits = nn.Dense(cfg.router_width, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, "experts_gate")),
                              name="gate")(x.astype(jnp.float32))
            bias = self.param("expert_bias", nn.initializers.zeros_init(), (cfg.router_width, ), cfg.param_dtype)
        experts = ExpertsFFN(num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
                             intermediate_size=cfg.moe_intermediate_size, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="experts")
        with jax.named_scope("ds_moe_grouped"):
            out, _, exp_counts = dropless_dispatch(x.astype(cfg.dtype), logits, experts.bank(),
                                                   cfg.num_experts_per_tok, token_mask, None, None, cfg.route_norm,
                                                   "sigmoid", bias, float(cfg.route_scale), cfg.held)
        self.sow("intermediates", "exp_counts", exp_counts)
        if cfg.num_shared_experts:
            out = out + Xing4MLP(cfg, cfg.moe_intermediate_size * cfg.num_shared_experts,
                                 name="shared_experts")(x).astype(jnp.float32)
        return out.astype(x.dtype)


class TrinityLayer(nn.Module):
    """One layer around its mixer: ``layer(x, mix, token_mask) -> (out, aux)``
    where ``mix(mixer, RMSNorm(x)) -> (mixed, aux)`` runs the attention as the
    caller's trunk needs it.  ``x`` [B, S, C] or the flat axis [T, C] of a
    serving step (one group to the router).  ``dense``: a leading layer, a
    SwiGLU of ``intermediate_size`` in the experts' place."""
    cfg: TrinityConfig
    dense: bool

    def setup(self):
        cfg = self.cfg
        self.input_layernorm = _norm(cfg, "input_layernorm")
        self.post_attention_layernorm = _norm(cfg, "post_attention_layernorm")
        self.pre_mlp_layernorm = _norm(cfg, "pre_mlp_layernorm")
        self.post_mlp_layernorm = _norm(cfg, "post_mlp_layernorm")
        self.self_attn = GatedAttention(cfg, name="self_attn")
        self.mlp = Xing4MLP(cfg, cfg.intermediate_size, name="mlp") if self.dense else TrinityMoE(cfg, name="mlp")

    def __call__(self, x, mix, token_mask=None):
        mixed, aux = mix(self.self_attn, self.input_layernorm(x))
        h = x + self.post_attention_layernorm(mixed.astype(x.dtype))
        u = self.pre_mlp_layernorm(h)
        if self.dense:
            m = self.mlp(u)
        else:
            u3 = u if u.ndim == 3 else u[None]
            mask = None if token_mask is None else token_mask.reshape(u3.shape[:2])
            m = self.mlp(u3, mask).reshape(u.shape)
        return h + self.post_mlp_layernorm(m.astype(x.dtype)), aux


def embed(cfg, input_ids):
    """``E[ids]``, times ``sqrt(hidden_size)`` with ``mup_enabled``."""
    x = embed_tokens(cfg)(input_ids)
    return x * jnp.asarray(cfg.hidden_size**0.5, x.dtype) if cfg.mup_enabled else x


def head_logits(cfg, x):
    """The final norm and the untied head over the vocabulary rows held."""
    x = _norm(cfg, "norm")(x)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


def layer_name(i: int) -> str:
    return f"layers_{i}"


class TrinityForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab_size]``."""
    cfg: TrinityConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)

        def whole(kind):
            def mix(mixer, h):
                q, k, v = mixer.qkv(h, positions if kind == SLIDING else None)
                window = cfg.sliding_window if kind == SLIDING else 0
                return mixer.out(dense_attention(q, k, v, cfg.head_dim**-0.5, window), h), None
            return mix

        x = embed(cfg, input_ids)
        for i, kind in enumerate(cfg.kinds):
            x, _ = TrinityLayer(cfg, i < cfg.num_dense_layers, name=layer_name(i))(x, whole(kind))
        return head_logits(cfg, x)
