"""MiniCPM-SALA (ref: https://huggingface.co/openbmb/MiniCPM-SALA ``config.json``,
``model_type`` ``minicpm_sala``): a dense trunk under MiniCPM's muP scalings
whose mixers are, by ``mixer_types``, block-selected sparse attention
(``minicpm4``: InfLLM-V2 as MiniCPM4 publishes it, arXiv:2506.07900,
arXiv:2509.24663) in one layer of four and Lightning linear attention
(``lightning-attn``: Lightning Attention-2, arXiv:2401.04658) in the rest.

  x = scale_emb E[ids];  x += r Mixer_i(RMSNorm(x));  x += r SwiGLU_i(RMSNorm(x));
  logits = W_head (RMSNorm(x) / (hidden_size / dim_model_base)),   r = scale_depth / sqrt(mup_denominator)

``r`` keeps the published ``mup_denominator`` whatever ``num_hidden_layers``
is cut to, and a layer's decay its published index ``first_layer + i``.

* **lightning-attn** (``lightning_nh`` heads of ``lightning_head_dim``):
  ``q, k, v = W x`` (no bias); ``q, k <- RMSNorm_head`` (``qk_norm``); ``q, k
  <- rope`` (``lightning_use_rope``, the whole head); ``q <- q / sqrt(d)``; a
  head's state ``S`` [keys, values] float32, zero at a sequence's start:

    S_t = lambda_h S_{t-1} + k_t v_t^T;   o_t = S_t^T q_t

  with the constant ``lambda_h = exp(-s_h (1 - l / (L - 1) + 1e-5))``, ``s_h =
  2^(-8 h / H)``, ``h = 1 .. H``, ``l`` the layer's published index and ``L``
  the published depth (``decay_slopes``: the slope rule of the Lightning
  Attention-2 reference code; a constant, not a weight); ``o <-
  RMSNorm_head(o)`` (``use_output_norm``) ``* sigmoid(W_g x)``
  (``use_output_gate``); out ``W_o o``.
* **minicpm4** (grouped heads, no position term: ``attn_use_rope`` false):
  ``q, k <- RMSNorm_head``; scores ``q . k / sqrt(d)``.  With ``sparse`` =
  {kernel_size, kernel_stride, block_size, init_blocks, window_size, topk,
  dense_len}: a compressed key ``Kc[i] = mean(k[stride i .. stride i + kernel_size
  - 1])`` a key head, there once its last token is; the query at position
  ``t`` scores them, ``p_h = softmax_i(q_h . Kc_g[i] / sqrt(d))`` over the
  ``i`` that are there, summed over the heads of its key head ``g``; a block's
  score is the largest over the compressed keys that touch it (``B_g[b] =
  max_{i in [m b - 1, m b + m - 1]}``, ``m = block_size / kernel_stride``); it
  sees block 0 .. ``init_blocks - 1``, the ``window_size / block_size``
  blocks up to its own, and of the rest the ``topk`` of highest ``B_g`` (ties
  to the lower index), and attends the key rows ``s <= t`` of those blocks
  alone.  A query at ``t < dense_len`` sees every ``s <= t`` (by the query's
  position, not the call's length: a result does not depend on how a prompt
  was cut into chunks).  ``o <- o * sigmoid(W_g x)``
  (``attn_use_output_gate``); out ``W_o o``.

Lightning's recurrence has three forms that
``tests/unit/inference/test_minicpm_sala.py`` ties together: position by
position (``lightning_recurrent``), a block of positions at a time under the
decay mask (``lightning_chunk``) and one position on the slot arena in place
(``ops/lightning_update.py``, the serving twin's decode rows).

The trunk scans each run of consecutive layers of one kind (``cfg.runs``):
layer ``i`` is entry ``i - start`` of ``run_<j>/layer``.  This file is the
full-sequence model; every parameter is shared with the serving twin
(``models/minicpm_sala_cache.py``).
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED, VOCAB
from .llama import RMSNorm, _logical, apply_rope, rotary_embedding
from .llama_cache import scan_blocks
from .phi4flash import _Weight, embed_tokens
from .xing4 import HIGHEST, Xing4MLP, _hashable

KINDS = ("minicpm4", "lightning-attn")

#: the published ``mixer_types``
PUBLISHED_MIXERS = tuple(KINDS[c == "L"] for c in "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS")

#: MiniCPM4's published ``sparse_config``
PUBLISHED_SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "init_blocks": 1, "window_size": 2048,
                    "topk": 64, "dense_len": 8192}

#: positions a block of the chunked form holds
LIGHTNING_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    """Fields carry the published key names; ``sparse``, ``first_layer`` and
    ``published_layers`` say what ``config.json`` leaves to the publications
    and to a cut in depth."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    attention_bias: bool = False
    hidden_act: str = "silu"
    mixer_types: Optional[Tuple[str, ...]] = None       # None: the published list's first num_hidden_layers
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 524288
    #: the selection's sizes (MiniCPM4's ``sparse_config``), kept as sorted items
    sparse: Any = None
    #: the published index of layer 0 here, and the published depth: a Lightning layer's decay is its
    #: published index's, whatever run of the published layers this configuration holds
    first_layer: int = 0
    published_layers: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "reference"           # reference | flash (the serving twin's kernels)

    def __post_init__(self):
        mixers = PUBLISHED_MIXERS[:self.num_hidden_layers] if self.mixer_types is None else self.mixer_types
        object.__setattr__(self, "mixer_types", tuple(str(m) for m in mixers))
        object.__setattr__(self, "sparse", _hashable(dict(self.sparse or PUBLISHED_SPARSE)))
        if len(self.mixer_types) != self.num_hidden_layers or set(self.mixer_types) - set(KINDS):
            raise ValueError(f"mixer_types must name one of {KINDS} for each of the {self.num_hidden_layers} layers")
        if self.attn_use_rope or not self.lightning_use_rope:
            raise NotImplementedError("the published position schemes alone are built: none in the minicpm4 layers "
                                      "(attn_use_rope false), rotary in the lightning layers (lightning_use_rope true)")
        if self.lightning_scale != "1/sqrt(d)" or self.lightning_nkv != self.lightning_nh:
            raise NotImplementedError("lightning_scale other than '1/sqrt(d)' and grouped keys in the linear mixer "
                                      "(lightning_nkv != lightning_nh) are not built")
        if self.attention_bias or self.tie_word_embeddings or self.hidden_act != "silu":
            raise NotImplementedError("attention_bias, tie_word_embeddings and an activation other than silu are "
                                      "not built for this family")
        sp = self.sparse_config
        if sp["kernel_size"] != 2 * sp["kernel_stride"] or sp["block_size"] % sp["kernel_stride"] \
                or sp["window_size"] % sp["block_size"] or sp["dense_len"] % sp["block_size"]:
            raise NotImplementedError("the selection is built for compressed keys of two strides, blocks of whole "
                                      "strides, and a window and a dense_len of whole blocks (the published sizes)")

    @property
    def sparse_config(self) -> dict:
        return dict(self.sparse)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, first layer, layers) of each run of consecutive layers of one kind."""
        out, kinds = [], self.mixer_types
        for i, kind in enumerate(kinds):
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, i, 1])
        return tuple(tuple(r) for r in out)

    def count(self, kind: str, before: Optional[int] = None) -> int:
        """Layers of ``kind`` (among the first ``before``)."""
        return self.mixer_types[:before].count(kind)

    @property
    def list_blocks(self) -> int:
        """Blocks a one-token row's selection names at most: the forced and
        the chosen ones, or every block of a row under ``dense_len``."""
        sp = self.sparse_config
        return max(sp["init_blocks"] + sp["window_size"] // sp["block_size"] + sp["topk"],
                   sp["dense_len"] // sp["block_size"])


def _norm(cfg, name):
    return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)


def _dense(cfg, features, name):
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.lecun_normal(), name=name)


def _head_norm(x, weight, eps):
    """RMSNorm over a head's channels in float32: ``x`` [..., H, d], ``weight`` [d]."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


# ----------------------------------------------------------------- lightning


def decay_slopes(cfg: MiniCPMSALAConfig, layer):
    """``log lambda_h`` [H] float32 (``<= 0``) of layer ``layer`` here (an
    index, traced in a scanned run): ``-s_h (1 - l / (L - 1) + 1e-5)`` with
    ``s_h = 2^(-8 h / H)``, ``h = 1 .. H``, ``l = first_layer + layer`` and
    ``L`` the published depth."""
    h = cfg.lightning_nh
    slopes = jnp.exp2(-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    depth = cfg.published_layers or cfg.num_hidden_layers
    at = (cfg.first_layer + jnp.asarray(layer, jnp.float32)) / max(depth - 1, 1)
    return -slopes * (1.0 - at + 1e-5)


def lightning_update_reference(q, k, v, log_decay, state):
    """One position in ``jax.numpy`` (what ``ops/lightning_update.lightning_update``
    computes on the slot arena): ``q``, ``k`` [B, H, K], ``v`` [B, H, V],
    ``log_decay`` [H], ``state`` [B, H, K, V] -> (``o`` [B, H, V], the new state)."""
    state = jnp.exp(log_decay)[:, None, None] * state + k[..., None] * v[..., None, :]
    return jnp.sum(q[..., None] * state, axis=-2), state


def lightning_recurrent(q, k, v, log_decay, state):
    """The recurrence position by position: ``q``, ``k`` [B, C, H, K], ``v``
    [B, C, H, V], float32 (``q`` scaled) -> (``o`` [B, C, H, V], the state
    after the last position)."""

    def step(state, at):
        o, state = lightning_update_reference(*at, log_decay, state)
        return state, o

    state, o = jax.lax.scan(step, state, tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
    return jnp.swapaxes(o, 0, 1), state


def lightning_chunk(q, k, v, log_decay, state, lens=None, block=LIGHTNING_BLOCK):
    """A chunk of positions (of any length) with no loop over positions:
    ``lightning_recurrent``'s arguments and results.  ``lens`` [B]: a row's
    first ``lens`` positions carry a token; the others leave the state alone
    (their outputs mean nothing).  The chunk goes ``block`` positions at a
    time; inside a block, with ``n_t`` the tokens up to and with position
    ``t``,

      o_t = lambda^(n_t) q_t S_0 + sum_{s <= t} lambda^(n_t - n_s) (q_t . k_s) v_s
      S_c = lambda^(n_c) S_0 + sum_s lambda^(n_c - n_s) k_s v_s^T

    so every exponent is ``<= 0`` and nothing overflows however fast a head forgets."""
    with jax.named_scope("ds_lightning_chunk"):
        f32 = jnp.float32
        b, c = q.shape[:2]
        lens = jnp.full((b, ), c, jnp.int32) if lens is None else lens
        n = -(-c // block)

        def blocks(t):   # [B, C, H, d] -> [n, B, H, block, d]
            t = jnp.pad(t.astype(f32), ((0, 0), (0, n * block - c), (0, 0), (0, 0)))
            return jnp.moveaxis(t.reshape(b, n, block, *t.shape[2:]), (1, 3), (0, 2))

        live = jnp.arange(n * block)[None, :] < lens[:, None]                     # [B, n block]
        live = jnp.moveaxis(live.reshape(b, n, block), 1, 0)                     # [n, B, block]
        mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=HIGHEST, preferred_element_type=f32)  # noqa: E731
        lower = jnp.arange(block)[:, None] >= jnp.arange(block)[None, :]
        rate = log_decay.astype(f32)[None, :, None]                               # [1, H, 1]

        def one(state, at):
            q_b, k_b, v_b, live_b = at
            k_b = jnp.where(live_b[:, None, :, None], k_b, 0.0)
            count = jnp.cumsum(live_b.astype(f32), axis=-1)[:, None, :]           # n_t  [B, 1, block]
            pair = jnp.where(lower, jnp.exp(rate[..., None] * (count[..., :, None] - count[..., None, :])), 0.0)
            o = jnp.exp(rate * count)[..., None] * mm("bhck,bhkv->bhcv", q_b, state) \
                + mm("bhts,bhsv->bhtv", mm("bhtk,bhsk->bhts", q_b, k_b) * pair, v_b)
            to_end = jnp.exp(rate * (count[..., -1:] - count))[..., None]         # [B, H, block, 1]
            state = jnp.exp(rate * count[..., -1:])[..., None] * state + mm("bhck,bhcv->bhkv", k_b * to_end, v_b)
            return state, o

        state, o = jax.lax.scan(one, state.astype(f32), (blocks(q), blocks(k), blocks(v), live))
        o = jnp.moveaxis(o, (0, 2), (1, 3))                                       # [B, n, block, H, V]
        return o.reshape(b, n * block, *o.shape[3:])[:, :c], state


class LightningMixer(nn.Module):
    """The projections, norms, rotary and gate of a Lightning layer; how the
    recurrence runs between ``qkv`` and ``finish`` (whole sequence here,
    through the slot arena in the serving twin) is the caller's."""
    cfg: MiniCPMSALAConfig

    def setup(self):
        cfg = self.cfg
        d, w = cfg.lightning_head_dim, cfg.lightning_nh * cfg.lightning_head_dim
        self.q_proj, self.k_proj, self.v_proj = (_dense(cfg, w, n) for n in ("q_proj", "k_proj", "v_proj"))
        if cfg.qk_norm:
            self.q_norm, self.k_norm = (_Weight(d, cfg.param_dtype, name=n) for n in ("q_norm", "k_norm"))
        if cfg.use_output_norm:
            self.o_norm = _Weight(d, cfg.param_dtype, name="o_norm")
        if cfg.use_output_gate:
            self.g_proj = _dense(cfg, w, "g_proj")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")

    def qkv(self, x, positions):
        """``x`` [..., hidden], ``positions`` [...] -> ``q`` (normalised,
        rotated, scaled), ``k`` (normalised, rotated), ``v`` [..., H, d], float32."""
        cfg = self.cfg
        d = cfg.lightning_head_dim
        heads = lambda t: t.reshape(t.shape[:-1] + (cfg.lightning_nh, d))  # noqa: E731
        q, k, v = heads(self.q_proj(x)), heads(self.k_proj(x)), heads(self.v_proj(x))
        if cfg.qk_norm:
            q, k = _head_norm(q, self.q_norm(), cfg.rms_norm_eps), _head_norm(k, self.k_norm(), cfg.rms_norm_eps)
        cos, sin = rotary_embedding(positions, d, cfg.rope_theta)
        q, k = apply_rope(q.astype(jnp.float32), cos, sin), apply_rope(k.astype(jnp.float32), cos, sin)
        return q * d**-0.5, k, v.astype(jnp.float32)

    def finish(self, o, x):
        """``o`` [..., H, d] float32 (the recurrence's output) and the
        mixer's input ``x`` (the gate's) -> [..., hidden]."""
        cfg = self.cfg
        if cfg.use_output_norm:
            o = _head_norm(o, self.o_norm(), cfg.rms_norm_eps)
        o = o.reshape(o.shape[:-2] + (-1, ))
        if cfg.use_output_gate:
            o = o * jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32))
        return self.o_proj(o.astype(cfg.dtype))

    def fresh(self, batch):
        cfg = self.cfg
        return jnp.zeros((batch, cfg.lightning_nh, cfg.lightning_head_dim, cfg.lightning_head_dim), jnp.float32)


# -------------------------------------------------------------- the selection


def compressed_keys(k, sparse):
    """``Kc[i] = mean(k[stride i .. stride i + kernel_size - 1])`` of whole
    sequences ``k`` [B, S, G, d] -> [B, ceil(S / stride), G, d] float32; an
    entry whose last token lies past the sequence is not one a query may
    score (``select_blocks`` asks by position) and holds the mean of what is there."""
    stride = sparse["kernel_stride"]
    b, s = k.shape[:2]
    n = -(-s // stride)
    sums = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, (n + 1) * stride - s), (0, 0), (0, 0)))
    sums = sums.reshape(b, n + 1, stride, *k.shape[2:]).sum(axis=2)
    return (sums[:, :-1] + sums[:, 1:]) / sparse["kernel_size"]


def select_blocks(q, ckeys, qpos, sparse):
    """Which blocks each query sees: ``q`` [B, C, H, d], ``ckeys`` [B, N, G,
    d] (compressed key ``i`` of the row's sequence at index ``i``), ``qpos``
    [B, C] -> bool [B, C, G, ceil(N / m)], ``m = block_size / kernel_stride``.
    Scores and their softmax in float32."""
    with jax.named_scope("ds_sparse_select"):
        f32 = jnp.float32
        b, c, h, d = q.shape
        n, g = ckeys.shape[1:3]
        stride, m = sparse["kernel_stride"], sparse["block_size"] // sparse["kernel_stride"]
        nb = -(-n // m)
        s = jnp.einsum("bcgrd,bngd->bcgrn", q.reshape(b, c, g, h // g, d), ckeys.astype(q.dtype),
                       preferred_element_type=f32) * d**-0.5
        there = (stride * jnp.arange(n) + sparse["kernel_size"] - 1)[None, None, :] <= qpos[..., None]   # [B, C, N]
        there = there[:, :, None, None, :]
        top = jnp.max(jnp.where(there, s, -jnp.inf), axis=-1, keepdims=True)
        e = jnp.where(there, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        p = jnp.sum(e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30), axis=3)      # [B, C, G, N]
        # a block's score: the largest over the compressed keys m b - 1 .. m b + m - 1
        p = jnp.pad(p, ((0, 0), (0, 0), (0, 0), (1, m * nb + m - 1 - n)), constant_values=-1.0)
        score = jnp.maximum(p[..., :m * nb].reshape(b, c, g, nb, m).max(axis=-1), p[..., m:m * nb + m:m])
        blk = jnp.arange(nb)
        own = (qpos // sparse["block_size"])[..., None, None]                                  # [B, C, 1, 1]
        win = sparse["window_size"] // sparse["block_size"]
        score = jnp.where((blk >= sparse["init_blocks"]) & (blk <= own - win), score, -1.0)
        # the topk largest, ties to the lower index: everything over the k-th value, and of its equals the first
        kth = jax.lax.top_k(score, min(sparse["topk"], nb))[0][..., -1:]
        over = score > kth
        tie = (score == kth) & (kth >= 0)
        room = min(sparse["topk"], nb) - jnp.sum(over, axis=-1, keepdims=True)
        chosen = over | (tie & (jnp.cumsum(tie, axis=-1) <= room))
        forced = (blk < sparse["init_blocks"]) | (blk > own - win)
        dense = (qpos < sparse["dense_len"])[..., None, None]
        return (dense | forced | chosen) & (blk <= own)


def key_mask(blocks, n_keys, block_size):
    """The block mask [..., nb] as one over key rows [..., n_keys]."""
    return jnp.repeat(blocks, block_size, axis=-1)[..., :n_keys]


def masked_attention(q, k, v, qpos, blocks, block_size, scale):
    """Dense products: ``q`` [B, C, H, d] at positions ``qpos`` [B, C], ``k``,
    ``v`` [B, S, G, d] (key row ``s`` at position ``s``), ``blocks`` [B, C, G,
    nb] -> [B, C, H, d]; float32 softmax over the key rows ``s <= t`` of the
    blocks the query sees."""
    b, c, h, d = q.shape
    s, g = k.shape[1:3]
    scores = jnp.einsum("bqgrd,bkgd->bqgrk", q.reshape(b, c, g, h // g, d).astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    seen = key_mask(blocks, s, block_size) & (jnp.arange(s)[None, None, None, :] <= qpos[..., None, None])
    probs = jax.nn.softmax(jnp.where(seen[:, :, :, None, :], scores, -1e30), axis=-1)
    return jnp.einsum("bqgrk,bkgd->bqgrd", probs, v.astype(jnp.float32)).reshape(b, c, h, d)


class SparseAttention(nn.Module):
    """The projections, the q/k norms and the output gate of a ``minicpm4``
    layer; how queries meet keys and values (the selection over compressed
    keys and a dense product here, the pages and the indexer's cache in the
    serving twin) is the caller's.  No rotary."""
    cfg: MiniCPMSALAConfig

    def setup(self):
        cfg = self.cfg
        d = cfg.head_dim
        self.q_proj = _dense(cfg, cfg.num_attention_heads * d, "q_proj")
        self.k_proj = _dense(cfg, cfg.num_key_value_heads * d, "k_proj")
        self.v_proj = _dense(cfg, cfg.num_key_value_heads * d, "v_proj")
        if cfg.qk_norm:
            self.q_norm, self.k_norm = (_Weight(d, cfg.param_dtype, name=n) for n in ("q_norm", "k_norm"))
        if cfg.attn_use_output_gate:
            self.g_proj = _dense(cfg, cfg.num_attention_heads * d, "g_proj")
        self.o_proj = _dense(cfg, cfg.hidden_size, "o_proj")

    def qkv(self, x):
        """``x`` [..., hidden] -> ``q`` [..., H, d], ``k``, ``v`` [..., G, d]
        in the compute dtype, ``q`` and ``k`` normalised a head."""
        cfg = self.cfg
        heads = lambda t, n: t.reshape(t.shape[:-1] + (n, cfg.head_dim))  # noqa: E731
        q, k = heads(self.q_proj(x), cfg.num_attention_heads), heads(self.k_proj(x), cfg.num_key_value_heads)
        if cfg.qk_norm:
            q = _head_norm(q, self.q_norm(), cfg.rms_norm_eps).astype(cfg.dtype)
            k = _head_norm(k, self.k_norm(), cfg.rms_norm_eps).astype(cfg.dtype)
        return q, k, heads(self.v_proj(x), cfg.num_key_value_heads)

    def out(self, a, x):
        """The attended values ``a`` [..., H, d] and the mixer's input ``x``
        (the gate's) -> [..., hidden]."""
        a = a.reshape(a.shape[:-2] + (-1, )).astype(self.cfg.dtype)
        if self.cfg.attn_use_output_gate:
            a = a * jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32)).astype(a.dtype)
        return self.o_proj(a)


# -------------------------------------------------------------------- layers


class SALALayer(nn.Module):
    """One layer around its mixer: ``layer(x, mix) -> (out, aux)`` where
    ``mix(mixer, RMSNorm(x)) -> (mixed, aux)`` runs the mixer as the caller's
    trunk needs it.  ``x`` [B, S, C] or the flat axis [T, C] of a serving step."""
    cfg: MiniCPMSALAConfig
    kind: str

    def setup(self):
        cfg = self.cfg
        self.input_layernorm = _norm(cfg, "input_layernorm")
        self.post_attention_layernorm = _norm(cfg, "post_attention_layernorm")
        self.mixer = {"lightning-attn": LightningMixer, "minicpm4": SparseAttention}[self.kind](cfg, name="mixer")
        self.mlp = Xing4MLP(cfg, cfg.intermediate_size, name="mlp")

    def __call__(self, x, mix):
        r = self.cfg.residual_scale
        mixed, aux = mix(self.mixer, self.input_layernorm(x))
        h = x + (r * mixed.astype(jnp.float32)).astype(x.dtype)
        return h + (r * self.mlp(self.post_attention_layernorm(h)).astype(jnp.float32)).astype(x.dtype), aux


def _whole_lightning(cfg, layer):
    def mix(mixer, h):
        b, s = h.shape[:2]
        q, k, v = mixer.qkv(h, jnp.broadcast_to(jnp.arange(s), (b, s)))
        o, _ = lightning_chunk(q, k, v, decay_slopes(cfg, layer), mixer.fresh(b))
        return mixer.finish(o, h), None

    return mix


def _whole_sparse(cfg):
    def mix(mixer, h):
        b, s = h.shape[:2]
        sp = cfg.sparse_config
        q, k, v = mixer.qkv(h)
        qpos = jnp.broadcast_to(jnp.arange(s), (b, s))
        blocks = select_blocks(q, compressed_keys(k, sp).astype(k.dtype), qpos, sp)
        return mixer.out(masked_attention(q, k, v, qpos, blocks, sp["block_size"], cfg.head_dim**-0.5), h), None

    return mix


class _WholeLayer(nn.Module):
    """A scan's body over the layers of one run: ``(x, layer index) -> x``."""
    cfg: MiniCPMSALAConfig
    kind: str

    @nn.compact
    def __call__(self, x, layer):
        mix = _whole_sparse(self.cfg) if self.kind == "minicpm4" else _whole_lightning(self.cfg, layer)
        x, _ = SALALayer(self.cfg, self.kind, name="layer")(x, mix)
        return x, None


def embed(cfg, ids):
    return (embed_tokens(cfg)(ids).astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)


def head_logits(cfg, x):
    """The final norm, the muP division and the untied head."""
    x = _norm(cfg, "norm")(x)
    x = (x.astype(jnp.float32) / (cfg.hidden_size / cfg.dim_model_base)).astype(cfg.dtype)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


class MiniCPMSALAForCausalLM(nn.Module):
    """``apply(variables, input_ids [B, S]) -> logits [B, S, vocab_size]``."""
    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        x = embed(cfg, input_ids)
        for j, (kind, start, n) in enumerate(cfg.runs):
            x, _ = scan_blocks(_WholeLayer, n, 0)(cfg, kind, name=f"run_{j}")(x, start + jnp.arange(n))
        return head_logits(cfg, x)
