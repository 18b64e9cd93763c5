"""Kimi-VL (ref: https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct
``config.json`` and the model's published ``modeling_kimi_vl.py``): a vision
tower (MoonViT) whose projected output takes the place of token embeddings,
in front of a latent-attention / expert language model of the family
``models/xing4.py`` serves, without that model's hyper-connected residual and
without its query bottleneck.

**Tower (``vision_config``).**  An image is ``h x w`` patches (both even),
row-major, each ``3 x p x p`` pixel values (``p = patch_size`` 14) scaled to
[-1, 1] by the client.  ``N = h w``, ``C_v = hidden_size`` 1152.

1. ``x = patches.reshape(N, 3 p p) W_pe + b_pe`` (the published ``Conv2d(3,
   C_v, p, stride p)`` as a matrix, channel-major) ``+ P(h, w)``.  ``P`` is
   the learned table ``[init_pos_emb_height, init_pos_emb_width, C_v]``
   interpolated to ``(h, w)`` as PyTorch's ``F.interpolate(mode="bicubic",
   align_corners=False)`` does: output index ``i`` of a side reads the source
   coordinate ``s = (i + 1/2) size_in / size_out - 1/2``, the four taps
   ``floor(s) - 1 .. floor(s) + 2`` (indices clamped to the border) with the
   cubic convolution kernel at ``A = -0.75``, ``t = s - floor(s)``:
   ``w_0 = ((A (t + 1) - 5 A)(t + 1) + 8 A)(t + 1) - 4 A``,
   ``w_1 = ((A + 2) t - (A + 3)) t^2 + 1``, ``w_2 = w_1(1 - t)``, ``w_3 =
   w_0(1 - t)``; the two sides multiply.  No antialiasing.  (Not
   ``jax.image.resize``'s cubic, whose ``A`` is -0.5.)  At ``(h, w)`` equal
   to the table's size the weights are (0, 1, 0, 0): the table itself.
2. 2D rotary on ``q`` and ``k`` a head (``d = C_v / heads`` 72): ``d / 4``
   frequencies ``f_i = 10000^(-4 i / d)``; for the patch in row ``y``, column
   ``x`` the complex pair ``(2 j, 2 j + 1)`` of the head is turned by ``x
   f_i`` for ``j = 2 i`` and by ``y f_i`` for ``j = 2 i + 1``.
3. ``num_hidden_layers`` pre-norm layers: ``[q | k | v] = LN_0(x) W_qkv +
   b_qkv``; ``x += W_o attn + b_o``, scores ``q . k d^-1/2``, softmax in
   float32 over the image's own patches, all of them (no causal mask); ``x +=
   W_1 gelu_tanh(W_0 LN_1(x) + b_0) + b_1``.  ``LN`` is LayerNorm with weight
   and bias, eps 1e-5.  Then ``final_layernorm``.
4. Merger (``merge_kernel_size`` 2 x 2): ``[h, w, C_v] -> [h w / 4, 4, C_v]``,
   the four patches of a 2 x 2 block in row-major order.
5. Projector: LayerNorm (eps 1e-5) on each of the four, concatenated to ``4
   C_v``; ``linear_2(gelu_erf(linear_1(.)))``, ``4 C_v -> 4 C_v -> hidden``,
   both with bias.
6. Merge: an image's rows take the place of ``E[id]`` at the positions whose
   id is ``media_placeholder_token_id``, in order: the prompt holds a run of
   exactly ``h w / 4`` placeholders an image.  Here the caller says which
   slot takes which row (``mm_index``, -1: the token's own embedding), so a
   *generated* token that happens to carry the placeholder's id is a token.

**Served on a padded bucket.**  ``MoonViT`` takes ``patches [P, 3 p p]`` and
the grid ``(h, w)`` as *values*, so one program serves every grid of a bucket
of ``P`` patches: positions, rotary angles and the merger's 2 x 2 gather are
computed from ``(h, w)`` inside it, and keys at ``N`` and behind are masked
inside the attention kernel (``ops/flash_attention.flash_attention_keylen``).
Rows of padding come out as garbage and are the caller's to leave unread.

**Language model.**  Pre-norm residual: ``x += attn(RMSNorm(x))``, ``x +=
mlp(RMSNorm(x))``.  Latent attention with ``q_lora_rank`` None (``[q_nope |
q_pe] = x W_q``), plain rotary (``rope_theta``, no scaling) on interleaved
pairs (the published code's order up to one permutation that ``q_pe`` and
``k_pe`` share and no score sees), scale ``qk_head_dim^-1/2``; layer 0 a
SwiGLU of ``intermediate_size``; after it sigmoid-routed experts with a
selection bias, ``num_experts_per_tok`` of ``n_routed_experts``, weights
renormalised and times ``routed_scaling_factor``, beside one ungated SwiGLU
of ``n_shared_experts x moe_intermediate_size``.  The projections, rotary,
router and expert block are ``models/xing4.py``'s.

Parameter tree: ``vision_tower`` (``patch_embed``, ``pos_emb``, ``layers``
under one scan, ``final_layernorm``), ``multi_modal_projector`` (``pre_norm``,
``linear_1``, ``linear_2``), ``language_model`` (``embed_tokens``,
``dense_layers_<i>``, ``layers``, ``norm``, ``lm_head``).  LayerNorm scales
are named ``weight`` (the benchmark's weights rule gives 1 to a norm's
``weight``).
"""

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..axes import EMBED, LAYERS, VOCAB
from .llama import RMSNorm, _logical
from .xing4 import (Xing4Attention, Xing4Config, Xing4MLP, Xing4MoE, _hashable, apply_rope_interleaved,
                    expanded_attention)

HIGHEST = jax.lax.Precision.HIGHEST
#: the cubic convolution kernel's constant in PyTorch's bicubic interpolation
BICUBIC_A = -0.75


@dataclasses.dataclass(frozen=True)
class MoonViTConfig:
    """The published ``vision_config``, its keys by name."""
    hidden_size: int = 1152
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    intermediate_size: int = 4304
    patch_size: int = 14
    init_pos_emb_height: int = 64
    init_pos_emb_width: int = 64
    merge_kernel_size: tuple = (2, 2)
    layer_norm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def patch_dim(self) -> int:
        return 3 * self.patch_size * self.patch_size

    @property
    def merge(self) -> int:
        """Patches that become one row of the language model."""
        return self.merge_kernel_size[0] * self.merge_kernel_size[1]


@dataclasses.dataclass(frozen=True)
class KimiVLConfig(Xing4Config):
    """The language model's published keys (``Xing4Config``'s names; no
    hyper-connection, so ``hc_mult`` is 1 and unused) and the tower's."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    q_lora_rank: Optional[int] = None
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.446
    hc_mult: int = 1
    rope_theta: float = 800000.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 0
    media_placeholder_token_id: int = 163605
    #: the published ``vision_config`` dict (kept as sorted items so the config hashes); None: its defaults
    vision_config: Any = None

    def __post_init__(self):
        super().__post_init__()
        vc = dict(self.vision_config or {})
        vc = {k: tuple(v) if isinstance(v, list) else v for k, v in vc.items()}
        object.__setattr__(self, "vision_config", _hashable(vc))

    @functools.cached_property
    def vision(self) -> MoonViTConfig:
        names = {f.name for f in dataclasses.fields(MoonViTConfig)}
        return MoonViTConfig(**{k: v for k, v in self.vision_config if k in names})


# ------------------------------------------------------------------ the tower


def bicubic_taps(index, size_in: int, size_out):
    """(taps [..., 4] int32, weights [..., 4] float32) of output ``index``
    along one side of ``size_in`` interpolated to ``size_out`` (a value)."""
    a = BICUBIC_A
    src = (index.astype(jnp.float32) + 0.5) * (size_in / jnp.maximum(size_out, 1).astype(jnp.float32)) - 0.5
    floor = jnp.floor(src)
    t = src - floor

    def outer(x):   # the kernel at distance 1 <= x <= 2
        return ((a * x - 5 * a) * x + 8 * a) * x - 4 * a

    def inner(x):   # at distance x <= 1
        return ((a + 2) * x - (a + 3)) * x * x + 1

    weights = jnp.stack([outer(t + 1), inner(t), inner(1 - t), outer(2 - t)], axis=-1)
    taps = jnp.clip(floor.astype(jnp.int32)[..., None] + jnp.arange(-1, 3), 0, size_in - 1)
    return taps, weights


def interpolated_positions(table, y, x, h, w):
    """``P(h, w)`` at the patches in rows ``y``, columns ``x`` [P]: sixteen
    gathers of the table's rows, weighted in float32.  table [H0, W0, C]."""
    h0, w0, c = table.shape
    ty, wy = bicubic_taps(y, h0, h)
    tx, wx = bicubic_taps(x, w0, w)
    flat = table.reshape(h0 * w0, c)
    out = jnp.zeros(y.shape + (c, ), jnp.float32)
    for i in range(4):
        for j in range(4):
            out = out + (wy[:, i] * wx[:, j])[:, None] * flat[ty[:, i] * w0 + tx[:, j]].astype(jnp.float32)
    return out


def rope_2d(vc: MoonViTConfig, y, x):
    """(cos, sin) [P, d / 2] float32: pair ``2 i`` of a head turns with the
    column, pair ``2 i + 1`` with the row, both at ``f_i = 10000^(-4 i / d)``."""
    d = vc.head_dim
    freqs = 1.0 / (10000.0**(jnp.arange(0, d, 4, dtype=jnp.float32) / d))
    angles = jnp.stack([x.astype(jnp.float32)[:, None] * freqs, y.astype(jnp.float32)[:, None] * freqs], axis=-1)
    angles = angles.reshape(y.shape[0], d // 2)
    return jnp.cos(angles), jnp.sin(angles)


def masked_attention(q, k, v, n_keys):
    """q, k, v [P, H, d]: every query over keys ``0 .. n_keys - 1``; float32."""
    f32 = jnp.float32
    scores = jnp.einsum("qhd,khd->hqk", q.astype(f32), k.astype(f32), precision=HIGHEST) * q.shape[-1]**-0.5
    scores = jnp.where(jnp.arange(k.shape[0])[None, None, :] < n_keys, scores, -1e30)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v.astype(f32), precision=HIGHEST).astype(q.dtype)


class LayerNorm(nn.Module):
    """LayerNorm in float32 with a ``weight`` and a ``bias``."""
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones_init(), (x.shape[-1], ), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (x.shape[-1], ), self.param_dtype)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(self.dtype)


class _ViTLayer(nn.Module):
    """A scan's body over the tower's layers: ``(x [P, C_v], None) -> (x, None)``."""
    vc: MoonViTConfig
    dtype: Any
    param_dtype: Any
    attention_impl: str

    @nn.compact
    def __call__(self, x, _, cos, sin, n_keys):
        vc = self.vc
        p, c = x.shape
        heads, d = vc.num_attention_heads, vc.head_dim

        def dense(features, name):
            return nn.Dense(features, use_bias=True, dtype=self.dtype, param_dtype=self.param_dtype, name=name)

        def norm(name):
            return LayerNorm(vc.layer_norm_eps, self.dtype, self.param_dtype, name=name)

        with jax.named_scope("ds_vit_attn"):
            qkv = dense(3 * c, "wqkv")(norm("norm0")(x)).reshape(p, 3, heads, d)
            q = apply_rope_interleaved(qkv[:, 0], cos[:, None, :], sin[:, None, :])
            k = apply_rope_interleaved(qkv[:, 1], cos[:, None, :], sin[:, None, :])
            if self.attention_impl == "flash" and p % 128 == 0:
                from ..ops.flash_attention import flash_attention_keylen
                o = flash_attention_keylen(q[None], k[None], qkv[None, :, 2], n_keys[None])[0]
            else:
                o = masked_attention(q, k, qkv[:, 2], n_keys)
            x = x + dense(c, "wo")(o.reshape(p, c))
        with jax.named_scope("ds_vit_mlp"):
            h = jax.nn.gelu(dense(vc.intermediate_size, "fc0")(norm("norm1")(x)), approximate=True)
            x = x + dense(c, "fc1")(h)
        return x, None


class MoonViT(nn.Module):
    """``(patches [P, 3 p p], grid [2] = (h, w)) -> [P, C_v]``: the tower on a
    bucket of ``P`` patches of which the first ``h w`` are the image's."""
    vc: MoonViTConfig
    dtype: Any
    param_dtype: Any
    attention_impl: str = "reference"

    @nn.compact
    def __call__(self, patches, grid):
        vc = self.vc
        h, w = grid[0], grid[1]
        index = jnp.arange(patches.shape[0])
        y, x = index // jnp.maximum(w, 1), index % jnp.maximum(w, 1)
        with jax.named_scope("ds_vit_embed"):
            table = self.param("pos_emb", nn.initializers.normal(0.02),
                               (vc.init_pos_emb_height, vc.init_pos_emb_width, vc.hidden_size), self.param_dtype)
            embed = nn.Dense(vc.hidden_size, use_bias=True, dtype=self.dtype, param_dtype=self.param_dtype,
                             name="patch_embed")(patches.astype(self.dtype))
            z = (embed.astype(jnp.float32) + interpolated_positions(table, y, x, h, w)).astype(self.dtype)
            cos, sin = rope_2d(vc, y, x)
        blocks = nn.scan(_ViTLayer, variable_axes={"params": 0}, split_rngs={"params": True},
                         in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast), length=vc.num_hidden_layers,
                         metadata_params={nn.PARTITION_NAME: LAYERS})
        z, _ = blocks(vc, self.dtype, self.param_dtype, self.attention_impl, name="layers")(
            z, jnp.arange(vc.num_hidden_layers), cos, sin, h * w)
        return LayerNorm(vc.layer_norm_eps, self.dtype, self.param_dtype, name="final_layernorm")(z)


def merge_patches(z, grid, merge_kernel_size):
    """The merger: [P, C_v] -> [P / 4, 4, C_v], row ``r`` the 2 x 2 block
    ``(r // (w / 2), r % (w / 2))`` in row-major order."""
    kh, kw = merge_kernel_size
    w = jnp.maximum(grid[1], kw)
    r = jnp.arange(z.shape[0] // (kh * kw))
    by, bx = r // (w // kw), r % (w // kw)
    dy, dx = jnp.repeat(jnp.arange(kh), kw), jnp.tile(jnp.arange(kw), kh)
    src = (by[:, None] * kh + dy) * w + bx[:, None] * kw + dx
    return z[jnp.minimum(src, z.shape[0] - 1)]


class Projector(nn.Module):
    """[R, 4, C_v] -> [R, hidden]."""
    vc: MoonViTConfig
    hidden_size: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, merged):
        width = merged.shape[1] * merged.shape[2]

        def dense(features, name):
            return nn.Dense(features, use_bias=True, dtype=self.dtype, param_dtype=self.param_dtype, name=name)

        z = LayerNorm(self.vc.layer_norm_eps, self.dtype, self.param_dtype, name="pre_norm")(merged)
        z = jax.nn.gelu(dense(width, "linear_1")(z.reshape(merged.shape[0], width)), approximate=False)
        return dense(self.hidden_size, "linear_2")(z)


# --------------------------------------------------------- the language model


def embed_tokens(cfg: KimiVLConfig, input_ids, mm_index=None, mm_rows=None):
    """``E[id]``, or, where ``mm_index`` is not negative, row ``mm_index`` of
    ``mm_rows`` [..., C] (its leading axes flattened): the merge."""
    embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)), name="embed_tokens")
    x = embed(input_ids)
    if mm_index is None:
        return x
    with jax.named_scope("ds_mm_merge"):
        rows = mm_rows.reshape(-1, mm_rows.shape[-1])[jnp.maximum(mm_index, 0)].astype(x.dtype)
        return jnp.where((mm_index >= 0)[..., None], rows, x)


def head_logits(cfg: KimiVLConfig, x):
    x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


def layer_forward(cfg: KimiVLConfig, sparse: bool, x, positions, attend, token_mask=None, stacked_banks=None):
    """One pre-norm layer on ``x`` [..., C], built in the calling module's
    scope so that the full-sequence model and the serving twin name the same
    parameters.  Returns (x, what ``attend`` handed back)."""

    def norm(name):
        return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name=name)

    y, aux = Xing4Attention(cfg, name="self_attn")(norm("input_layernorm")(x), positions, attend)
    x = x + y
    h = norm("post_attention_layernorm")(x)
    if not sparse:
        return x + Xing4MLP(cfg, cfg.intermediate_size, name="mlp")(h), aux
    h3 = h if h.ndim == 3 else h.reshape((1, -1, h.shape[-1]))
    mask = None if token_mask is None else token_mask.reshape(h3.shape[:2])
    return x + Xing4MoE(cfg, name="mlp")(h3, mask, stacked_banks).reshape(h.shape), aux


class _DenseLayer(nn.Module):
    cfg: KimiVLConfig

    @nn.compact
    def __call__(self, x, positions, attend):
        return layer_forward(self.cfg, False, x, positions, attend)[0]


class _SparseLayer(nn.Module):
    """A scan's body: ``(x, None) -> (x, None)``."""
    cfg: KimiVLConfig

    @nn.compact
    def __call__(self, x, _, positions):
        cfg = self.cfg
        return layer_forward(cfg, True, x, positions, lambda *a: (expanded_attention(cfg, *a), None))[0], None


class _LanguageModel(nn.Module):
    cfg: KimiVLConfig

    @nn.compact
    def __call__(self, input_ids, positions, mm_index, mm_rows):
        cfg = self.cfg
        x = embed_tokens(cfg, input_ids, mm_index, mm_rows)
        attend = lambda *a: (expanded_attention(cfg, *a), None)  # noqa: E731
        for i in range(cfg.first_k_dense_replace):
            x = _DenseLayer(cfg, name=f"dense_layers_{i}")(x, positions, attend)
        if cfg.num_sparse_layers:
            blocks = nn.scan(_SparseLayer, variable_axes={"params": 0, "intermediates": 0},
                             split_rngs={"params": True}, in_axes=(0, nn.broadcast), length=cfg.num_sparse_layers,
                             metadata_params={nn.PARTITION_NAME: LAYERS})
            x, _ = blocks(cfg, name="layers")(x, jnp.arange(cfg.num_sparse_layers), positions)
        return head_logits(cfg, x)


class VisionFront(nn.Module):
    """What the full-sequence model and the serving twin share: the tower,
    the merger and the projector under the names of the parameter tree, and
    ``encode_images``.  A subclass's ``setup`` calls ``setup_vision``."""
    cfg: KimiVLConfig

    def setup_vision(self):
        cfg = self.cfg
        self.vision_tower = MoonViT(cfg.vision, cfg.dtype, cfg.param_dtype, cfg.attention_impl)
        self.multi_modal_projector = Projector(cfg.vision, cfg.hidden_size, cfg.dtype, cfg.param_dtype)

    def encode_images(self, patches, grid):
        """One image on a bucket: (patches [P, 3 p p], grid [2]) -> rows [P /
        4, hidden], of which the first ``h w / 4`` are the image's."""
        z = self.vision_tower(patches, grid)
        with jax.named_scope("ds_mm_project"):
            return self.multi_modal_projector(merge_patches(z, grid, self.cfg.vision.merge_kernel_size))

    def init_vision(self):
        """Under ``init`` the tower's parameters are made too, whatever the call carries."""
        if self.is_initializing():
            vc = self.cfg.vision
            self.encode_images(jnp.zeros((4 * vc.merge, vc.patch_dim), self.cfg.dtype), jnp.array([4, vc.merge], jnp.int32))


class KimiVLForCausalLM(VisionFront):
    """``apply(variables, input_ids [B, S], mm_index=, mm_rows=) -> logits [B,
    S, vocab]``: the full-sequence model, the expanded attention in jnp;
    ``apply(variables, patches, grid, method="encode_images")`` the tower."""

    def setup(self):
        self.setup_vision()
        self.language_model = _LanguageModel(self.cfg)

    def __call__(self, input_ids, positions=None, mm_index=None, mm_rows=None):
        self.init_vision()
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        return self.language_model(input_ids, positions, mm_index, mm_rows)
