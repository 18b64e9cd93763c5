"""Paged-KV serving twins for the non-llama model families.

ref: deepspeed/inference/v2/model_implementations/{falcon,opt,phi,qwen_v2_moe}
— the reference serves these arches through FastGen with per-arch policy +
container classes; here each gets a cache twin whose param tree mirrors its
training model exactly (so converted HF checkpoints apply unchanged) and
whose attention goes through the shared ``paged_attention_core``
(models/llama_cache.py): chunked forward, KV arena threaded through, one
program for prefill / continuation / decode.  Like every twin they take a
step's row groups (models/llama_cache.py "Row groups"): norms, projections,
rope, OPT's position table, the MLPs and the experts run on the flat axis
[T, hidden], the page writes and the attention group by group.
"""

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..inference.v2.geometry import LinearGeometry, RingSummaryGeometry, SlotPagesGeometry
from .llama import (EMBED, HEAD_DIM, HEADS, KV_HEADS, MLP, VOCAB, LlamaConfig, RMSNorm, _logical, apply_rope,
                    rotary_embedding)
from .llama_cache import (LlamaAttentionCache, LlamaForCausalLMWithCache, flat_positions, flat_step, init_kv_cache,
                          lm_head, logits_as, paged_attention_core, sampled_rows, scan_blocks)
from .evabyte import EvaByteConfig
from .evabyte_cache import EvaByteForCausalLMWithCache
from .falcon import FalconConfig
from .granite_hybrid import GraniteHybridConfig
from .granite_hybrid_cache import GraniteHybridForCausalLMWithCache, slot_state_bytes
from .granite_hybrid_cache import init_cache as init_granite_hybrid_cache
from .solar_open2 import SolarOpen2Config
from .solar_open2_cache import SolarOpen2ForCausalLMWithCache
from .solar_open2_cache import init_cache as init_solar_open2_cache
from .solar_open2_cache import slot_state_bytes as solar_open2_state_bytes
from .minicpm_sala import MiniCPMSALAConfig
from .minicpm_sala_cache import MiniCPMSALAForCausalLMWithCache, SparseSlotPagesGeometry
from .minicpm_sala_cache import init_cache as init_minicpm_sala_cache
from .kimi_vl import KimiVLConfig
from .kimi_vl_cache import KimiVLForCausalLMWithCache
from .mixtral import MixtralConfig
from .mixtral_cache import MixtralForCausalLMWithCache
from .opt import OPTConfig
from .phi import PhiConfig, apply_partial_rope
from .phi4flash import Phi4FlashConfig
from .phi4flash_cache import Phi4FlashForCausalLMWithCache
from .phi4flash_cache import init_cache as init_phi4flash_cache
from .qwen2_moe import Qwen2MoeConfig, Qwen2MoeDenseMLP, Qwen2MoeSparseMLP
from .trinity import TrinityConfig
from .trinity_cache import TrinityForCausalLMWithCache
from .trinity_cache import geometry as trinity_geometry, init_cache as init_trinity_cache
from .xing4 import Xing4Config
from .xing4_cache import LatentPagesGeometry, Xing4ForCausalLMWithCache
from .xing4_cache import init_cache as init_xing4_cache, walk_rows as xing4_walk_rows


# ------------------------------------------------------------------- falcon


class FalconAttentionCache(nn.Module):
    cfg: FalconConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, x, positions, pages, block_table, start_pos, chunk_lens, layer):
        cfg = self.cfg
        H, KV = cfg.num_attention_heads, cfg.num_kv_heads
        D = cfg.hidden_size // H
        dense = partial(nn.DenseGeneral, use_bias=cfg.bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = dense(features=(H, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)),
                  name="q_proj")(x)
        k = dense(features=(KV, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="k_proj")(x)
        v = dense(features=(KV, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="v_proj")(x)
        slopes = None
        if cfg.alibi:
            # falcon-rw: alibi position bias instead of rotary (same folding
            # as models/falcon.py's training path)
            from .falcon import alibi_slopes
            slopes = jnp.asarray(alibi_slopes(H))
        else:
            cos, sin = rotary_embedding(positions, D, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        out, pages = paged_attention_core(self.groups, q, k, v, pages, layer, block_table, start_pos, chunk_lens,
                                          self.page_size, attention_impl=cfg.attention_impl, alibi_slopes=slopes)
        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=cfg.bias,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                              name="dense")(out)
        return out, pages


class FalconBlockCache(nn.Module):
    cfg: FalconConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg = self.cfg
        x, pages = carry
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)

        def mlp(mlp_in):
            ffn = cfg.ffn_hidden_size or cfg.hidden_size * 4
            h = nn.Dense(ffn, use_bias=cfg.bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)),
                         name="dense_h_to_4h")(mlp_in)
            return nn.Dense(cfg.hidden_size, use_bias=cfg.bias, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EMBED)),
                            name="dense_4h_to_h")(jax.nn.gelu(h, approximate=False))

        if not cfg.parallel_attn:
            # falcon-rw sequential residual: ln1 → attn → add; ln2 → mlp → add
            attn_in = ln(name="input_layernorm")(x)
            attn_out, pages = FalconAttentionCache(cfg, self.page_size, self.groups, name="self_attention")(
                attn_in, positions, pages, block_table, start_pos, chunk_lens, layer)
            h = x + attn_out
            return (h + mlp(ln(name="post_attention_layernorm")(h)), pages), None

        if cfg.num_ln_in_parallel_attn == 2:
            attn_in = ln(name="ln_attn")(x)
            mlp_in = ln(name="ln_mlp")(x)
        else:
            attn_in = ln(name="input_layernorm")(x)
            mlp_in = attn_in
        attn_out, pages = FalconAttentionCache(cfg, self.page_size, self.groups, name="self_attention")(
            attn_in, positions, pages, block_table, start_pos, chunk_lens, layer)
        return (x + attn_out + mlp(mlp_in), pages), None


class FalconForCausalLMWithCache(nn.Module):
    cfg: FalconConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="word_embeddings")
        x = embed(tokens)
        (x, cache), _ = scan_blocks(FalconBlockCache, cfg.num_hidden_layers)(cfg, self.page_size, groups, name="h")(
            (x, cache), jnp.arange(cfg.num_hidden_layers), positions, block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="ln_f")(x)
        return logits_as(lm_head(cfg, embed, x), input_ids, last_only), cache


# ---------------------------------------------------------------------- opt


class OPTAttentionCache(nn.Module):
    cfg: OPTConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, x, pages, block_table, start_pos, chunk_lens, layer):
        cfg = self.cfg
        H = cfg.num_attention_heads
        D = cfg.hidden_size // H
        dense = partial(nn.DenseGeneral, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        q = dense(features=(H, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)),
                  name="q_proj")(x)
        k = dense(features=(H, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="k_proj")(x)
        v = dense(features=(H, D), kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="v_proj")(x)
        out, pages = paged_attention_core(self.groups, q, k, v, pages, layer, block_table, start_pos, chunk_lens,
                                          self.page_size, attention_impl=cfg.attention_impl)
        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=True,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                              name="out_proj")(out)
        return out, pages


class OPTBlockCache(nn.Module):
    cfg: OPTConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg = self.cfg
        x, pages = carry
        ln = partial(nn.LayerNorm, epsilon=1e-5, dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        a_in = ln(name="self_attn_layer_norm")(x) if cfg.do_layer_norm_before else x
        a, pages = OPTAttentionCache(cfg, self.page_size, self.groups, name="self_attn")(
            a_in, pages, block_table, start_pos, chunk_lens, layer)
        h = x + a
        if not cfg.do_layer_norm_before:
            h = ln(name="self_attn_layer_norm")(h)
        m_in = ln(name="final_layer_norm")(h) if cfg.do_layer_norm_before else h
        m = nn.Dense(cfg.ffn_dim, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)), name="fc1")(m_in)
        m = nn.Dense(cfg.hidden_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EMBED)),
                     name="fc2")(jax.nn.relu(m))
        out = h + m
        if not cfg.do_layer_norm_before:
            out = ln(name="final_layer_norm")(out)
        return (out, pages), None


class OPTForCausalLMWithCache(nn.Module):
    cfg: OPTConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        proj_dim = cfg.word_embed_proj_dim or cfg.hidden_size
        embed = nn.Embed(cfg.vocab_size, proj_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        pos_embed = nn.Embed(cfg.max_position_embeddings + 2, cfg.hidden_size, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, embedding_init=nn.initializers.normal(0.02),
                             name="embed_positions")
        # pad-region positions can exceed the learned table (prefill chunk >
        # max_position): clamp — jnp.take would otherwise FILL (NaN)
        safe_pos = jnp.minimum(positions, cfg.max_position_embeddings - 1)
        x = embed(tokens)
        if proj_dim != cfg.hidden_size:
            x = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="project_in")(x)
        x = x + pos_embed(safe_pos + 2)
        (x, cache), _ = scan_blocks(OPTBlockCache, cfg.num_hidden_layers)(cfg, self.page_size, groups, name="layers")(
            (x, cache), jnp.arange(cfg.num_hidden_layers), positions, block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        if cfg.do_layer_norm_before:
            x = nn.LayerNorm(epsilon=1e-5, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                             name="final_layer_norm")(x)
        if proj_dim != cfg.hidden_size:
            x = nn.Dense(proj_dim, use_bias=False, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="project_out")(x)
        return logits_as(lm_head(cfg, embed, x), input_ids, last_only), cache


# ---------------------------------------------------------------------- phi


class PhiAttentionCache(nn.Module):
    cfg: PhiConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, x, positions, pages, block_table, start_pos, chunk_lens, layer):
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
            q = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="q_layernorm")(q)
            k = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="k_layernorm")(k)
        cos, sin = rotary_embedding(positions, rot_dim, cfg.rope_theta)
        q = apply_partial_rope(q, cos, sin, rot_dim)
        k = apply_partial_rope(k, cos, sin, rot_dim)
        out, pages = paged_attention_core(self.groups, q, k, v, pages, layer, block_table, start_pos, chunk_lens,
                                          self.page_size, attention_impl=cfg.attention_impl)
        out = nn.DenseGeneral(features=cfg.hidden_size, axis=(-2, -1), use_bias=True,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                              name="dense")(out)
        return out, pages


class PhiBlockCache(nn.Module):
    cfg: PhiConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg = self.cfg
        x, pages = carry
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="input_layernorm")(x)
        attn_out, pages = PhiAttentionCache(cfg, self.page_size, self.groups, name="self_attn")(
            h, positions, pages, block_table, start_pos, chunk_lens, layer)
        m = nn.Dense(cfg.intermediate_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, MLP)), name="fc1")(h)
        m = jax.nn.gelu(m, approximate=True)
        mlp_out = nn.Dense(cfg.hidden_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (MLP, EMBED)), name="fc2")(m)
        return (x + attn_out + mlp_out, pages), None


class PhiForCausalLMWithCache(nn.Module):
    cfg: PhiConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(tokens)
        (x, cache), _ = scan_blocks(PhiBlockCache, cfg.num_hidden_layers)(cfg, self.page_size, groups, name="layers")(
            (x, cache), jnp.arange(cfg.num_hidden_layers), positions, block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="final_layernorm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=True, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                          kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                          name="lm_head")(x)
        return logits_as(logits, input_ids, last_only), cache


# ---------------------------------------------------------------- qwen2-moe


class Qwen2MoeBlockCache(nn.Module):
    cfg: Qwen2MoeConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]
    sparse: bool = True   # mixed stacks: dense SwiGLU for mlp_only/off-step layers

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg = self.cfg
        x, pages = carry
        attn_out, pages = LlamaAttentionCache(cfg.as_llama(), self.page_size, self.groups, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_layernorm")(x), positions,
            pages, block_table, start_pos, chunk_lens, layer)
        h = x + attn_out
        mlp = Qwen2MoeSparseMLP(cfg, name="mlp") if self.sparse else Qwen2MoeDenseMLP(cfg, name="mlp")
        # the step is one group of T tokens to the router and the sort
        out = h + mlp(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_attention_layernorm")(h)[None])[0]
        return (out, pages), None


class Qwen2MoeForCausalLMWithCache(nn.Module):
    cfg: Qwen2MoeConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(tokens)
        if cfg.mixed_stack:
            # dense/sparse layers can't share one scanned body — unroll with
            # per-layer dispatch, mirroring the training model's layers_{i}
            # naming so converted checkpoints apply unchanged; the arena stays
            # whole here too, each block naming its layer in it
            for i in range(cfg.num_hidden_layers):
                (x, cache), _ = Qwen2MoeBlockCache(cfg, self.page_size, groups, sparse=cfg.layer_is_sparse(i),
                                                   name=f"layers_{i}")((x, cache), i, positions, block_table,
                                                                       start_pos, chunk_lens)
        else:
            (x, cache), _ = scan_blocks(Qwen2MoeBlockCache, cfg.num_hidden_layers)(
                cfg, self.page_size, groups, name="layers")((x, cache), jnp.arange(cfg.num_hidden_layers), positions,
                                                            block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        return logits_as(lm_head(cfg, embed, x), input_ids, last_only), cache


@dataclasses.dataclass(frozen=True)
class CacheTwin:
    """What serves a configuration: ``model(cfg, page_size=)`` builds its
    paged-cache twin, ``geometry(cfg, page_size)`` says what a page of its
    arena holds and whether a sequence holds a state slot besides
    (inference/v2/geometry.py), ``init_cache(cfg, kv, dtype, n_slots, chunk)`` makes
    what the engine keeps as ``eng.cache`` and hands the twin: the one arena
    of pages [L, P, page, 2, n_kv, hd], or pages (of the layers whose keys
    and values grow: one, or several under one block table) and state slots
    together, of which ``pages(cache)`` is the arena the paged kernel reads.
    ``walk_rows(page_size, table_width)``: key rows a block of the walk holds
    where the twin's pages go through a kernel of its own (latent pages,
    ``ops/mla_attention.py``); None: ``ds_paged_attention``'s, from the arena's shape."""
    model: Callable
    geometry: Callable = lambda cfg, page_size: LinearGeometry(page_size)
    init_cache: Callable = lambda cfg, kv, dtype, n_slots, chunk: init_kv_cache(cfg, kv, dtype=dtype)
    pages: Callable = lambda cache: cache
    walk_rows: Callable = None


def _dropless_mixtral(cfg, page_size):
    if cfg.drop_tokens:
        # serving must be dropless: capacity drops would silently zero
        # routed tokens and diverge from HF (the reference FastGen moe
        # gating has no capacity limit at inference)
        cfg = cfg.__class__(**{**cfg.__dict__, "drop_tokens": False})
    return MixtralForCausalLMWithCache(cfg, page_size=page_size)


#: the one place that says which twin and which geometry serve a configuration
CACHE_MODEL_REGISTRY = {
    LlamaConfig: CacheTwin(LlamaForCausalLMWithCache),
    MixtralConfig: CacheTwin(_dropless_mixtral),
    FalconConfig: CacheTwin(FalconForCausalLMWithCache),
    OPTConfig: CacheTwin(OPTForCausalLMWithCache),
    PhiConfig: CacheTwin(PhiForCausalLMWithCache),
    Qwen2MoeConfig: CacheTwin(Qwen2MoeForCausalLMWithCache),
    EvaByteConfig: CacheTwin(EvaByteForCausalLMWithCache,
                             lambda cfg, page_size: RingSummaryGeometry(page_size, cfg.window_size)),
    Phi4FlashConfig: CacheTwin(Phi4FlashForCausalLMWithCache,
                               lambda cfg, page_size: SlotPagesGeometry(page_size, cfg.sliding_window),
                               init_phi4flash_cache, lambda cache: cache["pages"]),
    GraniteHybridConfig: CacheTwin(GraniteHybridForCausalLMWithCache,
                                   lambda cfg, page_size: SlotPagesGeometry(page_size,
                                                                            state_bytes=slot_state_bytes(cfg)),
                                   init_granite_hybrid_cache, lambda cache: cache["pages"]),
    SolarOpen2Config: CacheTwin(SolarOpen2ForCausalLMWithCache,
                                # the one slot-holding twin that hands a slot's state from row to row: runs
                                lambda cfg, page_size: SlotPagesGeometry(page_size, chunk_runs=True,
                                                                         state_bytes=solar_open2_state_bytes(cfg)),
                                init_solar_open2_cache, lambda cache: cache["pages"]),
    # the sparse layers read their pages through a list walk of their own, which no contiguous walk's count fits
    MiniCPMSALAConfig: CacheTwin(MiniCPMSALAForCausalLMWithCache, SparseSlotPagesGeometry, init_minicpm_sala_cache,
                                 lambda cache: cache["pages"], walk_rows=lambda page_size, table_width: 0),
    # window layers in rings in the slot beside full layers in pages; a ring needs no handing on: runs
    TrinityConfig: CacheTwin(TrinityForCausalLMWithCache, trinity_geometry, init_trinity_cache,
                             lambda cache: cache["pages"]),
    Xing4Config: CacheTwin(Xing4ForCausalLMWithCache, lambda cfg, page_size: LatentPagesGeometry(page_size),
                           init_xing4_cache, walk_rows=xing4_walk_rows),
    # the same latent pages under the same kernel; a subclass of Xing4Config, found by its own type first
    KimiVLConfig: CacheTwin(KimiVLForCausalLMWithCache, lambda cfg, page_size: LatentPagesGeometry(page_size),
                            init_xing4_cache, walk_rows=xing4_walk_rows),
}


def cache_twin(cfg) -> CacheTwin:
    """The registry's entry for ``cfg``: its own type's, else that of a type it derives from."""
    if type(cfg) in CACHE_MODEL_REGISTRY:
        return CACHE_MODEL_REGISTRY[type(cfg)]
    for cfg_cls, twin in CACHE_MODEL_REGISTRY.items():
        if isinstance(cfg, cfg_cls):
            return twin
    raise TypeError(f"{type(cfg).__name__} has no paged-cache twin: models/cache_zoo.CACHE_MODEL_REGISTRY "
                    f"serves {', '.join(c.__name__ for c in CACHE_MODEL_REGISTRY)}")


def cache_geometry(cfg, page_size: int):
    """The geometry of ``cfg``'s pages in an arena of ``page_size``-row pages."""
    return cache_twin(cfg).geometry(cfg, page_size)
