"""Granite 4.0-H through pages and state slots: the serving twin of
models/granite_hybrid.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens) -> (logits, cache)``, one chunked forward for
prefill chunks, continuation chunks and decode.  The parameter tree is the
full-sequence model's.

What a sequence holds (``inference/v2/geometry.SlotPagesGeometry``, with no
window).  Every attention layer's keys and values grow with the sequence:
they live in **pages**, one arena of as many layers as the model has
attention layers under one block table, token ``t`` in row ``t % page`` of
the page in column ``t // page`` of the sequence's row.  Every Mamba-2
layer's recurrent state ``[heads, d_head, d_state]`` in float32 and the last
``d_conv - 1`` inputs of its convolution are of fixed size and live in the
sequence's **state slot**, whose index rides in the **last column** of the
row.  Slot 0 is scratch, as page 0 is the null page: padding rows write
there, and a row built for the linear layout alone (the benchmark's check)
runs in it.  A row whose ``start_pos`` is 0 starts from a zero state.

``cache`` is a dict of three arrays: ``pages`` [attention layers, P, page, 2,
H_kv / k, k d], ``ssm`` [Mamba layers, slots, heads, d_head, d_state] float32
and ``conv`` [Mamba layers, slots, d_conv - 1, conv_dim].  All three are
carried through the layer loops whole and updated in place.

Key heads are **packed** ``k`` to a page head of 128 lanes (``kv_pack``: two
heads of 64 at the published sizes): ``[k_2j | k_2j+1]``, the values
likewise, which is how the projections' outputs lie already; a query is its
own ``d`` lanes in the place of its key head and zero elsewhere, so a dot
over 128 lanes is ``q . k`` of its own key head, and of the 128 lanes that
come out it keeps its own.  A page is then whole tiles of the chip and
``ds_paged_attention`` copies it itself, the path every other cell runs.

A step of one token a row (decode) advances the states where they lie,
``ops/ssd_update.ds_ssd_update``; a step that carries a chunk gathers the
rows' states, takes the block form (``granite_hybrid.ssd_chunk``) and
scatters them back.
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.ssd_update import FRESH, LIVE, ssd_update
from .granite_hybrid import (GraniteHybridConfig, GraniteHybridLayer, _norm, embed_tokens, layer_name, scaled_logits,
                             ssd_chunk)
from .llama_cache import (PagedKVConfig, _write_pages, paged_attention, reads_through_kernel, sampled_rows,
                          scan_blocks)

_LANES = 128


def kv_pack(cfg: GraniteHybridConfig) -> int:
    """Key heads a page head of 128 lanes holds."""
    k = max(_LANES // cfg.head_dim, 1)
    return k if cfg.num_key_value_heads % k == 0 else 1


def init_cache(cfg: GraniteHybridConfig, kv: PagedKVConfig, dtype, n_slots: int, chunk: int):
    """Pages for every attention layer, ``n_slots`` slots (slot 0 is scratch)
    for every Mamba layer's state and convolution tail."""
    del chunk   # a slot holds nothing sized by the step
    k, mamba = kv_pack(cfg), cfg.count("mamba")
    return {
        "pages": jnp.zeros((cfg.count("attention"), kv.num_pages, kv.page_size, 2, cfg.num_key_value_heads // k,
                            k * cfg.head_dim), dtype),
        "ssm": jnp.zeros((mamba, n_slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32),
        "conv": jnp.zeros((mamba, n_slots, cfg.mamba_d_conv - 1, cfg.conv_dim), dtype),
    }


def slot_state_bytes(cfg: GraniteHybridConfig) -> int:
    """Bytes of one sequence's recurrent states, every Mamba layer."""
    return 4 * cfg.count("mamba") * cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state


def _pack_queries(cfg, q):
    """[B, C, H, d] -> [B, C, H, k d]: head ``i``'s lanes where its key head lies in the page head."""
    k = kv_pack(cfg)
    place = (jnp.arange(cfg.num_attention_heads) // (cfg.num_attention_heads // cfg.num_key_value_heads)) % k
    onehot = jax.nn.one_hot(place, k, dtype=q.dtype)                       # [H, k]
    return (q[..., None, :] * onehot[:, :, None]).reshape(q.shape[:-1] + (k * cfg.head_dim, )), onehot


def _mamba(cfg, name, x, cache, index, slot, start_pos, chunk_lens):
    """A Mamba-2 layer through its slot, ``index`` among the cache's Mamba layers: (x, cache)."""
    decode = x.shape[1] == 1
    fresh = (start_pos == 0) & (chunk_lens > 0)      # a row that carries no token changes nothing

    def mix(mixer, h):
        tail = jnp.where(fresh[:, None, None], 0, cache["conv"][index, slot])
        z, xs, b_mat, c_mat, dt, tail = mixer.project(h, tail, chunk_lens)
        a = mixer.neg_a()
        if decode:
            f32 = jnp.float32
            flags = jnp.where(chunk_lens > 0, LIVE, 0) | jnp.where(fresh, FRESH, 0)
            dt1 = dt[:, 0]                                                 # [B, H]
            y, ssm = ssd_update(cache["ssm"], index, slot, flags, xs[:, 0].astype(f32) * dt1[..., None],
                                jnp.exp(dt1 * a), b_mat[:, 0].astype(f32), c_mat[:, 0].astype(f32))
            y = y[:, None]
        else:
            state = jnp.where(fresh[:, None, None, None], 0.0, cache["ssm"][index, slot])
            y, state = ssd_chunk(xs, dt, a, b_mat, c_mat, state)
            ssm = cache["ssm"].at[index, slot].set(state)
        return mixer.finish(y, xs, z), (ssm, tail)

    x, (ssm, tail) = GraniteHybridLayer(cfg, "mamba", name=name)(x, mix)
    return x, dict(cache, ssm=ssm, conv=cache["conv"].at[index, slot].set(tail.astype(cache["conv"].dtype)))


def _attention(cfg, name, x, cache, index, table, start_pos, chunk_lens, page_size):
    """An attention layer: write the chunk's packed keys and values into its
    layer of the pages, read them back through the table."""

    def mix(mixer, h):
        q, k, v = mixer.qkv(h)
        q, onehot = _pack_queries(cfg, q)
        packed = lambda t: t.reshape(t.shape[:2] + cache["pages"].shape[-2:]).astype(cache["pages"].dtype)  # noqa: E731
        pages = _write_pages(cache["pages"], packed(k), packed(v), table, start_pos, page_size, chunk_lens, layer=index)
        if reads_through_kernel(cfg.attention_impl):
            from ..ops.paged_attention import paged_attention_pallas
            a = paged_attention_pallas(q, pages, table, start_pos, chunk_lens, page_size, layer=index,
                                       scale=cfg.attention_multiplier)
        else:
            a = paged_attention(q, pages[index], table, start_pos, chunk_lens, page_size,
                                scale=cfg.attention_multiplier)
        # of the page head's lanes a query head keeps its own key head's
        a = jnp.sum(a.reshape(a.shape[:-1] + (onehot.shape[1], cfg.head_dim)) * onehot[:, :, None].astype(a.dtype),
                    axis=-2)
        return mixer.out(a), pages

    x, pages = GraniteHybridLayer(cfg, "attention", name=name)(x, mix)
    return x, dict(cache, pages=pages)


class _CachePeriod(nn.Module):
    """One period of the twin, a scan's body: layer ``j`` of period ``period``
    is layer ``period x (its kind's layers a period) + (those before it in
    the period)`` of its kind in the cache."""
    cfg: GraniteHybridConfig
    page_size: int

    @nn.compact
    def __call__(self, carry, period, slot, table, start_pos, chunk_lens):
        cfg = self.cfg
        x, cache = carry
        for j, kind in enumerate(cfg.layer_types[:cfg.period]):
            index = period * cfg.per_period(kind) + cfg.per_period(kind, before=j)
            if kind == "mamba":
                x, cache = _mamba(cfg, layer_name(j), x, cache, index, slot, start_pos, chunk_lens)
            else:
                x, cache = _attention(cfg, layer_name(j), x, cache, index, table, start_pos, chunk_lens, self.page_size)
        return (x, cache), None


class GraniteHybridForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens)``
    -> (logits [B, C, vocab_size] in float32, new cache); with ``last_only``
    the logits of each row's last real token alone, [B, 1, vocab_size]."""
    cfg: GraniteHybridConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False):
        cfg = self.cfg
        if chunk_lens is None:
            chunk_lens = jnp.full(start_pos.shape, input_ids.shape[1], jnp.int32)
        n_periods = cfg.num_hidden_layers // cfg.period
        slot, table = block_table[:, -1], block_table[:, :-1]
        embed = embed_tokens(cfg)
        x = (cfg.embedding_multiplier * embed(input_ids)).astype(cfg.dtype)
        (x, cache), _ = scan_blocks(_CachePeriod, n_periods)(cfg, self.page_size, name="periods")(
            (x, cache), jnp.arange(n_periods), slot, table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only)
        return scaled_logits(cfg, embed, _norm(cfg, "norm")(x)), cache
