"""Granite 4.0-H through pages and state slots: the serving twin of
models/granite_hybrid.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode.  The
parameter tree is the full-sequence model's.

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

A step is a list of row groups on one flat token axis
(``models/llama_cache.py`` "Row groups"; a rectangle is the one group): the
embedding, the norms, ``in_proj``, the gated norm with ``out_proj``, the
attention's projections and the MLPs run on the flat axis; the convolution
with the slot's tail, the recurrence with the slot's state, the pages' writes
and the paged attention run a group at a time, the ``cache`` dict threaded
through the groups.  The recurrence takes the form the group's width asks
for: a group of one token a row (the decode rows, in a decode step and
beside a prefilling prompt alike) advances the states where they lie,
``ops/ssd_update.ds_ssd_update``; a wider group (the prefill rows) gathers
its rows' states, takes the block form (``granite_hybrid.ssd_chunk``) and
scatters them back.

A sibling with routed experts (``num_local_experts`` > 0; granite-4.0-h-small)
runs them on the flat axis beside the shared MLP, one group to the router:
the step's live-slot mask goes into the block, so a padded slot of the decode
bucket reaches no expert, and the periods' stacks of the banks are handed to
the blocks whole with the period's index (``solar_open2_cache.stacked_banks``:
the grouped product reads its layer of a stack in place).  Heads of 128 fill
a page head alone (``kv_pack`` 1).  A dense sibling takes the path it took
before the experts came, with the same parameter tree and the same programs.
"""

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.ssd_update import FRESH, LIVE, ssd_update
from .granite_hybrid import (GraniteHybridConfig, GraniteHybridLayer, _norm, embed_tokens, layer_name, scaled_logits,
                             ssd_chunk)
from .llama_cache import (PagedKVConfig, _write_pages, flat_step, live_slots, logits_as, over_row_groups,
                          paged_attention, reads_through_kernel, sampled_rows, scan_blocks)
from .phi4flash_cache import layer_traced_once
from .solar_open2_cache import _layer_traced_once as layer_with_experts_traced_once
from .solar_open2_cache import stacked_banks

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


def _mamba_mix(mixer, h, cfg, groups, cache, index, slot, start_pos, chunk_lens, live):
    """A Mamba-2 layer's mixer through its slots, ``index`` among the cache's
    Mamba layers: (mixed, cache).  ``h`` is the flat axis [T, hidden] of
    ``groups``: the projections, the gate and the norm run there, the
    convolution and the recurrence a group at a time, the recurrence in the
    form the group's width asks for."""

    def fresh_rows(start_pos, chunk_lens):
        return (start_pos == 0) & (chunk_lens > 0)       # a row that carries no token changes nothing

    def convolve(cache, xbc, slot, start_pos, chunk_lens):
        tail = jnp.where(fresh_rows(start_pos, chunk_lens)[:, None, None], 0, cache["conv"][index, slot])
        xbc, tail = mixer.convolve(xbc, tail, chunk_lens)
        return xbc, dict(cache, conv=cache["conv"].at[index, slot].set(tail.astype(cache["conv"].dtype)))

    def recur(cache, xs, b_mat, c_mat, dt, slot, start_pos, chunk_lens):
        fresh, a = fresh_rows(start_pos, chunk_lens), mixer.neg_a()
        if xs.shape[1] == 1:    # one position a row: the states advance where they lie
            f32 = jnp.float32
            flags = jnp.where(chunk_lens > 0, LIVE, 0) | jnp.where(fresh, FRESH, 0)
            dt1 = dt[:, 0]                                                 # [B, H]
            y, ssm = ssd_update(cache["ssm"], index, slot, flags, xs[:, 0].astype(f32) * dt1[..., None],
                                jnp.exp(dt1 * a), b_mat[:, 0].astype(f32), c_mat[:, 0].astype(f32))
            return y[:, None], dict(cache, ssm=ssm)
        state = jnp.where(fresh[:, None, None, None], 0.0, cache["ssm"][index, slot])
        y, state = ssd_chunk(xs, dt, a, b_mat, c_mat, state)
        return y, dict(cache, ssm=cache["ssm"].at[index, slot].set(state))

    rows = (slot, start_pos, chunk_lens)
    z, xbc, dt = mixer.in_project(h, live)
    xbc, cache = over_row_groups(groups, convolve, cache, (xbc, ), rows)
    # a slot that carries no token gives the recurrence zeros: under ``dt`` = 0 alone what it holds is still a
    # factor, and 0 x NaN of a padding slot would reach the row's state
    xs, b_mat, c_mat = mixer.x_b_c(jnp.where(live[:, None], xbc, 0))
    y, cache = over_row_groups(groups, recur, cache, (xs, b_mat, c_mat, dt), rows)
    return mixer.finish(y, xs, z), cache


def _attention_mix(mixer, h, cfg, groups, page_size, pages, index, table, start_pos, chunk_lens):
    """An attention layer's mixer: the projections on the flat axis; a group
    at a time, write the chunk's packed keys and values into its layer of the
    pages and read them back through the table: (mixed, pages)."""
    q, k, v = mixer.qkv(h)
    q, onehot = _pack_queries(cfg, q)
    packed = lambda t: t.reshape(t.shape[:-2] + pages.shape[-2:]).astype(pages.dtype)  # noqa: E731

    def attend(pages, q, k, v, table, start_pos, chunk_lens):
        pages = _write_pages(pages, k, v, table, start_pos, page_size, chunk_lens, layer=index)
        if reads_through_kernel(cfg.attention_impl):
            from ..ops.paged_attention import paged_attention_pallas
            return paged_attention_pallas(q, pages, table, start_pos, chunk_lens, page_size, layer=index,
                                          scale=cfg.attention_multiplier), pages
        return paged_attention(q, pages[index], table, start_pos, chunk_lens, page_size,
                               scale=cfg.attention_multiplier), pages

    a, pages = over_row_groups(groups, attend, pages, (q, packed(k), packed(v)), (table, start_pos, chunk_lens))
    # of the page head's lanes a query head keeps its own key head's
    a = jnp.sum(a.reshape(a.shape[:-1] + (onehot.shape[1], cfg.head_dim)) * onehot[:, :, None].astype(a.dtype),
                axis=-2)
    return mixer.out(a), pages


class _CachePeriod(nn.Module):
    """One period of the twin, a scan's body: layer ``j`` of period ``period``
    is layer ``period x (its kind's layers a period) + (those before it in
    the period)`` of its kind in the cache.  ``x`` is the flat axis [T,
    hidden] of ``groups`` (models/llama_cache.py "Row groups")."""
    cfg: GraniteHybridConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, period, slot, table, start_pos, chunk_lens, live, banks=None):
        cfg = self.cfg
        x, cache = carry
        for j, kind in enumerate(cfg.layer_types[:cfg.period]):
            index = period * cfg.per_period(kind) + cfg.per_period(kind, before=j)
            layer = GraniteHybridLayer(cfg, kind, name=layer_name(j))

            def traced(mix, static, *arrays):
                if not cfg.num_local_experts:
                    return layer_traced_once(layer, mix, static, x, *arrays)
                # the layer's expert block also takes the live slots and its layer of the periods' stack of banks
                stacked = None if banks is None else (banks[j], period)
                return layer_with_experts_traced_once(layer, mix, static, x, arrays, live, stacked)

            if kind == "mamba":
                x, cache = traced(_mamba_mix, (cfg, self.groups), cache, index, slot, start_pos, chunk_lens, live)
            else:
                x, pages = traced(_attention_mix, (cfg, self.groups, self.page_size), cache["pages"], index, table,
                                  start_pos, chunk_lens)
                cache = dict(cache, pages=pages)
        return (x, cache), None


class GraniteHybridForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens)``
    -> (logits [B, C, vocab_size] in float32, new cache); with ``last_only``
    the logits of each row's last real token alone, [B, 1, vocab_size]; a
    rectangle of tokens or, with ``groups``, the flat axis of several
    (``LlamaForCausalLMWithCache``)."""
    cfg: GraniteHybridConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        n_periods = cfg.num_hidden_layers // cfg.period
        slot, table = block_table[:, -1], block_table[:, :-1]
        embed = embed_tokens(cfg)
        x = (cfg.embedding_multiplier * embed(tokens)).astype(cfg.dtype)
        banks = (stacked_banks(self, cfg, "block_sparse_moe"), ) if cfg.num_local_experts else ()
        (x, cache), _ = scan_blocks(_CachePeriod, n_periods, 5 + len(banks))(
            cfg, self.page_size, groups, name="periods")(
                (x, cache), jnp.arange(n_periods), slot, table, start_pos, chunk_lens, live_slots(groups, chunk_lens),
                *banks)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(scaled_logits(cfg, embed, _norm(cfg, "norm")(x)), input_ids, last_only), cache
