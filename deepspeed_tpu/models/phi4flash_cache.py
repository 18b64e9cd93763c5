"""Phi-4-mini-flash through pages and state slots: the serving twin of
models/phi4flash.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode.  The
parameter tree is the full-sequence model's.

A step is a list of row groups on one flat token axis
(``models/llama_cache.py`` "Row groups"; a rectangle is the one group): the
embedding, the norms, every projection (``in_proj``, ``x_proj``, ``dt_proj``,
the gate with ``out_proj``, queries, keys and values), the gated memory units
with the memory they read, ``DiffAttention.combine`` and the MLPs run on the
flat axis; the convolution with the slot's tail, the scan with the slot's
state, the rings' and the pages' writes and the paged attention run a group
at a time, the ``cache`` dict threaded through the groups.  The scan is one
code path at any width: a decode group runs it for one position over its
rows, a prefill group for a chunk over its one or few.

What a sequence holds (``inference/v2/geometry.SlotPagesGeometry``).  Of
``L`` layers only one, the full-attention layer ``L/2 + 1``, has keys and
values that grow with the sequence: they live in **pages**, token ``t`` in row
``t % page`` of the page in column ``t // page`` of the sequence's block-table
row, and the cross-attention layers read the same pages.  Everything else is
of fixed size and lives in the sequence's **state slot**, whose index rides in
the **last column** of the row:

* a **ring** a window layer: the keys and values of the last
  ``sliding_window`` tokens, the scheduler's prefill chunk and a page more,
  token ``t`` in row ``t % page`` of ring page ``(t // page) % ring_pages`` of
  the slot (``init_cache`` sizes it for the chunk the engine feeds; the twin
  reads the size off the cache it is handed);
* a Mamba layer's recurrent state ``[d_state, d_inner]`` in float32 and the
  last ``d_conv - 1`` inputs of its convolution.

Slot 0 is scratch, as page 0 is the null page: padding rows write there, and
a row built for the linear layout alone (the benchmark's check: consecutive
pages, every other column 0) runs in it.  A row whose ``start_pos`` is 0
starts from a zero recurrent state; a ring needs no reset, rows a sequence
has not written lie beyond what its queries may see.

``cache`` is a dict of four arrays: ``pages`` [1, G x P, page, 2, Hkv/2G, 2d],
``ring`` [window layers, G x (1 + slots x ring_pages), page, 2, Hkv/2G, 2d]
(``G`` groups of key pairs a page, ``page_heads``: 5 groups of 2 at the
published 10 pairs; an
arena of ring pages, so that the paged kernel reads it through a table built
here, ``_ring_view``; its page 0 is the null page that a chunk's padding
writes to, and belongs to no slot, the scratch slot's included), ``ssm`` [Mamba layers, slots, d_state, d_inner] and ``conv``
[Mamba layers, slots, d_conv - 1, d_inner].  All four are carried through
the layer loops whole and updated in place.  Key and value pairs are packed
into heads of ``2d`` = 128 lanes (models/phi4flash.py), so every attention
layer gives ``ds_paged_attention`` pages of whole tiles.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .llama_cache import (PagedKVConfig, _write_pages, flat_step, logits_as, over_row_groups, paged_attention,
                          reads_through_kernel, sampled_rows)
from .phi4flash import Phi4FlashConfig, Phi4FlashLayer, _norm, embed_tokens, scan_pairs, tied_logits


def ring_pages(cfg: Phi4FlashConfig, page_size: int, chunk: int) -> int:
    """Pages of one window layer's ring in a slot: the window, the longest
    chunk a step feeds, and one page for a chunk that starts inside a page."""
    return -(-(cfg.sliding_window + chunk) // page_size) + 1


def _ring_pages_of(cfg: Phi4FlashConfig, cache) -> int:
    """``ring_pages`` of the cache in hand: its ring arena less the null page, a slot."""
    return (cache["ring"].shape[1] // page_groups(cfg) - 1) // cache["ssm"].shape[1]


def page_heads(cfg: Phi4FlashConfig) -> int:
    """Key pairs a device page holds: the most of 8, 4, 2, 1 that divides
    their count.  The chip tiles a page's ``[heads, 128]`` rows by 8 sublanes
    (or the power of two that holds fewer heads): 10 key pairs in one page
    would be padded to 16 and leave the paged kernel's whole-tile path, so a
    page of 16 tokens is kept as 5 device pages of 2 pairs each, page ``p``'s
    group ``g`` at ``page_groups * p + g`` of the arena, and the kernel runs a
    row a (sequence, group): ``_by_group``.  (These groups of heads,
    ``pgroups`` in the code, are not the step's row groups, ``groups``.)"""
    pairs = cfg.num_key_value_heads // 2
    return next(h for h in (8, 4, 2, 1) if pairs % h == 0)


def page_groups(cfg: Phi4FlashConfig) -> int:
    """Device pages a page of tokens is kept as: groups of ``page_heads`` key pairs."""
    return cfg.num_key_value_heads // 2 // page_heads(cfg)


def init_cache(cfg: Phi4FlashConfig, kv: PagedKVConfig, dtype, n_slots: int, chunk: int):
    """The two blocks of per-sequence state: pages for the one layer that
    grows, ``n_slots`` slots (slot 0 is scratch) for everything else, the
    rings wide enough for steps of ``chunk`` tokens a row."""
    mamba, window = cfg.n_self_pairs + 1, cfg.n_self_pairs
    heads, lanes, pgroups = page_heads(cfg), 2 * cfg.head_dim, page_groups(cfg)
    n_ring = 1 + n_slots * ring_pages(cfg, kv.page_size, chunk)
    return {
        "pages": jnp.zeros((1, pgroups * kv.num_pages, kv.page_size, 2, heads, lanes), dtype),
        "ring": jnp.zeros((window, pgroups * n_ring, kv.page_size, 2, heads, lanes), dtype),
        "ssm": jnp.zeros((mamba, n_slots, cfg.d_state, cfg.d_inner), jnp.float32),
        "conv": jnp.zeros((mamba, n_slots, cfg.d_conv - 1, cfg.d_inner), dtype),
    }


def _ring_view(slot, start_pos, n_ring, window, page_size):
    """(the table of ring pages the paged kernel sees, the row's virtual
    start).  Column ``c`` is the page of tokens ``page * (a0 + c) ..``, with
    ``a0`` the page of the first key the row's first query may see, so that a
    token's virtual position is its own less ``page * a0`` and the kernel's
    two bounds are the window's.  Ring page 0 is the null page.  Ring pages ahead of the row's last token
    hold rows of a lap ago, which lie past every query's last visible key."""
    a0 = jnp.maximum(start_pos - window + 1, 0) // page_size
    view = 1 + slot[:, None] * n_ring + (a0[:, None] + jnp.arange(n_ring)[None, :]) % n_ring
    return view, start_pos - a0 * page_size


def _by_group(x, pgroups):
    """[B, C, H, D] -> [B * pgroups, C, H / pgroups, D]: a row a (sequence, group of heads)."""
    b, c, h, d = x.shape
    return x.reshape(b, c, pgroups, h // pgroups, d).swapaxes(1, 2).reshape(b * pgroups, c, h // pgroups, d)


def _from_groups(x, pgroups):
    """The inverse of ``_by_group``."""
    bg, c, h, d = x.shape
    return x.reshape(bg // pgroups, pgroups, c, h, d).swapaxes(1, 2).reshape(bg // pgroups, c, pgroups * h, d)


def _group_rows(pgroups, table, *per_row):
    """A row group's arrays a (sequence, group of heads) row: the table of
    group ``g``'s device pages, ``pgroups * page + g``, and the rest repeated."""
    b, width = table.shape
    table = (table[:, None, :] * pgroups + jnp.arange(pgroups, dtype=table.dtype)[None, :, None]).reshape(
        b * pgroups, width)
    return (table, ) + tuple(jnp.repeat(a, pgroups, axis=0) for a in per_row)


def _attend(mixer, cfg, q, arena, layer, table, start, chunk_lens, page_size, window=0):
    """``q`` [B * pgroups, C, query heads a group, 2d] against the group's pages."""
    if reads_through_kernel(cfg.attention_impl):
        from ..ops.paged_attention import paged_attention_pallas
        return paged_attention_pallas(q, arena, table, start, chunk_lens, page_size, layer=layer, window=window,
                                      scale=mixer.scale)
    return paged_attention(q, arena[layer], table, start, chunk_lens, page_size, sliding_window=window,
                           scale=mixer.scale)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _apply_layer(layer, mix, static, params, x, arrays):
    return layer.apply({"params": params}, x, lambda mixer, h: mix(mixer, h, *static, *arrays))


def layer_traced_once(layer, mix, static, x, *arrays):
    """``layer(x, mix')`` for a serving block whose layers take ``(x, mix)``
    (the slot-holding twins'), with ``mix'(mixer, h) = mix(mixer, h, *static,
    *arrays)``: ``mix`` a function of its module, ``static`` hashable,
    ``arrays`` pytrees of arrays.  While the parameters are made the layer is
    called as it is; afterwards through one jitted function of the layer's
    own parameters, so that a program that holds a layer many times over (a
    period of nine Mamba layers; a scan's body, which flax traces twice)
    traces and lowers it once a shape, as it does the kernels' wrappers.  On a
    v5e's host the seven step programs of ``granite4h_sessions`` and
    ``phi4flash_reason`` are otherwise 29 and 26 s of tracing and lowering at
    every start, compile cache or none (PERF.md section 6, PR 38)."""
    if layer.is_initializing():
        return layer(x, lambda mixer, h: mix(mixer, h, *static, *arrays))
    return _apply_layer(layer.clone(parent=None, name=None), mix, static, layer.variables["params"], x, arrays)


def _mamba_mix(mixer, h, groups, cache, index, slot, start_pos, chunk_lens):
    """A Mamba layer's mixer through its slots: (mixed, (the scan's ungated
    output [T, d_inner], cache)).  ``h`` is the flat axis [T, hidden] of the
    row ``groups``: the projections and the gate run there, the convolution
    and the scan a group at a time (a decode group is a scan of one
    position)."""

    def convolve(cache, u, slot, start_pos, chunk_lens):
        tail = jnp.where((start_pos == 0)[:, None, None], 0, cache["conv"][index, slot])
        u, tail = mixer.convolve(u, tail, chunk_lens)
        # a position that carries no token gives the scan zeros: under ``dt`` = 0 alone what it holds is still
        # a factor, and 0 x NaN of a padding slot would reach the row's state
        u = jnp.where(jnp.arange(u.shape[1])[None, :, None] < chunk_lens[:, None, None], u, 0)
        return u, dict(cache, conv=cache["conv"].at[index, slot].set(tail.astype(cache["conv"].dtype)))

    def scan(cache, u, dt, b_mat, c_mat, slot, start_pos, chunk_lens):
        state = jnp.where((start_pos == 0)[:, None, None], 0.0, cache["ssm"][index, slot])
        y, state = mixer.scan(u, dt, b_mat, c_mat, state, chunk_lens)
        return y, dict(cache, ssm=cache["ssm"].at[index, slot].set(state))

    rows = (slot, start_pos, chunk_lens)
    u, z = mixer.in_project(h)
    u, cache = over_row_groups(groups, convolve, cache, (u, ), rows)
    y, cache = over_row_groups(groups, scan, cache, (u, ) + mixer.scan_inputs(u), rows)
    return mixer.gate_out(y, z), (y, cache)


def _mamba(cfg, name, x, cache, index, groups, slot, start_pos, chunk_lens):
    """Layer ``name``, the Mamba layer ``index`` of the cache's: (x, cache, the memory its scan gives)."""
    x, (y, cache) = layer_traced_once(Phi4FlashLayer(cfg, "mamba", name=name), _mamba_mix, (groups, ), x, cache,
                                      jnp.asarray(index, jnp.int32), slot, start_pos, chunk_lens)
    return x, cache, y


def _attention_mix(mixer, h, cfg, groups, page_size, window, arena, layer, index, *rows):
    """An attention layer's mixer: the projections and the combination on the
    flat axis; a row group at a time, write the chunk's packed keys and values
    into layer ``index`` of ``arena`` and read them back through the table:
    (mixed, arena).  ``rows``: (table, start, chunk_lens) a row."""
    pgroups = page_groups(cfg)

    def attend(arena, q, k, v, *rows):
        rows = _group_rows(pgroups, *rows)
        k, v = (_by_group(a, pgroups).astype(arena.dtype) for a in (k, v))
        arena = _write_pages(arena, k, v, rows[0], rows[1], page_size, rows[2], layer=index)
        a = _attend(mixer, cfg, _by_group(q, pgroups), arena, index, *rows, page_size, window)
        return _from_groups(a, pgroups), arena

    a, arena = over_row_groups(groups, attend, arena, (mixer.queries(h), ) + mixer.keys_values(h), rows)
    return mixer.combine(a, layer), arena


def _self_attention(cfg, name, x, cache, which, layer, index, groups, rows, page_size, window=0):
    """Layer ``name`` with index ``layer``, a window (``which`` = ``ring``) or
    the full (``pages``) attention layer, layer ``index`` of that arena: (x, cache)."""
    x, arena = layer_traced_once(Phi4FlashLayer(cfg, "attn", name=name), _attention_mix,
                                 (cfg, groups, page_size, window), x, cache[which], jnp.asarray(layer, jnp.int32),
                                 jnp.asarray(index, jnp.int32), *rows)
    return x, {**cache, which: arena}


def _cross_mix(mixer, h, cfg, groups, page_size, pages, layer, *rows):
    """A cross-attention layer's mixer: queries of its own against the shared
    pages, a row group at a time; it writes nothing."""
    pgroups = page_groups(cfg)

    def attend(pages, q, *rows):
        # the arena's one layer, named as the full-attention layer's call names it: the two share the kernel's trace
        a = _attend(mixer, cfg, _by_group(q, pgroups), pages, jnp.zeros((), jnp.int32), *_group_rows(pgroups, *rows),
                    page_size)
        return _from_groups(a, pgroups), pages

    a, _ = over_row_groups(groups, attend, pages, (mixer.queries(h), ), rows)
    return mixer.combine(a, layer), None


def _memory_mix(mixer, h, memory):
    return mixer(h, memory), None


class SelfPairCache(nn.Module):
    """Layers ``2j`` (Mamba) and ``2j + 1`` (window attention) of the twin;
    ``x`` is the flat axis [T, hidden] of ``groups`` (models/llama_cache.py
    "Row groups")."""
    cfg: Phi4FlashConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, j, slot, start_pos, chunk_lens, ring_rows):
        cfg = self.cfg
        x, cache = carry
        x, cache, _ = _mamba(cfg, "mamba", x, cache, j, self.groups, slot, start_pos, chunk_lens)
        x, cache = _self_attention(cfg, "attn", x, cache, "ring", 2 * j + 1, j, self.groups, ring_rows,
                                   self.page_size, cfg.sliding_window)
        return (x, cache), None


class CrossPairCache(nn.Module):
    """Layers ``L/2 + 2 + 2j`` (gated memory unit) and ``+ 1`` (cross-attention
    to the shared pages).  Neither holds state: the unit is a function of a
    token and its memory, and the cross-attention reads the pages a row group
    at a time and writes nothing."""
    cfg: Phi4FlashConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, x, j, memory, pages, page_rows):
        cfg = self.cfg
        first = cfg.num_hidden_layers // 2 + 2
        x, _ = layer_traced_once(Phi4FlashLayer(cfg, "gmu", name="gmu"), _memory_mix, (), x, memory)
        x, _ = layer_traced_once(Phi4FlashLayer(cfg, "cross", name="cross"), _cross_mix,
                                 (cfg, self.groups, self.page_size), x, pages, first + 2 * j + 1, *page_rows)
        return x, None


class Phi4FlashForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens)``
    -> (logits [B, C, vocab_size] in float32, new cache); with ``last_only``
    the logits of each row's last real token alone, [B, 1, vocab_size]; a
    rectangle of tokens or, with ``groups``, the flat axis of several
    (``LlamaForCausalLMWithCache``)."""
    cfg: Phi4FlashConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg, page = self.cfg, self.page_size
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        n_ring, widest = _ring_pages_of(cfg, cache), max(width for _, width in groups)
        if ring_pages(cfg, page, widest) > n_ring:
            raise ValueError(f"a chunk of {widest} tokens: the cache's rings of {n_ring} pages hold the "
                             f"window and {(n_ring - 1) * page - cfg.sliding_window} more")
        half = cfg.num_hidden_layers // 2
        slot, table = block_table[:, -1], block_table[:, :-1]
        # a row's view of its rings and its table of the shared pages; a (sequence, group of key pairs) row
        # of them is made a row group at a time (``_group_rows``)
        ring_rows = _ring_view(slot, start_pos, n_ring, cfg.sliding_window, page) + (chunk_lens, )
        page_rows = (table, start_pos, chunk_lens)
        embed = embed_tokens(cfg)
        x = embed(tokens)
        (x, cache), _ = scan_pairs(SelfPairCache, cfg.n_self_pairs, 4)(cfg, page, groups, name="self_decoder")(
            (x, cache), jnp.arange(cfg.n_self_pairs), slot, start_pos, chunk_lens, ring_rows)
        x, cache, memory = _mamba(cfg, "mid_mamba", x, cache, cfg.n_self_pairs, groups, slot, start_pos, chunk_lens)
        x, cache = _self_attention(cfg, "mid_attn", x, cache, "pages", half + 1, 0, groups, page_rows, page)
        x, _ = scan_pairs(CrossPairCache, cfg.n_cross_pairs, 3)(cfg, page, groups, name="cross_decoder")(
            x, jnp.arange(cfg.n_cross_pairs), memory, cache["pages"], page_rows)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(tied_logits(embed, _norm(cfg, "final_layernorm")(x)), input_ids, last_only), cache
