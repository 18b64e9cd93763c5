"""Phi-4-mini-flash through pages and state slots: the serving twin of
models/phi4flash.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens) -> (logits, cache)``, one chunked forward for
prefill chunks, continuation chunks and decode.  The parameter tree is the
full-sequence model's.

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

import jax
import jax.numpy as jnp
from flax import linen as nn

from .llama_cache import PagedKVConfig, _write_pages, paged_attention, reads_through_kernel, sampled_rows
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
    group ``g`` at ``groups * p + g`` of the arena, and the kernel runs a row a
    (sequence, group): ``_by_group``."""
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
    heads, lanes, groups = page_heads(cfg), 2 * cfg.head_dim, page_groups(cfg)
    n_ring = 1 + n_slots * ring_pages(cfg, kv.page_size, chunk)
    return {
        "pages": jnp.zeros((1, groups * kv.num_pages, kv.page_size, 2, heads, lanes), dtype),
        "ring": jnp.zeros((window, groups * n_ring, kv.page_size, 2, heads, lanes), dtype),
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


def _by_group(x, groups):
    """[B, C, H, D] -> [B * groups, C, H / groups, D]: a row a (sequence, group of heads)."""
    b, c, h, d = x.shape
    return x.reshape(b, c, groups, h // groups, d).swapaxes(1, 2).reshape(b * groups, c, h // groups, d)


def _from_groups(x, groups):
    """The inverse of ``_by_group``."""
    bg, c, h, d = x.shape
    return x.reshape(bg // groups, groups, c, h, d).swapaxes(1, 2).reshape(bg // groups, c, groups * h, d)


def _group_rows(groups, table, *per_row):
    """The batch's arrays a (sequence, group) row: the table of group
    ``g``'s device pages, ``groups * page + g``, and the rest repeated."""
    b, width = table.shape
    table = (table[:, None, :] * groups + jnp.arange(groups, dtype=table.dtype)[None, :, None]).reshape(b * groups, width)
    return (table, ) + tuple(jnp.repeat(a, groups, axis=0) for a in per_row)


def _attend(mixer, cfg, q, arena, layer, table, start, chunk_lens, page_size, window=0):
    """``q`` [B * groups, C, query heads a group, 2d] against the group's pages."""
    if reads_through_kernel(cfg.attention_impl):
        from ..ops.paged_attention import paged_attention_pallas
        return paged_attention_pallas(q, arena, table, start, chunk_lens, page_size, layer=layer, window=window,
                                      scale=mixer.scale)
    return paged_attention(q, arena[layer], table, start, chunk_lens, page_size, sliding_window=window,
                           scale=mixer.scale)


def _mamba(cfg, name, x, cache, index, slot, start_pos, chunk_lens):
    """A Mamba layer through its slot: (x, cache, the scan's ungated output)."""

    def mix(mixer, h):
        fresh = (start_pos == 0)[:, None, None]
        state = jnp.where(fresh, 0.0, cache["ssm"][index, slot])
        tail = jnp.where(fresh, 0, cache["conv"][index, slot])
        out, y, state, tail = mixer(h, state, tail, chunk_lens)
        return out, (y, state, tail)

    x, (y, state, tail) = Phi4FlashLayer(cfg, "mamba", name=name)(x, mix)
    cache = dict(cache, ssm=cache["ssm"].at[index, slot].set(state),
                 conv=cache["conv"].at[index, slot].set(tail.astype(cache["conv"].dtype)))
    return x, cache, y


def _self_attention(cfg, name, x, cache, which, layer, index, rows, page_size, window=0):
    """A window (``which`` = ``ring``) or the full (``pages``) attention layer:
    write the chunk's packed keys and values, read them back through the
    table.  ``rows``: (table, start, chunk_lens) a (sequence, group) row."""
    groups = page_groups(cfg)

    def mix(mixer, h):
        k, v = (_by_group(a, groups).astype(cache[which].dtype) for a in mixer.keys_values(h))
        arena = _write_pages(cache[which], k, v, rows[0], rows[1], page_size, rows[2], layer=index)
        a = _attend(mixer, cfg, _by_group(mixer.queries(h), groups), arena, index, *rows, page_size, window)
        return mixer.combine(_from_groups(a, groups), layer), arena

    x, arena = Phi4FlashLayer(cfg, "attn", name=name)(x, mix)
    return x, {**cache, which: arena}


class SelfPairCache(nn.Module):
    """Layers ``2j`` (Mamba) and ``2j + 1`` (window attention) of the twin."""
    cfg: Phi4FlashConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, carry, j, slot, start_pos, chunk_lens, ring_rows):
        cfg = self.cfg
        x, cache = carry
        x, cache, _ = _mamba(cfg, "mamba", x, cache, j, slot, start_pos, chunk_lens)
        x, cache = _self_attention(cfg, "attn", x, cache, "ring", 2 * j + 1, j, ring_rows, self.page_size,
                                   cfg.sliding_window)
        return (x, cache), None


class CrossPairCache(nn.Module):
    """Layers ``L/2 + 2 + 2j`` (gated memory unit) and ``+ 1`` (cross-attention
    to the shared pages).  Neither holds state."""
    cfg: Phi4FlashConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, x, j, memory, pages, page_rows):
        cfg = self.cfg
        first = cfg.num_hidden_layers // 2 + 2
        groups = page_groups(cfg)

        def cross_attention(mixer, h):
            a = _attend(mixer, cfg, _by_group(mixer.queries(h), groups), pages, 0, *page_rows, self.page_size)
            return mixer.combine(_from_groups(a, groups), first + 2 * j + 1), None

        x, _ = Phi4FlashLayer(cfg, "gmu", name="gmu")(x, lambda mixer, h: (mixer(h, memory), None))
        x, _ = Phi4FlashLayer(cfg, "cross", name="cross")(x, cross_attention)
        return x, None


class Phi4FlashForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens)``
    -> (logits [B, C, vocab_size] in float32, new cache); with ``last_only``
    the logits of each row's last real token alone, [B, 1, vocab_size]."""
    cfg: Phi4FlashConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False):
        cfg, page = self.cfg, self.page_size
        n_ring = _ring_pages_of(cfg, cache)
        if ring_pages(cfg, page, input_ids.shape[1]) > n_ring:
            raise ValueError(f"a chunk of {input_ids.shape[1]} tokens: the cache's rings of {n_ring} pages hold the "
                             f"window and {(n_ring - 1) * page - cfg.sliding_window} more")
        if chunk_lens is None:
            chunk_lens = jnp.full(start_pos.shape, input_ids.shape[1], jnp.int32)
        half = cfg.num_hidden_layers // 2
        slot, table = block_table[:, -1], block_table[:, :-1]
        # the batch a (sequence, group of key pairs) row: the rings' view and the shared pages' table
        ring_rows = _group_rows(page_groups(cfg), *_ring_view(slot, start_pos, n_ring, cfg.sliding_window, page),
                                chunk_lens)
        page_rows = _group_rows(page_groups(cfg), table, start_pos, chunk_lens)
        embed = embed_tokens(cfg)
        x = embed(input_ids)
        (x, cache), _ = scan_pairs(SelfPairCache, cfg.n_self_pairs, 4)(cfg, page, name="self_decoder")(
            (x, cache), jnp.arange(cfg.n_self_pairs), slot, start_pos, chunk_lens, ring_rows)
        x, cache, memory = _mamba(cfg, "mid_mamba", x, cache, cfg.n_self_pairs, slot, start_pos, chunk_lens)
        x, cache = _self_attention(cfg, "mid_attn", x, cache, "pages", half + 1, 0, page_rows, page)
        x, _ = scan_pairs(CrossPairCache, cfg.n_cross_pairs, 3)(cfg, page, name="cross_decoder")(
            x, jnp.arange(cfg.n_cross_pairs), memory, cache["pages"], page_rows)
        x = sampled_rows(x, chunk_lens, last_only)
        return tied_logits(embed, _norm(cfg, "final_layernorm")(x)), cache
