"""Trinity through rings and pages: the serving twin of models/trinity.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode, a
rectangle of tokens or the flat axis of several row groups
(``models/llama_cache.py`` "Row groups").  The parameter tree is the
full-sequence model's.

What a sequence holds (``inference/v2/geometry.SlotPagesGeometry`` with a
``window``).  A full-attention layer's keys and values grow with the
sequence and live in **pages**, token ``t`` in row ``t % page`` of the page
in column ``t // page`` of the sequence's block-table row; one arena of as
many layers as the model has full-attention layers, under the one table.  A
window layer keeps the last ``sliding_window`` tokens and no more: a **ring**
in the sequence's **state slot**, whose index rides in the last column of the
row.  A ring is ``ring_pages`` pages of an arena of ring pages: the window,
the most tokens one sequence feeds in a step (``TrinityConfig.run_tokens``)
and a page for a step that starts inside one; token ``t`` in row ``t % page``
of ring page ``(t // page) % ring_pages`` of the slot.  The paged kernel
reads a ring through a table
built here (``phi4flash_cache._ring_view``: the ring's pages in the order of
the tokens they hold, from the page of the first key the row's first query
may see) with the window as its second bound, and the pages through the
sequence's own table with none: both kinds are ``paged_attention_core`` over
``ds_paged_attention``, under the scopes ``ds_swa_window`` and
``ds_swa_full``.  Slot 0 is scratch, as page 0 and ring page 0 are the null
pages: a row built for the linear layout alone (the benchmark's check) runs
in it.  A ring needs no reset: rows a sequence has not written lie beyond
what its queries may see.

**Runs of chunks.**  The rows of a prefill group may be consecutive chunks of
one sequence (``SplitFuseScheduler.run_rows``).  A group's rows all write
their keys and values, rings and pages alike, before any of them attends, so
a row finds the rows before it there; a ring holds them as long as the run is
no longer than its slack, which is what the geometry's ``chunk_limit`` sees to
(``run_tokens``).  Nothing is handed from row to row: the twin's entry in
``cache_zoo.CACHE_MODEL_REGISTRY`` says ``chunk_runs=True`` for that alone.

``cache`` is a dict of two arrays, both of whole tiles (8 key heads of 128
lanes): ``pages`` [full layers, P, page, 2, H_kv, d] and ``ring`` [window
layers, 1 + slots x ring_pages, page, 2, H_kv, d].  Both are carried through
the layers whole and updated in place.  The projections, the head norms, the
rotary turn, the gate, the norms and the expert block run on the flat axis;
the writes and the paged attention a group at a time.
"""

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..inference.v2.geometry import SlotPagesGeometry
from .llama_cache import (PagedKVConfig, flat_positions, flat_step, live_slots, logits_as, paged_attention_core,
                          sampled_rows)
from .phi4flash_cache import _ring_view
from .trinity import FULL, SLIDING, TrinityConfig, TrinityLayer, embed, head_logits, layer_name


def ring_pages(cfg: TrinityConfig, page_size: int) -> int:
    """Pages of one window layer's ring in a slot: the window, the most tokens
    a sequence feeds in a step and one page for a step that starts inside a page."""
    return -(-(cfg.sliding_window + cfg.run_tokens) // page_size) + 1


def init_cache(cfg: TrinityConfig, kv: PagedKVConfig, dtype, n_slots: int, chunk: int):
    """Pages for every full-attention layer, ``n_slots`` slots (slot 0 is
    scratch) of a ring a window layer, wide enough for steps of
    ``run_tokens`` a sequence."""
    if chunk > cfg.run_tokens:
        raise ValueError(f"a prefill chunk of {chunk} tokens: the rings hold the window and run_tokens = "
                         f"{cfg.run_tokens} more; set TrinityConfig.run_tokens to the widest step of one sequence")
    heads, d = cfg.num_key_value_heads, cfg.head_dim
    n_ring = 1 + n_slots * ring_pages(cfg, kv.page_size)
    return {
        "pages": jnp.zeros((cfg.count(FULL), kv.num_pages, kv.page_size, 2, heads, d), dtype),
        "ring": jnp.zeros((cfg.count(SLIDING), n_ring, kv.page_size, 2, heads, d), dtype),
    }


def geometry(cfg: TrinityConfig, page_size: int) -> SlotPagesGeometry:
    """Pages for the full layers and a slot of rings a sequence; a prompt's
    consecutive chunks may share a step as far as the rings' slack goes."""
    return SlotPagesGeometry(page_size, window=cfg.sliding_window, chunk_runs=True, run_tokens=cfg.run_tokens,
                             ring_rows=ring_pages(cfg, page_size) * page_size)


def _attention_mix(mixer, h, groups, cfg, page_size, kind, cache, index, positions, chunk_lens, ring_rows, page_rows):
    """An attention layer's mixer: the projections, the norms, the turn and
    the gate on the flat axis; a group at a time, write the chunk's keys and
    values into layer ``index`` of its kind's arena and read them back through
    the kind's table: (mixed, cache).  ``ring_rows`` and ``page_rows``: (table,
    start) a row of the rings' view and of the sequence's own pages."""
    sliding = kind == SLIDING
    q, k, v = mixer.qkv(h, positions if sliding else None)
    which, (table, start) = ("ring", ring_rows) if sliding else ("pages", page_rows)
    with jax.named_scope("ds_swa_window" if sliding else "ds_swa_full"):
        a, arena = paged_attention_core(groups, q, k, v, cache[which], index, table, start, chunk_lens, page_size,
                                        attention_impl=cfg.attention_impl,
                                        sliding_window=cfg.sliding_window if sliding else 0)
    return mixer.out(a, h), {**cache, which: arena}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_layer(layer, static, params, x, arrays, live):
    return layer.apply({"params": params}, x, lambda mixer, h: _attention_mix(mixer, h, *static, *arrays), live)


def _layer_traced_once(layer, static, x, arrays, live):
    """``phi4flash_cache.layer_traced_once`` for a layer that also takes the
    expert block's token mask: while the parameters are made the layer is
    called as it is; afterwards through one jitted function of the layer's own
    parameters, so a program that holds three window layers over experts
    traces and lowers one."""
    if layer.is_initializing():
        return layer(x, lambda mixer, h: _attention_mix(mixer, h, *static, *arrays), live)
    return _apply_layer(layer.clone(parent=None, name=None), static, layer.variables["params"], x, arrays, live)


class TrinityForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens,
    last_only, groups)`` -> (logits, new cache): every twin's contract."""
    cfg: TrinityConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg, page = self.cfg, self.page_size
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        n_ring, widest = ring_pages(cfg, page), max(width for _, width in groups)
        if widest > cfg.run_tokens:
            raise ValueError(f"a chunk of {widest} tokens: the rings hold the window and run_tokens = "
                             f"{cfg.run_tokens} more")
        slot, table = block_table[:, -1], block_table[:, :-1]
        ring_rows = _ring_view(slot, start_pos, n_ring, cfg.sliding_window, page)
        arrays = (flat_positions(groups, start_pos), chunk_lens, ring_rows, (table, start_pos))
        live = live_slots(groups, chunk_lens)
        x = embed(cfg, tokens)
        for i, kind in enumerate(cfg.kinds):
            layer = TrinityLayer(cfg, i < cfg.num_dense_layers, name=layer_name(i))
            x, cache = _layer_traced_once(layer, (groups, cfg, page, kind), x,
                                          (cache, jnp.asarray(cfg.index(i), jnp.int32)) + arrays, live)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(head_logits(cfg, x), input_ids, last_only), cache
