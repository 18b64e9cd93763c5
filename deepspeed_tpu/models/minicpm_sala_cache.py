"""MiniCPM-SALA through pages, an indexer's cache and state slots: the serving
twin of models/minicpm_sala.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode, a
rectangle of tokens or the flat axis of several row groups
(``models/llama_cache.py`` "Row groups").  The parameter tree is the
full-sequence model's.

What a sequence holds (``SparseSlotPagesGeometry``: ``SlotPagesGeometry`` and
the step records' counts).  ``cache`` is a dict of three arrays, carried
through the layer loops whole and updated in place:

* ``pages`` [sparse layers, P, page, 2, H_kv, d]: every ``minicpm4`` layer's
  keys and values, one arena under one block table;
* ``ckeys`` [sparse layers, slots, columns + 1, H_kv, d], **the indexer's
  cache**: with ``page_size`` = ``kernel_stride`` (and ``kernel_size`` two
  strides) the compressed key ``Kc[i]`` is the mean over a sequence's pages
  ``i`` and ``i + 1``; it is written by the step that completes page ``i + 1``
  (read back out of the pages, so it does not depend on how the prompt was
  cut) at column ``i`` of the sequence's **state slot**, so a row's
  compressed keys lie one after another and the selection reads them as one
  slab a row (kept at page ``i``'s index in the arena, as first built, the
  selection's gather of 4,161 rows of 512 B a sequence through the block
  table took 1.9 ms a sparse layer and step on the chip, a quarter of a decode
  step; builder, PR 49).  The slot goes with the sequence, so the allocator
  and the release need nothing new; what an earlier sequence left in a slot
  lies past the positions a query may score.  The last column takes the
  writes of what is not there yet;
* ``state`` [lightning layers, slots, heads, keys, values] float32: every
  Lightning layer's state, in the sequence's **state slot**, whose index rides
  in the last column of the block-table row; slot 0 is scratch.  A row whose
  ``start_pos`` is 0 starts from a zero state.

The projections, norms, rotary, gates and the SwiGLU run on the flat axis; the
pages' and the compressed keys' writes, the selection and the attention, and
the recurrence with the slot's state run a group at a time, in the form the
group's width asks for.  A group of one token a row (the decode rows): the
selection's block mask becomes a list of pages a row and key head and
``ops/sparse_paged_attention.sparse_paged_decode`` walks it (where
``attention_impl`` is ``flash``); the states advance where they lie
(``ops/lightning_update.lightning_update``).  A wider group (the prefill
rows): the blocked ``jax.numpy`` walk under the block mask
(``sparse_paged_blocked``; a tile's queries see different blocks and under
random weights their union is nearly every block, so prefill gets no cheaper
by the selection here), and the chunked form on the gathered states
(``minicpm_sala.lightning_chunk``).
"""

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..inference.v2.geometry import SlotPagesGeometry
from ..ops.lightning_update import FRESH, LIVE, lightning_update
from ..ops.sparse_paged_attention import block_lists, page_lists, sparse_paged_blocked, sparse_paged_decode
from .llama_cache import (PagedKVConfig, _write_pages, flat_positions, flat_step, live_slots, logits_as,
                          over_row_groups, sampled_rows, scan_blocks)
from .minicpm_sala import (MiniCPMSALAConfig, SALALayer, decay_slopes, embed, head_logits, lightning_chunk,
                           select_blocks)


def init_cache(cfg: MiniCPMSALAConfig, kv: PagedKVConfig, dtype, n_slots: int, chunk: int):
    """Pages for every sparse layer; ``n_slots`` slots (slot 0 is scratch) of a
    compressed key a page column for every sparse layer and of every Lightning
    layer's state."""
    del chunk   # a slot holds nothing sized by the step
    sparse, d = cfg.count("minicpm4"), cfg.lightning_head_dim
    return {
        "pages": jnp.zeros((sparse, kv.num_pages, kv.page_size, 2, cfg.num_key_value_heads, cfg.head_dim), dtype),
        "ckeys": jnp.zeros((sparse, n_slots, kv.max_pages_per_seq, cfg.num_key_value_heads, cfg.head_dim), dtype),
        "state": jnp.zeros((cfg.count("lightning-attn"), n_slots, cfg.lightning_nh, d, d), jnp.float32),
    }


def slot_state_bytes(cfg: MiniCPMSALAConfig) -> int:
    """Bytes of one sequence's recurrent states, every Lightning layer."""
    return 4 * cfg.count("lightning-attn") * cfg.lightning_nh * cfg.lightning_head_dim**2


class SparseSlotPagesGeometry(SlotPagesGeometry):
    """Pages and a state slot a sequence, and the step records' counts that
    the two kernels' roofline shares read (``telemetry/step_anatomy.COUNTS``):
    host-side counts from positions, as ``attn_rows_visible`` is, the least
    work a decode row asks for and not what the program walked."""

    def __init__(self, cfg: MiniCPMSALAConfig, page_size: int):
        super().__init__(page_size, state_bytes=slot_state_bytes(cfg))
        self.sparse = cfg.sparse_config
        #: (sparse layer, key head) pairs that select and attend
        self.lists = cfg.count("minicpm4") * cfg.num_key_value_heads

    def state_counts(self, start: int, n_tokens: int, calls: int = 1) -> dict:
        """For a row of one token a call (a decode row; a chunk counts
        nothing): ``sparse_decode_rows_read``: key rows the selection names,
        a sparse layer and key head each, ``min(t // block + 1, forced +
        topk)`` blocks at ``t >= dense_len`` and ``t + 1`` rows under it
        (what a walk reads beyond them, whole pages of every key head, is the
        roofline share's to show); ``lightning_state_bytes``: the states the
        one-position kernel reads and writes once a call."""
        if not n_tokens or int(n_tokens) != int(calls):
            return {}
        sp = self.sparse
        t = np.arange(int(start), int(start) + int(n_tokens), dtype=np.int64)
        most = sp["init_blocks"] + sp["window_size"] // sp["block_size"] + sp["topk"]
        named = np.where(t >= sp["dense_len"], np.minimum(t // sp["block_size"] + 1, most) * sp["block_size"], t + 1)
        return {"sparse_decode_rows_read": int(named.sum()) * self.lists,
                "lightning_state_bytes": 2 * self.state_bytes * int(calls)}


class _CacheLayer(nn.Module):
    """A scan's body over the layers of one run: ``((x, cache), (layer,
    its index among its kind's layers in the cache), ...) -> (x, cache)``.
    ``x`` is the flat axis [T, hidden] of ``groups``."""
    cfg: MiniCPMSALAConfig
    kind: str
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, at, slot, table, positions, start_pos, chunk_lens, live):
        x, cache = carry
        layer, index = at
        mix = self._sparse if self.kind == "minicpm4" else self._lightning
        x, cache = SALALayer(self.cfg, self.kind, name="layer")(
            x, lambda mixer, h: mix(mixer, h, cache, layer, index, slot, table, positions, start_pos, chunk_lens, live))
        return (x, cache), None

    def _lightning(self, mixer, h, cache, layer, index, slot, table, positions, start_pos, chunk_lens, live):
        del table
        log_decay = decay_slopes(self.cfg, layer)

        def recur(cache, q, k, v, slot, start_pos, chunk_lens):
            fresh = (start_pos == 0) & (chunk_lens > 0)        # a row that carries no token changes nothing
            if q.shape[1] == 1:     # one position a row: the states advance where they lie
                flags = jnp.where(chunk_lens > 0, LIVE, 0) | jnp.where(fresh, FRESH, 0)
                o, state = lightning_update(cache["state"], index, slot, flags, q[:, 0], k[:, 0], v[:, 0], log_decay)
                return o[:, None], dict(cache, state=state)
            state = jnp.where(fresh[:, None, None, None], 0.0, cache["state"][index, slot])
            o, state = lightning_chunk(q, k, v, log_decay, state, chunk_lens)
            return o, dict(cache, state=cache["state"].at[index, slot].set(state))

        # a slot that carries no token gives the recurrence zeros: 0 x NaN of a padding slot would reach the state
        q, k, v = (jnp.where(live[:, None, None], t, 0.0) for t in mixer.qkv(h, positions))
        o, cache = over_row_groups(self.groups, recur, cache, (q, k, v), (slot, start_pos, chunk_lens))
        return mixer.finish(o, h), cache

    def _sparse(self, mixer, h, cache, layer, index, slot, table, positions, start_pos, chunk_lens, live):
        del layer, positions, live
        cfg, page_size = self.cfg, self.page_size
        sp = cfg.sparse_config

        def attend(cache, q, k, v, slot, table, start_pos, chunk_lens):
            pages = _write_pages(cache["pages"], k.astype(cache["pages"].dtype), v.astype(cache["pages"].dtype), table,
                                 start_pos, page_size, chunk_lens, layer=index)
            ckeys = write_compressed_keys(cache["ckeys"], pages, index, slot, table, start_pos, chunk_lens, q.shape[1],
                                          page_size)
            qpos = start_pos[:, None] + jnp.arange(q.shape[1])[None, :]
            held = ckeys[index, slot, :table.shape[1]]                                     # [B, W, G, d]
            if q.shape[1] == 1:
                blocks = select_blocks(q, held, qpos, sp)                                  # [B, C, G, nb]
            else:   # a row at a time: the scores of a chunk over a long row's compressed keys are its largest array
                blocks = jax.lax.map(lambda row: select_blocks(*(a[None] for a in row), sp)[0], (q, held, qpos))
            if q.shape[1] == 1 and cfg.attention_impl == "flash":
                order, count = block_lists(blocks[:, 0], cfg.list_blocks)
                lists, n_pages = page_lists(order, count, table, start_pos, chunk_lens > 0, page_size, sp["block_size"])
                o = sparse_paged_decode(q[:, 0], pages, index, lists, n_pages, start_pos, page_size)[:, None]
            else:
                o = sparse_paged_blocked(q, pages, index, table, start_pos, chunk_lens, blocks, page_size,
                                         sp["block_size"])
            return o, dict(cache, pages=pages, ckeys=ckeys)

        q, k, v = mixer.qkv(h)
        o, cache = over_row_groups(self.groups, attend, cache, (q, k, v), (slot, table, start_pos, chunk_lens))
        return mixer.out(o, h), cache


def write_compressed_keys(ckeys, pages, index, slot, table, start_pos, chunk_lens, chunk, page_size):
    """The compressed keys a chunk of up to ``chunk`` tokens a row completes,
    into layer ``index`` of ``ckeys`` [L, slots, columns + 1, G, d]: ``Kc[i]``,
    the mean of the rows of the sequence's pages ``i`` and ``i + 1`` (the
    chunk's own already written into ``pages``), at column ``i`` of the row's
    slot, for every ``i`` whose last token the row now holds, from the first
    one the chunk's first token can complete.  One written a second time is
    the same value; what is not there yet goes to the slot's last column as
    zeros."""
    width = table.shape[1]
    first = jnp.maximum((start_pos - page_size) // page_size, 0)                        # [B]
    i = first[:, None] + jnp.arange(chunk // page_size + 1)[None, :]                    # [B, n]
    there = (page_size * (i + 2) <= (start_pos + chunk_lens)[:, None]) & (chunk_lens > 0)[:, None]
    column = jnp.minimum(jnp.stack([i, i + 1], axis=-1), width - 1)                     # [B, n, 2]
    page = jnp.take_along_axis(table, column.reshape(table.shape[0], -1), axis=1).reshape(column.shape)
    keys = pages[index, page][:, :, :, :, 0].astype(jnp.float32)                        # [B, n, 2, page, G, d]
    mean = jnp.where(there[:, :, None, None], keys.mean(axis=(2, 3)), 0.0)
    at = jnp.where(there, i, ckeys.shape[2] - 1)
    return ckeys.at[index, slot[:, None], at].set(mean.astype(ckeys.dtype))


class MiniCPMSALAForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens,
    last_only, groups)`` -> (logits, new cache): every twin's contract."""
    cfg: MiniCPMSALAConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        if self.page_size != cfg.sparse_config["kernel_stride"]:
            raise NotImplementedError(f"the indexer's cache keeps a compressed key a page: page_size {self.page_size} "
                                      f"must be the selection's kernel_stride {cfg.sparse_config['kernel_stride']}")
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        slot, table = block_table[:, -1], block_table[:, :-1]
        carry = (embed(cfg, tokens), cache)
        rows = (slot, table, flat_positions(groups, start_pos), start_pos, chunk_lens, live_slots(groups, chunk_lens))
        for j, (kind, start, n) in enumerate(cfg.runs):
            at = (start + jnp.arange(n), cfg.count(kind, before=start) + jnp.arange(n))
            carry, _ = scan_blocks(_CacheLayer, n, len(rows))(cfg, kind, self.page_size, groups, name=f"run_{j}")(
                carry, at, *rows)
        x, cache = carry
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(head_logits(cfg, x), input_ids, last_only), cache
