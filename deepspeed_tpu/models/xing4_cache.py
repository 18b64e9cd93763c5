"""Xing4.0 through latent pages: the serving twin of models/xing4.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode, a
rectangle of tokens or the flat axis of several row groups
(``models/llama_cache.py`` "Row groups").  The parameter tree is the
full-sequence model's.

**The page.**  A token's cache in a layer is the one row ``[c_kv | k_pe]``
all heads share, ``cfg.latent_dim`` numbers (576: 1,152 B in bfloat16), kept
in ``latent_lanes`` lanes (640, the tail zero) so that a page ``[page, W]`` is
whole tiles of the chip and one DMA of the kernel's own: the arena is ``[L,
P, page, W]``, not ``[L, P, page, 2, heads, lanes]``, read once for keys and
values alike (``ops/mla_attention.py``).  Pages are laid out linearly and
never change once full, so the prefix cache, rewinds and speculative
verification work as they do for the softmax twins
(``LatentPagesGeometry``); page export and import, snapshots and the host
tier take the arena by its page axis and work on the latent page as they
lie.  Tensor-parallel serving is refused in words (``engine_v2._serving_shardings``):
a row is every head's, and no head-sharded call of the kernel is built.

**The form.**  Every call, decode rows and prefill chunks alike, takes the
*absorbed* form: the query is carried into the latent space (``q_lat = q_nope
W_UK^T`` a head, 512 wide) and multiplied with the cached rows as they lie,
and ``W_UV`` takes the attended latents to the head's values afterwards.  A
query-key pair costs ``H (576 + 512) 2`` operations against the expanded
form's ``H (192 + 128) 2`` plus ``512 x H (128 + 128) x 2`` for every cached
row a call rebuilds keys and values from: they cross at a chunk of about 171
queries, above the engine's chunks of 128 (PERF.md section 6, PR 37).  The
full-sequence model computes the expanded form; the tests hold the two
together.

**The trunk.**  The streams ``X [T, n, C]`` and the arena ride in the carry;
the ``first_k_dense_replace`` dense layers are unrolled (arena layers 0 ..),
the expert layers are one ``scan_blocks`` over their indices and read their
expert banks in place (``MixtralForCausalLMWithCache._stacked_banks``).
"""

from typing import Callable, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..inference.v2.geometry import LinearGeometry
from ..ops import mla_attention
from .llama_cache import (PagedKVConfig, flat_positions, flat_step, live_slots, logits_as, over_row_groups,
                          sampled_rows, scan_blocks)
from .xing4 import Xing4Config, embed_streams, head_logits, layer_forward


def init_cache(cfg: Xing4Config, kv: PagedKVConfig, dtype, n_slots: int = 0, chunk: int = 0):
    """The latent arena [L, P, page, W]; page 0 is the null page."""
    del n_slots, chunk   # no state slot, nothing sized by the step
    return jnp.zeros((cfg.num_hidden_layers, kv.num_pages, kv.page_size, mla_attention.latent_lanes(cfg.latent_dim)),
                     dtype)


def walk_rows(page_size: int, table_width: int) -> int:
    """Key rows a block of the latent kernel's walk holds (the engine's step records ask)."""
    return page_size * mla_attention.walk_block(page_size, table_width)


class LatentPagesGeometry(LinearGeometry):
    """The linear geometry, plus the step records' count of what the latent
    kernel's calls read."""

    def state_counts(self, start: int, n_tokens: int, calls: int = 1) -> dict:
        """``mla_rows_read``: cached rows the calls read, each call once up to
        its last token's row (a layer): tokens ``start .. start + n_tokens -
        1`` go through the kernel in ``calls`` calls of equal length."""
        a_call = -(-int(n_tokens) // calls)
        ends = [min((c + 1) * a_call, int(n_tokens)) for c in range(calls)]
        return {"mla_rows_read": sum(int(start) + e for e in ends if e > 0)}


def absorbed_attend(cfg: Xing4Config, page_size, groups, pages, layer, block_table, start_pos, chunk_lens):
    """``Xing4Attention``'s ``attend`` through the pages: writes the chunk's
    latent rows into layer ``layer`` of the arena and attends in the absorbed
    form, group by group.  Returns ``attend(...) -> (o [T, H, v], arena)``."""
    rank, nope, width = cfg.kv_lora_rank, cfg.qk_nope_head_dim, pages.shape[-1]

    def attend(q_nope, q_pe, c_kv, k_pe, w_kvb):
        # the query into the latent space: [T, H, rank], then [q_lat | q_pe | 0] as the rows lie
        q_lat = jnp.einsum("thd,lhd->thl", q_nope, w_kvb[..., :nope])
        pad = width - cfg.latent_dim
        q = jnp.pad(jnp.concatenate([q_lat, q_pe], axis=-1), ((0, 0), (0, 0), (0, pad)))
        rows = jnp.pad(jnp.concatenate([c_kv, k_pe], axis=-1), ((0, 0), (0, pad))).astype(pages.dtype)

        def one_group(arena, q, rows, table, start, lens):
            arena = mla_attention.write_latent(arena, rows, table, start, page_size, lens, layer=layer)
            if cfg.attention_impl == "flash":
                o = mla_attention.mla_absorbed_pallas(q, arena, table, start, lens, page_size, d_v=rank,
                                                      scale=cfg.softmax_scale, layer=layer)
            else:
                o = mla_attention.mla_absorbed_reference(q, arena[layer], table, start, lens, page_size, d_v=rank,
                                                         scale=cfg.softmax_scale)
            return o, arena

        o_lat, arena = over_row_groups(groups, one_group, pages, (q, rows), (block_table, start_pos, chunk_lens))
        return jnp.einsum("thl,lhd->thd", o_lat, w_kvb[..., nope:]), arena

    return attend


class _DenseLayerCache(nn.Module):
    """``forward`` is the family's layer (``xing4.layer_forward``; ``kimi_vl``'s
    plain residual), which names the parameters."""
    cfg: Xing4Config
    page_size: int
    groups: Tuple[Tuple[int, int], ...]
    forward: Callable = layer_forward

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        x, pages = carry
        attend = absorbed_attend(self.cfg, self.page_size, self.groups, pages, layer, block_table, start_pos,
                                 chunk_lens)
        return self.forward(self.cfg, False, x, positions, attend)


class _SparseLayerCache(nn.Module):
    """A scan's body over the expert layers' indices: sparse layer ``i`` is
    layer ``first_k_dense_replace + i`` of the arena and ``i`` of the stacked banks."""
    cfg: Xing4Config
    page_size: int
    groups: Tuple[Tuple[int, int], ...]
    forward: Callable = layer_forward

    @nn.compact
    def __call__(self, carry, index, positions, block_table, start_pos, chunk_lens, stacked_banks=None):
        cfg = self.cfg
        x, pages = carry
        attend = absorbed_attend(cfg, self.page_size, self.groups, pages, cfg.first_k_dense_replace + index,
                                 block_table, start_pos, chunk_lens)
        # a chunk's padding goes to no routed expert (the mask the page write uses)
        return self.forward(cfg, True, x, positions, attend, live_slots(self.groups, chunk_lens),
                            None if stacked_banks is None else (stacked_banks, index)), None


def stacked_banks(module, cfg):
    """The expert layers' banks of ``module``'s parameters as the scan holds
    them, [L, E, ...], for the blocks to read in place
    (``MixtralForCausalLMWithCache._stacked_banks``); None where they are not
    held in the compute dtype."""
    experts = module.variables.get("params", {}).get("layers", {}).get("mlp", {}).get("experts")
    if experts is None:
        return None
    banks = tuple(nn.meta.unbox(experts[name]) for name in ("w_gate", "w_up", "w_down"))
    return banks if all(w.dtype == cfg.dtype for w in banks) else None


class Xing4ForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens,
    last_only, groups)`` -> (logits, new cache): every twin's contract."""
    cfg: Xing4Config
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        x = embed_streams(cfg, tokens)                                     # [T, n, C]
        for i in range(cfg.first_k_dense_replace):
            x, cache = _DenseLayerCache(cfg, self.page_size, groups, name=f"dense_layers_{i}")(
                (x, cache), i, positions, block_table, start_pos, chunk_lens)
        if cfg.num_sparse_layers:
            (x, cache), _ = scan_blocks(_SparseLayerCache, cfg.num_sparse_layers, n_broadcast=5)(
                cfg, self.page_size, groups, name="layers")((x, cache), jnp.arange(cfg.num_sparse_layers), positions,
                                                            block_table, start_pos, chunk_lens, stacked_banks(self, cfg))
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(head_logits(cfg, x), input_ids, last_only), cache
