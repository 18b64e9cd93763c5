"""EvaByte through the paged arena: the serving twin of models/evabyte.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens) -> (logits, cache)``, one chunked forward for
prefill chunks, continuation chunks and decode.  The parameter tree is the
full-sequence model's.  The logits are head 0's (the next byte); the other
prediction heads are for a drafter, which this twin does not feed.

What a page holds (``inference/v2/geometry.RingSummaryGeometry``, with
``page_size == chunk_size``).  A sequence's block-table row is ``[ring |
summary pages]``:

* the **ring**, the first ``window_size / page_size`` columns: exact keys
  and values of the current window, token ``t`` in row ``t % page_size`` of
  ring page ``(t % window_size) // page_size``, overwritten in place when the
  next window starts;
* the **summary pages**, the columns after it: one row a chunk, chunk ``c``
  in row ``c % page_size`` of summary page ``c // page_size``, written in the
  step in which the chunk's last token arrives.

Everything is a function of ``start_pos``.  A row whose chunk starts in
window ``w`` gets, built in the program, the table the kernel sees: the
summary pages of the ``w`` complete windows, then the ring.  In that order a
summary row has virtual position below ``w * window/chunk`` and the exact
row of token ``j`` has ``w * window/chunk + (j - w * window)``, so with the
query moved to ``t - w * (window - window/chunk)`` the causal mask of the
existing ``ds_paged_attention`` is exactly EVA's visibility and its one
softmax is the joint one.  Stale ring rows (of the window before) lie above
the query's virtual position and are masked like any future token.  A chunk
must not cross a window (the geometry's ``chunk_limit`` tells the scheduler);
the fused decode rung may, being one-token chunks.

The arena is carried through the layer loop whole, [L, P, page, 2, H, D],
and every write and read names its layer: the loop updates it in place.  (A
scan that takes a layer's pages in and hands them out stacks a second arena,
which an arena of half the chip's memory has no room for.)
"""

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from .evabyte import EvaByteConfig, EvaByteHead, EvaProjections, eva_embed, eva_norm, summarise_chunks
from .llama import LlamaMLP
from .llama_cache import (_write_pages, flat_positions, flat_step, logits_as, over_row_groups, paged_attention,
                          reads_through_kernel, sampled_rows, scan_blocks)


def _summarise_completed(arena, layer, block_table, start_pos, chunk_lens, width, phi, mu, page_size, ring):
    """Write the summary row of every chunk whose last token arrived in this
    step (none or one in a decode step; up to ``ceil(width / page_size)`` in a
    prefill chunk).  Reads the chunk's rows back from its ring page, so the
    summary is of the keys as the cache holds them.  Candidates that complete
    nothing write zeros to the null page, as padding does."""
    b = block_table.shape[0]
    n_cand = -(-width // page_size)
    chunk = start_pos[:, None] // page_size + jnp.arange(n_cand)[None, :]             # [B, n]
    done = (chunk + 1) * page_size <= (start_pos + chunk_lens)[:, None]               # its last token is in
    ring_page = jnp.take_along_axis(block_table, chunk % ring, axis=1)
    rows = arena[layer, ring_page.reshape(-1)]                                        # [B*n, page, 2, H, D]
    k_sum, v_sum = summarise_chunks(rows[:, :, 0], rows[:, :, 1], phi, mu)            # [B*n, H, D]
    col = jnp.minimum(ring + chunk // page_size, block_table.shape[1] - 1)
    page = jnp.where(done, jnp.take_along_axis(block_table, col, axis=1), 0).reshape(-1)
    row = jnp.stack([k_sum, v_sum], axis=1).astype(arena.dtype)                       # [B*n, 2, H, D]
    row = jnp.where(done.reshape(b * n_cand, 1, 1, 1), row, 0)
    return arena.at[layer, page, (chunk % page_size).reshape(-1)].set(row)


def _kernel_view(block_table, start_pos, page_size, ring, window, max_windows):
    """(the table the paged kernel sees, the query's virtual start) per row."""
    per_window = ring // page_size                       # summary pages a window fills
    w = start_pos // window
    n_sum = (w * per_window)[:, None]
    width = block_table.shape[1]
    i = jnp.arange(ring + min(width - ring, per_window * (max_windows - 1)))[None, :]
    col = jnp.where(i < n_sum, ring + i, i - n_sum)      # a summary page, else a ring page
    live = (i < n_sum) | (i - n_sum < ring)
    view = jnp.where(live, jnp.take_along_axis(block_table, jnp.clip(col, 0, width - 1), axis=1), 0)
    return view, start_pos - w * (window - ring)


class EvaByteBlockCache(nn.Module):
    """``x`` is the flat axis [T, hidden] of ``groups`` (models/llama_cache.py):
    projections, rope and the MLP run there; the ring's writes, the summaries,
    the kernel's view and the attention are a row's, group by group."""
    cfg: EvaByteConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg, page = self.cfg, self.page_size
        x, arena = carry
        ring = cfg.window_size // page
        attn = EvaProjections(cfg, name="self_attn")
        q, k, v = attn.qkv(eva_norm(cfg, "input_layernorm")(x), positions)

        def attend(arena, q, k, v, block_table, start_pos, chunk_lens):
            arena = _write_pages(arena, k.astype(arena.dtype), v.astype(arena.dtype), block_table[:, :ring],
                                 start_pos % cfg.window_size, page, chunk_lens, layer=layer)
            with jax.named_scope("ds_eva_summarise"):
                arena = _summarise_completed(arena, layer, block_table, start_pos, chunk_lens, q.shape[1],
                                             attn.adaptive_phi, attn.adaptive_mu_k, page, ring)
            view, vstart = _kernel_view(block_table, start_pos, page, ring, cfg.window_size,
                                        -(-cfg.max_position_embeddings // cfg.window_size))
            if reads_through_kernel(cfg.attention_impl):
                from ..ops.paged_attention import paged_attention_pallas
                return paged_attention_pallas(q, arena, view, vstart, chunk_lens, page, layer=layer), arena
            return paged_attention(q, arena[layer], view, vstart, chunk_lens, page), arena

        o, arena = over_row_groups(self.groups, attend, arena, (q, k, v), (block_table, start_pos, chunk_lens))
        x = x + attn.o_proj(o.astype(cfg.dtype)).astype(x.dtype)
        x = x + LlamaMLP(cfg, name="mlp")(eva_norm(cfg, "post_attention_layernorm")(x)).astype(x.dtype)
        return (x, arena), None


class EvaByteForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens)``
    -> (head 0's logits [B, C, vocab_size] in float32, new cache); a rectangle
    of tokens or, with ``groups``, the flat axis of several
    (``LlamaForCausalLMWithCache``)."""
    cfg: EvaByteConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        if self.page_size != cfg.chunk_size:
            raise ValueError(f"EvaByte's chunk is its page: page_size {self.page_size} != chunk_size {cfg.chunk_size}")
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        x = eva_embed(cfg)(tokens).astype(jnp.float32)
        (x, cache), _ = scan_blocks(EvaByteBlockCache, cfg.num_hidden_layers)(
            cfg, self.page_size, groups, name="layers")((x, cache), jnp.arange(cfg.num_hidden_layers), positions,
                                                        block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        logits = EvaByteHead(cfg, name="lm_head")(eva_norm(cfg, "norm", jnp.float32)(x), 1)[..., 0, :]
        return logits_as(logits, input_ids, last_only), cache
