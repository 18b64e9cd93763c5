"""Llama with paged KV cache — the inference-path twin of models/llama.py.

Reference: the v1 kernel-injection containers keep KV in a global inference
context arena (``csrc/transformer/inference/includes/inference_context.h``)
and v2 FastGen uses a blocked KV cache with blocked-flash kernels
(``deepspeed/inference/v2/ragged/kv_cache.py:40 BlockedKVCache``,
``inference/v2/kernels/ragged_ops``).  TPU-native realisation: the cache is
an explicit JAX array arena of fixed-size pages, functionally threaded
through the forward pass; attention gathers a sequence's pages via its
block table.

What updates the arena in place.  The engine donates the arena to every step
program, but donation only gives the result somewhere to land: whether the
step touches the rows it writes or moves the whole arena hangs on how the
trunk hands it to the layers.  The scanned trunks carry it whole,
[L, P, page, 2, n_kv, hd], beside the activations, and every write and read
names its layer (``layer=``): the loop's carry is one buffer from the step's
argument to its result, a write is a scatter of the chunk's rows into it and
the kernel reads a layer's pages where they lie.  (A scan that takes a
layer's pages in and hands them out slices a layer out, stacks a second arena
and copies it back: three moves of the whole arena a model step, a third of
served Mixtral's busy time on the chip: PERF.md, PR 27.)

Param-tree compatibility: module/submodule names mirror LlamaForCausalLM
exactly (embed_tokens, model/layers/{self_attn/{q,k,v,o}_proj,
input_layernorm, post_attention_layernorm, mlp/{gate,up,down}_proj}, norm,
lm_head), so weights trained with the training model apply unchanged.

One program serves prefill chunks, continuation chunks and decode (C=1) —
the Dynamic-SplitFuse property that all phases are the same computation at
different chunk sizes (ref: blogs/deepspeed-fastgen — SplitFuse; here it
falls out of the unified chunked forward).

Row groups.  A step's tokens are one flat axis of ``T = sum(rows x width)``
slots: a short static list of groups ``(rows, width)``, one after another.
``start_pos``, the block tables and ``chunk_lens`` stay one entry a row, the
groups' rows concatenated.  A rectangle ``[B, C]`` is the one group
``((B, C),)``; a mixed step is the decode rows at one slot each beside the
prefill rows at a chunk each, ``((16, 1), (1, 128))``: 144 slots where the
rectangle has 2,048.  Whatever is a function of a token alone (embedding,
norms, projections, rope, the MLP or the experts, the residual) runs on the
flat axis, ``[T, hidden]``; what needs a row's sequence (the page writes, the
paged attention) runs group by group at the group's own rectangle
(``over_row_groups``).  Every twin is written so, and the engine hands each
a mixed step in two groups.  A twin that holds a state slot a sequence
(``phi4flash_cache.py``, ``granite_hybrid_cache.py``) also runs group by
group what reads or writes the slot: the causal convolution with the slot's
tail, the recurrence with the slot's state (in the form the group's width
asks for: one position a row, or a chunk), a window layer's ring; its
``arena`` is the dict of its cache's arrays, threaded through the groups
whole as a single arena is.
"""

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .llama import (EMBED, HEAD_DIM, HEADS, KV_HEADS, LAYERS, MLP, VOCAB, LlamaConfig, LlamaMLP, RMSNorm, _logical,
                    apply_rope, rotary_embedding)


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    """Cache geometry (ref: inference/v2/ragged/manager_configs.py)."""
    num_pages: int = 128
    page_size: int = 16
    max_pages_per_seq: int = 8


def init_kv_cache(cfg, kv: PagedKVConfig, dtype=jnp.bfloat16):
    """Allocate the paged arena: [L, P, page, 2, n_kv, hd].  Page 0 is the
    reserved null page (block tables point unused slots at it).  Works for
    any model-family config (falcon names its kv-head count differently;
    MHA models have none).  A page is ``page`` rows of one key and one value
    a head; which rows a twin keeps there is its own business: the keys and
    values of 16 consecutive tokens (every softmax-attention twin), or, for
    chunked linear attention, either 16 exact tokens of the current window
    or 16 chunk summaries (``models/evabyte_cache.py``)."""
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    n_kv = getattr(cfg, "num_key_value_heads", None) or getattr(cfg, "num_kv_heads", None) \
        or cfg.num_attention_heads
    return jnp.zeros((cfg.num_hidden_layers, kv.num_pages, kv.page_size, 2, n_kv, head_dim),
                     dtype)


def _write_pages(pages, k_new, v_new, block_table, start_pos, page_size, chunk_lens, layer):
    """Scatter a chunk's K/V into layer ``layer`` of the arena.

    pages: the whole arena [L, P, page, 2, n_kv, hd]   k/v_new: [B, C, n_kv, hd]
    block_table: [B, max_pages]  start_pos: [B]  chunk_lens: [B] —
    positions at/after a row's chunk_len are padding; their writes are
    redirected to the reserved null page 0.  ``layer`` is an index (traced in
    a scanned trunk).  The arena is the layer loop's carry, so the scatter's
    operand and result are one buffer and only the chunk's B*C rows move; a
    layer sliced out of a stacked arena by a scan would be a copy, and so
    would the stack it goes back into.
    """
    b, c = k_new.shape[0], k_new.shape[1]
    positions = start_pos[:, None] + jnp.arange(c)[None, :]          # [B, C]
    # page lookup must stay in-bounds for the pad region too (out-of-range
    # take_along_axis would read junk pages)
    page_slot = jnp.minimum(positions // page_size, block_table.shape[1] - 1)
    page_idx = jnp.take_along_axis(block_table, page_slot, axis=1)   # [B, C]
    kv_chunk = jnp.stack([k_new, v_new], axis=2)                      # [B, C, 2, n_kv, hd]
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]              # [B, C]
    page_idx = jnp.where(valid, page_idx, 0)
    # ALSO zero the redirected values: pad-region activations can be
    # non-finite (e.g. out-of-range learned-position lookups fill NaN),
    # and a NaN-poisoned null page turns masked attention into NaN via
    # 0 * NaN in the probs @ V matmul
    kv_chunk = jnp.where(valid[:, :, None, None, None], kv_chunk, 0)
    slot_idx = positions % page_size                                  # [B, C]
    flat_kv = kv_chunk.reshape((-1, ) + kv_chunk.shape[2:])           # [B*C, 2, n_kv, hd]
    return pages.at[layer, page_idx.reshape(-1), slot_idx.reshape(-1)].set(flat_kv)


def paged_attention(q, pages, block_table, start_pos, chunk_lens, page_size, sliding_window=0,
                    alibi_slopes=None, scale=None):
    """Attention of a chunk's queries against (history + chunk) keys.

    q: [B, C, H, hd] (RoPE applied); pages: [P, page, 2, n_kv, hd] with the
    chunk's K/V already written; block_table: [B, max_pages]; start_pos: [B]
    = context length before this chunk; chunk_lens: [B] or None — query rows
    at/after a row's chunk_len (ragged padding) get zero output.
    ``alibi_slopes`` [H]: falcon-rw per-key position bias slope·kpos·scale
    (softmax is row-shift invariant, so the per-key form matches HF's
    build_alibi_tensor — same folding as models/falcon.py's training path).
    jnp reference implementation — the Pallas blocked-decode kernel slots in
    behind the same signature (ops/paged_attention.py).
    """
    b, c, h, d = q.shape
    max_pages = block_table.shape[1]
    n_kv = pages.shape[3]
    gathered = pages[block_table.reshape(-1)]                         # [B*maxp, page, 2, n_kv, hd]
    gathered = gathered.reshape(b, max_pages * page_size, 2, n_kv, d)
    k = gathered[:, :, 0]                                             # [B, S_kv, n_kv, hd]
    v = gathered[:, :, 1]
    if n_kv != h:
        rep = h // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32) if scale is None else jnp.float32(scale)
    logits = jnp.einsum("bcnd,bknd->bnck", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = start_pos[:, None] + jnp.arange(c)[None, :]                # [B, C]
    kpos = jnp.arange(max_pages * page_size)[None, :]                 # [1, S_kv]
    if alibi_slopes is not None:
        # HF adds alibi to RAW scores pre-scaling → fold the scale in
        bias = alibi_slopes.astype(jnp.float32)[None, :, None, None] * \
            kpos[0].astype(jnp.float32)[None, None, None, :] * scale
        logits = logits + bias
    mask = kpos[:, None, :] <= qpos[..., None]                        # [B, C, S_kv]
    if sliding_window and sliding_window > 0:  # mistral window (decode path)
        mask = mask & (kpos[:, None, :] > qpos[..., None] - sliding_window)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnck,bknd->bcnd", probs.astype(v.dtype), v)
    if chunk_lens is not None:
        valid = jnp.arange(c)[None, :] < chunk_lens[:, None]          # [B, C]
        out = jnp.where(valid[..., None, None], out, 0)
    return out


def reads_through_kernel(attention_impl, alibi=False) -> bool:
    """Whether a twin's attention reads its pages through ``ds_paged_attention``
    (a window does: the kernel takes a first visible row as well as a last;
    alibi bias goes through the jnp form).  The engine asks too, for its
    step records."""
    return attention_impl == "flash" and not alibi


def paged_attention_core(groups, q, k, v, pages, layer, block_table, start_pos, chunk_lens, page_size,
                         attention_impl="reference", sliding_window=0, alibi_slopes=None):
    """Shared paged-KV attention core of the softmax twins whose pages hold a
    token's keys and values: group by group of the step's ``groups``, write
    the chunk's K/V into layer ``layer`` of the arena ``pages``
    (``_write_pages``), then attend the chunk's queries against (history +
    chunk).  q/k/v are post-projection, post-RoPE, on the flat axis
    [T, N(H|KV), D]; the block table, ``start_pos`` and ``chunk_lens`` one
    entry a row.  Returns (out [T, H, D], the arena)."""

    def attend(pages, q, k, v, block_table, start_pos, chunk_lens):
        pages = _write_pages(pages, k.astype(pages.dtype), v.astype(pages.dtype), block_table, start_pos, page_size,
                             chunk_lens, layer=layer)
        if reads_through_kernel(attention_impl, alibi_slopes is not None):
            from ..ops.paged_attention import paged_attention_pallas
            return paged_attention_pallas(q, pages, block_table, start_pos, chunk_lens, page_size, layer=layer,
                                          window=sliding_window), pages
        # out of the whole arena the jnp form reads a layer's slice, 1/L of an arena
        return paged_attention(q, pages[layer], block_table, start_pos, chunk_lens, page_size,
                               sliding_window=sliding_window, alibi_slopes=alibi_slopes), pages

    return over_row_groups(groups, attend, pages, (q, k, v), (block_table, start_pos, chunk_lens))


def flat_step(input_ids, chunk_lens, groups):
    """A twin's first argument as (flat tokens [T], its groups, chunk_lens
    [R]): a rectangle ``[B, C]`` is one group, flat tokens ``[T]`` come with
    their ``groups``; rows without ``chunk_lens`` carry a token in every slot."""
    if groups is None:
        groups = (tuple(input_ids.shape), )
    groups = tuple((int(rows), int(width)) for rows, width in groups)
    if sum(rows * width for rows, width in groups) != input_ids.size:
        raise ValueError(f"row groups {groups} do not hold tokens of shape {input_ids.shape}")
    if chunk_lens is None:
        chunk_lens = jnp.asarray(np.repeat([w for _, w in groups], [r for r, _ in groups]), jnp.int32)
    return input_ids.reshape(-1), groups, chunk_lens


def spread_rows(groups, per_row):
    """[R, ...] -> [T, ...]: a row's entry in every slot of its chunk."""
    out, r0 = [], 0
    for rows, width in groups:
        out.append(jnp.repeat(per_row[r0:r0 + rows], width, axis=0, total_repeat_length=rows * width))
        r0 += rows
    return jnp.concatenate(out)


def slot_in_chunk(groups):
    """[T], static: each slot's index in its row's chunk."""
    return np.concatenate([np.tile(np.arange(width, dtype=np.int32), rows) for rows, width in groups])


def flat_positions(groups, start_pos):
    """[T]: each slot's position in its row's sequence."""
    return spread_rows(groups, start_pos) + slot_in_chunk(groups)


def live_slots(groups, chunk_lens):
    """[T] bool: whether the slot carries a token (a chunk's first ``chunk_lens``)."""
    return slot_in_chunk(groups) < spread_rows(groups, chunk_lens)


def over_row_groups(groups, attend, arena, flat, per_row):
    """What needs a row's sequence, group by group: ``attend(arena, *rect,
    *rows) -> (out [rows, width, ...], arena)`` is the code of one rectangle;
    it gets each of ``flat`` ([T, ...]) as the group's ``[rows, width, ...]``
    and each of ``per_row`` ([R, ...]) as the group's rows, the arena is
    threaded through the groups, and the results come back flat [T, ...]."""
    out, t0, r0 = [], 0, 0
    for rows, width in groups:
        n = rows * width
        rect = [a[t0:t0 + n].reshape((rows, width) + a.shape[1:]) for a in flat]
        o, arena = attend(arena, *rect, *(a[r0:r0 + rows] for a in per_row))
        out.append(o.reshape((n, ) + o.shape[2:]))
        t0, r0 = t0 + n, r0 + rows
    return (out[0] if len(out) == 1 else jnp.concatenate(out)), arena


def sampled_rows(x, chunk_lens, last_only, groups):
    """What of a trunk's output goes on to the final norm and the head: with
    ``last_only`` each row's last real token alone, [R, 1, H].  The engine's
    step programs sample from nothing else, and a head over every slot of a
    mixed step is its largest product and buffer where the vocabulary is
    large; the logits of every position are for who compares them (the verify
    program, the benchmark's check, the tests).  ``x`` is the flat axis
    [T, H] of ``groups``, of which a row's last real token is that of its
    chunk in its group."""
    if not last_only:
        return x
    first, t0 = [], 0
    for rows, width in groups:
        first.append(t0 + width * np.arange(rows, dtype=np.int32))
        t0 += rows * width
    return x[np.concatenate(first) + jnp.maximum(chunk_lens - 1, 0)][:, None]


def logits_as(logits, input_ids, last_only):
    """The logits of every position in the shape the tokens came in
    ([B, C, V] of a rectangle, [T, V] of flat tokens); ``last_only``'s
    [R, 1, V] as they are."""
    return logits if last_only else logits.reshape(input_ids.shape + logits.shape[-1:])


def lm_head(cfg, embed, x):
    """The logits of ``x``: the tied embedding's, else an ``lm_head`` without
    bias, made in the caller's scope."""
    if cfg.tie_word_embeddings:
        return embed.attend(x)
    return nn.DenseGeneral(features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)), name="lm_head")(x)


def stack_layer_params(variables, num_layers):
    """Convert unrolled params (``model/layers_{i}/*``, what a model trained
    with ``scan_layers=False`` holds) to the scan-stacked layout the serving
    twins take (``model/layers/*``, leaves ``[L, ...]``).  A tree that is
    stacked already comes back as it is."""
    had_wrapper = isinstance(variables, dict) and "params" in variables
    p = dict(variables["params"]) if had_wrapper else dict(variables)
    m = dict(p.get("model", {}))
    if "layers_0" not in m:
        return variables
    boxed = lambda x: isinstance(x, nn.meta.AxisMetadata)

    def stack(*xs):
        if not boxed(xs[0]):
            return jnp.stack(xs)
        # what nn.scan does to a partitioned leaf: the layers' axis joins its names
        return xs[0].replace_boxed(jnp.stack([x.value for x in xs])).add_axis(0, {nn.PARTITION_NAME: LAYERS})

    m["layers"] = jax.tree.map(stack, *(m.pop(f"layers_{i}") for i in range(num_layers)), is_leaf=boxed)
    p["model"] = m
    return {"params": p} if had_wrapper else p


class LlamaAttentionCache(nn.Module):
    """``x`` and ``positions`` are the flat axis of ``groups``, [T, hidden]
    and [T]: the projections and rope run there, and the page writes and the
    attention group by group."""
    cfg: LlamaConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, x, positions, pages, block_table, start_pos, chunk_lens, layer):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        from functools import partial
        dense = partial(nn.DenseGeneral, use_bias=cfg.attention_bias, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        q = dense(features=(cfg.num_attention_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, HEADS, HEAD_DIM)),
                  name="q_proj")(x)
        k = dense(features=(cfg.num_key_value_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="k_proj")(x)
        v = dense(features=(cfg.num_key_value_heads, head_dim),
                  kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, KV_HEADS, HEAD_DIM)),
                  name="v_proj")(x)
        cos, sin = rotary_embedding(positions, head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out, pages = paged_attention_core(self.groups, q, k, v, pages, layer, block_table, start_pos, chunk_lens,
                                          self.page_size, attention_impl=cfg.attention_impl,
                                          sliding_window=cfg.sliding_window)
        out = nn.DenseGeneral(features=cfg.hidden_size,
                              axis=(-2, -1),
                              use_bias=False,
                              dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype,
                              kernel_init=_logical(nn.initializers.lecun_normal(), (HEADS, HEAD_DIM, EMBED)),
                              name="o_proj")(out)
        return out, pages


class LlamaBlockCache(nn.Module):
    """One block in the shape of a scan's body: ``(carry, layer, ...) ->
    (carry, None)`` with ``carry = (x, pages)``.  The trunk carries the whole
    arena and scans over the layers' indices.  Every softmax twin's block has
    this form.  ``x`` is the flat axis [T, hidden] of ``groups``."""
    cfg: LlamaConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens):
        cfg = self.cfg
        x, pages = carry
        attn_out, pages = LlamaAttentionCache(cfg, self.page_size, self.groups, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_layernorm")(x), positions, pages,
            block_table, start_pos, chunk_lens, layer)
        h = x + attn_out
        out = h + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_attention_layernorm")(h))
        return (out, pages), None


def scan_blocks(block_cls, num_layers, n_broadcast=4):
    """``nn.scan`` of a serving block over the layers' indices, the arena in
    the carry: ``blocks(...)((x, arena), jnp.arange(L), *broadcast)`` ->
    ``((x, arena), None)``."""
    return nn.scan(block_cls,
                   variable_axes={"params": 0},
                   split_rngs={"params": True},
                   in_axes=(0, ) + (nn.broadcast, ) * n_broadcast,
                   length=num_layers,
                   metadata_params={nn.PARTITION_NAME: LAYERS})


class LlamaForCausalLMWithCache(nn.Module):
    """Chunked forward with paged KV.  ``apply(variables, tokens, start_pos,
    block_table, cache, chunk_lens, last_only)`` → (logits, new_cache); the
    logits of every position, or with ``last_only`` of each row's last real
    token ([B, 1, V]: ``sampled_rows``).  Every twin's contract.  ``tokens``
    is a rectangle [B, C], or with ``groups`` (static) the flat axis [T] of
    several, and the logits of every position have its shape."""
    cfg: LlamaConfig
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        embed = nn.Embed(num_embeddings=cfg.vocab_size,
                         features=cfg.hidden_size,
                         dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(tokens)

        class _Trunk(nn.Module):
            """Named 'model' to match LlamaForCausalLM's param tree."""
            cfg: LlamaConfig
            page_size: int

            @nn.compact
            def __call__(self, x, cache, positions, block_table, start_pos, chunk_lens):
                (x, cache), _ = scan_blocks(LlamaBlockCache, self.cfg.num_hidden_layers)(
                    self.cfg, self.page_size, groups, name="layers")(
                        (x, cache), jnp.arange(self.cfg.num_hidden_layers), positions, block_table, start_pos,
                        chunk_lens)
                return x, cache

        x, cache = _Trunk(cfg, self.page_size, name="model")(x, cache, positions, block_table, start_pos, chunk_lens)
        x = sampled_rows(x, chunk_lens, last_only, groups)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        return logits_as(lm_head(cfg, embed, x), input_ids, last_only), cache
