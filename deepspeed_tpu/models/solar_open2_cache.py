"""Solar-Open2 through pages and state slots: the serving twin of
models/solar_open2.py.

Same contract as every twin: ``apply(params, input_ids, start_pos,
block_table, cache, chunk_lens, last_only, groups) -> (logits, cache)``, one
chunked forward for prefill chunks, continuation chunks and decode, a
rectangle of tokens or the flat axis of several row groups
(``models/llama_cache.py`` "Row groups").  The parameter tree is the
full-sequence model's.

What a sequence holds (``inference/v2/geometry.SlotPagesGeometry``, with no
window), as Granite's twin (``models/granite_hybrid_cache.py``).  Every
attention layer's keys and values grow with the sequence and live in
**pages**, one arena of as many layers as the model has attention layers
under one block table.  Every KDA layer's state ``[heads, keys, values]`` in
float32 (4.19 MB a layer at 64 heads of 128 x 128) and the last ``conv - 1``
inputs of its convolution are of fixed size and live in the sequence's
**state slot**, whose index rides in the last column of the block-table row;
slot 0 is scratch.  A row whose ``start_pos`` is 0 starts from a zero state.

``cache`` is a dict of three arrays: ``pages`` [attention layers, P, page, 2,
H_kv, d], ``kda`` [KDA layers, slots, heads, keys, values] float32 and
``conv`` [KDA layers, slots, conv - 1, 3 W].  All three are carried through
the layer loops whole and updated in place.

The projections, the gates, the gated norm, the attention's projections and
the expert block run on the flat axis; the convolution with the slot's tail,
the recurrence with the slot's state, the pages' writes and the paged
attention run a group at a time.  The recurrence takes the form the group's
width asks for: a group of one token a row (the decode rows) advances the
states where they lie, ``ops/kda_update.kda_update``; a wider group (the
prefill rows) gathers its rows' states, takes the chunked form
(``solar_open2.kda_chunk``) and scatters them back.  The expert banks of a
period's layers are read in place out of the periods' stack
(``MixtralForCausalLMWithCache._stacked_banks``).

**A continuing row.**  The rows of a prefill group may be consecutive chunks
of one sequence (a run: ``SplitFuseScheduler.run_rows``, laid out by
``ragged.pack_groups``).  A row that carries tokens, holds the slot of the row
before it and starts where that row, a full chunk, ends *continues* it
(``continuing_rows``, read from the step's own ``slot``, ``start_pos`` and
``chunk_lens``): its recurrent state is the one that row leaves and its
convolution's tail that row's last ``conv - 1`` inputs, not the slot's; a row
that continues nothing reads its slot as ever, and the slot receives the
state and the tail of the run's last row, once (a row that hands on writes
nothing).  A group of one row, or of one token a row, is as it was; a wider
group of several rows takes them row after row
(``solar_open2.kda_chunk(continues=)``), whether one continues another or
none does: one form, which costs four rows of different prompts 0.4 ms of a
step of 34 (PERF.md section 6, PR 50).  The
attention layers need nothing: a step's rows are all written to the pages
before any attends.  This is what the twin's entry in
``cache_zoo.CACHE_MODEL_REGISTRY`` says with ``chunk_runs=True``.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.kda_update import FRESH, LIVE, kda_update
from .granite_hybrid import layer_name
from .llama_cache import (PagedKVConfig, flat_step, live_slots, logits_as, over_row_groups, paged_attention_core,
                          sampled_rows, scan_blocks)
from .phi4flash import embed_tokens
from .solar_open2 import SolarOpen2Config, SolarOpen2Layer, head_logits, kda_chunk


def init_cache(cfg: SolarOpen2Config, kv: PagedKVConfig, dtype, n_slots: int, chunk: int):
    """Pages for every attention layer, ``n_slots`` slots (slot 0 is scratch)
    for every KDA layer's state and convolution tail."""
    del chunk   # a slot holds nothing sized by the step
    kda, d = cfg.count("kda"), cfg.kda_head_dim
    return {
        "pages": jnp.zeros((cfg.count("gqa"), kv.num_pages, kv.page_size, 2, cfg.num_key_value_heads, cfg.head_dim),
                           dtype),
        "kda": jnp.zeros((kda, n_slots, cfg.kda_heads, d, d), jnp.float32),
        "conv": jnp.zeros((kda, n_slots, cfg.conv_size - 1, 3 * cfg.kda_width), dtype),
    }


def slot_state_bytes(cfg: SolarOpen2Config) -> int:
    """Bytes of one sequence's recurrent states, every KDA layer."""
    return 4 * cfg.count("kda") * cfg.kda_heads * cfg.kda_head_dim**2


def continuing_rows(slot, start_pos, chunk_lens, width):
    """Which rows of a group ``width`` wide go on from the row before them
    (``goes_on`` [R]) and which are gone on from (``handed_on`` [R]): a row
    continues the row before it where it carries tokens, holds that row's
    slot and starts where that row, a full one, ends.  Read from the step's
    own inputs: the engine packs a run's chunks so (``ragged.pack_groups``)."""
    goes_on = (chunk_lens[1:] > 0) & (slot[1:] == slot[:-1]) & (chunk_lens[:-1] == width) & \
        (start_pos[1:] == start_pos[:-1] + width)
    no = jnp.zeros((1, ), bool)
    return jnp.concatenate([no, goes_on]), jnp.concatenate([goes_on, no])


def _kda_mix(mixer, h, groups, cache, index, slot, start_pos, chunk_lens, live):
    """A KDA layer's mixer through its slots, ``index`` among the cache's KDA
    layers: (mixed, cache).  ``h`` is the flat axis [T, hidden] of ``groups``."""

    def fresh_rows(start_pos, chunk_lens):
        return (start_pos == 0) & (chunk_lens > 0)       # a row that carries no token changes nothing

    def continuing(slot, start_pos, chunk_lens, rows, width):
        """(``goes_on``, the slots to write: none for a row that hands its state on), or None where a
        group's shape lets no row continue another."""
        if rows == 1 or width == 1:
            return None, slot
        goes_on, handed_on = continuing_rows(slot, start_pos, chunk_lens, width)
        return goes_on, jnp.where(handed_on, cache["kda"].shape[1], slot)     # past the arena: dropped

    def convolve(cache, qkv, slot, start_pos, chunk_lens):
        tail = jnp.where(fresh_rows(start_pos, chunk_lens)[:, None, None], 0, cache["conv"][index, slot])
        goes_on, put = continuing(slot, start_pos, chunk_lens, *qkv.shape[:2])
        if goes_on is not None:     # the inputs before a continuing row's first are the last of the row before it
            before = jnp.roll(qkv[:, qkv.shape[1] - tail.shape[1]:], 1, axis=0).astype(tail.dtype)
            tail = jnp.where(goes_on[:, None, None], before, tail)
        qkv, tail = mixer.convolve(qkv, tail, chunk_lens)
        return qkv, dict(cache, conv=cache["conv"].at[index, put].set(tail.astype(cache["conv"].dtype), mode="drop"))

    def recur(cache, q, k, v, g, beta, slot, start_pos, chunk_lens):
        fresh = fresh_rows(start_pos, chunk_lens)
        if q.shape[1] == 1:     # one position a row: the states advance where they lie
            flags = jnp.where(chunk_lens > 0, LIVE, 0) | jnp.where(fresh, FRESH, 0)
            o, kda = kda_update(cache["kda"], index, slot, flags, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            return o[:, None], dict(cache, kda=kda)
        state = jnp.where(fresh[:, None, None, None], 0.0, cache["kda"][index, slot])
        goes_on, put = continuing(slot, start_pos, chunk_lens, *q.shape[:2])
        o, state = kda_chunk(q, k, v, g, beta, state, continues=goes_on)
        return o, dict(cache, kda=cache["kda"].at[index, put].set(state, mode="drop"))

    rows = (slot, start_pos, chunk_lens)
    qkv, g, beta, gate = mixer.in_project(h, live)
    qkv, cache = over_row_groups(groups, convolve, cache, (qkv, ), rows)
    # a slot that carries no token gives the recurrence zeros: under ``beta`` = 0 alone what it holds is still a
    # factor, and 0 x NaN of a padding slot would reach the row's state
    q, k, v = mixer.heads(jnp.where(live[:, None], qkv, 0))
    o, cache = over_row_groups(groups, recur, cache, (q, k, v, g, beta), rows)
    return mixer.finish(o, gate), cache


def _gqa_mix(mixer, h, groups, cfg, page_size, cache, index, table, start_pos, chunk_lens):
    """An attention layer's mixer: the projections and the gate on the flat
    axis; a group at a time, write the chunk's keys and values into its layer
    of the pages and read them back through the table: (mixed, cache)."""
    q, k, v = mixer.qkv(h)
    a, pages = paged_attention_core(groups, q, k, v, cache["pages"], index, table, start_pos, chunk_lens, page_size,
                                    attention_impl=cfg.attention_impl)
    return mixer.out(a, h), dict(cache, pages=pages)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _apply_layer(layer, mix, static, params, x, arrays, live, banks):
    return layer.apply({"params": params}, x, lambda mixer, h: mix(mixer, h, *static, *arrays), live, banks)


def _layer_traced_once(layer, mix, static, x, arrays, live, banks):
    """``phi4flash_cache.layer_traced_once`` for a layer that also takes the
    expert block's arguments: while the parameters are made the layer is
    called as it is; afterwards through one jitted function of the layer's own
    parameters, so a program that holds the KDA layer three times a period
    traces and lowers it once."""
    if layer.is_initializing():
        return layer(x, lambda mixer, h: mix(mixer, h, *static, *arrays), live, banks)
    return _apply_layer(layer.clone(parent=None, name=None), mix, static, layer.variables["params"], x, arrays, live,
                        banks)


class _CachePeriod(nn.Module):
    """One period of the twin, a scan's body: layer ``j`` of period ``period``
    is layer ``period x (its kind's layers a period) + (those before it in
    the period)`` of its kind in the cache.  ``x`` is the flat axis [T,
    hidden] of ``groups``.  ``banks``: per layer of the period, the periods'
    stack of its expert bank, or None."""
    cfg: SolarOpen2Config
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, period, slot, table, start_pos, chunk_lens, live, banks):
        cfg = self.cfg
        x, cache = carry
        for j, kind in enumerate(cfg.layer_types[:cfg.period]):
            index = period * cfg.per_period(kind) + cfg.per_period(kind, before=j)
            layer = SolarOpen2Layer(cfg, kind, name=layer_name(j))
            stacked = None if banks is None else (banks[j], period)
            if kind == "kda":
                x, cache = _layer_traced_once(layer, _kda_mix, (self.groups, ), x,
                                              (cache, index, slot, start_pos, chunk_lens, live), live, stacked)
            else:
                x, cache = _layer_traced_once(layer, _gqa_mix, (self.groups, cfg, self.page_size), x,
                                              (cache, index, table, start_pos, chunk_lens), live, stacked)
        return (x, cache), None


def stacked_banks(module, cfg, block="mlp"):
    """Per layer of a period, the periods' stack of its expert bank as the
    scan holds it, [periods, E, ...], for the blocks to read in place; None
    where the banks are not held in the compute dtype (or not made yet).
    ``block``: the name of a layer's expert block."""
    periods = module.variables.get("params", {}).get("periods")
    if periods is None:
        return None
    banks = tuple(tuple(nn.meta.unbox(periods[layer_name(j)][block]["experts"][name])
                        for name in ("w_gate", "w_up", "w_down")) for j in range(cfg.period))
    return banks if all(w.dtype == cfg.dtype for bank in banks for w in bank) else None


class SolarOpen2ForCausalLMWithCache(nn.Module):
    """``apply(variables, tokens, start_pos, block_table, cache, chunk_lens,
    last_only, groups)`` -> (logits, new cache): every twin's contract."""
    cfg: SolarOpen2Config
    page_size: int = 16

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        n_periods = cfg.num_hidden_layers // cfg.period
        slot, table = block_table[:, -1], block_table[:, :-1]
        x = embed_tokens(cfg)(tokens)
        (x, cache), _ = scan_blocks(_CachePeriod, n_periods, 6)(cfg, self.page_size, groups, name="periods")(
            (x, cache), jnp.arange(n_periods), slot, table, start_pos, chunk_lens, live_slots(groups, chunk_lens),
            stacked_banks(self, cfg))
        x = sampled_rows(x, chunk_lens, last_only, groups)
        return logits_as(head_logits(cfg, x), input_ids, last_only), cache
