"""The scanned serving trunks carry the arena whole and every write and read
names its layer (``models/llama_cache.py``).  A prefill in ragged chunks then
decode steps through the carried scan must read the full-sequence model's
logits and leave the arena that the per-layer form leaves: the same blocks
driven layer by layer here, each handed its own layer's pages as an arena of
one layer.  Bit for bit: the rows written, every other page untouched, the
null page zero.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh, trace_mesh
from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
from deepspeed_tpu.models.cache_zoo import FalconBlockCache
from deepspeed_tpu.models.falcon import FalconConfig, FalconForCausalLM
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import LlamaBlockCache, PagedKVConfig, init_kv_cache, paged_attention
from deepspeed_tpu.models.mixtral import PRESETS as MIXTRAL_PRESETS, MixtralForCausalLM
from deepspeed_tpu.models.mixtral_cache import MixtralBlockCache
from deepspeed_tpu.ops.paged_attention import paged_attention_pallas

KV = PagedKVConfig(num_pages=24, page_size=4, max_pages_per_seq=6)
ROWS, LENGTH = 3, 24
LLAMA = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                    num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                    rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)
MIXTRAL = dataclasses.replace(MIXTRAL_PRESETS["tiny"], dtype=jnp.float32, remat=False, drop_tokens=False)
FALCON_ALIBI = FalconConfig(vocab_size=128, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                            num_kv_heads=4, alibi=True, parallel_attn=False, bias=True,
                            max_position_embeddings=128, dtype=jnp.float32, remat=False)

#: name -> (config, full-sequence model, block, where the stacked blocks and the embedding lie in the tree).
#: A block takes the flat axis of row groups: here the rectangle as its one group
FAMILIES = {
    "llama": (LLAMA, LlamaForCausalLM, LlamaBlockCache, ("model", "layers"), "embed_tokens"),
    "mixtral": (MIXTRAL, MixtralForCausalLM, MixtralBlockCache, ("layers", ), "embed_tokens"),
    "mistral_window": (dataclasses.replace(LLAMA, sliding_window=6), LlamaForCausalLM, LlamaBlockCache,
                       ("model", "layers"), "embed_tokens"),
    "falcon_alibi": (FALCON_ALIBI, FalconForCausalLM, FalconBlockCache, ("h", ), "word_embeddings"),
}

#: (chunk width, a row's real tokens in it) a step: ragged prefill chunks, then one-token steps
SCHEDULES = {
    "rows_finish_apart": [(8, (8, 5, 0)), (8, (3, 8, 6)), (1, (1, 1, 1)), (1, (1, 0, 1))],
    "short_then_wide": [(6, (2, 6, 1)), (6, (6, 0, 5)), (6, (6, 6, 6)), (1, (0, 1, 1)), (1, (1, 1, 1))],
}


def _per_layer_arena(one_layer, layers, x, arena, *batch):
    """The arena after one step of the per-layer form: block ``i`` with its own
    parameters and layer ``i``'s pages, an arena of one layer."""
    out = []
    for i in range(arena.shape[0]):
        x, pages = one_layer(jax.tree.map(lambda w, i=i: w[i], layers), x, arena[i:i + 1], *batch)
        out.append(pages)
    return jnp.concatenate(out)


def _check(family, impl, schedule):
    """Run ``schedule`` through the twin and, beside it, through the per-layer
    form; hold every step's logits to the full-sequence model's and its arena
    to the per-layer form's."""
    cfg, full_cls, block_cls, layers_at, embed_at = FAMILIES[family]
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    page = KV.page_size
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (ROWS, LENGTH), dtype=np.int32)
    params = nn.meta.unbox(full_cls(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    want = full_cls(cfg).apply(params, jnp.asarray(tokens))
    want = np.asarray(want[0] if isinstance(want, tuple) else want)      # an expert model returns its losses too
    layers = params["params"]
    for key in layers_at:
        layers = layers[key]

    def one_layer(width):
        block = block_cls(cfg, page, ((ROWS, width), ))
        return jax.jit(lambda w, x, pages, *batch: block.apply({"params": w}, (x, pages), 0, *batch)[0])

    twin = jax.jit(build_cache_model(cfg, page).apply)
    # an arena that is not blank, so that "untouched" says something; the null page is zero
    arena = jax.random.normal(jax.random.PRNGKey(1), init_kv_cache(cfg, KV, jnp.float32).shape).at[:, 0].set(0)
    tables = 1 + np.arange(ROWS * KV.max_pages_per_seq, dtype=np.int32).reshape(ROWS, -1)
    start = np.zeros((ROWS, ), np.int32)
    for width, lens in SCHEDULES[schedule]:
        lens = np.asarray(lens, np.int32)
        ids = np.zeros((ROWS, width), np.int32)
        for r in range(ROWS):
            ids[r, :lens[r]] = tokens[r, start[r]:start[r] + lens[r]]
        positions = start[:, None] + np.arange(width)[None, :]
        x = params["params"][embed_at]["embedding"][jnp.asarray(ids)].reshape(ROWS * width, -1)
        positions = positions.reshape(-1)
        by_layer = np.asarray(_per_layer_arena(one_layer(width), layers, x, arena, jnp.asarray(positions),
                                               jnp.asarray(tables), jnp.asarray(start), jnp.asarray(lens)))
        logits, after = twin(params, jnp.asarray(ids), jnp.asarray(start), jnp.asarray(tables), arena,
                             jnp.asarray(lens))
        logits, before, after = np.asarray(logits), np.asarray(arena), np.asarray(after)
        for r in range(ROWS):
            np.testing.assert_allclose(logits[r, :lens[r]], want[r, start[r]:start[r] + lens[r]], atol=3e-5, rtol=3e-5)
        np.testing.assert_array_equal(after, by_layer)
        assert not after[:, 0].any(), "padding wrote into the null page"
        written = np.zeros(after.shape[1:3], bool)       # [page, row of the page]
        for r in range(ROWS):
            for t in range(start[r], start[r] + lens[r]):
                written[tables[r, t // page], t % page] = True
        np.testing.assert_array_equal(after[:, ~written], before[:, ~written])
        assert (after[:, written] != before[:, written]).any(axis=(-1, -2, -3)).all(), "a real row was not written"
        arena, start = jnp.asarray(after), start + lens


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("impl", ["reference", "flash"])
@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_carried_scan_reads_the_models_logits_and_writes_the_per_layer_arena(family, impl, schedule, monkeypatch):
    """``impl``: the jnp path (a layer's slice of the arena is read) and the
    Pallas kernel, interpreted (the layer is a prefetched scalar).  The arenas
    are compared bit for bit, which means the experts' dense form: three rows
    of 2 over 4 experts would take the sorted one (``takes_sorted``), whose
    CPU stand-in adds a stack's groups in another order than a layer's own."""
    from deepspeed_tpu.moe import sharded_moe
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: False)
    _check(family, impl, schedule)


@pytest.mark.parametrize("family,impl", [("falcon_alibi", "reference"), ("mistral_window", "flash")])
def test_alibi_and_window_read_a_layers_slice_of_the_carried_arena(family, impl):
    """These go through the jnp path whatever the configuration asks for (the
    full-sequence Falcon refuses any other under alibi)."""
    _check(family, impl, "rows_finish_apart")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_head_over_the_sampled_rows_equals_the_all_position_logits(family):
    """The engine's step programs ask the twin for each row's last real token
    alone (``last_only``: the rows are gathered before the final norm and the
    head); whoever compares logits asks for every position.  One answer, and
    the same arena, at every step of a ragged schedule."""
    cfg, full_cls, *_ = FAMILIES[family]
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (ROWS, LENGTH), dtype=np.int32)
    params = nn.meta.unbox(full_cls(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    twin = jax.jit(build_cache_model(cfg, KV.page_size).apply, static_argnums=6)
    arena = init_kv_cache(cfg, KV, jnp.float32)
    tables = jnp.asarray(1 + np.arange(ROWS * KV.max_pages_per_seq, dtype=np.int32).reshape(ROWS, -1))
    start = np.zeros((ROWS, ), np.int32)
    for width, lens in SCHEDULES["rows_finish_apart"]:
        lens = np.asarray(lens, np.int32)
        ids = np.zeros((ROWS, width), np.int32)
        for r in range(ROWS):
            ids[r, :lens[r]] = tokens[r, start[r]:start[r] + lens[r]]
        batch = (jnp.asarray(ids), jnp.asarray(start), tables, arena, jnp.asarray(lens))
        every, after = twin(params, *batch, False)
        last, after_last = twin(params, *batch, True)
        assert last.shape == (ROWS, 1, cfg.vocab_size)
        for r in np.flatnonzero(lens):
            np.testing.assert_allclose(last[r, 0], every[r, lens[r] - 1], atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(after, after_last)
        arena, start = after, start + lens


@pytest.mark.parametrize("traced", [True, False], ids=["traced_index", "static_index"])
def test_kernel_reads_a_layer_of_the_whole_arena_under_a_tensor_mesh(traced):
    """``_paged_sharded``: the arena sharded over its key heads, the layer's
    index replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = create_mesh(MeshSpec(data=1, tensor=2), devices=jax.devices()[:2])
    layers, heads, n_kv, d, chunk = 3, 4, 2, 8, 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    arena = jax.random.normal(k1, (layers, KV.num_pages, KV.page_size, 2, n_kv, d))
    q = jax.random.normal(k2, (ROWS, chunk, heads, d))
    tables = 1 + jnp.arange(ROWS * KV.max_pages_per_seq, dtype=jnp.int32).reshape(ROWS, -1)
    start, lens = jnp.asarray([0, 7, 13], jnp.int32), jnp.asarray([4, 2, 0], jnp.int32)
    arena_sh = jax.device_put(arena, NamedSharding(mesh, P(None, None, None, None, "tensor", None)))
    for layer in range(layers):
        want = paged_attention(q, arena[layer], tables, start, lens, KV.page_size)
        with mesh, trace_mesh(mesh):
            if traced:
                got = jax.jit(lambda q, a, ly: paged_attention_pallas(q, a, tables, start, lens, KV.page_size,
                                                                      layer=ly, interpret=True))(q, arena_sh, layer)
            else:
                got = jax.jit(lambda q, a: paged_attention_pallas(q, a, tables, start, lens, KV.page_size,
                                                                  layer=layer, interpret=True))(q, arena_sh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
