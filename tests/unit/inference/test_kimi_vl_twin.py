"""The serving twin of Kimi-VL (``models/kimi_vl_cache.py``) against the
full-sequence model: SplitFuse chunks that cross text / image borders (an
image spans chunks, a chunk holds text and image slots), then decode through
the latent pages; the same as two row groups; on scattered pages."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.kimi_vl_cache import KimiVLForCausalLMWithCache
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.xing4_cache import LatentPagesGeometry

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_kimi_vl import prompt_with_images, small  # noqa: E402

PAGE, CHUNK = 16, 16


@pytest.fixture(scope="module")
def served():
    cfg, _, model, params = small()
    ids, mm_index, images = prompt_with_images(np.random.default_rng(11), [(4, 6), (8, 6), (4, 4)])
    rows = jnp.concatenate([model.apply(params, jnp.asarray(px), jnp.asarray(g), method="encode_images")
                            for px, g in images])
    rows = jnp.pad(rows, ((0, -rows.shape[0] % 4), (0, 0)))
    ids = np.concatenate([ids, np.random.default_rng(12).integers(1, 400, 6)])        # six decode steps behind
    mm_index = np.concatenate([mm_index, np.full(6, -1)])
    full = model.apply(params, jnp.asarray(ids)[None], mm_index=jnp.asarray(mm_index)[None], mm_rows=rows)[0]
    return cfg, params, ids, mm_index, rows.reshape(-1, 4, cfg.hidden_size), full


def test_the_registry_serves_the_family_on_latent_pages():
    cfg = small()[0]
    twin = cache_twin(cfg)
    assert isinstance(twin.model(cfg, page_size=PAGE), KimiVLForCausalLMWithCache)      # not its parent's twin
    assert isinstance(cache_geometry(cfg, PAGE), LatentPagesGeometry)
    cache = twin.init_cache(cfg, PagedKVConfig(num_pages=8, page_size=PAGE, max_pages_per_seq=4), jnp.float32, 0, 0)
    assert cache.shape == (cfg.num_hidden_layers, 8, PAGE, 128)                          # 64 + 16 in 128 lanes


@pytest.mark.parametrize("pages", [[1, 2, 3, 4, 5], [9, 2, 14, 5, 11]], ids=["consecutive", "scattered"])
def test_chunks_across_text_and_image_borders_then_decode(served, pages):
    cfg, params, ids, mm_index, mm_rows, full = served
    n_prompt = len(ids) - 6
    twin = cache_twin(cfg).model(cfg, page_size=PAGE)
    kv = PagedKVConfig(num_pages=16, page_size=PAGE, max_pages_per_seq=8)
    cache = cache_twin(cfg).init_cache(cfg, kv, jnp.float32, 0, 0)
    table = np.zeros((1, 8), np.int32)
    table[0, :len(pages)] = pages
    got = []

    def feed(width, start, n, cache):
        toks, index = np.zeros((1, width), np.int32), np.full((1, width), -1, np.int32)
        toks[0, :n], index[0, :n] = ids[start:start + n], mm_index[start:start + n]
        logits, cache = twin.apply(params, jnp.asarray(toks), jnp.asarray([start]), jnp.asarray(table), cache,
                                   jnp.asarray([n]), False, None, jnp.asarray(index), mm_rows)
        got.append(logits[0, :n])
        return cache

    borders = 0
    for s in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - s)
        borders += len({bool(i >= 0) for i in mm_index[s:s + n]}) == 2
        cache = feed(CHUNK, s, n, cache)
    assert borders >= 3                                    # chunks that hold text and image slots
    for s in range(n_prompt, len(ids)):                    # decode: one token, no image argument
        logits, cache = twin.apply(params, jnp.asarray(ids[s:s + 1])[None], jnp.asarray([s]), jnp.asarray(table),
                                   cache, jnp.asarray([1]))
        got.append(logits[0])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got)), np.asarray(full), atol=2e-4)


def test_two_row_groups_decode_rows_beside_a_prefilling_row(served):
    cfg, params, ids, mm_index, mm_rows, full = served
    twin = cache_twin(cfg).model(cfg, page_size=PAGE)
    kv = PagedKVConfig(num_pages=32, page_size=PAGE, max_pages_per_seq=8)
    cache = cache_twin(cfg).init_cache(cfg, kv, jnp.float32, 0, 0)
    tables = np.zeros((3, 8), np.int32)
    tables[0, :5], tables[1, :5], tables[2, :5] = [3, 9, 1, 12, 7], [20, 4, 17, 6, 25], [30, 2, 28, 11, 19]
    head = 40                                              # rows 0 and 1 hold 40 and 41 tokens and decode; row 2 prefills
    for row, have in ((0, head), (1, head + 1)):
        for s in range(0, have, CHUNK):
            n = min(CHUNK, have - s)
            toks, index = np.zeros((1, CHUNK), np.int32), np.full((1, CHUNK), -1, np.int32)
            toks[0, :n], index[0, :n] = ids[s:s + n], mm_index[s:s + n]
            _, cache = twin.apply(params, jnp.asarray(toks), jnp.asarray([s]), jnp.asarray(tables[row:row + 1]), cache,
                                  jnp.asarray([n]), False, None, jnp.asarray(index), mm_rows)
    groups = ((2, 1), (1, CHUNK))
    start = 16                                             # row 2's second chunk: the tail of image 1's run and text
    for s in range(0, start, CHUNK):
        toks, index = ids[s:s + CHUNK][None], mm_index[s:s + CHUNK][None]
        _, cache = twin.apply(params, jnp.asarray(toks), jnp.asarray([s]), jnp.asarray(tables[2:3]), cache,
                              jnp.asarray([CHUNK]), False, None, jnp.asarray(index), mm_rows)
    flat = np.concatenate([[ids[head]], [ids[head + 1]], ids[start:start + CHUNK]]).astype(np.int32)
    flat_index = np.concatenate([[mm_index[head]], [mm_index[head + 1]], mm_index[start:start + CHUNK]]).astype(np.int32)
    logits, cache = twin.apply(params, jnp.asarray(flat), jnp.asarray([head, head + 1, start]), jnp.asarray(tables),
                               cache, jnp.asarray([1, 1, CHUNK]), False, groups, jnp.asarray(flat_index), mm_rows)
    want = np.concatenate([full[head:head + 1], full[head + 1:head + 2], full[start:start + CHUNK]])
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-4)
    # last_only: the rows' last tokens alone, as the engine's step samples them
    last, _ = twin.apply(params, jnp.asarray(flat), jnp.asarray([head, head + 1, start]), jnp.asarray(tables), cache,
                         jnp.asarray([1, 1, CHUNK]), True, groups, jnp.asarray(flat_index), mm_rows)
    np.testing.assert_allclose(np.asarray(last[:, 0]), want[[0, 1, -1]], atol=2e-4)
