"""MiniCPM-SALA's selection (``models/minicpm_sala.select_blocks``) against the
plain reference's (``benchmark/refs/minicpm_sala.chosen_blocks``): the blocks
chosen, the forced ones, the switch at ``dense_len`` by the query's position,
ties, and that with ``topk`` at least the number of blocks a sparse layer is
dense attention; then the two ways the chosen blocks are read through the
pages (``ops/sparse_paged_attention.py``): the list walk of one-token rows
(kernel ``ds_sparse_paged_attention``, interpreted) and the blocked form."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.minicpm_sala import (MiniCPMSALAForCausalLM, compressed_keys, masked_attention,
                                               select_blocks)
from deepspeed_tpu.ops.sparse_paged_attention import (block_lists, page_lists, sparse_paged_blocked,
                                                      sparse_paged_decode, sparse_paged_decode_reference)

from test_minicpm_sala import CFG, SPARSE, draw, ref, rel  # noqa: F401

S, H, G, D = 416, 4, 2, 32


@pytest.fixture(scope="module")
def qk():
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    return 2.0 * jax.random.normal(keys[0], (1, S, H, D)), jax.random.normal(keys[1], (1, S, G, D))


@pytest.fixture(scope="module")
def blocks(qk):
    q, k = qk
    return np.asarray(select_blocks(q, compressed_keys(k, SPARSE), jnp.arange(S)[None], SPARSE))[0]     # [S, G, nb]


def test_chosen_blocks_equal_the_references(qk, blocks):
    q, k = qk
    n_ck = (S - SPARSE["kernel_size"]) // SPARSE["kernel_stride"] + 1
    kc = jnp.stack([k[0, 8 * i:8 * i + 16].mean(axis=0) for i in range(n_ck)])
    want, margin = ref.chosen_blocks(q[0], kc, jnp.arange(S), SPARSE, -(-S // 32))
    np.testing.assert_array_equal(blocks[..., :want.shape[-1]], np.asarray(want))
    assert not blocks[..., want.shape[-1]:].any()
    # neighbouring blocks share a compressed key, so exact ties are common (margin 0) and go to the lower index
    margin = np.asarray(margin)
    assert np.isinf(margin[:160]).all() and (margin[192:] >= 0).all() and (margin[192:] == 0).any() \
        and (margin[192:] > 0).any()


def test_forced_blocks_the_switch_by_position_and_the_count(blocks):
    t = np.arange(S)
    own = t // 32
    for g in range(G):
        seen = blocks[:, g]
        assert all(seen[i, :own[i] + 1].all() for i in range(128))             # under dense_len: every block up to its own
        assert seen[128:, 0].all()                                             # the initial block
        assert all(seen[i, own[i] - 1:own[i] + 1].all() for i in range(128, S))   # the window's two
        assert not any(seen[i, own[i] + 1:].any() for i in range(S))           # nothing ahead
        # 1 + 2 forced and 2 chosen, once there are two to choose among
        assert (seen[160:].sum(axis=-1) == 5).all() and (seen[128:160].sum(axis=-1) == own[128:160] + 1).all()
    # position 127 sees all four blocks, position 192 five of seven: the switch is the position's, not the call's
    assert blocks[127, 0].sum() == 4 and blocks[192, 0].sum() == 5
    assert (blocks[:, 0] != blocks[:, 1]).any()                                # the key heads choose for themselves


def test_ties_go_to_the_lower_index():
    """Equal compressed keys give equal block scores: the chosen ones are the first."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, H, D))
    ck = jnp.ones((1, 52, G, D))
    seen = np.asarray(select_blocks(q, ck, jnp.asarray([[415]]), SPARSE))[0, 0]
    assert (np.flatnonzero(seen[0]) == [0, 1, 2, 11, 12]).all() and (seen[0] == seen[1]).all()


def test_with_topk_over_the_blocks_a_sparse_layer_is_dense_attention():
    params = draw(CFG)
    ids = jnp.asarray(np.random.default_rng(1).integers(1, CFG.vocab_size, (1, 240)))
    everything = dataclasses.replace(CFG, sparse=dict(SPARSE, topk=64))
    dense = dataclasses.replace(CFG, sparse=dict(SPARSE, dense_len=4096))
    with jax.default_matmul_precision("highest"):
        got, want, sparse = (np.asarray(jax.jit(MiniCPMSALAForCausalLM(c).apply)(params, ids)) for c in
                             (everything, dense, CFG))
    np.testing.assert_array_equal(got, want)
    assert rel(sparse[0, 160:], want[0, 160:]) > 1e-3


# ------------------------------------------------------------ through the pages


def _paged(seed, b=3, n_pages=64, page=8, width=20):
    """Keys and values of ``b`` rows of ``width`` pages each on scattered pages of layer 1 of an arena of 2."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    arena = jax.random.normal(keys[0], (2, n_pages, page, 2, G, D))
    table = np.random.default_rng(seed).permutation(np.arange(1, n_pages))[:b * width].reshape(b, width).astype(np.int32)
    return arena, jnp.asarray(table), 2.0 * jax.random.normal(keys[1], (b, 1, H, D))


def _dense_rows(arena, table, page):
    rows = arena[1][table]                                                      # [B, W, page, 2, G, D]
    b, w = table.shape
    return rows[:, :, :, 0].reshape(b, w * page, G, D), rows[:, :, :, 1].reshape(b, w * page, G, D)


def test_list_walk_kernel_equals_the_masked_dense_product():
    arena, table, q = _paged(0)
    pos = jnp.asarray([150, 97, 41])               # the second under dense_len... all three choose by position
    live = jnp.asarray([True, True, False])
    k, _ = _dense_rows(arena, table, 8)
    ck = compressed_keys(k, SPARSE)[:, :table.shape[1]]
    sp = dict(SPARSE, dense_len=64)
    blocks = select_blocks(q, ck, pos[:, None], sp)                              # [B, 1, G, nb]
    order, count = block_lists(blocks[:, 0], 6)
    assert np.asarray(count).tolist() == [[5, 5], [4, 4], [2, 2]]
    lists, n_pages = page_lists(order, count, table, pos, live, 8, 32)
    assert lists.shape == (3, G, 24) and np.asarray(n_pages).tolist() == [4 * 4 + 3, 3 * 4 + 1, 0]
    got = sparse_paged_decode(q[:, 0], arena, jnp.asarray(1), lists, n_pages, pos, 8, interpret=True)
    same = sparse_paged_decode_reference(q[:, 0], arena, 1, lists, n_pages, pos, 8)
    k, v = _dense_rows(arena, table, 8)
    want = masked_attention(q, k, v, pos[:, None], blocks, 32, D**-0.5)[:, 0]
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(same[:2], want[:2], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[2]).any() and not np.asarray(same[2]).any()


def test_blocked_form_equals_the_masked_dense_product_for_a_chunk():
    arena, table, _ = _paged(1, b=2)
    c = 24
    q = 2.0 * jax.random.normal(jax.random.PRNGKey(9), (2, c, H, D))
    start, lens = jnp.asarray([130, 0]), jnp.asarray([24, 9])
    k, v = _dense_rows(arena, table, 8)
    qpos = start[:, None] + jnp.arange(c)[None]
    blocks = select_blocks(q, compressed_keys(k, SPARSE)[:, :table.shape[1]], qpos, dict(SPARSE, dense_len=64))
    want = masked_attention(q, k, v, qpos, blocks, 32, D**-0.5)
    for block_keys in (32, 64):
        got = jax.jit(lambda *a: sparse_paged_blocked(*a, 8, 32, block_keys=block_keys))(
            q, arena, jnp.asarray(1), table, start, lens, blocks)
        np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got[1, :9], want[1, :9], atol=2e-5, rtol=2e-5)
        assert not np.asarray(got[1, 9:]).any()
    # the loop's pages come through a kernel on the chip (a DMA a page); interpreted, it is the plain gather
    from deepspeed_tpu.ops.sparse_paged_attention import _gather_pages
    np.testing.assert_array_equal(_gather_pages(arena, jnp.asarray(1), table[:, :5], True), arena[1][table[:, :5]])
