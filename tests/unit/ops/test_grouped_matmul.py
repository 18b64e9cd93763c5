"""``ops/grouped_matmul.py`` against ``jax.lax.ragged_dot``, the contract it
keeps: the kernels ``ds_gmm`` and ``ds_tgmm`` in interpret mode on the CPU at
small shapes, values and gradients, one row of ``CASES`` a shape of trouble."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.grouped_matmul import _tgmm_tiles, _tiles, _visits, grouped_matmul, takes_kernel

#: name: (m, k, n, group sizes)
CASES = {
    "ragged_with_empty_groups": (512, 256, 384, [100, 0, 156, 0, 50, 206]),
    "tail_of_rows_in_no_group": (512, 256, 384, [100, 0, 156, 0, 50, 0]),
    "rows_not_a_multiple_of_the_tile": (300, 128, 256, [100, 0, 156, 1]),
    "rows_fewer_than_a_tile": (64, 128, 128, [10, 0, 20]),
    "groups_end_on_tile_edges": (384, 128, 128, [128, 0, 128, 128]),
    "every_row_in_one_group": (256, 128, 256, [0, 256, 0]),
    "stack_with_one_layer_live": (512, 256, 256, [0, 0, 0, 0, 30, 0, 200, 40, 0, 0, 0, 0]),
    "one_row_a_group": (256, 128, 128, [1, 1, 1, 1, 1]),
    "no_row_at_all": (256, 128, 128, [0, 0, 0]),
}


def _operands(name, dtype=jnp.float32):
    m, k, n, sizes = CASES[name]
    rng = np.random.default_rng(sum(sizes) + m)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)) / np.sqrt(k), dtype)
    live = jnp.arange(m) < sum(sizes)
    # the caller's mask: what lies beyond the groups' sum is not defined
    weight = jnp.asarray(rng.normal(size=(m, n)), dtype) * live[:, None]
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), live, weight


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max(initial=0.0))), rtol=0)


@pytest.mark.parametrize("name", CASES)
def test_forward_equals_ragged_dot_on_the_groups_rows(name):
    lhs, rhs, sizes, live, _ = _operands(name)
    got = grouped_matmul(lhs, rhs, sizes, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert got.dtype == lhs.dtype
    _close(jnp.where(live[:, None], got, 0), jnp.where(live[:, None], want, 0), 1e-5)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "checkpoint"])
@pytest.mark.parametrize("name", CASES)
def test_gradients_of_both_operands_equal_ragged_dots(name, remat):
    lhs, rhs, sizes, live, weight = _operands(name)

    def grads(product):
        product = jax.checkpoint(product) if remat else product
        loss = lambda a, b: jnp.sum(jnp.where(live[:, None], product(a, b, sizes), 0) * weight)  # noqa: E731
        return jax.grad(loss, argnums=(0, 1))(lhs, rhs)

    got = grads(lambda a, b, s: grouped_matmul(a, b, s, interpret=True))
    want = grads(jax.lax.ragged_dot)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and bool(jnp.all(jnp.isfinite(g)))
        _close(g, w, 1e-5)
    # a row in no group and an empty group's bank have nothing to differentiate
    assert not np.asarray(got[0])[~np.asarray(live)].any()
    assert not np.asarray(got[1])[np.asarray(sizes) == 0].any()


@pytest.mark.parametrize("name", ["ragged_with_empty_groups", "stack_with_one_layer_live"])
def test_bfloat16_operands_accumulate_in_float32(name):
    lhs, rhs, sizes, live, weight = _operands(name, jnp.bfloat16)
    got = grouped_matmul(lhs, rhs, sizes, interpret=True)
    exact = jax.lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    assert got.dtype == jnp.bfloat16
    # one rounding of the float32 sum to bfloat16: 2^-8 of the value
    _close(jnp.where(live[:, None], got, 0), jnp.where(live[:, None], exact, 0), 2.0**-8)
    loss = lambda fn: lambda a, b: jnp.sum((jnp.where(live[:, None], fn(a, b), 0) * weight).astype(jnp.float32))  # noqa: E731
    got = jax.grad(loss(lambda a, b: grouped_matmul(a, b, sizes, interpret=True)), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(a, b, sizes)), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        _close(g, w, 2.0**-6)


def test_an_empty_group_costs_no_visit():
    """The stack a scanned trunk hands in, L x E groups of which E have rows:
    as many (group, row tile) visits as the layer's own E groups alone, each
    of a group with rows, so no step reads an empty group's weights."""
    m, tm = 1024, 128
    own = jnp.asarray([100, 37, 0, 220, 3, 150, 60, 90], jnp.int32)
    stack = jnp.zeros((24, ), jnp.int32).at[8:16].set(own)
    _, ids_own, tiles_own, n_own = _visits(own, m, tm, False)
    _, ids_stack, tiles_stack, n_stack = _visits(stack, m, tm, False)
    n = int(n_own)
    assert n == int(n_stack)
    # rows 0-659 lie in 6 tiles, and 6 of the 7 groups with rows start inside a tile another group began
    assert n == 6 + 6
    np.testing.assert_array_equal(np.asarray(ids_stack)[:n], np.asarray(ids_own)[:n] + 8)
    np.testing.assert_array_equal(np.asarray(tiles_stack)[:n], np.asarray(tiles_own)[:n])
    assert (np.asarray(stack)[np.asarray(ids_stack)[:n]] > 0).all()


def test_the_weight_gradient_visits_an_empty_group_once_to_zero_it():
    sizes = jnp.asarray([128, 0, 100, 0], jnp.int32)
    _, ids, tiles, n = _visits(sizes, 256, 128, True)
    assert int(n) == 4
    np.testing.assert_array_equal(np.asarray(ids)[:4], [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(tiles)[:4], [0, 1, 1, 1])


def test_on_the_cpu_the_product_is_ragged_dot():
    """The path rule's first half: no TPU, no kernel (the second half, the
    meshes, is in ``tests/unit/moe/test_moe.py``)."""
    lhs, rhs, sizes, _, _ = _operands("ragged_with_empty_groups")
    assert not takes_kernel()
    text = str(jax.make_jaxpr(grouped_matmul)(lhs, rhs, sizes))
    assert "ragged_dot" in text and "pallas_call" not in text
    text = str(jax.make_jaxpr(lambda a, b, s: grouped_matmul(a, b, s, interpret=True))(lhs, rhs, sizes))
    assert "pallas_call" in text and "ragged_dot" not in text


@pytest.mark.parametrize("shape,tiles", [((4096, 4096, 14336), (128, 4096, 2048)), ((4096, 14336, 4096), (128, 14336, 512)),
                                         ((16384, 2048, 1408), (128, 2048, 1408)), ((16384, 1408, 2048), (128, 1408, 2048)),
                                         ((4096, 2**17, 4096), (128, 2**16, 128)), ((48, 16, 32), (48, 16, 32))])
def test_tiles_of_the_cells_shapes_are_the_sweeps_winners(shape, tiles):
    """Serving's gate/up and down, training's; a contraction too long for one
    block is cut; a toy shape is one block."""
    assert _tiles(*shape, 2) == tiles
    (m, k, n), (tm, tk, tn) = shape, tiles
    assert k % tk == 0 and n % tn == 0 and tm % 8 == 0


def test_weight_gradient_tiles_hold_a_training_bank_whole_and_cut_a_serving_one():
    assert _tgmm_tiles(16384, 2048, 1408) == (128, 2048, 1408)
    assert _tgmm_tiles(16384, 1408, 2048) == (128, 1408, 2048)
    assert _tgmm_tiles(4096, 4096, 14336) == (128, 1024, 2048)
