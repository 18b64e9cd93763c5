"""Weights from the seed, made by the benchmark and handed to the program:
nothing the program makes (an initializer, a table, a scale) reaches the
reference.

The rule is the published one of both families (``initializer_range`` 0.02
in their ``config.json``): every matrix N(0, 0.02^2), every norm weight 1.
Biases, which the published initializer zeroes, also take N(0, 0.02^2) so
that a dropped bias shows.  One jitted call fills the whole tree on the
device in the dtype it is served or trained from."""

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def make_params(abstract_tree, seed: int, dtype=jnp.bfloat16, out_shardings=None):
    """Fill ``abstract_tree`` (shapes, as ``jax.eval_shape`` of the program's
    own ``init`` gives them) leaf by leaf inside one jitted program.  A leaf
    is keyed by its path, so a leaf added later does not reshuffle the rest."""
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(abstract_tree)]
    treedef = jax.tree.structure(abstract_tree)
    shapes = [l.shape for l in jax.tree.leaves(abstract_tree)]

    def fill(key):
        leaves = []
        for path, shape in zip(paths, shapes):
            if "norm" in path and path.endswith("['weight']"):
                leaves.append(jnp.ones(shape, dtype))
                continue
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            leaves.append((INIT_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype))
        return jax.tree.unflatten(treedef, leaves)

    # --seed may exceed 32 signed bits: split it over two folds
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(fill, out_shardings=out_shardings)(key)
