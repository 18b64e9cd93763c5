"""Grouped matrix product over ragged row groups: ``jax.lax.ragged_dot``'s
contract as a Pallas kernel.

    out[rows of group i] = lhs[rows of group i] @ rhs[i]

``lhs`` [m, k] holds the groups' rows one group after another,
``group_sizes`` [g] int32 says how many each has, ``rhs`` is the bank
[g, k, n].  Operands in bfloat16 (float32 in the tests) are multiplied with
float32 accumulation and the result has the operands' dtype.  Rows beyond the
groups' sum are in no group: no grid step touches them and what the output
holds there is whatever the buffer held (``moe/sharded_moe._experts_grouped``
masks them).

The kernels are megablox's (``jax.experimental.pallas.ops.tpu.megablox``),
carried and cut to what the expert layer needs: no shard of the groups, no
output to add to, tiles that divide the contraction.  The rows are cut into
tiles of ``tm``; a grid step is one *visit* of a (group, row tile) pair that
share rows, found on the device from ``group_sizes`` and scalar-prefetched, so
an empty group costs no step and reads no weights (the ``L x E`` stack a
scanned trunk hands in, of which ``E`` groups have rows).  A tile that two
groups share is visited once for each and the store is masked to the group's
rows.

* ``ds_gmm`` (forward, and the input gradient with the bank read transposed):
  grid ``(n tiles, visits, k tiles)``.  Where ``tk = k`` consecutive visits of
  one group name the same block of the bank and the pipeline does not fetch it
  again: a group's weights are read once a column tile however many row tiles
  it spans.
* ``ds_tgmm`` (weight gradient): ``out[i] = lhs[rows of i]^T @ rhs[rows of
  i]``, grid ``(n tiles, k tiles, visits)``, both operands masked to the
  group's rows, an empty group visited once to be zeroed.

Which path: the kernel where the program is traced for a TPU and runs on one
device or inside a fully manual ``shard_map`` (a Mosaic call is not GSPMD's to
partition); ``jax.lax.ragged_dot`` on the CPU and under a mesh the compiler
partitions.  ``takes_kernel`` is that rule, for the engine's step records too.

Tiles follow the shapes (``_tiles``; the sweep on the chip is in PERF.md
section 5); there is no option.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import get_abstract_mesh

from ..comm.mesh import get_trace_mesh, traced_for_tpu

LANE = 128
#: what a kernel's blocks may take of VMEM before the compiler is asked for
#: more than its default scoped limit (16 MiB on a v5e, of 128 MiB)
_DEFAULT_VMEM = 12 * 2**20
#: bytes of the bank a grid step of ``ds_gmm`` holds (twice that with the
#: pipeline's second buffer), and elements of ``ds_tgmm``'s accumulator
_BANK_BLOCK = 16 * 2**20
_ACC_ELEMENTS = 3 * 2**20


def takes_kernel() -> bool:
    """True where ``grouped_matmul`` is the Pallas kernel: traced for a TPU,
    on one device or with every mesh axis of more than one device manual."""
    if not traced_for_tpu():
        return False
    am = get_abstract_mesh()
    if am.manual_axes:
        return all(am.shape[a] == 1 for a in am.axis_names if a not in am.manual_axes)
    mesh = get_trace_mesh()
    return mesh is None or mesh.size == 1


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: Optional[bool] = None):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)``: lhs [m, k], rhs
    [g, k, n], group_sizes [g] int32 -> [m, n].  ``interpret``: None picks the
    path (``takes_kernel``); True or False forces the kernel, interpreted or
    compiled (the tests, an offline compile)."""
    if interpret is None:
        if not takes_kernel():
            return jax.lax.ragged_dot(lhs, rhs, group_sizes)
        interpret = False
    return _grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32), bool(interpret))


def _product(lhs, rhs, group_sizes, interpret):
    (m, k), n = lhs.shape, rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, _tiles(m, k, n, lhs.dtype.itemsize), False, interpret)


_grouped_matmul = jax.custom_vjp(_product, nondiff_argnums=(3, ))


def _fwd(lhs, rhs, group_sizes, interpret):
    return _product(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    dlhs = gmm(dout, rhs, group_sizes, _tiles(m, n, k, lhs.dtype.itemsize), True, interpret)
    # a row in no group has no product to differentiate, and no visit wrote it
    dlhs = jnp.where((jnp.arange(m) < jnp.sum(group_sizes))[:, None], dlhs, 0).astype(lhs.dtype)
    drhs = tgmm(lhs, dout, group_sizes, _tgmm_tiles(m, k, n), interpret).astype(rhs.dtype)
    return dlhs, drhs, None


_grouped_matmul.defvjp(_fwd, _bwd)


def _divisor_tile(dim: int, target: int) -> int:
    """The largest multiple of 128 up to ``target`` that divides ``dim``;
    ``dim`` itself where there is none (a block may span a whole dimension)."""
    for t in range(min(target, dim) // LANE * LANE, 0, -LANE):
        if dim % t == 0:
            return t
    return dim


def _row_tile(m: int) -> int:
    """Rows in tiles of 128: an expert of a serving step has some 40 rows and
    one of a training step some 270, the MXU takes as long over fewer than 128
    rows as over 128, and a taller tile multiplies more of other groups' rows
    under the mask."""
    return 128 if m >= 128 else -(-m // 8) * 8


def _tiles(m: int, k: int, n: int, itemsize: int):
    """(tm, tk, tn) of ``ds_gmm`` for [m, k] x [g, k, n].

    The whole contraction in one block, so that a group's second row tile
    finds the bank's block where the first left it, and the block as wide as
    ``_BANK_BLOCK`` allows.  The number of groups changed no winner of the
    sweep (8 of 24 with rows, 60 of 60): PERF.md section 5.
    """
    tn = _divisor_tile(n, max(LANE, _BANK_BLOCK // (k * itemsize)))
    tk = k if k * tn * itemsize <= _BANK_BLOCK else _divisor_tile(k, _BANK_BLOCK // (tn * itemsize))
    return _row_tile(m), tk, tn


def _tgmm_tiles(m: int, k: int, n: int):
    """(tm, tk, tn) of ``ds_tgmm`` for [m, k]^T x [m, n] -> [g, k, n]: of a
    group's [k, n] as much in one block as the float32 accumulator may hold,
    so that the rows are read once."""
    tn = _divisor_tile(n, 2048)
    return _row_tile(m), _divisor_tile(k, max(LANE, _ACC_ELEMENTS // tn)), tn


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """The (group, row tile) pairs that share rows, in order of rows (jitted:
    the six kernels of a layer's forward and backward trace it twice).

    Returns (group_offsets [g + 1], group_ids, m_tile_ids [tiles + g - 1], the
    number of pairs).  A tile is visited once by every group with a row in
    it; with ``visit_empty`` an empty group visits the tile it would start in
    (the weight gradient has its block to zero).  Entries behind the count
    repeat the last pair's and are never run.
    """
    g = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1, ), jnp.int32), ends]).astype(jnp.int32)
    # tiles a group touches: from its start rounded down to its end rounded up
    group_tiles = jnp.where(group_sizes == 0, 0, (ends + tm - 1) // tm - starts // tm)
    if visit_empty:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    length = tiles_m + g - 1
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), group_tiles, total_repeat_length=length)
    # a tile is visited once by the group that owns its first row (or by
    # none, and then never run) and once more by every group that starts
    # inside it
    starts_inside = (group_sizes > 0) & (starts % tm != 0)
    if visit_empty:
        starts_inside |= group_sizes == 0
    extra = jnp.zeros((tiles_m, ), jnp.int32).at[jnp.where(starts_inside, starts // tm, tiles_m)].add(1, mode="drop")
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), extra + 1, total_repeat_length=length)
    return offsets, group_ids, m_tile_ids, jnp.sum(group_tiles)


def _rows_of_group(offsets, group_ids, m_tile_ids, visit, tm: int, width: int):
    """[tm, width] bool: the tile's rows that belong to the visit's group."""
    group = group_ids[visit]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0) + m_tile_ids[visit] * tm
    return (row >= offsets[group]) & (row < offsets[group + 1])


def _vmem_limit(block_bytes: int):
    """None while the blocks fit the compiler's default; else what they need
    (inputs and outputs are double-buffered by the pipeline) and some room."""
    return None if block_bytes <= _DEFAULT_VMEM else min(block_bytes + 16 * 2**20, 100 * 2**20)


def _padded_rows(m: int, tm: int, *arrays):
    """Rows up to a whole number of tiles (the padding is in no group)."""
    pad = -m % tm
    return m + pad, [jnp.pad(a, ((0, pad), (0, 0))) if pad else a for a in arrays]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def gmm(lhs, rhs, group_sizes, tiles, transpose_rhs: bool, interpret: bool):
    """``ds_gmm``: lhs [m, k] x rhs [g, k, n] (or [g, n, k] with
    ``transpose_rhs``) -> [m, n] in lhs's dtype.  A jitted function of its
    own, so that a program which calls it three times a layer traces and
    lowers the kernel once a shape."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiles
    if k % tk:
        raise ValueError(f"ds_gmm: the contraction tile {tk} does not divide {k}")
    tiles_k, tiles_n = k // tk, pl.cdiv(n, tn)
    m_pad, (lhs, ) = _padded_rows(m, tm, lhs)
    offsets, group_ids, m_tile_ids, visits = _visits(group_sizes, m_pad, tm, False)

    def kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        dims = (((1, ), (1, )), ((), ())) if transpose_rhs else (((1, ), (0, )), ((), ()))
        acc[...] += jax.lax.dot_general(lhs[...], rhs[...], dims, preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            # the tile's other rows keep what an earlier visit of it stored
            mask = _rows_of_group(offsets, group_ids, m_tile_ids, visit, tm, tn)
            out[...] = jax.lax.select(mask, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)

    def rhs_block(n_i, visit, k_i, offsets, group_ids, m_tile_ids):
        return (group_ids[visit], n_i, k_i) if transpose_rhs else (group_ids[visit], k_i, n_i)

    item = lhs.dtype.itemsize
    blocks = 2 * item * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, visits, tiles_k),
            in_specs=[pl.BlockSpec((tm, tk), lambda n_i, visit, k_i, o, g, t: (t[visit], k_i)),
                      pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_block)],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, visit, k_i, o, g, t: (t[visit], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_vmem_limit(blocks)),
        cost_estimate=pl.CostEstimate(flops=2 * m * k * n, transcendentals=0,
                                      bytes_accessed=item * (m * k * tiles_n + rhs.size + m * n)),
        interpret=interpret,
        name="ds_gmm",
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
    return out[:m]


@functools.partial(jax.jit, static_argnums=(3, 4))
def tgmm(lhs, rhs, group_sizes, tiles, interpret: bool):
    """``ds_tgmm``: lhs [m, k], rhs [m, n] -> [g, k, n] in lhs's dtype,
    ``out[i] = lhs[rows of i]^T @ rhs[rows of i]``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    g = group_sizes.shape[0]
    tm, tk, tn = tiles
    tiles_k, tiles_n = pl.cdiv(k, tk), pl.cdiv(n, tn)
    m_pad, (lhs, rhs) = _padded_rows(m, tm, lhs, rhs)
    offsets, group_ids, m_tile_ids, visits = _visits(group_sizes, m_pad, tm, True)

    def kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, acc):
        visit = pl.program_id(2)
        group = group_ids[visit]
        first = (visit == 0) | (group_ids[jnp.maximum(visit - 1, 0)] != group)
        last = (visit == pl.num_programs(2) - 1) | (group_ids[jnp.minimum(visit + 1, pl.num_programs(2) - 1)] != group)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _():
            # both sides masked: a tile's other rows are another group's, or
            # in none and then anything
            rows = functools.partial(_rows_of_group, offsets, group_ids, m_tile_ids, visit, tm)
            a = jnp.where(rows(tk), lhs[...].astype(jnp.float32), 0.0).astype(lhs.dtype)
            b = jnp.where(rows(tn), rhs[...].astype(jnp.float32), 0.0).astype(rhs.dtype)
            acc[...] += jax.lax.dot_general(a, b, (((0, ), (0, )), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out[...] = acc[...].astype(out.dtype)

    item = lhs.dtype.itemsize
    blocks = 2 * item * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn + 8 * tm * (tk + tn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, tiles_k, visits),
            in_specs=[pl.BlockSpec((tm, tk), lambda n_i, k_i, visit, o, g, t: (t[visit], k_i)),
                      pl.BlockSpec((tm, tn), lambda n_i, k_i, visit, o, g, t: (t[visit], n_i))],
            out_specs=pl.BlockSpec((None, tk, tn), lambda n_i, k_i, visit, o, g, t: (g[visit], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_vmem_limit(blocks)),
        cost_estimate=pl.CostEstimate(flops=2 * m * k * n, transcendentals=0,
                                      bytes_accessed=item * (m * k * tiles_n + m * n * tiles_k + g * k * n)),
        interpret=interpret,
        name="ds_tgmm",
    )(offsets, group_ids, m_tile_ids, lhs, rhs)
