"""Pallas TPU paged (blocked-KV) decode attention.

TPU-native equivalent of the reference FastGen's blocked-flash/linear-KV
attention kernels (ref: deepspeed/inference/v2/kernels/ragged_ops —
``blocked_flash``, ``linear_blocked_kv_rotary``; KV geometry from
``inference/v2/ragged/kv_cache.py``).  The kernel attends a (small) chunk of
queries per sequence against that sequence's paged KV history, gathering
pages from the shared arena through the block table.

Implementation notes:
  * the block table and start positions ride in scalar-prefetch SMEM
    (``PrefetchScalarGridSpec``) so each grid step's page DMA address is
    computed from ``block_table[b, j]`` — the Pallas analog of the
    reference's atom-builder indirection (ragged/csrc/fast_host_buffer.cpp).
  * grid = (batch, pages); the page dimension is "arbitrary" (sequential)
    and carries the online-softmax state in VMEM scratch.  Each grid step
    DMAs one WHOLE page — [page, 2, n_kv, D], whose trailing block dims are
    the full array dims and therefore always tile-legal — and loops the kv
    heads in-kernel with per-head scratch.  (A per-head grid with a
    [page, 1, 1, D] block is rejected by the TPU tiling rules: the
    second-minor block dim 1 is neither 8-aligned nor the full n_kv dim.)
  * GQA: queries are laid out group-major ([B, n_kv, rep·C, D]) so each
    head iteration contracts its whole query group against the page.
  * pages whose first key is beyond the chunk's last visible position are
    skipped (`pl.when`), so decode cost scales with the sequence's true
    length, not max_pages — SplitFuse's "decode is O(context)" property.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _paged_kernel(bt_ref, sp_ref, q_ref, pg_ref, o_ref, *scr, page_size, max_pages, chunk,
                  scale, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ms, ls, accs = scr[:n_kv], scr[n_kv:2 * n_kv], scr[2 * n_kv:]

    @pl.when(j == 0)
    def _init():
        for hh in range(n_kv):
            ms[hh][:] = jnp.full_like(ms[hh], -jnp.inf)
            ls[hh][:] = jnp.zeros_like(ls[hh])
            accs[hh][:] = jnp.zeros_like(accs[hh])

    start = sp_ref[b]
    # last visible key position of this chunk is start + chunk - 1
    @pl.when(j * page_size <= start + chunk - 1)
    def _compute():
        for hh in range(n_kv):
            # bf16 operands straight into the MXU, f32 accumulation
            q = q_ref[0, hh]             # [repC, D]
            k = pg_ref[0, :, 0, hh]      # [page, D]
            v = pg_ref[0, :, 1, hh]      # [page, D]
            rep_c = q.shape[0]
            s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale  # [repC, page]
            # row r of the group-major q block is chunk position r % chunk
            row_c = jax.lax.broadcasted_iota(jnp.int32, (rep_c, page_size), 0) % chunk
            kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (rep_c, page_size), 1)
            s = jnp.where(kpos <= start + row_c, s, DEFAULT_MASK_VALUE)
            m_prev = ms[hh][:]
            l_prev = ls[hh][:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            ls[hh][:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            accs[hh][:] = accs[hh][:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            ms[hh][:] = m_new

    @pl.when(j == max_pages - 1)
    def _finalize():
        for hh in range(n_kv):
            o_ref[0, hh] = (accs[hh][:] / jnp.maximum(ls[hh][:], 1e-30)).astype(o_ref.dtype)


def _paged_sharded(q, pages, block_table, start_pos, chunk_lens, page_size, interpret, mesh, layer=None):
    """Run the paged kernel inside shard_map over the governing (trace) mesh.

    Mosaic custom calls cannot be auto-partitioned by GSPMD — the TP-sharded
    serving engine (inference/v2) traces this under a tensor-axis mesh, so the
    kernel wraps itself the way ``flash_attention._flash_sharded`` does.
    Attention is head-local: q shards on H, the page arena on its n_kv dim
    (one layer's pages or, with ``layer``, the whole arena under one more
    leading dimension), block tables, positions and the layer's index
    replicate, and no collective is needed inside — the o_proj allreduce after
    it is GSPMD's to insert.  A tensor degree that does not divide n_kv
    replicates (correct, just not distributed)."""
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import TENSOR_AXIS
    h, n_kv = q.shape[2], pages.shape[-2]
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    head_axes = (TENSOR_AXIS, ) if tp > 1 and n_kv % tp == 0 and h % tp == 0 else ()
    qspec = P(None, None, head_axes or None, None)
    pspec = P(*(None, ) * (pages.ndim - 2), head_axes or None, None)
    optional = {"chunk_lens": (chunk_lens, P(None)),
                "layer": (None if layer is None else jnp.asarray(layer, jnp.int32), P())}
    given = {name: arg_spec for name, arg_spec in optional.items() if arg_spec[0] is not None}

    def local(q_, pg_, bt_, sp_, *rest):
        kw = dict(zip(given, rest))
        return paged_attention_pallas(q_, pg_, bt_, sp_, kw.get("chunk_lens"), page_size,
                                      layer=kw.get("layer"), interpret=interpret)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, pspec, P(None, None), P(None), *(spec for _, spec in given.values())),
        out_specs=qspec,
        # pallas_call out_shapes carry no varying-mesh-axes annotation
        check_vma=False)
    return fn(q, pages, block_table, start_pos, *(arg for arg, _ in given.values()))


def paged_attention_pallas(q, pages, block_table, start_pos, chunk_lens, page_size,
                           *, layer=None, interpret: Optional[bool] = None):
    """Drop-in twin of ``models/llama_cache.paged_attention`` (jnp golden).

    q: [B, C, H, D]; pages: [P, page, 2, n_kv, D] (chunk K/V already
    written); block_table: [B, max_pages]; start_pos/chunk_lens: [B].
    With ``layer`` (an index, traced in a scanned trunk) ``pages`` is the
    whole arena [L, P, page, 2, n_kv, D] and the kernel reads that layer's
    pages where they lie: no layer of the arena is sliced out first.
    """
    from ..comm.mesh import get_trace_mesh, in_manual_mesh
    if interpret is None:
        tm = get_trace_mesh()
        dev = tm.devices.flat[0] if tm is not None else jax.devices()[0]
        interpret = getattr(dev, "platform", "") != "tpu"
    if isinstance(q, jax.core.Tracer) and not in_manual_mesh():
        mesh = get_trace_mesh()
        if mesh is not None and mesh.size > 1:
            return _paged_sharded(q, pages, block_table, start_pos, chunk_lens, page_size,
                                  interpret, mesh, layer)
    b, c, h, d = q.shape
    n_kv = pages.shape[-2]
    max_pages = block_table.shape[1]
    rep = h // n_kv
    scale = 1.0 / (d**0.5)

    # group-major query layout: [B, n_kv, rep*C, D], row = r*C + c
    qg = q.transpose(0, 2, 1, 3).reshape(b, n_kv, rep, c, d).reshape(b, n_kv, rep * c, d)

    grid = (b, max_pages)
    kernel = functools.partial(_paged_kernel, page_size=page_size, max_pages=max_pages,
                               chunk=c, scale=scale, n_kv=n_kv)

    def page_of(b, j, bt, sp):
        # j is CLAMPED to the row's last needed page: past it the index map
        # repeats the same page and Mosaic's pipeline skips the refetch —
        # pages beyond the true sequence length cost no DMA (they were still
        # copied pre-r4 even though pl.when skipped their compute)
        return bt[b, jnp.minimum(j, (sp[b] + c - 1) // page_size)]

    # one whole page: trailing dims (page, 2, n_kv, d) are the full array
    # dims → always tile-legal
    if layer is None:
        prefetch = (block_table, start_pos)
        page_spec = pl.BlockSpec((1, page_size, 2, n_kv, d),
                                 lambda b, j, bt, sp: (page_of(b, j, bt, sp), 0, 0, 0, 0))
    else:
        prefetch = (block_table, start_pos, jnp.reshape(layer, (1, )).astype(jnp.int32))
        page_spec = pl.BlockSpec((None, 1, page_size, 2, n_kv, d),
                                 lambda b, j, bt, sp, ly: (ly[0], page_of(b, j, bt, sp), 0, 0, 0, 0))
    kernel_fn = kernel if layer is None else (lambda bt, sp, ly, *refs: kernel(bt, sp, *refs))
    out = pl.pallas_call(
        kernel_fn,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                # q stays resident across the page sweep (index map constant in j)
                pl.BlockSpec((1, n_kv, rep * c, d), lambda b, j, *_: (b, 0, 0, 0)),
                page_spec,
            ],
            out_specs=pl.BlockSpec((1, n_kv, rep * c, d), lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=([pltpu.VMEM((rep * c, 1), jnp.float32)] * n_kv +
                            [pltpu.VMEM((rep * c, 1), jnp.float32)] * n_kv +
                            [pltpu.VMEM((rep * c, d), jnp.float32)] * n_kv),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep * c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ds_paged_attention",
    )(*prefetch, qg, pages)

    out = out.reshape(b, n_kv, rep, c, d).reshape(b, h, c, d).transpose(0, 2, 1, 3)
    if chunk_lens is not None:
        valid = jnp.arange(c)[None, :] < chunk_lens[:, None]
        out = jnp.where(valid[..., None, None], out, 0)
    return out
