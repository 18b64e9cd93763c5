"""The paged kernel compiled for the chip, without the chip, at the shapes the
benchmark's serving cells give it: what interpret mode cannot refuse (tiling,
the VMEM a block of 512 key rows and a row's softmax state need, strided
loads of a head's rows, DMAs out of an arena left in HBM, which pages such a
DMA may take) the chip's compiler does, here, in a second or two a shape.  And a small Mixtral twin's step
programs, to read the compiler's buffer assignment for a second arena.
Nothing runs and no time is read.

The topology is described inside a fixture (never at import: every xdist
worker imports this file, only the one that runs it may load the TPU's
library) and the tests skip where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.paged_attention import (_BLOCK_BYTES, _copies_pages, _padded_heads, paged_attention_pallas,
                                                walk_block)


@pytest.fixture(scope="module")
def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(described_chip):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(described_chip)


def _lower(one_chip, chunk, n_q, n_kv, table_width, layers=1, d=128, dtype=jnp.bfloat16, batch=16, **bounds):
    """The kernel alone over an arena of ``layers`` layers, the layer a traced
    index, lowered for the described chip."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    args = [sds((batch, chunk, n_q, d), dtype), sds((layers, 64, 16, 2, n_kv, d), dtype),
            sds((batch, table_width), jnp.int32), sds((batch, ), jnp.int32), sds((batch, ), jnp.int32),
            sds((), jnp.int32)]

    def call(q, pages, table, start, lens, layer):
        return paged_attention_pallas(q, pages, table, start, lens, 16, layer=layer, interpret=False, **bounds)

    lowered = jax.jit(call).lower(*args)
    # the kernel by its name, not another form of the same attention
    assert "ds_paged_attention" in lowered.as_text()
    return lowered


def _compile(*args, **kwargs):
    """... and compiled: the program's text.  Under the ``no_compile_cache``
    fixture."""
    return _lower(*args, **kwargs).compile().as_text()


@pytest.mark.parametrize("chunk", [128, 1])
def test_grouped_heads_one_layer_of_pages(one_chip, no_compile_cache, chunk):
    """Mixtral's heads, 32 query heads over 8 key heads, out of an arena of
    one layer."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 32, 8, 770)


@pytest.mark.parametrize("table_width", [776, 282])
@pytest.mark.parametrize("chunk", [128, 1])
def test_grouped_heads_out_of_the_whole_arena(one_chip, no_compile_cache, chunk, table_width):
    """Mixtral's shape as its scanned twin gives it: 32 query heads over 8
    key heads, the layer named by an index into an arena of 3, at the table
    widths of the document cell (776) and the chat cell (282), neither a
    multiple of the walk's block of 32 pages."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 32, 8, table_width, layers=3)


@pytest.mark.parametrize("chunk", [128, 1])
def test_two_key_heads_a_tensor_parallel_shard(one_chip, no_compile_cache, chunk):
    """What a shard of ``tensor_parallel=4`` gives the kernel: 8 query heads
    over 2 key heads, a page of two rows a token and a half."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 8, 2, 776, layers=3)


@pytest.mark.parametrize("chunk", [128, 1])
def test_ungrouped_heads_out_of_the_whole_arena(one_chip, no_compile_cache, chunk):
    """EvaByte's shape: 32 key heads, no grouping, the layer named by an
    index into the whole arena, a table of 248 virtual pages."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 32, 32, 248, layers=8)


@pytest.mark.parametrize("table_width, window", [(41, 512), (193, 0)])
@pytest.mark.parametrize("chunk", [128, 1])
def test_two_key_pairs_a_row_with_the_windows_bound(one_chip, no_compile_cache, chunk, table_width, window):
    """Phi-4-mini-flash's shapes (``models/phi4flash_cache.py``): 32 sequences
    x 5 groups of key pairs are 160 kernel rows of 8 query heads over 2 key
    heads of 128 lanes, the scores scaled by 1/8; a window layer's ring is a
    table of 41 pages read under the window's two bounds (the walk's first
    block a traced number), the shared pages a table of 193 under one."""
    assert _copies_pages(2, 128, 2)
    assert "tpu_custom_call" in _compile(one_chip, chunk, 8, 2, table_width, layers=8, batch=160, window=window,
                                         scale=0.125)


#: the decode shape of each serving cell that reads through the kernel: (rows, query heads, key heads, table width,
#: the window its window layers bound the walk by or, for a cell that has none, one to compile it under)
DECODE_SHAPES = {
    "mixtral_16x8x4": (16, 32, 8, 776, 4096),
    "evabyte_16x32x1": (16, 32, 32, 248, 2048),
    "phi4flash_160x2x4": (160, 8, 2, 257, 512),
    "granite_32x4x8": (32, 32, 4, 257, 1024),
    "solar_open2_32x8x8": (32, 64, 8, 2177, 8192),
    "trinity_32x8x6": (32, 48, 8, 2081, 4096),
}


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("cell", list(DECODE_SHAPES))
def test_the_decode_form_at_each_cells_decode_shape(one_chip, no_compile_cache, cell, windowed):
    """One query position a row: the decode form (``takes_decode_form``), all
    the call's rows one grid step, with a window's two bounds and with one."""
    from deepspeed_tpu.ops.paged_attention import takes_decode_form
    batch, n_q, n_kv, width, window = DECODE_SHAPES[cell]
    assert takes_decode_form(1, n_kv, 128, 2)
    assert "tpu_custom_call" in _compile(one_chip, 1, n_q, n_kv, width, layers=8, batch=batch,
                                         window=window if windowed else 0, scale=0.125)


#: the length of the lowered module's text at three cells' decode shapes on the parent of PR 55 (a grid step a row,
#: 96 page copies unrolled in its body), read there with this file's ``_lower``; the Mosaic module rides in that
#: text, so its length follows the size of the kernel's body, which every program pays to trace and to lower at
#: every start, compile cache or none
#: (without a window, with the cell's)
PARENT_TEXT = {"phi4flash_160x2x4": (49916, 51748), "solar_open2_32x8x8": (49896, 51744), "granite_32x4x8": (49892, 51736)}


@pytest.mark.parametrize("cell", list(PARENT_TEXT))
def test_the_decode_forms_body_stays_in_its_budget(one_chip, cell):
    """The set-up budget of ISSUE 55: the decode form's lowered text is at
    most 1.1 times as long as the kernel's was at the same shape before it
    had the form (PR 53 did the same work in a body that cost three serving
    cells 7 to 16 s of ``setup_s`` and was refused for it).  No clock."""
    batch, n_q, n_kv, width, window = DECODE_SHAPES[cell]
    for w, parent in zip((0, window), PARENT_TEXT[cell]):
        text = _lower(one_chip, 1, n_q, n_kv, width, layers=8, batch=batch, window=w, scale=0.125).as_text()
        assert len(text) <= 1.1 * parent, (w, len(text), parent)


#: pages the chip's tiling pads, or heads no strided load takes: (query heads, key heads, lanes, dtype)
PIPELINED = {
    "falcon_7b_one_key_head_of_64_lanes": (71, 1, 64, jnp.bfloat16),
    "opt_125m_12_heads_of_64_lanes": (12, 12, 64, jnp.bfloat16),
    "phi_2_32_heads_of_80_lanes": (32, 32, 80, jnp.bfloat16),
    "a_mixtral_shard_of_tensor_parallel_8": (4, 1, 128, jnp.bfloat16),
    "six_key_heads": (12, 6, 128, jnp.bfloat16),
    "three_key_heads_float32": (3, 3, 128, jnp.float32),
    "heads_of_256_lanes": (8, 8, 256, jnp.bfloat16),
}


@pytest.mark.parametrize("chunk", [128, 1])
@pytest.mark.parametrize("model", list(PIPELINED))
def test_pages_the_kernel_cannot_copy_itself(one_chip, no_compile_cache, model, chunk):
    """The chip's compiler refuses a kernel's own DMA out of a page its
    tiling pads ("Slice shape along dimension 5 must be aligned to tiling
    (128), but is 64"): for such a model the pipeline brings the pages, one
    ``BlockSpec`` each, and the same kernel compiles."""
    n_q, n_kv, d, dtype = PIPELINED[model]
    assert not _copies_pages(n_kv, d, jnp.dtype(dtype).itemsize)
    assert "tpu_custom_call" in _compile(one_chip, chunk, n_q, n_kv, 282, layers=3, d=d, dtype=dtype)


@pytest.mark.parametrize("n_kv, dtype", [(2, jnp.bfloat16), (4, jnp.bfloat16), (16, jnp.bfloat16), (24, jnp.bfloat16),
                                         (64, jnp.bfloat16), (1, jnp.float32), (2, jnp.float32), (24, jnp.float32)])
def test_pages_the_kernel_copies_itself(one_chip, no_compile_cache, n_kv, dtype):
    """Every count of key heads ``_copies_pages`` takes for whole tiles: the
    compiler accepts the DMA out of the arena and the strided load."""
    assert _copies_pages(n_kv, 128, jnp.dtype(dtype).itemsize)
    assert "tpu_custom_call" in _compile(one_chip, 1, n_kv, n_kv, 100, layers=3, dtype=dtype)


@pytest.mark.parametrize("n_kv, width", [(8, 776), (8, 282), (2, 776), (32, 248), (64, 100)])
def test_the_blocks_scratch_stays_in_its_budget(n_kv, width):
    """The two slots of the scratch, with the heads it pads, at the cells'
    shapes: within the budget the block was chosen under (a count, no compile)."""
    from deepspeed_tpu.ops.paged_attention import _decode_block
    for ppb in (walk_block(16, width, n_kv, 128, 2), _decode_block(16, width, n_kv, 128, 2)):
        assert ppb * 16 >= 128
        assert 2 * ppb * 16 * 2 * _padded_heads(n_kv, 2) * 128 * 2 <= _BLOCK_BYTES
    # the decode form's block: 1,024 key rows of 8 or 2 key heads, 512 of EvaByte's 32 (padded to 40) and of 64
    assert _decode_block(16, width, n_kv, 128, 2) == (64 if n_kv <= 8 else 32 if n_kv == 32 else 16)


@pytest.mark.parametrize("program", ["step_c128", "step_c1", "fused_2"])
def test_scanned_twin_holds_no_second_arena(described_chip, no_compile_cache, program):
    """A small Mixtral twin's donated step programs, compiled for one chip
    with an arena that dwarfs everything else in them (101 MB beside 25 MB of
    weights and a few MB of activations): the compiler's temporaries stay under
    half an arena, in the mixed step, the one-token step and the fused decode
    program (a ``fori_loop`` of 2 around the layer scan, the arena the carry
    of both).  On the parent of PR 27 all three fail, with 101 to 102 MB of
    temporaries: the scan took a layer's pages in and stacked them on the way
    out (at the benchmark's size, 4.09 GB beside a 2.15 GB arena)."""
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import compile_aot_serving
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama_cache import PagedKVConfig
    from deepspeed_tpu.models.mixtral import MixtralConfig
    cfg = MixtralConfig(vocab_size=512, hidden_size=512, intermediate_size=512, num_hidden_layers=3,
                        num_attention_heads=4, num_key_value_heads=2, num_local_experts=4, num_experts_per_tok=2,
                        max_position_embeddings=4096, drop_tokens=False, dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16, attention_impl="flash", scan_layers=True, remat=False)
    kv = PagedKVConfig(num_pages=2048, page_size=16, max_pages_per_seq=64)
    arena_bytes = cfg.num_hidden_layers * kv.num_pages * kv.page_size * 2 * cfg.num_key_value_heads * 128 * 2
    econf = RaggedInferenceEngineConfig(kv=kv, scheduler=SchedulerConfig(token_budget=1024, max_seqs=8,
                                                                         prefill_chunk=128, decode_bucket=8))
    mesh = create_mesh(MeshSpec(), devices=[described_chip])
    how = {"step_c128": dict(chunk=128), "step_c1": dict(chunk=1), "fused_2": dict(fused_steps=2)}[program]
    compiled, _ = compile_aot_serving(cfg, mesh, econf, batch=8, **how)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= arena_bytes, "the donated arena is not the result's buffer"
    assert mem.temp_size_in_bytes < arena_bytes // 2, (mem.temp_size_in_bytes, arena_bytes)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the mixed step's 1,024 slots take the sorted form and its products the grouped kernel; a decode step's 8 say
    # "dense" and hold both forms under one conditional on the rows that live (PR 48), which copies no bank either
    assert "ds_gmm" in text and (" conditional(" in text) == (program != "step_c128")


@pytest.mark.parametrize("pinned", [True, False], ids=["as_served", "control_without_the_layout"])
def test_both_forms_of_the_experts_copy_no_bank(described_chip, one_chip, no_compile_cache, pinned, monkeypatch):
    """An expert layer that holds both forms under one conditional (PR 48),
    scanned over a stack of four layers' banks at Solar-Open2's widths (40
    held of 320, 8 a token), 128 slots (the benchmark check's chunk): compiled
    for one chip it holds a few MB beside its arguments.  The control shows
    what the dense branch's layout constraint is for: without it XLA hands
    that branch a transposed copy of a whole stack, 1.7 GB made anew every
    layer (at 128 slots of Xing4's and of Mixtral's banks 2.8 GB, when their
    programs still held both forms there: ``xing4_longdoc``'s check did not
    fit the chip and Mixtral's ran three times as long)."""
    from jax.sharding import Mesh
    import numpy as np
    from deepspeed_tpu.comm.mesh import trace_mesh
    from deepspeed_tpu.moe import sharded_moe
    if not pinned:
        monkeypatch.setattr(sharded_moe, "_experts_dense_in_place", sharded_moe._experts_dense)
    layers, e, held, d, f, k, s = 4, 320, (0, 40), 4096, 1280, 8, 128
    assert sharded_moe.live_rows_sorted(s, k, e) == 119
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bank = tuple(sds((layers, held[1]) + shape, jnp.bfloat16) for shape in ((d, f), (d, f), (f, d)))

    def trunk(x, w_router, bank, mask):
        def layer(x, index):
            out, _, _ = sharded_moe.dropless_moe(x, x.astype(jnp.float32) @ w_router, bank, k, mask, None, index,
                                                 True, "sigmoid", held=held)
            return x + out.astype(x.dtype), None
        return jax.lax.scan(layer, x, jnp.arange(layers))[0]

    with trace_mesh(Mesh(np.array([described_chip]), ("data", ))):
        compiled = jax.jit(trunk).lower(sds((s, d), jnp.bfloat16), sds((d, e), jnp.float32), bank,
                                        sds((s, ), jnp.bool_)).compile()
    text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    assert " conditional(" in text and "ds_gmm" in text
    assert (temp < 64 * 2**20) if pinned else (temp > 2**30), temp


def _slot_twin(family):
    """A small twin of a slot-holding family at its published head sizes, so
    that both take their kernels, whose states, rings and pages are 148 to 268
    MB each beside a few MB of weights (Phi-4's state 32 times the published
    one, its window four times: an arena of 100 MB the compiler moves into a
    faster memory whole, as it does the convolution tails)."""
    if family == "phi4flash":
        from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
        return Phi4FlashConfig(vocab_size=512, hidden_size=512, intermediate_size=512, num_hidden_layers=8,
                               num_attention_heads=8, num_key_value_heads=4, sliding_window=2048, d_state=512,
                               max_position_embeddings=4096, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                               attention_impl="flash")
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
    return GraniteHybridConfig(vocab_size=512, hidden_size=512, intermediate_size=512, shared_intermediate_size=512,
                               num_hidden_layers=4, layer_types=("mamba", "mamba", "attention", "mamba"),
                               num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=16, mamba_d_head=64,
                               mamba_d_state=512, max_position_embeddings=4096, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16, attention_impl="flash")


@pytest.mark.parametrize("prefill_rows", [1, 4])
@pytest.mark.parametrize("family", ["phi4flash", "granitehybrid"])
def test_slot_twins_two_group_step_copies_no_arena(described_chip, no_compile_cache, family, prefill_rows):
    """The mixed step of ``phi4flash_reason`` and ``granite4h_sessions``
    (``step:b32:c1:b1:c128`` and ``b4``), donated and compiled for one chip:
    every array of the cache is the result's buffer, and no ``copy`` has the
    shape of the recurrent states, the rings or the pages, which are threaded
    through two row groups and the layer scan and updated where they lie (at
    the benchmark's size a copy of Granite's states is 2.49 GB, 6 ms).  The
    convolution tails are left out: the compiler moves that arena into a
    faster memory around the layer loop, 9 and 31 MB at the benchmark's size,
    in the one-group program and the rectangle's too."""
    import re

    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.engine_v2 import _init_cache, compile_aot_serving
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama_cache import PagedKVConfig
    cfg = _slot_twin(family)
    econf = RaggedInferenceEngineConfig(kv=PagedKVConfig(num_pages=16384, page_size=16, max_pages_per_seq=64),
                                        scheduler=SchedulerConfig(token_budget=4096, max_seqs=32, prefill_chunk=128,
                                                                  decode_bucket=32))
    arenas = jax.eval_shape(lambda: _init_cache(cfg, econf))
    mesh = create_mesh(MeshSpec(), devices=[described_chip])
    compiled, _ = compile_aot_serving(cfg, mesh, econf, groups=((32, 1), (prefill_rows, 128)))
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text
    held = sum(a.size * a.dtype.itemsize for a in arenas.values())
    assert mem.alias_size_in_bytes >= held, "a donated array of the cache is not the result's buffer"
    for name in set(arenas) - {"conv"}:
        a = arenas[name]
        shape = {"bfloat16": "bf16", "float32": "f32"}[a.dtype.name] + "[" + ",".join(map(str, a.shape)) + "]"
        assert shape in text, (name, shape)                                  # the shape is spelled as the text spells it
        copies = re.findall(r"= " + re.escape(shape) + r"(?:\{[^}]*\})? copy(?:-done)?\(", text)
        assert not copies, (name, shape, len(copies))
        assert a.size * a.dtype.itemsize > 140e6, (name, a.size * a.dtype.itemsize)   # too large to be moved whole


#: the grouped product's operands in the benchmark's cells: (rows, contraction, columns, groups)
GROUPED = {
    "mixtral_gate_and_up_in_a_stack_of_3_layers": (4096, 4096, 14336, 24),
    "mixtral_down_in_a_stack_of_3_layers": (4096, 14336, 4096, 24),
    "qwen15_moe_gate_and_up": (16384, 2048, 1408, 60),
    "qwen15_moe_down": (16384, 1408, 2048, 60),
}


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "gradients"])
@pytest.mark.parametrize("operands", list(GROUPED))
def test_grouped_product_at_the_cells_operands(one_chip, no_compile_cache, operands, backward):
    """``ds_gmm`` forward, and with ``ds_tgmm`` the gradients of both
    operands, at the tiles ``ops/grouped_matmul.py`` picks for the serving
    cells' banks (16 MiB of a bank a grid step, 52 MiB of VMEM asked for) and
    the train cell's (a group's whole bank a step, an accumulator of 11.5 MiB):
    what fits is the compiler's to say."""
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    m, k, n, g = GROUPED[operands]
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    product = lambda a, b, s: grouped_matmul(a, b, s, interpret=False)  # noqa: E731
    if backward:
        product = jax.grad(lambda a, b, s: jnp.sum(grouped_matmul(a, b, s, interpret=False).astype(jnp.float32)), (0, 1))
    lowered = jax.jit(product).lower(sds((m, k)), sds((g, k, n)), sds((g, ), jnp.int32))
    assert ("ds_gmm" in lowered.as_text()) and (("ds_tgmm" in lowered.as_text()) == backward)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("kernel", ["list_walk", "gather_pages", "lightning_update"])
def test_minicpm_salas_kernels_at_the_cells_shapes(one_chip, no_compile_cache, kernel):
    """The three kernels ``models/minicpm_sala_cache.py`` brings, at the
    shapes of ``minicpmsala_longctx``: 32 one-token rows over lists of 512
    pages a key head out of an arena of two key heads of 128; a step's 32
    pages a row of a prefill group; 32 rows' states of 32 x 128 x 128 float32
    on the slot arena in place."""
    from deepspeed_tpu.ops.lightning_update import lightning_update
    from deepspeed_tpu.ops.sparse_paged_attention import _gather_pages, sparse_paged_decode
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    arena = sds((2, 64, 16, 2, 2, 128), bf16)
    if kernel == "list_walk":
        args = [sds((32, 32, 128), bf16), arena, sds((), i32), sds((32, 2, 512), i32), sds((32, ), i32), sds((32, ), i32)]
        call, name = lambda q, p, l, lists, n, pos: sparse_paged_decode(q, p, l, lists, n, pos, 16, interpret=False), \
            "ds_sparse_paged_attention"
    elif kernel == "gather_pages":
        args = [arena, sds((), i32), sds((4, 32), i32)]
        call, name = lambda p, l, ids: _gather_pages(p, l, ids, False), "ds_gather_pages"
    else:
        row = sds((32, 32, 128), f32)
        args = [sds((6, 33, 32, 128, 128), f32), sds((), i32), sds((32, ), i32), sds((32, ), i32), row, row, row,
                sds((32, ), f32)]
        call, name = lambda a, l, s, f, q, k, v, d: lightning_update(a, l, s, f, q, k, v, d, interpret=False), \
            "ds_lightning_update"
    lowered = jax.jit(call).lower(*args)
    assert name in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("rows, chunk", [(32, 1), (4, 256)], ids=["decode_bucket", "a_run_of_four"])
@pytest.mark.parametrize("kind", ["window", "full"])
def test_whole_tile_heads_under_a_window_of_4096(one_chip, no_compile_cache, kind, rows, chunk):
    """The two calls of ``models/trinity_cache.py`` at the shapes of
    ``trinity_mixed_queue``: 48 query heads over 8 key heads of 128; a window
    layer over its ring's view of 321 pages out of an arena of four layers
    with the window's bound, 4,096 rows; the full layer over a table of 2,081
    pages out of an arena of one with none."""
    width, layers, bounds = (321, 4, {"window": 4096}) if kind == "window" else (2081, 1, {})
    assert "tpu_custom_call" in _compile(one_chip, chunk, 48, 8, width, layers=layers, batch=rows, **bounds)
