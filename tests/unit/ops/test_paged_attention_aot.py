"""The paged kernel compiled for the chip, without the chip, at the shapes the
benchmark's serving cells give it: what interpret mode cannot refuse (tiling,
scoped VMEM, 32 key heads unrolled in one grid step) the chip's compiler
does, here, in a second or two a shape.  Nothing runs and no time is read.

The topology is described inside a fixture (never at import: every xdist
worker imports this file, only the one that runs it may load the TPU's
library) and the tests skip where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.paged_attention import paged_attention_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, chunk, n_q, n_kv, table_width, layers=None):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    arena = (64, 16, 2, n_kv, 128) if layers is None else (layers, 64, 16, 2, n_kv, 128)
    args = [sds((16, chunk, n_q, 128), jnp.bfloat16), sds(arena, jnp.bfloat16), sds((16, table_width), jnp.int32),
            sds((16, ), jnp.int32), sds((16, ), jnp.int32)]

    def call(q, pages, table, start, lens, layer=None):
        return paged_attention_pallas(q, pages, table, start, lens, 16, layer=layer, interpret=False)

    if layers is not None:
        args.append(sds((), jnp.int32))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)   # a described device's compile cannot be read back
    try:
        return jax.jit(call).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("chunk", [128, 1])
def test_grouped_heads_one_layer_of_pages(one_chip, chunk):
    """Mixtral's shape: 32 query heads over 8 key heads, a layer's pages."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 32, 8, 770).as_text()


@pytest.mark.parametrize("chunk", [128, 1])
def test_ungrouped_heads_out_of_the_whole_arena(one_chip, chunk):
    """EvaByte's shape: 32 key heads, no grouping, the layer named by an
    index into the whole arena, a table of 248 virtual pages."""
    assert "tpu_custom_call" in _compile(one_chip, chunk, 32, 32, 248, layers=8).as_text()
