"""Tests for the API-parity shims: utils.groups, utils.nvtx,
ops.transformer legacy layer, axes vocabulary, examples importability."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def test_groups_facade():
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
    from deepspeed_tpu.utils import groups
    set_global_mesh(create_mesh(MeshSpec(data=2, expert=2, seq=2), devices=jax.devices()[:8]))
    assert groups.get_data_parallel_world_size() == 8
    assert groups.get_expert_parallel_world_size() == 2
    assert groups.get_sequence_parallel_world_size() == 2
    assert groups.get_model_parallel_world_size() == 1
    assert "expert" not in groups.get_expert_data_parallel_group()
    # (the autouse _reset_global_mesh fixture restores the mesh afterwards)


def test_nvtx_shim(tmp_path):
    import glob

    from jax.profiler import ProfileData

    from deepspeed_tpu.utils import nvtx
    from deepspeed_tpu.utils.nvtx import instrument_w_nvtx, profiler_range, range_pop, range_push

    @instrument_w_nvtx
    def f(x):
        return x * 2

    range_push("outer")
    assert f(21) == 42
    range_pop()
    range_pop()  # extra pop is a no-op

    # the wrapped function runs under a jax.profiler.TraceAnnotation (the
    # host-side analogue of an NVTX range), not under a named_scope
    assert isinstance(profiler_range("x"), jax.profiler.TraceAnnotation)
    assert "named_scope" not in open(nvtx.__file__).read().split('"""', 2)[2]
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert f(4) == 8
        with profiler_range("ds.step") as rng:
            rng.set_metadata(key="step:b4:c1:b1:c8", slots=12)
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    host = {ev.name: dict(ev.stats) for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert f.__qualname__ in host
    assert host["ds.step"] == {"key": "step:b4:c1:b1:c8", "slots": 12}


def test_legacy_transformer_layer_pre_and_post_ln():
    from deepspeed_tpu.ops.transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer
    x = jnp.ones((2, 8, 64), jnp.float32)
    outs = {}
    for pre in (True, False):
        layer = DeepSpeedTransformerLayer(DeepSpeedTransformerConfig(
            hidden_size=64, intermediate_size=128, heads=4, pre_layer_norm=pre))
        v = layer.init(jax.random.PRNGKey(0), x)
        outs[pre] = np.asarray(layer.apply(v, x))
        assert np.isfinite(outs[pre]).all()
    # the two variants are genuinely different architectures
    assert not np.allclose(outs[True], outs[False])


def test_axes_vocabulary_single_source():
    from deepspeed_tpu import axes
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.moe import experts
    from deepspeed_tpu.module_inject import tp_rules
    assert llama.EMBED is axes.EMBED
    assert experts.EXPERT_EMBED is axes.EXPERT_EMBED
    assert tp_rules.EXPERTS is axes.EXPERTS


def test_examples_parse():
    import ast, glob
    for f in glob.glob(os.path.join(os.path.dirname(__file__), "..", "..", "..", "examples", "*.py")):
        ast.parse(open(f).read(), filename=f)


def test_bin_scripts_parse():
    import ast, glob
    for f in glob.glob(os.path.join(os.path.dirname(__file__), "..", "..", "..", "bin", "*")):
        ast.parse(open(f).read(), filename=f)
