"""What keeps the chip path honest, checked without a chip: no fallback
hides a missing device, the compile cache is placed from outside, and
``chip_smoke.py`` refuses to pass on the CPU."""

import os
import subprocess
import sys
import time

import jax
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
sys.path.insert(0, REPO)


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: a test must
    not switch the persistent cache on for the rest of the session."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_env_var_owns_the_directory(monkeypatch, config_updates, tmp_path):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert [k for k in config_updates if k.endswith("cache_dir")] == []
    assert config_updates  # thresholds are still lowered


def test_compile_cache_defaults_to_checkout(monkeypatch, config_updates):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert [v for k, v in config_updates.items() if k.endswith("cache_dir")] == [want]


def test_get_accelerator_reraises_device_errors(monkeypatch):
    from deepspeed_tpu.accelerator import real_accelerator

    def busy():
        raise RuntimeError("TPU is held by another process")

    monkeypatch.setattr(real_accelerator, "ds_accelerator", None)
    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    monkeypatch.delenv("DS_TPU_ACCELERATOR", raising=False)
    monkeypatch.setattr(jax, "devices", busy)
    with pytest.raises(RuntimeError, match="held by another process"):
        real_accelerator.get_accelerator()


def test_explicit_mesh_sets_the_data_parallel_degree():
    """A one-device mesh on a many-device host trains with dp=1 (the
    one-chip legs of chip_smoke.py on a four-chip host)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    from deepspeed_tpu.models.llama import PRESETS, LlamaForCausalLM
    mesh = create_mesh(MeshSpec(), devices=jax.devices()[:1])
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(PRESETS["tiny"]), mesh=mesh,
                                    config={"train_batch_size": 1})
    assert engine._config.train_micro_batch_size_per_gpu == 1


def test_chip_smoke_parent_imports_without_jax():
    code = "import sys, chip_smoke; sys.exit('jax' in sys.modules)"
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO).returncode == 0


def test_chip_smoke_fails_fast_without_a_chip():
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, text=True,
                          capture_output=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert time.monotonic() - t0 < 10
    assert proc.returncode != 0
    assert "no TPU chip found" in proc.stderr
    assert '"ok"' not in proc.stdout
