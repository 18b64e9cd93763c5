"""Host-streamed grouped optimizer (r5 — the tier that broke the 792M
single-chip ceiling: 1.62B trained on a 16 GB v5e, docs/PERF.md r5).

ref: deepspeed/runtime/zero/stage_1_and_2.py CPU offload + cpu_adam —
fp32 master/moments out of device memory, touched in bounded pieces.
The TPU realisation bounds HBM staging at the DISPATCH level (XLA will
not bound it within one program — docs/PERF.md r4 receipts), reusing the
pipelined-NVMe orchestration with a host-memory storage tier."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.runtime.swap_tensor.host_streamed_optimizer import HostStreamedOptimizer

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                  max_position_embeddings=64, rope_theta=1e4)


def _engine(offload: bool, **cfg_over):
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    zero = {"stage": 2}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu", "pipeline_read": True}
    import dataclasses
    cfg = dataclasses.replace(CFG, **cfg_over) if cfg_over else CFG
    # the streamed tier is single-device by design (multi-chip scale = ZeRO)
    mesh = create_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(cfg), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": zero,
        "bf16": {"enabled": True}}, mesh=mesh, dist_init_required=False)
    return engine


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 128, (8, 16)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def test_host_streamed_selected_and_loss_parity():
    """device=cpu + pipeline_read selects the grouped tier; trajectory
    matches the on-device update to bf16 noise."""
    b = _batch()
    eh = _engine(True)
    ed = _engine(False)
    lh = [float(eh.train_batch(batch=b)) for _ in range(5)]
    ld = [float(ed.train_batch(batch=b)) for _ in range(5)]
    assert type(getattr(eh, "_nvme_opt", None)).__name__ == "HostStreamedOptimizer"
    assert getattr(ed, "_nvme_opt", None) is None
    np.testing.assert_allclose(lh, ld, rtol=3e-3, atol=3e-3)
    # device state is params-only: master/opt_state live in the group store
    assert eh.state.master == () and eh.state.opt_state == ()


def test_plain_cpu_offload_unchanged():
    """device=cpu WITHOUT pipeline_read keeps the r4 single-program
    compute_on path (memory-kind shardings, no grouped orchestration)."""
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    mesh = create_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(CFG), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2, "offload_optimizer": {"device": "cpu"}},
        "bf16": {"enabled": True}}, mesh=mesh, dist_init_required=False)
    loss = engine.train_batch(batch=_batch())
    assert getattr(engine, "_nvme_opt", None) is None
    assert np.isfinite(float(loss))


def test_grouping_is_byte_balanced_and_covers_all_leaves():
    rng = np.random.default_rng(0)
    leaves = [jnp.asarray(rng.normal(size=s), jnp.bfloat16)
              for s in ((64, 64), (256, ), (32, 32), (64, 64), (128, 128), (8, ))]
    from deepspeed_tpu.ops.adam import fused_adam
    opt = HostStreamedOptimizer(fused_adam(lr=1e-3), leaves, n_groups=3)
    covered = sorted(i for g in opt.groups for i in g)
    assert covered == list(range(len(leaves)))
    assert 1 <= opt.n_groups <= 3


def test_step_and_events_order():
    rng = np.random.default_rng(1)
    leaves = [jnp.asarray(rng.normal(size=(32, 32)), jnp.bfloat16) for _ in range(4)]
    from deepspeed_tpu.ops.adam import fused_adam
    opt = HostStreamedOptimizer(fused_adam(lr=1e-2), leaves, n_groups=2)
    grads = [jnp.ones_like(l) for l in leaves]
    new = opt.step(grads, jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
                   flush=True)
    assert len(new) == 4 and all(p.dtype == jnp.bfloat16 for p in new)
    # params moved against the positive grads
    assert all(float(jnp.mean(n.astype(jnp.float32) - l.astype(jnp.float32))) < 0
               for n, l in zip(new, leaves))
    # double-buffered pipeline issue order: BOTH uploads are issued before
    # group 0's compute is dispatched (upload g+1 rides under compute g),
    # download g before compute g+1, fences trail one group behind
    kinds = [(e[0], e[1]) for e in opt.events]
    assert kinds == [("upload_issue", 0), ("upload_issue", 1),
                     ("compute_issue", 0), ("download_issue", 0),
                     ("compute_issue", 1), ("download_issue", 1),
                     ("update_done", 0), ("update_done", 1)]


def test_engine_checkpoint_roundtrip_preserves_moments(tmp_path):
    """save/load with the host tier must carry the Adam moments (they live
    in process RAM — nothing else makes them durable): the restored engine
    continues with IDENTICAL next-step losses, and a fresh engine without
    the saved files falls back to resync (warned, moments reset)."""
    b = _batch()
    e1 = _engine(True)
    for _ in range(3):
        e1.train_batch(batch=b)
    e1.save_checkpoint(tmp_path, tag="t")
    e2 = _engine(True)
    e2.train_batch(batch=b)  # materialize (different random init + moments)
    e2.load_checkpoint(tmp_path, tag="t")
    l1 = float(e1.train_batch(batch=b))
    l2 = float(e2.train_batch(batch=b))
    assert abs(l1 - l2) < 2e-3, (l1, l2)
    # moments really restored, not resynced-to-zero: exp_avg of a trained
    # group is nonzero
    sd = e2._nvme_opt.state_dict_host()
    assert any(np.abs(m).max() > 0 for g in sd for m in g["mu"])


def test_checkpoint_resync_surface():
    rng = np.random.default_rng(2)
    leaves = [jnp.asarray(rng.normal(size=(16, 16)), jnp.bfloat16) for _ in range(2)]
    from deepspeed_tpu.ops.adam import fused_adam
    opt = HostStreamedOptimizer(fused_adam(lr=1e-2), leaves, n_groups=2)
    assert opt.master_matches_params(leaves, jnp.bfloat16)
    other = [l + 1.0 for l in leaves]
    assert not opt.master_matches_params(other, jnp.bfloat16)
    opt.resync_master_from_params(other)
    assert opt.master_matches_params(other, jnp.bfloat16)
    sd = opt.state_dict_host()
    assert len(sd) == opt.n_groups
    assert all(np.abs(g["mu"][0]).max() == 0 for g in sd)  # moments reset
