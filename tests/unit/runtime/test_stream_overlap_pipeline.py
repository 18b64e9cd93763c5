"""Double-buffered host-streamed pipeline + measured-overlap instrumentation
(r6 tentpole).

The overlap the pre-r6 tier ASSERTED by docstring is now structural and
measured: uploads are separate dispatches into a bounded staging arena,
staged buffers are donated exactly once into the fused-Adam program, the
engine issues the first uploads during the BACKWARD, and a serialized
probe sweep attributes per-group upload/compute/download seconds that
``overlap_report`` folds into an overlap fraction with a transfer-/
compute-bound floor.

Everything here runs on the CPU backend: the dispatch structure, donation
discipline, event ordering and instrumentation math are identical — only
the memory kinds collapse (``host_tier_distinct`` False)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.ops.adam import fused_adam
from deepspeed_tpu.runtime.swap_tensor.host_streamed_optimizer import HostStreamedOptimizer

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                  max_position_embeddings=64, rope_theta=1e4)


def _opt(n_leaves=6, n_groups=3, **kw):
    rng = np.random.default_rng(0)
    leaves = [jnp.asarray(rng.normal(size=(32, 32)), jnp.bfloat16) for _ in range(n_leaves)]
    return HostStreamedOptimizer(fused_adam(lr=1e-2), leaves, n_groups=n_groups, **kw), leaves


def _sweep(opt, leaves, serialize=False, flush=False):
    grads = [jnp.ones_like(l) for l in leaves]
    return opt.step(grads, jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
                    serialize=serialize, flush=flush)


def _engine(offload=True):
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    zero = {"stage": 2}
    if offload:
        zero["offload_optimizer"] = {"device": "cpu", "pipeline_read": True,
                                     "buffer_count": 3}
    mesh = create_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    engine, _, _, _ = ds.initialize(model=LlamaForCausalLM(CFG), config={
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": zero,
        "bf16": {"enabled": True}}, mesh=mesh, dist_init_required=False)
    return engine


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 128, (8, 16)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def test_upload_issued_before_prior_compute_completes():
    """The double buffer's defining property: group g+1's upload dispatch
    is ISSUED strictly before group g's compute completes (timestamped
    instrumentation events, not docstring assertion)."""
    opt, leaves = _opt(n_groups=3)
    _sweep(opt, leaves, flush=True)
    up = opt.instrumentation.events_of("upload_issue")
    done = opt.instrumentation.events_of("compute_done")
    assert set(up) == {0, 1, 2} and set(done) == {0, 1, 2}
    for g in range(opt.n_groups - 1):
        assert up[g + 1] < done[g], (
            f"upload({g + 1}) issued at {up[g + 1]} AFTER compute({g}) "
            f"completed at {done[g]} — pipeline serialized")
    # downloads are issued before the NEXT group's compute completes too
    dl = opt.instrumentation.events_of("download_issue")
    for g in range(opt.n_groups - 1):
        assert dl[g] < done[g + 1]


def test_staging_bound_and_donation_safety():
    """At most max_staged slots live; a consumed (donated) slot cannot be
    taken again; masters stay readable after the sweep (nothing reads a
    donated buffer)."""
    opt, leaves = _opt(n_groups=3, max_staged=2)
    assert opt.prefetch(0) and opt.prefetch(1)
    assert not opt.prefetch(2), "third staged slot must be refused (bound=2)"
    assert not opt.prefetch(0), "re-staging a live slot must be a no-op"
    opt._take_staged(0)
    with pytest.raises(RuntimeError, match="donated"):
        opt._take_staged(0)
    assert opt.prefetch(2), "slot freed by consumption must be reusable"
    # drop the un-consumed slots (their buffers were never donated), then a
    # full sweep must leave no live slots and fully readable host state
    opt._staged.clear()
    _sweep(opt, leaves, flush=True)
    assert opt._staged == {}
    for g in range(opt.n_groups):
        for arr in opt._master[g] + opt._mu[g] + opt._nu[g]:
            np.asarray(jax.device_get(arr))  # raises if donated/deleted


def test_max_staged_one_still_correct():
    """A degenerate single-slot arena serializes the uploads but must not
    deadlock or skip groups."""
    opt, leaves = _opt(n_groups=3, max_staged=1)
    new = _sweep(opt, leaves, flush=True)
    assert all(p is not None for p in new)
    assert opt._staged == {}


def test_serialized_probe_counters_sum_to_wall():
    """The probe's per-group phase seconds are an exact partition of the
    fenced sweep: each >= 0 and their total within tolerance of the probe
    wall time (the residue is host loop overhead)."""
    opt, leaves = _opt(n_groups=3)
    _sweep(opt, leaves, serialize=True)
    probe = opt.instrumentation.probe
    assert probe is not None and len(probe["per_group"]) == opt.n_groups
    for g in probe["per_group"]:
        assert g["upload_s"] >= 0 and g["compute_s"] >= 0 and g["download_s"] >= 0
    serial = probe["serialized_s"]
    wall = probe["wall_s"]
    assert serial <= wall, "phase seconds cannot exceed the fenced wall time"
    assert wall - serial <= max(0.25, 0.5 * wall), (
        f"unattributed time {wall - serial:.4f}s of {wall:.4f}s — the phase "
        "counters no longer partition the sweep")


def test_overlap_report_fields_and_parity():
    """report() combines probe + pipelined step into the artifact fields;
    the serialized probe computes the SAME update as the pipelined sweep."""
    opt_a, leaves = _opt(n_groups=3)
    opt_b, _ = _opt(n_groups=3)
    assert opt_a.overlap_report() is None, "no report before a probe ran"
    new_a = _sweep(opt_a, leaves, serialize=True)
    new_b = _sweep(opt_b, leaves, flush=True)
    for a, b in zip(new_a, new_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _sweep(opt_a, leaves, flush=True)  # pipelined step -> wall + gaps
    rep = opt_a.overlap_report()
    for key in ("upload_s", "compute_s", "download_s", "serialized_s",
                "transfer_s", "ideal_pipelined_s", "bound", "pipelined_wall_s",
                "overlap_fraction", "n_groups", "per_group"):
        assert key in rep, f"missing artifact field {key}"
    assert rep["bound"] in ("transfer", "compute")
    assert 0.0 <= rep["overlap_fraction"] <= 1.0
    assert rep["n_groups"] == 3 and len(rep["per_group"]) == 3
    assert rep["host_tier_distinct"] in (False, True)
    gaps = rep.get("device_idle_gap_s_per_group")
    assert gaps is not None and len(gaps) == 2 and all(x >= 0 for x in gaps)


def test_engine_backward_phase_prefetch_and_measurement():
    """The engine issues the first uploads right after the fwd/bwd dispatch
    (before the optimizer sweep begins), and measure_stream_overlap returns
    the artifact on real train steps."""
    e = _engine()
    b = _batch()
    e.train_batch(batch=b)
    nv = e._nvme_opt
    up = nv.instrumentation.events_of("upload_issue")
    ci = nv.instrumentation.events_of("compute_issue")
    assert 0 in up and 1 in up and 0 in ci
    assert up[0] < ci[0] and up[1] < ci[0], (
        "backward-phase prefetch must issue uploads for groups 0 and 1 "
        "before group 0's compute is dispatched")
    rep = e.measure_stream_overlap(b)
    assert rep is not None and 0.0 <= rep["overlap_fraction"] <= 1.0
    assert e._nvme_step_mode is None, "measurement mode must reset"
    # trajectory stays sane through the probe steps (they are real updates)
    loss = float(e.train_batch(batch=b))
    assert np.isfinite(loss)


def test_serialized_probe_loss_parity_with_pipelined():
    """A training trajectory that interleaves probe (serialized) steps with
    pipelined steps matches an all-pipelined trajectory: the probe is a
    measurement mode, not a different optimizer."""
    b = _batch()
    e1, e2 = _engine(), _engine()
    l1 = [float(e1.train_batch(batch=b)) for _ in range(4)]
    e2.train_batch(batch=b)
    e2._nvme_step_mode = "serialize"
    e2.train_batch(batch=b)
    e2._nvme_step_mode = None
    l2 = [float(e2.train_batch(batch=b)) for _ in range(2)]
    np.testing.assert_allclose(l1[2:], l2, rtol=3e-3, atol=3e-3)


def test_load_state_mismatch_probe_resyncs(tmp_path):
    """Same-shaped host_opt_group*.npz from a DIFFERENT run must not
    silently revert params: load_checkpoint probes master-vs-params after a
    successful load_state and resyncs (moments zeroed) on mismatch.

    Since the r8 crc manifest, a bare file swap is caught EARLIER (manifest
    verification fails and the tag is not loadable), so the adversary here
    must be manifest-consistent: the wrong-run files arrive with a
    re-written manifest (an operator "restoring" files from another run and
    refreshing checksums, or a pre-manifest-era checkpoint).  Checksums
    then pass — only the semantic probe can catch the mismatch."""
    b = _batch()
    e1 = _engine()
    for _ in range(3):
        e1.train_batch(batch=b)
    e1.save_checkpoint(tmp_path / "a", tag="t")
    # same shapes, different training state: the "wrong run" files
    for _ in range(3):
        e1.train_batch(batch=_batch(seed=7))
    e1.save_checkpoint(tmp_path / "b", tag="t")
    import shutil
    for f in (tmp_path / "b" / "t").glob("host_opt_group*.npz"):
        shutil.copy(f, tmp_path / "a" / "t" / f.name)
    from deepspeed_tpu.resilience import atomic_io
    atomic_io.write_manifest(str(tmp_path / "a" / "t"), site=None)
    e2 = _engine()
    e2.train_batch(batch=b)  # materialize
    e2.load_checkpoint(tmp_path / "a", tag="t")
    nv = e2._nvme_opt
    leaves = jax.tree.leaves(e2.state.params)
    assert nv.master_matches_params(leaves, e2.compute_dtype), (
        "master must correspond to the restored params after the probe")
    sd = nv.state_dict_host()
    assert all(np.abs(m).max() == 0 for g in sd for m in g["mu"]), (
        "mismatched optimizer files must be resynced with zeroed moments")


def test_true_resume_keeps_moments(tmp_path):
    """The mismatch probe must NOT false-positive on a genuine resume: the
    restored moments survive and the next-step losses match exactly."""
    b = _batch()
    e1 = _engine()
    for _ in range(3):
        e1.train_batch(batch=b)
    e1.save_checkpoint(tmp_path, tag="t")
    e2 = _engine()
    e2.train_batch(batch=b)
    e2.load_checkpoint(tmp_path, tag="t")
    sd = e2._nvme_opt.state_dict_host()
    assert any(np.abs(m).max() > 0 for g in sd for m in g["mu"]), (
        "true resume lost its Adam moments (false-positive resync)")
    l1 = float(e1.train_batch(batch=b))
    l2 = float(e2.train_batch(batch=b))
    assert abs(l1 - l2) < 2e-3, (l1, l2)
