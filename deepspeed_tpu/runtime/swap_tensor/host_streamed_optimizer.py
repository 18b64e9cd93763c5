"""Grouped host-streamed optimizer states (ZeRO-Infinity CPU tier).

Reference: ``deepspeed/runtime/zero/stage_1_and_2.py`` CPU offload +
``csrc/adam/cpu_adam_impl.cpp`` — fp32 master/moments live in host memory
and the update touches them in bounded pieces, never materializing the
whole state beside the model.

TPU-native problem this solves (r4's receipts, docs/PERF.md): XLA will not
bound HBM staging for host-resident state inside ONE program — a
whole-tree update against ``pinned_host`` gets every host→HBM pull
hoisted to the program top, ``optimization_barrier`` chains are ignored
by buffer assignment, and ``compute_on("device_host")`` still stages its
I/O through HBM.  So the bounding is done at the DISPATCH level: fp32
master + Adam moments are partitioned into byte-balanced leaf groups held
as ``pinned_host`` jax Arrays, and each group runs three SEPARATE
dispatches through a double-buffered HBM staging arena:

  upload(g)   host→HBM ``device_put`` of master/mu/nu (the staging slot);
  compute(g)  fused Adam over the staged buffers, which are DONATED —
              the slot's HBM is reused for the outputs;
  download(g) HBM→host ``device_put`` of the updated state (async).

The pipeline keeps at most ``max_staged`` (default 2) groups staged but
unconsumed: upload(g+1) is issued before compute(g) is even dispatched, so
it rides the transfer engine under compute(g); download(g) is issued right
after compute(g) and drains under compute(g+1); the host thread fences one
group BEHIND the dispatch front (on compute(g-1) before leaving iteration
g), which both enforces the staging bound and yields per-group completion
timestamps.  The engine additionally calls ``prefetch(0)``/``prefetch(1)``
right after dispatching the fwd/bwd program, so the first uploads overlap
the BACKWARD of the same step rather than starting at the step boundary.

Unlike the pre-r6 single-dispatch-per-group form (host pulls inside the
update program), the overlap here is measured, not asserted:
``instrumentation`` (overlap_instrumentation.py) records timestamped
events every step, ``step(..., serialize=True)`` runs a fenced probe sweep
attributing per-group upload/compute/download seconds, and
``overlap_report()`` combines them into the overlap fraction and the
transfer-/compute-bound floor.

Interface-compatible with ``PipelinedNVMeOptimizer`` so the engine's
``_nvme_train_step`` orchestration (fwd/bwd program + grouped update loop)
drives either storage tier.  Selected by
``offload_optimizer: {device: cpu, pipeline_read: true}`` on a
single-device mesh (the multi-chip answer is ZeRO sharding, not offload —
asserted by the multichip dryrun).
"""

from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.logging import log_dist
from .overlap_instrumentation import OverlapInstrumentation, now


class _NoopSwapper:
    """Duck-typed stand-in for the NVMe swapper's flush surface: host
    arrays are always durable (nothing is in flight on aio threads)."""

    def flush_writes(self):
        pass

    def teardown(self):
        pass


class HostStreamedOptimizer:
    """fp32 master + Adam moments in TPU-host pinned memory, updated by a
    double-buffered upload/compute/download pipeline of per-group
    dispatches with donated staging buffers."""

    def __init__(self, opt, param_leaves, n_groups: int = 8,
                 compute_dtype=jnp.bfloat16, mesh=None, max_staged: int = 2):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ...comm.mesh import get_global_mesh
        self.opt = opt
        self.compute_dtype = compute_dtype
        mesh = mesh if mesh is not None else get_global_mesh()
        self._dev_sh = NamedSharding(mesh, P())
        try:  # same probe as the engine's try_host_offload: CPU test
            # backends have no pinned_host memory kind — the grouped
            # dispatch structure (and its numerics) is identical, the
            # state just stays in device space there
            self._host_sh = self._dev_sh.with_memory_kind("pinned_host")
            jax.jit(lambda x: x, out_shardings=self._host_sh) \
                .lower(jax.ShapeDtypeStruct((1, ), jnp.float32)).compile()
        except Exception:
            log_dist("HostStreamedOptimizer: pinned_host unsupported on this "
                     "backend; grouped state stays in device memory", ranks=[0])
            self._host_sh = self._dev_sh
        # True when host and device are genuinely distinct memory spaces
        # (on CPU fallback uploads are zero-copy aliases)
        self.host_tier_distinct = self._host_sh is not self._dev_sh
        self.swapper = _NoopSwapper()
        self.events = deque(maxlen=512)
        self.instrumentation = OverlapInstrumentation()
        self.max_staged = max(1, int(max_staged))
        # staging arena: group id -> (master, mu, nu) device-resident lists;
        # a slot is consumed (and its buffers donated) exactly once
        self._staged: Dict[int, Tuple[List[Any], List[Any], List[Any]]] = {}
        self._update_fns: Dict[int, Callable] = {}

        # byte-balanced contiguous leaf partition (same policy as the NVMe
        # swapper so group sizes, and therefore the HBM staging bound, are
        # predictable: ~total_fp32_bytes x 3 x max_staged / n_groups live)
        sizes = [int(np.prod(l.shape)) * 4 for l in param_leaves]
        target = max(1, sum(sizes) // max(1, n_groups))
        self.groups: List[List[int]] = []
        cur, acc = [], 0
        for i, s in enumerate(sizes):
            cur.append(i)
            acc += s
            if acc >= target and len(self.groups) < n_groups - 1:
                self.groups.append(cur)
                cur, acc = [], 0
        if cur:
            self.groups.append(cur)
        self.n_groups = len(self.groups)

        # initialize host-resident state leaf-by-leaf: the fp32 master is
        # cast on device and streamed out (one leaf of HBM at a time, never
        # the whole fp32 tree); moments are born in host space
        to_host_f32 = jax.jit(lambda p: p.astype(jnp.float32), out_shardings=self._host_sh)
        self._master: List[List[Any]] = []
        self._mu: List[List[Any]] = []
        self._nu: List[List[Any]] = []
        for idxs in self.groups:
            ms, mus, nus = [], [], []
            for i in idxs:
                p = param_leaves[i]
                ms.append(to_host_f32(p))
                zeros = jax.jit(lambda p=p: jnp.zeros(p.shape, jnp.float32),
                                out_shardings=self._host_sh)()
                mus.append(zeros)
                nus.append(jax.jit(lambda p=p: jnp.zeros(p.shape, jnp.float32),
                                   out_shardings=self._host_sh)())
            self._master.append(ms)
            self._mu.append(mus)
            self._nu.append(nus)
        jax.block_until_ready(self._master[-1][-1])
        gb = sum(sizes) * 3 / 1e9
        log_dist(f"HostStreamedOptimizer: {len(param_leaves)} leaves in "
                 f"{self.n_groups} groups, {gb:.1f} GB fp32 state in host memory, "
                 f"~{gb / self.n_groups:.1f} GB HBM staging per slot "
                 f"(x{self.max_staged} slots)", ranks=[0])

    # ---------------------------------------------------------- update prog

    def _group_update(self, g: int):
        """Jitted per-group fused-Adam program over DEVICE-resident staged
        buffers.  The staged master/moments are donated: the staging slot's
        HBM is reused for the outputs, so one slot's bytes never count
        twice against the arena bound."""
        if g not in self._update_fns:
            from ...ops.adam import AdamState
            n = len(self.groups[g])
            dev = self._dev_sh

            def upd(master, mu, nu, grads, count, scale):
                g32 = [x.astype(jnp.float32) * scale for x in grads]
                updates, st = self.opt.update(g32, AdamState(count, mu, nu), master)
                new_master = [m + u for m, u in zip(master, updates)]
                new_params = [m.astype(self.compute_dtype) for m in new_master]
                return new_master, st.exp_avg, st.exp_avg_sq, new_params

            self._update_fns[g] = jax.jit(
                upd,
                donate_argnums=(0, 1, 2),
                in_shardings=([dev] * n, [dev] * n, [dev] * n, [dev] * n, dev, dev),
                out_shardings=([dev] * n, [dev] * n, [dev] * n, [dev] * n))
        return self._update_fns[g]

    # ------------------------------------------------------------- pipeline

    def prefetch(self, g: int) -> bool:
        """Issue group ``g``'s host→HBM upload (async ``device_put`` into a
        staging slot).  Bounded: refuses when ``max_staged`` slots are
        already live, so a caller racing ahead cannot blow the HBM arena.
        Idempotent per live slot.  Called by the engine right after the
        fwd/bwd dispatch so the first uploads overlap the backward."""
        if not (0 <= g < self.n_groups) or g in self._staged:
            return False
        if len(self._staged) >= self.max_staged:
            return False
        self.events.append(("upload_issue", g))
        self.instrumentation.record("upload_issue", g)
        self._staged[g] = jax.device_put(
            (self._master[g], self._mu[g], self._nu[g]), self._dev_sh)
        return True

    def _take_staged(self, g: int):
        """Consume group ``g``'s staging slot for the compute dispatch.
        The slot is removed BEFORE its buffers are donated: a second take
        (which would read donated buffers) fails loudly instead of
        returning deleted arrays."""
        staged = self._staged.pop(g, None)
        if staged is None:
            raise RuntimeError(
                f"HostStreamedOptimizer: staging slot for group {g} was never "
                "uploaded or was already consumed (donated) — double-consume "
                "would read a donated buffer")
        return staged

    def pending_writes(self) -> int:
        return 0  # host buffers: durable once their d2h device_put drains

    def step(self, grad_leaves, count, clip_scale, serialize: bool = False,
             flush: bool = False):
        """Per-group update sweep.  Returns new compute-dtype param leaves
        (device), original leaf order.

        Default (pipelined): upload(g+1) is issued before compute(g) is
        dispatched, download(g) right after — transfers ride under compute.
        The host fences one group behind the front; the LAST group's
        compute and all downloads are left in flight so they drain under
        the next step's fwd/bwd (``flush=True`` fences them and records
        the full pipelined wall time for measurement).

        ``serialize=True`` runs the instrumentation probe: a hard fence
        after every phase, recording honest per-group phase seconds into
        ``instrumentation.probe`` (numerics identical — same programs, same
        order, just fenced)."""
        if serialize:
            return self._step_serialized(grad_leaves, count, clip_scale)
        t_entry = now()
        # fence on the grads: compute cannot start before them anyway, and
        # everything already issued (incl. the backward-phase prefetches)
        # keeps running while the host waits here
        if grad_leaves:
            jax.block_until_ready(grad_leaves)
        t0 = now()
        bwd_wait_s = t0 - t_entry
        prefetch_wait_s = None
        self.prefetch(0)
        if 0 in self._staged:
            tw = now()
            jax.block_until_ready(self._staged[0])
            prefetch_wait_s = now() - tw  # ~0 when the upload hid behind bwd
        new_params: List[Optional[Any]] = [None] * sum(len(g) for g in self.groups)
        compute_done_ts: List[float] = []
        prev_probe = None  # (group, first param leaf) fencing one behind
        for g, idxs in enumerate(self.groups):
            # next group's upload rides the transfer engine WHILE this
            # group's compute runs (the double buffer)
            self.prefetch(g + 1)
            m, mu, nu = self._take_staged(g)
            # slot g is consumed: a refused prefetch above (max_staged=1)
            # gets its second chance now that the slot is free
            self.prefetch(g + 1)
            self.events.append(("compute_issue", g))
            self.instrumentation.record("compute_issue", g)
            nm, nmu, nnu, np_leaves = self._group_update(g)(
                m, mu, nu, [grad_leaves[i] for i in idxs], count, clip_scale)
            # async write-back: group g's d2h drains while g+1 computes —
            # and the LAST groups' downloads drain under the next fwd/bwd
            self.events.append(("download_issue", g))
            self.instrumentation.record("download_issue", g)
            self._master[g], self._mu[g], self._nu[g] = jax.device_put(
                (nm, nmu, nnu), self._host_sh)
            for i, p in zip(idxs, np_leaves):
                new_params[i] = p
            if prev_probe is not None:
                # fence ONE group behind the dispatch front: compute(g) and
                # upload(g+1) are already enqueued, so the device stays busy
                # while the host waits; this bounds live staging slots and
                # timestamps compute completion per group
                pg, leaf = prev_probe
                jax.block_until_ready(leaf)
                self.events.append(("update_done", pg))
                compute_done_ts.append(self.instrumentation.record("compute_done", pg))
            prev_probe = (g, np_leaves[0] if np_leaves else None)
        if flush and prev_probe is not None:
            pg, leaf = prev_probe
            jax.block_until_ready(leaf)
            self.events.append(("update_done", pg))
            compute_done_ts.append(self.instrumentation.record("compute_done", pg))
            jax.block_until_ready(self._master)  # all d2h write-backs landed
            self.instrumentation.set_step(now() - t0, bwd_wait_s=bwd_wait_s,
                                          prefetch_wait_s=prefetch_wait_s,
                                          compute_done_ts=compute_done_ts)
        return new_params

    def _step_serialized(self, grad_leaves, count, clip_scale):
        """Instrumentation probe sweep: same programs and issue order as the
        pipelined step, but with a hard fence after every phase so each
        group's upload/compute/download seconds are attributed exactly."""
        if grad_leaves:
            jax.block_until_ready(grad_leaves)
        # any slots staged by a backward-phase prefetch would blur the
        # upload attribution — drain and drop them (re-uploaded fenced)
        if self._staged:
            jax.block_until_ready(self._staged)
            self._staged.clear()
        t_sweep0 = now()
        new_params: List[Optional[Any]] = [None] * sum(len(g) for g in self.groups)
        per_group = []
        for g, idxs in enumerate(self.groups):
            t0 = now()
            self.prefetch(g)
            jax.block_until_ready(self._staged[g])
            t1 = self.instrumentation.record("upload_done", g)
            m, mu, nu = self._take_staged(g)
            self.events.append(("compute_issue", g))
            self.instrumentation.record("compute_issue", g)
            nm, nmu, nnu, np_leaves = self._group_update(g)(
                m, mu, nu, [grad_leaves[i] for i in idxs], count, clip_scale)
            jax.block_until_ready(np_leaves)
            self.events.append(("update_done", g))
            t2 = self.instrumentation.record("compute_done", g)
            self.events.append(("download_issue", g))
            self.instrumentation.record("download_issue", g)
            self._master[g], self._mu[g], self._nu[g] = jax.device_put(
                (nm, nmu, nnu), self._host_sh)
            jax.block_until_ready((self._master[g], self._mu[g], self._nu[g]))
            t3 = self.instrumentation.record("download_done", g)
            per_group.append({"upload_s": t1 - t0, "compute_s": t2 - t1,
                              "download_s": t3 - t2})
            for i, p in zip(idxs, np_leaves):
                new_params[i] = p
        self.instrumentation.set_probe(per_group, wall_s=now() - t_sweep0)
        return new_params

    def overlap_report(self):
        """Measured-overlap artifact (see overlap_instrumentation.report);
        None until a ``serialize=True`` probe sweep has run."""
        rep = self.instrumentation.report()
        if rep is not None:
            rep["host_tier_distinct"] = self.host_tier_distinct
            rep["max_staged"] = self.max_staged
        return rep

    # ------------------------------------------------- checkpoint surface

    def master_matches_params(self, param_leaves, compute_dtype) -> bool:
        """One representative leaf per group, compared in compute dtype
        (params were cast from exactly this master on a true resume)."""
        for g, idxs in enumerate(self.groups):
            disk = np.asarray(jax.device_get(self._master[g][0]),
                              np.float32).astype(compute_dtype)
            live = np.asarray(jax.device_get(param_leaves[idxs[0]]))
            if disk.shape != live.shape or not np.array_equal(disk, live):
                return False
        return True

    def resync_master_from_params(self, param_leaves):
        self._staged.clear()
        to_host_f32 = jax.jit(lambda p: p.astype(jnp.float32), out_shardings=self._host_sh)
        zeros_like_host = jax.jit(lambda p: jnp.zeros_like(p, jnp.float32),
                                  out_shardings=self._host_sh)
        for g, idxs in enumerate(self.groups):
            self._master[g] = [to_host_f32(param_leaves[i]) for i in idxs]
            self._mu[g] = [zeros_like_host(param_leaves[i]) for i in idxs]
            self._nu[g] = [zeros_like_host(param_leaves[i]) for i in idxs]

    def state_dict_host(self):
        out = []
        for g in range(self.n_groups):
            out.append({"master": [np.asarray(jax.device_get(x)) for x in self._master[g]],
                        "mu": [np.asarray(jax.device_get(x)) for x in self._mu[g]],
                        "nu": [np.asarray(jax.device_get(x)) for x in self._nu[g]]})
        return out

    # checkpoint persistence: UNLIKE the NVMe tier (whose swap files are
    # already durable on disk), host-tier state lives in process RAM — the
    # engine persists it into the checkpoint tag directory (as the
    # extra-state callback inside save_checkpoint's durability fence, so
    # the npz files are covered by the tag's crc32 manifest and written
    # BEFORE `latest` is published)
    def save_state(self, directory: str):
        import os

        from ...resilience.atomic_io import atomic_savez
        for g in range(self.n_groups):
            arrs = {}
            for name, store in (("master", self._master), ("mu", self._mu), ("nu", self._nu)):
                for i, x in enumerate(store[g]):
                    arrs[f"{name}_{i}"] = np.asarray(jax.device_get(x))
            atomic_savez(os.path.join(directory, f"host_opt_group{g}.npz"), arrs,
                         site="host_opt.save")

    def load_state(self, directory: str) -> bool:
        """Restore group state saved by ``save_state``; False when the files
        are absent, torn/corrupt (checksum manifest or archive read fails —
        rejected up front, never mid-restore), or shaped for a different
        partitioning.  The live state is only replaced once EVERY group
        verified and loaded."""
        import os
        import zipfile

        from ...resilience import events
        from ...resilience import fault_injection as fi
        from ...resilience.atomic_io import verify_manifest
        from ...resilience.retry import RetryPolicy, retry_call
        from ...utils.logging import logger
        # transient read errors at the load entry are retryable (the
        # os_error taxonomy contract); archive-level failures below degrade
        # to a False return instead
        retry_call(lambda: fi.check("host_opt.load"),
                   RetryPolicy(max_attempts=3, base_delay_s=0.01, max_delay_s=0.25,
                               budget_s=2.0),
                   site="host_opt.load")
        if not all(os.path.exists(os.path.join(directory, f"host_opt_group{g}.npz"))
                   for g in range(self.n_groups)):
            return False
        # the tag-level resilience manifest (written post-fence by
        # save_checkpoint) pins every npz to its crc32; a tag saved before
        # the manifest existed falls through to the archive-read guard
        errors = verify_manifest(directory,
                                 match=lambda rel: rel.startswith("host_opt_group"))
        if errors:
            logger.warning("host-streamed offload: rejecting host_opt_group*.npz "
                           f"state at {directory} — checksum manifest failed: "
                           f"{errors[0]}")
            events.emit("resilience/host_opt_reject")
            return False
        loads = []
        for g in range(self.n_groups):
            path = os.path.join(directory, f"host_opt_group{g}.npz")
            try:
                with np.load(path) as z:
                    grp = {name: [z[f"{name}_{i}"] for i in range(len(self.groups[g]))]
                           for name in ("master", "mu", "nu")}
            except (OSError, ValueError, KeyError, zipfile.BadZipFile, EOFError) as e:
                logger.warning(f"host-streamed offload: rejecting truncated/corrupt "
                               f"{path}: {e}")
                events.emit("resilience/host_opt_reject")
                return False
            if any(g_arr.shape != np.asarray(jax.device_get(cur)).shape
                   for g_arr, cur in zip(grp["master"], self._master[g])):
                return False
            loads.append(grp)
        self._staged.clear()  # staged slots would upload pre-restore state
        for g, grp in enumerate(loads):
            self._master[g] = [jax.device_put(x, self._host_sh) for x in grp["master"]]
            self._mu[g] = [jax.device_put(x, self._host_sh) for x in grp["mu"]]
            self._nu[g] = [jax.device_put(x, self._host_sh) for x in grp["nu"]]
        return True

    def teardown(self):
        self._master = self._mu = self._nu = []
        self._staged.clear()
