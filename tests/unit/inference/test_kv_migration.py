"""Host-staged KV migration (serving/kvtransfer + fleet disaggregation):
export/import staging correctness, the crc-tagged snapshot contract, the
serving engine's MIGRATING lifecycle, replica roles + the disaggregated
policy's two-phase dispatch, failover KV reuse, and the seeded workload
generators — all on the tiny CPU model with deterministic clocks."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import (RequestState, ServingEngine, VirtualClock)
from deepspeed_tpu.serving.kvtransfer import (KVExporter, KVImportError,
                                              SnapshotAborted,
                                              SnapshotIntegrityError,
                                              import_snapshot)
from deepspeed_tpu.serving.fleet import (DisaggregatedPolicy, FleetSimulator,
                                         FleetState, ReplicaPool, ReplicaRole,
                                         Router, heavy_tail_arrivals,
                                         make_policy, poisson_mixed_arrivals)

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)
PAGE = 8


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _factory(trained_params, num_pages=64, max_seqs=8, prefill_chunk=8,
             max_pages_per_seq=16):
    def make():
        kv = PagedKVConfig(num_pages=num_pages, page_size=PAGE,
                           max_pages_per_seq=max_pages_per_seq)
        sched = SchedulerConfig(token_budget=64, max_seqs=max_seqs,
                                prefill_chunk=prefill_chunk, decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1))
    return make


PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9], [11, 4, 4]]


def _arrivals(prompts, max_new=8, spacing=0.5):
    return [dict(prompt=p, max_new_tokens=max_new, arrival_ts=round(i * spacing, 6))
            for i, p in enumerate(prompts)]


def _run_until(serve, pred, max_ticks=200):
    for _ in range(max_ticks):
        if pred():
            return
        serve.tick()
    raise AssertionError("condition never reached")


def _export_all(exporter):
    while not exporter.step_chunk():
        pass
    return exporter.snapshot


def _clean_arena(engine):
    """Allocator cleanliness: no live sequences, and after dropping the
    prefix cache every page but the reserved null page is free."""
    assert not engine.state.seqs
    if engine.kv.prefix_cache is not None:
        engine.kv.prefix_cache.evict(engine.kv.num_pages)
    assert engine.kv.allocator.free_pages == engine.kv.num_pages - 1


# -------------------------------------------------------- staging primitives


def test_export_import_pages_roundtrip_and_validation(trained_params):
    eng = _factory(trained_params)()
    eng.put([0], [PROMPTS[2]])
    for _ in range(4):
        eng.step()
    seq = eng.state.seqs[0]
    pages = list(seq.pages[:2])
    block = eng.kv.export_pages(eng.cache, pages)
    assert block.shape[1] == 2 and str(block.dtype) == str(eng.cache.dtype)
    # import back into the SAME slots is a byte-identical no-op
    arena2 = eng.kv.import_pages(eng.cache, pages, block)
    np.testing.assert_array_equal(np.asarray(arena2[:, pages]), block)
    with pytest.raises(ValueError, match="out of range"):
        eng.kv.export_pages(eng.cache, [0])          # reserved null page
    with pytest.raises(ValueError, match="out of range"):
        eng.kv.export_pages(eng.cache, [eng.kv.num_pages])
    with pytest.raises(ValueError, match="block shape"):
        eng.kv.import_pages(eng.cache, pages, block[:, :1])
    with pytest.raises(ValueError, match="dtype"):
        eng.kv.import_pages(eng.cache, pages, block.astype(np.float16))


def test_snapshot_crc_and_completeness(trained_params):
    eng = _factory(trained_params)()
    eng.put([0], [PROMPTS[2]], max_new_tokens=6)
    for _ in range(5):                               # the prompt in one step (a run of two chunks), four tokens more
        eng.step()
    seq = eng.state.seqs[0]
    seq.paused = True
    exporter = KVExporter(eng, 0, chunk_pages=1)
    exporter.step_chunk()
    with pytest.raises(SnapshotIntegrityError, match="incomplete"):
        exporter.snapshot.verify()                   # partial export unusable
    snap = _export_all(exporter)
    snap.verify()
    snap.chunks[0] = snap.chunks[0].copy()           # np.asarray(jax) is read-only
    snap.chunks[0].flat[3] += 1.0                    # torn/bit-rotted staging
    with pytest.raises(SnapshotIntegrityError, match="crc mismatch"):
        snap.verify()


def test_exporter_aborts_when_source_changes(trained_params):
    eng = _factory(trained_params)()
    eng.put([0], [PROMPTS[2]], max_new_tokens=6)
    for _ in range(5):                               # the prompt in one step (a run of two chunks), four tokens more
        eng.step()
    eng.state.seqs[0].paused = True
    exporter = KVExporter(eng, 0, chunk_pages=1)
    exporter.step_chunk()
    eng.flush(0)                                     # preempted/flushed mid-export
    with pytest.raises(SnapshotAborted):
        exporter.step_chunk()


def test_import_rejections_leak_nothing(trained_params):
    src = _factory(trained_params)()
    src.put([0], [PROMPTS[2]], max_new_tokens=6)
    for _ in range(5):
        src.step()
    seq = src.state.seqs[0]
    seq.paused = True
    snap = _export_all(KVExporter(src, 0, chunk_pages=2))

    dst = _factory(trained_params)()
    free_before = dst.kv.allocator.free_pages
    with pytest.raises(KVImportError, match="token history mismatch"):
        import_snapshot(dst, 1, seq.tokens + [7], snap, max_new_tokens=4)
    with pytest.raises(KVImportError, match="page_size mismatch"):
        bad = type(snap)(tokens=list(seq.tokens), seen_tokens=snap.seen_tokens,
                         page_size=PAGE * 2, block_shape=snap.block_shape,
                         dtype=snap.dtype, chunks=snap.chunks, crcs=snap.crcs,
                         complete=True)
        import_snapshot(dst, 1, seq.tokens, bad, max_new_tokens=4)
    dst.put([9], [PROMPTS[0]])
    with pytest.raises(KVImportError, match="already live"):
        import_snapshot(dst, 9, seq.tokens, snap, max_new_tokens=4)
    dst.flush(9)
    assert dst.kv.allocator.free_pages == free_before  # zero refcount drift

    # capacity shortfall: a target too small for the snapshot rejects it
    tiny = _factory(trained_params, num_pages=2)()
    with pytest.raises(KVImportError, match="short"):
        import_snapshot(tiny, 1, seq.tokens, snap, max_new_tokens=4)
    assert tiny.kv.allocator.free_pages == tiny.kv.num_pages - 1


def test_import_resumes_byte_identically(trained_params):
    max_new = 10
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=max_new)[0]
    src = _factory(trained_params)()
    src.put([0], [PROMPTS[2]], max_new_tokens=max_new)
    _k = 4
    while len(src.state.seqs[0].generated) < _k:
        src.step()
    seq = src.state.seqs[0]
    head = list(seq.generated)
    seq.paused = True
    snap = _export_all(KVExporter(src, 0, chunk_pages=2))
    dst = _factory(trained_params)()
    import_snapshot(dst, 7, seq.tokens, snap,
                    max_new_tokens=max_new - len(head))
    out = []
    while 7 in dst.state.seqs and not dst.state.seqs[7].done:
        out.extend(dst.step().get(7, []))
    assert head + out == golden


# --------------------------------------------- serving engine MIGRATING flow


def _serve(trained_params, **kw):
    return ServingEngine(_factory(trained_params, **kw)(), clock=VirtualClock())


def test_serving_migration_roundtrip_and_stats(trained_params):
    max_new = 8
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=max_new)[0]
    a, b = _serve(trained_params), _serve(trained_params)
    req = a.submit(PROMPTS[2], max_new_tokens=max_new)
    _run_until(a, lambda: req.state is RequestState.DECODE)
    exporter = a.begin_migration(req.uid, chunk_pages=2)
    assert exporter is not None and req.state is RequestState.MIGRATING
    snap = _export_all(exporter)
    closed = a.complete_migration(req.uid)
    assert closed.state is RequestState.MIGRATED and a.stats.migrated == 1
    assert req.uid not in a.engine.state.seqs
    _clean_arena(a.engine)

    req2 = b.submit(PROMPTS[2], max_new_tokens=max_new,
                    resume_tokens=list(req.tokens), kv_snapshot=snap)
    b.drain()
    assert req2.state is RequestState.DONE
    assert req2.tokens == golden
    assert b.stats.kv_imports == 1 and b.stats.kv_import_fallbacks == 0


def test_serving_import_fallback_recomputes_identically(trained_params):
    max_new = 8
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=max_new)[0]
    a, b = _serve(trained_params), _serve(trained_params)
    req = a.submit(PROMPTS[2], max_new_tokens=max_new)
    _run_until(a, lambda: req.state is RequestState.DECODE)
    snap = _export_all(a.begin_migration(req.uid, chunk_pages=2))
    a.complete_migration(req.uid)
    snap.chunks[0] = snap.chunks[0].copy()
    snap.chunks[0].flat[0] += 1.0            # torn in host staging
    req2 = b.submit(PROMPTS[2], max_new_tokens=max_new,
                    resume_tokens=list(req.tokens), kv_snapshot=snap)
    b.drain()
    assert req2.state is RequestState.DONE and req2.tokens == golden
    assert b.stats.kv_imports == 0 and b.stats.kv_import_fallbacks == 1
    _clean_arena_after_drain(b)


def _clean_arena_after_drain(serve):
    assert not serve._active and not serve._queue
    _clean_arena(serve.engine)


def test_paused_sequence_takes_no_steps_and_pages_stay_stable(trained_params):
    a = _serve(trained_params)
    victim = a.submit(PROMPTS[2], max_new_tokens=12)
    _run_until(a, lambda: victim.state is RequestState.DECODE)
    exporter = a.begin_migration(victim.uid, chunk_pages=1)
    tokens_at_pause = list(victim.tokens)
    first = exporter.step_chunk()
    ref = a.engine.kv.export_pages(a.engine.cache, exporter._pages)
    # serve OTHER traffic for a while: the paused sequence must not step
    # and its pages must stay byte-stable under the neighbours' churn
    others = [a.submit(p, max_new_tokens=6) for p in (PROMPTS[0], PROMPTS[1])]
    for _ in range(30):
        a.tick()
    assert all(o.state is RequestState.DONE for o in others)
    assert victim.tokens == tokens_at_pause
    np.testing.assert_array_equal(
        np.asarray(a.engine.kv.export_pages(a.engine.cache, exporter._pages)), np.asarray(ref))
    assert not first or exporter.snapshot.complete
    # abort: decode resumes in place and finishes exactly as unmigrated
    a.abort_migration(victim.uid)
    assert victim.state is RequestState.DECODE
    a.drain()
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=12)[0]
    assert victim.tokens == golden


def test_begin_migration_windows(trained_params):
    a = _serve(trained_params, prefill_chunk=8)
    assert a.begin_migration(999) is None            # unknown uid
    long_prompt = [int(x) for x in np.random.default_rng(3).integers(1, 100, 72)]
    req = a.submit(long_prompt, max_new_tokens=6)
    a.tick()                                          # admit + the first run of four chunks
    seq = a.engine.state.seqs[req.uid]
    assert req.state is RequestState.PREFILL
    # too early: more than one chunk of prefill remains
    assert seq.remaining_prefill > 8
    assert a.begin_migration(req.uid) is None and not seq.paused
    while seq.remaining_prefill > 8:
        a.tick()
    if req.state is RequestState.PREFILL:             # late-prefill window
        exporter = a.begin_migration(req.uid, chunk_pages=8)
        assert exporter is not None and req.state is RequestState.MIGRATING
        a.abort_migration(req.uid)
        assert req.state is RequestState.PREFILL      # resumes the same phase
    a.drain()
    golden = _factory(trained_params)().generate([long_prompt], max_new_tokens=6)[0]
    assert req.tokens == golden


# ------------------------------------------------------- fleet disaggregation


def _fleet(trained_params, roles, policy="disaggregated", n=None, tracer=None,
           role_factories=None, **router_kw):
    pool = ReplicaPool(_factory(trained_params), n or len(roles),
                       clock=VirtualClock(), roles=roles, tracer=tracer,
                       role_factories=role_factories)
    return Router(pool, make_policy(policy), tracer=tracer, **router_kw), pool


def test_disaggregated_fleet_identical_outputs(trained_params):
    golden = _factory(trained_params)().generate(PROMPTS, max_new_tokens=8)
    router, pool = _fleet(trained_params, ["prefill", "decode"],
                          migration_chunk_pages=1, migration_chunk_cost=0.05)
    reqs = FleetSimulator(router).run(_arrivals(PROMPTS))
    assert [r.state for r in reqs] == [FleetState.DONE] * 4
    assert [r.tokens for r in reqs] == golden
    assert all(r.migrations == 1 for r in reqs)
    assert all([d[0] for d in r.dispatches] == [0, 1] for r in reqs)
    mig = router.summary()["migration"]
    assert mig["completed"] == 4 and mig["kv_imports"] == 4
    assert mig["import_fallbacks"] == 0 and mig["fallbacks"] == 0
    # per-replica terminal accounting: source counts MIGRATED, not DONE
    assert pool.replica(0).serve.stats.migrated == 4
    assert pool.replica(1).serve.stats.kv_imports == 4


def test_prefill_handoff_runs_final_chunk_on_decode_replica(trained_params):
    prompt = [int(x) for x in np.random.default_rng(5).integers(1, 100, 40)]
    golden = _factory(trained_params)().generate([prompt], max_new_tokens=6)[0]
    router, pool = _fleet(trained_params, ["prefill", "decode"],
                          migration_chunk_pages=8, migration_chunk_cost=0.05,
                          prefill_handoff=True)
    reqs = FleetSimulator(router).run(_arrivals([prompt], max_new=6))
    fr = reqs[0]
    assert fr.state is FleetState.DONE and fr.tokens == golden
    assert fr.migrations == 1 and [d[0] for d in fr.dispatches] == [0, 1]
    # the DistServe boundary: the first token was sampled on the DECODE
    # replica — the prefill attempt delivered nothing
    assert fr.first_token_ts >= fr.dispatches[1][1]
    assert pool.replica(1).serve.stats.kv_imports == 1


def test_migration_aborts_when_decode_pool_vanishes(trained_params):
    """Export completes but every decode replica is dead by handoff time:
    decode resumes IN PLACE on the source (fallback ladder, not a loss)."""
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=8)
    router, pool = _fleet(trained_params, ["prefill", "decode"],
                          migration_chunk_pages=1)
    fr = router.submit(PROMPTS[2], max_new_tokens=8, arrival_ts=0.0)
    # run rounds by hand until the export is in flight, then kill the
    # decode replica mid-export: the export still completes, but the
    # handoff finds no decode pool and aborts in place
    for _ in range(60):
        now = pool.clock.now()
        router.dispatch_pending(now)
        costs = []
        for rid in pool.rids:
            if pool.health.serving(rid):
                pool.tick(rid)
                c = pool.replica(rid).clock.take_cost()
                if c:
                    costs.append(c)
        if costs:
            pool.clock.advance(max(costs))
        router.poll(pool.clock.now())
        if fr.fid in router._migrations:
            break
    assert fr.fid in router._migrations
    router.kill_replica(1)
    reqs = FleetSimulator(router).run([])
    assert fr.state is FleetState.DONE and fr.tokens == golden[0]
    assert router.stats["migration_fallbacks"] >= 1
    assert fr.migrations >= 1 and len(fr.dispatches) == 1  # never left replica 0


def test_failover_reuses_exported_kv_on_target_death(trained_params):
    """The failover-reuse satellite: the decode TARGET dies after the
    handoff was dispatched but before it admitted the request — the
    host-staged snapshot survives and the OTHER decode replica resumes
    through the KV-import fast path, outputs identical."""
    golden = _factory(trained_params)().generate([PROMPTS[2]], max_new_tokens=8)
    router, pool = _fleet(trained_params, ["prefill", "decode", "decode"],
                          migration_chunk_pages=1, migration_chunk_cost=0.05)
    fr = router.submit(PROMPTS[2], max_new_tokens=8, arrival_ts=0.0)
    for _ in range(100):
        now = pool.clock.now()
        router.dispatch_pending(now)
        for rid in pool.rids:
            if pool.health.serving(rid):
                pool.tick(rid)
                c = pool.replica(rid).clock.take_cost()
                if c:
                    pool.clock.advance(c)
        router.poll(pool.clock.now())
        if len(fr.dispatches) == 2:
            break
    assert len(fr.dispatches) == 2, "handoff never dispatched"
    target = fr.dispatches[1][0]
    assert target in (1, 2)
    # the handed-off request is still QUEUED on the target (admission runs
    # on the target's NEXT tick) — kill it now
    assert fr._current[1].state is RequestState.QUEUED
    router.kill_replica(target)
    assert fr._kv_snapshot is not None               # snapshot harvested back
    assert router.stats["migration_failover_reuse"] == 1
    reqs = FleetSimulator(router).run([])
    survivor = 3 - target
    assert fr.state is FleetState.DONE and fr.tokens == golden[0]
    assert fr.dispatches[2][0] == survivor
    assert pool.replica(survivor).serve.stats.kv_imports == 1   # fast path, no recompute


def test_roles_and_policy_fallback(trained_params):
    with pytest.raises(ValueError, match="roles"):
        ReplicaPool(_factory(trained_params), 2, clock=VirtualClock(),
                    roles=["prefill"])
    # a decode-only rump still serves fresh prompts (availability beats
    # specialization): the policy falls back to the full candidate list
    router, pool = _fleet(trained_params, ["decode", "decode"])
    reqs = FleetSimulator(router).run(_arrivals(PROMPTS[:2]))
    assert [r.state for r in reqs] == [FleetState.DONE] * 2
    assert router.summary()["migration"]["started"] == 0
    # role matching: fresh → prefill, token-carrying → decode
    pol = DisaggregatedPolicy()

    class _C:
        def __init__(self, role):
            self.role = role

    cands = [(0, _C(ReplicaRole.PREFILL), {"outstanding_tokens": 50, "queue_depth": 0,
                                           "active": 1, "ewma_step_s": None}),
             (1, _C(ReplicaRole.DECODE), {"outstanding_tokens": 0, "queue_depth": 0,
                                          "active": 0, "ewma_step_s": None})]

    class _R:
        tokens = []
    rid, info = pol.select(_R(), cands)
    assert rid == 0 and info["phase"] == "prefill" and info["role_match"]

    class _R2:
        tokens = [1, 2]
    rid, info = pol.select(_R2(), cands)
    assert rid == 1 and info["phase"] == "decode" and info["role_match"]


def test_role_factories_survive_recover(trained_params):
    rf = {"decode": _factory(trained_params, num_pages=96)}
    pool = ReplicaPool(_factory(trained_params, num_pages=64), 2,
                       clock=VirtualClock(), roles=["prefill", "decode"],
                       role_factories=rf)
    assert pool.replica(0).serve.engine.kv.num_pages == 64
    assert pool.replica(1).serve.engine.kv.num_pages == 96
    pool.kill(1)
    pool.recover(1)
    assert pool.replica(1).serve.engine.kv.num_pages == 96  # role kept its factory


def test_migration_phase_spans_positive_width(trained_params):
    from deepspeed_tpu.telemetry import Tracer
    clock = VirtualClock()
    pool = ReplicaPool(_factory(trained_params), 2, clock=clock,
                       roles=["prefill", "decode"], tracer=Tracer(clock=clock))
    router = Router(pool, make_policy("disaggregated"), tracer=pool.tracer,
                    migration_chunk_pages=1, migration_chunk_cost=0.05)
    reqs = FleetSimulator(router).run(_arrivals(PROMPTS, max_new=6))
    assert all(r.state is FleetState.DONE for r in reqs)
    mig_spans = [s for s in pool.tracer.spans if s.name == "phase/migrating"]
    completed = router.summary()["migration"]["completed"]
    assert completed == len(PROMPTS)
    assert len(mig_spans) == completed
    assert all(s.end_ts > s.start_ts for s in mig_spans)  # cost is visible


# ----------------------------------------------------------- workload library


def test_workload_generators_deterministic_and_shaped():
    a1 = poisson_mixed_arrivals(seed=7, n_requests=50, rate=2.0, vocab=100)
    a2 = poisson_mixed_arrivals(seed=7, n_requests=50, rate=2.0, vocab=100)
    assert a1 == a2                                   # bit-identical per seed
    assert a1 != poisson_mixed_arrivals(seed=8, n_requests=50, rate=2.0, vocab=100)
    assert len(a1) == 50
    lens = [len(a["prompt"]) for a in a1]
    assert any(x >= 72 for x in lens) and any(x <= 10 for x in lens)  # both classes
    assert all(a["deadline"] is None for a in a1)
    assert all(a1[i]["arrival_ts"] <= a1[i + 1]["arrival_ts"] for i in range(49))
    wd = poisson_mixed_arrivals(seed=7, n_requests=10, rate=2.0, vocab=100,
                                deadline_slack=5.0)
    assert all(d["deadline"] == round(d["arrival_ts"] + 5.0, 6) for d in wd)

    h1 = heavy_tail_arrivals(seed=3, n_requests=200, rate=4.0, vocab=100)
    assert h1 == heavy_tail_arrivals(seed=3, n_requests=200, rate=4.0, vocab=100)
    lens = [len(a["prompt"]) for a in h1]
    assert max(lens) <= 192 and min(lens) >= 2        # Pareto tail clipped
    assert sorted(lens)[len(lens) // 2] < 30          # lognormal body stays small
    assert max(lens) > 64                             # the tail actually appears
    assert all(2 <= a["max_new_tokens"] <= 24 for a in h1)
