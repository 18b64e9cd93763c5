"""Control-plane transport tests (deepspeed_tpu/serving/fleet/transport.py
+ the lease/fencing/feed machinery it carries — docs/SERVING.md
"Control-plane transport"): deterministic fault schedules, heartbeat-lease
health, staleness-annotated routing signals, the sequence-numbered prefix
feed with gap-resync, the ack/retry migration chunk channel, and the
directory-driven recovery warm-up — all on the tiny CPU model with one
shared deterministic VirtualClock."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import ServingConfig, VirtualClock
from deepspeed_tpu.serving.fleet import (ControlTransport, FleetHealthView,
                                         FleetSimulator, FleetState,
                                         LeaseConfig, LeaseState,
                                         LeastOutstandingPolicy, LinkFaults,
                                         PartitionWindow, PrefixDirectory,
                                         ReplicaPool, Router, RoundRobinPolicy,
                                         make_policy)

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)

PAGE = 8


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _factory(trained_params, num_pages=64, max_seqs=8):
    def make():
        kv = PagedKVConfig(num_pages=num_pages, page_size=PAGE, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=8,
                                decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1))
    return make


def _fleet(trained_params, n_replicas, policy=None, faults=None, partitions=(),
           lease=None, seed=0, directory=None, **pool_kw):
    clock = VirtualClock()
    transport = ControlTransport(clock, faults=faults, seed=seed,
                                 partitions=partitions)
    pool = ReplicaPool(_factory(trained_params), n_replicas, clock=clock,
                       transport=transport, prefix_directory=directory,
                       **pool_kw)
    if directory is not None and policy is None:
        policy = make_policy("prefix_directory", directory=directory)
    router = Router(pool, policy or LeastOutstandingPolicy(),
                    transport=transport,
                    lease_config=lease or LeaseConfig(suspect_after=2.0,
                                                      lease=6.0))
    return router, pool, transport


PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9], [11, 4, 4]]


def _arrivals(prompts, max_new=6, spacing=0.5):
    return [dict(prompt=p, max_new_tokens=max_new,
                 arrival_ts=round(i * spacing, 6))
            for i, p in enumerate(prompts)]


# ------------------------------------------------------- transport fabric


def test_transport_deterministic_schedule():
    def run(seed):
        clock = VirtualClock()
        tr = ControlTransport(clock, faults=LinkFaults(
            loss_p=0.3, dup_p=0.2, reorder_p=0.3, reorder_delay=1.0), seed=seed)
        log = []
        for i in range(50):
            tr.send("heartbeat", 0, "router", {"i": i}, seq=i)
            clock.advance(0.5)
            log.extend((m.seq, m.send_ts) for m in tr.deliver())
        clock.advance(10.0)
        log.extend((m.seq, m.send_ts) for m in tr.deliver())
        return log, dict(tr.stats)

    log_a, stats_a = run(7)
    log_b, stats_b = run(7)
    assert log_a == log_b and stats_a == stats_b   # bit-reproducible
    log_c, _ = run(8)
    assert log_c != log_a                          # and seed-sensitive
    assert stats_a["dropped"] > 0 and stats_a["duplicated"] > 0 \
        and stats_a["reordered"] > 0
    # conservation: every sent message is delivered or accounted lost
    assert stats_a["delivered"] + stats_a["dropped"] \
        + stats_a["partition_dropped"] == stats_a["sent"] + stats_a["duplicated"]


def test_partition_window_severs_both_ends_and_next_wake():
    clock = VirtualClock()
    tr = ControlTransport(clock, partitions=[
        PartitionWindow("cut", 2.0, 5.0, (("router", 1),))])
    assert tr.connected("router", 1, 1.9) and tr.connected(1, "router", 5.0)
    assert not tr.connected(1, "router", 2.0)
    # sent pre-cut, due mid-cut: the partition eats it at DELIVERY time
    clock.advance(1.5)
    tr.link_faults[frozenset(("router", 1))] = LinkFaults(delay=1.0)
    tr.send("fence", "router", 1, {})
    clock.advance(1.0)       # deliver_ts 2.5 inside the window
    assert tr.deliver() == []
    assert tr.stats["partition_dropped"] == 1
    # sent mid-cut: dropped at send
    clock.advance(0.5)
    tr.send("fence", "router", 1, {})
    assert tr.stats["partition_dropped"] == 2
    # an unrelated link is untouched
    assert tr.send("fence", "router", 0, {}) is not None
    # wake-ups include the window boundaries
    assert 5.0 in tr.next_wake(3.0)
    with pytest.raises(ValueError):
        tr.send("bogus_kind", "router", 0, {})
    with pytest.raises(ValueError):
        PartitionWindow("empty", 3.0, 3.0, (("router", 0),))


def test_lease_view_transitions_and_fencing_epochs():
    clock = VirtualClock()
    events = []
    view = FleetHealthView([0], config=LeaseConfig(suspect_after=2.0, lease=6.0),
                           clock=clock, emit=lambda n, v: events.append((n, v)))
    stats = {"queue_depth": 0}
    assert view.observe_heartbeat(0, 1, "healthy", stats, 0.0, 0.0) == "ok"
    # reordered OLD heartbeat never rewinds the view
    assert view.observe_heartbeat(0, 1, "healthy", stats, 0.0, 0.5) == "stale"
    clock.advance(3.0)
    assert view.tick(3.0) == [] and view.state(0) is LeaseState.SUSPECT
    assert not view.dispatchable(0)
    assert view.observe_heartbeat(0, 2, "healthy", stats, 3.0, 3.0) == "ok"
    assert view.state(0) is LeaseState.ALIVE and view.dispatchable(0)
    # a dispatchable lease still respects the replica's own report
    view.observe_heartbeat(0, 3, "draining", stats, 3.1, 3.1)
    assert not view.dispatchable(0)
    clock.advance(7.0)
    assert view.tick(10.0) == [0] and view.state(0) is LeaseState.DEAD
    assert view.epoch[0] == 1
    # heartbeats resume: zombie until the fence acks; stale-epoch acks ignored
    assert view.observe_heartbeat(0, 4, "healthy", stats, 9.5, 10.0) == "zombie"
    assert view.state(0) is LeaseState.FENCING
    assert view.fence_pending(10.0) == [0]
    assert view.note_fence_sent(0, 10.0) is True
    assert view.fence_pending(10.5) == []          # retry timer holds
    assert view.fence_pending(12.5) == [0]         # ...then re-sends
    assert not view.on_fence_ack(0, epoch=0, now=12.5)
    assert view.on_fence_ack(0, epoch=1, now=12.5)
    assert view.state(0) is LeaseState.ALIVE
    names = [n for n, _ in events]
    assert names == ["fleet/lease_suspect", "fleet/lease_renewed",
                     "fleet/lease_expired", "fleet/lease_renewed"]


def test_transport_must_be_shared_both_directions(trained_params):
    """Router and pool must ride the SAME fabric — a pool-only transport
    would heartbeat into a queue nobody drains (and never write the
    directory), a router-only one would read a fabric nobody feeds."""
    clock = VirtualClock()
    tr = ControlTransport(clock)
    pool = ReplicaPool(_factory(trained_params), 1, clock=clock, transport=tr)
    with pytest.raises(ValueError, match="SAME transport"):
        Router(pool, RoundRobinPolicy())            # pool has one, router not
    pool2 = ReplicaPool(_factory(trained_params), 1, clock=clock)
    with pytest.raises(ValueError, match="SAME transport"):
        Router(pool2, RoundRobinPolicy(), transport=tr)   # router-only


def test_duplicate_fence_is_idempotent_per_epoch(trained_params):
    """A duplicated/retried FENCE delivered AFTER the ack re-admitted the
    replica must not cancel legitimately re-dispatched work: fences
    execute once per epoch and late copies re-ack with zeros."""
    router, pool, tr = _fleet(trained_params, 2)
    serve = pool.replica(0).serve
    serve.submit([1, 2, 3], max_new_tokens=4)
    assert serve._queue or serve._active
    counts = pool.fence_replica(0, epoch=1)
    assert counts["queued"] + counts["active"] == 1
    # post-rejoin work lands on the replica...
    serve.submit([4, 5, 6], max_new_tokens=4)
    # ...and the duplicate of the SAME epoch's fence must not touch it
    assert pool.fence_replica(0, epoch=1) == {"queued": 0, "active": 0}
    assert len(serve._queue) + len(serve._active) == 1
    # a NEW epoch (a real second expiry) fences again
    assert pool.fence_replica(0, epoch=2)["queued"] == 1


# --------------------------------------------------- fleet over the fabric


def test_perfect_transport_matches_direct_fleet(trained_params):
    golden = _factory(trained_params)().generate(PROMPTS, max_new_tokens=6)
    router, pool, tr = _fleet(trained_params, 2, policy=RoundRobinPolicy())
    reqs = FleetSimulator(router).run(_arrivals(PROMPTS))
    assert [r.state for r in reqs] == [FleetState.DONE] * 4
    assert [r.tokens for r in reqs] == golden
    cp = router.summary()["control_plane"]
    assert cp["lease_expirations"] == 0 and cp["fenced_replicas"] == 0
    assert cp["transport"]["dropped"] == 0
    # the staleness annotation rides every candidate snapshot
    assert all("age" in st for _, _, st in router._candidates())


def test_lossy_transport_still_serves_goldens(trained_params):
    golden = _factory(trained_params)().generate(PROMPTS, max_new_tokens=6)
    router, pool, tr = _fleet(
        trained_params, 2,
        faults=LinkFaults(loss_p=0.15, dup_p=0.1, reorder_p=0.15,
                          reorder_delay=1.0), seed=11)
    reqs = FleetSimulator(router).run(_arrivals(PROMPTS))
    assert [r.state for r in reqs] == [FleetState.DONE] * 4
    assert [r.tokens for r in reqs] == golden
    assert tr.stats["dropped"] + tr.stats["duplicated"] > 0


def test_partition_heals_before_lease_tokens_catch_up(trained_params):
    """A partition SHORTER than the lease: no failover at all — the
    attempt stays current and the poll re-sync catches the tokens up
    after the heal, byte-identically."""
    golden = _factory(trained_params)().generate([PROMPTS[0]], max_new_tokens=12)
    router, pool, tr = _fleet(
        trained_params, 2,
        partitions=[PartitionWindow("blip", 3.0, 6.0, (("router", 0),))],
        lease=LeaseConfig(suspect_after=4.0, lease=12.0))
    reqs = FleetSimulator(router).run(
        [dict(prompt=PROMPTS[0], max_new_tokens=12, arrival_ts=0.0)])
    assert reqs[0].state is FleetState.DONE
    assert reqs[0].tokens == golden[0]
    assert reqs[0].failovers == 0 and reqs[0].dispatches[0][0] == 0
    assert router.summary()["control_plane"]["lease_expirations"] == 0


def test_kill_recover_inside_lease_window_generation_fences(trained_params):
    """A replica that dies AND comes back before its lease expires renews
    the lease — the bumped engine generation in its heartbeat is what
    re-homes the attempts its old engine took to the grave."""
    golden = _factory(trained_params)().generate([PROMPTS[0]], max_new_tokens=12)
    router, pool, tr = _fleet(trained_params, 2,
                              lease=LeaseConfig(suspect_after=4.0, lease=12.0))
    reqs = FleetSimulator(router).run(
        [dict(prompt=PROMPTS[0], max_new_tokens=12, arrival_ts=0.0)],
        schedule=[(2.0, "kill", 0), (3.0, "recover", 0)])
    assert reqs[0].state is FleetState.DONE
    assert reqs[0].tokens == golden[0]
    assert reqs[0].failovers >= 1
    assert router.summary()["control_plane"]["lease_expirations"] == 0


# ------------------------------------------------ prefix feed + gap resync


def _warm_fleet_with_directory(trained_params, **kw):
    directory = PrefixDirectory(page_size=PAGE)
    router, pool, tr = _fleet(trained_params, 2, directory=directory, **kw)
    return router, pool, tr, directory


def test_publish_gap_detected_and_resynced(trained_params):
    """Drop one publish from a replica's seq-numbered stream: the router
    must DETECT the gap (``prefix/publish_gap``), pull a full-digest
    resync, and end with a directory that agrees with the replica's cache
    — stale-cold absorption is exactly what r16 removes."""
    router, pool, tr, directory = _warm_fleet_with_directory(trained_params)
    router.dir_gap_timeout = 1.0
    prefix = list(range(1, 2 * PAGE + 1))
    prompts = [prefix + [40 + i] for i in range(4)]
    # sever nothing, lose nothing — run warm first
    reqs = FleetSimulator(router).run(_arrivals(prompts[:2], max_new=4,
                                                spacing=3.0))
    assert all(r.state is FleetState.DONE for r in reqs)
    assert router.stats["publish_gaps"] == 0
    # now eat exactly the next dir_publish message from the warm replica
    warm_rid = reqs[0].dispatches[0][0]
    real_send = tr.send
    eaten = []

    def eat_one_publish(kind, src, dst, payload, seq=0):
        if kind == "dir_publish" and src == warm_rid and not eaten:
            eaten.append((seq, payload))
            tr._count("dropped")
            return None
        return real_send(kind, src, dst, payload, seq=seq)

    tr.send = eat_one_publish
    # BOTH follow-ups mint a NEW full page on the warm replica: the first
    # one's publish is eaten, the second's arrives with a later seq — the
    # gap is thereby detectable (a lost FINAL publish with no successor is
    # pure tail silence; the post-rejoin/periodic resyncs cover that case)
    reqs2 = FleetSimulator(router).run(
        [dict(prompt=prefix + list(range(60, 60 + PAGE)) + [99],
              max_new_tokens=4, arrival_ts=0.0),
         dict(prompt=prefix + list(range(70, 70 + PAGE)) + [88],
              max_new_tokens=6, arrival_ts=8.0)])    # long enough for the gap's timeout to pass in the run
    tr.send = real_send
    assert all(r.state is FleetState.DONE for r in reqs2)
    assert eaten, "the drop hook never fired"
    assert router.stats["publish_gaps"] >= 1
    assert router.stats["dir_resyncs"] >= 1
    # post-resync: directory agrees with every live cache exactly
    for rid in pool.rids:
        pc = pool.replica(rid).serve.engine.kv.prefix_cache
        held = set(pc.held_digests())
        assert {d for d, holders in directory._holders.items()
                if rid in holders} == held


def test_duplicate_resync_reply_rejected_and_gap_clock_per_gap(trained_params):
    """Receiver-side feed hardening: (1) a duplicated resync reply (the
    first copy already applied; ``resync_since`` cleared) must not purge
    live state or rewind the sequence; (2) draining one gap that exposes
    a second restarts the gap clock — the new gap gets its own timeout."""
    router, pool, tr, directory = _warm_fleet_with_directory(trained_params)
    feed = router._dir_feeds[0]
    # in-order + buffered out-of-order publishes
    router._on_dir_publish(0, 1, {"op": "publish", "digest": 101}, now=0.0)
    router._on_dir_publish(0, 3, {"op": "publish", "digest": 103}, now=0.0)
    router._on_dir_publish(0, 7, {"op": "publish", "digest": 107}, now=0.5)
    assert feed.expect == 2 and feed.gap_since == 0.0
    router._on_dir_publish(0, 2, {"op": "publish", "digest": 102}, now=1.9)
    # 2-3 drained; the 4..6 gap just FORMED: its clock starts now
    assert feed.expect == 4 and feed.buffer == {7: ("publish", 107)}
    assert feed.gap_since == 1.9
    # a resync reply with no outstanding request is a duplicate: dropped
    assert feed.resync_since is None
    before = ({d: set(h) for d, h in directory._holders.items()}, feed.expect)
    router._on_dir_resync(0, {"digests": [999], "barrier": 1}, now=2.0)
    after = ({d: set(h) for d, h in directory._holders.items()}, feed.expect)
    assert after == before          # no purge, no ghost 999, no rewind


def test_direct_death_observation_not_double_accounted(trained_params):
    """A death the router OBSERVES (device loss on a synchronous RPC)
    folds into the lease view immediately — the later heartbeat silence
    must not declare, account, and emit the same death a second time."""
    router, pool, tr = _fleet(trained_params, 2)
    router.on_replica_dead(0, now=1.0, reason="injected device loss")
    assert router.lease.state(0) is LeaseState.DEAD
    assert router.lease.epoch[0] == 1
    pool.clock.advance(30.0)        # far past suspect_after + lease
    router.transport_poll(pool.clock.now())
    # replica 0's death stays accounted ONCE (replica 1's lease expiring
    # after 30 heartbeat-less seconds is a separate, legitimate record)
    assert sum(1 for r in router.kill_records if r["rid"] == 0) == 1
    assert router.kill_records[0]["reason"] == "injected device loss"


def test_warmup_on_recover_joins_warm(trained_params):
    """Directory-driven autoscale warm-up: a recovered replica pre-imports
    the directory's hottest chains while still RECOVERING, and its FIRST
    post-recovery dispatch of a matching prompt lands warm."""
    router, pool, tr, directory = _warm_fleet_with_directory(trained_params)
    prefix = list(range(1, 2 * PAGE + 1))
    prompts = [prefix + [40 + i] for i in range(3)]
    reqs = FleetSimulator(router).run(_arrivals(prompts, max_new=4, spacing=3.0))
    assert all(r.state is FleetState.DONE for r in reqs)
    victim = 1 - reqs[0].dispatches[0][0]   # the COLD replica dies...
    pool.kill(victim, reason="test kill")
    router.recover_replica(victim)
    # ...and rejoins WARM, before any dispatch touched it
    pc = pool.replica(victim).serve.engine.kv.prefix_cache
    assert pc.lookup_depth(prefix + [99]) == 2
    assert router.stats["warmup_imports"] >= 1
    # the first post-recovery dispatch of a matching prompt hits cache
    warm_req = router.submit(prefix + [101], max_new_tokens=4)
    # drain the lease handshake so the recovered replica is dispatchable
    reqs2 = FleetSimulator(router).run(
        [dict(prompt=prefix + [103], max_new_tokens=4, arrival_ts=4.0)])
    assert warm_req.state is FleetState.DONE
    assert warm_req.affinity_hits + sum(r.affinity_hits for r in reqs2) >= 1


# --------------------------------------------------- migration chunk channel


def test_migration_chunks_ack_retry_idempotent(trained_params):
    """Disaggregated handoff over a 30%-loss fabric: chunks flow
    stop-and-wait with cumulative acks and index-checked (idempotent)
    assembly — every migration completes through the KV-import fast path,
    outputs byte-identical, loss visible only as retransmits."""
    prompts = [list(range(1, 25)), list(range(30, 50)), [7, 8, 9]]
    golden = _factory(trained_params)().generate(prompts, max_new_tokens=8)
    clock = VirtualClock()
    tr = ControlTransport(clock, faults=LinkFaults(loss_p=0.3), seed=5)
    pool = ReplicaPool(_factory(trained_params), 2, clock=clock, transport=tr,
                       roles=("prefill", "decode"),
                       serving_config=ServingConfig(
                           step_cost=lambda t: 0.25 + 0.01 * t))
    router = Router(pool, make_policy("disaggregated"), transport=tr,
                    migration_chunk_pages=1, migration_chunk_cost=0.05,
                    lease_config=LeaseConfig(suspect_after=4.0, lease=12.0))
    reqs = FleetSimulator(router).run(_arrivals(prompts, max_new=8, spacing=1.0))
    assert [r.state for r in reqs] == [FleetState.DONE] * 3
    assert [r.tokens for r in reqs] == golden
    mig = router.summary()["migration"]
    assert mig["completed"] == 3 and mig["kv_imports"] == 3
    assert mig["fallbacks"] == 0
    assert tr.stats["retransmits"] > 0       # loss cost time, not correctness
    assert not router._mig_rx                # assembly state fully drained
