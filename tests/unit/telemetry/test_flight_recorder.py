"""Flight-recorder + SLO burn-rate + slowdown-attribution acceptance
(telemetry/flight_recorder.py, telemetry/slo.py, scripts/why_slow.py):
the bounded always-on ring retains under a hard cap and dumps a valid
crash-scoped Chrome trace on fencing; multi-window burn-rate alerts fire
and clear deterministically under the r14 flash-crowd generator, only
inside the injected degradation; a split-brain run's displaced request
has its tail attributed to ``lease_expiry`` + ``fenced`` by why_slow's
fold (which tiles every request's e2e within 1e-6, exit 1 on sabotage);
and ``why_slow.py --json`` is byte-identical across repeat CLI runs."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import VirtualClock
from deepspeed_tpu.serving.fleet import (ControlTransport, FleetSimulator,
                                         FleetState, LeaseConfig, LinkFaults,
                                         PartitionWindow, ReplicaPool, Router,
                                         TenantRegistry, TenantSpec,
                                         flash_crowd_arrivals, make_policy)
from deepspeed_tpu.telemetry import (BurnRateConfig, FlightRecorder,
                                     MetricsRegistry, SLOBurnMonitor, Tracer,
                                     load_chrome_trace, to_chrome_trace,
                                     write_chrome_trace)

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
WHY_SLOW = os.path.join(REPO_ROOT, "scripts", "why_slow.py")

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True,
                  remat=False)

PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9], [11, 4, 4]]


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _factory(trained_params, max_seqs=8):
    def make():
        kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=max_seqs,
                                prefill_chunk=8, decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=1))
    return make


def _why_slow():
    spec = importlib.util.spec_from_file_location("why_slow", WHY_SLOW)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------- ring semantics


def test_ring_bound_and_dropped_counts():
    """The always-on contract: O(tracks x N) memory forever — the ring
    holds at most max_per_track spans per track and COUNTS what it
    evicted instead of hiding it."""
    rec = FlightRecorder(max_per_track=4)
    for i in range(10):
        rec.span("ctrl/heartbeat", "ctrl/link/router-0", float(i), i + 0.5)
    assert len(rec.track("ctrl/link/router-0")) == 4
    assert rec.dropped["ctrl/link/router-0"] == 6
    assert rec.n_spans == 4
    # the retained spans are the NEWEST four (a black box records the
    # moments before the crash, not the takeoff)
    assert [s.start_ts for s in rec.track("ctrl/link/router-0")] == \
        [6.0, 7.0, 8.0, 9.0]
    assert rec.summary()["dropped"] == {"ctrl/link/router-0": 6}
    with pytest.raises(ValueError):
        FlightRecorder(max_per_track=0)


def test_note_state_intervals_tile_and_same_state_is_noop():
    rec = FlightRecorder(max_per_track=16)
    rec.note_state("ctrl/lease/replica/0", "ctrl/lease/alive", 0.0)
    rec.note_state("ctrl/lease/replica/0", "ctrl/lease/alive", 1.0)  # no-op
    rec.note_state("ctrl/lease/replica/0", "ctrl/lease/suspect", 2.0,
                   attrs={"reason": "hb_gap"})
    rec.note_state("ctrl/lease/replica/0", "ctrl/lease/dead", 3.5)
    # two closed intervals in the ring; the third is open
    closed = rec.track("ctrl/lease/replica/0")
    assert [(s.name, s.start_ts, s.end_ts) for s in closed] == \
        [("ctrl/lease/alive", 0.0, 2.0), ("ctrl/lease/suspect", 2.0, 3.5)]
    # snapshot closes the open interval at `now` WITHOUT mutating it
    snap = rec.snapshot_spans(now=5.0)
    opens = [s for s in snap if s.attrs and s.attrs.get("open")]
    assert [(s.name, s.start_ts, s.end_ts) for s in opens] == \
        [("ctrl/lease/dead", 3.5, 5.0)]
    assert rec.summary()["open"] == {"ctrl/lease/replica/0": "ctrl/lease/dead"}
    # intervals tile: no gaps between consecutive retained intervals
    for a, b in zip(closed, closed[1:]):
        assert a.end_ts == b.start_ts


def test_failed_dump_does_not_inflate_count(tmp_path):
    """Regression: the dump counter moves only once the file exists, so a
    failed write cannot desync the cumulative ``recorder/dump`` event
    value from the dumps actually on disk."""
    blocked = tmp_path / "not_a_dir"
    blocked.write_text("")
    rec = FlightRecorder(max_per_track=4, dump_dir=str(blocked / "sub"))
    rec.instant("ctrl/fence", "ctrl/replica0", ts=1.0)
    with pytest.raises(OSError):
        rec.maybe_dump("fence", now=2.0)
    assert rec.dumps == 0 and rec.dump_log == []
    rec.dump_dir = str(tmp_path)
    assert rec.maybe_dump("fence", now=3.0).endswith("flight_001_fence.json")
    assert rec.dumps == 1


def test_dump_writes_valid_chrome_trace_and_ring_only_mode(tmp_path):
    rec = FlightRecorder(max_per_track=8)
    rec.instant("ctrl/fence", "ctrl/replica0", ts=1.0, attrs={"queued": 2})
    assert rec.maybe_dump("fence", now=2.0) is None  # ring-only: no files
    # a not-yet-created dump_dir is made on first dump (a black box that
    # silently can't write is worse than none)
    rec2 = FlightRecorder(max_per_track=8,
                          dump_dir=str(tmp_path / "flights" / "sub"))
    rec2.span("ctrl/heartbeat", "ctrl/link/router-0", 0.0, 0.4)
    rec2.note_state("ctrl/overload", "ctrl/overload/normal", 0.0)
    path = rec2.maybe_dump("lease expired!", now=3.0)
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path) == "flight_001_lease_expired_.json"
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["reason"] == "lease expired!"
    assert doc["otherData"]["dump_seq"] == 1
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"ctrl/heartbeat", "ctrl/overload/normal"} <= names
    # the dump round-trips through the standard loader
    assert load_chrome_trace(path) == doc
    assert len([e for e in doc["traceEvents"] if e.get("ph") == "X"]) == 2
    assert rec2.dump_log == [("lease expired!", 3.0, path)]


def test_link_loss_ewma_counts_deliver_side_drops():
    """Regression: the adaptive-lease-sizing signal resolves each message
    exactly once, at the point its fate is known — a partition that opens
    while a message is mid-flight (or a deliver fault) counts as loss, so
    a link whose sends depart fine but whose deliveries all die cannot
    read 0.0."""
    clock = VirtualClock()
    t = ControlTransport(clock, faults=LinkFaults(delay=0.5), partitions=[
        PartitionWindow("cut", 0.1, 100.0, (("router", 0),))])
    assert t.send("heartbeat", "router", 0, {}) is not None  # departed at 0
    clock.advance(1.0)
    assert t.deliver() == []                 # ...and died inside the cut
    assert t.link_loss_ewma("router", 0) == pytest.approx(0.2)
    assert t.summary()["links"]["0-router"] == \
        {"resolved": 1, "eaten": 1, "loss_ewma": 0.2}
    # a clean delivery resolves as success on ITS link
    t.send("heartbeat", "router", 1, {})
    clock.advance(1.0)
    assert len(t.deliver()) == 1
    assert t.link_loss_ewma("router", 1) == 0.0


# ------------------------------------------------- burn-rate alert logic


def _mon(**cfg):
    tenants = TenantRegistry([TenantSpec("prem", ttft_slo=1.0)])
    events = []
    mon = SLOBurnMonitor(
        tenants,
        BurnRateConfig(**{"fast_window": 4.0, "slow_window": 16.0,
                          "min_requests": 2, "sub_buckets": 4, **cfg}),
        emit=lambda name, value: events.append(name))
    return mon, events


def test_burn_rate_fires_on_both_windows_and_clears_with_hysteresis():
    mon, events = _mon()
    # a healthy stretch first: the slow window must carry real evidence
    for i in range(8):
        mon.observe("prem", 0.5, now=0.5 * i)  # good TTFTs
    mon.tick(now=4.0)
    assert not mon.active("prem") and events == []
    # onset: every request violates — fast burns hot immediately, but the
    # alert needs the SLOW window hot too (one spike cannot page)
    for i in range(8):
        mon.observe("prem", 3.0, now=4.0 + 0.5 * i)
    mon.tick(now=8.0)
    assert mon.active("prem")
    assert events == ["slo/alert_fired/prem"]
    fired = mon.alerts[-1]
    assert fired["cleared_ts"] is None and fired["fired_fast"] >= 1.0
    # recovery: good requests flush the FAST window; the alert clears even
    # though the slow window still remembers the bad stretch (hysteresis
    # is on the fast window only — recovery visible within one window)
    for i in range(10):
        mon.observe("prem", 0.4, now=8.5 + 0.5 * i)
    mon.tick(now=14.0)
    assert not mon.active("prem")
    assert events == ["slo/alert_fired/prem", "slo/alert_cleared/prem"]
    assert mon.alerts[-1]["cleared_ts"] == 14.0


def test_min_requests_evidence_gate_and_slo_less_tenants_ignored():
    mon, events = _mon(min_requests=4)
    # one terrible request is not evidence — an empty fleet cannot page
    mon.observe("prem", 99.0, now=0.1)
    mon.tick(now=0.2)
    assert not mon.active("prem") and events == []
    assert mon.burn_rates("prem", now=0.2) == (0.0, 0.0)
    # tenants without a ttft_slo never enter the monitor at all
    mon.observe("walkup", 99.0, now=0.3)
    assert "walkup" not in mon.summary()["tenants"]
    assert mon.observed == 1


def test_burn_config_validation():
    with pytest.raises(ValueError):
        BurnRateConfig(fast_window=8.0, slow_window=8.0)
    with pytest.raises(ValueError):
        BurnRateConfig(clear_threshold=1.0, fire_threshold=1.0)
    with pytest.raises(ValueError):
        BurnRateConfig(sub_buckets=1)
    with pytest.raises(ValueError):
        TenantSpec("t", error_budget=0.0)


# ---------------------------------------- flash-crowd alert determinism


def _flash_crowd_run(trained_params, dump_dir=None):
    """A premium tenant with a tight TTFT SLO over a 2-replica fleet hit
    by the r14 flash-crowd generator: the crowd window is the injected
    degradation, and the burn-rate alert must fire inside it (violations
    are observed at completion, so 'inside' includes the queue drain)."""
    clock = VirtualClock()
    recorder = FlightRecorder(clock=clock, max_per_track=256,
                              dump_dir=dump_dir)
    tracer = Tracer(clock=clock)
    pool = ReplicaPool(_factory(trained_params), 2, clock=clock,
                       tracer=tracer, metrics=MetricsRegistry())
    tenants = TenantRegistry([TenantSpec("prem", weight=2.0, ttft_slo=2.0,
                                         error_budget=0.1),
                              TenantSpec("bulk", weight=1.0)])
    slo = SLOBurnMonitor(tenants, BurnRateConfig(
        fast_window=4.0, slow_window=16.0, min_requests=3, sub_buckets=4))
    router = Router(pool, make_policy("least_outstanding"), tenants=tenants,
                    recorder=recorder, slo=slo)
    crowd = {"crowd_start": 4.0, "crowd_duration": 4.0}
    arrivals = flash_crowd_arrivals(
        seed=7, n_requests=36, base_rate=0.4, crowd_rate=10.0,
        vocab=CFG.vocab_size, tenants=[("prem", 0.5, None),
                                       ("bulk", 0.5, None)], **crowd)
    reqs = FleetSimulator(router).run(arrivals)
    assert all(r.state is FleetState.DONE for r in reqs)
    return slo.summary(), router.summary(), crowd, recorder


def test_flash_crowd_alert_fires_in_window_clears_after_and_repeats(
        trained_params):
    sum1, rsum1, crowd, _ = _flash_crowd_run(trained_params)
    sum2, rsum2, _, _ = _flash_crowd_run(trained_params)
    # determinism: the whole alert timeline (fire/clear instants, burn
    # rates at firing) is identical across same-seed runs
    assert sum1 == sum2
    assert rsum1 == rsum2
    alerts = sum1["alerts"]
    assert alerts, "the flash crowd never tripped the burn-rate monitor"
    t0 = crowd["crowd_start"]
    # violations surface at COMPLETION time: the window closes after the
    # crowd's queue drains, bounded well under the run's tail
    t1 = t0 + crowd["crowd_duration"] + 12.0
    for a in alerts:
        assert a["tenant"] == "prem"  # bulk carries no ttft_slo
        assert t0 <= a["fired_ts"] <= t1, (a, crowd)
        assert a["cleared_ts"] is not None and a["cleared_ts"] > a["fired_ts"]
    assert sum1["active"] == []  # nothing left firing at drain


# ------------------------------- split brain: attribution + dump-on-fence


@pytest.fixture(scope="module")
def split_brain(trained_params, tmp_path_factory):
    """One split-brain run shared by the attribution, dump and CLI tests:
    a partition severs replica 0 mid-request, its lease expires (dump 1),
    the displaced request re-homes onto a SATURATED replica 1 (filler
    arrivals keep its 2 slots + 1-deep admission queue full, so the
    victim's re-home wait is a real ``phase/pending`` stretch), and the
    fence handshake completes on heal (dump 2)."""
    from deepspeed_tpu.serving.admission import AdmissionConfig
    from deepspeed_tpu.serving.engine import ServingConfig

    dump_dir = str(tmp_path_factory.mktemp("flight"))
    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    recorder = FlightRecorder(clock=clock, max_per_track=64,
                              dump_dir=dump_dir)
    transport = ControlTransport(clock, faults=LinkFaults(loss_p=0.02),
                                 seed=3, partitions=[
        PartitionWindow("splitbrain", 6.0, 30.0, (("router", 0),))])
    pool = ReplicaPool(_factory(trained_params, max_seqs=2), 2, clock=clock,
                       transport=transport, tracer=tracer,
                       metrics=MetricsRegistry(),
                       serving_config=ServingConfig(
                           admission=AdmissionConfig(max_queue_depth=1)))
    router = Router(pool, make_policy("least_outstanding"),
                    transport=transport, recorder=recorder,
                    lease_config=LeaseConfig(suspect_after=2.0, lease=6.0))
    arrivals = [dict(prompt=PROMPTS[0], max_new_tokens=16, arrival_ts=0.0)]
    # fillers arrive after the partition opens: only replica 1 can admit
    # them, so its slots are full when the victim is displaced at expiry
    arrivals += [dict(prompt=PROMPTS[1 + i % 3], max_new_tokens=20,
                      arrival_ts=6.5 + 0.1 * i) for i in range(4)]
    arrivals += [dict(prompt=PROMPTS[1], max_new_tokens=16, arrival_ts=34.0)]
    reqs = FleetSimulator(router).run(arrivals)
    assert all(r.state is FleetState.DONE for r in reqs)
    assert reqs[0].failovers == 1
    assert all(r.failovers == 0 for r in reqs[1:])
    assert router.summary()["control_plane"]["lease_expirations"] == 1
    doc = to_chrome_trace(tracer.spans, dropped_spans=tracer.dropped_spans)
    return doc, recorder, router, dump_dir


def test_split_brain_why_slow_attributes_lease_expiry_and_fenced(split_brain):
    """The displaced request's tail is NAMED: its post-displacement
    re-home wait is ``lease_expiry``, the zombie window served outside
    the lease is ``fenced`` — and the causes still tile its e2e."""
    doc, _, _, _ = split_brain
    report = _why_slow().fold(doc, tol=1e-6)
    assert report["verification"]["mismatches"] == 0, report["verification"]
    assert report["n_requests"] == 6
    displaced = next(r for r in report["requests"] if r["failovers"] == 1)
    assert displaced["causes"]["lease_expiry"] > 0, displaced["causes"]
    assert displaced["causes"]["fenced"] > 0, displaced["causes"]
    # ... and the undisplaced requests carry neither cause
    for clean in (r for r in report["requests"] if r["failovers"] == 0):
        assert clean["causes"]["lease_expiry"] == 0
        assert clean["causes"]["fenced"] == 0
    # aggregate surface names both causes too
    assert report["causes"]["lease_expiry"]["total_s"] > 0
    assert report["causes"]["fenced"]["total_s"] > 0


def test_flight_recorder_dumps_on_fence_with_bounded_memory(split_brain):
    doc, recorder, router, dump_dir = split_brain
    reasons = [r for r, _, _ in recorder.dump_log]
    assert "lease_expired" in reasons, reasons
    assert "fence" in reasons, reasons
    files = sorted(os.listdir(dump_dir))
    assert len(files) == recorder.dumps == len(reasons)
    # every dump is a loadable Chrome trace whose control tracks tell the
    # episode's story: lease lifecycle intervals + transport message spans
    fence_dump = os.path.join(
        dump_dir, next(f for f in files if "fence" in f and "lease" not in f))
    with open(fence_dump) as f:
        dumped = json.load(f)
    tracks = dumped["otherData"]["tracks"]
    assert any(t.startswith("ctrl/lease/replica/") for t in tracks), tracks
    assert any(t.startswith("ctrl/link/") for t in tracks), tracks
    tid_of = {e["args"]["name"]: e["tid"] for e in dumped["traceEvents"]
              if e.get("ph") == "M"}
    lease_states = [e["name"] for e in dumped["traceEvents"]
                    if e.get("ph") == "X"
                    and e["tid"] == tid_of["ctrl/lease/replica/0"]]
    # the fenced replica's full lifecycle is visible in the black box
    assert "ctrl/lease/suspect" in lease_states, lease_states
    assert "ctrl/lease/dead" in lease_states, lease_states
    # bounded memory: no track ever exceeds the cap, and the router
    # summary carries the recorder receipt
    assert all(len(recorder.track(t)) <= recorder.max_per_track
               for t in recorder.summary()["tracks"])
    assert router.summary()["recorder"]["dumps"] == recorder.dumps


def test_why_slow_cli_byte_identical_and_sabotage_exit1(split_brain, tmp_path):
    doc, _, _, _ = split_brain
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps(doc))
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, WHY_SLOW, str(trace), "--json"],
                           capture_output=True)
        assert r.returncode == 0, r.stderr.decode()
        outs.append(r.stdout)
    assert outs[0] == outs[1]  # byte-identical repeat runs
    # sabotage: shrink one decode phase — the causes no longer tile that
    # request's e2e and the CLI must exit 1 (trace_report discipline)
    broken = json.loads(json.dumps(doc))
    victim = next(e for e in broken["traceEvents"]
                  if e.get("ph") == "X" and e["name"] == "phase/decode")
    victim["dur"] -= 2e6
    bad = tmp_path / "broken.json"
    bad.write_text(json.dumps(broken))
    r = subprocess.run([sys.executable, WHY_SLOW, str(bad), "--json"],
                       capture_output=True)
    assert r.returncode == 1
    assert b"MISMATCH" in r.stderr
    # ... unless the trace DECLARES span eviction (a flight-recorder dump
    # under ring pressure): then a residual is indistinguishable from
    # truncation — reported as possibly_truncated, warned, exit 0
    broken["otherData"]["dropped_spans"] = 3
    partial = tmp_path / "partial.json"
    partial.write_text(json.dumps(broken))
    r = subprocess.run([sys.executable, WHY_SLOW, str(partial), "--json"],
                       capture_output=True)
    assert r.returncode == 0, r.stderr.decode()
    assert b"dropped spans" in r.stderr
    ver = json.loads(r.stdout)["verification"]
    assert ver["partial_trace"] and ver["possibly_truncated"] == 1 \
        and ver["mismatches"] == 0


def test_recorder_without_tracer_still_records_replica_fence(trained_params):
    """Regression: the replica-side ``ctrl/fence`` instant is recorded via
    the engine's DIRECT recorder attachment, so the headline always-on
    configuration (recorder on, full tracing off) keeps both halves of the
    fencing episode in the dump."""
    clock = VirtualClock()
    recorder = FlightRecorder(clock=clock, max_per_track=64)
    transport = ControlTransport(clock, partitions=[
        PartitionWindow("cut", 6.0, 30.0, (("router", 0),))])
    pool = ReplicaPool(_factory(trained_params), 2, clock=clock,
                       transport=transport)  # NO tracer
    router = Router(pool, make_policy("least_outstanding"),
                    transport=transport, recorder=recorder,
                    lease_config=LeaseConfig(suspect_after=2.0, lease=6.0))
    arrivals = [dict(prompt=PROMPTS[0], max_new_tokens=16, arrival_ts=0.0),
                dict(prompt=PROMPTS[1], max_new_tokens=16, arrival_ts=34.0)]
    reqs = FleetSimulator(router).run(arrivals)
    assert all(r.state is FleetState.DONE for r in reqs)
    assert router.summary()["control_plane"]["lease_expirations"] == 1
    fences = recorder.track("ctrl/replica0")
    assert [s.name for s in fences] == ["ctrl/fence"], recorder.summary()
    assert sorted(fences[0].attrs) == ["active", "parked", "queued"]
    # ...and a replacement engine (the recover()/restart() path) inherits
    # the attachment like it inherits the tracer
    pool._attach_engine(0)
    assert pool.replica(0).serve.recorder is recorder


# ------------------------------------------- per-link transport gauges


def test_transport_link_gauges_exported_once_per_round(split_brain):
    """Satellite: the once-per-round observability sweep publishes the
    per-link health gauges — ROADMAP's adaptive-lease-sizing input."""
    _, _, router, _ = split_brain
    snap = router.pool.metrics.snapshot()
    for rid in router.pool.rids:
        assert f"transport/link_loss_ewma/{rid}" in snap, sorted(snap)
        assert f"transport/feed_gap_age/{rid}" in snap
    assert "transport/retransmit_depth" in snap
    # the partitioned link observed real loss; the healthy one stayed
    # clean or near-clean (random loss_p=0.02 may nick it)
    assert router.transport.link_loss_ewma("router", 0) > 0.0
    links = router.transport.summary()["links"]
    assert links["0-router"]["eaten"] > 0
