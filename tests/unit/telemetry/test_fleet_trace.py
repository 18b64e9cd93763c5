"""Fleet tracing acceptance: two same-seed FleetSimulator runs export
byte-identical Chrome traces; the client trace_id survives replica
failover (the resumed attempt links to the dead replica's span); the
trace_report critical-path fold verifies span sums against the TTFT/TPOT
accounting; and the exporter's trace validator accepts a real trace
while catching the drift classes it exists for."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import VirtualClock
from deepspeed_tpu.serving.fleet import (FleetSimulator, FleetState, ReplicaPool,
                                         Router, RoundRobinPolicy)
from deepspeed_tpu.telemetry import (Tracer, to_chrome_trace, validate_chrome_trace,
                                     write_chrome_trace)

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)

PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 4, 5, 6, 7, 8, 9], [11, 4, 4]]


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _script(name):
    path = os.path.join(REPO_ROOT, "scripts", name)
    spec = importlib.util.spec_from_file_location(name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_fleet(trained_params, schedule=None, n_replicas=2, max_new=6,
               deadline=None):
    def make():
        kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8,
                                decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1))

    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    pool = ReplicaPool(make, n_replicas, clock=clock, tracer=tracer)
    router = Router(pool, RoundRobinPolicy())
    arrivals = [dict(prompt=p, max_new_tokens=max_new,
                     arrival_ts=round(i * 0.5, 6), deadline=deadline)
                for i, p in enumerate(PROMPTS)]
    reqs = FleetSimulator(router).run(arrivals, schedule=schedule)
    return router, tracer, reqs


# ------------------------------------------------------------ determinism


def test_same_seed_fleet_runs_export_byte_identical_traces(trained_params, tmp_path):
    """ACCEPTANCE: the trace is a reproducible artifact, not a log."""
    schedule = [(3.0, "kill", 1), (8.0, "recover", 1)]
    paths = []
    for i in range(2):
        _, tracer, _ = _run_fleet(trained_params, schedule=schedule)
        p = tmp_path / f"trace{i}.json"
        write_chrome_trace(str(p), tracer.spans, dropped_spans=tracer.dropped_spans)
        paths.append(p)
    b0, b1 = paths[0].read_bytes(), paths[1].read_bytes()
    assert b0 == b1, "same seed + same schedule must serialize byte-identically"
    assert len(b0) > 500  # not trivially empty


# --------------------------------------------------------------- failover


def test_client_trace_id_survives_failover_and_links_dead_span(trained_params):
    """ACCEPTANCE: one client trace spans the killed replica AND the
    survivor; the resumed attempt names the dead attempt's span id."""
    router, tracer, reqs = _run_fleet(trained_params,
                                      schedule=[(2.0, "kill", 1)], max_new=8)
    assert [r.state for r in reqs] == [FleetState.DONE] * 4
    failed_over = [r for r in reqs if r.failovers]
    assert failed_over, "the kill must displace at least one in-flight request"
    for fr in failed_over:
        tid = fr.trace["trace_id"]
        spans = [s for s in tracer.spans if s.trace_id == tid]
        attempts = sorted([s for s in spans if s.name == "attempt"],
                          key=lambda s: s.start_ts)
        assert len(attempts) >= 2
        dead, resumed = attempts[0], attempts[-1]
        assert dead.attrs["outcome"] == "displaced"
        assert dead.track == "replica1"       # the killed replica
        assert resumed.attrs["outcome"] == "done"
        assert resumed.track != dead.track, "resume must land on a survivor"
        assert resumed.attrs["resumed_from"] == dead.span_id
        assert isinstance(resumed.attrs["resume_tokens"], int) \
            and resumed.attrs["resume_tokens"] >= 0
        # every span of the client request carries the ONE trace id, and
        # all parent to the single root
        root = next(s for s in spans if s.name == "request")
        assert root.attrs["failovers"] == fr.failovers
        for s in spans:
            if s is not root:
                assert s.parent_id in {root.span_id} | {a.span_id for a in attempts}
        # phases tile across the displacement: dead attempt's partial
        # phases + pending gap + survivor phases == e2e
        phase_sum = sum(s.duration for s in spans if s.name.startswith("phase/"))
        assert abs(phase_sum - root.attrs["e2e"]) < 1e-6
        # failover is visible as a root span event
        assert any(n == "failover" for n, _, _ in root.events)
    # the kill landed mid-decode: at least one resume carried tokens
    # forward (the recompute-on-resume contract the link documents)
    resumed_tokens = []
    for fr in failed_over:
        tid = fr.trace["trace_id"]
        for s in tracer.spans:
            if s.trace_id == tid and s.name == "attempt" \
                    and "resumed_from" in s.attrs:
                resumed_tokens.append(s.attrs["resume_tokens"])
    assert any(n > 0 for n in resumed_tokens), resumed_tokens


def test_kill_after_finish_before_poll_does_not_duplicate_phase_spans(trained_params):
    """A wall-clock driver can deliver a death notice AFTER a request's
    finishing tick but BEFORE the router polls.  The replica frontend
    already emitted the attempt's phase spans at _finish; the failover
    path must not fold the terminal history a second time (span_sum would
    double and trace_report would reject a correct run)."""
    from deepspeed_tpu.serving.request import RequestState

    def make():
        kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8,
                                decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1))

    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    pool = ReplicaPool(make, 2, clock=clock, tracer=tracer)
    router = Router(pool, RoundRobinPolicy())
    fr = router.submit(PROMPTS[0], max_new_tokens=4)
    router.dispatch_pending()
    rid = fr._current[0]
    for _ in range(60):
        pool.tick(rid)
        cost = pool.replica(rid).clock.take_cost()
        if cost:
            clock.advance(cost)
        if fr._current[1].state is RequestState.DONE:
            break
    assert fr._current[1].state is RequestState.DONE, "request must finish on-replica"
    sr_finish = fr._current[1].finish_ts
    router.kill_replica(rid)        # death notice lands before poll ran
    assert fr.state is FleetState.DONE, \
        "an already-finished request resolves at the death notice"
    assert fr.failovers == 0, "finishing before the kill is not a failover"
    assert fr.finish_ts == sr_finish, "replica-side finish time is kept"
    root = next(s for s in tracer.spans
                if s.trace_id == fr.trace["trace_id"] and s.name == "request")
    phases = [s for s in tracer.spans
              if s.trace_id == fr.trace["trace_id"] and s.name.startswith("phase/")]
    span_sum = sum(s.duration for s in phases)
    assert abs(span_sum - root.attrs["e2e"]) < 1e-6, \
        (span_sum, root.attrs["e2e"], [(s.name, s.start_ts, s.end_ts) for s in phases])
    keys = [(s.name, s.start_ts, s.end_ts) for s in phases]
    assert len(keys) == len(set(keys)), f"duplicated phase spans: {keys}"


def test_router_rejects_tracer_the_pool_does_not_share(trained_params):
    """A router-only tracer would produce attempt spans with no phase
    children (the replica frontends trace nothing) — a half-instrumented
    trace that fails the tiling invariant; refuse it at construction."""
    def make():
        kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8,
                                decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1))

    clock = VirtualClock()
    pool = ReplicaPool(make, 1, clock=clock)          # no tracer
    with pytest.raises(ValueError, match="ReplicaPool"):
        Router(pool, RoundRobinPolicy(), tracer=Tracer(clock=clock))
    # an explicitly-DISABLED tracer means "tracing off", same as None
    from deepspeed_tpu.telemetry import NULL_TRACER
    assert Router(pool, RoundRobinPolicy(), tracer=NULL_TRACER).tracer is NULL_TRACER
    # passing the POOL's tracer explicitly stays legal (and redundant)
    tracer = Tracer(clock=clock)
    pool2 = ReplicaPool(make, 1, clock=clock, tracer=tracer)
    assert Router(pool2, RoundRobinPolicy(), tracer=tracer).tracer is tracer


# ------------------------------------------------------------ trace_report


def test_trace_report_folds_and_verifies(trained_params):
    router, tracer, reqs = _run_fleet(trained_params,
                                      schedule=[(2.0, "kill", 1)], max_new=8)
    doc = to_chrome_trace(tracer.spans)
    report = _script("trace_report.py").fold(doc, tol=1e-6)
    assert report["n_requests"] == 4
    assert report["verification"]["mismatches"] == 0
    assert report["verification"]["checked"] == 4
    assert report["failovers"] == sum(r.failovers for r in reqs) > 0
    cp = report["critical_path"]
    assert cp["decode"]["total_s"] > 0
    assert 0.999 < sum(v["fraction"] for v in cp.values()) < 1.001
    # displaced requests' re-queue time is attributed as retry cost
    assert report["retry_queue_s"] >= 0
    total = sum(v["total_s"] for v in cp.values())
    assert abs(total - report["total_span_s"]) < 1e-6


def test_replica_timeout_trace_tiles_at_the_replica_stamp(trained_params):
    """Regression: a request that TIMED_OUT on a replica closes its
    attempt and root at the REPLICA-side timeout instant, not at the
    poll-time now one round later — phases must still tile, and the
    fold must pass on a trace containing timeouts."""
    router, tracer, reqs = _run_fleet(trained_params, n_replicas=1,
                                      max_new=20, deadline=3.0)
    timed_out = [r for r in reqs if r.state is FleetState.TIMED_OUT]
    assert timed_out, "deadline=3.0 with 20-token outputs must time out"
    for fr in timed_out:
        tid = fr.trace["trace_id"]
        spans = [s for s in tracer.spans if s.trace_id == tid]
        root = next(s for s in spans if s.name == "request")
        assert root.attrs["state"] == "timed_out"
        phase_sum = sum(s.duration for s in spans if s.name.startswith("phase/"))
        assert abs(phase_sum - root.duration) < 1e-6, \
            (phase_sum, root.duration, fr.fid)
    report = _script("trace_report.py").fold(to_chrome_trace(tracer.spans),
                                             tol=1e-6)
    assert report["verification"]["mismatches"] == 0
    assert report["states"].get("timed_out", 0) == len(timed_out)


def test_split_brain_trace_tiles_with_fenced_phase(trained_params):
    """Regression (r17 lease-aware tracing): a lease-expired attempt's
    replica-side phase spans are folded at displacement with the open
    tail attributed to ``phase/fenced`` — time served outside the lease
    and discarded by the fence — so a transport-mode split-brain trace
    tiles [arrival, terminal] and the fold's verify passes, instead of
    under-tiling by the whole zombie attempt window."""
    from deepspeed_tpu.serving.fleet import (ControlTransport, LeaseConfig,
                                             LeastOutstandingPolicy,
                                             PartitionWindow)

    def make():
        kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
        sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8,
                                decode_bucket=4)
        return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=1))

    clock = VirtualClock()
    tracer = Tracer(clock=clock)
    transport = ControlTransport(clock, partitions=[
        PartitionWindow("splitbrain", 6.0, 30.0, (("router", 0),))])
    pool = ReplicaPool(make, 2, clock=clock, transport=transport,
                       tracer=tracer)
    router = Router(pool, LeastOutstandingPolicy(), transport=transport,
                    lease_config=LeaseConfig(suspect_after=2.0, lease=6.0))
    arrivals = [dict(prompt=PROMPTS[0], max_new_tokens=16, arrival_ts=0.0),
                # a trailing arrival past the heal keeps the simulation
                # alive through the fence handshake
                dict(prompt=PROMPTS[1], max_new_tokens=16, arrival_ts=34.0)]
    reqs = FleetSimulator(router).run(arrivals)
    assert [r.state for r in reqs] == [FleetState.DONE] * 2
    assert reqs[0].failovers == 1
    assert router.summary()["control_plane"]["lease_expirations"] == 1
    report = _script("trace_report.py").fold(to_chrome_trace(tracer.spans),
                                             tol=1e-6)
    assert report["verification"]["mismatches"] == 0, \
        report["verification"]
    assert report["n_requests"] == 2
    # the displaced attempt's post-sync window landed in the new phase
    assert report["critical_path"]["fenced"]["total_s"] > 0


def test_trace_report_flags_unaccounted_time(trained_params):
    _, tracer, _ = _run_fleet(trained_params)
    doc = to_chrome_trace(tracer.spans)
    # sabotage: shrink one decode phase — the spans no longer account for
    # the recorded latency and the fold must say so
    victim = next(e for e in doc["traceEvents"]
                  if e.get("ph") == "X" and e["name"] == "phase/decode")
    victim["dur"] -= 1e6
    report = _script("trace_report.py").fold(doc, tol=1e-6)
    assert report["verification"]["mismatches"] == 1
    assert report["verification"]["worst_residual"] > 0.9


# ---------------------------------------------------------- schema checker


def test_schema_validator_accepts_real_trace_and_catches_drift(trained_params):
    _, tracer, _ = _run_fleet(trained_params, schedule=[(2.0, "kill", 1)])
    doc = to_chrome_trace(tracer.spans, dropped_spans=tracer.dropped_spans)
    assert validate_chrome_trace(doc) is None

    def broken(mutate):
        d = json.loads(json.dumps(doc))
        mutate(d)
        return validate_chrome_trace(d)

    # span whose parent does not exist
    def orphan(d):
        e = next(e for e in d["traceEvents"]
                 if e.get("ph") == "X" and "parent_id" in e["args"])
        e["args"]["parent_id"] = 999_999
    assert "does not exist" in broken(orphan)

    # serving root closed non-terminal
    def non_terminal(d):
        e = next(e for e in d["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "request")
        e["args"]["state"] = "decode"
    assert "non-terminal" in broken(non_terminal)

    # per-track timestamps going backwards
    def backwards(d):
        xs = [e for e in d["traceEvents"] if e.get("ph") == "X"]
        tid = xs[0]["tid"]
        same = [e for e in xs if e["tid"] == tid]
        assert len(same) >= 2
        same[-1]["ts"] = same[0]["ts"] - 1000.0
    assert "BACKWARDS" in broken(backwards)

    # negative duration
    def neg_dur(d):
        next(e for e in d["traceEvents"] if e.get("ph") == "X")["dur"] = -1.0
    assert "bad dur" in broken(neg_dur)

    # not a trace at all
    assert validate_chrome_trace({"hello": 1}) is not None
