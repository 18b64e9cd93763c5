"""Async double-buffered serving dispatch + AOT step set (r20):
``warm_all`` closes the compile set up front; ``ServingConfig(
async_dispatch=True)`` runs step g+1's host work while step g is in
flight, byte-identical to the serial loop — under forced KV-pressure
preemption, spec-on and spec-off, and a chaos crash mid-pipeline (tokens
never half-applied); ``engine.aot_compile`` faults fall back to lazy JIT
instead of a dead replica; and a recovered fleet replica's first request
pays zero compiles (the ``warm_all``-on-recover regression pin)."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        SpecConfig, build_engine)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.resilience.fault_injection import (
    INJECTION_SITES, InjectedCrash, configure_fault_injection)
from deepspeed_tpu.serving import (RequestState, ServingConfig, ServingEngine,
                                   VirtualClock, WallClock)
from deepspeed_tpu.telemetry import StepAnatomy

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True,
                  remat=False)

PAGE = 8


@pytest.fixture(scope="module")
def trained_params():
    return LlamaForCausalLM(CFG).init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), jnp.int32))


def _engine(trained_params, num_pages=64, max_pages=8, spec=None):
    kv = PagedKVConfig(num_pages=num_pages, page_size=PAGE,
                       max_pages_per_seq=max_pages)
    sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8,
                            decode_bucket=4)
    return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched, kv_dtype=jnp.float32,
        decode_steps_per_dispatch=1, spec=spec))


# the repetitive prompt reliably engages the n-gram drafter
PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 1, 2, 3, 1, 2],
           [11, 4, 6, 2], [9, 1, 4, 9, 1, 4, 9], [2, 8, 2, 8, 2],
           [7, 7, 5, 1], [6, 2, 6, 2, 6, 2]]


# --------------------------------------------------------- AOT step set


def test_warm_all_closes_the_step_set(trained_params):
    """``warm_all`` AOT-compiles every key ``step_shape_set`` enumerates;
    serving after it pays ZERO lazy compiles (the compile log holds only
    deliberate ``aot`` entries and no steady-state recompile fires)."""
    eng = _engine(trained_params, spec=SpecConfig(max_draft=4))
    clock = VirtualClock()
    anat = eng.set_anatomy(StepAnatomy(clock=clock))
    res = eng.warm_all()
    assert res["fallback"] == 0 and res["cached"] == 0
    assert res["compiled"] == len(res["keys"]) == len(eng.step_shape_set())
    # decode_bucket rungs alone and beside each prefill rung (1, 4, max_seqs
    # rows at prefill_chunk) + one verify width
    assert set(res["keys"]) == {
        "step:b4:c1", "step:b8:c1",
        "step:b4:c1:b1:c8", "step:b4:c1:b4:c8", "step:b4:c1:b8:c8",
        "step:b8:c1:b1:c8", "step:b8:c1:b4:c8", "step:b8:c1:b8:c8",
        "verify:b4:w5", "verify:b8:w5"}
    assert all(c.aot for c in anat.compiles)
    anat.mark_steady()
    # a second call is a pure cache hit
    res2 = eng.warm_all()
    assert res2["compiled"] == 0 and res2["cached"] == len(res["keys"])
    serve = ServingEngine(eng, clock=clock, config=ServingConfig())
    reqs = serve.run([dict(prompt=p, max_new_tokens=8, arrival_ts=0.0)
                      for p in PROMPTS])
    assert all(r.state is RequestState.DONE for r in reqs)
    assert eng.spec_stats.rounds > 0          # speculation genuinely ran
    assert anat.steady_state_recompiles == 0
    assert sum(r.compiles for r in anat.steps) == 0


def test_aot_fault_falls_back_to_lazy_jit(trained_params):
    """``engine.aot_compile`` is an armable chaos site: transient I/O and
    device-loss faults during ``warm_all`` leave the key on the lazy JIT
    path (slower first dispatch, never a dead engine); only
    ``InjectedCrash`` — simulated process death — propagates."""
    assert "engine.aot_compile" in INJECTION_SITES
    eng = _engine(trained_params)
    configure_fault_injection({"seed": 0, "sites": [
        {"site": "engine.aot_compile", "kind": "os_error", "at": 1},
        {"site": "engine.aot_compile", "kind": "device_loss", "at": 3}]})
    try:
        res = eng.warm_all()
    finally:
        configure_fault_injection(None)
    assert res["fallback"] == 2
    assert res["compiled"] == len(res["keys"]) - 2
    # NOT dead: the missed keys compile lazily and outputs are unchanged
    outs = eng.generate(PROMPTS[:4], max_new_tokens=6)
    assert outs == _engine(trained_params).generate(PROMPTS[:4],
                                                    max_new_tokens=6)
    res2 = eng.warm_all()                     # re-warm closes the set
    assert res2["fallback"] == 0
    assert res2["compiled"] + res2["cached"] == len(res2["keys"])

    eng2 = _engine(trained_params)
    configure_fault_injection({"seed": 0, "sites": [
        {"site": "engine.aot_compile", "kind": "crash", "at": 1}]})
    try:
        with pytest.raises(InjectedCrash):
            eng2.warm_all()
    finally:
        configure_fault_injection(None)


# ------------------------------------------------- serial/async parity


def _serve_once(trained_params, async_dispatch, spec, num_pages,
                max_new_tokens=20):
    eng = _engine(trained_params, num_pages=num_pages, max_pages=4,
                  spec=spec)
    serve = ServingEngine(eng, clock=VirtualClock(),
                          config=ServingConfig(async_dispatch=async_dispatch))
    reqs = serve.run([dict(prompt=p, max_new_tokens=max_new_tokens,
                           arrival_ts=0.0) for p in PROMPTS])
    outputs = [(r.state.value, list(r.tokens), r.finish_ts) for r in reqs]
    return outputs, serve.stats.preemptions, eng


@pytest.mark.parametrize("spec", [None, SpecConfig(max_draft=4)],
                         ids=["spec-off", "spec-on"])
def test_async_parity_under_forced_preemption(trained_params, spec):
    """ACCEPTANCE (greedy parity): the pipelined loop's token streams are
    byte-identical to the serial loop's, with the arena sized so
    KV-pressure preemption genuinely fires mid-run (evict, requeue,
    recompute-on-resume) — spec-off and spec-on.  Virtual finish
    timestamps are NOT compared here: the pipelined admission sees pages
    released one step later, so the step census (not the tokens) may
    shift under pressure — the documented skew."""
    serial, pre_s, _ = _serve_once(trained_params, False, spec, num_pages=16)
    piped, pre_a, eng = _serve_once(trained_params, True, spec, num_pages=16)
    assert [o[:2] for o in serial] == [o[:2] for o in piped]
    assert all(state == "done" for state, _, _ in serial)
    assert pre_s > 0, "arena not tight enough — preemption never fired"
    assert pre_a == pre_s
    if spec is not None:
        assert eng.spec_stats.rounds > 0, "speculation never engaged"


def test_async_overlap_attribution_wall_clock(trained_params):
    """On a real clock the pipelined tick records the caller's loop in
    step g's OPEN window as the ``overlap`` segment (the serial loop
    records none: it runs no host work under a dispatch in flight), and
    both ticks name their admission and their delivery."""
    def run(async_dispatch):
        eng = _engine(trained_params)
        clock = WallClock()
        anat = eng.set_anatomy(StepAnatomy(clock=clock))
        eng.warm_all()
        anat.mark_steady()
        anat.reset_steps()
        serve = ServingEngine(eng, clock=clock,
                              config=ServingConfig(
                                  async_dispatch=async_dispatch))
        reqs = serve.run([dict(prompt=p, max_new_tokens=8, arrival_ts=0.0)
                          for p in PROMPTS])
        assert all(r.state is RequestState.DONE for r in reqs)
        return anat

    anat_s, anat_a = run(False), run(True)
    rows_s = [r.to_row() for r in anat_s.steps]
    rows_a = [r.to_row() for r in anat_a.steps]
    assert sum(r["segments"]["overlap"] for r in rows_s) == 0.0
    assert sum(r["segments"]["overlap"] for r in rows_a) > 0.0
    assert anat_s.steady_state_recompiles == 0
    assert anat_a.steady_state_recompiles == 0
    # per-step tiling holds in both modes on a wall clock
    for row in rows_s + rows_a:
        assert abs(row["wall_s"] - (row["host_gap_s"]
                                    + sum(row["segments"].values())
                                    + row["device_s"])) <= 1e-9
    for rows in (rows_s, rows_a):
        assert sum(r["segments"]["admit"] for r in rows) > 0.0
        assert sum(r["segments"]["deliver"] for r in rows) > 0.0


# -------------------------------------------------- chaos mid-pipeline


def test_crash_mid_pipeline_never_half_applies(trained_params):
    """A chaos crash fired inside the pipelined dispatch (the
    ``engine.verify_step`` site, spec path) surfaces from ``tick()`` with
    every row's staged-but-unverified draft rolled back out of its token
    history — and once disarmed, the SAME frontend drains to token
    streams byte-identical to an undisturbed serial run."""
    spec = SpecConfig(max_draft=4)
    baseline, _, _ = _serve_once(trained_params, False, spec, num_pages=64,
                                 max_new_tokens=12)
    eng = _engine(trained_params, num_pages=64, max_pages=4, spec=spec)
    serve = ServingEngine(eng, clock=VirtualClock(),
                          config=ServingConfig(async_dispatch=True))
    reqs = [serve.submit(p, max_new_tokens=12, arrival_ts=0.0)
            for p in PROMPTS]
    configure_fault_injection({"seed": 0, "sites": [
        {"site": "engine.verify_step", "kind": "crash", "at": 1}]})
    try:
        with pytest.raises(InjectedCrash):
            for _ in range(256):
                serve.tick()
    finally:
        configure_fault_injection(None)
    # never half-applied: every live history is prompt + accounted output
    for uid, seq in eng.state.seqs.items():
        req = next(r for r in reqs if r.uid == uid)
        assert len(seq.tokens) == len(req.prompt) + len(seq.generated)
    serve.run([])                              # disarmed: drain to done
    assert [(r.state.value, list(r.tokens), r.finish_ts)
            for r in reqs] == baseline


def test_fence_drains_dangling_inflight(trained_params):
    """``fence()`` with a step still in flight blocks on its readback and
    drops the output WHOLE — no token of the fenced step reaches any
    request — then flushes every sequence, exactly like the serial-mode
    fence."""
    eng = _engine(trained_params)
    serve = ServingEngine(eng, clock=VirtualClock(),
                          config=ServingConfig(async_dispatch=True))
    reqs = [serve.submit(p, max_new_tokens=8, arrival_ts=0.0)
            for p in PROMPTS[:4]]
    for _ in range(3):
        serve.tick()
    assert serve._inflight is not None
    tokens_before = [list(r.tokens) for r in reqs]
    counts = serve.fence()
    assert serve._inflight is None
    assert counts["queued"] + counts["active"] == len(reqs)
    assert not serve._active and not serve._queue
    assert not eng.state.seqs                  # pages + descriptors gone
    assert [list(r.tokens) for r in reqs] == tokens_before


# ------------------------------------------------- fleet recovery pin


def test_replica_recovery_first_request_pays_no_compile(trained_params):
    """Regression pin for warm-on-recover: a ``ReplicaPool`` replacement
    replica re-enters dispatch AOT-warmed (``warm_all``) and already
    steady, so its first post-recovery request pays ZERO JIT compiles
    (``compiles == 0`` on every step, ``compile_wait == 0`` segments, no
    steady-state recompile).  An AOT chaos fault during recovery still
    yields a LIVE replica (lazy-JIT fallback), never a dead one."""
    from deepspeed_tpu.serving.fleet import (ReplicaPool,
                                             RoundRobinPolicy, Router)

    def factory():
        return _engine(trained_params)

    pool = ReplicaPool(factory, 2, clock=VirtualClock(), anatomy=True)
    router = Router(pool, RoundRobinPolicy())

    def serve_one(rid, prompt):
        rep = pool.replica(rid)
        req = rep.serve.submit(prompt, max_new_tokens=6,
                               arrival_ts=pool.clock.now())
        for _ in range(64):
            pool.tick(rid)
            if req.state is RequestState.DONE:
                return req
        raise AssertionError(f"request never finished on replica {rid}")

    router.kill_replica(0)
    router.recover_replica(0)
    anat0 = pool.anatomy(0)
    assert anat0.steady
    assert anat0.compiles and all(c.aot for c in anat0.compiles)
    serve_one(0, [5, 9, 2, 7, 1])
    steps = list(anat0.steps)
    assert steps, "no steps recorded post-recovery"
    assert all(r.compiles == 0 for r in steps)
    assert all(r.segments["compile_wait"] == 0.0 for r in steps)
    assert anat0.steady_state_recompiles == 0

    # chaos during the recovery warm-up: every AOT compile faults, the
    # replacement falls back to lazy JIT — alive and serving (the lazy
    # compiles now fire the steady-state guard, which is the alarm doing
    # its job, not a dead replica)
    router.kill_replica(1)
    configure_fault_injection({"seed": 0, "sites": [
        {"site": "engine.aot_compile", "kind": "device_loss", "at": 1,
         "times": 99}]})
    try:
        router.recover_replica(1)
    finally:
        configure_fault_injection(None)
    anat1 = pool.anatomy(1)
    assert anat1.steady and not anat1.compiles   # nothing pre-compiled
    serve_one(1, [3, 3, 8])
    assert anat1.steady_state_recompiles > 0     # the guard fired...
    assert pool.replica(1).serve is not None     # ...on a live replica
