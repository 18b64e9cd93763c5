"""Speculative decoding (inference/v2/spec + engine verify path): n-gram
drafter contract, greedy parity by construction, paged-KV rollback
(truncate/release_tail), capacity-cap and EOS-surplus satellites, serving
integration (per-request control + acceptance accounting), and a seeded
admit/speculate/reject/preempt/resume property audit — all on the tiny CPU
model with deterministic clocks."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (NGramDrafter, RaggedInferenceEngineConfig,
                                        SpecConfig, build_engine, make_drafter)
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, StateManager
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import (RequestState, ServingConfig, ServingEngine,
                                   VirtualClock)

from reference_greedy import greedy

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)

PAGE = 8


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _engine(trained_params, num_pages=64, max_pages=8, spec=SpecConfig(max_draft=4),
            **overrides):
    kv = PagedKVConfig(num_pages=num_pages, page_size=PAGE, max_pages_per_seq=max_pages)
    sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8, decode_bucket=4)
    return build_engine(CFG, trained_params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched, kv_dtype=jnp.float32, **overrides, spec=spec))


#: cache-free greedy decode via the training model: ``_reference_greedy(params, prompt, n_new)``
_reference_greedy = functools.partial(greedy, LlamaForCausalLM(CFG).apply, width=32)


PROMPTS = [[5, 9, 2, 7, 1], [3, 3, 8], [1, 2, 3, 1, 2, 3, 1, 2], [11, 4, 6, 2]]


# ------------------------------------------------------------- drafter


def test_ngram_drafter_longest_suffix_most_recent_match():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # trailing trigram (7, 8, 9) occurred earlier; propose its continuation
    toks = [7, 8, 9, 1, 2, 3, 7, 8, 9]
    assert d.draft(toks, 3) == [1, 2, 3]
    assert d.draft(toks, 2) == [1, 2]          # max_tokens caps the proposal
    # two occurrences of the trailing unigram: the MOST RECENT one wins
    toks = [5, 1, 9, 5, 2, 9, 5]
    assert d.draft(toks, 2) == [2, 9]
    # no earlier occurrence at any n -> no draft
    assert d.draft([1, 2, 3, 4], 4) == []
    assert d.draft([1, 2], 0) == []
    assert d.draft([], 4) == []


def test_ngram_drafter_deterministic_and_registry():
    d = make_drafter(SpecConfig(max_draft=4, max_ngram=2))
    toks = list(np.random.default_rng(0).integers(1, 20, 40))
    assert d.draft(toks, 4) == d.draft(list(toks), 4)
    with pytest.raises(ValueError, match="unknown drafter"):
        make_drafter(SpecConfig(drafter="nope"))
    with pytest.raises(ValueError, match="max_draft"):
        SpecConfig(max_draft=0)
    with pytest.raises(ValueError, match="min_ngram"):
        SpecConfig(min_ngram=3, max_ngram=2)


# ---------------------------------------------------------- engine parity


def test_spec_generate_matches_reference(trained_params):
    """ACCEPTANCE (greedy parity): speculative decode emits byte-identical
    tokens to both the cache-free reference and a spec-off engine — every
    emitted token is the model's argmax given the exact accepted history."""
    eng = _engine(trained_params)
    outs = eng.generate(PROMPTS, max_new_tokens=12)
    for prompt, got in zip(PROMPTS, outs):
        assert got == _reference_greedy(trained_params, prompt, 12), prompt
    # speculation genuinely engaged (not a vacuous fallback run)
    assert eng.spec_stats.rounds > 0 and eng.spec_stats.proposed > 0
    assert eng.spec_stats.accepted > 0
    assert eng.spec_stats.emitted >= eng.spec_stats.accepted + eng.spec_stats.rounds


def test_spec_disabled_under_sampling(trained_params):
    """The accept rule is an argmax identity: a sampling engine must drop
    its SpecConfig (emitting drafted tokens would need the full
    rejection-sampling correction) and still decode."""
    eng = _engine(trained_params, greedy=False, temperature=0.8)
    assert eng.econfig.spec is None and eng.drafter is None
    outs = eng.generate([[5, 9, 2, 7, 1]], max_new_tokens=4)
    assert len(outs[0]) == 4


def test_verify_program_one_per_batch_bucket(trained_params):
    """Steady-state serving compiles ONE verify program per batch bucket
    (width pinned at max_draft+1; shorter drafts ride as ragged rows)."""
    eng = _engine(trained_params)
    eng.generate(PROMPTS, max_new_tokens=12)
    eng.generate([[9, 1, 4, 9, 1, 4, 9]], max_new_tokens=12)
    verify_keys = [k for k in eng._step_fns if k[0] == "verify"]
    assert verify_keys, "no verify program compiled — speculation never ran"
    widths = {k[2] for k in verify_keys}
    assert widths == {eng.econfig.spec.max_draft + 1}
    assert len(verify_keys) == len({k[1] for k in verify_keys})


def test_verify_step_fault_site_restores_history(trained_params):
    """engine.verify_step is an armable chaos site: a device loss injected
    there surfaces from step() as a classifiable DeviceLossError, the
    staged (unverified) drafts are rolled OUT of every row's token
    history, and — the fault firing before the cache dispatch — the
    engine resumes to byte-identical outputs once disarmed."""
    from deepspeed_tpu.resilience.fault_injection import (
        DeviceLossError, INJECTION_SITES, configure_fault_injection)
    assert "engine.verify_step" in INJECTION_SITES
    eng = _engine(trained_params)
    configure_fault_injection(
        {"seed": 0, "sites": [{"site": "engine.verify_step",
                               "kind": "device_loss", "at": 1}]})
    try:
        uids = list(range(len(PROMPTS)))
        eng.put(uids, PROMPTS, max_new_tokens=12)
        with pytest.raises(DeviceLossError, match="DEVICE_LOST"):
            # the workload test_spec_generate_matches_reference proves
            # reaches a verify round (spec_stats.rounds > 0)
            for _ in range(64):
                eng.step()
        # no unverified draft baked into any history: every token is
        # either prompt or accounted generated output
        for u in uids:
            seq = eng.state.seqs[u]
            assert len(seq.tokens) == len(PROMPTS[u]) + len(seq.generated)
    finally:
        configure_fault_injection(None)
    # the fault fired before the verify dispatch donated the cache, so the
    # engine is genuinely resumable: finishing the run matches reference
    for _ in range(64):
        eng.step()
        if all(eng.state.seqs[u].done for u in uids):
            break
    for u in uids:
        assert list(eng.state.seqs[u].generated) == \
            _reference_greedy(trained_params, PROMPTS[u], 12)


def test_warm_verify_precompiles_and_preserves_parity(trained_params):
    """warm_verify's all-padding dispatch compiles the verify buckets up
    front (no jit inside measured serving) without perturbing engine
    state: a warmed engine still matches the reference exactly."""
    eng = _engine(trained_params)
    eng.warm_verify([1, 8])
    warmed = {k for k in eng._step_fns if k[0] == "verify"}
    assert warmed
    outs = eng.generate(PROMPTS, max_new_tokens=12)
    for prompt, got in zip(PROMPTS, outs):
        assert got == _reference_greedy(trained_params, prompt, 12)
    assert eng.spec_stats.rounds > 0
    assert {k for k in eng._step_fns if k[0] == "verify"} == warmed
    # no-op on a spec-less engine
    _engine(trained_params, spec=None).warm_verify([1, 8])


# ------------------------------------------------------ scheduler budget


def test_scheduler_mixed_step_never_charges_verify_tokens():
    """REGRESSION: verify rounds run only on pure-decode steps, so a mixed
    plan (prefill pending) must charge decode rows 1 token each — charging
    1 + spec_verify_tokens there would throttle prefill for verify work
    that cannot happen (e.g. every request opted out via spec=False)."""
    kv = BlockedKVCache(num_pages=64, page_size=8, max_pages_per_seq=8)
    state = StateManager(kv, max_batch=8)
    for uid in range(2):   # 2 decodes -> bucket of 4
        seq = state.get_or_create(uid, list(range(1, 10)))
        seq.seen_tokens = len(seq.tokens)
        seq.generated = [7]
    state.get_or_create(10, list(range(1, 40)))
    sched = SplitFuseScheduler(SchedulerConfig(token_budget=32, max_seqs=8,
                                               prefill_chunk=16, decode_bucket=4,
                                               spec_verify_tokens=4))
    plan = sched.plan(state)
    assert len(plan.decode) == 2
    # budget 32 - bucketed 4 = 28: the prefill plans its full 16-token
    # chunk.  Under the rejected 1+k charging (32 - 4*5 = 12) the chunk
    # would have been clipped to 12.
    assert [n for _, n in plan.prefill] == [16]


def test_plan_drafts_respects_token_budget(trained_params):
    """Verify slots ARE planned against the SplitFuse token budget: the
    round's total fed tokens (1 + draft per row) shrink until they fit
    token_budget, exactly like page pressure shrinks them."""
    eng = _engine(trained_params)
    # 4 decode-state rows whose repetitive history drafts the full k=4
    for uid in range(4):
        seq = eng.state.get_or_create(uid, [1, 2, 3, 1, 2, 3, 1, 2])
        eng.kv.ensure_capacity(seq, seq.remaining_prefill)
        seq.seen_tokens = len(seq.tokens) - 1
        seq.generated = [seq.tokens[-1]]
        eng._max_new[uid] = 16
    seqs = [eng.state.seqs[u] for u in range(4)]
    drafts = eng._plan_drafts(seqs)
    # the repeating history drafts its cycle continuation on every row
    assert all(len(d) >= 3 for d in drafts)              # budget 64: untouched
    import dataclasses
    eng.econfig = dataclasses.replace(
        eng.econfig, scheduler=dataclasses.replace(eng.econfig.scheduler,
                                                   token_budget=12))
    shrunk = eng._plan_drafts(seqs)
    assert sum(1 + len(d) for d in shrunk) <= 12
    assert any(shrunk), "halving overshot: budget 12 fits 4 rows x 2-token slots"


def test_engine_derives_verify_tokens_from_spec(trained_params):
    eng = _engine(trained_params)
    assert eng.econfig.scheduler.spec_verify_tokens == eng.econfig.spec.max_draft


# ------------------------------------------------------- rollback primitives


def test_truncate_clamps_seen_and_frees_tail_pages():
    kv = BlockedKVCache(32, PAGE, 8, enable_prefix_cache=False)
    state = StateManager(kv, max_batch=8)
    seq = state.get_or_create(0, list(range(1, 11)))    # 10 tokens
    kv.ensure_capacity(seq, seq.remaining_prefill + 22)  # room for 32 = 4 pages
    seq.seen_tokens = 30
    assert len(seq.pages) == 4
    free_before = kv.allocator.free_pages
    freed = state.truncate(seq, 17)                      # keep ceil(17/8) = 3 pages
    assert freed == 1 and len(seq.pages) == 3
    assert seq.seen_tokens == 17
    assert kv.allocator.free_pages == free_before + 1    # visible immediately
    # truncate past the current length is a no-op clamp, not an extension
    assert state.truncate(seq, 40) == 0 and seq.seen_tokens == 17


def test_release_tail_never_drops_prefix_cache_published_pages():
    """register()'s cursor indexes into seq.pages: rollback must clamp at
    pc_pages even if asked for less, or every later index shifts under
    the cache's feet."""
    kv = BlockedKVCache(32, PAGE, 8, enable_prefix_cache=True)
    state = StateManager(kv, max_batch=8)
    seq = state.get_or_create(0, list(range(1, 2 * PAGE + 2)))  # 2 full pages + 1
    kv.ensure_capacity(seq, seq.remaining_prefill)
    seq.seen_tokens = len(seq.tokens)
    state.note_progress(seq)                              # publishes 2 full pages
    assert seq.pc_pages == 2
    assert kv.release_tail(seq, 0) == 1                   # only the partial tail
    assert len(seq.pages) == 2
    assert kv.release_tail(seq, 0) == 0                   # published pages stay


# ------------------------------------------------- engine rollback accounting


def test_spec_rollback_frees_pages_and_allocator_stays_clean(trained_params):
    """Rejected drafts' wholly-surplus pages return to the arena at the end
    of the verify round, and a full serve leaves zero refcount drift."""
    eng = _engine(trained_params, num_pages=64, enable_prefix_cache=False)
    eng.generate(PROMPTS, max_new_tokens=16)
    st = eng.spec_stats
    assert st.proposed > st.accepted, "every draft accepted — rollback untested"
    # all sequences flushed by generate(): the whole arena must be free
    assert eng.kv.allocator.free_pages == eng.kv.num_pages - 1
    assert (eng.kv.allocator._rc[1:] == 0).all()


def test_multi_decode_capacity_capped_at_remaining(trained_params):
    """SATELLITE: the fused rung must reserve min(k, remaining) pages — a
    short-tail row (remaining << k) must not grab KV pages it can never
    keep.  8 usable pages fit prompt(9 tokens -> 2 pages) + 1; an uncapped
    k=8 reservation would demand 3 pages for the tail row and starve the
    arena under pressure."""
    eng = _engine(trained_params, num_pages=16, spec=None,
                  enable_prefix_cache=False, decode_steps_per_dispatch=8)
    prompt = [5, 9, 2, 7, 1, 3, 3, 8, 4, 2, 6, 1]        # 12 tokens
    eng.put([0], [prompt], max_new_tokens=2)
    eng.step()                                           # the prompt's two chunks as a run: emits token 1
    seq = eng.state.seqs[0]
    assert not seq.done and len(seq.generated) == 1
    eng.step()                                           # fused rung, remaining=1
    assert seq.done and len(seq.generated) == 2
    # 14 final tokens = 2 pages; the uncapped k=8 reservation would have
    # allocated for seen+8 = 20 tokens = 3 pages
    assert len(seq.pages) == -(-len(seq.tokens) // PAGE) == 2


def test_eos_mid_rung_releases_surplus_same_step(trained_params):
    """SATELLITE: a row hitting EOS mid-rung returns its surplus tail pages
    the same step (visible to single_step_page_demand / the KV-pressure
    preflight), not at sequence death."""
    ref = _reference_greedy(trained_params, [5, 9, 2, 7, 1], 8)
    eos = ref[2]
    eng = _engine(trained_params, spec=None, enable_prefix_cache=False,
                  decode_steps_per_dispatch=8, eos_token_id=eos)
    eng.put([0], [[5, 9, 2, 7, 1]], max_new_tokens=24)
    eng.step()                                           # prefill
    seq = eng.state.seqs[0]
    while not seq.done:
        eng.step()
    assert list(seq.generated) == ref[:3]
    # the rung wrote KV for its full k block; the EOS break truncated the
    # sequence to 8 tokens = 1 page — surplus pages are already free HERE,
    # with the sequence still alive
    assert len(seq.pages) == -(-len(seq.tokens) // PAGE) == 1
    assert eng.kv.allocator.free_pages == eng.kv.num_pages - 1 - len(seq.pages)


# ----------------------------------------------------------- serving layer


def _serve(trained_params, spec=SpecConfig(max_draft=4), num_pages=64, **eng_kw):
    eng = _engine(trained_params, num_pages=num_pages, spec=spec,
                  decode_steps_per_dispatch=1, **eng_kw)
    return ServingEngine(eng, clock=VirtualClock(), config=ServingConfig())


def test_serving_spec_parity_acceptance_and_metrics(trained_params):
    """ACCEPTANCE: ServingEngine outputs with speculation enabled are
    byte-identical to spec-off runs; per-request acceptance lands on the
    request and the spec/* metrics; TPOT (virtual-clock steps per token)
    strictly improves for requests with accepted drafts."""
    from deepspeed_tpu.telemetry import MetricsRegistry
    baseline = _serve(trained_params, spec=None)
    base_reqs = [baseline.submit(p, max_new_tokens=10) for p in PROMPTS]
    baseline.drain()

    metrics = MetricsRegistry()
    serve = _serve(trained_params)
    serve.metrics = metrics
    reqs = [serve.submit(p, max_new_tokens=10) for p in PROMPTS]
    serve.drain()

    assert [list(r.tokens) for r in reqs] == [list(r.tokens) for r in base_reqs]
    assert all(r.state is RequestState.DONE for r in reqs)
    accepted = sum(r.spec_accepted for r in reqs)
    proposed = sum(r.spec_proposed for r in reqs)
    assert proposed > 0 and accepted > 0
    assert metrics.counter("spec/proposed").value == proposed
    assert metrics.counter("spec/accepted").value == accepted
    hist = metrics.histogram("spec/acceptance_rate")
    assert hist.count > 0
    winners = [i for i, r in enumerate(reqs) if r.spec_accepted]
    assert winners
    for i in winners:
        assert reqs[i].tpot < base_reqs[i].tpot
        assert reqs[i].spec_acceptance == \
            reqs[i].spec_accepted / reqs[i].spec_proposed


def test_serving_per_request_spec_opt_out(trained_params):
    serve = _serve(trained_params)
    r_on = serve.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=10)
    r_off = serve.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=10, spec=False)
    serve.drain()
    assert list(r_on.tokens) == list(r_off.tokens)        # parity either way
    assert r_off.spec_proposed == 0 and r_off.spec_acceptance is None
    assert r_on.spec_proposed > 0


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_preempt_during_speculation_resume_identical(trained_params, prefix_cache):
    """ACCEPTANCE (rollback under the PR-2 contract): KV pressure preempting
    a speculating request mid-decode still reproduces token-identical
    outputs on resume, prefix cache on and off, with zero page drift."""
    rng = np.random.default_rng(0)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    p2 = [int(x) for x in rng.integers(1, 100, 9)]
    golden = _engine(trained_params, num_pages=64, spec=None,
                     decode_steps_per_dispatch=1).generate([p1, p2], max_new_tokens=20)

    # 6 usable pages: both sequences admit (2 pages each + slack) but their
    # final footprints (4 pages each) cannot coexist — preemption is forced
    # whatever the speculation timeline does
    serve = _serve(trained_params, num_pages=7, enable_prefix_cache=prefix_cache)
    r1 = serve.submit(p1, max_new_tokens=20)
    r2 = serve.submit(p2, max_new_tokens=20)
    serve.drain()
    assert serve.stats.preemptions >= 1
    assert [r1.state, r2.state] == [RequestState.DONE] * 2
    assert [list(r1.tokens), list(r2.tokens)] == golden
    assert r1.spec_proposed + r2.spec_proposed > 0, "speculation never engaged"
    eng = serve.engine
    cached = eng.kv.prefix_cache.cached_pages if eng.kv.prefix_cache else 0
    assert eng.kv.allocator.free_pages + cached == eng.kv.num_pages - 1


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_property_speculate_reject_preempt_resume_cycles(trained_params, seed):
    """ACCEPTANCE (seeded property): random admit/speculate/reject/preempt/
    resume cycles — a tight arena forces preemption while verify rounds
    accept and reject drafts — leave zero page-refcount drift in
    BlockedKVCache and every resumed output token-identical to an
    unpressured spec-off run."""
    rng = np.random.default_rng(seed)
    prompts = [[int(x) for x in rng.integers(1, 100, int(rng.integers(4, 10)))]
               for _ in range(5)]
    lens = [int(rng.integers(6, 14)) for _ in prompts]
    ref = _engine(trained_params, num_pages=64, spec=None,
                  decode_steps_per_dispatch=1)
    golden = [ref.generate([p], max_new_tokens=n)[0] for p, n in zip(prompts, lens)]

    serve = _serve(trained_params, num_pages=12)
    reqs = [serve.submit(p, max_new_tokens=n, arrival_ts=float(i))
            for i, (p, n) in enumerate(zip(prompts, lens))]
    serve.drain()
    assert all(r.state is RequestState.DONE for r in reqs)
    assert [list(r.tokens) for r in reqs] == golden
    eng = serve.engine
    rc = eng.kv.allocator._rc
    free = eng.kv.allocator._free
    assert len(free) == len(set(free)), "free list has duplicates"
    for p in free:
        assert rc[p] == 0
    cached = eng.kv.prefix_cache.cached_pages if eng.kv.prefix_cache else 0
    assert eng.kv.allocator.free_pages + cached == eng.kv.num_pages - 1
    assert eng.spec_stats.rollback_pages >= 0
