"""Tiered paged KV tests (deepspeed_tpu/serving/kvtier): park/resume
byte-identity against never-parked goldens (spec on/off, prefix cache
on/off), prefetch-hidden promotion, demotion-first preemption, the
warm-on-host prefix roundtrip, the tiered fleet directory, and a seeded
property audit over random admit/park/resume/preempt/expiry interleavings
— all on the tiny CPU model with a deterministic virtual clock."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                        SpecConfig, build_engine)
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import (RequestState, ServingConfig, ServingEngine,
                                   VirtualClock)
from deepspeed_tpu.serving.kvtier import TierConfig, TieredKVManager

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True,
                  remat=False)
PAGE = 8


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def _engine(trained_params, num_pages=64, max_seqs=8, **overrides):
    kv = PagedKVConfig(num_pages=num_pages, page_size=PAGE,
                       max_pages_per_seq=8)
    sched = SchedulerConfig(token_budget=64, max_seqs=max_seqs,
                            prefill_chunk=8, decode_bucket=4)
    eng_cfg = RaggedInferenceEngineConfig(kv=kv, scheduler=sched,
                                          kv_dtype=jnp.float32,
                                          decode_steps_per_dispatch=1,
                                          **overrides)
    return build_engine(CFG, trained_params, eng_cfg)


def _serve(trained_params, tier_config=None, config=None, **eng_kw):
    serve = ServingEngine(_engine(trained_params, **eng_kw),
                          clock=VirtualClock(),
                          config=config or ServingConfig())
    tier = TieredKVManager(serve.engine, config=tier_config)
    serve.attach_tier(tier)
    return serve, tier


def _decode_until(serve, req, min_tokens=2, max_ticks=200):
    """Tick until ``req`` is decoding with at least ``min_tokens`` out."""
    for _ in range(max_ticks):
        if req.state is RequestState.DECODE and len(req.tokens) >= min_tokens:
            return
        serve.tick()
    raise AssertionError(f"uid={req.uid} never reached DECODE with "
                         f"{min_tokens} tokens (state={req.state})")


def _assert_clean(serve, tier):
    eng = serve.engine
    assert not eng.state.seqs
    if eng.kv.prefix_cache is not None:
        eng.kv.prefix_cache.evict(eng.kv.num_pages)
    assert eng.kv.allocator.free_pages == eng.kv.num_pages - 1
    # host-tier internal accounting: the LRU IS the occupancy ledger
    assert tier.host.pages_used == sum(tier.host._lru.values())
    assert tier.host.pages_used <= tier.host.capacity_pages


# ----------------------------------------------------- park/resume identity


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_park_resume_matches_never_parked_golden(trained_params, prefix_cache):
    """ACCEPTANCE: a session parked mid-decode and resumed produces the
    byte-identical token stream of a never-parked run — the promote path
    restores the exact KV pages the demotion staged."""
    rng = np.random.default_rng(0)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    p2 = [int(x) for x in rng.integers(1, 100, 5)]
    golden = _engine(trained_params).generate([p1, p2], max_new_tokens=10)

    serve, tier = _serve(trained_params, enable_prefix_cache=prefix_cache)
    r1 = serve.submit(p1, max_new_tokens=10)
    r2 = serve.submit(p2, max_new_tokens=10)
    _decode_until(serve, r1, min_tokens=2)
    assert serve.park(r1.uid)
    assert serve.load_stats()["parked"] == 1
    # the parked session holds ZERO device pages: its engine seq is gone
    assert r1.uid not in serve.engine.state.seqs
    for _ in range(3):
        serve.tick()        # r2 keeps decoding while r1 sleeps
    assert serve.resume(r1.uid)
    serve.drain()

    assert [r1.state, r2.state] == [RequestState.DONE] * 2
    assert [list(r1.tokens), list(r2.tokens)] == golden
    assert RequestState.PARKED in [s for s, _ in r1.history]
    assert serve.stats.parks == 1 and serve.stats.resumes == 1
    assert tier.stats["demotions"] == 1 and tier.stats["promotions"] == 1
    assert serve.stats.kv_imports >= 1
    assert serve.stats.kv_import_fallbacks == 0
    _assert_clean(serve, tier)


def test_park_resume_with_spec_decoding_identical(trained_params):
    """Spec on: the resumed stream still equals the never-parked golden
    (the verify loop replays from imported KV exactly)."""
    rng = np.random.default_rng(3)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    golden = _engine(trained_params,
                     spec=SpecConfig(max_draft=4)).generate(
                         [p1], max_new_tokens=10)

    serve, tier = _serve(trained_params, spec=SpecConfig(max_draft=4))
    r1 = serve.submit(p1, max_new_tokens=10)
    _decode_until(serve, r1, min_tokens=2)
    assert serve.park(r1.uid)
    serve.tick()
    assert serve.resume(r1.uid)
    serve.drain()
    assert r1.state is RequestState.DONE
    assert [list(r1.tokens)] == golden
    assert tier.stats["promotions"] == 1
    _assert_clean(serve, tier)


def test_prefetch_resume_hides_transfer(trained_params):
    """The prefetch-hidden promotion contract: with a nonzero h2d cost and
    the transfer issued AHEAD of resume (prefetch_resume), the promote
    hides under the intervening device windows — hidden fraction ~1, and
    the resumed stream is still byte-identical."""
    rng = np.random.default_rng(1)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    p2 = [int(x) for x in rng.integers(1, 100, 9)]
    golden = _engine(trained_params).generate([p1, p2], max_new_tokens=12)

    serve, tier = _serve(trained_params,
                         tier_config=TierConfig(h2d_page_s=0.002))
    r1 = serve.submit(p1, max_new_tokens=12)
    r2 = serve.submit(p2, max_new_tokens=12)
    _decode_until(serve, r1, min_tokens=2)
    assert serve.park(r1.uid)
    assert serve.prefetch_resume(r1.uid)    # transfer issued NOW
    for _ in range(8):
        serve.tick()                        # device windows it hides under
    assert serve.resume(r1.uid)
    serve.drain()
    assert [list(r1.tokens), list(r2.tokens)] == golden
    assert tier.hidden_frac is not None and tier.hidden_frac > 0.5
    # the carved promote window landed on the request for span attribution
    assert r1.promote_windows
    _assert_clean(serve, tier)


def test_unhinted_resume_stalls_but_stays_identical(trained_params):
    """An immediate resume (no hiding window) pays the transfer as a
    stall — slower, never wrong — and the stall is charged on the clock."""
    rng = np.random.default_rng(2)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    golden = _engine(trained_params).generate([p1], max_new_tokens=8)
    serve, tier = _serve(trained_params,
                         tier_config=TierConfig(h2d_page_s=0.01))
    r1 = serve.submit(p1, max_new_tokens=8)
    _decode_until(serve, r1, min_tokens=2)
    assert serve.park(r1.uid)
    t0 = serve.clock.now()
    assert serve.resume(r1.uid)
    serve.tick()        # admission settles the un-hidden transfer
    assert serve.clock.now() - t0 >= 0.01   # >= one page of stall
    serve.drain()
    assert [list(r1.tokens)] == golden
    assert tier.hidden_frac is not None and tier.hidden_frac < 1.0
    _assert_clean(serve, tier)


# ------------------------------------------------- demotion-first pressure


def test_pressure_preemption_demotes_first_and_promotes_back(trained_params):
    """ACCEPTANCE: with the tier attached, KV-pressure preemption stages
    the victim's pages host-side BEFORE evicting, and the victim's
    re-admission imports (promotes) instead of recomputing — outputs
    byte-identical to the unpreempted golden."""
    rng = np.random.default_rng(0)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    p2 = [int(x) for x in rng.integers(1, 100, 9)]
    golden = _engine(trained_params, num_pages=64).generate(
        [p1, p2], max_new_tokens=20)

    # 7 usable pages: both sequences end at 4 pages -> cannot coexist
    serve, tier = _serve(trained_params, num_pages=8)
    r1 = serve.submit(p1, max_new_tokens=20)
    r2 = serve.submit(p2, max_new_tokens=20)
    serve.drain()

    assert serve.stats.preemptions >= 1
    assert tier.stats["demotions"] >= 1
    assert serve.stats.kv_imports >= 1       # promoted, not recomputed
    assert [r1.state, r2.state] == [RequestState.DONE] * 2
    assert [list(r1.tokens), list(r2.tokens)] == golden
    _assert_clean(serve, tier)


def test_parked_resume_cheaper_than_evicted_recompute(trained_params):
    """Resume-cost regression: the same pressure workload completes in
    LESS simulated time with the tier (demote + free promote) than
    without (evict + recompute prefill) — the clock receipt demotion-first
    exists to win."""
    rng = np.random.default_rng(0)
    p1 = [int(x) for x in rng.integers(1, 100, 9)]
    p2 = [int(x) for x in rng.integers(1, 100, 9)]

    def run(with_tier):
        # max_seqs 4: no rung of four prefill rows under max_seqs, so a resumed
        # prompt is recomputed a chunk a step (a run would feed it in one)
        if with_tier:
            serve, tier = _serve(trained_params, num_pages=8, max_seqs=4)
        else:
            serve = ServingEngine(_engine(trained_params, num_pages=8, max_seqs=4),
                                  clock=VirtualClock(), config=ServingConfig())
            tier = None
        a = serve.submit(p1, max_new_tokens=20)
        b = serve.submit(p2, max_new_tokens=20)
        serve.drain()
        assert a.state is RequestState.DONE and b.state is RequestState.DONE
        return serve, tier, (list(a.tokens), list(b.tokens))

    s_tier, tier, out_tier = run(True)
    s_evict, _, out_evict = run(False)
    assert out_tier == out_evict
    assert s_tier.stats.kv_imports >= 1 and s_evict.stats.kv_imports == 0
    assert s_tier.clock.now() < s_evict.clock.now()
    assert tier.stats["demotions"] >= 1


# ------------------------------------------------ warm-on-host prefix tier


def test_prefix_evict_demotes_to_host_and_promotes_back(trained_params):
    """A prefix page evicted under device pressure lands host-side
    (warm-on-host); the next admission of a matching prompt promotes the
    chain back and serves byte-identical output."""
    prefix = list(range(1, 2 * PAGE + 1))
    prompts = [prefix + [40], prefix + [41]]
    golden = _engine(trained_params).generate(
        [list(p) for p in prompts], max_new_tokens=4)

    serve, tier = _serve(trained_params)
    r1 = serve.submit(prompts[0], max_new_tokens=4)
    serve.drain()
    pc = serve.engine.kv.prefix_cache
    assert pc.cached_pages >= 2
    pc.evict(serve.engine.kv.num_pages)       # device pressure: drop all
    assert pc.cached_pages == 0
    assert tier.stats["prefix_demotions"] >= 2
    assert tier.host_prefix_depth(prompts[1]) >= 2
    r2 = serve.submit(prompts[1], max_new_tokens=4)
    serve.drain()
    assert [list(r1.tokens), list(r2.tokens)] == golden
    assert tier.stats["prefix_promotions"] >= 2
    # promoted pages are device-warm again, dropped from the host tier
    assert tier.host_prefix_depth(prompts[1]) == 0
    _assert_clean(serve, tier)


def test_host_capacity_bounds_and_oversize_rejection():
    """HostKVTier is strictly bounded: LRU demotion under pressure, and a
    put larger than the whole tier is refused outright."""
    from deepspeed_tpu.serving.kvtier import HostKVTier
    from deepspeed_tpu.serving.kvtransfer import KVSnapshot

    def snap(tokens, n_pages):
        s = KVSnapshot(tokens=list(tokens), seen_tokens=len(tokens),
                       page_size=PAGE, block_shape=(2, PAGE, 2, 2, 4),
                       dtype="float32", source="test")
        s.add_chunk(np.zeros((2, n_pages, PAGE, 2, 2, 4), np.float32))
        s.complete = True
        return s

    tier = HostKVTier(capacity_pages=4)
    assert tier.put_seq(1, snap([1] * 8, 2))
    assert tier.put_seq(2, snap([2] * 8, 2))
    assert tier.pages_used == 4
    assert not tier.put_seq(3, snap([3] * 48, 6))   # oversize: refused
    assert tier.stats["rejected_oversize"] == 1
    assert tier.put_seq(4, snap([4] * 8, 2))        # evicts uid=1 (LRU)
    assert tier.pages_used == 4
    assert tier.peek_seq(1) is None and tier.peek_seq(2) is not None
    assert tier.take_seq(2).n_pages == 2
    assert tier.pages_used == 2


# ------------------------------------------------- tiered fleet directory


def test_directory_tiered_depths_and_host_routing():
    """The fleet directory's host tier: tiered_depths distinguishes
    device-warm from host-warm, the policy prefers device > host > cold,
    and purge forgets both tiers."""
    from deepspeed_tpu.inference.v2.ragged import prefix_chain_hashes
    from deepspeed_tpu.serving.fleet import (PrefixDirectory,
                                             PrefixDirectoryPolicy)

    tokens = list(range(1, 3 * PAGE + 2))
    chain = prefix_chain_hashes(tokens, PAGE)
    d = PrefixDirectory(page_size=PAGE)
    # rid 0: 2 pages device-warm; rid 1: 1 device + 2 host; rid 2: cold
    d.publish(0, chain[0]); d.publish(0, chain[1])
    d.publish(1, chain[0])
    d.publish_host(1, chain[1]); d.publish_host(1, chain[2])
    td = d.tiered_depths(tokens, [0, 1, 2])
    assert td == {0: (2, 2), 1: (1, 3), 2: (0, 0)}
    # plain depths (device tier) is unchanged by host publishes
    assert d.depths(tokens, [0, 1, 2]) == {0: 2, 1: 1, 2: 0}

    class _FR:
        pass
    fr = _FR()
    fr.prompt, fr.tokens = tokens, []
    pol = PrefixDirectoryPolicy(d, saturation_queue_depth=4)

    def mk(rid):
        return rid, None, {"queue_depth": 0, "outstanding": 0}
    # deepest DEVICE warmth wins over deeper host warmth at the first key
    rid, info = pol.select(fr, [mk(0), mk(1), mk(2)])
    assert rid == 0 and info["affinity_hit"] and "host_warm" not in info
    # host-warm replica beats the cold one when the device-warm is gone
    rid, info = pol.select(fr, [mk(1), mk(2)])
    assert rid == 1 and info["affinity_hit"]
    assert info["host_warm"] and info["host_pages"] == 2

    assert d.purge(1) == 3       # 1 device + 2 host entries
    assert d.tiered_depths(tokens, [1])[1] == (0, 0)
    assert d.host_entries == 0


# ------------------------------------------------------ seeded property audit


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_property_audit_random_park_resume_preempt(trained_params, seed):
    """Seeded audit: random interleavings of admit / park / prefetch /
    resume / preempt / idle-gap / parked-deadline-expiry must keep every
    output a golden prefix (DONE = full golden), terminals exactly-once,
    the host tier within capacity at every step, and zero page drift."""
    rng = np.random.default_rng(seed)
    prompts = [[int(x) for x in rng.integers(1, 100, int(rng.integers(5, 12)))]
               for _ in range(8)]
    golden = _engine(trained_params).generate(
        [list(p) for p in prompts], max_new_tokens=10)

    serve, tier = _serve(trained_params, num_pages=32, max_seqs=4,
                         tier_config=TierConfig(host_capacity_pages=12,
                                                h2d_page_s=0.001))
    reqs = []
    pending = list(enumerate(prompts))
    for _ in range(120):
        op = rng.choice(["tick", "tick", "admit", "park", "resume",
                         "prefetch", "idle"])
        if op == "admit" and pending:
            i, p = pending.pop(0)
            # two of the eight carry a deadline a long park will blow
            deadline = serve.clock.now() + 2.0 if i in (2, 5) else None
            reqs.append(serve.submit(list(p), max_new_tokens=10,
                                     deadline=deadline))
        elif op == "park":
            decoding = [u for u, r in serve._active.items()
                        if r.state is RequestState.DECODE]
            if decoding:
                serve.park(int(rng.choice(decoding)))
        elif op == "resume":
            parked = sorted(serve._parked)
            if parked:
                serve.resume(int(rng.choice(parked)))
        elif op == "prefetch":
            parked = sorted(serve._parked)
            if parked:
                serve.prefetch_resume(int(rng.choice(parked)))
        elif op == "idle":
            serve.clock.wait_until(serve.clock.now() + 0.3)
        else:
            serve.tick()
        assert tier.host.pages_used <= tier.host.capacity_pages
        assert tier.host.pages_used == sum(tier.host._lru.values())
    for i, p in pending:
        reqs.append(serve.submit(list(p), max_new_tokens=10))
    for uid in sorted(serve._parked):
        serve.resume(uid)
    serve.drain()
    while serve._parked:            # resume anything parked by late ops
        serve.resume(sorted(serve._parked)[0])
        serve.drain()

    # pending popped in order, so reqs[i] serves prompts[i]
    assert len(reqs) == 8
    for req, gold in zip(reqs, golden):
        terminals = [s for s, _ in req.history if s.terminal]
        assert len(terminals) == 1, req
        if req.state is RequestState.DONE:
            assert list(req.tokens) == gold
        else:
            assert req.state is RequestState.TIMED_OUT
            assert list(req.tokens) == gold[:len(req.tokens)]
    _assert_clean(serve, tier)


# --------------------------------------------------- watermark enforcement


def test_device_watermark_demotes_cold_prefix_with_hysteresis(trained_params):
    """Capacity-pressure demotion (``enforce_watermarks``, run every
    serving tick): crossing the device HIGH watermark demotes LRU-leaf
    prefix pages down to the LOW watermark — staged warm-on-host — and the
    hysteresis band means a tier sitting between lo and hi is untouched,
    so back-to-back sweeps cannot thrash."""
    cfg = TierConfig(host_capacity_pages=64,
                     device_watermark_hi=0.08, device_watermark_lo=0.03)
    serve, tier = _serve(trained_params, tier_config=cfg, max_seqs=4)   # a chunk a sequence and step
    # three finished prompts leave ~6 cold prefix pages device-side
    for i in range(3):
        serve.submit(list(range(10 * i + 1, 10 * i + 2 * PAGE + 1)),
                     max_new_tokens=2)
    serve.drain()
    pc = serve.engine.kv.prefix_cache
    alloc = serve.engine.kv.allocator
    usable = serve.engine.kv.num_pages - 1
    used = usable - alloc.free_pages
    assert used / usable >= cfg.device_watermark_hi   # above hi: must act
    out = tier.enforce_watermarks()
    assert out["device_demoted"] > 0
    used_after = usable - alloc.free_pages
    assert used_after <= int(cfg.device_watermark_lo * usable)
    # demoted pages stayed warm — they landed in the host prefix tier
    assert tier.stats["prefix_demotions"] >= out["device_demoted"]
    assert tier.stats["watermark_demotions"] == out["device_demoted"]
    # hysteresis: now below hi, an immediate second sweep is a no-op
    assert tier.enforce_watermarks() == {"device_demoted": 0, "host_dropped": 0}
    # ... and a tick runs the sweep implicitly without firing it again
    serve.tick()
    assert tier.stats["watermark_demotions"] == out["device_demoted"]
    assert pc.cached_pages == used_after


def test_host_watermark_drops_coldest_first(trained_params):
    """Host-side watermark: crossing hi drops LRU-COLDEST entries (the
    ledger's insertion/touch order) until occupancy is back at lo — the
    newest snapshot survives, the stalest die first."""
    from deepspeed_tpu.serving.kvtransfer import KVSnapshot

    def snap(uid, n_pages=2):
        s = KVSnapshot(tokens=[uid] * (n_pages * PAGE),
                       seen_tokens=n_pages * PAGE, page_size=PAGE,
                       block_shape=(2, PAGE, 2, 2, 4), dtype="float32",
                       source="test")
        s.add_chunk(np.zeros((2, n_pages, PAGE, 2, 2, 4), np.float32))
        s.complete = True
        return s

    cfg = TierConfig(host_capacity_pages=8,
                     host_watermark_hi=0.7, host_watermark_lo=0.3)
    serve, tier = _serve(trained_params, tier_config=cfg)
    for uid in (1, 2, 3):
        assert tier.host.put_seq(uid, snap(uid))
    assert tier.host.pages_used == 6                  # 6/8 = 0.75 >= hi
    out = tier.enforce_watermarks()
    assert out["host_dropped"] == 4
    # coldest-first: uids 1 and 2 (stalest) died, 3 (newest) survives
    assert tier.host.peek_seq(1) is None and tier.host.peek_seq(2) is None
    assert tier.host.peek_seq(3) is not None
    assert tier.host.pages_used == 2 <= int(cfg.host_watermark_lo * 8)
    assert tier.stats["watermark_host_drops"] == 4
    # hysteresis: below hi now — no further drops
    assert tier.enforce_watermarks() == {"device_demoted": 0, "host_dropped": 0}
    assert tier.host.pages_used == sum(tier.host._lru.values())
