"""Phi-4-mini-flash through the engine (``test_phi4flash.py`` holds the model
and the twin, and the small size, weights and tolerance this file uses):
``InferenceEngineV2`` over state slots and rings, what it refuses in words,
the registry's entry, and the one trace that layers of one configuration
share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import SlotPagesGeometry
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.inference.v2.spec import SpecConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLayer
from deepspeed_tpu.models.phi4flash_cache import (Phi4FlashForCausalLMWithCache, _apply_layer, _memory_mix, init_cache,
                                                  layer_traced_once, ring_pages)
from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

from reference_greedy import greedy
from test_phi4flash import CFG, CHUNK, KV, PAGE, WINDOW, _full, _table, ids, params  # noqa: F401 (the fixtures are this module's too)


# ------------------------------------------------------------------ (c) the engine


def _engine(params, max_seqs=4, **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                                  decode_bucket=max_seqs),
                  max_new_tokens=12, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32)
    return InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(**{**fields, **over}))


@pytest.fixture(scope="module")
def continuations(params, ids):
    """Greedy continuations by the full-sequence model."""
    return [greedy(_full, params, ids[i, :n], 12, 96, "highest") for i, n in ((0, 70), (1, 45))]


def test_engine_serves_two_sequences_and_reuses_their_slots(params, ids, continuations):
    """``InferenceEngineV2 -> warm_all -> generate``: prefill in chunks of 32,
    fused decode, slots allocated with the sequences and released at their
    flush; a second round in the released slots gives the same tokens."""
    eng = _engine(params)
    assert isinstance(eng.kv.geometry, SlotPagesGeometry) and eng.kv.table_width == KV.max_pages_per_seq
    assert eng.kv.max_tokens_per_seq == (KV.max_pages_per_seq - 1) * PAGE
    assert eng.warm_all()["fallback"] == 0
    prompts = [ids[0, :70].tolist(), ids[1, :45].tolist()]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=12)
        assert eng.kv.slot_allocator.free_pages == 4 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=12)
    assert first == continuations and second == continuations[::-1]


def test_preempted_sequence_is_prefilled_again_from_its_tokens(params, ids, continuations):
    eng = _engine(params)
    with jax.default_matmul_precision("highest"):
        eng.put([7], [ids[0, :70].tolist()], max_new_tokens=12)
        while len(eng.state.seqs[7].generated) < 5:
            eng.step()
        seq = eng.preempt(7)
        assert seq.slot == 0 and not seq.pages and eng.kv.slot_allocator.free_pages == 4
        done = len(seq.generated)
        eng.put([8], [seq.tokens], max_new_tokens=12 - done)
        while not eng.state.seqs[8].done:
            eng.step()
    assert seq.generated + eng.state.seqs[8].generated == continuations[0]


def test_more_requests_than_slots_wait_at_admission_and_all_finish(params, ids):
    """``ServingEngine`` over two slots: five requests, never more than two
    sequences in the engine, no tick raises, every request gets its tokens,
    and the first two's are the unloaded engine's."""
    from deepspeed_tpu.serving import RequestState, ServingEngine, VirtualClock
    prompts = [ids[i % 2, :n].tolist() for i, n in enumerate((70, 45, 33, 90, 17))]
    with jax.default_matmul_precision("highest"):
        golden = _engine(params).generate(prompts[:2], max_new_tokens=12)
        serve = ServingEngine(_engine(params, max_seqs=2), clock=VirtualClock())
        reqs = [serve.submit(p, max_new_tokens=12) for p in prompts]
        most = 0
        while any(not r.state.terminal for r in reqs):
            serve.tick()
            most = max(most, len(serve.engine.state.seqs))
    assert most == 2 and [r.state for r in reqs] == [RequestState.DONE] * 5
    assert [list(r.tokens) for r in reqs[:2]] == golden and all(len(r.tokens) == 12 for r in reqs)
    assert serve.engine.kv.slot_allocator.free_pages == 2


def test_step_records_count_slots_scan_rows_and_window_rows(params, ids):
    eng = _engine(params)
    anat = eng.set_anatomy(StepAnatomy())
    eng.generate([ids[0, :70].tolist()], max_new_tokens=6)
    rows = [r.to_row() for r in anat.steps]
    fed = sum(r["tokens_real"] for r in rows)
    t = np.arange(fed)
    assert sum(r["ssm_rows"] for r in rows) == fed
    assert sum(r["window_rows_visible"] for r in rows) == int(np.minimum(t + 1, WINDOW).sum())
    assert sum(r["attn_rows_visible"] for r in rows) == int((t + 1).sum())


# ------------------------------------------------------- (d) what is refused, in words


def test_prefix_cache_speculation_snapshots_and_host_tier_are_refused(params):
    from deepspeed_tpu.serving.kvtier.tier import TieredKVManager
    from deepspeed_tpu.serving.kvtransfer.snapshot import KVSnapshot, KVExporter, import_snapshot
    with pytest.raises(NotImplementedError, match="prefix cache over SlotPagesGeometry"):
        _engine(params, enable_prefix_cache=True)
    with pytest.raises(NotImplementedError, match="speculative decoding over SlotPagesGeometry"):
        _engine(params, spec=SpecConfig())
    # the rings are sized for the scheduler's chunk; a twin handed narrower ones says so
    wide = _engine(params, scheduler=SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=64, decode_bucket=4))
    assert wide.cache["ring"].shape[1] == 1 + 5 * ring_pages(CFG, PAGE, 64) == 1 + 5 * 7
    with pytest.raises(ValueError, match="a chunk of 64 tokens: the cache's rings of 5 pages hold the window and 32"):
        Phi4FlashForCausalLMWithCache(CFG, page_size=PAGE).apply(
            params, jnp.zeros((1, 64), jnp.int32), jnp.zeros((1, ), jnp.int32), jnp.asarray([_table(1, 8, slot=1)]),
            init_cache(CFG, KV, jnp.float32, 5, CHUNK))
    eng = _engine(params)
    eng.put([1], [[5, 6, 7]])
    eng.step()
    with pytest.raises(NotImplementedError, match="export_pages over SlotPagesGeometry"):
        eng.kv.export_pages(eng.cache, eng.state.seqs[1].pages)
    with pytest.raises(NotImplementedError, match="import_pages over SlotPagesGeometry"):
        eng.kv.import_pages(eng.cache, [1], np.zeros(1))
    with pytest.raises(NotImplementedError, match="KVSnapshot export over SlotPagesGeometry"):
        KVExporter(eng, 1)
    snapshot = KVSnapshot(tokens=[5, 6, 7], seen_tokens=3, page_size=PAGE, block_shape=(1, PAGE, 2, 1, 64),
                          dtype="float32")
    snapshot.complete = True
    with pytest.raises(NotImplementedError, match="KVSnapshot import over SlotPagesGeometry"):
        import_snapshot(eng, 2, [5, 6, 7], snapshot, 4)
    with pytest.raises(NotImplementedError, match="HostKVTier over SlotPagesGeometry"):
        TieredKVManager(eng)


def test_registry_names_the_twin_and_its_geometry():
    twin = cache_twin(CFG)
    assert isinstance(twin.model(CFG, page_size=PAGE), Phi4FlashForCausalLMWithCache)
    geometry = cache_geometry(CFG, PAGE)
    assert geometry.state_slots and geometry.window == WINDOW and cache_geometry(Phi4FlashConfig(), 16).window == 512


class _TwoLayersOfOneConfiguration(nn.Module):
    """Two gated memory units that differ by name and parameters alone."""
    traced: bool

    @nn.compact
    def __call__(self, x, memory):
        out = []
        for name in ("first", "second"):
            layer = Phi4FlashLayer(CFG, "gmu", name=name)
            if self.traced:
                out.append(layer_traced_once(layer, _memory_mix, (), x, memory)[0])
            else:
                out.append(layer(x, lambda mixer, h: _memory_mix(mixer, h, memory))[0])
        return out


def test_layers_of_one_configuration_share_a_trace_and_not_their_parameters():
    """``layer_traced_once`` keys its jitted function on the layer without its
    name, so two layers of one configuration are traced once between them;
    their parameters are arguments of that function, and each gives what it
    gives when called as it is."""
    x, memory = (jax.random.normal(jax.random.PRNGKey(i), (5, width)) for i, width in ((1, CFG.hidden_size), (2, CFG.d_inner)))
    variables = _TwoLayersOfOneConfiguration(True).init(jax.random.PRNGKey(0), x, memory)
    assert set(variables["params"]) == {"first", "second"}                   # made under the layers' own names
    before = _apply_layer._cache_size()
    first, second = _TwoLayersOfOneConfiguration(True).apply(variables, x, memory)
    assert _apply_layer._cache_size() == before + 1
    want_first, want_second = _TwoLayersOfOneConfiguration(False).apply(variables, x, memory)
    np.testing.assert_allclose(first, want_first, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(second, want_second, rtol=1e-6, atol=1e-6)
    assert float(jnp.max(jnp.abs(first - second))) > 1e-2                    # and they are two layers
    swapped = {"params": {"first": variables["params"]["second"], "second": variables["params"]["first"]}}
    np.testing.assert_allclose(_TwoLayersOfOneConfiguration(True).apply(swapped, x, memory)[0], want_second, rtol=1e-6,
                               atol=1e-6)
