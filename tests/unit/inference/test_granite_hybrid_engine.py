"""Granite 4.0-H through the engine (``test_granite_hybrid.py`` holds the model,
the recurrence's two forms and the twin, and the small size and weights this
file uses): ``InferenceEngineV2`` over state slots, what it refuses in words,
and the registry's entry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import SlotPagesGeometry
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.inference.v2.spec import SpecConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.granite_hybrid_cache import GraniteHybridForCausalLMWithCache, slot_state_bytes
from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

from reference_greedy import greedy
from test_granite_hybrid import CFG, CHUNK, KV, PAGE, _full, ids, params  # noqa: F401 (the fixtures are this module's too)


# ------------------------------------------------------------------ (d) the engine


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
    fused decode through the kernel, slots allocated with the sequences and
    released at their flush; a second round in the released slots gives the
    same tokens."""
    eng = _engine(params)
    assert isinstance(eng.kv.geometry, SlotPagesGeometry) and eng.kv.geometry.window is None
    assert eng.kv.max_tokens_per_seq == (KV.max_pages_per_seq - 1) * PAGE
    assert eng.warm_all()["fallback"] == 0
    prompts = [ids[0, :70].tolist(), ids[1, :45].tolist()]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=12)
        assert eng.kv.slot_allocator.free_pages == 4 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=12)
    assert first == continuations and second == continuations[::-1]


def test_more_requests_than_slots_wait_at_admission_and_all_finish(params, ids, continuations):
    """``ServingEngine`` over two slots: five requests, never more than two
    sequences in the engine, every request gets its tokens, and the first
    two's are the full-sequence model's."""
    from deepspeed_tpu.serving import RequestState, ServingEngine, VirtualClock
    prompts = [ids[i % 2, :n].tolist() for i, n in enumerate((70, 45, 33, 90, 17))]
    with jax.default_matmul_precision("highest"):
        serve = ServingEngine(_engine(params, max_seqs=2), clock=VirtualClock())
        reqs = [serve.submit(p, max_new_tokens=12) for p in prompts]
        most = 0
        while any(not r.state.terminal for r in reqs):
            serve.tick()
            most = max(most, len(serve.engine.state.seqs))
    assert most == 2 and [r.state for r in reqs] == [RequestState.DONE] * 5
    assert [list(r.tokens) for r in reqs[:2]] == continuations and all(len(r.tokens) == 12 for r in reqs)
    assert serve.engine.kv.slot_allocator.free_pages == 2


def test_step_records_count_the_state_bytes_a_step_moves(params, ids):
    eng = _engine(params)
    anat = eng.set_anatomy(StepAnatomy())
    eng.generate([ids[0, :70].tolist()], max_new_tokens=6)
    rows = [r.to_row() for r in anat.steps]
    fed = sum(r["tokens_real"] for r in rows)
    assert sum(r["ssm_rows"] for r in rows) == fed and all(r["window_rows_visible"] == 0 for r in rows)
    assert sum(r["attn_rows_visible"] for r in rows) == int((np.arange(fed) + 1).sum())
    state = slot_state_bytes(CFG)
    assert state == 4 * 6 * 16 * 32 * 32
    for r in rows:      # a chunk step moves the row's states once each way, a fused dispatch of k rounds k times
        calls = r["tokens_real"] if r["key"].startswith("multi") else 1
        assert r["ssd_state_bytes"] == 2 * state * calls, r


# ------------------------------------------------------- (e) what is refused, in words


def test_prefix_cache_speculation_and_snapshots_are_refused(params):
    from deepspeed_tpu.serving.kvtransfer.snapshot import KVExporter
    with pytest.raises(NotImplementedError, match="prefix cache over SlotPagesGeometry"):
        _engine(params, enable_prefix_cache=True)
    with pytest.raises(NotImplementedError, match="speculative decoding over SlotPagesGeometry"):
        _engine(params, spec=SpecConfig())
    eng = _engine(params)
    eng.put([1], [[5, 6, 7]])
    eng.step()
    with pytest.raises(NotImplementedError, match="export_pages over SlotPagesGeometry"):
        eng.kv.export_pages(eng.cache, eng.state.seqs[1].pages)
    with pytest.raises(NotImplementedError, match="KVSnapshot export over SlotPagesGeometry"):
        KVExporter(eng, 1)


def test_registry_names_the_twin_and_its_geometry():
    twin = cache_twin(CFG)
    assert isinstance(twin.model(CFG, page_size=PAGE), GraniteHybridForCausalLMWithCache)
    geometry = cache_geometry(CFG, PAGE)
    assert geometry.state_slots and geometry.window is None and geometry.state_bytes == slot_state_bytes(CFG)
    assert twin.pages({"pages": 1, "ssm": 2, "conv": 3}) == 1
