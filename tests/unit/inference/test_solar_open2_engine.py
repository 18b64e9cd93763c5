"""Solar-Open2 through the engine (``test_solar_open2.py`` holds the small
size and the weights this file uses): ``InferenceEngineV2`` with the
scheduler over state slots and pages, greedy tokens against the padded,
jitted full-sequence model (``reference_greedy.py``), what it refuses in
words, the registry's entry and the step records' count of the state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import SlotPagesGeometry
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.inference.v2.spec import SpecConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.solar_open2_cache import SolarOpen2ForCausalLMWithCache, slot_state_bytes
from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

from reference_greedy import greedy
from test_solar_open2 import CFG, _full, draw

PAGE, CHUNK = 16, 32
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * 200).reshape(3, 200)


def _engine(params, max_seqs=4, **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                                  decode_bucket=max_seqs),
                  max_new_tokens=12, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32)
    return InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(**{**fields, **over}))


@pytest.fixture(scope="module")
def continuations(params, ids):
    """Greedy continuations by the full-sequence model."""
    return [greedy(_full, params, ids[i, :n], 12, 96, "highest") for i, n in ((0, 70), (1, 45))]


@pytest.fixture(scope="module")
def engine(params):
    """One warmed engine for the tests that serve: seven programs compiled once."""
    eng = _engine(params)
    assert eng.warm_all()["fallback"] == 0
    return eng


def test_engine_serves_two_sequences_and_reuses_their_slots(engine, ids, continuations):
    """``InferenceEngineV2 -> warm_all -> generate``: prefill in chunks of 32
    (the chunked form), fused decode through ``ds_kda_update``, slots
    allocated with the sequences and released at their flush; a second round
    in the released slots gives the same tokens."""
    eng = engine
    assert isinstance(eng.kv.geometry, SlotPagesGeometry) and eng.kv.geometry.window is None
    prompts = [ids[0, :70].tolist(), ids[1, :45].tolist()]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=12)
        assert eng.kv.slot_allocator.free_pages == 4 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=12)
    assert first == continuations and second == continuations[::-1]


def test_step_records_count_the_state_bytes_a_step_moves(engine, ids):
    eng = engine
    anat = eng.set_anatomy(StepAnatomy())
    eng.generate([ids[0, :70].tolist()], max_new_tokens=6)
    rows = [r.to_row() for r in anat.steps]
    fed = sum(r["tokens_real"] for r in rows)
    assert sum(r["ssm_rows"] for r in rows) == fed
    state = slot_state_bytes(CFG)
    assert state == 4 * 6 * 4 * 32 * 32
    for r in rows:      # a chunk step moves the row's states once each way, a fused dispatch of k rounds k times
        calls = r["tokens_real"] if r["key"].startswith("multi") else 1
        assert r["ssd_state_bytes"] == 2 * state * calls, r
    assert all(r["expert_rows"] == r["tokens_real"] * CFG.num_experts_per_tok for r in rows)


def test_prefix_cache_speculation_and_snapshots_are_refused(params):
    from deepspeed_tpu.serving.kvtransfer.snapshot import KVExporter
    with pytest.raises(NotImplementedError, match="prefix cache over SlotPagesGeometry"):
        _engine(params, enable_prefix_cache=True)
    with pytest.raises(NotImplementedError, match="speculative decoding over SlotPagesGeometry"):
        _engine(params, spec=SpecConfig())
    eng = _engine(params)
    eng.put([1], [[5, 6, 7]])
    eng.step()
    with pytest.raises(NotImplementedError, match="KVSnapshot export over SlotPagesGeometry"):
        KVExporter(eng, 1)


def test_registry_names_the_twin_and_its_geometry():
    twin = cache_twin(CFG)
    assert isinstance(twin.model(CFG, page_size=PAGE), SolarOpen2ForCausalLMWithCache)
    geometry = cache_geometry(CFG, PAGE)
    assert geometry.state_slots and geometry.window is None and geometry.state_bytes == slot_state_bytes(CFG)
    assert twin.pages({"pages": 1, "kda": 2, "conv": 3}) == 1
