"""MiniCPM-SALA through the engine (``test_minicpm_sala.py`` holds the small
size and the weights this file uses): ``InferenceEngineV2`` with the scheduler
over pages, the indexer's cache and state slots, fused decode dispatch
included, and ``ServingEngine`` over it; greedy tokens against the padded,
jitted full-sequence model (``reference_greedy.py``), what it refuses in
words, the registry's entry and the step records' counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.inference.v2.spec import SpecConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.minicpm_sala_cache import (MiniCPMSALAForCausalLMWithCache, SparseSlotPagesGeometry,
                                                     slot_state_bytes)
from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

from reference_greedy import greedy
from test_minicpm_sala import CFG, _full, draw

PAGE, CHUNK, NEW = 8, 32, 10
KV = PagedKVConfig(num_pages=128, page_size=PAGE, max_pages_per_seq=32)
FLASH = dataclasses.replace(CFG, attention_impl="flash")          # the decode rows through the list walk


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 2 * 240).reshape(2, 240)


def _engine(params, max_seqs=2, **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=2 + 4 * CHUNK, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                                  decode_bucket=max_seqs),
                  max_new_tokens=NEW, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32)
    return InferenceEngineV2(FLASH, params, RaggedInferenceEngineConfig(**{**fields, **over}))


@pytest.fixture(scope="module")
def continuations(params, ids):
    """Greedy continuations by the full-sequence model: the first prompt ends
    past ``dense_len`` with blocks to choose among, the second under it."""
    return [greedy(_full, params, ids[i, :n], NEW, 256, "highest") for i, n in ((0, 200), (1, 45))]


@pytest.fixture(scope="module")
def engine(params):
    eng = _engine(params)
    assert eng.warm_all()["fallback"] == 0
    return eng


def test_engine_serves_two_sequences_and_reuses_their_slots(engine, ids, continuations):
    """``InferenceEngineV2 -> warm_all -> generate``: prefill in chunks of 32
    (the blocked walk and the chunked form), fused decode through
    ``ds_sparse_paged_attention`` and ``ds_lightning_update``, slots allocated
    with the sequences and released at their flush; a second round in the
    released slots and pages gives the same tokens."""
    eng = engine
    assert isinstance(eng.kv.geometry, SparseSlotPagesGeometry)
    prompts = [ids[0, :200].tolist(), ids[1, :45].tolist()]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=NEW)
        assert eng.kv.slot_allocator.free_pages == 2 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=NEW)
    assert first == continuations and second == continuations[::-1]
    assert any(key[0] == "multi" for key in eng._step_fns)


def test_more_requests_than_slots_wait_at_admission_and_all_finish(engine, ids, continuations):
    from deepspeed_tpu.serving import RequestState, ServingEngine, VirtualClock
    prompts = [ids[0, :200].tolist(), ids[1, :45].tolist(), ids[1, :90].tolist()]
    with jax.default_matmul_precision("highest"):
        serve = ServingEngine(engine, clock=VirtualClock())
        reqs = [serve.submit(p, max_new_tokens=NEW) for p in prompts]
        most = 0
        while any(not r.state.terminal for r in reqs):
            serve.tick()
            most = max(most, len(serve.engine.state.seqs))
    assert most == 2 and [r.state for r in reqs] == [RequestState.DONE] * 3
    assert [list(r.tokens) for r in reqs[:2]] == continuations and len(reqs[2].tokens) == NEW
    assert serve.engine.kv.slot_allocator.free_pages == 2


def test_step_records_count_what_the_selection_and_the_states_cost(engine, ids):
    eng = engine
    anat = eng.set_anatomy(StepAnatomy())
    eng.generate([ids[0, :200].tolist()], max_new_tokens=6)
    rows = [r.to_row() for r in anat.steps]
    fed = sum(r["tokens_real"] for r in rows)
    assert sum(r["ssm_rows"] for r in rows) == 0
    state = slot_state_bytes(CFG)
    assert state == 4 * 2 * 4 * 32 * 32
    decode = [r for r in rows if r["sparse_decode_rows_read"]]
    assert decode and all(r["lightning_state_bytes"] == 0 for r in rows if r not in decode)
    for r in decode:    # one-token rows: a fused dispatch of k rounds moves the row's states k times each way
        assert r["lightning_state_bytes"] == 2 * state * r["tokens_real"], r
    # 4 lists (2 sparse layers x 2 key heads); positions 200..204 past dense_len 128 name 5 blocks of 32 rows
    assert sum(r["sparse_decode_rows_read"] for r in decode) == 4 * 5 * 32 * (fed - 200)
    assert all(r["attn_rows_walked"] == 0 for r in rows)            # no contiguous walk reads these pages
    geometry = eng.kv.geometry          # under dense_len the selection names every row
    assert geometry.state_counts(50, 1, 1)["sparse_decode_rows_read"] == 4 * 51
    assert geometry.state_counts(0, 32, 1) == {}                                  # a chunk is no decode row


def test_prefix_cache_speculation_and_snapshots_are_refused(params):
    from deepspeed_tpu.serving.kvtransfer.snapshot import KVExporter
    with pytest.raises(NotImplementedError, match="prefix cache over SparseSlotPagesGeometry"):
        _engine(params, enable_prefix_cache=True)
    with pytest.raises(NotImplementedError, match="speculative decoding over SparseSlotPagesGeometry"):
        _engine(params, spec=SpecConfig())
    eng = _engine(params)
    eng.put([1], [[5, 6, 7]])
    eng.step()
    with pytest.raises(NotImplementedError, match="KVSnapshot export over SparseSlotPagesGeometry"):
        KVExporter(eng, 1)


def test_registry_names_the_twin_its_geometry_and_the_page_size_it_needs():
    twin = cache_twin(CFG)
    assert isinstance(twin.model(CFG, page_size=PAGE), MiniCPMSALAForCausalLMWithCache)
    geometry = cache_geometry(CFG, PAGE)
    assert geometry.state_slots and not geometry.chunk_runs and geometry.state_bytes == slot_state_bytes(CFG)
    assert twin.pages({"pages": 1, "ckeys": 2, "state": 3}) == 1 and twin.walk_rows(PAGE, 32) == 0
    with pytest.raises(NotImplementedError, match="page_size 16 must be the selection's kernel_stride 8"):
        twin.model(CFG, page_size=16).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros(1, jnp.int32),
                                           jnp.zeros((1, 3), jnp.int32), None)
