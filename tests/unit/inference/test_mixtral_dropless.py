"""Served Mixtral on the dropless sorted dispatch: a chunk's padding goes to
no expert and changes no real position's logits, the serving model equals the
training model with ``drop_tokens=False`` on the same weights, and the engine's
step records count the rows the experts multiplied."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
from deepspeed_tpu.models.llama_cache import PagedKVConfig, init_kv_cache
from deepspeed_tpu.models.mixtral import PRESETS, MixtralForCausalLM
from deepspeed_tpu.models.mixtral_cache import MixtralForCausalLMWithCache

CFG = dataclasses.replace(PRESETS["tiny"], dtype=jnp.float32, remat=False, drop_tokens=False)
KV = PagedKVConfig(num_pages=32, page_size=4, max_pages_per_seq=4)
CHUNK = 8
ROW = [5, 9, 2, 7, 1]


@pytest.fixture(scope="module", params=["grouped", "dense"])
def served(request):
    """(params, jitted chunk forward) under each form of the dropless path:
    a serving step of few tokens takes the dense one, a mixed step the
    sorted dispatch, and both must hold what is tested here."""
    from deepspeed_tpu.moe import sharded_moe
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sharded_moe, "takes_sorted", lambda s, k, e: request.param == "grouped")
        yield _served()


def _served():
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    params = MixtralForCausalLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, CHUNK), jnp.int32))
    model = MixtralForCausalLMWithCache(CFG, page_size=KV.page_size)

    @jax.jit
    def chunk_logits(tokens, chunk_lens):
        b = tokens.shape[0]
        tables = 1 + jnp.arange(b * KV.max_pages_per_seq, dtype=jnp.int32).reshape(b, -1)  # page 0 is the null page
        logits, _ = model.apply(params, tokens, jnp.zeros((b, ), jnp.int32), tables,
                                init_kv_cache(CFG, KV, jnp.float32), chunk_lens)
        return logits

    return params, chunk_logits


def _rows(*rows):
    tokens = np.zeros((len(rows), max(len(r) for r in rows)), np.int32)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r
    return jnp.asarray(tokens)


def test_real_positions_read_the_same_logits_whatever_the_padding(served):
    """A row alone at its own length, the row beside an empty row and a
    longer one, and the row with its chunk padded to C: the logits at its
    real positions agree (guards the mask and the un-sort)."""
    _, chunk_logits = served
    n = len(ROW)
    alone = np.asarray(chunk_logits(_rows(ROW), jnp.asarray([n], jnp.int32)))[0]
    beside = np.asarray(chunk_logits(_rows([3, 3, 3, 3, 3, 3, 3], ROW, [], [4, 6]),
                                     jnp.asarray([7, n, 0, 2], jnp.int32)))[1, :n]
    padded = np.asarray(chunk_logits(_rows(ROW + [0] * (CHUNK - n)), jnp.asarray([n], jnp.int32)))[0, :n]
    np.testing.assert_allclose(beside, alone, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(padded, alone, atol=2e-5, rtol=2e-5)
    # other tokens in the padding do not move them either
    junk = np.asarray(chunk_logits(_rows(ROW + [11, 12, 13]), jnp.asarray([n], jnp.int32)))[0, :n]
    np.testing.assert_allclose(junk, alone, atol=2e-5, rtol=2e-5)


def test_serving_model_equals_training_model_without_dropping(served):
    params, chunk_logits = served
    tokens = _rows(ROW + [8, 4, 6])
    want, _ = MixtralForCausalLM(CFG).apply(params, tokens)
    got = chunk_logits(tokens, jnp.asarray([CHUNK], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_blocks_read_their_experts_in_the_scanned_stack(served, monkeypatch):
    """The serving trunk hands every block the stacked banks and its index;
    a block that reads its own slice of them instead gives the same logits."""
    params, chunk_logits = served
    model = MixtralForCausalLMWithCache(CFG, page_size=KV.page_size)
    assert model.bind(params)._stacked_banks()[0].shape[:2] == (CFG.num_hidden_layers, CFG.num_local_experts)
    tokens, lens = _rows(ROW + [8, 4, 6], ROW), jnp.asarray([CHUNK, len(ROW)], jnp.int32)
    got = chunk_logits(tokens, lens)
    monkeypatch.setattr(MixtralForCausalLMWithCache, "_stacked_banks", lambda self: None)
    tables = 1 + jnp.arange(2 * KV.max_pages_per_seq, dtype=jnp.int32).reshape(2, -1)
    want, _ = model.apply(params, tokens, jnp.zeros((2, ), jnp.int32), tables, init_kv_cache(CFG, KV, jnp.float32), lens)
    np.testing.assert_allclose(np.asarray(got)[1, :len(ROW)], np.asarray(want)[1, :len(ROW)], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0], atol=2e-5, rtol=2e-5)


def test_step_records_count_expert_rows(served):
    """``expert_rows`` = ``tokens_real`` x experts a token, on every record of
    an engine that serves an expert model."""
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.serving import VirtualClock
    from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy
    params, _ = served
    sched = SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=CHUNK, decode_bucket=2)
    eng = build_engine(CFG, params, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=40, page_size=4, max_pages_per_seq=16), scheduler=sched,
        kv_dtype=jnp.float32, decode_steps_per_dispatch=4, max_new_tokens=6))
    anat = eng.set_anatomy(StepAnatomy(clock=VirtualClock()))
    eng.generate([ROW, list(range(1, 13))], max_new_tokens=6)
    assert len(anat.steps) >= 3 and {r.path for r in anat.steps} >= {"prefill", "multi_decode"}
    assert all(r.expert_rows == r.tokens_real * CFG.num_experts_per_tok > 0 for r in anat.steps)
    assert all("expert_rows" in r.to_row() for r in anat.steps)


@pytest.mark.parametrize("kernel_path", [True, False], ids=["one_tpu_device", "cpu_or_gspmd"])
def test_step_records_count_the_rows_through_the_grouped_kernel(served, kernel_path, monkeypatch):
    """``expert_rows_kernel``: where the grouped product is the kernel
    ``ds_gmm`` (one TPU device; said here, the CPU's own answer is no) all of
    a step's ``expert_rows`` if its slots take the sorted form, none if every
    expert multiplies every row (a decode step, each round of a fused
    dispatch); where the product is ``ragged_dot``, none at all."""
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine, engine_v2
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.serving import VirtualClock
    from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy
    params, _ = served
    if kernel_path:
        monkeypatch.setattr(engine_v2, "takes_kernel", lambda: True)
    # a decode bucket's rows stay dense, a chunk's slots do not (by the rule itself the toy's 8 experts, 2 a
    # token, would turn sorted at 2 rows: tests/unit/moe/test_expert_form.py holds the rule, test_expert_form_records.py
    # the records under it)
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: s > 4)
    sched = SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=CHUNK, decode_bucket=2)
    eng = build_engine(CFG, params, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=40, page_size=4, max_pages_per_seq=16), scheduler=sched,
        kv_dtype=jnp.float32, decode_steps_per_dispatch=4, max_new_tokens=6))
    anat = eng.set_anatomy(StepAnatomy(clock=VirtualClock()))
    eng.generate([ROW, list(range(1, 13))], max_new_tokens=6)
    chunked = [r for r in anat.steps if r.key.endswith(f":c{CHUNK}")]
    decode = [r for r in anat.steps if r not in chunked]
    assert chunked and decode and all(r.expert_rows > 0 for r in anat.steps)
    assert all(r.expert_rows_kernel == (r.expert_rows if kernel_path else 0) for r in chunked)
    assert all(r.expert_rows_kernel == 0 for r in decode)
    assert all("expert_rows_kernel" in r.to_row() for r in anat.steps)
