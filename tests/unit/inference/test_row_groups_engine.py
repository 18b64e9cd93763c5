"""The engine hands every twin a mixed step in two row groups
(``engine_v2._step_groups``): the decode bucket at one slot a row beside a
prefill group of a rung of rows at the chunk.  Held here: the token streams
(against a row-at-a-time reference that knows no batching), the closure of
``step_shape_set`` over every plan the scheduler can make under the benchmark
cells' scheduler configurations, no compile after ``warm_all``, and what the
step record of a two-group step holds.  The families: the three softmax
twins here and, in ``test_row_groups_engine_slots.py`` (a worker of their
own), the two that hold a state slot a sequence (Phi-4-mini-flash, Granite
4.0-H), whose row-at-a-time reference runs each prompt in a slot of its own.
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
from deepspeed_tpu.inference.v2.ragged import SequenceDescriptor
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.evabyte import EvaByteConfig
from deepspeed_tpu.models.falcon import FalconConfig
from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
from deepspeed_tpu.models.llama import LlamaConfig
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.mixtral import PRESETS as MIXTRAL_PRESETS
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig
from deepspeed_tpu.serving import RequestState, ServingConfig, ServingEngine, VirtualClock
from deepspeed_tpu.telemetry import StepAnatomy

BENCHMARK = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark")
sys.path.insert(0, BENCHMARK)
import step_trace  # noqa: E402

PAGE, CHUNK = 16, 16
CONFIGS = {
    "llama": LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=512,
                         rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False),
    "mixtral": dataclasses.replace(MIXTRAL_PRESETS["tiny"], dtype=jnp.float32, remat=False, drop_tokens=False,
                                   num_hidden_layers=2, max_position_embeddings=512),
    "evabyte": EvaByteConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
                             num_key_value_heads=4, max_position_embeddings=2048, window_size=256,
                             chunk_size=PAGE, dtype=jnp.float32, param_dtype=jnp.float32),
    # the slot-holding twins, at the fewest layers their patterns allow (tests/unit/inference/test_row_groups.py)
    "phi4flash": Phi4FlashConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=8,
                                 num_attention_heads=4, num_key_value_heads=2, sliding_window=32,
                                 max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32),
    "granitehybrid": GraniteHybridConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                         shared_intermediate_size=64, num_hidden_layers=2,
                                         layer_types=("mamba", "attention"), num_attention_heads=4,
                                         num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                                         max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32),
}
#: a twin of ``cache_zoo.py`` that no benchmark cell serves
FALCON = FalconConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_kv_heads=4,
                      alibi=False, parallel_attn=True, bias=False, max_position_embeddings=512, dtype=jnp.float32,
                      remat=False)
KV = PagedKVConfig(num_pages=160, page_size=PAGE, max_pages_per_seq=24)
SCHED = SchedulerConfig(token_budget=40, max_seqs=8, prefill_chunk=CHUNK, decode_bucket=4)
#: the slot-holding twins' cells keep one batch bucket, all their rows: six programs to warm where SCHED has twelve
ONE_BUCKET = dataclasses.replace(SCHED, decode_bucket=SCHED.max_seqs)
NEW = 6


def _cache(cfg):
    """Pages, and for a twin that holds state slots the scratch slot and one more."""
    return cache_twin(cfg).init_cache(cfg, KV, jnp.float32, 2, CHUNK)


def _sched(cfg):
    return ONE_BUCKET if cache_geometry(cfg, PAGE).state_slots else SCHED


@functools.lru_cache(maxsize=None)   # a configuration's twin and weights, once a module
def _params(cfg):
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    twin = build_cache_model(cfg, PAGE)
    table = jnp.zeros((1, KV.max_pages_per_seq), jnp.int32)
    one = jnp.zeros((1, ), jnp.int32)
    return twin, nn.meta.unbox(jax.jit(twin.init)(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), one, table,
                                                  _cache(cfg), jnp.ones((1, ), jnp.int32)))


def _engine(cfg, params, k=1, sched=None, kv=KV):
    return build_engine(cfg, params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched or _sched(cfg), kv_dtype=jnp.float32, decode_steps_per_dispatch=k, max_new_tokens=NEW,
        enable_prefix_cache=False))


def _prompts(cfg, seed=3):
    """Mixed traffic: prompts of less than a chunk, of a chunk and a bit and
    of several chunks, so that under a budget of two and a half chunks some
    rows decode while others are still prefilling, one or several at a time."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in (5, 70, 17, 3, 41, 16, 9, 33)]


def _row_at_a_time(cfg, twin, params, prompts, new=NEW):
    """Greedy streams from the twin fed one sequence at a time as rectangles
    of one row: the prompt in chunks, then one token a step; where a sequence
    holds a state slot, in slot 1 (the row's last column)."""
    table = 1 + np.arange(KV.max_pages_per_seq, dtype=np.int32)[None]
    if cache_geometry(cfg, PAGE).state_slots:
        table[0, -1] = 1
    table = jnp.asarray(table)
    step = jax.jit(lambda c, t, s, n: twin.apply(params, t, s, table, c, n, True))
    out = []
    for prompt in prompts:
        cache, pos, toks = _cache(cfg), 0, list(prompt)
        while len(toks) < len(prompt) + new:
            n = min(CHUNK, len(prompt) - pos) if pos < len(prompt) else 1
            ids = np.zeros((1, CHUNK if n > 1 or pos < len(prompt) else 1), np.int32)
            ids[0, :n] = toks[pos:pos + n]
            logits, cache = step(cache, jnp.asarray(ids), jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32))
            pos += n
            if pos >= len(prompt):
                toks.append(int(jnp.argmax(logits[0, 0])))
        out.append(toks[len(prompt):])
    return out


SLOT_HOLDING = [name for name in sorted(CONFIGS) if cache_geometry(CONFIGS[name], PAGE).state_slots]


def families(names):
    """The fixture ``family`` over ``names`` of ``CONFIGS``."""

    @pytest.fixture(scope="module", params=names)
    def family(request):
        cfg = CONFIGS[request.param]
        twin, params = _params(cfg)
        prompts = _prompts(cfg)
        return cfg, params, prompts, _row_at_a_time(cfg, twin, params, prompts)

    return family


family = families([name for name in sorted(CONFIGS) if name not in SLOT_HOLDING])


def _two_group_rows(anat):
    return [r for r in (s.to_row() for s in anat.steps) if r["key"].count(":b") == 2]


def test_generate_emits_the_row_at_a_time_streams(family):
    cfg, params, prompts, want = family
    eng = _engine(cfg, params)
    assert eng.generate(prompts) == want
    rows = _two_group_rows(eng.anatomy)
    assert rows and any(r["rows_decode"] and r["rows_prefill"] for r in rows), "no mixed step ran in two groups"
    # once fewer than four prompts are left in prefill, the long ones run ahead where the geometry lets them
    assert any(r["rows_prefill"] > r["seqs_prefill"] for r in rows) == eng.kv.geometry.chunk_runs


@pytest.mark.parametrize("name", ["mixtral", "evabyte"])
def test_runs_give_the_streams_of_a_chunk_a_step_in_fewer_steps(name):
    """A prompt of 300 tokens (over EvaByte's window of 256: a run ends where
    the window ends and the next starts behind it) and one of 41, fed in runs
    and, with the scheduler's ``run_rows`` at 1, a chunk a sequence and step
    as every engine fed them: both give the row-at-a-time streams, runs in
    fewer steps, and every token sees the rows it saw (``attn_rows_visible``)."""
    cfg = CONFIGS[name]
    twin, params = _params(cfg)
    prompts = [np.random.default_rng(5).integers(1, cfg.vocab_size, n).tolist() for n in (300, 41)]
    want = _row_at_a_time(cfg, twin, params, prompts)
    steps, visible = {}, {}
    for run_rows in (4, 1):
        eng = _engine(cfg, params)
        assert eng.scheduler.run_rows == 4 and eng.kv.geometry.chunk_runs
        eng.scheduler.run_rows = run_rows
        assert eng.generate(prompts) == want
        rows = [s.to_row() for s in eng.anatomy.steps]
        assert all(r["rows_prefill"] <= 4 for r in rows)
        assert any(r["rows_prefill"] > r["seqs_prefill"] for r in rows) == (run_rows == 4)
        steps[run_rows] = sum(1 for r in rows if r["rows_prefill"])
        visible[run_rows] = sum(r["attn_rows_visible"] for r in rows)
    assert visible[4] == visible[1] and steps[4] < steps[1]


@pytest.mark.parametrize("async_dispatch", [False, True])
def test_both_serving_ticks_emit_the_row_at_a_time_streams_and_compile_nothing(family, async_dispatch):
    cfg, params, prompts, want = family
    eng = _engine(cfg, params, k=4)
    clock = VirtualClock()
    anat = eng.set_anatomy(StepAnatomy(clock=clock))
    warm = eng.warm_all()
    assert warm["fallback"] == 0 and warm["compiled"] == len(eng.step_shape_set())
    programs = set(eng._step_fns)
    serve = ServingEngine(eng, clock=clock, config=ServingConfig(async_dispatch=async_dispatch))
    # arrivals a few ticks apart: later prompts prefill beside rows that decode
    reqs = serve.run([dict(prompt=p, max_new_tokens=NEW, arrival_ts=0.02 * i) for i, p in enumerate(prompts)])
    assert all(r.state is RequestState.DONE for r in reqs)
    assert [r.tokens for r in reqs] == want
    assert set(eng._step_fns) == programs and anat.steady_state_recompiles == 0
    assert sum(r.compiles for r in anat.steps) == 0
    assert _two_group_rows(anat)
    # runs among them: a program of the step set each, the rung of four
    assert any(r.rows_prefill > r.seqs_prefill for r in anat.steps) == eng.kv.geometry.chunk_runs


def test_the_step_record_of_a_two_group_step(family):
    cfg, params, prompts, _ = family
    eng = _engine(cfg, params)
    eng.put([0, 1], [prompts[0], prompts[3]])
    while not all(s.in_decode for s in eng.state.seqs.values()):
        eng.step()
    eng.put([2], [prompts[1]])                      # 70 tokens: chunks of 16 beside two decoding rows
    plan = eng.scheduler.plan(eng.state)
    bucket = _sched(cfg).decode_bucket
    # where the geometry lets a sequence's chunks share a step, what the budget has left
    # of the rung of four rows: 16 + 16 + 4 of 40 - 4; a slot-holding twin's one chunk
    runs = eng.kv.geometry.chunk_runs
    fed, rows = (SCHED.token_budget - bucket, 3) if runs else (CHUNK, 1)
    rung = 4 if runs else 1
    assert len(plan.decode) == 2 and [n for _, n in plan.prefill] == [fed]
    eng.step(plan)
    assert eng.state.seqs[2].seen_tokens == fed and not eng.state.seqs[2].generated
    row = eng.anatomy.last_step.to_row()
    assert row["key"] == f"step:b{bucket}:c1:b{rung}:c16" and row["path"] == "mixed"
    assert row["slots"] == bucket + rung * CHUNK and row["tokens_real"] == plan.planned_tokens == 2 + fed
    assert (row["rows_decode"], row["rows_prefill"], row["seqs_prefill"]) == (2, rows, 1)
    # the key survives _named -> the lowered module's name -> benchmark/step_trace.program_key
    module = eng._aot_lower(((bucket, 1), (rung, CHUNK))).as_text()[:400]
    assert f"jit_ds_step_b{bucket}_c1_b{rung}_c16" in module
    assert step_trace.program_key(f"jit_ds_step_b{bucket}_c1_b{rung}_c16(1234)") == row["key"]
    for _ in range(7):                               # the prompt's other four chunks, then one-token steps
        eng.step()
    rows = [s.to_row() for s in eng.anatomy.steps]
    mixed = sum(1 for r in rows if r["key"] != f"step:b{bucket}:c1")
    assert step_trace.mixed_step_share(rows) == pytest.approx(mixed / len(rows)) and 3 <= mixed < len(rows)
    assert step_trace.slot_fill_share([row]) == pytest.approx((2 + fed) / (bucket + rung * CHUNK))


@pytest.mark.parametrize("name", ["phi4flash", "granitehybrid"])
def test_a_slot_holding_twin_takes_row_groups_and_its_cell_warms_seven_programs(name):
    """Under the scheduler of its benchmark cell (one bucket of 32 rows,
    chunks of 128, eight fused steps) the twin's engine reaches the decode
    step, the decode bucket beside 1, 4 and 32 prefill rows, and three fused
    rungs: no rectangle of 32 x 128."""
    cell = {"phi4flash": "phi4-mini-flash-serve-1chip", "granitehybrid": "granite-4.0-h-micro-serve-1chip"}[name]
    with open(os.path.join(BENCHMARK, "configs", cell + ".json")) as f:
        engine = json.load(f)["engine"]
    cfg = CONFIGS[name]
    _, params = _params(cfg)
    eng = _engine(cfg, params, k=engine["decode_steps_per_dispatch"], sched=SchedulerConfig(**engine["scheduler"]),
                  kv=dataclasses.replace(KV, num_pages=32))
    assert {eng._key_label(k) for k in eng.step_shape_set()} == {
        "step:b32:c1", "step:b32:c1:b1:c128", "step:b32:c1:b4:c128", "step:b32:c1:b32:c128",
        "multi:b32:k8", "multi:b32:k4", "multi:b32:k2"}


def test_a_zoo_twins_engine_reaches_the_decode_bucket_beside_each_prefill_rung_and_no_rectangle_at_the_chunk():
    _, params = _params(FALCON)
    eng = _engine(FALCON, params)
    keys = {eng._key_label(k) for k in eng.step_shape_set()}
    assert keys == {"step:b4:c1", "step:b8:c1"} | {f"step:b{b}:c1:b{rung}:c16" for b in (4, 8) for rung in (1, 4, 8)}
    prompts = _prompts(FALCON)
    outs = eng.generate(prompts[:3])
    assert all(len(o) == NEW for o in outs)
    assert {s.to_row()["key"] for s in eng.anatomy.steps} <= keys


# ------------------------------------------------ every plan has its program


def _cell_schedulers():
    out = {}
    for name in sorted(os.listdir(os.path.join(BENCHMARK, "configs"))):
        with open(os.path.join(BENCHMARK, "configs", name)) as f:
            engine = json.load(f).get("engine")
        if engine and "scheduler" in engine:   # the serving cells' configurations
            out[name[:-len(".json")]] = SchedulerConfig(**engine["scheduler"])
    return out


CELL_SCHEDULERS = _cell_schedulers()


@pytest.mark.parametrize("twin", ["llama", "zoo"])
@pytest.mark.parametrize("cell", sorted(CELL_SCHEDULERS))
def test_every_plan_of_a_cells_scheduler_maps_to_a_key_of_the_step_set(cell, twin):
    """Random populations of decoding and prefilling sequences, up to more
    than the scheduler admits: whatever ``plan`` returns, ``_step_groups``
    names a program of ``step_shape_set`` and holds every row of the plan."""
    sched = CELL_SCHEDULERS[cell]
    cfg = CONFIGS["llama"] if twin == "llama" else FALCON
    _, params = _params(cfg)
    eng = _engine(cfg, params, k=8, sched=sched, kv=dataclasses.replace(KV, num_pages=32))
    keys = set(eng.step_shape_set())
    assert len([k for k in keys if not isinstance(k[0], str)]) <= 4
    rng = np.random.default_rng(0)
    seen = set()
    for trial in range(300):
        eng.state.seqs.clear()
        n_decode, n_prefill = rng.integers(0, sched.max_seqs + 3), rng.integers(0, sched.max_seqs + 3)
        if trial % 3 == 0:
            n_prefill = min(n_prefill, 2)        # the usual population: few prompts arrive at once
        for uid in range(n_decode + n_prefill):
            length = int(rng.integers(1, 4 * sched.prefill_chunk))
            seq = SequenceDescriptor(uid=uid, tokens=[1] * length)
            seq.seen_tokens = length - 1 if uid < n_decode else int(rng.integers(0, length))
            if uid < n_decode:
                seq.generated = [1]
            eng.state.seqs[uid] = seq
        plan = eng.scheduler.plan(eng.state)
        if not plan.decode and not plan.prefill:
            continue
        packed = eng._step_groups(plan)
        groups = tuple((rows, width) for _, rows, width in packed)
        assert groups in keys, (groups, len(plan.decode), plan.prefill)
        # a row a chunk: a run of one sequence's chunks takes several, and only inside the rung of four
        assert all(sum(-(-n // width) for _, n in work) <= rows for work, rows, width in packed)
        assert all(n <= width for work, _, width in packed for _, n in work) or packed[-1][1] == 4
        assert sum(len(work) for work, _, _ in packed) == len(plan.decode) + len(plan.prefill)
        assert sum(n for work, _, _ in packed for _, n in work) == plan.planned_tokens
        seen.add(groups)
    single = {k for k in keys if not isinstance(k[0], str)}
    assert seen == single, f"programs no plan reached: {single - seen}"
