"""Trinity through rings and pages (``test_trinity.py`` holds the small size
and the weights this file uses): steps of the twin on scattered pages in
slots other than 0, prompts past two laps of a ring, runs of chunks against a
chunk a step, all against the plain reference's full forward; then
``InferenceEngineV2`` with the scheduler: greedy tokens against the padded,
jitted full-sequence model, runs ended by ``chunk_limit``, what it refuses in
words, the registry's entry and the step records' counts."""

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
from deepspeed_tpu.models.trinity_cache import TrinityForCausalLMWithCache, init_cache, ring_pages

from reference_greedy import greedy
from test_trinity import CFG, TOL, WIDTH, _full, draw, reference

PAGE, CHUNK, SLOTS = 16, 32, 6
KV = PagedKVConfig(num_pages=96, page_size=PAGE, max_pages_per_seq=24)
RING = ring_pages(CFG, PAGE) * PAGE          # 144 rows: a prompt of 330 laps it twice


@pytest.fixture(scope="module")
def params():
    return draw(CFG)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, WIDTH)


@pytest.fixture(scope="module")
def want(params, ids):
    return reference(params, ids)


def _tables():
    """Two sequences' rows: scattered pages of the arena, slots 4 and 2."""
    pages = np.random.default_rng(5).permutation(np.arange(1, KV.num_pages))
    rows = np.zeros((2, KV.max_pages_per_seq), np.int32)
    rows[0, :23], rows[1, :23] = pages[:23], pages[23:46]
    rows[:, -1] = (4, 2)
    return rows


TABLES = _tables()
_TWIN = TrinityForCausalLMWithCache(CFG, page_size=PAGE)
_APPLY = jax.jit(lambda p, c, t, s, bt, l, groups: _TWIN.apply(p, t, s, bt, c, l, groups=groups), static_argnums=6)


def _step(params, cache, ids, rows, decode=()):
    """One step through the twin: ``rows`` (table row, first position, tokens)
    are the rows of a prefill group ``CHUNK`` wide (table row -1: padding),
    ``decode`` (table row, position) the rows of a group of one token before
    it.  -> (per row its tokens' logits, the cache)."""
    groups = ((len(decode), 1), ) * bool(decode) + ((len(rows), CHUNK), )
    rect = np.zeros((len(rows), CHUNK), np.int32)
    for j, (_, at, n) in enumerate(rows):
        rect[j, :n] = ids[at:at + n]
    toks = np.concatenate([np.asarray([ids[at] for _, at in decode], np.int32), rect.reshape(-1)])
    order = np.asarray([r for r, _ in decode] + [r for r, _, _ in rows])
    tables = np.where(order[:, None] >= 0, TABLES[np.maximum(order, 0)], 0)
    with jax.default_matmul_precision("highest"):
        logits, cache = _APPLY(params, cache, jnp.asarray(toks),
                               jnp.asarray([at for _, at in decode] + [at for _, at, _ in rows], jnp.int32),
                               jnp.asarray(tables), jnp.asarray([1] * len(decode) + [n for _, _, n in rows], jnp.int32),
                               groups)
    logits = np.asarray(logits)
    at_row = len(decode) + CHUNK * np.arange(len(rows))
    return [logits[j:j + 1] for j in range(len(decode))] + [logits[t0:t0 + n] for t0, (_, _, n) in zip(at_row, rows)], \
        cache


def _fresh():
    """A cache whose every row holds something: what a ring's stale laps and a null page must not leak."""
    cache = init_cache(CFG, KV, jnp.float32, SLOTS, CHUNK)
    return {**cache, "ring": cache["ring"].at[:, 1:].add(0.5), "pages": cache["pages"].at[:, 1:].add(-0.25)}


# ---------------------------------------------------------------- (a) steps of the twin


@pytest.mark.parametrize("rows_a_step", [1, 2], ids=["a_chunk_a_step", "runs_of_two"])
def test_prefill_past_two_laps_then_decode_equals_the_references_full_forward(params, ids, want, rows_a_step):
    """330 prompt tokens (2.3 laps of the ring of 144 rows, five windows) in
    chunks of 32 on scattered pages in slot 4, a chunk a step or two
    consecutive chunks as rows of one step (a run as wide as the ring's
    slack), then 22 decode steps of one token: every position's logits."""
    cache, prompt, got = _fresh(), 330, []
    starts = list(range(0, prompt, CHUNK))
    for i in range(0, len(starts), rows_a_step):
        rows = [(0, s, min(CHUNK, prompt - s)) for s in starts[i:i + rows_a_step]]
        rows += [(-1, 0, 0)] * (rows_a_step - len(rows))
        out, cache = _step(params, cache, ids, rows)
        got += [o for o, (_, _, n) in zip(out, rows) if n]
    for at in range(prompt, WIDTH):
        out, cache = _step(params, cache, ids, [(-1, 0, 0)], decode=[(0, at)])
        got.append(out[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=TOL)
    # slot 2 and the rings' null page were never written
    n_ring = RING // PAGE
    untouched = np.asarray(cache["ring"])[:, 1 + 2 * n_ring:1 + 3 * n_ring]
    assert (untouched == 0.5).all() and (np.asarray(cache["ring"])[:, 0] == 0).all()


def test_a_run_beside_another_prompts_row_and_a_decode_row(params, ids, want):
    """A mixed step: a decode row of sequence 1 (slot 2) at position 150, and
    in the prefill group a run of two chunks of sequence 0 (slot 4) from
    position 192, past the ring's first lap; each against the reference, and
    the rings and pages it leaves against the same work a step each."""
    cache = _fresh()
    for s in range(0, 192, CHUNK):      # sequence 1 holds 150 tokens, sequence 0 six chunks
        _, cache = _step(params, cache, ids, [(0, s, CHUNK), (1, s, min(CHUNK, 150 - s)) if s < 150 else (-1, 0, 0)])
    rows, decode = [(0, 192, CHUNK), (0, 224, CHUNK)], [(1, 150)]
    got, after = _step(params, cache, ids, rows, decode)
    np.testing.assert_allclose(got[0], want[150:151], atol=TOL)
    np.testing.assert_allclose(np.concatenate(got[1:]), want[192:256], atol=TOL)
    a_step_each = cache
    _, a_step_each = _step(params, a_step_each, ids, [(-1, 0, 0)], decode)
    for row in rows:
        _, a_step_each = _step(params, a_step_each, ids, [row])
    for name in ("ring", "pages"):
        np.testing.assert_allclose(np.asarray(after[name])[:, 1:], np.asarray(a_step_each[name])[:, 1:], atol=2e-5)


def test_a_run_longer_than_the_rings_slack_is_far_from_the_reference(params, ids, want):
    """The guard of the guard: four chunks of one sequence in one step from
    position 192 are 128 tokens where the ring's slack is 64 and a page: the
    fourth row's writes land on rows the first row's queries still see."""
    cache = _fresh()
    for s in range(0, 192, CHUNK):
        _, cache = _step(params, cache, ids, [(0, s, CHUNK)])
    got, _ = _step(params, cache, ids, [(0, 192 + CHUNK * j, CHUNK) for j in range(4)])
    assert np.abs(got[0] - want[192:224]).max() > 100 * TOL


def test_a_chunk_wider_than_the_rings_slack_is_refused(params):
    with pytest.raises(ValueError, match="run_tokens = 64"):
        init_cache(CFG, KV, jnp.float32, SLOTS, 128)
    cache = init_cache(CFG, KV, jnp.float32, SLOTS, CHUNK)
    with pytest.raises(ValueError, match="a chunk of 128 tokens"):
        _TWIN.apply(params, jnp.zeros((1, 128), jnp.int32), jnp.zeros((1, ), jnp.int32),
                    jnp.zeros((1, KV.max_pages_per_seq), jnp.int32), cache)


# ---------------------------------------------------------------- (b) the engine


def _engine(params, max_seqs=8, **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=max_seqs + 4 * CHUNK, max_seqs=max_seqs,
                                                  prefill_chunk=CHUNK, decode_bucket=max_seqs),
                  max_new_tokens=12, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32)
    return InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(**{**fields, **over}))


def _serve(params, prompt, run_rows, new=12):
    """The prompt through the engine: (what its rings and pages hold when the
    prompt is in, the greedy tokens, the steps' records)."""
    eng = _engine(params)
    assert eng.scheduler.run_rows == 4 and eng.kv.geometry.chunk_runs
    eng.scheduler.run_rows = run_rows
    with jax.default_matmul_precision("highest"):
        eng.put([9], [prompt])      # slot 1; a sequence before it would take it
        seq = eng.state.seqs[9]
        while seq.in_prefill and not seq.in_decode:
            eng.step()
        n_ring = RING // PAGE
        held = {"ring": np.asarray(eng.cache["ring"])[:, 1 + seq.slot * n_ring:1 + (seq.slot + 1) * n_ring],
                "pages": np.asarray(eng.cache["pages"])[:, list(seq.pages)]}
        while not seq.done:
            eng.step()
    return held, list(seq.generated)[:new], [s.to_row() for s in eng.anatomy.steps]


@pytest.fixture(scope="module")
def prompt():
    return np.random.default_rng(11).integers(1, CFG.vocab_size, 10 * CHUNK + 11).tolist()


@pytest.fixture(scope="module")
def served(params, prompt):
    return _serve(params, prompt, 4), _serve(params, prompt, 1)


def test_the_engine_ends_a_run_where_the_rings_slack_ends(served):
    """A prompt of 10 chunks and 11 tokens under a rung of four rows: the
    geometry's ``chunk_limit`` gives a sequence 64 tokens a step, runs of two
    rows, six steps; with ``run_rows`` 1 eleven steps of a row."""
    (_, _, rows), (_, _, rows_1) = served
    prefill, prefill_1 = ([r for r in some if r["rows_prefill"]] for some in (rows, rows_1))
    assert [r["rows_prefill"] for r in prefill] == [2, 2, 2, 2, 2, 1] and all(r["seqs_prefill"] == 1 for r in prefill)
    assert [r["tokens_real"] for r in prefill] == [64] * 5 + [11]
    assert [r["rows_prefill"] for r in prefill_1] == [1] * 11
    assert {r["key"] for r in prefill} == {"step:b8:c1:b4:c32", "step:b8:c1:b1:c32"}


def test_a_prompt_served_with_runs_leaves_what_a_chunk_a_step_leaves_and_the_models_tokens(params, prompt, served):
    (held, tokens, _), (held_1, tokens_1, _) = served
    for name in held:
        assert np.abs(held[name]).max() > 0.1
        np.testing.assert_allclose(held[name], held_1[name], atol=2e-5, rtol=1e-5)
    assert len(tokens) == 12 and tokens == tokens_1
    assert tokens == greedy(_full, params, prompt, 12, WIDTH, "highest")


def test_step_records_count_window_rows_ring_rows_and_the_full_layers_rows(served):
    (_, _, rows), _ = served
    fed = sum(r["tokens_real"] for r in rows)
    positions = np.arange(fed)
    assert sum(r["window_rows_visible"] for r in rows) == int(np.minimum(positions + 1, 64).sum())
    assert sum(r["attn_rows_visible"] for r in rows) == int((positions + 1).sum())
    for r in rows:      # a chunk row holds its ring once, a fused dispatch of k rounds k times
        calls = r["tokens_real"] if r["key"].startswith("multi") else r["rows_prefill"] + r["rows_decode"]
        assert r["ring_rows_held"] == RING * calls, r
        assert 0 < r["ring_rows_seen"] <= 64 * calls
    assert all(r["expert_rows"] == r["tokens_real"] * CFG.num_experts_per_tok for r in rows)


def test_engine_serves_two_sequences_and_reuses_their_slots(params, ids):
    eng = _engine(params, max_seqs=4)
    assert eng.warm_all()["fallback"] == 0
    assert isinstance(eng.kv.geometry, SlotPagesGeometry) and eng.kv.geometry.window == 64
    prompts = [ids[:170].tolist(), ids[100:145].tolist()]
    want = [greedy(_full, params, p, 12, WIDTH, "highest") for p in prompts]
    with jax.default_matmul_precision("highest"):
        first = eng.generate(prompts, max_new_tokens=12)
        assert eng.kv.slot_allocator.free_pages == 4 and eng.kv.allocator.free_pages == KV.num_pages - 1
        second = eng.generate(prompts[::-1], max_new_tokens=12)
    assert first == want and second == want[::-1]


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
    assert isinstance(twin.model(CFG, page_size=PAGE), TrinityForCausalLMWithCache)
    geometry = cache_geometry(CFG, PAGE)
    assert type(geometry) is SlotPagesGeometry and geometry.state_slots and geometry.window == CFG.sliding_window
    assert geometry.chunk_runs and geometry.run_tokens == CFG.run_tokens and geometry.ring_rows == RING
    assert twin.pages({"pages": 1, "ring": 2}) == 1
