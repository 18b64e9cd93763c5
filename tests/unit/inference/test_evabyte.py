"""EvaByte (chunked linear attention) against its plain reference
(``benchmark/refs/evabyte.py``) on the CPU at a small size: the full-sequence
model, the serving twin through the paged arena, the engine, and the cache
geometry that owns "pages a sequence holds".

Small size: 2 layers, 4 heads of 16, chunk (= page) 16, window 256.  The
window is 256 and not 64 because a window's summary rows must fill whole
16-row pages (``window % chunk^2 == 0``, as the published 2048 / 16 does):
the paged kernel's one-sided mask cannot hide the tail of a summary page.

Everything here is float32, so the tolerances are float32 rounding through
two layers (observed 1e-5 to 4e-5 on logits of size 2): 2e-4.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.geometry import LinearGeometry, RingSummaryGeometry
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, SequenceDescriptor, StateManager
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, SplitFuseScheduler
from deepspeed_tpu.models.cache_zoo import cache_geometry
from deepspeed_tpu.models.evabyte import EvaByteConfig, EvaByteForCausalLM
from deepspeed_tpu.models.evabyte_cache import EvaByteForCausalLMWithCache
from deepspeed_tpu.models.llama import PRESETS
from deepspeed_tpu.models.llama_cache import PagedKVConfig, init_kv_cache
from deepspeed_tpu.ops.paged_attention import walk_block

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import evabyte as ref  # noqa: E402

from reference_greedy import greedy  # noqa: E402

WINDOW, PAGE = 256, 16
CFG = EvaByteConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=2048, window_size=WINDOW, chunk_size=PAGE,
                    dtype=jnp.float32, param_dtype=jnp.float32)
REF_CFG = {f: getattr(CFG, f) for f in ("num_attention_heads", "chunk_size", "window_size", "rope_theta",
                                        "rms_norm_eps", "num_hidden_layers")}
TOL = 2e-4   # float32 rounding through two layers; see the module docstring


@pytest.fixture(scope="module")
def params():
    """Seeded weights with ``adaptive_phi`` and ``adaptive_mu_k`` at unit
    scale (chunk weights far from uniform, ``mu`` as large as a key) and norm
    offsets away from 0: dropping any of them moves the logits by far more
    than TOL."""
    p = nn.meta.unbox(EvaByteForCausalLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    def bump(path, x):
        name = jax.tree_util.keystr(path)
        if "adaptive" in name:
            return jax.random.normal(jax.random.PRNGKey(len(name)), x.shape, x.dtype)
        return jnp.full_like(x, 0.3) if "norm" in name else x

    return jax.tree_util.tree_map_with_path(bump, p)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 3 * WINDOW + 40)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's head-0 logits of the whole sequence."""
    return np.asarray(ref.forward(params, jnp.asarray(ids), REF_CFG)[0])


# ---------------------------------------------------------------- (a) the model


def _full(params, tokens):
    """The full-sequence model; jitted where it is called, one program a length."""
    return EvaByteForCausalLM(CFG).apply(params, tokens)


@pytest.mark.parametrize("length", [10, WINDOW - 1, WINDOW, WINDOW + 1, 3 * WINDOW + 40])
def test_full_sequence_model_matches_reference_on_all_heads(params, ids, length):
    got = jax.jit(_full)(params, jnp.asarray(ids[None, :length]))[0]
    ref_all = ref.forward_all_heads(params, jnp.asarray(ids[:length]), REF_CFG)
    assert got.shape == (length, CFG.num_pred_heads, CFG.vocab_size)
    np.testing.assert_allclose(got, ref_all, atol=TOL)


@pytest.mark.parametrize("drop", ["adaptive_phi", "adaptive_mu_k"])
def test_dropping_a_summary_vector_fails_the_comparison(params, ids, drop):
    """The guard of the guard: with ``phi`` or ``mu`` zeroed in the model the
    comparison above would fail by two orders of magnitude."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if drop in jax.tree_util.keystr(path) else x, params)
    got = jax.jit(_full)(broken, jnp.asarray(ids[None]))[0]
    ref_all = ref.forward_all_heads(params, jnp.asarray(ids), REF_CFG)
    assert float(jnp.max(jnp.abs(got - ref_all)[WINDOW:])) > 100 * TOL     # from the second window on
    np.testing.assert_allclose(got[:WINDOW], ref_all[:WINDOW], atol=TOL)    # no summary is visible before


# ------------------------------------------------------ (b) the twin, through the arena


def _feed(params, ids, steps, table_width=24, n_pages=24, attention_impl="reference"):
    """Prefill then decode one sequence through the twin in the given chunk
    lengths; head 0's logits of every position fed."""
    twin = EvaByteForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    cache = init_kv_cache(CFG, PagedKVConfig(num_pages=64, page_size=PAGE), dtype=jnp.float32)
    table = np.zeros((1, table_width), np.int32)
    table[0, :n_pages] = 1 + np.arange(n_pages)   # a linear table: ring first, summary pages after it
    step = jax.jit(lambda c, t, s, n: twin.apply(params, t, s, jnp.asarray(table), c, n))
    pos, out = 0, []
    for n in steps:
        width = 1 if n == 1 else 48
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = ids[pos:pos + n]
        logits, cache = step(cache, jnp.asarray(toks), jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32))
        out.append(np.asarray(logits[0, :n]))
        pos += n
    return np.concatenate(out)


PLANS = {
    "aligned_chunks": [32] * 18 + [1] * 40,                                   # 576, then decode
    "chunk_ends_mid_page": [32] * 7 + [20, 12] + [32] * 8 + [5, 27] + [1] * 30,   # ends at 244, 517
    "start_no_multiple_of_16": [7, 41, 48, 48, 48, 48, 16] + [32] * 9 + [1] * 30,
    "decode_across_a_window": [48] * 5 + [12] + [1] * 20,                    # 252, then 252..271 one by one
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_twin_prefill_then_decode_matches_reference(params, ids, want, plan):
    steps = PLANS[plan]
    assert all(p // WINDOW == (p + n - 1) // WINDOW for p, n in zip(np.cumsum([0] + steps), steps))
    got = _feed(params, ids, steps)
    np.testing.assert_allclose(got, want[:len(got)], atol=TOL)


def test_twin_matches_reference_through_the_paged_kernel(params, ids, want):
    """The same through ``ds_paged_attention`` (interpreted on the CPU),
    which reads a layer's pages out of the whole arena: prefill into the
    second window, then decode."""
    got = _feed(params, ids, [48] * 5 + [16, 30] + [1] * 6, attention_impl="flash")
    np.testing.assert_allclose(got, want[:len(got)], atol=TOL)


def _manager(num_pages=64, max_pages=48):
    kv = BlockedKVCache(num_pages, PAGE, max_pages, enable_prefix_cache=False,
                        geometry=RingSummaryGeometry(PAGE, WINDOW))
    return StateManager(kv, max_batch=4)


def test_plan_never_makes_a_chunk_that_crosses_a_window():
    """The geometry tells ``plan`` where a chunk must end: whatever the budget
    leaves and wherever a chunk starts, it stays inside one window."""
    mgr = _manager()
    sched = SplitFuseScheduler(SchedulerConfig(token_budget=100, max_seqs=4, prefill_chunk=48, decode_bucket=4))
    seqs = [mgr.get_or_create(u, list(range(1, n + 1))) for u, n in ((0, 700), (1, 300), (2, 513))]
    chunks = []
    while any(s.in_prefill for s in seqs):
        plan = sched.plan(mgr)
        assert plan.prefill
        for s, n in plan.prefill:
            assert 0 < n <= 48 and s.seen_tokens // WINDOW == (s.seen_tokens + n - 1) // WINDOW
            chunks.append(n)
            s.seen_tokens += n
    assert any(n < 48 for n in chunks)   # some chunk was cut at a window's end or by the budget
    # the linear geometry cuts nowhere
    assert LinearGeometry(PAGE).chunk_limit(250, 48) == 48 and RingSummaryGeometry(PAGE, WINDOW).chunk_limit(250, 48) == 6


# ------------------------------------------------------------------ (c) the engine


def _engine(params, cfg=CFG, **over):
    econf = dict(kv=PagedKVConfig(num_pages=128, page_size=PAGE, max_pages_per_seq=40),
                 scheduler=SchedulerConfig(token_budget=96, max_seqs=4, prefill_chunk=48, decode_bucket=4),
                 max_new_tokens=48, enable_prefix_cache=False, decode_steps_per_dispatch=8,
                 kv_dtype=jnp.float32)
    econf.update(over)
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(**econf))


#: greedy continuation by the full-sequence model, head 0: ``_greedy(params, prompt, n)``
_greedy = functools.partial(greedy, lambda p, t: _full(p, t)[:, :, 0], width=300 + 48)


def test_engine_generate_matches_full_sequence_model_over_two_windows(params, ids):
    """Two sequences of unequal length in one batch: one crosses the window
    in its prefill, the other inside a fused 8-step decode dispatch (230 + 48
    tokens pass position 256 in the fourth dispatch).  Greedy tokens are
    compared, not logits: float32 on both sides, and a near-tie would show as
    a mismatch to look at, not to tolerate."""
    prompts = [ids[:230].tolist(), ids[300:600].tolist()]
    eng = _engine(params)
    assert eng.kv.table_width == WINDOW // PAGE + 3 and eng.kv.max_pages_per_seq == 19   # 640 tokens: 16 + 3
    outs = eng.generate(prompts, max_new_tokens=48)
    assert any(k[0] == "multi" for k in eng._step_fns)
    for prompt, out in zip(prompts, outs):
        assert out == _greedy(params, prompt, 48)
    assert eng.kv.allocator.free_pages == 127   # every page came back


def test_step_records_count_summaries_wraps_and_visible_rows(params, ids):
    from deepspeed_tpu.telemetry import StepAnatomy
    eng = _engine(params, dataclasses.replace(CFG, attention_impl="flash"))
    anat = eng.set_anatomy(StepAnatomy())
    eng.generate([ids[:300].tolist()], max_new_tokens=20)
    rows = [r.to_row() for r in anat.steps]
    fed = 319 + sum(r["tokens_discarded"] for r in rows)   # the prompt, 19 sampled tokens, the last rung's overshoot
    t = np.arange(fed)
    assert sum(r["attn_rows_visible"] for r in rows) == int((t % WINDOW + 1 + t // WINDOW * (WINDOW // PAGE)).sum())
    # the walk covers what a query sees, in whole blocks (heads of 16 lanes: the pipeline brings blocks of 128 rows):
    # never less, and less than a block a token more than the row its call's last token sees (a call is a chunk of up
    # to 48 tokens, or one token of the fused rung)
    block = walk_block(PAGE, eng.kv.table_width, 4, 16, 4) * PAGE
    assert block == 128
    for r in rows:
        slack = r["attn_rows_walked"] - r["attn_rows_visible"]
        assert 0 <= slack < r["tokens_real"] * (block + 48), r
    # the linear geometry counts the whole history
    assert LinearGeometry(PAGE).step_counts(250, 48)[0] == int((np.arange(250, 298) + 1).sum())


# ---------------------------------------------------------------- (d) the geometry


@pytest.mark.parametrize("n, pages", [(15, 1 + 1), (16, 1 + 1), (17, 2 + 1), (2047, 128 + 8), (2048, 128 + 8),
                                      (2049, 128 + 9), (24968, 128 + 98)])
def test_ring_and_summary_pages_at_the_corners(n, pages):
    geo = RingSummaryGeometry(16, 2048)
    assert geo.pages_for(n) == pages
    # in order of need, the first pages_for(n) entries fill exactly the ring and summary columns n tokens use
    cols = sorted(geo.slots(pages).tolist())
    assert cols == list(range(min(-(-n // 16), 128))) + list(range(128, 128 + -(-n // 256)))
    assert geo.table_width(n) == 128 + -(-n // 256)


def test_linear_geometry_is_the_ceiling_division_everywhere_it_is_asked():
    geo = LinearGeometry(16)
    for n in (0, 1, 15, 16, 17, 2047, 2048, 2049, 24968):
        assert geo.pages_for(n) == -(-n // 16) == geo.table_width(n)
        assert geo.slots(geo.pages_for(n)) == slice(0, -(-n // 16))
    assert geo.rewind_floor(5000) == 0 and geo.pages_immutable
    # every family but EvaByte gets it, and BlockedKVCache keeps its old numbers under it
    assert isinstance(cache_geometry(PRESETS["tiny"], 16), LinearGeometry)
    assert isinstance(cache_geometry(CFG, PAGE), RingSummaryGeometry)
    kv = BlockedKVCache(64, 16, 8)
    assert (kv.max_pages_per_seq, kv.table_width, kv.max_tokens_per_seq) == (8, 8, 128)
    seq = SequenceDescriptor(uid=0, tokens=list(range(40)))
    assert kv.pages_needed(seq, 0) == 3 and kv.pages_needed(seq, 17) == 2


def test_prefix_cache_on_raises(params):
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        _engine(params, enable_prefix_cache=True)


def _run_to(eng, uid, seen):
    while eng.state.seqs[uid].seen_tokens < seen:
        eng.step()


def test_truncate_inside_a_window_restores_parity_and_across_one_raises(params, ids):
    """Rewind a decoding sequence by 21 tokens inside its window (over a page
    and a chunk boundary, so a summary row is written again): the tokens it
    then generates are the ones it generated before.  One token below the
    window's start the exact rows are gone, and ``truncate`` raises."""
    prompt = ids[:270].tolist()
    eng = _engine(params, decode_steps_per_dispatch=1)
    eng.put([7], [prompt], max_new_tokens=48)
    _run_to(eng, 7, 300)
    seq = eng.state.seqs[7]
    first = list(seq.generated)
    keep = 280
    eng.state.truncate(seq, keep)                      # 300 -> 280: inside the window that starts at 256
    del seq.tokens[keep + 1:], seq.generated[keep + 1 - len(prompt):]
    assert seq.seen_tokens == keep and len(seq.pages) == eng.kv.geometry.pages_for(keep)
    _run_to(eng, 7, 300)
    assert seq.generated[:len(first)] == first
    with pytest.raises(RuntimeError, match="cannot rewind"):
        eng.state.truncate(seq, 255)
    eng.state.truncate(seq, 256)                       # the window's own start is still there


def test_preempt_export_import_round_trip_a_sequence_with_both_kinds_of_page(params, ids):
    """A sequence 300 tokens in holds 16 ring pages and 2 summary pages.
    Export them, preempt the sequence, import the block into other pages of
    another engine, and decoding goes on as if nothing had happened."""
    prompt = ids[:290].tolist()
    eng = _engine(params, decode_steps_per_dispatch=1)
    eng.put([1], [prompt], max_new_tokens=30)
    _run_to(eng, 1, 300)
    seq = eng.state.seqs[1]
    assert len(seq.pages) == 16 + 2
    block = eng.kv.export_pages(eng.cache, seq.pages)
    tokens, seen, generated = list(seq.tokens), seq.seen_tokens, list(seq.generated)
    uninterrupted = _engine(params, decode_steps_per_dispatch=1)
    uninterrupted.put([1], [prompt], max_new_tokens=30)
    _run_to(uninterrupted, 1, 310)

    free_before = eng.kv.allocator.free_pages
    eng.preempt(1)
    assert eng.kv.allocator.free_pages == free_before + 18

    other = _engine(params, decode_steps_per_dispatch=1)
    other.kv.allocator.allocate(5)                      # so the pages get other ids
    pages = other.kv.allocator.allocate(18)
    other.cache = other.kv.import_pages(other.cache, pages, block)
    other.state.seqs[1] = SequenceDescriptor(uid=1, tokens=tokens, pages=pages, seen_tokens=seen,
                                             generated=generated)
    other._max_new[1] = 30
    _run_to(other, 1, 310)
    assert other.state.seqs[1].generated == uninterrupted.state.seqs[1].generated
