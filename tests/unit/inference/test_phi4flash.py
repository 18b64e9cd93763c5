"""Phi-4-mini-flash (SambaY: Mamba, window attention, one full-attention
layer whose keys and values the cross-attention layers share, gated memory
units, differential attention) against its plain reference
(``benchmark/refs/phi4flash.py``) on the CPU at a small size: the
full-sequence model, the serving twin through pages and state slots, the
engine.

Small size: 8 layers (two [Mamba, window] pairs, the middle pair, one [GMU,
cross] pair), hidden 128, 4 query and 2 key heads of 32 (one key pair, two
query pairs), ``d_state`` 16, window 32, page 16, chunks of 32 at most: a
slot's ring is 5 pages = 80 rows, so a sequence of 200 tokens wraps it twice.

The weights are drawn so that every mixer matters: ``A = -(1..16)``, ``dt``
in [1e-3, 1e-1], ``D = 1`` as published Mamba has them, matrices at
``1 / sqrt(fan_in)`` so that every mixer's output is of the residual's order
(under the benchmark's rule, 0.02 for all of them, a wrong scan could hide
behind the residual).  Everything is float32; the tolerance is its rounding
through eight layers.
"""

import dataclasses
import os
import sys

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
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM, Phi4FlashLayer
from deepspeed_tpu.models.phi4flash_cache import (Phi4FlashForCausalLMWithCache, _apply_layer, _memory_mix, init_cache,
                                                  layer_traced_once, page_heads, ring_pages)
from deepspeed_tpu.telemetry.step_anatomy import StepAnatomy

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import phi4flash as ref  # noqa: E402

WINDOW, PAGE, CHUNK = 32, 16, 32
CFG = Phi4FlashConfig(vocab_size=512, hidden_size=128, intermediate_size=256, num_hidden_layers=8,
                      num_attention_heads=4, num_key_value_heads=2, sliding_window=WINDOW,
                      max_position_embeddings=4096, dtype=jnp.float32, param_dtype=jnp.float32)
REF_CFG = {f: getattr(CFG, f) for f in ("num_attention_heads", "num_key_value_heads", "num_hidden_layers",
                                        "sliding_window", "layer_norm_eps")}
TOL = 2e-4
KV = PagedKVConfig(num_pages=64, page_size=PAGE, max_pages_per_seq=20)


@pytest.fixture(scope="module")
def params():
    p = nn.meta.unbox(Phi4FlashForCausalLM(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    def draw(path, x):
        name = jax.tree_util.keystr(path)
        key = jax.random.PRNGKey(len(name) + 7 * sum(map(ord, name)))
        if "dt_proj']['bias" in name:      # softplus(bias) log-uniform in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(key, x.shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return jnp.log(jnp.expm1(dt))
        if name.endswith("['bias']") or "conv_bias" in name:
            return 0.1 * jax.random.normal(key, x.shape)
        if "norm" in name:                 # norm weights away from 1
            return 1.0 + 0.3 * jax.random.normal(key, x.shape)
        if "lambda" in name:
            return 0.3 * jax.random.normal(key, x.shape)
        return x                           # matrices: lecun_normal; A_log = log(1..16); D = 1

    return jax.tree_util.tree_map_with_path(draw, p)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(1, CFG.vocab_size, 2 * 200).reshape(2, 200)


@pytest.fixture(scope="module")
def want(params, ids):
    """The reference's logits of both whole sequences."""
    return [np.asarray(ref.forward(params, jnp.asarray(row), REF_CFG)[0]) for row in ids]


# ---------------------------------------------------------------- (a) the model


@pytest.mark.parametrize("length", [10, WINDOW, WINDOW + 1, 200])
def test_full_sequence_model_matches_reference(params, ids, want, length):
    with jax.default_matmul_precision("highest"):
        got = Phi4FlashForCausalLM(CFG).apply(params, jnp.asarray(ids[:1, :length]))[0]
    assert got.shape == (length, CFG.vocab_size) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want[0][:length], atol=TOL)


@pytest.mark.parametrize("zeroed", ["self_decoder']['mamba']['mixer']['out_proj", "mid_mamba']['mixer']['D",
                                    "cross_decoder']['gmu']['mixer']['out_proj", "lambda_q1", "sub_norm",
                                    "cross_decoder']['cross']['mixer']['o_proj", "self_decoder']['attn']['mixer']['v_proj"])
def test_every_mixer_matters_under_these_weights(params, ids, want, zeroed):
    """The guard of the guard: with one mixer's parameters zeroed the
    comparison fails by two orders of magnitude."""
    broken = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if zeroed in jax.tree_util.keystr(path) else x, params)
    got = Phi4FlashForCausalLM(CFG).apply(broken, jnp.asarray(ids[:1]))[0]
    assert float(np.abs(np.asarray(got) - want[0]).max()) > 100 * TOL


# --------------------------------------------- (b) the twin, through slots, rings and pages


def _feed(params, rows, plans, tables, attention_impl="reference", n_slots=4, cache=None):
    """Feed ``rows`` (token ids a row) through the twin, row ``i`` in the
    chunk lengths ``plans[i]`` (0: the row sits a step out), all rows in one
    batch; per row the logits of every position fed, and the cache."""
    twin = Phi4FlashForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    if cache is None:
        cache = init_cache(CFG, KV, jnp.float32, n_slots, CHUNK)
    step = jax.jit(lambda c, t, s, n: twin.apply(params, t, s, jnp.asarray(tables), c, n))
    pos, out = [0] * len(rows), [[] for _ in rows]
    with jax.default_matmul_precision("highest"):
        for lens in zip(*plans):
            width = 1 if max(lens) == 1 else CHUNK
            toks = np.zeros((len(rows), width), np.int32)
            for i, n in enumerate(lens):
                toks[i, :n] = rows[i][pos[i]:pos[i] + n]
            logits, cache = step(cache, jnp.asarray(toks), jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32))
            for i, n in enumerate(lens):
                out[i].append(np.asarray(logits[i, :n]))
                pos[i] += n
    return [np.concatenate(o) for o in out], cache


def _table(first_page, n_pages, slot, width=14):
    """A block-table row: consecutive pages, then zeros, the slot in the last column."""
    row = np.zeros(width, np.int32)
    row[:n_pages] = first_page + np.arange(n_pages)
    row[-1] = slot
    return row


PLANS = {
    # the ring of 80 rows wraps at 80 and 160; the window's edge crosses every chunk
    "aligned_chunks_then_decode": [32] * 5 + [1] * 40,
    "chunks_that_start_and_end_inside_a_page": [7, 32, 20, 12, 32, 5, 27, 32, 9] + [1] * 24,
    "decode_from_the_second_token": [1] * 100,
    "one_short_chunk": [19],
}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_twin_chunks_then_decode_match_reference(params, ids, want, plan):
    got, _ = _feed(params, ids[:1], [PLANS[plan]], _table(1, 13, slot=1)[None])
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_table_built_for_the_linear_layout_runs_in_the_scratch_slot(params, ids, want):
    """The benchmark's check builds its own table as ``program_logits`` does:
    columns ``0 .. ceil(len / page) - 1`` hold consecutive pages from page 1,
    every other column 0, and it passes no slot: the row's last column reads
    0, the scratch slot."""
    n_tokens, width = 180, 14
    pages_each = -(-n_tokens // PAGE)
    table = np.zeros((1, width), np.int32)
    table[0, :pages_each] = 1 + 0 * pages_each + np.arange(pages_each)
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 32, 32, 8] + [1] * 12], table)     # the last chunk has padding
    np.testing.assert_allclose(got[0], want[0][:180], atol=TOL)


def test_two_sequences_in_one_batch_with_different_starts(params, ids, want):
    """Row 1 starts three steps after row 0 and runs in chunks of its own;
    later a decode row rides beside a prefill chunk."""
    tables = np.stack([_table(1, 13, slot=2), _table(20, 13, slot=1)])
    plans = [[32, 32, 32, 32, 1, 1, 1, 1, 1] + [1] * 10, [0, 0, 0, 17, 32, 32, 32, 3, 1] + [1] * 10]
    got, _ = _feed(params, ids, plans, tables)
    for i in range(2):
        np.testing.assert_allclose(got[i], want[i][:len(got[i])], atol=TOL)


def test_twin_matches_reference_through_the_paged_kernel(params, ids, want):
    """The same through ``ds_paged_attention`` (interpreted on the CPU): the
    rings through the table built on the device and the window bound, the
    shared pages by the middle layer and the cross-attention layer."""
    got, _ = _feed(params, ids[:1], [[32, 32, 32, 30] + [1] * 6], _table(1, 13, slot=3)[None], attention_impl="flash")
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_key_pairs_in_groups_of_device_pages_match_reference(ids, impl):
    """Six key pairs do not fill the chip's tiles of 8, so a page is kept as
    three device pages of two pairs and the kernel runs a row a (sequence,
    group), eight query heads each: the published 10 pairs' form (5 groups of
    2).  Two sequences, one of them in the scratch slot with a padded chunk."""
    cfg = dataclasses.replace(CFG, hidden_size=192, num_attention_heads=24, num_key_value_heads=12, attention_impl=impl)
    assert page_heads(cfg) == 2 and page_heads(Phi4FlashConfig()) == 2 and page_heads(CFG) == 1
    p = nn.meta.unbox(Phi4FlashForCausalLM(cfg).init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))
    twin = Phi4FlashForCausalLMWithCache(cfg, page_size=PAGE)
    cache = init_cache(cfg, KV, jnp.float32, 3, CHUNK)
    assert cache["pages"].shape == (1, 3 * 64, PAGE, 2, 2, 16) and cache["ring"].shape[1] == 3 * (1 + 3 * 5)
    tables = jnp.asarray(np.stack([_table(1, 8, slot=0), _table(30, 8, slot=2)]))
    ref_cfg = {**REF_CFG, "num_attention_heads": 24, "num_key_value_heads": 12}
    pos = [0, 0]
    with jax.default_matmul_precision("highest"):
        for lens in ([32, 20], [32, 32], [9, 32], [1, 1], [1, 1]):
            toks = np.zeros((2, max(lens)), np.int32)
            for i, n in enumerate(lens):
                toks[i, :n] = ids[i, pos[i]:pos[i] + n]
            logits, cache = twin.apply(p, jnp.asarray(toks), jnp.asarray(pos, jnp.int32), tables, cache,
                                       jnp.asarray(lens, jnp.int32))
            for i, n in enumerate(lens):
                want_i = ref.forward(p, jnp.asarray(ids[i, :pos[i] + n]), ref_cfg)[0][pos[i]:]
                np.testing.assert_allclose(logits[i, :n], want_i, atol=TOL)
                pos[i] += n


def test_a_slot_used_before_gives_what_a_fresh_one_gives(params, ids, want):
    """A row whose ``start_pos`` is 0 starts from a zero recurrent state and
    sees no row of the ring's last owner."""
    table = _table(1, 13, slot=1)[None]
    _, cache = _feed(params, ids[1:], [[32] * 6], table)                 # another sequence, 192 tokens deep
    got, _ = _feed(params, ids[:1], [[32, 32, 32] + [1] * 8], table, cache=cache)
    np.testing.assert_allclose(got[0], want[0][:len(got[0])], atol=TOL)


def test_a_state_that_is_not_carried_fails_the_comparison(params, ids, want):
    """The guard of the guard for the slots: fed in two chunks with the
    recurrent state zeroed between them, the second chunk is far off."""
    table = _table(1, 13, slot=1)[None]
    _, cache = _feed(params, ids[:1], [[32]], table)
    cache = {**cache, "ssm": jnp.zeros_like(cache["ssm"])}
    twin = Phi4FlashForCausalLMWithCache(CFG, page_size=PAGE)
    logits, _ = twin.apply(params, jnp.asarray(ids[:1, 32:64]), jnp.asarray([32], jnp.int32), jnp.asarray(table), cache,
                           jnp.asarray([32], jnp.int32))
    assert float(np.abs(np.asarray(logits[0]) - want[0][32:64]).max()) > 100 * TOL


def test_a_sequence_holds_one_layer_s_pages_and_one_slot():
    """What the cache is: pages for one layer, and in a slot 2 rings of 5
    pages, 3 recurrent states and 3 convolution tails."""
    cache = init_cache(CFG, KV, jnp.float32, n_slots=4, chunk=CHUNK)
    assert ring_pages(CFG, PAGE, CHUNK) == (WINDOW + CHUNK) // PAGE + 1 == 5
    assert {k: v.shape for k, v in cache.items()} == {
        "pages": (1, 64, PAGE, 2, 1, 64), "ring": (2, 1 + 4 * 5, PAGE, 2, 1, 64),
        "ssm": (3, 4, 16, 256), "conv": (3, 4, 3, 256)}
    assert cache["ssm"].dtype == jnp.float32
    full = Phi4FlashConfig()
    big = jax.eval_shape(lambda: init_cache(full, PagedKVConfig(6400, 16, 194), jnp.bfloat16, 33, 128))
    # 10 key pairs a token in 5 device pages of 2 (whole tiles on the chip): 5,120 B a token all the same
    assert big["ring"].shape[1] == 5 * (1 + 33 * 41) and big["pages"].shape == (1, 5 * 6400, 16, 2, 2, 128)
    per_slot = sum(int(np.prod(v.shape[:1] + v.shape[2:])) * v.dtype.itemsize * (5 * 41 if k == "ring" else 1)
                   for k, v in big.items() if k != "pages")
    assert 29.0e6 < per_slot < 31.0e6                                      # 8 rings of 656 rows, 9 states and tails


# ------------------------------------------------------------------ (c) the engine


def _engine(params, max_seqs=4, **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                                  decode_bucket=max_seqs),
                  max_new_tokens=12, decode_steps_per_dispatch=4, enable_prefix_cache=False, kv_dtype=jnp.float32)
    return InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(**{**fields, **over}))


def _greedy(want_row, params, prompt, n):
    """Greedy continuation by the full-sequence model."""
    toks = list(prompt)
    for _ in range(n):
        with jax.default_matmul_precision("highest"):
            logits = Phi4FlashForCausalLM(CFG).apply(params, jnp.asarray([toks]))[0, -1]
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def continuations(params, ids, want):
    return [_greedy(want[i], params, ids[i, :n], 12) for i, n in ((0, 70), (1, 45))]


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


# ------------------------------------------- (f) the chip's check of the twin, at the rehearsal size


def test_check_in_real_slots_under_weights_for_every_mixer_at_the_rehearsal_size():
    """``tests/tpu/phi4flash_check.py`` is what the chip runs at the cell's
    size; here its control flow at the configuration file's rehearsal size,
    bfloat16 as served: three sequences in slots 4, 1 and 3 on scattered
    pages, the head over the sampled rows against the all-position logits at
    both batches.  At a width of 128 the matrices' 0.02 gives every product a
    gain of a quarter, so of the mixer kinds only those the residual is made
    of show here; the chip's run holds all six."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "tpu"))
    import phi4flash_check
    import run as bench
    config = bench.load_json("configs", "phi4-mini-flash-serve-1chip.json")
    traffic = bench.load_json("traffic", "reason_short_in_long_out.json")
    config, traffic = bench.merge(config, config["rehearsal"]), bench.merge(traffic, traffic["rehearsal"])
    rows = [(200, 8, 4, 136), (70, 8, 1, 0), (33, 8, 3, 0)]
    out = phi4flash_check.readings(config, traffic, 3000003601, rows)
    per_row = phi4flash_check.report(out, rows)
    assert out["steps"] == 7 + 8 and out["last_only"] < 1e-5 and out["last_only_exact"] < 1e-5 and out["bucket"] < 0.03
    assert all(program < 0.02 and all(zeroed[kind] > 3 * program for kind in ("mamba", "window", "full", "cross"))
               for program, zeroed in per_row)
