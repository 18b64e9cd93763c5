"""The Xing4.0 serving twin through latent pages, in rectangles and in two row
groups, and the engine over it, against the plain reference at the small size
and under the weights of ``test_xing4.py`` (float32, 2e-4)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.cache_zoo import cache_geometry, cache_twin
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.xing4 import Xing4Config
from deepspeed_tpu.models.xing4_cache import LatentPagesGeometry, Xing4ForCausalLMWithCache, init_cache, walk_rows

from reference_greedy import greedy
from test_xing4 import CFG, CHUNK, KV, PAGE, TOL, _full, ids, params, want  # noqa: F401 (the fixtures are this module's too)


# ------------------------------------------------------- (d) the twin, through pages


def _feed(params, rows, steps, tables, attention_impl="reference"):
    """Feed ``rows`` through the twin.  A step is a list of groups, a group a
    list of ``(row, tokens)`` fed as one rectangle, ``CHUNK`` wide or, where
    no row carries more than a token, 1; a step of more than one group goes
    as row groups on one flat axis; a row named more than once in a group is
    a run, its chunks one behind the other through the same block-table row.  Per row the logits of every position
    fed, and the cache."""
    twin = Xing4ForCausalLMWithCache(dataclasses.replace(CFG, attention_impl=attention_impl), page_size=PAGE)
    cache = init_cache(CFG, KV, jnp.float32)
    pos, out = [0] * len(rows), [[] for _ in rows]
    apply = jax.jit(lambda c, t, s, bt, n, groups: twin.apply(params, t, s, bt, c, n, False, groups),
                    static_argnames="groups")
    with jax.default_matmul_precision("highest"):
        for step in steps:
            groups = tuple((len(g), 1 if max(n for _, n in g) <= 1 else CHUNK) for g in step)
            toks, order, starts, at = [], [r for g in step for r, _ in g], [], list(pos)
            for g, (_, width) in zip(step, groups):
                rect = np.zeros((len(g), width), np.int32)
                for j, (r, n) in enumerate(g):      # a row named again is the next chunk of its run
                    rect[j, :n] = rows[r][at[r]:at[r] + n]
                    starts.append(at[r])
                    at[r] += n
                toks.append(rect.reshape(-1))
            lens = [n for g in step for _, n in g]
            flat = jnp.asarray(np.concatenate(toks))
            logits, cache = apply(cache, flat if len(groups) > 1 else flat.reshape(groups[0]),
                                  jnp.asarray(starts, jnp.int32), jnp.asarray(tables[order]),
                                  jnp.asarray(lens, jnp.int32), groups=groups if len(groups) > 1 else None)
            logits, t0 = np.asarray(logits).reshape(-1, logits.shape[-1]), 0
            for g, (_, width) in zip(step, groups):
                for r, n in g:
                    out[r].append(logits[t0:t0 + n])
                    pos[r] += n
                    t0 += width
    return [np.concatenate(o) if o else None for o in out], cache


def _tables(n_rows, width=13):
    return np.asarray(jax.random.permutation(jax.random.PRNGKey(9), np.arange(1, 1 + n_rows * width))).reshape(
        n_rows, width).astype(np.int32)


RECTANGLES = {
    "chunks_that_start_and_end_inside_a_page": [[[(0, n)]] for n in (7, 32, 20, 12, 32, 5)] + [[[(0, 1)]]] * 8,
    "three_rows_of_different_lengths": [[[(0, 32), (1, 20), (2, 32)]], [[(0, 32), (1, 0), (2, 11)]]] +
    [[[(0, 1), (1, 1), (2, 1)]]] * 8,
}


@pytest.mark.parametrize("plan, attention_impl", [(p, "reference") for p in sorted(RECTANGLES)] +
                         [("chunks_that_start_and_end_inside_a_page", "flash")])
def test_twin_chunks_then_decode_match_reference(params, ids, want, plan, attention_impl):
    got, _ = _feed(params, ids, RECTANGLES[plan], _tables(3), attention_impl)
    for i, g in enumerate(got):
        if g is not None:
            np.testing.assert_allclose(g, want[i][:len(g)], atol=TOL)


@pytest.mark.parametrize("attention_impl", ["flash"])
def test_a_mixed_step_in_two_row_groups_matches_reference(params, ids, want, attention_impl):
    """Rows 0 and 1 prefill, then decode one slot each beside row 2's chunks:
    ``((2, 1), (1, 32))`` on one flat axis."""
    steps = [[[(0, 32), (1, 25)]], [[(0, 9), (1, 0)]]] + [[[(0, 1), (1, 1)], [(2, 32)]]] * 3 + \
        [[[(0, 1), (1, 1)], [(2, 13)]]] + [[[(0, 1), (1, 1), (2, 1)]]] * 4
    got, _ = _feed(params, ids, steps, _tables(3), attention_impl)
    assert [len(g) for g in got] == [41 + 8, 25 + 8, 96 + 13 + 4]
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[i][:len(g)], atol=TOL)


@pytest.mark.parametrize("attention_impl", ["reference"])   # the kernel under a run: tests/tpu/test_xing4_on_chip.py
def test_a_run_of_one_prompts_chunks_in_one_step_matches_reference(params, ids, want, attention_impl):
    """Row 2's prompt as consecutive chunks in the rows of one prefill group,
    beside two rows that decode: ``((2, 1), (4, 32))`` with row 2 in three of
    the four rows (the last ends inside a page and inside its chunk), then in
    one row more.  Every position's logits are the reference's, the last
    prompt position's among them, and so are those of the decode steps that
    read the pages the run wrote."""
    steps = [[[(0, 32), (1, 25)]], [[(0, 9), (1, 0)]], [[(0, 1), (1, 1)], [(2, 32), (2, 32), (2, 27), (1, 0)]],
             [[(0, 1), (1, 1)], [(2, 18)]]] + [[[(0, 1), (1, 1), (2, 1)]]] * 2
    got, _ = _feed(params, ids, steps, _tables(3), attention_impl)
    assert [len(g) for g in got] == [41 + 4, 25 + 4, 109 + 2]
    for i, g in enumerate(got):
        np.testing.assert_allclose(g, want[i][:len(g)], atol=TOL)


def test_a_token_is_one_row_of_latents_a_layer():
    cache = init_cache(CFG, KV, jnp.float32)
    assert cache.shape == (3, 64, PAGE, 128)                              # 32 + 8 numbers in one tile of lanes
    cell = Xing4Config(num_hidden_layers=7, first_k_dense_replace=1)
    big = jax.eval_shape(lambda: init_cache(cell, PagedKVConfig(24800, 16, 2066), jnp.bfloat16))
    assert big.shape == (7, 24800, 16, 640) and cell.latent_dim == 576
    assert cell.latent_dim * 2 == 1152 and big.shape[0] * big.shape[3] * 2 == 8960     # published, and as kept, a token
    assert walk_rows(16, 2066) == 512 and walk_rows(16, 13) == 13 * 16


# ------------------------------------------------------------------ (e) the engine


def _engine(params, max_seqs=2, attention_impl="reference", **over):
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=CHUNK,
                                                  decode_bucket=max_seqs),
                  max_new_tokens=12, decode_steps_per_dispatch=4, enable_prefix_cache=True, kv_dtype=jnp.float32)
    cfg = dataclasses.replace(CFG, attention_impl=attention_impl)
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(**{**fields, **over}))


#: greedy continuation by the full-sequence model: ``_greedy(params, prompt, n)``
_greedy = functools.partial(greedy, _full, width=96, precision="highest")


def test_engine_serves_two_row_groups_with_the_prefix_cache_on(params, ids):
    """``InferenceEngineV2 -> ServingEngine``: a request decodes while the
    next prefills (two row groups in one step program), the tokens are the
    full-sequence model's, and a prompt sent again takes its full pages from
    the prefix cache."""
    from deepspeed_tpu.serving import RequestState, ServingEngine, VirtualClock
    eng = _engine(params)
    assert isinstance(eng.kv.geometry, LatentPagesGeometry) and eng.kv.geometry.pages_immutable
    anat = eng.anatomy
    prompts = [ids[0, :70].tolist(), ids[1, :45].tolist(), ids[0, :70].tolist()]
    with jax.default_matmul_precision("highest"):
        serve = ServingEngine(eng, clock=VirtualClock())
        first = serve.submit(prompts[0], max_new_tokens=8)
        for _ in range(4):
            serve.tick()
        rest = [serve.submit(p, max_new_tokens=8) for p in prompts[1:]]
        while any(not r.state.terminal for r in [first] + rest):
            serve.tick()
    assert [r.state for r in [first] + rest] == [RequestState.DONE] * 3
    assert list(first.tokens) == list(rest[1].tokens) == _greedy(params, prompts[0], 8)
    assert list(rest[0].tokens) == _greedy(params, prompts[1], 8)
    rows = [r.to_row() for r in anat.steps]
    assert any(r["key"] == f"step:b2:c1:b1:c{CHUNK}" for r in rows)          # decode rows beside a prefill row
    assert sum(r["tokens_real"] for r in rows) < 70 + 45 + 70 + 24           # the third prompt's full pages were not fed again
    assert all(r["attn_rows_visible"] > 0 and r["attn_rows_walked"] == 0 for r in rows)   # the jnp form walks nothing
    for r in rows:                                                           # a decode row reads its context once a call
        if r["path"] == "decode" and r["key"].startswith("step"):
            assert r["mla_rows_read"] == r["attn_rows_visible"]
    # through the kernel the walk is the latent kernel's own, not ds_paged_attention's reading of the arena's shape
    assert _engine(params, attention_impl="flash")._walk_rows() == KV.max_pages_per_seq * PAGE == walk_rows(PAGE, 13)


def test_engine_feeds_a_prompt_in_runs_and_counts_its_rows_one_by_one(params, ids):
    """``max_seqs`` 8: rungs of 1, 4 and 8 prefill rows, so a prompt alone in
    prefill takes the rung of four.  100 tokens are one step of four rows (32,
    32, 32, 4) where they were four steps; the tokens are the full-sequence
    model's, ``mla_rows_read`` and ``attn_rows_visible`` are the sums over the
    same chunks fed one a step (each row reads to its own end, not the run's),
    and the prefix cache holds the same pages."""
    sched = SchedulerConfig(token_budget=160, max_seqs=8, prefill_chunk=CHUNK, decode_bucket=8)
    prompt = ids[0, :100].tolist()
    seen = {}
    for run_rows in (4, 1):
        eng = _engine(params, scheduler=sched)
        assert eng.scheduler.run_rows == 4 and eng.kv.geometry.chunk_runs
        eng.scheduler.run_rows = run_rows
        with jax.default_matmul_precision("highest"):
            eng.put([0], [prompt], max_new_tokens=6)
            while not eng.state.seqs[0].done:
                eng.step()
        rows = [r.to_row() for r in eng.anatomy.steps if r.rows_prefill]
        assert [(r["key"], r["rows_prefill"], r["seqs_prefill"], r["tokens_real"]) for r in rows] == (
            [(f"step:b8:c1:b4:c{CHUNK}", 4, 1, 100)] if run_rows == 4 else
            [(f"step:b8:c1:b1:c{CHUNK}", 1, 1, n) for n in (32, 32, 32, 4)])
        assert sum(r["mla_rows_read"] for r in rows) == 32 + 64 + 96 + 100
        assert sum(r["attn_rows_visible"] for r in rows) == 100 * 101 // 2
        cache = eng.kv.prefix_cache
        seen[run_rows] = (list(eng.state.seqs[0].generated), cache.held_digests(), eng.state.seqs[0].pages[:6])
    assert seen[4] == seen[1] and seen[4][0] == _greedy(params, prompt, 6, width=112)
    assert len(seen[4][1]) == (100 + 5) // PAGE


def test_step_records_count_the_latent_rows_a_call_reads():
    g = LatentPagesGeometry(PAGE)
    assert not g.state_slots and g.pages_immutable
    assert g.state_counts(0, 32) == {"mla_rows_read": 32}                   # a chunk reads up to its last row, once
    assert g.state_counts(100, 32) == {"mla_rows_read": 132}
    assert g.state_counts(100, 1) == {"mla_rows_read": 101}                 # a decode row: its context and itself
    assert g.state_counts(100, 4, calls=4) == {"mla_rows_read": 101 + 102 + 103 + 104}   # a fused dispatch of four
    assert g.step_counts(100, 32, block_rows=64) == (sum(range(101, 133)), 32 * 192)


def test_pages_are_exported_and_imported_as_they_lie(params, ids):
    """Snapshots, the host tier and export/import take the arena by its page
    axis and work on the latent page as on any other."""
    eng = _engine(params, enable_prefix_cache=False)
    eng.put([1], [ids[0, :40].tolist()])
    eng.step()
    eng.step()
    pages = eng.state.seqs[1].pages
    block = eng.kv.export_pages(eng.cache, pages)
    assert block.shape == (3, len(pages), PAGE, 128) and np.abs(block[:, :2]).max() > 0
    wiped = eng.cache.at[:, np.asarray(pages)].set(0)
    np.testing.assert_array_equal(np.asarray(eng.kv.import_pages(wiped, pages, block)), np.asarray(eng.cache))


def test_registry_names_the_twin_and_its_geometry():
    twin = cache_twin(CFG)
    assert isinstance(twin.model(CFG, page_size=PAGE), Xing4ForCausalLMWithCache)
    assert isinstance(cache_geometry(CFG, PAGE), LatentPagesGeometry)
    assert twin.walk_rows(PAGE, 2066) == 512 and twin.pages("arena") == "arena"


def test_tensor_parallel_serving_of_latent_pages_is_refused_in_words(params):
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
    mesh = create_mesh(MeshSpec(data=1, tensor=2), devices=jax.devices()[:2])
    fields = dict(kv=KV, scheduler=SchedulerConfig(token_budget=64, max_seqs=2, prefill_chunk=CHUNK, decode_bucket=2),
                  kv_dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="tensor-parallel serving of latent pages"):
        InferenceEngineV2(CFG, params, RaggedInferenceEngineConfig(**fields), mesh=mesh)
