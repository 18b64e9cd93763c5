"""Requests that carry images through ``ServingEngine.submit(...,
images=...)`` and ``tick()`` on a small Kimi-VL: the served tokens are the
full model's; the tower's work a tick is bounded and recorded; the three
rejections; a prefix hash that knows the image; nothing compiles after
``warm_all`` whatever grid arrives; a preempted sequence encodes again."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.ragged import PREFIX_CHAIN_SEED, iter_prefix_chain_hashes
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.request import RequestState
from deepspeed_tpu.telemetry import MetricsRegistry, Tracer

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import reference_greedy  # noqa: E402
from test_kimi_vl import small  # noqa: E402

PH = 500
BUCKETS = (16, 32, 64)


def engine(cfg, params, per_tick=64, rows=64, max_seqs=4, **over):
    sched = SchedulerConfig(token_budget=64, max_seqs=max_seqs, prefill_chunk=16, decode_bucket=max_seqs,
                            vision_patch_buckets=list(BUCKETS), vision_patches_per_tick=per_tick, vision_rows=rows)
    econf = RaggedInferenceEngineConfig(kv=PagedKVConfig(num_pages=64, page_size=16, max_pages_per_seq=8),
                                        scheduler=sched, kv_dtype=jnp.float32, decode_steps_per_dispatch=1, **over)
    return InferenceEngineV2(cfg, params, econf)


def request(rng, grids, text=(5, 3, 7)):
    """(prompt, images): text, a placeholder run an image, text behind."""
    ids, images = [], []
    for i, (h, w) in enumerate(grids):
        ids += rng.integers(1, 400, text[min(i, 1)]).tolist() + [PH] * (h * w // 4)
        images.append((rng.standard_normal((h * w, 3, 2, 2)).astype(np.float32), (h, w)))
    return ids + rng.integers(1, 400, text[2]).tolist(), images


def greedy(model, params, prompt, images, n):
    """``n`` greedy tokens of the full-sequence model: ``mm_index`` names an
    image row at the prompt's placeholders and -1 behind them, as wide as the
    padded tokens (80: the longest prompt here has 63)."""
    width = 80
    rows = jnp.concatenate([model.apply(params, jnp.asarray(px.reshape(len(px), -1)), jnp.asarray(g),
                                        method="encode_images") for px, g in images])
    index = np.full((1, width), -1)
    index[0, np.flatnonzero(np.asarray(prompt) == PH)] = np.arange(rows.shape[0])
    return reference_greedy.greedy(lambda a, t: model.apply(a[0], t, mm_index=a[1], mm_rows=a[2]),
                                   (params, index, rows), prompt, n, width)


@pytest.fixture(scope="module")
def kimi():
    return small()


def test_images_through_submit_and_tick_give_the_full_models_tokens(kimi):
    cfg, _, model, params = kimi
    eng = engine(cfg, params, per_tick=32)
    tracer, metrics = Tracer(), MetricsRegistry()
    serve = ServingEngine(eng, tracer=tracer, metrics=metrics)
    rng = np.random.default_rng(0)
    a, b = request(rng, [(4, 6), (8, 6)]), request(rng, [(4, 4)])
    reqs = [serve.submit(p, max_new_tokens=4, images=i) for p, i in (a, b)]
    text = serve.submit(rng.integers(1, 400, 20).tolist(), max_new_tokens=3)            # images=None: the text path
    serve.tick()
    assert len(eng.anatomy.encodes) == 1 and reqs[0].state is RequestState.PREFILL      # 32 patches a tick: one image went
    assert not eng.state.seqs[reqs[0].uid].seen_tokens and eng.state.seqs[text.uid].seen_tokens   # text does not wait
    serve.drain(max_ticks=200)
    assert [r.state for r in reqs + [text]] == [RequestState.DONE] * 3
    for req, (prompt, images) in zip(reqs, (a, b)):
        assert req.tokens == greedy(model, params, prompt, images, 4)
        assert len(req.encode_windows) == 1 and req.encode_windows[0][1] >= req.encode_windows[0][0]
    rows = [r.to_row() for r in eng.anatomy.steps]
    assert sum(r["mm_tokens"] for r in rows) == 6 + 12 + 4
    assert all("vision_encode" in r["segments"] for r in rows)       # a segment of its own (0 s on a virtual clock)
    encodes = list(eng.anatomy.encodes)
    assert [(e["key"], e["vit_patches_real"], e["vit_patches_padded"]) for e in encodes] == \
        [("vit:p32", 24, 32), ("vit:p64", 48, 64), ("vit:p16", 16, 16)]
    assert [e["vit_pairs"] for e in encodes] == [24 * 24, 48 * 48, 16 * 16] and not any(e["vit_reencoded"] for e in encodes)
    assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1                         # every unit given back
    names = [s.name for s in tracer.finished()]
    assert names.count("serving/vision_encode") == 3 and names.count("phase/vision_encode") == 2
    assert metrics.counter("serving/vision_images").value == 3 and metrics.counter("serving/vision_patches_padded").value == 112


def test_an_image_whose_rows_straddle_two_rows_of_a_run(kimi):
    """A prompt of 47 positions alone in prefill goes in one step, as three
    rows of the rung of four (``max_seqs`` 8: rungs 1, 4, 8); its image's 12
    rows lie at positions 5-16, the end of the run's first row and the start
    of its second.  The tokens are the full model's and those of a chunk a
    step, and the units go back when the run has passed the image."""
    cfg, _, model, params = kimi
    prompt, images = request(np.random.default_rng(5), [(8, 6)], text=(5, 3, 30))
    assert len(prompt) == 47 and prompt[5:17] == [PH] * 12
    served = {}
    eng = engine(cfg, params, max_seqs=8, enable_prefix_cache=False)       # the second pass feeds the prompt again
    assert eng.scheduler.run_rows == 4
    serve = ServingEngine(eng)
    for run_rows in (4, 1):
        eng.scheduler.run_rows = run_rows
        first = len(eng.anatomy.steps)
        req = serve.submit(prompt, max_new_tokens=4, images=images)
        serve.drain(max_ticks=50)
        assert req.state is RequestState.DONE
        rows = [r.to_row() for r in list(eng.anatomy.steps)[first:] if r.rows_prefill]
        assert [(r["key"], r["rows_prefill"], r["seqs_prefill"], r["tokens_real"], r["mm_tokens"]) for r in rows] == (
            [("step:b8:c1:b4:c16", 3, 1, 47, 12)] if run_rows == 4 else
            [("step:b8:c1:b1:c16", 1, 1, n, mm) for n, mm in ((16, 11), (16, 1), (15, 0))])
        assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1
        served[run_rows] = list(req.tokens)
    # the token behind the run's last row is the full model's (the other test holds four tokens of a chunk a step)
    assert served[4] == served[1] and served[4][:1] == greedy(model, params, prompt, images, 1)


def test_the_three_rejections_and_a_model_without_a_tower(kimi):
    cfg, _, _, params = kimi
    serve = ServingEngine(engine(cfg, params))
    rng = np.random.default_rng(1)
    pixels = lambda h, w: rng.standard_normal((h * w, 3, 2, 2)).astype(np.float32)  # noqa: E731
    odd = serve.submit([7] * 3 + [PH] * 3 + [9], images=[(pixels(3, 4), (3, 4))])
    large = serve.submit([7] + [PH] * 20 + [9], images=[(pixels(8, 10), (8, 10))])       # 80 patches over the bucket of 64
    runs = serve.submit([7] + [PH] * 5 + [9], images=[(pixels(4, 6), (4, 6))])           # 5 placeholders for 6 rows
    none = serve.submit([7] + [PH] * 6 + [9], images=[(pixels(4, 6), (4, 6)), (pixels(4, 4), (4, 4))])   # a grid too many
    assert [r.state for r in (odd, large, runs, none)] == [RequestState.REJECTED] * 4
    assert [r.reject_reason for r in (odd, large, runs, none)] == \
        ["image_grid_odd", "image_over_largest_bucket", "image_placeholders_mismatch", "image_placeholders_mismatch"]
    assert not serve.engine.state.seqs and serve.stats.submitted == 4
    with pytest.raises(ValueError, match="image_grid_odd"):                               # the engine's own door raises
        serve.engine.put([99], [[7] * 3 + [PH] * 3 + [9]], images=[[(pixels(3, 4), (3, 4))]])
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    lcfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
                       num_key_value_heads=2, dtype=jnp.float32, param_dtype=jnp.float32)
    lparams = LlamaForCausalLM(lcfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    plain = ServingEngine(InferenceEngineV2(lcfg, lparams, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=16, page_size=16, max_pages_per_seq=4), kv_dtype=jnp.float32)))
    refused = plain.submit([1, 2, 3], images=[(pixels(4, 4), (4, 4))])
    assert refused.state is RequestState.REJECTED and refused.reject_reason == "no_vision_tower"


def test_the_prefix_hash_knows_the_image(kimi):
    cfg, _, model, params = kimi
    rng = np.random.default_rng(2)
    # 20 text tokens, then 16 + 16 rows: pages 1, 2 and 3 hold image rows
    prompt, images = request(rng, [(8, 8), (8, 8)], text=(20, 2, 9))
    other = [(rng.standard_normal(px.shape).astype(np.float32), g) for px, g in images]
    text = list(range(1, 40))
    h0 = hash((PREFIX_CHAIN_SEED, tuple(text[:16])))
    assert list(iter_prefix_chain_hashes(text, 16)) == [h0, hash((h0, tuple(text[16:32])))]    # text: the rule it was
    assert list(iter_prefix_chain_hashes(text, 16, {1: 77}))[0] == list(iter_prefix_chain_hashes(text, 16))[0]
    assert list(iter_prefix_chain_hashes(text, 16, {1: 77}))[1] != list(iter_prefix_chain_hashes(text, 16))[1]

    eng = engine(cfg, params)
    serve = ServingEngine(eng)
    first = serve.submit(prompt, max_new_tokens=3, images=images)
    serve.drain(max_ticks=200)
    cached = eng.kv.prefix_cache.cached_pages
    assert cached >= 4
    # the same ids with other images: the text page in front matches, no page of image rows does
    second = serve.submit(prompt, max_new_tokens=3, images=other)
    serve.tick()
    assert eng.state.seqs[second.uid].pc_pages == 1 and eng.kv.prefix_cache.hits == 1
    serve.drain(max_ticks=200)
    assert second.tokens == greedy(model, params, prompt, other, 3) and second.tokens != first.tokens
    # the same ids with the same images: every full page is shared, and the image those pages hold whole is not
    # encoded again (the second one's last rows lie behind the last full page: it is)
    encodes = len(eng.anatomy.encodes)
    third = serve.submit(prompt, max_new_tokens=3, images=images)
    serve.tick()
    assert eng.state.seqs[third.uid].pc_pages == (len(prompt) - 1) // 16 >= 3
    serve.drain(max_ticks=200)
    assert third.tokens == first.tokens and len(eng.anatomy.encodes) == encodes + 1
    assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1


def test_nothing_compiles_after_warm_all_whatever_grid_arrives(kimi):
    cfg, _, _, params = kimi
    eng = engine(cfg, params, enable_prefix_cache=False)
    warm = eng.warm_all()
    assert warm["fallback"] == 0 and {"vit:p16", "vit:p32", "vit:p64"} <= set(warm["keys"])
    jax.random.split(eng.rng)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: compiles.append(name) if name.endswith("backend_compile_duration") else None)
    serve = ServingEngine(eng)
    rng = np.random.default_rng(3)
    grids = [(4, 4), (2, 8), (4, 6), (6, 4), (4, 8), (8, 6), (6, 8), (8, 8), (2, 32)]     # every bucket, squares and strips
    reqs = []
    for i in range(0, len(grids), 3):
        for some, n in ((grids[i:i + 2], 3), (grids[i + 2:i + 3], 2)):
            prompt, images = request(rng, some)
            reqs.append(serve.submit(prompt, max_new_tokens=n, images=images))
        serve.drain(max_ticks=300)
    assert all(r.state is RequestState.DONE for r in reqs)
    assert compiles == [] and all(c.aot for c in eng.anatomy.compiles)
    assert {e["key"] for e in eng.anatomy.encodes} == {"vit:p16", "vit:p32", "vit:p64"}


def test_a_preempted_sequence_gives_its_rows_back_and_encodes_again(kimi):
    cfg, _, _, params = kimi
    eng = engine(cfg, params)
    prompt, images = request(np.random.default_rng(4), [(8, 8), (4, 6)])
    eng.put([0], [prompt], max_new_tokens=4, images=[images])
    assert len(eng.encode_images()) == 1 and eng.state.seqs[0].images_pending           # 64 patches a call: the first image
    assert eng.scheduler.plan(eng.state).prefill == []                                   # not planned before its images are through
    assert len(eng.encode_images()) == 1 and not eng.state.seqs[0].images_pending
    held = eng.mm_alloc.num_pages - 1 - eng.mm_alloc.free_pages
    assert held == 64 // 4 // 4 + 32 // 4 // 4
    eng.step()
    eng.preempt(0)
    assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1
    # what a resumed sequence generated may carry the placeholder's id: it is text, and no image's run
    assert eng.check_images(prompt + [PH, 7], images) == "image_placeholders_mismatch"
    assert eng.check_images(prompt + [PH, 7], images, strict=False) is None
    eng.put([0], [prompt + [PH, 7]], max_new_tokens=4, images=[images], reencode=True)
    assert int((eng.state.seqs[0].images[-1].end)) < len(prompt)
    while not eng.state.seqs[0].done:
        eng.step()
    assert [e["vit_reencoded"] for e in eng.anatomy.encodes] == [0, 0, 1, 1]
    assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1
