"""FastGen-v2 engine tests (ref: tests/unit/inference/v2 — ragged batching,
scheduler, engine generate correctness vs the cache-free reference path)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2, RaggedInferenceEngineConfig,
                                        build_engine)
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache, StateManager
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig, SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig

from reference_greedy import greedy


CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128,
                  rope_theta=1e4, dtype=jnp.float32, scan_layers=True, remat=False)


@pytest.fixture(scope="module")
def trained_params():
    model = LlamaForCausalLM(CFG)
    ids = jnp.zeros((1, 8), jnp.int32)
    return model.init(jax.random.PRNGKey(0), ids)


def _engine(trained_params, cfg=CFG, **overrides):
    kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
    sched = SchedulerConfig(token_budget=64, max_seqs=8, prefill_chunk=8, decode_bucket=4)
    eng_cfg = RaggedInferenceEngineConfig(kv=kv, scheduler=sched, kv_dtype=jnp.float32,
                                          **overrides)
    return build_engine(cfg, trained_params, eng_cfg)


#: cache-free greedy decode via the training model (golden): ``_reference_greedy(params, prompt, n_new)``
_reference_greedy = functools.partial(greedy, LlamaForCausalLM(CFG).apply, width=32)


def test_generate_matches_cachefree_reference(trained_params):
    eng = _engine(trained_params)
    prompts = [[5, 9, 2, 7, 1], [3, 3, 8]]
    outs = eng.generate(prompts, max_new_tokens=6)
    for prompt, got in zip(prompts, outs):
        expected = _reference_greedy(trained_params, prompt, 6)
        assert got == expected, (got, expected)


def test_fused_decode_overshoot_matches_reference(trained_params):
    """Fused-decode OVERSHOOT (k rung larger than tokens remaining; surplus
    discarded host-side) must produce exactly the reference greedy tokens."""
    eng = _engine(trained_params, decode_steps_per_dispatch=4)
    prompts = [[5, 9, 2, 7, 1], [3, 3, 8]]
    # prefill emits token 1; the remaining 5 take a k=4 rung plus a second
    # rung that OVERSHOOTS by 3 — those surplus tokens must be discarded
    # host-side without corrupting the sequence
    outs = eng.generate(prompts, max_new_tokens=6)
    for prompt, got in zip(prompts, outs):
        expected = _reference_greedy(trained_params, prompt, 6)
        assert got == expected, (got, expected)


def test_unscanned_checkpoint_served_through_the_scanned_twin():
    """A tree trained with ``scan_layers=False`` (``model/layers_<i>``) is
    stacked once at engine init: one arena, the reference's tokens."""
    cfg = dataclasses.replace(CFG, scan_layers=False)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
    assert "layers_0" in params["params"]["model"]
    eng = _engine(params, cfg)
    assert eng.cache.ndim == 6
    prompt = [5, 9, 2, 7, 1]
    assert eng.generate([prompt], max_new_tokens=6) == [greedy(model.apply, params, prompt, 6, 32)]


def test_mixed_dense_sparse_stack_served_in_the_one_arena():
    """The mixed Qwen2-MoE stack (layers differ in shape, so its twin loops
    over ``layers_<i>``) names its layer in the whole arena like every other
    twin: served by the engine it gives the training model's greedy tokens."""
    from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
    from deepspeed_tpu.models.llama_cache import init_kv_cache
    from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM
    cfg = Qwen2MoeConfig(vocab_size=128, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                         shared_expert_intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, num_experts=4, num_experts_per_tok=2, mlp_only_layers=(0, ),
                         max_position_embeddings=128, rope_theta=1e4, dtype=jnp.float32, scan_layers=False,
                         remat=False)
    assert cfg.mixed_stack
    kv = PagedKVConfig(num_pages=32, page_size=8, max_pages_per_seq=4)
    one = jnp.zeros((1, ), jnp.int32)
    params = build_cache_model(cfg, kv.page_size).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 1), jnp.int32), one, jnp.zeros((1, kv.max_pages_per_seq), jnp.int32),
        init_kv_cache(cfg, kv, dtype=jnp.float32), one)
    sched = SchedulerConfig(token_budget=32, max_seqs=4, prefill_chunk=8, decode_bucket=4)
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(kv=kv, scheduler=sched, kv_dtype=jnp.float32))
    prompt = [5, 9, 2, 7, 1]
    assert eng.generate([prompt], max_new_tokens=5) == [greedy(Qwen2MoeForCausalLM(cfg).apply, params, prompt, 5, 32)]
    assert eng.cache.ndim == 6


def test_long_prompt_splitfuse_chunking(trained_params):
    """Prompt longer than prefill_chunk is split across steps yet matches."""
    eng = _engine(trained_params)
    prompt = list(np.random.default_rng(0).integers(1, 100, size=21))
    outs = eng.generate([prompt], max_new_tokens=4)
    assert outs[0] == _reference_greedy(trained_params, prompt, 4)


def test_continuous_batching_join_mid_flight(trained_params):
    """A sequence admitted while another decodes shares step programs and
    both match the golden (continuous batching)."""
    eng = _engine(trained_params)
    p1, p2 = [5, 9, 2, 7, 1], [11, 4, 6, 2]
    eng.put([100], [p1], max_new_tokens=5)
    eng.step()  # p1 prefill
    eng.step()  # p1 first decode
    eng.put([200], [p2], max_new_tokens=5)
    for _ in range(12):
        eng.step()
        if eng.state.seqs[100].done and eng.state.seqs[200].done:
            break
    assert list(eng.state.seqs[100].generated) == _reference_greedy(trained_params, p1, 5)
    assert list(eng.state.seqs[200].generated) == _reference_greedy(trained_params, p2, 5)


def test_eos_stops_generation(trained_params):
    eng = _engine(trained_params)
    ref = _reference_greedy(trained_params, [5, 9, 2, 7, 1], 8)
    eos = ref[2]
    eng2 = _engine(trained_params, eos_token_id=eos)
    outs = eng2.generate([[5, 9, 2, 7, 1]], max_new_tokens=8)
    assert outs[0] == ref[:3], (outs[0], ref)


def test_compiled_program_reuse(trained_params):
    """Steady-state serving uses a BOUNDED, shape-bucketed program set:
    one prefill-chunk program, the fused-decode ladder (K, K/2, ... — one
    per rung), and the single-step tail — never a per-shape compile."""
    import math
    eng = _engine(trained_params)
    eng.generate([[5, 9, 2, 7, 1], [3, 3, 8]], max_new_tokens=8)
    k = eng.econfig.decode_steps_per_dispatch
    bound = 2 + max(0, int(math.log2(max(1, k))))
    assert len(eng._step_fns) <= bound, list(eng._step_fns)
    # a second generation of the same shape compiles NOTHING new
    before = set(eng._step_fns)
    eng.generate([[9, 1, 4], [2, 2, 6, 8]], max_new_tokens=8)
    assert set(eng._step_fns) == before, (before, set(eng._step_fns))


def test_kv_pages_released_on_flush(trained_params):
    eng = _engine(trained_params)
    free0 = eng.kv.allocator.free_pages
    eng.generate([[5, 9, 2, 7, 1]], max_new_tokens=4)
    # every page is either back on the free list or retained (refcount 1)
    # by the prefix cache for future prefix hits — none is leaked to a
    # flushed sequence
    cached = eng.kv.prefix_cache.cached_pages
    assert eng.kv.allocator.free_pages + cached == free0
    # with the cache off, flush returns everything to the free list
    eng2 = _engine(trained_params, enable_prefix_cache=False)
    free0 = eng2.kv.allocator.free_pages
    eng2.generate([[5, 9, 2, 7, 1]], max_new_tokens=4)
    assert eng2.kv.allocator.free_pages == free0


def _save_tiny_hf(tmp_path, kind):
    import torch
    torch.manual_seed(0)
    if kind == "mixtral":
        from transformers import MixtralConfig as HFC, MixtralForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
                     num_local_experts=4, num_experts_per_tok=2, rope_theta=1e4,
                     tie_word_embeddings=False)
    elif kind == "qwen2":
        from transformers import Qwen2Config as HFC, Qwen2ForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=64,
                     rope_theta=1e4, use_sliding_window=False, tie_word_embeddings=False)
    elif kind == "falcon":
        from transformers import FalconConfig as HFC, FalconForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                     new_decoder_architecture=True, num_kv_heads=2, parallel_attn=True,
                     bias=False, alibi=False, hidden_dropout=0.0, attention_dropout=0.0,
                     tie_word_embeddings=True, num_ln_in_parallel_attn=2)
    elif kind == "falcon_rw":
        from transformers import FalconConfig as HFC, FalconForCausalLM as HFM
        # falcon-rw: alibi positions, sequential residual, multi-head kv
        hf_cfg = HFC(vocab_size=128, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                     new_decoder_architecture=False, multi_query=False, parallel_attn=False,
                     bias=True, alibi=True, hidden_dropout=0.0, attention_dropout=0.0,
                     tie_word_embeddings=True)
    elif kind == "qwen2_moe_mixed":
        from transformers import Qwen2MoeConfig as HFC, Qwen2MoeForCausalLM as HFM
        # mixed dense/sparse stack: layer 0 dense (mlp_only_layers)
        hf_cfg = HFC(vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
                     shared_expert_intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, num_experts=4, num_experts_per_tok=2,
                     max_position_embeddings=64, rope_theta=1e4, norm_topk_prob=False,
                     tie_word_embeddings=False, mlp_only_layers=[0], decoder_sparse_step=1)
    elif kind == "opt":
        from transformers import OPTConfig as HFC, OPTForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, ffn_dim=96, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64, word_embed_proj_dim=64,
                     do_layer_norm_before=True, dropout=0.0, attention_dropout=0.0,
                     activation_function="relu")
    elif kind == "phi":
        from transformers import PhiConfig as HFC, PhiForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                     num_attention_heads=4, num_key_value_heads=4, partial_rotary_factor=0.5,
                     max_position_embeddings=64, rope_theta=1e4, hidden_dropout=0.0,
                     attention_dropout=0.0, tie_word_embeddings=False)
    else:
        from transformers import Qwen2MoeConfig as HFC, Qwen2MoeForCausalLM as HFM
        hf_cfg = HFC(vocab_size=128, hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
                     shared_expert_intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, num_experts=4, num_experts_per_tok=2,
                     max_position_embeddings=64, rope_theta=1e4, norm_topk_prob=False,
                     tie_word_embeddings=False, mlp_only_layers=[], decoder_sparse_step=1)
    hf_model = HFM(hf_cfg).eval()
    d = tmp_path / kind
    hf_model.save_pretrained(d)
    return str(d), hf_model


def _hf_greedy(hf_model, prompt, n_new):
    import torch
    ids = torch.tensor([prompt], dtype=torch.int64)
    with torch.no_grad():
        for _ in range(n_new):
            logits = hf_model(ids).logits
            ids = torch.cat([ids, logits[:, -1].argmax(-1, keepdim=True)], dim=1)
    return [int(t) for t in ids[0, len(prompt):]]


@pytest.mark.parametrize("kind", ["qwen2", "mixtral", "falcon", "falcon_rw", "opt", "phi", "qwen2_moe", "qwen2_moe_mixed"])
def test_build_hf_engine_paged_generate(kind, tmp_path):
    """Every arch the reference serves through FastGen must generate through
    the paged v2 engine matching HF greedy decode (VERDICT r1 #4 + the full
    model_implementations sweep: llama-family, mixtral MoE, falcon parallel-
    residual, opt learned-positions, phi partial-rotary, qwen2-moe shared
    expert).  ref: inference/v2/model_implementations/*/policy.py."""
    from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine
    path, hf_model = _save_tiny_hf(tmp_path, kind)
    eng = build_hf_engine(path)
    # fp32 for tight logits parity; the serving path itself forces dropless
    # MoE routing (build_cache_model), so no drop_tokens override here
    cfg = eng.cfg
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32, "remat": False})
    kv = PagedKVConfig(num_pages=64, page_size=8, max_pages_per_seq=8)
    eng = InferenceEngineV2(cfg, eng.params,
                            RaggedInferenceEngineConfig(kv=kv, kv_dtype=jnp.float32))
    prompt = [5, 9, 2, 7, 1, 3]
    got = eng.generate([prompt], max_new_tokens=6)[0]
    want = _hf_greedy(hf_model, prompt, 6)
    assert got == want, f"{kind}: paged decode {got} != HF greedy {want}"


def test_v1_engine_generate_matches(trained_params):
    """v1 init_inference greedy generate == cache-free golden."""
    import deepspeed_tpu as ds
    model = LlamaForCausalLM(CFG)
    eng = ds.init_inference(model=model, config={"tensor_parallel": 1, "dtype": "fp32"},
                            params=trained_params)
    prompt = [5, 9, 2, 7, 1]
    out = eng.generate(np.asarray([prompt], np.int32), max_new_tokens=6)
    assert list(out[0, len(prompt):]) == _reference_greedy(trained_params, prompt, 6)


def test_v1_kernel_inject_and_dtype(trained_params):
    """replace_with_kernel_inject switches to the Pallas attention impl;
    dtype casts params (ref: inference/engine.py kernel-injection + dtype)."""
    import deepspeed_tpu as ds
    model = LlamaForCausalLM(CFG)
    eng = ds.init_inference(model=model, config={"replace_with_kernel_inject": True,
                                                 "dtype": "bf16"}, params=trained_params)
    assert eng.module.cfg.attention_impl == "flash"
    ids = jnp.zeros((1, 8), jnp.int32)
    logits = eng.forward(ids)
    leaf = jax.tree.leaves(eng.params)[0]
    assert leaf.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ------------------------------------------------------------------ prefix cache


def test_prefix_cache_shares_pages_and_matches_reference(trained_params):
    """Shared system prompt: the second+ sequences reuse the first's full
    prefix pages (one physical set) and still decode greedily identical to
    the cache-free model (ref: prefix_cache_manager.py)."""
    eng = _engine(trained_params)
    prefix = list(range(1, 25))          # 24 tokens = 3 full pages @ page_size 8
    prompts = [prefix + [30 + i] for i in range(4)]

    outs = []
    for i, p in enumerate(prompts):
        eng.put([100 + i], [p], max_new_tokens=4)
        while 100 + i in eng.state.seqs and not eng.state.seqs[100 + i].done:
            eng.step()
        outs.append(list(eng.state.seqs[100 + i].generated))

    pc = eng.kv.prefix_cache
    assert pc is not None and pc.hits >= 3, (pc.hits, pc.misses)
    # all four sequences share the SAME 3 physical prefix pages
    first_pages = eng.state.seqs[100].pages[:3]
    for i in range(1, 4):
        assert eng.state.seqs[100 + i].pages[:3] == first_pages
        assert eng.state.seqs[100 + i].seen_tokens >= 24
    # and the outputs match the cache-free golden decode
    for p, got in zip(prompts, outs):
        assert got == _reference_greedy(trained_params, p, 4), (p, got)


def test_prefix_cache_page_accounting(trained_params):
    """A shared-prefix batch allocates ~one set of prefix pages: 4 sequences
    with a 3-page common prefix use 3 shared + 4 private tails, not 4x4."""
    eng = _engine(trained_params)
    alloc = eng.kv.allocator
    base_free = alloc.free_pages
    prefix = list(range(1, 25))
    for i in range(4):
        eng.put([200 + i], [prefix + [40 + i]], max_new_tokens=2)
        while not eng.state.seqs[200 + i].done:
            eng.step()
    in_use = base_free - alloc.free_pages
    # 3 prefix pages + <=2 tail pages per seq (25th token + 2 generated)
    assert in_use <= 3 + 4 * 2, in_use
    # releasing the sequences keeps the cached pages alive for future hits
    cached_before = eng.kv.prefix_cache.cached_pages
    for i in range(4):
        eng.flush(200 + i)
    assert eng.kv.prefix_cache.cached_pages == cached_before
    eng.put([299], [prefix + [99]], max_new_tokens=2)
    assert eng.state.seqs[299].seen_tokens >= 24  # hit after creators released


def test_prefix_cache_eviction_under_pressure(trained_params):
    """Allocator pressure evicts LRU cache-only pages instead of raising."""
    kv = PagedKVConfig(num_pages=12, page_size=8, max_pages_per_seq=8)
    sched = SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=8, decode_bucket=4)
    eng = build_engine(CFG, trained_params,
                       RaggedInferenceEngineConfig(kv=kv, scheduler=sched, kv_dtype=jnp.float32))
    # fill the cache with a 3-page prefix, then release
    eng.put([1], [list(range(1, 26))], max_new_tokens=2)
    while not eng.state.seqs[1].done:
        eng.step()
    eng.flush(1)
    assert eng.kv.prefix_cache.cached_pages >= 3
    # a DIFFERENT long prompt needs more pages than remain free → eviction
    eng.put([2], [list(range(50, 75))], max_new_tokens=2)
    while not eng.state.seqs[2].done:
        eng.step()
    assert eng.state.seqs[2].generated == _reference_greedy(trained_params, list(range(50, 75)), 2)


def test_prefix_cache_disabled(trained_params):
    eng = _engine(trained_params, enable_prefix_cache=False)
    assert eng.kv.prefix_cache is None
    eng.put([1], [list(range(1, 20))], max_new_tokens=2)
    while not eng.state.seqs[1].done:
        eng.step()
    assert eng.state.seqs[1].generated == _reference_greedy(trained_params, list(range(1, 19 + 1)), 2)


def test_prefix_cache_evicts_leaves_first(trained_params):
    """Eviction drops the NEWEST chain entries (leaves): freeing a root
    would make every descendant unmatchable while staying pinned."""
    eng = _engine(trained_params)
    pc = eng.kv.prefix_cache
    prompt = list(range(1, 26))        # 3 full pages @ page_size 8
    eng.put([1], [prompt], max_new_tokens=2)
    while not eng.state.seqs[1].done:
        eng.step()
    eng.flush(1)
    before = pc.cached_pages
    assert before >= 3
    assert pc.evict(1) == 1
    # the surviving prefix still matches (2 of the 3 prompt pages)
    pages, _ = pc.match(prompt)
    assert len(pages) == 2, len(pages)
    eng.kv.allocator.free(pages)  # drop the refs match() took


def test_prefix_cache_rejects_hash_collision(trained_params):
    """A (simulated) chain-hash collision must NOT attach another prompt's
    pages: match verifies the stored token tuple."""
    eng = _engine(trained_params)
    pc = eng.kv.prefix_cache
    prompt = list(range(1, 18))        # 2 full pages
    eng.put([1], [prompt], max_new_tokens=2)
    while not eng.state.seqs[1].done:
        eng.step()
    # poison: rewrite the stored token tuples to a different prompt, keeping
    # the hashes — as a real collision would
    for h, (page, _, parent) in list(pc._pages.items()):
        pc._pages[h] = (page, tuple(range(900, 900 + eng.kv.page_size)), parent)
    pages, _ = pc.match(prompt)
    assert pages == [], "collision-mismatched pages must not match"


def test_prefix_cache_evicts_cold_chain_before_hot(trained_params):
    """Two cached chains; the recently-matched (hot) one survives eviction —
    leaf-only LRU, not global MRU."""
    eng = _engine(trained_params)
    pc = eng.kv.prefix_cache
    cold = list(range(1, 26))
    hot = list(range(50, 75))
    for uid, p in ((1, cold), (2, hot)):
        eng.put([uid], [p], max_new_tokens=2)
        while not eng.state.seqs[uid].done:
            eng.step()
        eng.flush(uid)
    # touch the hot chain (refreshes its whole LRU position)
    pages, _ = pc.match(hot)
    eng.kv.allocator.free(pages)
    assert pc.evict(2) == 2
    # cold chain lost its two leaves; hot chain fully intact
    hot_pages, _ = pc.match(hot)
    cold_pages, _ = pc.match(cold)
    assert len(hot_pages) == 3, len(hot_pages)
    assert len(cold_pages) == 1, len(cold_pages)
    eng.kv.allocator.free(hot_pages + cold_pages)
