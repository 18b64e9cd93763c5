"""Fixtures the telemetry tests share."""

import pytest


@pytest.fixture(scope="module")
def tiny_serving():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            build_engine)
    from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.models.llama_cache import PagedKVConfig

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128,
                      rope_theta=1e4, dtype=jnp.float32, scan_layers=True,
                      remat=False)
    params = LlamaForCausalLM(cfg).init(jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8), jnp.int32))

    def make(k=1):
        kv = PagedKVConfig(num_pages=40, page_size=4, max_pages_per_seq=16)
        sched = SchedulerConfig(token_budget=64, max_seqs=4, prefill_chunk=8,
                                decode_bucket=2)
        return build_engine(cfg, params, RaggedInferenceEngineConfig(
            kv=kv, scheduler=sched, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=k, max_new_tokens=6))
    return make
