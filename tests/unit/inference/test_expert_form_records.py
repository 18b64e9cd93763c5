"""The step records follow the form the layer took: ``engine_v2._expert_rows``
asks ``moe/sharded_moe.takes_sorted``, the one function ``dropless_moe`` asks,
with the engine's own experts a token and router width.  A decode dispatch of
a 64-expert twin (4 rows of 4 over 64: a fifth of the bank) writes all its
``expert_rows`` as ``expert_rows_kernel`` where the grouped product is the
kernel; Mixtral's (16 slots of 2 over 8: all of the bank) asks the rule of
the rows that live as well, as its program does when it runs: with 3 of the
16 live it writes all of them, with all 16 none."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig, engine_v2
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.mixtral import PRESETS, MixtralForCausalLM
from deepspeed_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from deepspeed_tpu.moe import sharded_moe

XING4_64 = Xing4Config(vocab_size=128, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
                       num_hidden_layers=2, first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
                       kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=64,
                       num_experts_per_tok=4, max_position_embeddings=256, dtype=jnp.float32, param_dtype=jnp.float32)
MIXTRAL_8 = dataclasses.replace(PRESETS["tiny"], num_hidden_layers=1, num_local_experts=8, num_experts_per_tok=2,
                                dtype=jnp.float32, remat=False, drop_tokens=False)
TWINS = {  # (config, full-sequence model, the router's experts, decode bucket, rows that decode, the slots sorted?,
    # those rows sorted?)
    "xing4_64_experts": (XING4_64, Xing4ForCausalLM, 64, 4, 2, True, True),
    "mixtral_8_experts": (MIXTRAL_8, MixtralForCausalLM, 8, 16, 3, False, True),
    "mixtral_8_experts_every_row_live": (MIXTRAL_8, MixtralForCausalLM, 8, 16, 16, False, False),
}


@pytest.mark.parametrize("kernel_path", [True, False], ids=["one_tpu_device", "cpu_or_gspmd"])
@pytest.mark.parametrize("twin", list(TWINS))
def test_a_decode_dispatch_records_the_form_its_layer_took(twin, kernel_path, monkeypatch):
    cfg, full, e, bucket, rows, sorted_form, rows_sorted = TWINS[twin]
    k = cfg.num_experts_per_tok
    if kernel_path:      # said here: the CPU's own answer is no
        monkeypatch.setattr(engine_v2, "takes_kernel", lambda: True)
    asked = []
    rule = sharded_moe.takes_sorted
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: asked.append((s, k, e)) or rule(s, k, e))
    params = nn.meta.unbox(jax.jit(full(cfg).init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    eng = InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=PagedKVConfig(num_pages=64, page_size=16, max_pages_per_seq=4),
        scheduler=SchedulerConfig(token_budget=64, max_seqs=bucket, prefill_chunk=8, decode_bucket=bucket),
        kv_dtype=jnp.float32, decode_steps_per_dispatch=4, max_new_tokens=6))
    eng.generate([[5, 9, 2, 7, 1 + i] for i in range(rows)], max_new_tokens=6)
    decode = [r for r in eng.anatomy.steps if r.path == "multi_decode"]
    assert decode and all(r.key.startswith(f"multi:b{bucket}:") and r.expert_rows == r.tokens_real * k for r in decode)
    assert rule(bucket, k, e) is sorted_form and rows in {r.rows_decode for r in decode}
    # the slots' answer where the program was traced, the live rows' where they said "dense" and it ran
    through_kernel = lambda r: kernel_path and (sorted_form or rule(r.rows_decode, k, e))  # noqa: E731
    assert all(r.expert_rows_kernel == (r.expert_rows if through_kernel(r) else 0) for r in decode)
    assert {bool(r.expert_rows_kernel) for r in decode if r.rows_decode == rows} == {kernel_path and rows_sorted}
    # the traced layers and the records asked the one function, with the same shapes (and of the live rows only
    # where the slots had said "dense")
    assert (bucket, k, e) in asked and ((rows, k, e) in asked or sorted_form)
    assert {(k_, e_) for _, k_, e_ in asked} == {(k, e)}
