"""A mixed step of the ``cache_zoo.py`` families that no benchmark cell serves
(Falcon with rope and with alibi, OPT, Phi, Qwen2-MoE scanned and as the
mixed dense/sparse stack): two sequences decode while a third prompt of more
than two chunks prefills beside them, and every sequence's tokens are the
greedy continuation of the family's full-sequence training model, which knows
no pages, no chunks and no batching.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig, build_engine
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.falcon import FalconConfig, FalconForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.opt import OPTConfig, OPTForCausalLM
from deepspeed_tpu.models.phi import PhiConfig, PhiForCausalLM
from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM

from reference_greedy import greedy

CHUNK, NEW = 16, 6
#: the prompt that prefills beside the decoding rows: two whole chunks and a part of a third
LONG = 2 * CHUNK + 8
#: the long sequence fills the position table to its end, so that the padding of
#: its last chunk lies past it (OPT's learned table: the clamp in its twin)
MAX_POS = LONG + NEW
KV = PagedKVConfig(num_pages=48, page_size=8, max_pages_per_seq=8)
SCHED = SchedulerConfig(token_budget=40, max_seqs=8, prefill_chunk=CHUNK, decode_bucket=4)

_COMMON = dict(vocab_size=128, hidden_size=64, num_attention_heads=4, max_position_embeddings=MAX_POS,
               dtype=jnp.float32, param_dtype=jnp.float32, remat=False)
_QWEN = dict(_COMMON, intermediate_size=128, moe_intermediate_size=32, shared_expert_intermediate_size=64,
             num_key_value_heads=2, num_experts=4, num_experts_per_tok=2, rope_theta=1e4)
#: name -> (configuration, full-sequence model).  The twins read their pages through
#: the kernel (interpreted here), but for alibi, which goes through the jnp form
FAMILIES = {
    "falcon": (FalconConfig(**_COMMON, num_hidden_layers=2, num_kv_heads=2, new_decoder_architecture=True,
                            parallel_attn=True, bias=False, attention_impl="flash"), FalconForCausalLM),
    "falcon_rw": (FalconConfig(**_COMMON, num_hidden_layers=3, num_kv_heads=4, alibi=True, parallel_attn=False,
                               bias=True), FalconForCausalLM),
    "opt": (OPTConfig(**_COMMON, ffn_dim=96, num_hidden_layers=2, attention_impl="flash"), OPTForCausalLM),
    "phi": (PhiConfig(**_COMMON, intermediate_size=128, num_hidden_layers=2, num_key_value_heads=2,
                      partial_rotary_factor=0.5, attention_impl="flash"), PhiForCausalLM),
    "qwen2_moe": (Qwen2MoeConfig(**_QWEN, num_hidden_layers=2, attention_impl="flash"), Qwen2MoeForCausalLM),
    "qwen2_moe_mixed": (Qwen2MoeConfig(**_QWEN, num_hidden_layers=3, mlp_only_layers=(0, ), scan_layers=False,
                                       attention_impl="flash"), Qwen2MoeForCausalLM),
}


def _seeded(params, key):
    """The tree with every leaf the initialisers left constant (biases at
    zero, norm scales at one) moved by a seeded draw, so that a bias or a
    scale a twin dropped would show in the tokens."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
                           if bool((leaf == leaf.reshape(-1)[0]).all()) else leaf for leaf, k in zip(leaves, keys)])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_prompt_prefills_beside_decoding_rows_and_every_stream_is_the_training_models(family):
    cfg, full_cls = FAMILIES[family]
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    # the reference reads plain attention whatever the twin's pages go through
    full = full_cls(dataclasses.replace(cfg, attention_impl="reference"))
    params = _seeded(nn.meta.unbox(full.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))),
                     jax.random.PRNGKey(1))
    rng = np.random.default_rng(7)
    short, long_ = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (5, 11)], \
        rng.integers(1, cfg.vocab_size, LONG).tolist()
    eng = build_engine(cfg, params, RaggedInferenceEngineConfig(
        kv=KV, scheduler=SCHED, kv_dtype=jnp.float32, decode_steps_per_dispatch=1, max_new_tokens=NEW,
        enable_prefix_cache=False))
    assert sum(len(k) == 1 and k[0][1] == 1 for k in eng.step_shape_set()) == 2, "two decode buckets"
    eng.put([0, 1], short)
    while not all(s.in_decode for s in eng.state.seqs.values()):
        eng.step()
    eng.put([2], [long_])
    while not all(s.done for s in eng.state.seqs.values()):
        eng.step()
    rows = [s.to_row() for s in eng.anatomy.steps]
    mixed = [r for r in rows if r["path"] == "mixed"]
    assert mixed and all(r["rows_decode"] == 2 and r["seqs_prefill"] == 1 for r in mixed)
    assert sum(r["tokens_real"] - r["rows_decode"] for r in mixed) == LONG, "the prompt prefilled beside them whole"
    apply = full.apply   # one object: the reference's compiled program is kept by it
    for uid, prompt in enumerate(short + [long_]):
        assert list(eng.state.seqs[uid].generated) == greedy(apply, params, prompt, NEW, MAX_POS), (family, uid)
