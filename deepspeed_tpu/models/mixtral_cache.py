"""Mixtral with paged KV cache — the serving twin of models/mixtral.py.

ref: deepspeed/inference/v2/model_implementations/mixtral/policy.py:1 (+
model.py) — the reference's marquee FastGen MoE target.  Same contract as
``LlamaForCausalLMWithCache``: one chunked forward serving prefill /
continuation / decode with the KV arena threaded through, except the dense
SwiGLU MLP is the top-k-routed expert bank.  Routing at serving time runs
``train=False`` (eval capacity factor, no gating noise) and the router aux
loss is discarded.

Param-tree compatibility: names mirror MixtralForCausalLM exactly
(embed_tokens, layers/{self_attn, input_layernorm, post_attention_layernorm,
block_sparse_moe/{gate, experts}}, norm, lm_head), so checkpoints converted
by MixtralPolicy.convert — or trained with the training model — apply
unchanged.
"""

from typing import Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..moe.layer import MoE
from .llama import EMBED, VOCAB, RMSNorm, _logical
from .llama_cache import (LlamaAttentionCache, flat_positions, flat_step, live_slots, logits_as, sampled_rows,
                          scan_blocks)
from .mixtral import MixtralConfig


class MixtralBlockCache(nn.Module):
    """``x`` is the flat axis [T, hidden] of ``groups`` (models/llama_cache.py)."""
    cfg: MixtralConfig
    page_size: int
    groups: Tuple[Tuple[int, int], ...]

    @nn.compact
    def __call__(self, carry, layer, positions, block_table, start_pos, chunk_lens, stacked_banks=None):
        cfg = self.cfg
        x, pages = carry
        attn_out, pages = LlamaAttentionCache(cfg.as_llama(), self.page_size, self.groups, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_layernorm")(x), positions,
            pages, block_table, start_pos, chunk_lens, layer)
        h = x + attn_out
        # the step is one group of T tokens to the router and the sort; a
        # chunk's padding goes to no expert (the mask the KV write uses)
        moe_out, _l_aux, _counts = MoE(hidden_size=cfg.hidden_size,
                                       num_experts=cfg.num_local_experts,
                                       intermediate_size=cfg.intermediate_size,
                                       k=cfg.num_experts_per_tok,
                                       capacity_factor=cfg.capacity_factor,
                                       eval_capacity_factor=cfg.eval_capacity_factor,
                                       min_capacity=cfg.min_capacity,
                                       drop_tokens=cfg.drop_tokens,
                                       dtype=cfg.dtype,
                                       param_dtype=cfg.param_dtype,
                                       name="block_sparse_moe")(
                                           RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                                                   name="post_attention_layernorm")(h)[None], train=False,
                                           token_mask=live_slots(self.groups, chunk_lens)[None],
                                           stacked_banks=None if stacked_banks is None else (stacked_banks, layer))
        return (h + moe_out[0], pages), None


class MixtralForCausalLMWithCache(nn.Module):
    """Chunked forward with paged KV over the MoE stack.  ``apply(variables,
    tokens, start_pos, block_table, cache)`` → (logits, new_cache); a
    rectangle of tokens or, with ``groups``, the flat axis of several
    (``LlamaForCausalLMWithCache``)."""
    cfg: MixtralConfig
    page_size: int = 16

    def _stacked_banks(self):
        """The blocks' expert banks as the scan holds them, [L, E, ...], for the
        blocks to read in place: the scan hands a block its slice of them,
        and the dropless path's grouped product, a custom call on the TPU,
        would copy that slice every layer (three banks, 2.8 GB at Mixtral's
        widths).  None where there is nothing to read yet (``init``) or the
        banks would first have to be cast to the compute dtype."""
        experts = self.variables.get("params", {}).get("layers", {}).get("block_sparse_moe", {}).get("experts")
        if experts is None:
            return None
        banks = tuple(nn.meta.unbox(experts[name]) for name in ("w_gate", "w_up", "w_down"))
        return banks if all(w.dtype == self.cfg.dtype for w in banks) else None

    @nn.compact
    def __call__(self, input_ids, start_pos, block_table, cache, chunk_lens=None, last_only=False, groups=None):
        cfg = self.cfg
        tokens, groups, chunk_lens = flat_step(input_ids, chunk_lens, groups)
        positions = flat_positions(groups, start_pos)
        embed = nn.Embed(num_embeddings=cfg.vocab_size,
                         features=cfg.hidden_size,
                         dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=_logical(nn.initializers.normal(0.02), (VOCAB, EMBED)),
                         name="embed_tokens")
        x = embed(tokens)
        # the arena rides in the carry and the blocks name their layer in it,
        # as they do in the stacked expert banks (models/llama_cache.py)
        (x, cache), _ = scan_blocks(MixtralBlockCache, cfg.num_hidden_layers, n_broadcast=5)(
            cfg, self.page_size, groups, name="layers")((x, cache), jnp.arange(cfg.num_hidden_layers), positions,
                                                        block_table, start_pos, chunk_lens, self._stacked_banks())
        x = sampled_rows(x, chunk_lens, last_only, groups)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="norm")(x)
        logits = nn.DenseGeneral(features=cfg.vocab_size,
                                 use_bias=False,
                                 dtype=cfg.dtype,
                                 param_dtype=cfg.param_dtype,
                                 kernel_init=_logical(nn.initializers.lecun_normal(), (EMBED, VOCAB)),
                                 name="lm_head")(x)
        return logits_as(logits, input_ids, last_only), cache
