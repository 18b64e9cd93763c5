"""MoE layer (ref: deepspeed/moe/layer.py:17 MoE → sharded_moe.py:533 MOELayer).

Drop-in FFN replacement: [B, S, d] → ([B, S, d], l_aux, exp_counts).
Wire it into a transformer block in place of the dense MLP; add ``l_aux``
(times a coefficient) to the loss — same contract as the reference, where
the MoE layer returns (output, l_aux, exp_counts).
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..comm.mesh import BATCH_AXES, EXPERT_AXIS, axis_size, get_global_mesh
from ..axes import EMBED
from .experts import ExpertsFFN
from .mappings import drop_tokens, gather_tokens
from .sharded_moe import _capacity, dispatch_combine, dropless_dispatch, top1_gating, topk_gating


class MoE(nn.Module):
    """ref: deepspeed/moe/layer.py MoE(hidden_size, expert, num_experts, ep_size,
    k, capacity_factor, eval_capacity_factor, min_capacity, drop_tokens,
    use_rts, noisy_gate_policy)."""
    hidden_size: int
    num_experts: int = 1
    intermediate_size: Optional[int] = None
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    drop_tokens: bool = True
    noisy_gate_policy: Optional[str] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True, token_mask=None, stacked_banks=None):
        """``token_mask``: [B, S] bool or None — positions that carry no token
        (a serving step's padding).  ``stacked_banks``: None, or (the expert
        banks of a scanned trunk, still stacked [L, E, ...] and in the compute
        dtype, this layer's index): the same weights as this layer's own
        ``experts``, read in place (``sharded_moe._experts_grouped`` says
        why).  Only the dropless path reads either: masked positions go to no
        expert there and come out as zeros."""
        b, s, d = x.shape
        mesh = get_global_mesh()
        # TP×EP: split the token dim across the TP group so each token is
        # routed exactly once (ref: moe/mappings.py drop_tokens before the
        # experts); gathered back after the combine below
        x = drop_tokens(x, dim=1)

        # gate projection (ref: TopKGate.wg — kept fp32 for stable softmax)
        gate_logits = nn.Dense(self.num_experts,
                               use_bias=False,
                               dtype=jnp.float32,
                               param_dtype=jnp.float32,
                               kernel_init=nn.with_logical_partitioning(nn.initializers.lecun_normal(),
                                                                        (EMBED, "experts_gate")),
                               name="gate")(x.astype(jnp.float32))
        experts = ExpertsFFN(num_experts=self.num_experts,
                             hidden_size=d,
                             intermediate_size=self.intermediate_size or 4 * d,
                             dtype=self.dtype,
                             param_dtype=self.param_dtype,
                             name="experts")
        use_noise = bool(self.k == 1 and self.noisy_gate_policy and train and self.has_rng("gating"))

        if not self.drop_tokens and mesh.shape.get(EXPERT_AXIS, 1) == 1:
            # dropless, experts on one shard: the sorted dispatch
            noise = None
            if use_noise and self.noisy_gate_policy == "RSample":
                noise = jax.random.gumbel(self.make_rng("gating"), gate_logits.shape)
            bank, layer = (experts.bank(), None) if stacked_banks is None else stacked_banks
            out, l_aux, exp_counts = dropless_dispatch(x.astype(self.dtype), gate_logits, bank, self.k, token_mask,
                                                       noise, layer)
            return gather_tokens(out.astype(x.dtype), dim=1), l_aux, exp_counts

        # capacity dispatch, a group a data shard; dropless over an expert
        # mesh axis keeps it, with room for every token (sharded_moe's header)
        groups = axis_size(mesh, *BATCH_AXES)
        if b % groups != 0:
            groups = 1
        tokens_per_group = (b // groups) * s
        cap_factor = self.capacity_factor if train else self.eval_capacity_factor
        capacity = (_capacity(tokens_per_group, self.num_experts, cap_factor, self.min_capacity, self.k)
                    if self.drop_tokens else tokens_per_group)

        xg = x.reshape(groups, tokens_per_group, d)
        lg = gate_logits.reshape(groups, tokens_per_group, self.num_experts)

        if use_noise:
            rngs = jax.random.split(self.make_rng("gating"), groups)
            l_aux, combine, dispatch, exp_counts = jax.vmap(
                lambda lg_i, rng_i: top1_gating(lg_i, capacity, self.noisy_gate_policy, rng_i))(lg, rngs)
        elif self.k == 1:
            l_aux, combine, dispatch, exp_counts = jax.vmap(
                lambda lg_i: top1_gating(lg_i, capacity, None, None))(lg)
        else:
            l_aux, combine, dispatch, exp_counts = jax.vmap(
                lambda lg_i: topk_gating(lg_i, self.k, capacity, self.drop_tokens))(lg)

        out = dispatch_combine(xg, combine, dispatch, experts)
        out = out.reshape(b, s, d).astype(x.dtype)
        out = gather_tokens(out, dim=1)
        return out, jnp.mean(l_aux), jnp.sum(exp_counts, axis=0)
