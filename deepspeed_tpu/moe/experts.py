"""Expert FFN bank (ref: deepspeed/moe/experts.py:13 Experts).

The reference deep-copies the expert module E/ep times per rank; here the
expert bank is ONE weight tensor with a leading expert dim carrying the
``experts`` logical axis → sharded over the ``expert`` mesh axis (see
module_inject/tp_rules.py).  Compute is a batched einsum that XLA maps onto
the MXU per expert shard.
"""

from typing import Any

import jax.numpy as jnp
from flax import linen as nn

from ..axes import EXPERT_EMBED, EXPERT_MLP, EXPERTS  # noqa: F401 (canonical vocabulary)


class ExpertsFFN(nn.Module):
    """E parallel SwiGLU FFNs.  ``__call__`` is the dense bank of the capacity
    dispatch: input [G, E, C, d] → [G, E, C, d].  ``bank()`` hands the three
    weight tensors, in the compute dtype, to the dropless path
    (``sharded_moe.dropless_moe``), which multiplies them by ragged groups."""
    num_experts: int
    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def setup(self):
        init = nn.initializers.lecun_normal()
        e, d, f = self.num_experts, self.hidden_size, self.intermediate_size
        self.w_gate = self.param("w_gate", nn.with_logical_partitioning(init, (EXPERTS, EXPERT_EMBED, EXPERT_MLP)),
                                 (e, d, f), self.param_dtype)
        self.w_up = self.param("w_up", nn.with_logical_partitioning(init, (EXPERTS, EXPERT_EMBED, EXPERT_MLP)),
                               (e, d, f), self.param_dtype)
        self.w_down = self.param("w_down", nn.with_logical_partitioning(init, (EXPERTS, EXPERT_MLP, EXPERT_EMBED)),
                                 (e, f, d), self.param_dtype)

    def bank(self):
        """(w_gate, w_up [E, d, f], w_down [E, f, d]) in the compute dtype."""
        return tuple(w.astype(self.dtype) for w in (self.w_gate, self.w_up, self.w_down))

    def __call__(self, x):
        w_gate, w_up, w_down = self.bank()
        x = x.astype(self.dtype)
        gate = jnp.einsum("gecd,edf->gecf", x, w_gate)
        up = jnp.einsum("gecd,edf->gecf", x, w_up)
        h = nn.silu(gate) * up
        return jnp.einsum("gecf,efd->gecd", h, w_down)
