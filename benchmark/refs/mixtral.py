"""Mixtral (mistralai/Mixtral-8x7B-v0.1, `modeling_mixtral.py`): the
published forward pass of one sequence in plain ``jax.numpy``, float32, no
kernels, cache or batching.  Per layer: RMSNorm -> grouped-query attention
with rotary embedding -> residual; RMSNorm -> router (softmax over all
experts, top-k, renormalised) -> sum of the chosen SwiGLU experts ->
residual.  No token is dropped.

Reads the run's bfloat16 weights (made by the benchmark from the seed) and
upcasts one expert at a time, so float32 copies of all experts are never
held at once.  Departures from the publication: none.
"""

import jax
import jax.numpy as jnp

from . import plain


def forward(params, ids, cfg, mode="f32", first=0):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margin [S - first]).  The margin of a position is
    the least, over the layers, of the gap between the router logit of the
    last expert chosen and that of the first one left out: where it is
    small, a rounding error anywhere before it changes which experts run."""
    p = params["params"]
    eps, n_exp, k = cfg["rms_norm_eps"], cfg["num_local_experts"], cfg["num_experts_per_tok"]
    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    layers = p["layers"]
    experts = layers["block_sparse_moe"]["experts"]
    for l in range(cfg["num_hidden_layers"]):
        small = plain.layer_slice({n: layers[n] for n in ("input_layernorm", "post_attention_layernorm",
                                                          "self_attn")}, l)
        router = layers["block_sparse_moe"]["gate"]["kernel"][l].astype(jnp.float32)
        x = x + plain.attention_block(plain.rms_norm(x, small["input_layernorm"]["weight"], eps),
                                      small["self_attn"], cfg, mode)
        h = plain.rms_norm(x, small["post_attention_layernorm"]["weight"], eps)
        router_logits = plain.matmul(h, router, mode)
        ranked = jax.lax.top_k(router_logits, k + 1)[0]
        gap = ranked[:, k - 1] - ranked[:, k]
        margin = gap if l == 0 else jnp.minimum(margin, gap)
        probs = jax.nn.softmax(router_logits, axis=-1)
        top_v, top_i = jax.lax.top_k(probs, k)
        top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        weights = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=jnp.float32) * top_v[..., None], axis=-2)

        def one_expert(e, acc, l=l, h=h, weights=weights):
            w = {n: jax.lax.dynamic_slice(a, (l, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)
                 for n, a in experts.items()}
            y = plain.swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode)
            return acc + jax.lax.dynamic_index_in_dim(weights, e, axis=1) * y

        x = x + jax.lax.fori_loop(0, n_exp, one_expert, jnp.zeros_like(x))
    x = plain.rms_norm(x[first:], p["norm"]["weight"].astype(jnp.float32), eps)
    return plain.matmul(x, p["lm_head"]["kernel"].astype(jnp.float32), mode), margin[first:]
