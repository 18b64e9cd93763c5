"""Qwen1.5-MoE (Qwen/Qwen1.5-MoE-A2.7B, `modeling_qwen2_moe.py`): the
published forward pass, loss and gradients in plain ``jax.numpy``, float32,
no kernels or batching.  Per layer: RMSNorm -> attention (biased q/k/v,
rotary embedding) -> residual; RMSNorm -> router (softmax over all experts,
top-k, not renormalised: ``norm_topk_prob`` false) -> sum of the chosen
SwiGLU experts, plus the shared expert scaled by the sigmoid of its gate ->
residual.

Gradients are taken a layer at a time (``jax.vjp`` of one layer in float32),
so float32 copies of the whole model and of its gradient are never held at
once; only the sum of squares of each layer's gradient is kept.
Departures from the publication: none.
"""

import jax
import jax.numpy as jnp

from . import plain


def _layer(w, x, cfg, mode):
    """One decoder layer on sequences x [n_seq, S, H]; ``w`` is its float32 weights."""
    eps, n_exp, k = cfg["rms_norm_eps"], cfg["num_experts"], cfg["num_experts_per_tok"]

    def one_sequence(x):
        x = x + plain.attention_block(plain.rms_norm(x, w["input_layernorm"]["weight"], eps),
                                      w["self_attn"], cfg, mode)
        h = plain.rms_norm(x, w["post_attention_layernorm"]["weight"], eps)
        m = w["mlp"]
        probs = jax.nn.softmax(plain.matmul(h, m["gate"]["kernel"], mode), axis=-1)
        top_v, top_i = jax.lax.top_k(probs, k)
        if cfg["norm_topk_prob"]:
            top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
        weights = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=jnp.float32) * top_v[..., None], axis=-2)

        def one_expert(acc, e):
            y = plain.swiglu(h, m["w_gate"][e], m["w_up"][e], m["w_down"][e], mode)
            return acc + weights[:, e, None] * y, None

        routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x), jnp.arange(n_exp))
        shared = plain.swiglu(h, m["shared_gate_proj"]["kernel"], m["shared_up_proj"]["kernel"],
                              m["shared_down_proj"]["kernel"], mode)
        gate = jax.nn.sigmoid(plain.matmul(h, m["shared_expert_gate"]["kernel"], mode))
        return x + routed + gate * shared

    return jax.lax.map(one_sequence, x)


def _head_loss(w, x, labels, mask, cfg, mode):
    x = plain.rms_norm(x, w["norm"], cfg["rms_norm_eps"])
    logits = plain.matmul(x, w["lm_head"], mode)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.sum(mask)


def _sumsq(tree):
    return sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree))


def forward(params, ids, cfg, mode="f32"):
    """Logits [n_seq, S, vocab] of the token ids [n_seq, S]."""
    p = params["params"]
    layer_fwd = jax.jit(lambda layers, l, x: _layer(plain.layer_slice(layers, l), x, cfg, mode))
    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        x = layer_fwd(p["layers"], l, x)

    @jax.jit
    def head(norm, lm_head, x):
        x = plain.rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
        return plain.matmul(x, lm_head.astype(jnp.float32), mode)

    return head(p["norm"]["weight"], p["lm_head"]["kernel"], x)


def loss_and_grad_norm(params, ids, labels, mask, cfg, mode="f32"):
    """(loss, global gradient norm) of sequences ids [n_seq, S]: the mean
    over the positions where ``mask`` is 1 of the next-token cross entropy,
    and the L2 norm of its gradient over every parameter."""
    p = params["params"]
    f32 = jnp.float32
    n_layers = cfg["num_hidden_layers"]

    layer_fwd = jax.jit(lambda layers, l, x: _layer(plain.layer_slice(layers, l), x, cfg, mode))

    @jax.jit
    def layer_bwd(layers, l, x, g):
        _, vjp = jax.vjp(lambda w, x: _layer(w, x, cfg, mode), plain.layer_slice(layers, l), x)
        dw, dx = vjp(g)
        return _sumsq(dw), dx

    # ids, labels and mask are arguments, not constants of the traced programs:
    # a new seed must find every program in the compile cache
    @jax.jit
    def head(norm, lm_head, x, labels, mask):
        w = {"norm": norm.astype(f32), "lm_head": lm_head.astype(f32)}
        loss, vjp = jax.vjp(lambda w, x: _head_loss(w, x, labels, mask, cfg, mode), w, x)
        dw, dx = vjp(jnp.ones((), f32))
        return loss, _sumsq(dw), dx

    @jax.jit
    def embed_sumsq(embedding, dx, ids):
        grad = jnp.zeros(embedding.shape, f32).at[ids.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
        return jnp.sum(jnp.square(grad))

    xs = [p["embed_tokens"]["embedding"][ids].astype(f32)]
    for l in range(n_layers):
        xs.append(layer_fwd(p["layers"], l, xs[-1]))
    loss, total, g = head(p["norm"]["weight"], p["lm_head"]["kernel"], xs[-1], labels, mask)
    for l in reversed(range(n_layers)):
        sq, g = layer_bwd(p["layers"], l, xs[l], g)
        total = total + sq
    total = total + embed_sumsq(p["embed_tokens"]["embedding"], g, ids)
    return loss, jnp.sqrt(total)
