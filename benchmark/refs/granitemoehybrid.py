"""Granite 4.0-H with routed experts
(https://huggingface.co/ibm-granite/granite-4.0-h-small ``config.json``:
``model_type`` ``granitemoehybrid``, 72 experts, ten a token; transformers
``models/granitemoehybrid/modeling_granitemoehybrid.py``): the forward pass of
one sequence in plain ``jax.numpy``, float32, no kernels, cache, pages, slots,
chunks or batching.  The mixers are the dense sibling's, imported from
``refs/granitehybrid.py`` (Mamba-2 one position a step, attention without
positions).  With ``x`` the residual stream and ``u = RMSNorm(h)``,

  x = embedding_multiplier * E[ids]
  h = x + residual_multiplier * mixer_i(RMSNorm(x))
  l = W_r u                                  [router width], float32, no bias
  the num_experts_per_tok largest of l;  g = softmax over those logits alone
  routed = sum_e g_e W2_e(silu(a_e) * b_e),   [a_e | b_e] = W1_e u     (2 x intermediate_size)
  shared = W2_s(silu(a) * b),                 [a | b] = W1_s u          (2 x shared_intermediate_size)
  x = h + residual_multiplier * (routed + shared)
  logits = RMSNorm(x) E^T / logits_scaling

Top-k of the logits first and then the softmax over the k, as published
(``GraniteMoeHybridTopKGating``).  Layout: the published ``input_linear`` of
the experts is one matrix ``[2 f, hidden]`` an expert whose first half goes
through the activation; the program's tree holds the halves as ``w_gate`` (the
first) and ``w_up`` ``[E, hidden, f]`` and ``W2`` as ``w_down`` ``[E, f,
hidden]``, and that tree is what this file reads.

**A chip's share**: ``num_local_experts`` experts from ``first_expert`` of a
router ``router_experts`` wide (absent: the bank holds them all).  The router
and the softmax are over the whole width; the experts not held add nothing
and nothing stands in their place; the shared MLP is whole.  ``vocab_size``
rows of the tied embedding are all there is.

The router margin of a position is the gap between its k-th and (k+1)-th
logit, the least over the layers.
"""

import jax
import jax.numpy as jnp

from . import plain
from .granitehybrid import _attention, _mamba, layer_place

#: what ``forward(without=)`` may leave out, each a fault the checks must see
CONTROLS = ("routed", "shared", "state", "expert")


def _f32(a):
    return a.astype(jnp.float32)


def _mamba_stateless(u, w, cfg, mode):
    """The Mamba-2 mixer with an empty state before every position: ``y_t =
    (dt_t x_t (x) B_t) C_t + D x_t``, what a recurrence that carried nothing gives."""
    heads, p_dim, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d, s = heads * p_dim, u.shape[0]
    proj = plain.matmul(u, w["in_proj"]["kernel"], mode)
    z, xbc, dt = proj[:, :d], proj[:, d:2 * d + 2 * n], proj[:, 2 * d + 2 * n:]
    k = w["conv_kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(padded[j:j + s] * w["conv_kernel"][j] for j in range(k))
    xbc = jax.nn.silu(conv + w["conv_bias"] if "conv_bias" in w else conv)
    x, b_mat, c_mat = xbc[:, :d].reshape(s, heads, p_dim), xbc[:, d:d + n], xbc[:, d + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = (dt * jnp.sum(b_mat * c_mat, axis=-1, keepdims=True))[:, :, None] * x
    y = (y + w["D"][:, None] * x).reshape(s, d) * jax.nn.silu(z)
    return plain.matmul(plain.rms_norm(y, w["norm"]["weight"], cfg["rms_norm_eps"]), w["out_proj"]["kernel"], mode)


def routed(u, router, bank, period, cfg, mode, held=None):
    """(this share's part of the routed experts' sum for ``u`` [S, C], the
    router margin [S]).  ``bank``: the periods' stack [periods, E held, ...] of
    the layer's experts, of which one expert is upcast at a time.  ``held``:
    how many of the bank's experts are counted (a control of the tests)."""
    k, n_held, first = cfg["num_experts_per_tok"], cfg["num_local_experts"], cfg.get("first_expert", 0)
    logits = plain.matmul(u, _f32(router), mode)                                   # the router's whole width
    ranked, top_i = jax.lax.top_k(logits, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    gates = jax.nn.softmax(ranked[:, :k], axis=-1)
    weights = jnp.sum(jax.nn.one_hot(top_i[:, :k], logits.shape[-1], dtype=jnp.float32) * gates[..., None], axis=-2)

    def one_expert(e, acc):
        we = {n: _f32(jax.lax.dynamic_slice(a, (period, e, 0, 0), (1, 1) + a.shape[2:])[0, 0])
              for n, a in bank.items()}
        y = plain.swiglu(u, we["w_gate"], we["w_up"], we["w_down"], mode)
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, axis=1) * y

    return jax.lax.fori_loop(0, n_held if held is None else held, one_expert, jnp.zeros_like(u)), margin


def shared(u, w, mode):
    a, b = jnp.split(plain.matmul(u, _f32(w["input_linear"]["kernel"]), mode), 2, axis=-1)
    return plain.matmul(jax.nn.silu(a) * b, _f32(w["output_linear"]["kernel"]), mode)


def layer(x, stacked, period, kind, cfg, mode, without=()):
    """One layer of the residual stream ``x`` [S, C] -> (x, the router margin [S])."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    w = plain.layer_slice({n: v for n, v in stacked.items() if n != "block_sparse_moe"}, period)
    u = plain.rms_norm(x, w["input_layernorm"]["weight"], eps)
    if kind == "mamba":
        mixed = (_mamba_stateless if "state" in without else _mamba)(u, w["mixer"], cfg, mode)
    else:
        mixed = _attention(u, w["mixer"], cfg, mode)
    h = x + res * mixed
    u = plain.rms_norm(h, w["post_attention_layernorm"]["weight"], eps)
    moe = stacked["block_sparse_moe"]
    router = jax.lax.dynamic_index_in_dim(moe["router"]["kernel"], period, keepdims=False)
    y, margin = routed(u, router, moe["experts"], period, cfg, mode,
                       cfg["num_local_experts"] - 1 if "expert" in without else None)
    m = (0.0 if "routed" in without else y) + (0.0 if "shared" in without else shared(u, w["shared_mlp"], mode))
    return h + res * m, margin


def forward(params, ids, cfg, mode="f32", first=0, without=()):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margins [S - first]: the gap between the last logit
    chosen and the first left out, least over the layers).  ``without`` (the
    tests' controls, each of which must fail the limits): "routed" (the
    experts add nothing), "shared" (the shared MLP adds nothing), "state"
    (the recurrence reads an empty state), "expert" (the last held expert
    adds nothing)."""
    p = params["params"]
    embedding = p["embed_tokens"]["embedding"]
    x = cfg["embedding_multiplier"] * _f32(embedding[ids])
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i, kind in enumerate(cfg["layer_types"]):
        period, name = layer_place(cfg["layer_types"], i)
        x, gap = layer(x, p["periods"][name], period, kind, cfg, mode, without)
        margin = jnp.minimum(margin, gap)
    x = plain.rms_norm(x[first:], _f32(p["norm"]["weight"]), cfg["rms_norm_eps"])
    # the head a block of the vocabulary at a time: its float32 copy is never held whole
    blocks = 8 if embedding.shape[0] % 1024 == 0 else 1
    rows = embedding.shape[0] // blocks
    logits = jnp.concatenate([plain.matmul(x, _f32(embedding[i * rows:(i + 1) * rows]).T, mode)
                              for i in range(blocks)], axis=-1)
    return logits / cfg["logits_scaling"], margin[first:]
