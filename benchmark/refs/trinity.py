"""Trinity (https://huggingface.co/arcee-ai/Trinity-Large-Preview ``config.json``,
``model_type`` ``afmoe``; transformers ``models/afmoe/modeling_afmoe.py``): the
forward pass of one sequence in plain ``jax.numpy``, float32 at the highest
matmul precision, no kernels, cache, rings, pages, chunks or batching, given
the share of the model that the configuration file states (the layers, the
experts and the vocabulary rows held).  With ``x`` the residual stream [S,
hidden] and RMSNorm at ``rms_norm_eps`` with a weight a channel,

  x = E[ids] * sqrt(hidden_size)                                     (mup_enabled)
  x += RMSNorm_post_attn(Attn_i(RMSNorm_in(x)));   x += RMSNorm_post_mlp(FFN_i(RMSNorm_pre_mlp(x)))
  logits = RMSNorm_final(x) W_head

  Attn_i   q, k, v, g = W_q u, W_k u, W_v u, W_g u (no bias), heads of head_dim, grouped;
           q, k <- RMSNorm over a head (one weight of head_dim each);
           layer_types[i] == "sliding_attention": q, k <- rope(rope_theta, the whole head, half-split);
                                                  the query at t sees keys t - sliding_window + 1 .. t
           layer_types[i] == "full_attention":    no position term; sees keys 0 .. t
           o = softmax(q k^T / sqrt(head_dim)) v;  o <- o * sigmoid(g) over [heads x head_dim];  out W_o o
  FFN_i    a dense layer (the first num_dense_layers): SwiGLU of intermediate_size
           else s = sigmoid(W_r u) over the router's published width; chosen = the num_experts_per_tok largest
           of s + expert_bias; w = s[chosen] / (sum + 1e-20) (route_norm) * route_scale;
           out = SwiGLU_shared(u) + sum over the chosen experts THAT ARE HELD (first_expert .. first_expert +
           num_experts - 1) of w_e SwiGLU_e(u).  What the absent experts would add is left out.

Departures from the published code, each the configuration file's: the layers
are the ``num_dense_layers`` first of ``layer_types`` and then the expert
layers from ``expert_layers_from`` on (a pipeline stage's); the bank holds
``num_experts`` of the router's ``router_experts``; the embedding and the head
hold ``vocab_size`` rows.  ``n_group``, ``topk_group``, ``num_expert_groups``
and ``num_limited_groups`` are 1: no group-limited choice.  The published code
computes in bfloat16 with a float32 router; this is float32 throughout.

Attention goes a block of queries at a time.  The parameters lie as the
program's trunk names them, layer ``i`` under ``layers_<i>``.  Imports
nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import plain

_BLOCK = 512   # queries a block of the attention

SLIDING = "sliding_attention"

#: the switches of ``forward(without=)``: each a control that must fail the limits
CONTROLS = ("window", "rope_split", "gate", "head_norms", "expert", "route_scale")


def layer_kinds(cfg):
    """The kind of each layer held: the dense layers, then the expert layers."""
    dense, n = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    first = cfg.get("expert_layers_from")
    first = dense if first is None else first
    return list(cfg["layer_types"][:dense]) + list(cfg["layer_types"][first:first + n - dense])


def _f32(a):
    return a.astype(jnp.float32)


def _attention(x, w, cfg, mode, sliding, without):
    s = x.shape[0]
    n, n_kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = plain.matmul(x, _f32(w["q_proj"]["kernel"]), mode).reshape(s, n, d)
    k = plain.matmul(x, _f32(w["k_proj"]["kernel"]), mode).reshape(s, n_kv, d)
    v = plain.matmul(x, _f32(w["v_proj"]["kernel"]), mode).reshape(s, n_kv, d)
    if "head_norms" not in without:
        q = plain.rms_norm(q, _f32(w["q_norm"]["weight"]), cfg["rms_norm_eps"])
        k = plain.rms_norm(k, _f32(w["k_norm"]["weight"]), cfg["rms_norm_eps"])
    if sliding or "rope_split" in without:
        q, k = plain.rope(q, cfg["rope_theta"]), plain.rope(k, cfg["rope_theta"])
    window = cfg["sliding_window"] if sliding and "window" not in without else s
    pos = jnp.arange(s)
    size = min(_BLOCK, s)
    q = jnp.pad(q, ((0, -s % size), (0, 0), (0, 0)))

    def head(i):
        """Query head i against its key head, a block of queries at a time: [S, d]."""
        q_i = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        k_i = jax.lax.dynamic_index_in_dim(k, i // (n // n_kv), axis=1, keepdims=False)
        v_i = jax.lax.dynamic_index_in_dim(v, i // (n // n_kv), axis=1, keepdims=False)

        def block(lo):
            qpos = lo + jnp.arange(size)
            scores = jnp.matmul(jax.lax.dynamic_slice_in_dim(q_i, lo, size), k_i.T, precision=plain.HIGHEST)
            seen = (pos[None, :] <= qpos[:, None]) & (pos[None, :] > qpos[:, None] - window)
            return jnp.matmul(jax.nn.softmax(jnp.where(seen, scores * d**-0.5, -jnp.inf), axis=-1), v_i,
                              precision=plain.HIGHEST)

        return jax.lax.map(block, jnp.arange(0, q.shape[0], size)).reshape(-1, d)[:s]

    out = jax.lax.map(head, jnp.arange(n)).swapaxes(0, 1).reshape(s, n * d)
    if "gate" not in without:
        out = out * jax.nn.sigmoid(plain.matmul(x, _f32(w["gate_proj"]["kernel"]), mode))
    return plain.matmul(out, _f32(w["o_proj"]["kernel"]), mode)


def _mlp(h, w, mode):
    return plain.swiglu(h, *(_f32(w[n]["kernel"]) for n in ("gate_proj", "up_proj", "down_proj")), mode)


def _experts(h, w, cfg, mode, without):
    """(this share's part of the expert block's output, the router margin) of
    h [S, C]; one expert of the bank [E held, ...] is upcast at a time."""
    k, n_held = cfg["num_experts_per_tok"], cfg["num_experts"]
    first = cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(plain.matmul(h, _f32(w["gate"]["kernel"]), mode))        # the router's whole width
    ranked, top_i = jax.lax.top_k(scores + _f32(w["expert_bias"]), k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_i = top_i[:, :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg["route_norm"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    if "route_scale" not in without:
        top_s = top_s * cfg["route_scale"]
    weights = jnp.sum(jax.nn.one_hot(top_i, scores.shape[-1], dtype=jnp.float32) * top_s[..., None], axis=-2)
    bank = w["experts"]

    def one_expert(e, acc):
        we = {n: jax.lax.dynamic_index_in_dim(a, e, axis=0, keepdims=False).astype(jnp.float32)
              for n, a in bank.items()}
        y = plain.swiglu(h, we["w_gate"], we["w_up"], we["w_down"], mode)
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, axis=1) * y

    out = jax.lax.fori_loop(0, n_held - 1 if "expert" in without else n_held, one_expert, jnp.zeros_like(h))
    if cfg["num_shared_experts"]:
        out = out + _mlp(h, w["shared_experts"], mode)
    return out, margin


def forward(params, ids, cfg, mode="f32", first=0, without=()):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margin [S - first]: the gap in ``s + expert_bias``
    between the last expert chosen and the first left out, least over the
    expert layers).  ``without`` (``CONTROLS``, the reference's own switches,
    each of which must fail the limits): "window" (the window layers see
    every key behind them), "rope_split" (rotary on the full layers too),
    "gate" (no output gate), "head_norms" (no norm on q and k), "expert" (the
    last held expert adds nothing), "route_scale" (1 in its place)."""
    unknown = set(without) - set(CONTROLS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}: {CONTROLS}")
    p = params["params"]

    def norm(x, w):
        return plain.rms_norm(x, _f32(w["weight"]), cfg["rms_norm_eps"])

    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"]**0.5
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i, kind in enumerate(layer_kinds(cfg)):
        w = p[f"layers_{i}"]
        a = _attention(norm(x, w["input_layernorm"]), w["self_attn"], cfg, mode, kind == SLIDING, without)
        x = x + norm(a, w["post_attention_layernorm"])
        u = norm(x, w["pre_mlp_layernorm"])
        if i < cfg["num_dense_layers"]:
            m = _mlp(u, w["mlp"], mode)
        else:
            m, gap = _experts(u, w["mlp"], cfg, mode, without)
            margin = jnp.minimum(margin, gap)
        x = x + norm(m, w["post_mlp_layernorm"])
    x = norm(x[first:], p["norm"])
    # the head a block of the vocabulary at a time: its float32 copy is never held whole
    head, blocks = p["lm_head"]["kernel"], 8 if cfg["vocab_size"] % 1024 == 0 else 1
    cols = head.shape[1] // blocks
    logits = jnp.concatenate([plain.matmul(x, head[:, i * cols:(i + 1) * cols].astype(jnp.float32), mode)
                              for i in range(blocks)], axis=-1)
    return logits, margin[first:]
