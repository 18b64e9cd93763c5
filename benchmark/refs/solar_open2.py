"""Solar-Open2 (https://huggingface.co/upstage/Solar-Open2-250B ``config.json``,
``model_type`` ``solar_open2``): the forward pass of one sequence in plain
``jax.numpy``, float32 at the highest matmul precision, no kernels, cache,
pages, slots, chunks or batching, given the share of the model that the
configuration file states (the experts and the vocabulary rows held).  With
``x`` the residual stream [S, hidden],

  x = E[ids];   x += Mixer_i(RMSNorm(x));   x += MoE_i(RMSNorm(x));   logits = RMSNorm(x) W_head

and the mixer by whether ``i`` is in ``gqa_layers``:

  gqa   q, k, v = W_q x, W_k x, W_v x (no bias, no rotary, no q/k norm), heads of d, grouped;
        causal softmax(q k^T / sqrt(d)) v;  o <- o * sigmoid(W_g x) over [H x d];  out W_o o
  kda   Kimi Delta Attention, low-rank gate projections:
        q, k, v = silu(conv(W x)) (causal depthwise convolution, no bias), heads [H, d];
        q, k <- x / sqrt(sum x^2 + 1e-6) a head;  q <- q / sqrt(d);
        g = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)  a key channel;  beta = 2 sigmoid(W_b x)  a head;
        a head's state S [keys, values], zero at the start:
          S <- diag(exp(g_t)) S;   S <- S + beta_t k_t (v_t - S^T k_t)^T;   o_t = S^T q_t
        o <- RMSNorm_head(o) * sigmoid(W_gb (W_ga x));  out W_o o

The recurrence is a ``lax.scan`` over positions, one position a step, never
a chunked form; attention goes a block of queries at a time.

  moe   s = sigmoid(W_r x) over the router's published width; chosen = the num_experts_per_tok largest of
        s + e_score_correction_bias; w = s[chosen] / (sum + 1e-20) * routed_scaling_factor (over all chosen);
        out = sum over the chosen experts THAT ARE HELD (first_expert .. first_expert + n_routed_experts - 1)
        of w_i SwiGLU_i(x), + SwiGLU_shared(x).  What the absent experts would add is left out.

The parameters lie as the program's trunk stacks them: the layer pattern's
shortest period is scanned; layer ``i`` is entry ``i // period`` of
``periods/layer_<i % period>``.  Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import plain

_BLOCK = 512   # queries a block of the attention


def layer_kinds(cfg):
    return ["gqa" if i in cfg["gqa_layers"] else "kda" for i in range(cfg["num_hidden_layers"])]


def layer_place(kinds, i):
    """(period index, the layer's name in its period) of layer ``i``."""
    n = len(kinds)
    period = next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))
    return i // period, f"layer_{i % period}"


def _f32(a):
    return a.astype(jnp.float32)


def _kda(x, w, cfg, mode, state_term=True):
    lin = cfg["linear_attn_config"]
    heads, d, ksize = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    s, width = x.shape[0], heads * d
    qkv = jnp.concatenate([plain.matmul(x, _f32(w[n]["kernel"]), mode) for n in ("q_proj", "k_proj", "v_proj")], -1)
    padded = jnp.concatenate([jnp.zeros((ksize - 1, 3 * width), jnp.float32), qkv])
    conv = _f32(w["conv_kernel"])
    qkv = jax.nn.silu(sum(padded[j:j + s] * conv[j] for j in range(ksize)))
    q, k, v = (t.reshape(s, heads, d) for t in jnp.split(qkv, 3, axis=-1))
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * d**-0.5, unit(k)
    f = plain.matmul(plain.matmul(x, _f32(w["f_a_proj"]["kernel"]), mode), _f32(w["f_b_proj"]["kernel"]), mode)
    g = -jnp.exp(_f32(w["A_log"]))[:, None] * jax.nn.softplus((f + _f32(w["dt_bias"])).reshape(s, heads, d))
    beta = jax.nn.sigmoid(plain.matmul(x, _f32(w["b_proj"]["kernel"]), mode))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at                                  # [H, K], [H, K], [H, V], [H, K], [H]
        state = jnp.exp(g_t)[:, :, None] * state
        if not state_term:     # a control of the tests: the state read as if it were empty
            state = jnp.zeros_like(state)
        u = beta_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * state, axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32), (q, k, v, g, beta))
    o = plain.rms_norm(o, _f32(w["o_norm"]["weight"]), cfg["rms_norm_eps"]).reshape(s, width)
    gate = plain.matmul(plain.matmul(x, _f32(w["g_a_proj"]["kernel"]), mode), _f32(w["g_b_proj"]["kernel"]), mode)
    return plain.matmul(o * jax.nn.sigmoid(gate), _f32(w["o_proj"]["kernel"]), mode)


def _gqa(x, w, cfg, mode):
    s = x.shape[0]
    n, n_kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = plain.matmul(x, _f32(w["q_proj"]["kernel"]), mode).reshape(s, n, d)
    k = plain.matmul(x, _f32(w["k_proj"]["kernel"]), mode).reshape(s, n_kv, d)
    v = plain.matmul(x, _f32(w["v_proj"]["kernel"]), mode).reshape(s, n_kv, d)
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
            scores = jnp.where(pos[None, :] <= qpos[:, None], scores * d**-0.5, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(scores, axis=-1), v_i, precision=plain.HIGHEST)

        return jax.lax.map(block, jnp.arange(0, q.shape[0], size)).reshape(-1, d)[:s]

    out = jax.lax.map(head, jnp.arange(n)).swapaxes(0, 1).reshape(s, n * d)
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(plain.matmul(x, _f32(w["g_proj"]["kernel"]), mode))
    return plain.matmul(out, _f32(w["o_proj"]["kernel"]), mode)


def _swiglu(h, gate, up, down, mode):
    return plain.matmul(jax.nn.silu(plain.matmul(h, gate, mode)) * plain.matmul(h, up, mode), down, mode)


def _experts(h, w, bank, period, cfg, mode, held=None):
    """(this share's part of the expert block's output, the router margin)
    of h [S, C]; ``bank`` is the periods' stack [periods, E held, ...] of the
    layer's experts, of which one expert is upcast at a time.  ``held``: the
    experts of the bank that are counted (a control of the tests)."""
    k, n_held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first = cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(plain.matmul(h, _f32(w["gate"]["kernel"]), mode))       # the router's whole width
    ranked, top_i = jax.lax.top_k(scores + _f32(w["e_score_correction_bias"]), k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_i = top_i[:, :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    weights = jnp.sum(jax.nn.one_hot(top_i, scores.shape[-1], dtype=jnp.float32) * top_s[..., None], axis=-2)

    def one_expert(e, acc):
        we = {n: jax.lax.dynamic_slice(a, (period, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)
              for n, a in bank.items()}
        y = _swiglu(h, we["w_gate"], we["w_up"], we["w_down"], mode)
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, axis=1) * y

    out = jax.lax.fori_loop(0, n_held if held is None else held, one_expert, jnp.zeros_like(h))
    sh = w["shared_experts"]
    return out + _swiglu(h, *(_f32(sh[n]["kernel"]) for n in ("gate_proj", "up_proj", "down_proj")), mode), margin


def forward(params, ids, cfg, mode="f32", first=0, without=()):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margin [S - first]: the gap in ``s + bias`` between
    the last expert chosen and the first left out, least over the layers).
    ``without`` (the tests' controls, each of which must fail the limits):
    "state" (the delta rule reads an empty state), "kda", "gqa" (the mixers
    of that kind add nothing), "expert" (the last held expert adds nothing)."""
    p = params["params"]
    eps, kinds = cfg["rms_norm_eps"], layer_kinds(cfg)
    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
    for i, kind in enumerate(kinds):
        period, name = layer_place(kinds, i)
        stacked = p["periods"][name]
        w = plain.layer_slice({n: v for n, v in stacked.items() if n != "mlp"}, period)
        u = plain.rms_norm(x, w["input_layernorm"]["weight"], eps)
        if kind not in without:
            x = x + (_kda(u, w["mixer"], cfg, mode, "state" not in without) if kind == "kda" else
                     _gqa(u, w["mixer"], cfg, mode))
        mlp = plain.layer_slice({n: v for n, v in stacked["mlp"].items() if n != "experts"}, period)
        y, gap = _experts(plain.rms_norm(x, w["post_attention_layernorm"]["weight"], eps), mlp,
                          stacked["mlp"]["experts"], period, cfg, mode,
                          cfg["n_routed_experts"] - 1 if "expert" in without else None)
        x, margin = x + y, jnp.minimum(margin, gap)
    x = plain.rms_norm(x[first:], p["norm"]["weight"].astype(jnp.float32), eps)
    # the head a block of the vocabulary at a time: its float32 copy is never held whole
    head, blocks = p["lm_head"]["kernel"], 8 if cfg["vocab_size"] % 1024 == 0 else 1
    cols = head.shape[1] // blocks
    logits = jnp.concatenate([plain.matmul(x, head[:, i * cols:(i + 1) * cols].astype(jnp.float32), mode)
                              for i in range(blocks)], axis=-1)
    return logits, margin[first:]
