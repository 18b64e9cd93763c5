"""Phi-4-mini-flash-reasoning, the SambaY decoder-hybrid-decoder
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning, ``config.json``:
``model_type`` ``phi4flash``): the forward pass of one sequence in plain
``jax.numpy``, float32, no kernels, cache, pages, slots, rings or batching.
There is no positional encoding.  With ``L`` layers and ``x`` the residual
stream, every layer is

  h = x + mixer(LN1(x));  x = h + W_down(silu(g) * u),  [g, u] = W_gate_up LN2(h)

with LayerNorm (weight and bias), and the mixer by the layer's index ``i``:

  i < L/2, i even; i = L/2   Mamba-1:  [u, z] = W_in x;  u <- silu(conv1d_causal(u, k=4) + b);
                             [dt_r, B, C] = W_x u;  dt = softplus(W_dt dt_r + b_dt);  A = -exp(A_log);
                             s_t = exp(dt_t A) s_{t-1} + dt_t B_t u_t;  y_t = C_t . s_t + D u_t;
                             out = W_out (y * silu(z)).  Layer L/2's y is the memory m.
  i < L/2, i odd             differential attention over the window  t - W < j <= t
  i = L/2 + 1                differential attention, causal; its keys and values are the shared cache
  i >= L/2 + 2, i even       gated memory unit  W_2 (m_t * silu(W_1 x_t))
  i >= L/2 + 2, i odd        differential attention, causal, queries of its own, keys and values of layer L/2 + 1

Differential attention with heads of d: query heads 2i, 2i+1 are q1_i, q2_i;
key heads 2j, 2j+1 are k1_j, k2_j, value heads v1_j, v2_j; pair j serves the
query pairs of its group;  a1 = softmax(q1 k1^T / sqrt(d)) [v1 | v2],
a2 = softmax(q2 k2^T / sqrt(d)) [v1 | v2];  lambda = exp(lq1 . lk1) -
exp(lq2 . lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 i);  a pair gives
(1 - l0) RMSNorm_2d(a1 - lambda a2) (the norm has a weight), and the pairs
go through W_o (bias).  Then a final LayerNorm and logits x E^T.

The scan is a ``lax.scan`` over positions; attention goes a block of queries
at a time, so that 3,072 positions fit.  A dense model has no router: the
margins are +inf.

Readings that are the configuration file's and not the config's (its
``assumed``): the Mamba sizes, the layer pattern, the differential attention
as above, biases on the attention projections and none on Mamba's but the
convolution's and dt's, that the window counts the query's own position.
"""

import jax
import jax.numpy as jnp

from . import plain

HIGHEST = plain.HIGHEST
_BLOCK = 512   # queries a block of the attention


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w["weight"] + w["bias"]


def _linear(x, w, mode):
    y = plain.matmul(x, w["kernel"], mode)
    return y + w["bias"] if "bias" in w else y


def _mamba(x, w, cfg, mode):
    """(the mixer's output [S, hidden], the scan's ungated output y [S, d_inner])."""
    n, k = w["A_log"].shape[1], w["conv_kernel"].shape[0]      # d_state, d_conv
    s = x.shape[0]
    u, z = jnp.split(_linear(x, w["in_proj"], mode), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    u = jax.nn.silu(sum(padded[j:j + s] * w["conv_kernel"][j] for j in range(k)) + w["conv_bias"])
    proj = _linear(u, w["x_proj"], mode)
    rank = proj.shape[1] - 2 * n
    dt = jax.nn.softplus(_linear(proj[:, :rank], w["dt_proj"], mode))                   # [S, D]
    b_mat, c_mat = proj[:, rank:rank + n], proj[:, rank + n:]
    a = -jnp.exp(w["A_log"])                                                             # [D, N]

    def step(state, at):
        u_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t[:, None] * a) * state + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=1)

    _, y = jax.lax.scan(step, jnp.zeros_like(a), (u, dt, b_mat, c_mat))
    y = y + w["D"] * u
    return _linear(y * jax.nn.silu(z), w["out_proj"], mode), y


def _softmax_blocks(q, k, v, d, window):
    """q [S, d], k [S, d], v [S, 2d] -> softmax(q k^T / sqrt(d)) v, a block of
    queries at a time; ``window`` 0: every key up to the query's own."""
    s = q.shape[0]
    pos = jnp.arange(s)
    size = min(_BLOCK, s)
    q = jnp.pad(q, ((0, -s % size), (0, 0)))

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, size)
        qpos = lo + jnp.arange(size)
        mask = pos[None, :] <= qpos[:, None]
        if window:
            mask = mask & (pos[None, :] > qpos[:, None] - window)
        scores = jnp.matmul(qb, k.T, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        return jnp.matmul(jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), v, precision=HIGHEST)

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(q.shape[0], -1)[:s]


def _diff_attention(x, w, cfg, mode, layer, window=0, shared=None):
    """(the mixer's output, (keys, values) [S, n_kv, d] as this layer made or took them)."""
    s, hid = x.shape
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // n
    q = _linear(x, w["q_proj"], mode).reshape(s, n, d)
    k, v = shared if shared is not None else (_linear(x, w[p], mode).reshape(s, n_kv, d) for p in ("k_proj", "v_proj"))
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = jnp.exp(jnp.dot(w["lambda_q1"], w["lambda_k1"])) - jnp.exp(jnp.dot(w["lambda_q2"], w["lambda_k2"])) + l0
    group = (n // 2) // (n_kv // 2)     # query pairs a key pair

    def pair(i):
        """Query pair i against key pair i // group: [S, 2d]."""
        j = i // group
        heads = lambda a, h: jax.lax.dynamic_index_in_dim(a, h, axis=1, keepdims=False)  # noqa: E731
        vv = jnp.concatenate([heads(v, 2 * j), heads(v, 2 * j + 1)], axis=-1)
        a1 = _softmax_blocks(heads(q, 2 * i), heads(k, 2 * j), vv, d, window)
        a2 = _softmax_blocks(heads(q, 2 * i + 1), heads(k, 2 * j + 1), vv, d, window)
        diff = a1 - lam * a2
        diff = diff * jax.lax.rsqrt(jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + cfg["layer_norm_eps"])
        return (1.0 - l0) * diff * w["sub_norm"]["weight"]

    out = jax.lax.map(pair, jnp.arange(n // 2)).swapaxes(0, 1).reshape(s, hid)     # a pair at a time
    return _linear(out, w["o_proj"], mode), (k, v)


def _layer(x, w, cfg, mode, mixer):
    mixed, aux = mixer(_layer_norm(x, w["input_layernorm"], cfg["layer_norm_eps"]), w["mixer"])
    h = x + mixed
    g, u = jnp.split(_linear(_layer_norm(h, w["post_attention_layernorm"], cfg["layer_norm_eps"]),
                             w["mlp"]["gate_up_proj"], mode), 2, axis=-1)
    return h + _linear(jax.nn.silu(g) * u, w["mlp"]["down_proj"], mode), aux


def forward(params, ids, cfg, mode="f32", first=0):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margins [S - first]: +inf, the model routes nothing)."""
    p = params["params"]
    layers = cfg["num_hidden_layers"]
    half = layers // 2
    embedding = p["embed_tokens"]["embedding"].astype(jnp.float32)
    x = embedding[ids]
    for i in range(half):          # the self-decoder
        w = plain.layer_slice(p["self_decoder"]["mamba" if i % 2 == 0 else "attn"], i // 2)
        if i % 2 == 0:
            x, _ = _layer(x, w, cfg, mode, lambda h, m: _mamba(h, m, cfg, mode))
        else:
            x, _ = _layer(x, w, cfg, mode, lambda h, m, i=i: _diff_attention(h, m, cfg, mode, i, cfg["sliding_window"]))
    x, memory = _layer(x, _f32(p["mid_mamba"]), cfg, mode, lambda h, m: _mamba(h, m, cfg, mode))
    x, shared = _layer(x, _f32(p["mid_attn"]), cfg, mode, lambda h, m: _diff_attention(h, m, cfg, mode, half + 1))
    for i in range(half + 2, layers):   # the cross-decoder
        w = plain.layer_slice(p["cross_decoder"]["gmu" if i % 2 == 0 else "cross"], (i - half - 2) // 2)
        if i % 2 == 0:
            x, _ = _layer(x, w, cfg, mode, lambda h, m: (
                _linear(memory * jax.nn.silu(_linear(h, m["in_proj"], mode)), m["out_proj"], mode), None))
        else:
            x, _ = _layer(x, w, cfg, mode, lambda h, m, i=i: _diff_attention(h, m, cfg, mode, i, shared=shared))
    x = _layer_norm(x[first:], _f32(p["final_layernorm"]), cfg["layer_norm_eps"])
    logits = plain.matmul(x, embedding.T, mode)
    return logits, jnp.full(logits.shape[:1], jnp.inf, jnp.float32)
