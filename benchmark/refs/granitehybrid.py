"""Granite 4.0-H (https://huggingface.co/ibm-granite/granite-4.0-h-micro,
``config.json``: ``model_type`` ``granitemoehybrid``, dense: no routed
experts): the forward pass of one sequence in plain ``jax.numpy``, float32,
no kernels, cache, pages, slots, chunks or batching.  There is no positional
encoding (``position_embedding_type: "nope"``).  With ``x`` the residual
stream,

  x = embedding_multiplier * E[ids]
  h = x + residual_multiplier * mixer_i(RMSNorm(x))
  x = h + residual_multiplier * W_out(silu(a) * b),   [a | b] = W_in RMSNorm(h)
  logits = RMSNorm(x) E^T / logits_scaling

and the mixer by ``layer_types[i]``:

  attention   q, k, v = W_q x, W_k x, W_v x (no bias, no rotary), heads of d, grouped;
              causal softmax(attention_multiplier * q k^T) v;  out W_o
  mamba       Mamba-2, one group:  [z | xBC | dt] = W_in u  (widths d_inner | d_inner + 2 N | H);
              xBC <- silu(conv1d_causal_depthwise(xBC, k) + b);  [x | B | C] = d_inner | N | N;
              dt = softplus(dt + dt_bias), A = -exp(A_log), both a head;
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t  a head, [P, N];  y_t = S_t C_t + D x_t;
              y <- RMSNorm(y * silu(z)) over d_inner, with a weight;  out W_out y

The recurrence is a ``lax.scan`` over positions, one position a step, never
a block form; attention goes a block of queries at a time, so that 3,072
positions fit.  A dense model has no router: the margins are +inf.

The parameters lie as the program's trunk stacks them: the layer pattern's
shortest period is scanned; layer ``i`` is entry ``i // period`` of
``periods/layer_<i % period>``.
"""

import jax
import jax.numpy as jnp

from . import plain

HIGHEST = plain.HIGHEST
_BLOCK = 512   # queries a block of the attention


def layer_place(layer_types, i):
    """(period index, the layer's name in its period) of layer ``i``."""
    kinds, n = list(layer_types), len(layer_types)
    period = next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))
    return i // period, f"layer_{i % period}"


def _layer_weights(p, layer_types, i):
    period, name = layer_place(layer_types, i)
    return plain.layer_slice(p["periods"][name], period)


def _mamba(u, w, cfg, mode):
    heads, p_dim, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    d = heads * p_dim
    s = u.shape[0]
    proj = plain.matmul(u, w["in_proj"]["kernel"], mode)
    z, xbc, dt = proj[:, :d], proj[:, d:2 * d + 2 * n], proj[:, 2 * d + 2 * n:]
    k = w["conv_kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(padded[j:j + s] * w["conv_kernel"][j] for j in range(k))
    xbc = jax.nn.silu(conv + w["conv_bias"] if "conv_bias" in w else conv)
    x, b_mat, c_mat = xbc[:, :d].reshape(s, heads, p_dim), xbc[:, d:d + n], xbc[:, d + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                                       # [S, H]
    a = -jnp.exp(w["A_log"])                                                      # [H]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at                                                  # [H, P], [H], [N], [N]
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p_dim, n), jnp.float32), (x, dt, b_mat, c_mat))
    y = (y + w["D"][:, None] * x).reshape(s, d) * jax.nn.silu(z)
    return plain.matmul(plain.rms_norm(y, w["norm"]["weight"], cfg["rms_norm_eps"]), w["out_proj"]["kernel"], mode)


def _attention(x, w, cfg, mode):
    s, hid = x.shape
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // n
    q = plain.matmul(x, w["q_proj"]["kernel"], mode).reshape(s, n, d)
    k = plain.matmul(x, w["k_proj"]["kernel"], mode).reshape(s, n_kv, d)
    v = plain.matmul(x, w["v_proj"]["kernel"], mode).reshape(s, n_kv, d)
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
            scores = jnp.matmul(jax.lax.dynamic_slice_in_dim(q_i, lo, size), k_i.T, precision=HIGHEST)
            scores = jnp.where(pos[None, :] <= qpos[:, None], scores * cfg["attention_multiplier"], -jnp.inf)
            return jnp.matmul(jax.nn.softmax(scores, axis=-1), v_i, precision=HIGHEST)

        return jax.lax.map(block, jnp.arange(0, s, size)).reshape(-1, d)[:s]

    out = jax.lax.map(head, jnp.arange(n)).swapaxes(0, 1).reshape(s, hid)
    return plain.matmul(out, w["o_proj"]["kernel"], mode)


def forward(params, ids, cfg, mode="f32", first=0):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margins [S - first]: +inf, the model routes nothing)."""
    p = params["params"]
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    embedding = p["embed_tokens"]["embedding"].astype(jnp.float32)
    x = cfg["embedding_multiplier"] * embedding[ids]
    for i, kind in enumerate(cfg["layer_types"]):
        w = _layer_weights(p, cfg["layer_types"], i)
        u = plain.rms_norm(x, w["input_layernorm"]["weight"], eps)
        mixed = _mamba(u, w["mixer"], cfg, mode) if kind == "mamba" else _attention(u, w["mixer"], cfg, mode)
        h = x + res * mixed
        a, b = jnp.split(plain.matmul(plain.rms_norm(h, w["post_attention_layernorm"]["weight"], eps),
                                      w["shared_mlp"]["input_linear"]["kernel"], mode), 2, axis=-1)
        x = h + res * plain.matmul(jax.nn.silu(a) * b, w["shared_mlp"]["output_linear"]["kernel"], mode)
    x = plain.rms_norm(x[first:], p["norm"]["weight"].astype(jnp.float32), eps)
    logits = plain.matmul(x, embedding.T, mode) / cfg["logits_scaling"]
    return logits, jnp.full(logits.shape[:1], jnp.inf, jnp.float32)
