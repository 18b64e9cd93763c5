"""EvaByte (https://huggingface.co/EvaByte/EvaByte, ``config.json``:
``model_type`` ``evabyte``, ``attention_class`` ``eva``): the forward pass of
one sequence in plain ``jax.numpy``, float32, no kernels, cache, pages, ring
or batching.  Per layer, with ``x`` the residual stream:

  h = RMSNorm(x) with weight 1 + g;  q, k, v = h Wq, h Wk, h Wv;  RoPE on q, k
  chunk c (16 tokens) is summarised to one key and one value:
      a_j = softmax_j((k_j . phi) / sqrt(d)),  k~_c = sum_j a_j k_j + mu,  v~_c = sum_j a_j v_j
  query t (window w = t // window_size) attends under one softmax of
  q . key / sqrt(d) to the exact (k_j, v_j) of its own window up to itself and
  to (k~_c, v~_c) of every chunk of the windows before it, never to a summary
  of its own window;  x = x + o Wo
  x = x + SwiGLU(RMSNorm(x))

then RMSNorm and one head matrix ``hidden x (num_pred_heads x vocab_size)``;
head i predicts byte t + 1 + i.  ``forward`` gives head 0 (what serving
samples from), ``forward_all_heads`` every head.

A dense model has no router: the margins are +inf.

Departures from the publication: none known.  Three readings are the
configuration file's and not the config's (its ``assumed``): the
``1/sqrt(d)`` on the ``phi`` logits, that a window's summaries become visible
only once the window is complete, and the head as one matrix of
``num_pred_heads x vocab_size`` columns.
"""

import jax
import jax.numpy as jnp

from . import plain

HIGHEST = plain.HIGHEST


def _norm(x, g, cfg):
    return plain.rms_norm(x, 1.0 + g, cfg["rms_norm_eps"])   # norm_add_unit_offset


def _attention(h, w, cfg, mode):
    s, hid = h.shape
    n = cfg["num_attention_heads"]
    d, chunk, window = hid // n, cfg["chunk_size"], cfg["window_size"]
    q, k, v = (plain.matmul(h, w[name]["kernel"].reshape(hid, n * d), mode).reshape(s, n, d)
               for name in ("q_proj", "k_proj", "v_proj"))
    q, k = plain.rope(q, cfg["rope_theta"]), plain.rope(k, cfg["rope_theta"])

    # one summary a complete chunk
    n_chunks = s // chunk
    kc = k[:n_chunks * chunk].reshape(n_chunks, chunk, n, d)
    vc = v[:n_chunks * chunk].reshape(n_chunks, chunk, n, d)
    a = jax.nn.softmax(jnp.einsum("cjnd,nd->cjn", kc, w["adaptive_phi"], precision=HIGHEST) / jnp.sqrt(jnp.float32(d)),
                       axis=1)[..., None]
    k_sum = jnp.sum(a * kc, axis=1) + w["adaptive_mu_k"]    # [n_chunks, n, d]
    v_sum = jnp.sum(a * vc, axis=1)

    out = []
    for lo in range(0, s, window):   # a loop over windows, dense masks inside
        hi = min(lo + window, s)
        n_sum = lo // chunk          # the summaries of every window before this one
        keys = jnp.concatenate([k_sum[:n_sum], k[lo:hi]])
        vals = jnp.concatenate([v_sum[:n_sum], v[lo:hi]])
        is_summary = jnp.arange(keys.shape[0]) < n_sum
        exact_pos = jnp.arange(keys.shape[0]) - n_sum + lo
        mask = is_summary[None, :] | (exact_pos[None, :] <= jnp.arange(lo, hi)[:, None])

        def head(qkv, mask=mask):
            qh, kh, vh = qkv         # [q, d], [keys, d], [keys, d]
            scores = jnp.matmul(qh, kh.T, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.matmul(probs, vh, precision=HIGHEST)

        # a head at a time, so that the scores of all heads are never held at once
        out.append(jax.lax.map(head, (q[lo:hi].swapaxes(0, 1), keys.swapaxes(0, 1), vals.swapaxes(0, 1))).swapaxes(0, 1))
    o = jnp.concatenate(out).reshape(s, n * d)
    return plain.matmul(o, w["o_proj"]["kernel"].reshape(n * d, hid), mode)


def forward_all_heads(params, ids, cfg, mode="f32", first=0):
    """Logits [S - first, num_pred_heads, vocab] of the positions from
    ``first`` on of the token ids [S]."""
    p = params["params"]
    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    for l in range(cfg["num_hidden_layers"]):
        w = plain.layer_slice(p["layers"], l)
        x = x + _attention(_norm(x, w["input_layernorm"]["weight"], cfg), w["self_attn"], cfg, mode)
        mlp = w["mlp"]
        x = x + plain.swiglu(_norm(x, w["post_attention_layernorm"]["weight"], cfg), mlp["gate_proj"]["kernel"],
                             mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], mode)
    x = _norm(x[first:], p["norm"]["weight"].astype(jnp.float32), cfg)
    head = p["lm_head"]["kernel"].astype(jnp.float32)    # [hidden, heads, vocab]
    return plain.matmul(x, head.reshape(head.shape[0], -1), mode).reshape(x.shape[0], *head.shape[1:])


def forward(params, ids, cfg, mode="f32", first=0):
    """(head 0's logits [S - first, vocab], router margins [S - first]: +inf,
    the model routes nothing)."""
    logits = forward_all_heads(params, ids, cfg, mode, first)[:, 0]
    return logits, jnp.full(logits.shape[:1], jnp.inf, jnp.float32)
