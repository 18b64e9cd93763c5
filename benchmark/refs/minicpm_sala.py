"""MiniCPM-SALA (https://huggingface.co/openbmb/MiniCPM-SALA ``config.json``,
``model_type`` ``minicpm_sala``): the forward pass of one sequence in plain
``jax.numpy``, float32 at the highest matmul precision, no kernels, cache,
pages, slots, chunks or batching.  With ``x`` the residual stream [S, hidden]
and ``r = scale_depth / sqrt(mup_denominator)`` (the published denominator,
whatever depth the configuration holds),

  x = scale_emb E[ids];  x += r Mixer_i(RMSNorm(x));  x += r SwiGLU_i(RMSNorm(x));
  logits = W_head (RMSNorm(x) / (hidden_size / dim_model_base))

and the mixer by ``mixer_types[i]``:

  lightning-attn   q, k, v = W x (no bias), heads [H, d];  q, k <- RMSNorm_head;  q, k <- rope (whole head);
        q <- q / sqrt(d);  a head's state S [keys, values], zero at the start:
          S_t = lambda_h S_{t-1} + k_t v_t^T;   o_t = S_t^T q_t
        lambda_h = exp(-s_h (1 - l / (L - 1) + 1e-5)), s_h = 2^(-8 h / H), h = 1 .. H, l = first_layer + i the
        layer's published index, L = published_layers;  o <- RMSNorm_head(o) * sigmoid(W_g x);  out W_o o
  minicpm4   q, k, v = W x, grouped heads of d, no position term;  q, k <- RMSNorm_head;  with ``sparse``
        = {kernel_size, kernel_stride, block_size, init_blocks, window_size, topk, dense_len}:
          Kc[i] = mean(k[stride i .. stride i + kernel_size - 1]) a key head, there once its last token is;
          for the query at t, head h of key head g: p_h = softmax_i(q_h . Kc_g[i] / sqrt(d)) over the i that
          are there;  P_g[i] = sum_{h in g} p_h[i];  B_g[b] = max_{i in [m b - 1, m b + m - 1]} P_g[i],
          m = block_size / kernel_stride;  seen: blocks 0 .. init_blocks - 1, the window_size / block_size
          blocks up to t's own, and of the rest the topk of highest B_g (ties to the lower index);
          o_h = softmax over the key rows s <= t of the seen blocks of q_h . k_g[s] / sqrt(d), times v_g[s];
          a query at t < dense_len sees every s <= t.
        o <- o * sigmoid(W_g x);  out W_o o

Departures from the published code, each by choice: (a) dense or sparse by
the QUERY'S POSITION (the published code switches by the call's length), so
that a result does not depend on how a prompt was cut into chunks; (b) the
softmax over compressed keys is exact (the published kernels approximate its
log-sum-exp from a second, coarser pooling); (c) the forced blocks (the
initial ones and the window's) are seen BESIDE the ``topk`` chosen ones, not
counted inside them (MiniCPM4's "about 6k of 128k visible" = 97 x 64; the
other reading would choose ``topk`` less the forced ones).

The recurrence is a ``lax.scan`` over positions, one position a step, never
a chunked form; attention goes a block of queries at a time.  The parameters
lie as the program's trunk stacks them: each run of consecutive layers of one
kind is scanned, layer ``i`` is entry ``i - start`` of ``run_<j>/layer``.
Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import plain

_BLOCK = 512   # queries a block of the attention


def runs(kinds):
    """(kind, first layer, layers) of each run of consecutive layers of one kind."""
    out = []
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, i, 1])
    return out


def _f32(a):
    return a.astype(jnp.float32)


def _head_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def log_decay(cfg, i):
    """``log lambda_h`` [H] of layer ``i`` here."""
    h = cfg["lightning_nh"]
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    depth = cfg.get("published_layers") or cfg["num_hidden_layers"]
    return -slopes * (1.0 - (cfg.get("first_layer", 0) + i) / max(depth - 1, 1) + 1e-5)


def _lightning(x, w, cfg, mode, i, state_term=True):
    s = x.shape[0]
    heads, d, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], cfg["rms_norm_eps"]
    q, k, v = (plain.matmul(x, _f32(w[n]["kernel"]), mode).reshape(s, heads, d) for n in ("q_proj", "k_proj", "v_proj"))
    q, k = _head_norm(q, _f32(w["q_norm"]["weight"]), eps), _head_norm(k, _f32(w["k_norm"]["weight"]), eps)
    q, k = plain.rope(q, cfg["rope_theta"]) * d**-0.5, plain.rope(k, cfg["rope_theta"])
    decay = jnp.exp(log_decay(cfg, i))[:, None, None]

    def step(state, at):
        q_t, k_t, v_t = at                                               # [H, K], [H, K], [H, V]
        if not state_term:     # a control of the tests: the state read as if it were empty
            state = jnp.zeros_like(state)
        state = decay * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32), (q, k, v))
    o = _head_norm(o, _f32(w["o_norm"]["weight"]), eps).reshape(s, heads * d)
    o = o * jax.nn.sigmoid(plain.matmul(x, _f32(w["g_proj"]["kernel"]), mode))
    return plain.matmul(o, _f32(w["o_proj"]["kernel"]), mode)


def chosen_blocks(q, kc, qpos, sp, n_blocks, shift=0):
    """(bool [Q, G, n_blocks]: the blocks each query sees; margin [Q]: the
    relative gap between the last block score chosen and the first left out,
    least over the key heads, inf for a query under ``dense_len``) for
    queries ``q`` [Q, H, d] at positions ``qpos`` [Q] over the compressed
    keys ``kc`` [N, G, d].  ``shift``: a control, the chosen blocks moved up
    by so many."""
    n_q, h, d = q.shape
    n, g = kc.shape[:2]
    m, topk = sp["block_size"] // sp["kernel_stride"], sp["topk"]
    scores = jnp.einsum("qgrd,ngd->qgrn", q.reshape(n_q, g, h // g, d), kc, precision=plain.HIGHEST) * d**-0.5
    there = (sp["kernel_stride"] * jnp.arange(n) + sp["kernel_size"] - 1)[None, :] <= qpos[:, None]      # [Q, N]
    p = jax.nn.softmax(jnp.where(there[:, None, None, :], scores, -jnp.inf), axis=-1)
    p = jnp.where(there[:, None, :], jnp.nan_to_num(p).sum(axis=2), -1.0)                                  # [Q, G, N]
    blk = jnp.arange(n_blocks)
    i = m * blk[:, None] - 1 + jnp.arange(m + 1)[None, :]                                                  # [nb, m + 1]
    pooled = jnp.where((i >= 0) & (i < n), p[:, :, jnp.clip(i, 0, n - 1)], -1.0).max(axis=-1)              # [Q, G, nb]
    own = (qpos // sp["block_size"])[:, None, None]
    win = sp["window_size"] // sp["block_size"]
    rest = (blk >= sp["init_blocks"]) & (blk <= own - win)
    ranked, top_i = jax.lax.top_k(jnp.where(rest, pooled, -1.0), min(topk + 1, n_blocks))
    picked = (ranked[..., :topk] >= 0)[..., None] & (top_i[..., :topk, None] + shift == blk)               # [Q, G, k, nb]
    dense = (qpos < sp["dense_len"])[:, None, None]
    seen = (dense | (blk < sp["init_blocks"]) | (blk > own - win) | picked.any(axis=2)) & (blk <= own)
    if ranked.shape[-1] > topk:
        gap = jnp.where(ranked[..., topk] >= 0, (ranked[..., topk - 1] - ranked[..., topk]) / ranked[..., topk - 1],
                        jnp.inf)
    else:
        gap = jnp.full(ranked.shape[:-1], jnp.inf)
    return seen, jnp.where(dense[:, 0, 0], jnp.inf, gap.min(axis=-1))


def _sparse(x, w, cfg, mode, walk="sparse"):
    """(the mixer's output [S, hidden], the selection margin [S]).  ``walk``
    (the tests' controls): "dense" every query sees every ``s <= t``;
    "shift" the chosen blocks are taken one block further on."""
    s = x.shape[0]
    n, g, d, eps, sp = (cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"], cfg["rms_norm_eps"],
                        cfg["sparse"])
    q = plain.matmul(x, _f32(w["q_proj"]["kernel"]), mode).reshape(s, n, d)
    k = plain.matmul(x, _f32(w["k_proj"]["kernel"]), mode).reshape(s, g, d)
    v = plain.matmul(x, _f32(w["v_proj"]["kernel"]), mode).reshape(s, g, d)
    q, k = _head_norm(q, _f32(w["q_norm"]["weight"]), eps), _head_norm(k, _f32(w["k_norm"]["weight"]), eps)
    stride, size = sp["kernel_stride"], min(_BLOCK, s)
    n_ck = max((s - sp["kernel_size"]) // stride + 1, 1)     # those whose last token the sequence holds (one at least)
    k_pad = jnp.pad(k, ((0, sp["kernel_size"]), (0, 0), (0, 0)))
    kc = jax.lax.map(lambda i: jnp.mean(jax.lax.dynamic_slice_in_dim(k_pad, stride * i, sp["kernel_size"]), axis=0),
                     jnp.arange(n_ck))
    n_blocks = -(-s // sp["block_size"])
    pos = jnp.arange(s)
    q = jnp.pad(q, ((0, -s % size), (0, 0), (0, 0)))

    def block(lo):
        qpos = lo + jnp.arange(size)
        q_b = jax.lax.dynamic_slice_in_dim(q, lo, size)                                       # [size, H, d]
        seen, margin = chosen_blocks(q_b, kc, qpos, sp, n_blocks, shift=1 if walk == "shift" else 0)
        if walk == "dense":
            seen = jnp.ones_like(seen)

        def key_head(at):
            """One key head's queries [size, rep, d] over its keys: the scores of all heads are never held at once."""
            q_g, k_g, v_g, seen_g = at
            keys = jnp.repeat(seen_g, sp["block_size"], axis=-1)[:, :s] & (pos[None, :] <= qpos[:, None])
            scores = jnp.einsum("qrd,kd->qrk", q_g, k_g, precision=plain.HIGHEST) * d**-0.5
            probs = jax.nn.softmax(jnp.where(keys[:, None, :], scores, -jnp.inf), axis=-1)
            return jnp.einsum("qrk,kd->qrd", probs, v_g, precision=plain.HIGHEST)

        o = jax.lax.map(key_head, (q_b.reshape(size, g, n // g, d).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                                   seen.swapaxes(0, 1)))
        return o.swapaxes(0, 1).reshape(size, n * d), margin

    out, margin = jax.lax.map(block, jnp.arange(0, q.shape[0], size))
    out, margin = out.reshape(-1, n * d)[:s], margin.reshape(-1)[:s]
    out = out * jax.nn.sigmoid(plain.matmul(x, _f32(w["g_proj"]["kernel"]), mode))
    return plain.matmul(out, _f32(w["o_proj"]["kernel"]), mode), margin


def forward(params, ids, cfg, mode="f32", first=0, without=()):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S]; the selection margin [S - first]: the relative gap between
    the ``topk``-th block score and the next, least over the sparse layers and
    key heads, inf where the position lies under ``dense_len``).  ``without``
    (the tests' controls, each of which must fail the limits): "state" (the
    linear layers read an empty state), "sparse" (a dense walk in the sparse
    layers' place), "shift" (the chosen blocks one block further on),
    "lightning" / "minicpm4" (the mixers of that kind add nothing)."""
    with jax.default_matmul_precision("highest"):
        p = params["params"]
        eps = cfg["rms_norm_eps"]
        r = cfg["scale_depth"] / cfg["mup_denominator"] ** 0.5
        x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32) * cfg["scale_emb"]
        margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
        walk = "dense" if "sparse" in without else "shift" if "shift" in without else "sparse"
        for j, (kind, start, count) in enumerate(runs(cfg["mixer_types"])):
            for i in range(start, start + count):
                w = plain.layer_slice(p[f"run_{j}"]["layer"], i - start)
                u = plain.rms_norm(x, w["input_layernorm"]["weight"], eps)
                if kind == "lightning-attn" and "lightning" not in without:
                    x = x + r * _lightning(u, w["mixer"], cfg, mode, i, "state" not in without)
                elif kind == "minicpm4" and "minicpm4" not in without:
                    y, gap = _sparse(u, w["mixer"], cfg, mode, walk)
                    x, margin = x + r * y, jnp.minimum(margin, gap)
                mlp = w["mlp"]
                x = x + r * plain.swiglu(plain.rms_norm(x, w["post_attention_layernorm"]["weight"], eps),
                                         *(mlp[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")), mode)
        x = plain.rms_norm(x[first:], p["norm"]["weight"].astype(jnp.float32), eps)
        x = x / (cfg["hidden_size"] / cfg["dim_model_base"])
        # the head a block of the vocabulary at a time: its float32 copy is never held whole
        head = p["lm_head"]["kernel"]
        blocks = next(b for b in (8, 4, 2, 1) if head.shape[1] % b == 0)
        cols = head.shape[1] // blocks
        logits = jnp.concatenate([plain.matmul(x, head[:, i * cols:(i + 1) * cols].astype(jnp.float32), mode)
                                  for i in range(blocks)], axis=-1)
        return logits, margin[first:]
