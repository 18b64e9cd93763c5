"""Xing4.0 (XingChen-AGI/Xing4.0-29B-A4B, ``model_type`` ``xing4_0``): the
forward pass of one sequence in plain ``jax.numpy``, float32 at the highest
matmul precision, no kernels, cache or batching; imports nothing of the
program.

A token carries ``n = hc_mult`` streams ``X [n, C]``, ``X_0`` = n copies of
``E[id]``.  Every layer is two hyper-connected sublayers (manifold-constrained
hyper-connections, arXiv:2512.24880), around the attention and around the MLP
(layers before ``first_k_dense_replace``) or the expert block:

  x~ = RMSNorm_g(vec(X));  [H~pre | H~post | H~res] = a * (x~ phi) + b
  Hpre = sigmoid(H~pre), Hpost = 2 sigmoid(H~post),
  Hres = SK(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max)): exp, then
  hc_sinkhorn_iters times rows over (their sums + hc_eps), columns likewise;
  u = Hpre X;  y = F(RMSNorm(u));  X' = Hres X + Hpost^T y.

Latent attention, the *expanded* form a head at a time: ``c_q = RMSNorm(x
W_qa)``, ``[q_nope | q_pe] = c_q W_qb``, ``[c_kv | k_pe] = x W_kva``, ``c_kv
<- RMSNorm(c_kv)``, YaRN rotary on interleaved pairs of ``q_pe`` and of the
one ``k_pe``, ``[k_nope | v]_h = c_kv W_kvb``, ``s = (q_nope . k_nope + q_pe .
k_pe) * qk_head_dim^-1/2 * (0.1 mscale_all_dim ln factor + 1)^2``, causal
softmax, ``concat_h(softmax(s) v_h) W_o``.

Experts: ``s = sigmoid(h W_g)``; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias``; ``w = s[chosen] / (sum + 1e-20) *
routed_scaling_factor``; ``sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)``; no
token dropped.  One expert is upcast at a time.  After the last layer the
streams are added, normed, and go to the head.

Computed in blocks so that a sequence of 8k tokens fits beside the served
model: what is a function of a token alone (the hyper-connection's maps, the
dense MLP) 512 rows at a time, a head's queries 512 at a time against all its
keys, the head an eighth of the vocabulary at a time.  The numbers are the
unblocked ones.

Departures from the equations the configuration file states: none.  (The
multi-token-prediction module is not part of the trunk's forward pass.)
"""

import math

import jax
import jax.numpy as jnp

from . import plain


#: rows a block holds where what is a function of a token alone is computed a block at a time
_ROWS = 512


def _f32(a):
    """A weight in float32, upcast where it is used: no layer's float32 copy is held whole."""
    return a.astype(jnp.float32)


def _by_rows(fn, *arrays):
    """``fn(*arrays)`` a block of ``_ROWS`` leading rows at a time (the same
    numbers: ``fn`` is a function of a row alone), so that a long sequence's
    temporaries are a block's; a sequence that is no whole number of blocks
    goes at once."""
    s = arrays[0].shape[0]
    if s <= _ROWS or s % _ROWS:
        return fn(*arrays)
    out = jax.lax.map(lambda block: fn(*block), tuple(a.reshape((s // _ROWS, _ROWS) + a.shape[1:]) for a in arrays))
    return jax.tree.map(lambda o: o.reshape((s, ) + o.shape[2:]), out)


def _inv_freq(cfg):
    dim, base, yarn = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg.get("rope_scaling")
    plain_freq = 1.0 / (base**(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if not yarn:
        return plain_freq
    original = yarn["original_max_position_embeddings"]
    dim_of = lambda rotations: dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(dim_of(yarn["beta_fast"])), 0), min(math.ceil(dim_of(yarn["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain_freq / yarn["factor"] * ramp + plain_freq * (1.0 - ramp)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, cfg):
    """Pairs (2i, 2i + 1) of x [S, ..., d] rotated by position * inv_freq[i]."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * _inv_freq(cfg)[None, :]
    yarn = cfg.get("rope_scaling")
    m = 1.0 if not yarn else _mscale(yarn["factor"], yarn.get("mscale", 1)) / _mscale(
        yarn["factor"], yarn.get("mscale_all_dim", 0) or 0)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _attention(h, w, cfg, mode):
    s, hid = h.shape
    heads, nope, rope, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                             cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = plain.rms_norm(plain.matmul(h, _f32(w["q_a_proj"]["kernel"]), mode), _f32(w["q_a_layernorm"]["weight"]), eps)
    kv_a = plain.matmul(h, _f32(w["kv_a_proj_with_mqa"]["kernel"]), mode)
    c_kv = plain.rms_norm(kv_a[:, :rank], _f32(w["kv_a_layernorm"]["weight"]), eps)
    k_pe = _rope(kv_a[:, rank:], cfg)
    yarn = cfg.get("rope_scaling")
    scale = (nope + rope)**-0.5
    if yarn and yarn.get("mscale_all_dim"):
        scale *= _mscale(yarn["factor"], yarn["mscale_all_dim"])**2

    q = plain.matmul(c_q, _f32(w["q_b_proj"]["kernel"]).reshape(-1, heads * (nope + rope)), mode).reshape(s, heads, -1)
    kv = plain.matmul(c_kv, _f32(w["kv_b_proj"]).reshape(rank, heads * (nope + dv)), mode).reshape(s, heads, nope + dv)
    q_pe = _rope(q[..., nope:], cfg)

    def head(args):
        q_nope, q_pe, k_nope, v = args                         # [S, nope], [S, rope], [S, nope], [S, dv]

        def queries(q_nope, q_pe, pos):                        # a block of queries against every key
            scores = (jnp.matmul(q_nope, k_nope.T, precision=plain.HIGHEST) +
                      jnp.matmul(q_pe, k_pe.T, precision=plain.HIGHEST))
            probs = jax.nn.softmax(jnp.where(pos[:, None] >= jnp.arange(s)[None, :], scores * scale, -jnp.inf), axis=-1)
            return jnp.matmul(probs, v, precision=plain.HIGHEST)

        return _by_rows(queries, q_nope, q_pe, jnp.arange(s))

    per_head = lambda a: a.swapaxes(0, 1)                      # noqa: E731  [S, H, d] -> [H, S, d]
    o = jax.lax.map(head, (per_head(q[..., :nope]), per_head(q_pe), per_head(kv[..., :nope]), per_head(kv[..., nope:])))
    return plain.matmul(o.swapaxes(0, 1).reshape(s, heads * dv), _f32(w["o_proj"]["kernel"]).reshape(heads * dv, hid), mode)


def _sinkhorn(m, iters, eps):
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _hyper(x, w, cfg, mode, fn):
    """One hyper-connected sublayer on the streams x [S, n, C] around ``fn``."""
    n = x.shape[1]
    a, b = _f32(w["a"]), _f32(w["b"])

    def read(x):                                               # the coefficients and the mixed stream, a token alone
        normed = plain.rms_norm(x.reshape(x.shape[0], -1), _f32(w["hc_norm"]["weight"]), cfg["rms_norm_eps"])
        proj = plain.matmul(normed, _f32(w["phi"]), mode)
        pre = jax.nn.sigmoid(a[0] * proj[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[:, n:2 * n] + b[n:2 * n])
        res = jnp.clip((a[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n), cfg["mhc_h_res_clamp_min"],
                       cfg["mhc_h_res_clamp_max"])
        return jnp.einsum("sn,snc->sc", pre, x, precision=plain.HIGHEST), post, _sinkhorn(
            res, cfg["hc_sinkhorn_iters"], cfg["hc_eps"])

    u, post, res = _by_rows(read, x)
    y = fn(u)
    return _by_rows(lambda x, y, post, res: jnp.einsum("sij,sjc->sic", res, x, precision=plain.HIGHEST) +
                    post[:, :, None] * y[:, None, :], x, y, post, res)


def _experts(h, w, bank, l, cfg, mode):
    """(the expert block's output, the router margin) of h [S, C]; ``bank``
    is the stacked [L, E, ...] expert weights as the run holds them, of which
    one expert of layer ``l`` is upcast at a time."""
    k, n_exp = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    scores = jax.nn.sigmoid(plain.matmul(h, _f32(w["gate"]["kernel"]), mode))
    ranked, top_i = jax.lax.top_k(scores + _f32(w["e_score_correction_bias"]), k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    top_i = top_i[:, :k]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    weights = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=jnp.float32) * top_s[..., None], axis=-2)

    def one_expert(e, acc):
        we = {n: jax.lax.dynamic_slice(a, (l, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)
              for n, a in bank.items()}
        y = plain.swiglu(h, we["w_gate"], we["w_up"], we["w_down"], mode)
        return acc + jax.lax.dynamic_index_in_dim(weights, e, axis=1) * y

    out = jax.lax.fori_loop(0, n_exp, one_expert, jnp.zeros_like(h))
    sh = w["shared_experts"]
    return out + plain.swiglu(h, *(_f32(sh[n]["kernel"]) for n in ("gate_proj", "up_proj", "down_proj")), mode), margin


def forward(params, ids, cfg, mode="f32", first=0):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S], router margin [S - first]: the gap in ``s + bias`` between
    the last expert chosen and the first left out, least over the layers)."""
    p = params["params"]
    eps, n = cfg["rms_norm_eps"], cfg["hc_mult"]
    emb = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    x = jnp.broadcast_to(emb[:, None, :], (emb.shape[0], n, emb.shape[1]))
    n_dense = cfg["first_k_dense_replace"]
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)

    def layer(x, w, sparse, l):
        nonlocal margin
        x = _hyper(x, w["attn_hc"], cfg, mode, lambda u: _attention(
            plain.rms_norm(u, _f32(w["input_layernorm"]["weight"]), eps), w["self_attn"], cfg, mode))

        def mlp(u):
            nonlocal margin
            h = plain.rms_norm(u, _f32(w["post_attention_layernorm"]["weight"]), eps)
            if not sparse:
                m = w["mlp"]
                return _by_rows(lambda h: plain.swiglu(h, *(_f32(m[n]["kernel"]) for n in ("gate_proj", "up_proj",
                                                                                               "down_proj")), mode), h)
            y, gap = _experts(h, w["mlp"], p["layers"]["mlp"]["experts"], l, cfg, mode)
            margin = jnp.minimum(margin, gap)
            return y

        return _hyper(x, w["mlp_hc"], cfg, mode, mlp)

    for i in range(n_dense):
        x = layer(x, p[f"dense_layers_{i}"], False, i)
    stacked = p.get("layers", {})
    for l in range(cfg["num_hidden_layers"] - n_dense):
        small = {k: v for k, v in stacked.items() if k != "mlp"}
        small["mlp"] = {k: v for k, v in stacked["mlp"].items() if k != "experts"}
        x = layer(x, jax.tree.map(lambda a: a[l], small), True, l)
    x = plain.rms_norm(jnp.sum(x[first:], axis=1), p["norm"]["weight"].astype(jnp.float32), eps)
    # the head a block of the vocabulary at a time: its float32 copy is never held whole
    head, blocks = p["lm_head"]["kernel"], 8 if cfg["vocab_size"] % 8192 == 0 else 1
    cols = head.shape[1] // blocks
    logits = jnp.concatenate([plain.matmul(x, head[:, i * cols:(i + 1) * cols].astype(jnp.float32), mode)
                              for i in range(blocks)], axis=-1)
    return logits, jnp.where(jnp.isinf(margin), 1.0, margin)[first:]
