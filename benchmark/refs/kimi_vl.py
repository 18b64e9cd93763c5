"""Kimi-VL (moonshotai/Kimi-VL-A3B-Instruct, ``config.json`` and the published
``modeling_kimi_vl.py``): the forward pass of one sequence with its images in
plain ``jax.numpy``, float32 at the highest matmul precision, no kernels,
cache, buckets or batching; imports nothing of the program.

**Tower** (``cfg["vision_config"]``).  An image is ``h x w`` patches,
row-major, each ``3 p p`` pixel values, ``N = h w``:

  x = pixels [N, 3 p p] W_pe + b_pe + P(h, w)
  P(h, w) = B_h T B_w^T: the learned table T [H0, W0, C] interpolated a side
    at a time by the matrix of PyTorch's bicubic (``align_corners`` false,
    ``A = -0.75``, border taps clamped, no antialiasing): row i of B holds,
    at the taps floor(s) - 1 .. floor(s) + 2 of s = (i + 1/2) H0 / h - 1/2,
    the cubic convolution weights of t = s - floor(s)
  27 x:  [q | k | v] = LN_0(x) W_qkv + b_qkv; 2D rotary on q, k (pair 2i of a
    head by x f_i, pair 2i + 1 by y f_i, f_i = 10000^(-4i/d)); softmax(q . k
    d^-1/2) over all N patches of the image; x += W_o o + b_o;
    x += W_1 gelu_tanh(W_0 LN_1(x) + b_0) + b_1
  final_layernorm; 2 x 2 blocks row-major -> [N / 4, 4, C]; LN on each;
  linear_2(gelu_erf(linear_1(concat))).

**Merge.**  The rows of the images, in order, take the place of ``E[id]`` at
the positions whose id is ``media_placeholder_token_id``.

**Language model.**  ``x += attn(RMSNorm(x))``, ``x += mlp(RMSNorm(x))``;
latent attention in the expanded form a head at a time with ``[q_nope |
q_pe] = x W_q`` (no bottleneck), plain rotary on interleaved pairs, scale
``(nope + rope)^-1/2``; layer 0 a SwiGLU; then sigmoid-routed experts with a
selection bias beside the ungated shared SwiGLU (``refs/xing4._experts``: the
family's router, written there from the same equations).

``ablate`` (the builder's chip test, never the check): ``"pos_table"`` leaves
``P`` out, ``"rope_2d"`` the tower's rotary, ``"merge"`` the merge (the
placeholders' own embeddings stay): each has to fail the comparison.

Departures from the equations the configuration file states: one.  Where the
prompt holds the placeholder id at a position no image accounts for, the
published code would fail; here the count of rows decides (the first rows'
worth of placeholders are replaced).  The harness's prompts hold none.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import plain, xing4
from .xing4 import _by_rows, _f32

A = -0.75


def bicubic_matrix(size_in: int, size_out: int) -> np.ndarray:
    """[size_out, size_in] float64: PyTorch's bicubic along one side."""
    out = np.zeros((size_out, size_in))
    for i in range(size_out):
        s = (i + 0.5) * size_in / size_out - 0.5
        f = int(np.floor(s))
        t = s - f
        weights = (((A * (t + 1) - 5 * A) * (t + 1) + 8 * A) * (t + 1) - 4 * A,
                   ((A + 2) * t - (A + 3)) * t * t + 1,
                   ((A + 2) * (1 - t) - (A + 3)) * (1 - t) * (1 - t) + 1,
                   ((A * (2 - t) - 5 * A) * (2 - t) + 8 * A) * (2 - t) - 4 * A)
        for k, wk in enumerate(weights):
            out[i, min(max(f - 1 + k, 0), size_in - 1)] += wk
    return out


def layer_norm(x, w, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(w["weight"]) + _f32(w["bias"])


def linear(x, w, mode):
    return plain.matmul(x, _f32(w["kernel"]), mode) + _f32(w["bias"])


def rope_2d(x, h, w):
    """x [N, H, d], the patches of an ``h x w`` grid row-major."""
    d = x.shape[-1]
    freqs = 1.0 / (10000.0**(np.arange(0, d, 4, dtype=np.float64) / d))
    ys, xs = np.divmod(np.arange(h * w), w)
    ang = np.stack([xs[:, None] * freqs, ys[:, None] * freqs], axis=-1).reshape(h * w, d // 2)
    cos, sin = jnp.asarray(np.cos(ang), jnp.float32)[:, None], jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def tower(p, pixels, grid, vc, mode, ablate=()):
    """[N, 3 p p] -> [N, C]: the tower on one image."""
    h, w = grid
    n, heads = h * w, vc["num_attention_heads"]
    x = linear(pixels.astype(jnp.float32), p["patch_embed"], mode)
    if "pos_table" not in ablate:
        table = _f32(p["pos_emb"])
        by = jnp.asarray(bicubic_matrix(table.shape[0], h), jnp.float32)
        bx = jnp.asarray(bicubic_matrix(table.shape[1], w), jnp.float32)
        pos = jnp.einsum("ya,abc->ybc", by, table, precision=plain.HIGHEST)
        x = x + jnp.einsum("xb,ybc->yxc", bx, pos, precision=plain.HIGHEST).reshape(n, -1)
    d = x.shape[-1] // heads

    def layer(x, lw):                                           # one scan over the layers: the numbers are the unrolled ones
        qkv = linear(layer_norm(x, lw["norm0"]), lw["wqkv"], mode).reshape(n, 3, heads, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        if "rope_2d" not in ablate:
            q, k = rope_2d(q, h, w), rope_2d(k, h, w)

        def head(args):
            qh, kh, vh = args                                   # [N, d] each: every patch sees every patch
            return _by_rows(lambda qb: jnp.matmul(jax.nn.softmax(
                jnp.matmul(qb, kh.T, precision=plain.HIGHEST) * d**-0.5, axis=-1), vh, precision=plain.HIGHEST), qh)

        o = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))).swapaxes(0, 1).reshape(n, -1)
        x = x + linear(o, lw["wo"], mode)
        x = x + linear(jax.nn.gelu(linear(layer_norm(x, lw["norm1"]), lw["fc0"], mode), approximate=True),
                       lw["fc1"], mode)
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    return layer_norm(x, p["final_layernorm"])


def image_rows(params, pixels, grid, cfg, mode="f32", ablate=()):
    """[N, 3 p p] -> [N / 4, hidden]: tower, merger, projector."""
    p = params["params"]
    h, w = grid
    kh, kw = cfg["vision_config"]["merge_kernel_size"]
    z = tower(p["vision_tower"], pixels.reshape(h * w, -1), grid, cfg["vision_config"], mode, ablate)
    z = z.reshape(h // kh, kh, w // kw, kw, -1).transpose(0, 2, 1, 3, 4).reshape(h * w // (kh * kw), kh * kw, -1)
    proj = p["multi_modal_projector"]
    z = layer_norm(z, proj["pre_norm"]).reshape(z.shape[0], -1)
    return linear(jax.nn.gelu(linear(z, proj["linear_1"], mode), approximate=False), proj["linear_2"], mode)


def _attention(h, w, cfg, mode):
    s, hid = h.shape
    heads, nope, rope, dv, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                                   cfg["v_head_dim"], cfg["kv_lora_rank"])
    q = plain.matmul(h, _f32(w["q_proj"]["kernel"]).reshape(hid, heads * (nope + rope)), mode).reshape(s, heads, -1)
    kv_a = plain.matmul(h, _f32(w["kv_a_proj_with_mqa"]["kernel"]), mode)
    c_kv = plain.rms_norm(kv_a[:, :rank], _f32(w["kv_a_layernorm"]["weight"]), cfg["rms_norm_eps"])
    k_pe, q_pe = xing4._rope(kv_a[:, rank:], cfg), xing4._rope(q[..., nope:], cfg)
    kv = plain.matmul(c_kv, _f32(w["kv_b_proj"]).reshape(rank, heads * (nope + dv)), mode).reshape(s, heads, nope + dv)
    scale = (nope + rope)**-0.5

    def head(args):
        q_nope, q_pe, k_nope, v = args

        def queries(q_nope, q_pe, pos):
            scores = (jnp.matmul(q_nope, k_nope.T, precision=plain.HIGHEST) +
                      jnp.matmul(q_pe, k_pe.T, precision=plain.HIGHEST))
            probs = jax.nn.softmax(jnp.where(pos[:, None] >= jnp.arange(s)[None, :], scores * scale, -jnp.inf), axis=-1)
            return jnp.matmul(probs, v, precision=plain.HIGHEST)

        return _by_rows(queries, q_nope, q_pe, jnp.arange(s))

    per_head = lambda a: a.swapaxes(0, 1)                      # noqa: E731
    o = jax.lax.map(head, (per_head(q[..., :nope]), per_head(q_pe), per_head(kv[..., :nope]), per_head(kv[..., nope:])))
    return plain.matmul(o.swapaxes(0, 1).reshape(s, heads * dv), _f32(w["o_proj"]["kernel"]).reshape(heads * dv, hid), mode)


def forward(params, ids, cfg, mode="f32", first=0, images=(), ablate=()):
    """(logits [S - first, vocab] of the positions from ``first`` on of the
    token ids [S] whose placeholder runs stand for ``images`` ([(pixels,
    (h, w)), ...], in order), router margin [S - first])."""
    p = params["params"]["language_model"]
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens"]["embedding"][ids].astype(jnp.float32)
    if images and "merge" not in ablate:
        rows = jnp.concatenate([image_rows(params, jnp.asarray(px), grid, cfg, mode, ablate) for px, grid in images])
        at = jnp.cumsum(ids == cfg["media_placeholder_token_id"]) - 1
        take = (ids == cfg["media_placeholder_token_id"]) & (at < rows.shape[0])
        x = jnp.where(take[:, None], rows[jnp.clip(at, 0, rows.shape[0] - 1)], x)
    n_dense = cfg["first_k_dense_replace"]
    margin = jnp.full(ids.shape, jnp.inf, jnp.float32)

    def layer(x, w, sparse, l):
        nonlocal margin
        x = x + _attention(plain.rms_norm(x, _f32(w["input_layernorm"]["weight"]), eps), w["self_attn"], cfg, mode)
        h = plain.rms_norm(x, _f32(w["post_attention_layernorm"]["weight"]), eps)
        if not sparse:
            m = w["mlp"]
            return x + _by_rows(lambda h: plain.swiglu(h, *(_f32(m[n]["kernel"]) for n in ("gate_proj", "up_proj",
                                                                                             "down_proj")), mode), h)
        y, gap = xing4._experts(h, w["mlp"], p["layers"]["mlp"]["experts"], l, cfg, mode)
        margin = jnp.minimum(margin, gap)
        return x + y

    for i in range(n_dense):
        x = layer(x, p[f"dense_layers_{i}"], False, i)
    stacked = p.get("layers", {})
    for l in range(cfg["num_hidden_layers"] - n_dense):
        small = {k: v for k, v in stacked.items() if k != "mlp"}
        small["mlp"] = {k: v for k, v in stacked["mlp"].items() if k != "experts"}
        x = layer(x, jax.tree.map(lambda a: a[l], small), True, l)
    x = plain.rms_norm(x[first:], _f32(p["norm"]["weight"]), eps)
    head, blocks = p["lm_head"]["kernel"], 8 if cfg["vocab_size"] % 8192 == 0 else 1
    cols = head.shape[1] // blocks
    logits = jnp.concatenate([plain.matmul(x, head[:, i * cols:(i + 1) * cols].astype(jnp.float32), mode)
                              for i in range(blocks)], axis=-1)
    return logits, jnp.where(jnp.isinf(margin), 1.0, margin)[first:]
