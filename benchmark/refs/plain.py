"""What the plain references share: float32 arithmetic at the highest matmul
precision, written from the published equations.  Imports nothing of the
program.

``mode`` selects the arithmetic of every matrix product:
  "f32"   the reference proper;
  "int8"  the control: both operands of every product rounded to 8-bit
          integers with a scale per row (weights: per output column), the
          step below bfloat16 that a later PR would be tempted to take on
          this chip (v5e multiplies int8 at twice its bf16 rate).  The
          rounding passes gradients straight through.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-30
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, mode):
    """x [..., K] @ w [K, N] in float32."""
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode != "f32":
        raise ValueError(f"unknown arithmetic mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, theta):
    """Rotary embedding, the half-split ("rotate_half") form of the published
    implementations.  x: [S, n, d] at positions 0..S-1."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """q [S, n, d], k/v [S, n_kv, d] -> [S, n, d]; grouped heads share k/v.
    A long sequence goes one group of heads at a time, so that the scores of
    all heads ([n, S, S] in float32) are never held at once."""
    s, n, d = q.shape
    n_kv = k.shape[1]
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def group(qkv):
        qg, kg, vg = qkv  # [S, rep, d], [S, d], [S, d]
        scores = jnp.einsum("qrd,kd->rqk", qg, kg, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", probs, vg, precision=HIGHEST)

    if s >= 2048:
        grouped = (q.reshape(s, n_kv, n // n_kv, d).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1))
        return jax.lax.map(group, grouped).swapaxes(0, 1).reshape(s, n, d)
    rep = n // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k, precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqk,knd->qnd", probs, v, precision=HIGHEST)


def attention_block(h, w, cfg, mode):
    """Self-attention of one sequence h [S, H] with one layer's weights ``w``
    (q/k/v/o kernels shaped [H, n, d] / [n, d, H], optional biases [n, d])."""
    s, hid = h.shape
    n, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = hid // n

    def proj(name, heads):
        y = matmul(h, w[name]["kernel"].reshape(hid, heads * d), mode)
        if "bias" in w[name]:
            y = y + w[name]["bias"].reshape(heads * d)
        return y.reshape(s, heads, d)

    q, k, v = proj("q_proj", n), proj("k_proj", n_kv), proj("v_proj", n_kv)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    o = causal_attention(q, k, v).reshape(s, n * d)
    return matmul(o, w["o_proj"]["kernel"].reshape(n * d, hid), mode)


def swiglu(x, w_gate, w_up, w_down, mode):
    return matmul(jax.nn.silu(matmul(x, w_gate, mode)) * matmul(x, w_up, mode), w_down, mode)


def layer_slice(stacked, l):
    """Layer ``l`` of a scanned (layer-leading) weight tree, upcast to
    float32: only that layer is ever held in float32."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False).astype(jnp.float32), stacked)


def rel_l2(a, b):
    """||a - b|| / ||b|| along the last axis."""
    return jnp.linalg.norm(a - b, axis=-1) / jnp.linalg.norm(b, axis=-1)
