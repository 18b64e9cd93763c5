"""The router forms of ``moe/sharded_moe.dropless_moe``: softmax (Mixtral;
Qwen2-MoE without the renormalisation), sigmoid scores with a selection bias
and a routing scale (``scoring_func: sigmoid``, ``topk_method: noaux_tc``,
``routed_scaling_factor``).  The new arguments' defaults leave the softmax
forms' arithmetic bit for bit; the sigmoid form is held to its equations on a
small bank, in the dense and in the sorted form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import _experts_dense, _experts_grouped, _one_hot, dropless_moe


def _case(s, e, d=32, f=48, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, logits = jax.random.normal(k[0], (s, d)), 2.0 * jax.random.normal(k[1], (s, e))
    bank = tuple(jax.random.normal(k[2 + i], shape) / np.sqrt(shape[1])
                 for i, shape in enumerate([(e, d, f), (e, d, f), (e, f, d)]))
    mask = jax.random.uniform(k[5], (s, )) > 0.1
    return x, logits, bank, mask


def _as_before_pr37(x, logits, bank, k, token_mask, normalize):
    """``dropless_moe`` as PR 35 left it (softmax, no bias, no scale), word for word."""
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)
    if normalize and k > 1:
        top_vals = top_vals / jnp.maximum(jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    live = jnp.ones((s, ), bool) if token_mask is None else token_mask
    mask1 = _one_hot(top_idx[:, 0], e) * live[:, None]
    l_aux = jnp.sum(jnp.mean(gates, axis=0) * jnp.mean(mask1, axis=0)) * e
    expert = jnp.where(live[:, None], top_idx, e)
    group_sizes = jnp.bincount(expert.reshape(-1), length=e + 1)[:e].astype(jnp.int32)
    experts = _experts_grouped if sharded_moe.takes_sorted(s, k, e) else _experts_dense
    return experts(x, top_vals, expert, group_sizes, bank, None), l_aux, group_sizes


@pytest.mark.parametrize("model, e, k, normalize", [("mixtral", 8, 2, True), ("qwen", 60, 4, False)])
@pytest.mark.parametrize("s", [16, 300])                       # the dense form, the sorted form
def test_softmax_routers_are_unchanged_bit_for_bit_under_the_new_defaults(model, e, k, normalize, s):
    x, logits, bank, mask = _case(s, e)
    new = jax.jit(lambda *a: dropless_moe(*a, k, mask, normalize=normalize))(x, logits, bank)
    old = jax.jit(lambda *a: _as_before_pr37(*a, k, mask, normalize))(x, logits, bank)
    for got, want in zip(new, old):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _by_hand(x, logits, bank, k, bias, scale, normalize):
    """The sigmoid router's equations, one token and one expert at a time."""
    x, logits, bias = np.asarray(x, np.float64), np.asarray(logits, np.float64), np.asarray(bias, np.float64)
    w_gate, w_up, w_down = (np.asarray(w, np.float64) for w in bank)
    scores = 1.0 / (1.0 + np.exp(-logits))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        chosen = np.argsort(-(scores[t] + bias), kind="stable")[:k]
        w = scores[t, chosen]
        if normalize:
            w = w / (w.sum() + 1e-20)
        for weight, i in zip(w * scale, chosen):
            g, u = x[t] @ w_gate[i], x[t] @ w_up[i]
            out[t] += weight * ((g / (1.0 + np.exp(-g)) * u) @ w_down[i])
    return out


@pytest.mark.parametrize("s", [12, 300])
@pytest.mark.parametrize("normalize", [True, False])
def test_sigmoid_router_with_bias_and_scale_follows_its_equations(s, normalize):
    x, logits, bank, _ = _case(s, 16, seed=3)
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (16, ))
    with jax.default_matmul_precision("highest"):
        out, _, counts = dropless_moe(x, logits, bank, 4, None, None, None, normalize, "sigmoid", bias, 2.0)
    np.testing.assert_allclose(np.asarray(out), _by_hand(x, logits, bank, 4, bias, 2.0, normalize), atol=2e-4)
    assert int(counts.sum()) == 4 * s


def test_the_bias_changes_the_choice_and_not_the_weight():
    """A bias that lifts expert 7 into every token's choice: it is chosen, and
    its weight is its own sigmoid score over the chosen scores' sum, as if it
    had been among the largest by itself."""
    x, logits, bank, _ = _case(40, 16, seed=5)
    bias = jnp.zeros(16).at[7].set(10.0)
    plain = dropless_moe(x, logits, bank, 4, scoring="sigmoid")
    lifted = dropless_moe(x, logits, bank, 4, scoring="sigmoid", select_bias=bias)
    assert int(lifted[2][7]) == 40 and int(plain[2][7]) < 40                    # chosen by every token now
    assert np.abs(np.asarray(lifted[0]) - np.asarray(plain[0])).max() > 1e-3
    with jax.default_matmul_precision("highest"):
        out = dropless_moe(x, logits, bank, 4, scoring="sigmoid", select_bias=bias)[0]
    np.testing.assert_allclose(np.asarray(out), _by_hand(x, logits, bank, 4, bias, 1.0, True), atol=2e-4)
    # the scale multiplies the routed output and nothing else
    doubled = dropless_moe(x, logits, bank, 4, scoring="sigmoid", select_bias=bias, route_scale=2.0)[0]
    np.testing.assert_allclose(np.asarray(doubled), 2.0 * np.asarray(lifted[0]), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="unknown router scoring"):
        dropless_moe(x, logits, bank, 4, scoring="tanh")


def test_six_of_sixty_four_at_scale_2_446_beside_two_shared_experts():
    """Kimi-VL's expert block (``models/kimi_vl.py`` through ``Xing4MoE``): 6
    of 64 by sigmoid score plus a selection bias, weights renormalised and
    times 2.446, beside one ungated SwiGLU of ``2 x moe_intermediate_size``."""
    from flax import linen as nn

    from deepspeed_tpu.models.kimi_vl import KimiVLConfig
    from deepspeed_tpu.models.xing4 import Xing4MoE
    cfg = KimiVLConfig(hidden_size=32, moe_intermediate_size=24, dtype=jnp.float32, param_dtype=jnp.float32)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.n_shared_experts) == (64, 6, 2)
    assert cfg.routed_scaling_factor == 2.446 and cfg.scoring_func == "sigmoid" and cfg.topk_method == "noaux_tc"
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 32))
    block = Xing4MoE(cfg)
    params = nn.meta.unbox(block.init(jax.random.PRNGKey(1), x))
    p = params["params"]
    p["e_score_correction_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(2), (64, ))
    assert p["shared_experts"]["gate_proj"]["kernel"].shape == (32, 48)            # two shared experts, one block
    with jax.default_matmul_precision("highest"):
        out = block.apply(params, x)[0]
    bank = tuple(p["experts"][n] for n in ("w_gate", "w_up", "w_down"))
    logits = np.asarray(x[0]) @ np.asarray(p["gate"]["kernel"])
    routed = _by_hand(x[0], logits, bank, 6, p["e_score_correction_bias"], 2.446, True)
    h = np.asarray(x[0], np.float64)
    sh = {n: np.asarray(p["shared_experts"][n]["kernel"], np.float64) for n in ("gate_proj", "up_proj", "down_proj")}
    g = h @ sh["gate_proj"]
    shared = (g / (1.0 + np.exp(-g)) * (h @ sh["up_proj"])) @ sh["down_proj"]
    np.testing.assert_allclose(np.asarray(out), routed + shared, atol=2e-4)
