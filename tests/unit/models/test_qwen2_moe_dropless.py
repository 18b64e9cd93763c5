"""Qwen2-MoE's sparse MLP through the dropless path (``moe/sharded_moe``):
the layer against a per-token loop written here, in the dense and in the
sorted form (``takes_sorted``), and a ZeRO-3 step on the CPU's host devices against
the one-device run."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import deepspeed_tpu as ds
from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh
from deepspeed_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM, Qwen2MoeSparseMLP
from deepspeed_tpu.moe.sharded_moe import DENSE_UP_TO_TOKENS, takes_sorted


def _layer_cfg(norm_topk_prob):
    return Qwen2MoeConfig(vocab_size=64, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
                          shared_expert_intermediate_size=48, num_hidden_layers=1, num_attention_heads=4,
                          num_key_value_heads=4, num_experts=8, num_experts_per_tok=4,
                          norm_topk_prob=norm_topk_prob, dtype=jnp.float32, param_dtype=jnp.float32)


def _per_token_mlp(p, x, k, norm_topk_prob):
    """HF's Qwen2MoeSparseMoeBlock one token at a time: router over all the
    experts, the k highest (renormalised or not), each a gated-SiLU MLP, and
    the shared expert scaled by its sigmoid gate."""

    def one_token(t):
        probs = jax.nn.softmax(t @ p["gate"]["kernel"])
        vals, idx = jax.lax.top_k(probs, k)
        if norm_topk_prob:
            vals = vals / vals.sum()
        out = jnp.zeros_like(t)
        for j in range(k):
            w_gate, w_up, w_down = (p[n][idx[j]] for n in ("w_gate", "w_up", "w_down"))
            out = out + vals[j] * ((jax.nn.silu(t @ w_gate) * (t @ w_up)) @ w_down)
        shared = (jax.nn.silu(t @ p["shared_gate_proj"]["kernel"]) *
                  (t @ p["shared_up_proj"]["kernel"])) @ p["shared_down_proj"]["kernel"]
        return out + jax.nn.sigmoid(t @ p["shared_expert_gate"]["kernel"]) * shared

    return jax.vmap(one_token)(x.reshape(-1, x.shape[-1])).reshape(x.shape)


@pytest.mark.parametrize("norm_topk_prob", [False, True], ids=["plain_topk", "norm_topk"])
@pytest.mark.parametrize("seq", [64, 160], ids=["dense_form", "grouped_form"])
def test_sparse_mlp_value_and_gradient_match_per_token_loop(seq, norm_topk_prob):
    """Value and gradient (router, bank, shared expert) of the layer equal the
    per-token loop's in float32, on either side of ``takes_sorted``'s rule, and
    ``intermediates`` holds the rows each expert multiplied."""
    cfg = _layer_cfg(norm_topk_prob)
    layer = Qwen2MoeSparseMLP(cfg)
    x = jnp.asarray(np.random.default_rng(seq).normal(size=(2, seq, cfg.hidden_size)), jnp.float32)
    tokens, k = 2 * seq, cfg.num_experts_per_tok
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(1), x))
    target = jnp.asarray(np.random.default_rng(7).normal(size=x.shape), jnp.float32)

    loss = lambda p: jnp.mean((layer.apply(p, x) - target)**2)
    want_loss = lambda p: jnp.mean((_per_token_mlp(p["params"], x, k, norm_topk_prob) - target)**2)
    # the form follows the tokens the layer is handed, and nothing else
    assert takes_sorted(tokens, k, cfg.num_experts) == (tokens > DENSE_UP_TO_TOKENS)   # 128 rows of 4 reach all 8
    assert ("ragged_dot" in str(jax.make_jaxpr(loss)(params))) == (tokens > DENSE_UP_TO_TOKENS)

    np.testing.assert_allclose(np.asarray(layer.apply(params, x)),
                               np.asarray(_per_token_mlp(params["params"], x, k, norm_topk_prob)), atol=1e-5)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, want_grads = jax.jit(jax.value_and_grad(want_loss))(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    flat, want_flat = (dict(jax.tree_util.tree_leaves_with_path(g)) for g in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path in flat:
        np.testing.assert_allclose(np.asarray(flat[path]), np.asarray(want_flat[path]), atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))

    _, state = layer.apply(params, x, mutable=["intermediates"])
    (counts, ) = state["intermediates"]["exp_counts"]
    assert counts.shape == (cfg.num_experts, ) and int(counts.sum()) == tokens * k


TRAIN_CFG = Qwen2MoeConfig(vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                           shared_expert_intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                           num_key_value_heads=4, num_experts=8, num_experts_per_tok=2, max_position_embeddings=160,
                           rope_theta=1e4, dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=True, remat=True)


def _zero3_step(spec, devices, bf16=False):
    """One ZeRO-3 AdamW step of the small model; 8 sequences of 160 tokens,
    so a shard of four holds 320 tokens: the grouped form."""
    cfg = dataclasses.replace(TRAIN_CFG, dtype=jnp.bfloat16) if bf16 else TRAIN_CFG
    engine, _, _, _ = ds.initialize(model=Qwen2MoeForCausalLM(cfg), mesh=create_mesh(spec, devices=devices),
                                    dist_init_required=False,
                                    config={"train_batch_size": 8, "steps_per_print": 0,
                                            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                                            "zero_optimization": {"stage": 3}, "bf16": {"enabled": bf16}})
    ids = np.random.default_rng(11).integers(0, TRAIN_CFG.vocab_size, (8, 160), dtype=np.int32)
    loss = float(engine.train_batch(batch={"input_ids": ids, "labels": ids}))
    return loss, jax.tree.map(np.asarray, engine.state.params)


@pytest.fixture(scope="module")
def one_device_step():
    return _zero3_step(MeshSpec(data=1), jax.devices()[:1])


@pytest.mark.parametrize("spec", [MeshSpec(data=4), MeshSpec(data=2, expert=2)], ids=["data4", "data2_expert2"])
def test_zero3_step_on_four_devices_matches_one_device(spec, one_device_step, capfd):
    """Scan and remat on, more than ``DENSE_UP_TO_TOKENS`` tokens a shard: the
    loss and the updated parameters of the one-device run, and a compile in
    which GSPMD replicates no tensor whole (``dryrun_multichip``'s guard)."""
    assert 8 * 160 // 4 > DENSE_UP_TO_TOKENS
    want_loss, want_params = one_device_step
    capfd.readouterr()
    loss, params = _zero3_step(spec, jax.devices()[:4])
    assert "Involuntary full rematerialization" not in capfd.readouterr().err
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat, want_flat = (dict(jax.tree_util.tree_leaves_with_path(p)) for p in (params, want_params))
    for path in flat:
        # AdamW's first step moves a weight by lr * g / (|g| + eps), which
        # magnifies the summation order's noise where g is next to nothing:
        # a tenth of the step's lr still catches a term left out
        np.testing.assert_allclose(flat[path], want_flat[path], atol=1e-4, err_msg=jax.tree_util.keystr(path))


def test_bf16_zero3_step_on_four_devices(one_device_step):
    """The train cell's precision (bf16 compute, float32 masters) on the CPU's
    mesh: the bank's cotangent is a bfloat16 psum inside ``shard_map``, which
    XLA's CPU compiler takes only under a wholly manual mesh
    (``dropless_dispatch``); the loss is the float32 run's to bf16's rounding."""
    loss, params = _zero3_step(MeshSpec(data=4), jax.devices()[:4], bf16=True)
    np.testing.assert_allclose(loss, one_device_step[0], rtol=2e-2)
    assert all(np.isfinite(p).all() for p in jax.tree.leaves(params))


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_model_collects_exp_counts_of_every_layer(scan_layers):
    """``mutable=["intermediates"]`` on the whole model gives each sparse
    layer's rows an expert, stacked by the layer scan where there is one."""
    cfg = dataclasses.replace(TRAIN_CFG, scan_layers=scan_layers)
    model = Qwen2MoeForCausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 160)), jnp.int32)
    _, state = model.apply(model.init(jax.random.PRNGKey(0), ids), ids, mutable=["intermediates"])
    counts = np.stack([np.asarray(c) for c in jax.tree.leaves(state["intermediates"])]).reshape(-1, cfg.num_experts)
    assert counts.shape[0] == cfg.num_hidden_layers
    np.testing.assert_array_equal(counts.sum(-1), 2 * 160 * cfg.num_experts_per_tok)
