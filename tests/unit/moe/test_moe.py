"""MoE tests (analog of tests/unit/moe/test_moe.py, 12 tests in reference)."""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.moe.sharded_moe import _capacity, top1_gating, topk_gating


def test_capacity_formula():
    assert _capacity(num_tokens=64, num_experts=8, capacity_factor=1.0, min_capacity=4, k=1) == 8
    assert _capacity(num_tokens=64, num_experts=8, capacity_factor=2.0, min_capacity=4, k=1) == 16
    assert _capacity(num_tokens=8, num_experts=8, capacity_factor=1.0, min_capacity=4, k=1) == 4  # min clamp
    assert _capacity(num_tokens=64, num_experts=8, capacity_factor=1.0, min_capacity=4, k=2) == 16


def test_top1_gating_dispatch_shapes():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    l_aux, combine, dispatch, counts = top1_gating(logits, capacity=8)
    assert combine.shape == (16, 4, 8)
    assert dispatch.shape == (16, 4, 8)
    # each token dispatched at most once
    per_token = np.asarray(dispatch).sum(axis=(1, 2))
    assert (per_token <= 1).all()
    assert float(l_aux) > 0


def test_top1_capacity_drops():
    # all tokens prefer expert 0 → only `capacity` survive
    logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (10, 1))
    _, combine, dispatch, counts = top1_gating(logits, capacity=3)
    assert int(np.asarray(dispatch).sum()) == 3


def test_topk_gating_two_experts_per_token():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    l_aux, combine, dispatch, counts = topk_gating(logits, k=2, capacity=16)
    per_token = np.asarray(dispatch).sum(axis=(1, 2))
    assert (per_token == 2).all()
    # combine weights normalized over the k experts
    w = np.asarray(combine).sum(axis=(1, 2))
    np.testing.assert_allclose(w, 1.0, atol=1e-5)


def test_topk_no_drop():
    # drop_tokens=False contract: caller sizes capacity to token count
    # (as MoE.__call__ does), so nothing is dropped
    logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (10, 1))
    _, _, dispatch, _ = topk_gating(logits, k=1, capacity=10, drop_tokens=False)
    assert int(np.asarray(dispatch).sum()) == 10


@pytest.mark.parametrize("ep", [1, 2])
def test_moe_layer_forward_backward(ep):
    mesh = create_mesh(MeshSpec(expert=ep))
    set_global_mesh(mesh)
    layer = MoE(hidden_size=32, num_experts=4, intermediate_size=64, k=2, capacity_factor=2.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16, 32)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)

    def loss_fn(p):
        out, l_aux, _ = layer.apply(p, x)
        return jnp.mean(out**2) + 0.01 * l_aux

    from flax import linen as nn
    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(nn.meta.unbox(grads)):
        assert np.isfinite(np.asarray(g)).all()


def test_moe_expert_sharding():
    """Expert weights must map their leading dim to the expert mesh axis."""
    mesh = create_mesh(MeshSpec(expert=2))
    set_global_mesh(mesh)
    layer = MoE(hidden_size=32, num_experts=4, intermediate_size=64, k=1)
    x = jnp.ones((8, 4, 32), jnp.float32)
    abs_vars = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    from deepspeed_tpu.module_inject.tp_rules import param_shardings
    sh = param_shardings(abs_vars, mesh, zero_stage=0)
    w_gate_sh = sh["params"]["experts"]["w_gate"]
    assert "expert" in str(w_gate_sh.spec), f"expert weights not expert-sharded: {w_gate_sh.spec}"


def test_tp_ep_mesh_matches_single_device():
    """TP×EP: with drop/gather token mappings (ref: moe/mappings.py:1) the
    MoE layer on a data×expert×tensor mesh must reproduce the single-device
    math — each token routed exactly once, slices gathered back."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import MeshSpec, create_mesh, set_global_mesh
    from deepspeed_tpu.moe.layer import MoE

    layer = MoE(hidden_size=32, num_experts=4, intermediate_size=64, k=2,
                capacity_factor=4.0, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 32), jnp.float32)

    # single-device golden (trivial mesh)
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    params = layer.init(jax.random.PRNGKey(0), x)
    gold, gold_aux, _ = jax.jit(lambda p, x: layer.apply(p, x))(params, x)

    mesh = create_mesh(MeshSpec(data=2, expert=2, tensor=2), devices=jax.devices()[:8])
    set_global_mesh(mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"), None, None)))

    def fwd(p, x):
        out, l_aux, _ = layer.apply(p, x)
        return out, l_aux

    out, l_aux = jax.jit(fwd)(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold), atol=2e-5, rtol=2e-5)
    # l_aux is a per-group statistic (ref: sharded_moe per-group balance
    # loss): the 8-device mesh has 4 token groups vs 1 on a single device,
    # so only rough agreement is expected
    np.testing.assert_allclose(float(l_aux), float(gold_aux), rtol=0.2)

    # grads must agree too (the mappings' backward transposes); l_aux is
    # excluded — its group decomposition differs by design
    def loss(p, x):
        out, _l_aux, _ = layer.apply(p, x)
        return (out**2).mean()

    g1 = jax.jit(jax.grad(loss))(params, x)
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    g0 = jax.jit(jax.grad(loss))(params, x)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=2e-4)


# ------------------------------------------------------------- dropless path


def _per_token_reference(x, logits, bank, k, idx, mask=None):
    """The dropless layer as its definition reads: a loop over tokens, each
    through its k highest experts in float32 (k == 1 keeps the gate value as
    the weight, as ``top1_gating`` does; k > 1 renormalises over the k).
    ``idx`` [S, k] are the concrete choices, so the loop also differentiates."""
    w_gate, w_up, w_down = bank
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    rows = []
    for t in range(x.shape[0]):
        y = jnp.zeros((w_down.shape[-1], ), jnp.float32)
        if mask is None or bool(mask[t]):
            vals = jnp.stack([gates[t, int(e)] for e in idx[t]])
            if k > 1:
                vals = vals / vals.sum()
            for j, e in enumerate(int(e) for e in idx[t]):
                y = y + vals[j] * ((jax.nn.silu(x[t] @ w_gate[e]) * (x[t] @ w_up[e])) @ w_down[e])
        rows.append(y)
    return jnp.stack(rows)


def _dropless_case(name):
    """(x [S, d], logits [S, E], k, mask or None) of one named case."""
    rng = np.random.default_rng(7)
    s, e, d, k, mask = 24, 4, 16, 2, None
    if name == "single_token":
        s = 1
    logits = rng.normal(size=(s, e)).astype(np.float32)
    if name == "k1":
        k = 1
    elif name == "empty_expert":
        logits[:, 2] = -30.0  # expert 2 is nobody's choice
    elif name == "one_expert":
        logits[:, 1] = 30.0  # everybody's first choice: a capacity of k*S/E would drop
    elif name == "masked_third":
        mask = np.arange(s) % 3 != 1
    x = rng.normal(size=(s, d)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(logits), k, mask


@pytest.mark.parametrize("form", ["grouped", "dense"])
@pytest.mark.parametrize("name", ["k1", "k2", "empty_expert", "one_expert", "masked_third", "single_token"])
def test_dropless_matches_per_token_loop(name, form, monkeypatch):
    """Both forms of the dropless path (sorted dispatch + grouped products;
    every expert over every row, for few tokens) == the per-token top-k loop,
    to 1e-5 in float32: no token dropped whatever the load, an expert with no
    token multiplies nothing, a masked row is exactly zero and counted for
    no expert."""
    from deepspeed_tpu.moe import sharded_moe
    x, logits, k, mask = _dropless_case(name)
    s, e = logits.shape
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: form == "grouped")
    rng = np.random.default_rng(11)
    bank = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)
                 for shape in ((e, 16, 32), (e, 16, 32), (e, 32, 16)))
    idx = np.argsort(-np.asarray(logits), axis=-1, kind="stable")[:, :k]
    want = _per_token_reference(x, logits, bank, k, idx, mask)
    out, l_aux, counts = jax.jit(
        lambda *a: sharded_moe.dropless_moe(*a, k, None if mask is None else jnp.asarray(mask)))(x, logits, bank)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)
    live = np.ones(s, bool) if mask is None else mask
    np.testing.assert_array_equal(np.asarray(counts), np.bincount(idx[live].ravel(), minlength=e))
    assert int(counts.sum()) == k * int(live.sum())
    assert np.isfinite(float(l_aux))
    if name == "empty_expert":
        assert int(counts[2]) == 0
    if name == "one_expert":
        assert int(counts[1]) == s > k * s // e
    if mask is not None:
        assert (np.asarray(out)[~mask] == 0.0).all()


@pytest.fixture(params=["grouped", "dense"])
def dropless_form(request, monkeypatch):
    """Run a test of the whole layer under each form of the dropless path."""
    from deepspeed_tpu.moe import sharded_moe
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: request.param == "grouped")
    return request.param


def test_dropless_reads_its_layer_in_a_stack_of_banks(dropless_form):
    """With ``layer`` the banks are a scanned trunk's, [L, E, ...], read in
    place: the same result as with that layer's own slice of them."""
    from deepspeed_tpu.moe.sharded_moe import dropless_moe
    x, logits, k, _ = _dropless_case("k2")
    mask = jnp.arange(x.shape[0]) % 4 != 3
    rng = np.random.default_rng(13)
    stack = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)
                  for shape in ((3, 4, 16, 32), (3, 4, 16, 32), (3, 4, 32, 16)))
    for layer in (0, 2):
        want, _, want_counts = dropless_moe(x, logits, tuple(w[layer] for w in stack), k, mask)
        got, _, counts = jax.jit(lambda *a: dropless_moe(*a[:3], k, mask, None, a[3]))(x, logits, stack, jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))


def _dropless_layer_loss(layer, params, x):
    out, l_aux, _ = layer.apply(params, x)
    return jnp.mean(out**2) + 0.01 * l_aux


def test_dropless_training_value_and_gradient_match_reference(dropless_form):
    """Training with ``drop_tokens=False``: the layer's loss and its gradient
    (router and bank) equal the per-token loop's, l_aux included."""
    from flax import linen as nn
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    layer = MoE(hidden_size=16, num_experts=4, intermediate_size=32, k=2, drop_tokens=False, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(3, 8, 16)), jnp.float32)
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(0), x))
    xf = x.reshape(-1, 16)
    idx = np.argsort(-np.asarray(xf @ params["params"]["gate"]["kernel"]), axis=-1, kind="stable")[:, :2]

    def reference_loss(p):
        p = p["params"]
        logits = xf @ p["gate"]["kernel"]
        out = _per_token_reference(xf, logits, (p["experts"]["w_gate"], p["experts"]["w_up"], p["experts"]["w_down"]),
                                   2, idx)
        gates = jax.nn.softmax(logits, axis=-1)
        l_aux = jnp.sum(jnp.mean(gates, axis=0) * jnp.mean(jax.nn.one_hot(idx[:, 0], 4), axis=0)) * 4
        return jnp.mean(out**2) + 0.01 * l_aux

    loss, grads = jax.jit(jax.value_and_grad(lambda p: _dropless_layer_loss(layer, p, x)))(params)
    want, want_grads = jax.value_and_grad(reference_loss)(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-4)


def test_dropless_data_shards_route_their_own_tokens(dropless_form):
    """Under a governing mesh with a data axis the batch is routed shard by
    shard (``shard_map``): same outputs, counts and gradients as one group on
    one device, and the expert-mesh path (capacity for every token) agrees."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import trace_mesh
    layer = MoE(hidden_size=16, num_experts=4, intermediate_size=32, k=2, drop_tokens=False, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(4, 8, 16)), jnp.float32)
    mask = jnp.arange(8)[None, :] < jnp.asarray([8, 3, 0, 5])[:, None]
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    params = layer.init(jax.random.PRNGKey(0), x)
    fwd = lambda p, x, m: layer.apply(p, x, token_mask=m)
    loss = lambda p, x: jnp.mean(layer.apply(p, x)[0]**2)
    gold, _, gold_counts = jax.jit(fwd)(params, x, mask)
    gold_grads = jax.jit(jax.grad(loss))(params, x)

    mesh = create_mesh(MeshSpec(data=2, tensor=2), devices=jax.devices()[:4])
    set_global_mesh(mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P(("data", "expert"), None, None)))
    with trace_mesh(mesh):
        out, _, counts = jax.jit(fwd)(params, xs, mask)
        grads = jax.jit(jax.grad(loss))(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(gold_counts))
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(gold_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-4)

    # an expert mesh axis keeps the capacity dispatch, with room for every token
    set_global_mesh(create_mesh(MeshSpec(expert=2), devices=jax.devices()[:2]))
    ep_out, _, ep_counts = jax.jit(lambda p, x: layer.apply(p, x))(params, x)
    full, _, full_counts = jax.jit(lambda p, x: layer.apply(p, x, token_mask=jnp.ones((4, 8), bool)))(params, x)
    set_global_mesh(create_mesh(MeshSpec(), devices=jax.devices()[:1]))
    one, _, one_counts = jax.jit(lambda p, x: layer.apply(p, x))(params, x)
    np.testing.assert_allclose(np.asarray(ep_out), np.asarray(one), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(ep_counts), np.asarray(one_counts))


# ------------------------------------------------- the grouped product's kernel


@pytest.mark.parametrize("name", ["k2", "empty_expert", "one_expert", "masked_third"])
def test_dropless_through_the_grouped_kernel(name, monkeypatch):
    """The sorted form with its three products forced through ``ds_gmm``
    (interpret mode; on the CPU the path is ``ragged_dot``): the per-token
    loop's outputs, ``exp_counts`` unchanged, masked rows exact zeros, and
    the same gradient for inputs and bank as through ``ragged_dot``."""
    import functools
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul
    x, logits, k, mask = _dropless_case(name)
    s, e = logits.shape
    monkeypatch.setattr(sharded_moe, "DENSE_UP_TO_TOKENS", 0)
    rng = np.random.default_rng(11)
    bank = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)
                 for shape in ((e, 16, 32), (e, 16, 32), (e, 32, 16)))
    token_mask = None if mask is None else jnp.asarray(mask)
    run = lambda x, bank: sharded_moe.dropless_moe(x, logits, bank, k, token_mask)  # noqa: E731
    loss = lambda x, bank: jnp.sum(run(x, bank)[0]**2)  # noqa: E731
    want, _, want_counts = run(x, bank)
    want_grads = jax.grad(loss, argnums=(0, 1))(x, bank)

    monkeypatch.setattr(sharded_moe, "grouped_matmul", functools.partial(grouped_matmul, interpret=True))
    assert "pallas_call" in str(jax.make_jaxpr(run)(x, bank))
    if mask is not None:  # what a masked row holds reaches no product
        x = jnp.where(token_mask[:, None], x, jnp.nan)
    out, _, counts = jax.jit(run)(x, bank)
    idx = np.argsort(-np.asarray(logits), axis=-1, kind="stable")[:, :k]
    np.testing.assert_allclose(np.asarray(out), np.asarray(_per_token_reference(x, logits, bank, k, idx, mask)),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    if mask is not None:
        assert (np.asarray(out)[~mask] == 0.0).all()
    for a, b in zip(jax.tree.leaves(jax.grad(loss, argnums=(0, 1))(x, bank)), jax.tree.leaves(want_grads)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("mesh_axes,kernel", [(None, True), (dict(data=1), True), (dict(data=4), True),
                                              (dict(data=2, tensor=2), False), (dict(tensor=2), False)],
                         ids=["no_mesh", "one_device", "fully_manual", "partly_manual", "gspmd_only"])
def test_grouped_product_takes_the_kernel_only_where_one_device_runs_it(mesh_axes, kernel, monkeypatch):
    """The path rule, with the platform said to be a TPU (nothing is lowered):
    the kernel with no governing mesh, on one device and inside a
    ``shard_map`` that is manual over every axis; ``ragged_dot`` where a
    ``tensor`` axis is left to the compiler, inside ``shard_map`` or not."""
    from deepspeed_tpu.comm.mesh import trace_mesh
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "traced_for_tpu", lambda: True)
    monkeypatch.setattr(sharded_moe, "DENSE_UP_TO_TOKENS", 0)
    mesh = None
    if mesh_axes is not None:
        mesh = create_mesh(MeshSpec(**mesh_axes), devices=jax.devices()[:int(np.prod(list(mesh_axes.values())))])
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(4, 8, 4)), jnp.float32)
    bank = tuple(jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((4, 16, 32), (4, 16, 32), (4, 32, 16)))
    with trace_mesh(mesh):
        text = str(jax.make_jaxpr(lambda *a: sharded_moe.dropless_dispatch(*a, 2))(x, logits, bank))
    assert ("pallas_call" in text) == kernel
    assert ("ragged_dot" in text) != kernel
    assert ("shard_map" in text) == (mesh_axes is not None and mesh_axes.get("data", 1) > 1)
