"""The share test of the ``model-configs`` guide for Trinity's expert layer: at
a small size on the CPU, the parts of the result that all 8 shares give (each
holding 2 experts of a router of 16, ``TrinityConfig(num_experts=2,
router_experts=16, first_expert=2 s)``), with the shared expert that every
chip computes alike counted once, add up to what the uncut reference gives
for the whole layer (``benchmark/refs/trinity.py`` with all 16 experts held).
The program's block (``models/trinity.TrinityMoE``) and the reference's, a
share at a time, against each other too."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.trinity import TrinityMoE

from test_trinity import CFG, ref, ref_cfg

SHARES = 8
WHOLE = dataclasses.replace(CFG, num_experts=16, router_experts=None, first_expert=0)


def share_of(s):
    return dataclasses.replace(CFG, num_experts=16 // SHARES, router_experts=16, first_expert=s * (16 // SHARES))


@pytest.fixture(scope="module")
def whole():
    """The uncut layer's parameters and 96 tokens of unit size."""
    params = nn.meta.unbox(TrinityMoE(WHOLE).init(jax.random.PRNGKey(3), jnp.zeros((1, 8, CFG.hidden_size))))["params"]
    params = {**params, "expert_bias": 0.1 * jax.random.normal(jax.random.PRNGKey(4), params["expert_bias"].shape)}
    return params, jax.random.normal(jax.random.PRNGKey(5), (96, CFG.hidden_size))


def cut(params, s):
    """Share ``s``'s parameters: its experts of the bank, everything else as it is."""
    n = 16 // SHARES
    return {**params, "experts": {k: w[s * n:(s + 1) * n] for k, w in params["experts"].items()}}


def _reference(cfg, params, x):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._experts(x, params, ref_cfg(cfg), "f32", ())[0])


def test_the_eight_shares_add_up_to_the_uncut_layer(whole):
    params, x = whole
    uncut = _reference(WHOLE, params, x)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._mlp(x, params["shared_experts"], "f32"))
    parts = [_reference(share_of(s), cut(params, s), x) for s in range(SHARES)]
    routed = sum(part - shared for part in parts)
    np.testing.assert_allclose(routed + shared, uncut, atol=2e-5)
    # every share routes over all 16 and holds its own: no two shares' routed parts are alike, none is all of it
    assert min(np.abs(a - b).max() for i, a in enumerate(parts) for b in parts[:i]) > 1e-3
    assert all(np.abs(part - uncut).max() > 1e-3 for part in parts)


@pytest.mark.parametrize("s", [0, 3, 7])
def test_the_programs_block_gives_its_shares_part(whole, s, monkeypatch):
    """``TrinityMoE`` with ``held=(2 s, 2)`` against the reference given the
    same share, in both forms of the dropless layer."""
    from deepspeed_tpu.moe import sharded_moe
    params, x = whole
    want = _reference(share_of(s), cut(params, s), x)
    for grouped in (True, False):
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s_, k, e, grouped=grouped: grouped)
        with jax.default_matmul_precision("highest"):
            got = TrinityMoE(share_of(s)).apply({"params": cut(params, s)}, x[None])[0]
        np.testing.assert_allclose(got, want, atol=2e-5)
