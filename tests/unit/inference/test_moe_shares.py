"""The shares of an expert layer add up (``moe/sharded_moe.dropless_moe(held=)``,
beside ``test_mixtral_dropless.py``): a layer told which experts it holds
routes over the router's whole width, renormalises over all the chosen and
multiplies the choices that fall on its own experts; the outputs of every
share, with the shared expert counted once, sum to the uncut layer's output
and to the uncut plain reference's.  Small size: 16 experts of width 24 over
a hidden size of 32, 4 a token, four shares of 4; sigmoid scores with a
selection bias, as Solar-Open2 routes; float32."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import dropless_dispatch, dropless_moe

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmark"))
from refs import solar_open2 as ref  # noqa: E402

E, K, D, F, S = 16, 4, 32, 24, 40
SHARES = [(0, 4), (4, 4), (8, 4), (12, 4)]
FORMS = {"dense": False, "grouped": True}


@pytest.fixture(scope="module")
def layer():
    k = jax.random.split(jax.random.PRNGKey(0), 9)
    bank = (jax.random.normal(k[0], (E, D, F)) / D**0.5, jax.random.normal(k[1], (E, D, F)) / D**0.5,
            jax.random.normal(k[2], (E, F, D)) / F**0.5)
    shared = {n: {"kernel": jax.random.normal(kk, s) / s[0]**0.5}
              for n, kk, s in (("gate_proj", k[3], (D, F)), ("up_proj", k[4], (D, F)), ("down_proj", k[5], (F, D)))}
    return {"x": jax.random.normal(k[6], (S, D)), "gate": jax.random.normal(k[7], (D, E)) / D**0.5,
            "bias": 0.1 * jax.random.normal(k[8], (E, )), "bank": bank, "shared": shared,
            "mask": jnp.arange(S) % 7 != 3}      # a few slots carry no token


def _routed(layer, form, held, monkeypatch, mask=True):
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: FORMS[form])
    bank = layer["bank"] if held is None else tuple(w[held[0]:held[0] + held[1]] for w in layer["bank"])
    with jax.default_matmul_precision("highest"):
        return dropless_moe(layer["x"], layer["x"] @ layer["gate"], bank, K, layer["mask"] if mask else None,
                            scoring="sigmoid", select_bias=layer["bias"], held=held)


def _reference(layer):
    """The uncut plain reference's expert block (routed experts and the shared one)."""
    cfg = {"num_experts_per_tok": K, "n_routed_experts": E, "first_expert": 0, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0}
    w = {"gate": {"kernel": layer["gate"]}, "e_score_correction_bias": layer["bias"], "shared_experts": layer["shared"]}
    bank = dict(zip(("w_gate", "w_up", "w_down"), (a[None] for a in layer["bank"])))
    return ref._experts(layer["x"], w, bank, 0, cfg, "f32")[0]


def _shared(layer):
    sh = layer["shared"]
    return ref._swiglu(layer["x"], *(sh[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj")), "f32")


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_shares_add_up_to_the_uncut_layer_and_to_the_reference(layer, form, monkeypatch):
    parts = [_routed(layer, form, held, monkeypatch, mask=False) for held in SHARES]
    whole, _, whole_counts = _routed(layer, form, None, monkeypatch, mask=False)
    total = sum(out for out, _, _ in parts)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_allclose(total + _shared(layer), _reference(layer), atol=2e-5)      # the shared expert once
    # a share's counts are the held experts' rows: together the uncut layer's, K a token
    counts = np.concatenate([np.asarray(c) for _, _, c in parts])
    np.testing.assert_array_equal(counts, np.asarray(whole_counts))
    assert counts.sum() == S * K and all(len(c) == 4 for _, _, c in parts)
    # one share is far from the whole: the comparison would catch a share that computed everything
    assert float(jnp.abs(parts[0][0] - whole).max()) > 0.1


@pytest.mark.parametrize("held", SHARES)
def test_dense_and_grouped_forms_agree_for_a_share(layer, held, monkeypatch):
    """With padding slots in the step: they go to no expert in either form."""
    dense, _, dense_counts = _routed(layer, "dense", held, monkeypatch)
    grouped, _, grouped_counts = _routed(layer, "grouped", held, monkeypatch)
    np.testing.assert_allclose(dense, grouped, atol=2e-5)
    np.testing.assert_array_equal(dense_counts, grouped_counts)
    assert not np.asarray(dense)[~np.asarray(layer["mask"])].any()
    assert not np.asarray(grouped)[~np.asarray(layer["mask"])].any()


@pytest.mark.parametrize("form", sorted(FORMS))
def test_no_share_is_todays_path_bit_for_bit(layer, form, monkeypatch):
    """``held=None`` is the path every other model takes: the bank whole
    under ``held=(0, E)`` gives the same bits, and ``held=None`` adds nothing
    to the program (no comparison with a share's bounds is lowered)."""
    whole, aux, counts = _routed(layer, form, None, monkeypatch)
    same, same_aux, same_counts = _routed(layer, form, (0, E), monkeypatch)
    np.testing.assert_array_equal(whole, same)
    np.testing.assert_array_equal(counts, same_counts)
    assert float(aux) == float(same_aux)
    lowered = lambda held: jax.jit(lambda x, logits, bank: dropless_moe(  # noqa: E731
        x, logits, bank, K, scoring="sigmoid", select_bias=layer["bias"], held=held)).lower(
            layer["x"], layer["x"] @ layer["gate"], layer["bank"]).as_text()
    assert lowered(None) != lowered((0, E)) and len(lowered(None)) < len(lowered((0, E)))


def test_a_bank_of_another_size_than_the_share_is_refused(layer):
    with pytest.raises(ValueError, match="the bank holds 16 experts, not 4"):
        dropless_moe(layer["x"], layer["x"] @ layer["gate"], layer["bank"], K, scoring="sigmoid", held=(4, 4))


def test_dispatch_over_a_batch_takes_the_share(layer, monkeypatch):
    monkeypatch.setattr(sharded_moe, "DENSE_UP_TO_TOKENS", 0)
    held = SHARES[2]
    bank = tuple(w[held[0]:held[0] + held[1]] for w in layer["bank"])
    x, logits = layer["x"].reshape(2, S // 2, D), (layer["x"] @ layer["gate"]).reshape(2, S // 2, E)
    with jax.default_matmul_precision("highest"):
        out, _, counts = dropless_dispatch(x, logits, bank, K, None, None, None, True, "sigmoid", layer["bias"], 1.0,
                                           held)
    want, _, want_counts = _routed(layer, "grouped", held, monkeypatch, mask=False)
    np.testing.assert_allclose(out.reshape(S, D), want, atol=2e-5)
    np.testing.assert_array_equal(counts, want_counts)
