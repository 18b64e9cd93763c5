"""Which form a dropless group takes (``moe/sharded_moe.takes_sorted``): a
rule of the rows, the choices a row and the router's experts, at the shapes
the benchmark's cells hold; and the two forms against each other at the
shapes the rule newly hands to the sorted one (12 rows over 64 experts
through a ``layer=`` stack, 32 rows over 40 held of 320)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import dropless_moe, takes_sorted

# (rows, choices a row, the router's experts, held or None, sorted?)
CELLS = {
    "mixtral_decode_16": (16, 2, 8, None, False),
    "mixtral_mixed_144": (144, 2, 8, None, False),
    "mixtral_run_528": (528, 2, 8, None, True),
    "xing4_decode_12": (12, 4, 64, None, True),
    "kimivl_decode_16": (16, 6, 64, None, True),
    "solar_decode_32": (32, 8, 320, (0, 40), True),
    "xing4_mixed_140": (140, 4, 64, None, False),
    "kimivl_mixed_144": (144, 6, 64, None, False),
    "solar_mixed_160": (160, 8, 320, (0, 40), False),
    "xing4_run_524": (524, 4, 64, None, True),
    "train_shard_4096": (4096, 4, 60, None, True),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_rule_at_the_cells_shapes(cell):
    s, k, e, _, want = CELLS[cell]
    assert takes_sorted(s, k, e) is want


def test_the_rule_is_monotone_between_the_two_limits():
    """More rows or more choices never turn a dense group sorted under the
    row limit, and every group above it is sorted whatever it touches."""
    for k, e in ((2, 8), (4, 64), (6, 64), (8, 320), (4, 60)):
        forms = [takes_sorted(s, k, e) for s in range(1, sharded_moe.DENSE_UP_TO_TOKENS + 1)]
        assert forms == sorted(forms, reverse=True), (k, e)
        assert takes_sorted(sharded_moe.DENSE_UP_TO_TOKENS + 1, k, e)
    assert takes_sorted(1, 2, 8) and not takes_sorted(16, 2, 8)


def _abstract_layer(s, k, e, held, layers=3, d=16, f=24):
    count = e if held is None else held[1]
    sds = jax.ShapeDtypeStruct
    bank = tuple(sds((layers, count) + shape, jnp.float32) for shape in ((d, f), (d, f), (f, d)))
    fn = lambda x, logits, bank, mask: dropless_moe(x, logits, bank, k, mask, None, 1, held=held)  # noqa: E731
    return str(jax.make_jaxpr(fn)(sds((s, d), jnp.float32), sds((s, e), jnp.float32), bank, sds((s, ), bool)))


@pytest.mark.parametrize("cell", ["mixtral_decode_16", "mixtral_mixed_144", "xing4_decode_12", "solar_decode_32"])
def test_the_traced_program_holds_the_form_the_rule_names(cell, monkeypatch):
    """``ragged_dot`` (the CPU's stand-in for ``ds_gmm``) is in the program
    of Xing4's 12-row step and of Solar's 32, and in neither of Mixtral's,
    whose programs are word for word what the dense form alone traces: the
    rule changed nothing for them."""
    s, k, e, held, want = CELLS[cell]
    text = _abstract_layer(s, k, e, held)
    assert ("ragged_dot" in text) == want
    monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: want)
    assert _abstract_layer(s, k, e, held) == text
    if not want:
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: s > sharded_moe.DENSE_UP_TO_TOKENS)
        assert _abstract_layer(s, k, e, held) == text      # the one test the parent of PR 47 made


def _case(s, e_router, count, d=32, f=48, layers=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, logits = jax.random.normal(keys[0], (s, d)), 2.0 * jax.random.normal(keys[1], (s, e_router))
    bank = tuple(jax.random.normal(keys[2 + i], (layers, count) + shape) / np.sqrt(shape[0])
                 for i, shape in enumerate([(d, f), (d, f), (f, d)]))
    return x, logits, bank, 0.3 * jax.random.normal(keys[5], (e_router, ))


def _both_forms(monkeypatch, x, logits, bank, k, mask, bias, held):
    outs = {}
    for form in ("dense", "grouped"):
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: form == "grouped")
        fn = lambda x, logits, bank, layer: dropless_moe(  # noqa: E731
            x, logits, bank, k, mask, None, layer, True, "sigmoid", bias, 2.0, held)
        assert ("ragged_dot" in str(jax.make_jaxpr(fn)(x, logits, bank, jnp.int32(1)))) == (form == "grouped")
        outs[form] = jax.jit(fn)(x, logits, bank, jnp.int32(1))
    return outs["dense"], outs["grouped"]


@pytest.mark.parametrize("live", [1, 3, 12])
def test_forms_agree_at_twelve_rows_over_sixty_four_experts(live, monkeypatch):
    """Xing4's decode bucket: 12 rows, 4 of 64 a row, sigmoid scores with a
    selection bias and a routing scale, the layer read out of a stack; 1, 3
    or all 12 rows live."""
    x, logits, bank, bias = _case(12, 64, 64)
    mask = jnp.arange(12) < live
    (dense, _, dense_counts), (grouped, _, counts) = _both_forms(monkeypatch, x, logits, bank, 4, mask, bias, None)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(dense_counts))
    assert int(counts.sum()) == 4 * live and (np.asarray(counts) > 0).sum() <= 4 * live
    assert (np.asarray(grouped)[live:] == 0.0).all() and (np.asarray(dense)[live:] == 0.0).all()
    assert np.abs(np.asarray(grouped)[:live]).min() > 0.0


@pytest.mark.parametrize("first", [0, 120, 280])
def test_forms_agree_at_thirty_two_rows_over_forty_held_of_320(first, monkeypatch):
    """Solar-Open2's decode bucket: 32 rows, 8 of 320 a row, of which this
    share holds 40: most choices fall elsewhere and are dead rows."""
    x, logits, bank, bias = _case(32, 320, 40, seed=first + 1)
    mask = jnp.arange(32) % 5 != 2
    held = (first, 40)
    (dense, _, dense_counts), (grouped, _, counts) = _both_forms(monkeypatch, x, logits, bank, 8, mask, bias, held)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(dense_counts))
    assert counts.shape == (40, ) and 0 < int(counts.sum()) < 8 * int(mask.sum())
    assert (np.asarray(grouped)[~np.asarray(mask)] == 0.0).all() and (np.asarray(dense)[~np.asarray(mask)] == 0.0).all()
