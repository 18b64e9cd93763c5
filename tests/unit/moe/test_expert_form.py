"""Which form a dropless group takes (``moe/sharded_moe.takes_sorted``): a
rule of the rows, the choices a row and the router's experts, at the shapes
the benchmark's cells hold; and the two forms against each other at the
shapes the rule newly hands to the sorted one (12 rows over 64 experts
through a ``layer=`` stack, 32 rows over 40 held of 320).  Where the slots
say "dense" and a mask says which of them live, the program holds both forms
and the live rows choose between them by the same rule (``sorted_up_to``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import dropless_moe, live_rows_sorted, sorted_up_to, takes_sorted

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
#: the cells whose slots say "dense" and whose program holds the sorted form as well, for so many live rows: where
#: that is at least half the live counts the group can have
BOTH_FORMS = {"mixtral_decode_16": 11, "solar_mixed_160": 119}


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


@pytest.mark.parametrize("k, e, rows", [(2, 8, 11), (4, 64, 47), (6, 64, 31), (8, 320, 119)])
def test_the_most_live_rows_that_are_sorted(k, e, rows):
    """The static bound a router: the rule's own crossing, no constant of its own."""
    assert sorted_up_to(k, e) == rows and takes_sorted(rows, k, e) and not takes_sorted(rows + 1, k, e)


@pytest.mark.parametrize("cell", [c for c in CELLS if not CELLS[c][4]])
def test_which_dense_groups_hold_the_sorted_form_too(cell):
    """A decode bucket of 16 against a bound of 11 rows, Solar's 160 slots
    against 119: both forms.  144 slots against 11, 140 against 47, 144
    against 31: the dense form alone, as before."""
    s, k, e, _, _ = CELLS[cell]
    assert live_rows_sorted(s, k, e) == BOTH_FORMS.get(cell, 0)
    assert live_rows_sorted(2 * sorted_up_to(k, e), k, e) and not live_rows_sorted(2 * sorted_up_to(k, e) + 1, k, e)


def _abstract_layer(s, k, e, held, masked=True, layers=3, d=16, f=24):
    count = e if held is None else held[1]
    sds = jax.ShapeDtypeStruct
    bank = tuple(sds((layers, count) + shape, jnp.float32) for shape in ((d, f), (d, f), (f, d)))
    fn = lambda x, logits, bank, mask: dropless_moe(  # noqa: E731
        x, logits, bank, k, mask if masked else None, None, 1, held=held)
    return jax.make_jaxpr(fn)(sds((s, d), jnp.float32), sds((s, e), jnp.float32), bank, sds((s, ), bool))


@pytest.mark.parametrize("masked", [True, False], ids=["mask", "no_mask"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_traced_program_holds_the_form_the_rule_names(cell, masked, monkeypatch):
    """A statically sorted shape (Xing4's 12-row step, Solar's 32, every run
    step, the train cell's 4,096 rows a shard) traces the sorted form alone,
    with a mask or without: word for word what it traces with the rule held
    to "sorted", no ``cond``.  A shape whose slots say "dense" traces the
    dense form alone, the parent's program word for word, where no mask is
    given (every row lives) or the bound is under half its slots (144 of 2
    over 8, 140 of 4 over 64); else, with a mask, one ``cond`` of which one
    branch holds ``ragged_dot`` (the CPU's stand-in for ``ds_gmm``) and the
    other none."""
    s, k, e, held, want = CELLS[cell]
    jaxpr = _abstract_layer(s, k, e, held, masked)
    conds = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]
    if not (masked and cell in BOTH_FORMS):
        assert not conds and ("ragged_dot" in str(jaxpr)) == want
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: want)
        assert str(_abstract_layer(s, k, e, held, masked)) == str(jaxpr)
    else:
        (cond, ) = conds
        dense, grouped = (str(branch) for branch in cond.params["branches"])      # the predicate false, true
        assert "ragged_dot" in grouped and "ragged_dot" not in dense
        assert not any("ragged_dot" in str(eqn) for eqn in jaxpr.eqns if eqn is not cond)
    if not want:         # the one test the parent of PR 47 made gives the dense form alone, the parent's program
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: s > sharded_moe.DENSE_UP_TO_TOKENS)
        alone = _abstract_layer(s, k, e, held, masked)
        assert "ragged_dot" not in str(alone) and not [eqn for eqn in alone.eqns if eqn.primitive.name == "cond"]
        if not (masked and cell in BOTH_FORMS):
            assert str(alone) == str(jaxpr)


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


@pytest.mark.parametrize("live", [1, 3, 11, 12, 16])
def test_the_live_rows_choose_the_form_at_sixteen_slots_of_two_over_eight(live, monkeypatch):
    """Mixtral's decode bucket: 16 slots say "dense", 11 live rows of them are
    the most that are sorted.  The branch that runs is the rule's of the live
    rows, its output is that forced form's bit for bit and the other's within
    the tolerance of the forms, and padding comes out as zeros."""
    x, logits, bank, _ = _case(16, 8, 8)
    mask = jnp.arange(16) < live
    fn = lambda x, logits, bank, layer: dropless_moe(x, logits, bank, 2, mask, None, layer)  # noqa: E731
    ran = []
    for name in ("_experts_grouped", "_experts_dense_in_place"):      # the two branches of the conditional
        form = getattr(sharded_moe, name)
        monkeypatch.setattr(sharded_moe, name, lambda *args, form=form, name=name:
                            (jax.debug.callback(lambda: ran.append(name)), form(*args))[1])
    got, _, counts = jax.jit(fn)(x, logits, bank, jnp.int32(1))
    jax.effects_barrier()
    assert ran == ["_experts_grouped" if takes_sorted(live, 2, 8) else "_experts_dense_in_place"]
    assert ran == ["_experts_grouped" if live <= 11 else "_experts_dense_in_place"]
    forced = {}
    for form in ("dense", "grouped"):
        monkeypatch.setattr(sharded_moe, "takes_sorted", lambda s, k, e: form == "grouped")
        forced[form], _, forced_counts = jax.jit(fn)(x, logits, bank, jnp.int32(1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(forced[form]), atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(forced_counts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(forced["grouped" if live <= 11 else "dense"]))
    assert int(counts.sum()) == 2 * live and (np.asarray(got)[live:] == 0.0).all()
    assert np.abs(np.asarray(got)[:live]).min() > 0.0
