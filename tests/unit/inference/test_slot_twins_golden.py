"""The two slot-holding families against what they were before their twins
took row groups (PR 38 split ``MambaMixer`` and ``Mamba2Mixer`` into parts and
moved the twins onto the flat axis): the parameter trees of the full-sequence
models and of the twins, name by name with shapes and dtypes, and what a
rectangle through either twin gives, logits and every array of the cache, as
sums and norms.  ``slot_twins_golden.json`` was written by this file on the
parent of PR 38:

    PYTHONPATH=<a checkout> JAX_PLATFORMS=cpu python tests/unit/inference/test_slot_twins_golden.py > slot_twins_golden.json

The benchmark makes its weights from the tree and its check feeds rectangles,
so both are contracts of the twins.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
from deepspeed_tpu.models.cache_zoo import cache_twin
from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from deepspeed_tpu.models.llama_cache import PagedKVConfig
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM

PAGE, WIDTH, TABLE, ROWS = 16, 32, 6, 3
KV = PagedKVConfig(num_pages=1 + ROWS * TABLE, page_size=PAGE, max_pages_per_seq=TABLE)
#: the small configurations of tests/unit/inference/test_row_groups.py
FAMILIES = {
    "phi4flash": (Phi4FlashForCausalLM,
                  Phi4FlashConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=8,
                                  num_attention_heads=4, num_key_value_heads=2, sliding_window=32,
                                  max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32)),
    "granitehybrid": (GraniteHybridForCausalLM,
                      GraniteHybridConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                                          shared_intermediate_size=64, num_hidden_layers=2,
                                          layer_types=("mamba", "attention"), num_attention_heads=4,
                                          num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                                          max_position_embeddings=512, dtype=jnp.float32, param_dtype=jnp.float32)),
}
#: three steps of rectangles [ROWS, WIDTH]: real tokens a row (a row that ends mid-chunk, a row that joins late and
#: starts from a zero state beside rows that carry theirs, rows of one token beside a chunk, a dead row)
STEPS = [[WIDTH, 0, 17], [WIDTH, WIDTH, 1], [1, 5, 0]]


def _tree(variables):
    return {jax.tree_util.keystr(path): [list(leaf.shape), jnp.dtype(leaf.dtype).name]
            for path, leaf in jax.tree_util.tree_leaves_with_path(nn.meta.unbox(variables))}


def _twin_inputs(cfg):
    cache = cache_twin(cfg).init_cache(cfg, KV, jnp.float32, ROWS + 1, WIDTH)
    tables = 1 + np.arange(ROWS * TABLE, dtype=np.int32).reshape(ROWS, TABLE)
    tables[:, -1] = [2, 1, 3]   # a row's slot in its last column
    return cache, jnp.asarray(tables)


def _real_from(cfg):
    """Per array of the cache, the first index of its second axis past the
    null page and the scratch slot, which hold whatever padding wrote."""
    if isinstance(cfg, Phi4FlashConfig):
        from deepspeed_tpu.models.phi4flash_cache import page_groups
        return {"pages": page_groups(cfg), "ring": page_groups(cfg), "ssm": 1, "conv": 1}
    return {"pages": 1, "ssm": 1, "conv": 1}


def fingerprint(family):
    """{the full-sequence model's tree, the twin's tree, per step and array (sum, norm)}."""
    full_cls, cfg = FAMILIES[family]
    twin = build_cache_model(cfg, PAGE)
    cache, tables = _twin_inputs(cfg)
    init_args = (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, ), jnp.int32), tables[:1], cache, jnp.ones((1, ), jnp.int32))
    out = {"model": _tree(jax.eval_shape(full_cls(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))),
           "twin": _tree(jax.eval_shape(twin.init, jax.random.PRNGKey(0), *init_args))}
    params = nn.meta.unbox(jax.jit(twin.init)(jax.random.PRNGKey(0), *init_args))
    # every slot holds something a row that starts must not see
    cache = {k: v if k == "pages" else v.at[:, 1:].set(0.5) for k, v in cache.items()}
    apply = jax.jit(twin.apply, static_argnums=(6, ))
    ids = np.random.default_rng(0).integers(1, cfg.vocab_size - 1, (ROWS, len(STEPS) * WIDTH), dtype=np.int32)
    pos = np.zeros(ROWS, np.int32)
    for i, lens in enumerate(STEPS):
        toks = np.zeros((ROWS, WIDTH), np.int32)
        for r, n in enumerate(lens):
            toks[r, :n] = ids[r, pos[r]:pos[r] + n]
        args = (params, jnp.asarray(toks), jnp.asarray(pos), tables, cache, jnp.asarray(lens, jnp.int32))
        last = apply(*args, True)[0]
        logits, cache = apply(*args, False)
        live = np.arange(WIDTH)[None, :] < np.asarray(lens)[:, None]          # a padding position's logits are not held
        arrays = {"logits": np.asarray(logits)[live], "last_only": np.asarray(last)[np.asarray(lens) > 0],
                  **{k: np.asarray(v)[:, _real_from(cfg)[k]:] for k, v in cache.items()}}
        out[f"step{i}"] = {k: [float(np.sum(v, dtype=np.float64)), float(np.linalg.norm(v.astype(np.float64)))]
                           for k, v in arrays.items()}
        pos += np.asarray(lens, np.int32)
    return out


def _golden():
    with open(os.path.join(os.path.dirname(__file__), "slot_twins_golden.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("which", ["model", "twin"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_parameter_tree_keeps_every_name_shape_and_dtype(family, which):
    full_cls, cfg = FAMILIES[family]
    if which == "model":
        tree = _tree(jax.eval_shape(full_cls(cfg).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    else:
        cache, tables = _twin_inputs(cfg)
        tree = _tree(jax.eval_shape(build_cache_model(cfg, PAGE).init, jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                                    jnp.zeros((1, ), jnp.int32), tables[:1], cache, jnp.ones((1, ), jnp.int32)))
    assert tree == _golden()[family][which]
    assert which == "model" or tree == _golden()[family]["model"]            # the twin's tree is the model's


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_rectangle_gives_the_logits_and_the_cache_it_gave(family):
    """Three steps of rectangles in real slots, under the parameters one key
    gives (so their values are held too): every position's logits, the head
    over the sampled rows, and every array of the cache."""
    got, want = fingerprint(family), _golden()[family]
    for step in (k for k in want if k.startswith("step")):
        assert got[step].keys() == want[step].keys()
        for name, numbers in want[step].items():
            np.testing.assert_allclose(got[step][name], numbers, rtol=2e-5, atol=2e-5, err_msg=f"{step} {name}")


if __name__ == "__main__":
    print(json.dumps({family: fingerprint(family) for family in FAMILIES}, indent=1))
