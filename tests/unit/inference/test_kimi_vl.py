"""Kimi-VL (``models/kimi_vl.py``) against the plain reference
(``benchmark/refs/kimi_vl.py``) on seeded float32 weights at a small size:
the tower on a square grid of the table's own size, an oblong one and an
interpolated one larger than the table; a padded bucket against the unpadded
image; the bicubic against hand-worked values at ``A = -0.75``; the whole
model with two images; the parameter count the configuration states."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from deepspeed_tpu.models.kimi_vl import (KimiVLConfig, KimiVLForCausalLM, bicubic_taps, interpolated_positions,
                                          merge_patches)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from refs import kimi_vl as ref  # noqa: E402

VISION = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=8, intermediate_size=96, patch_size=2,
              init_pos_emb_height=8, init_pos_emb_width=8, merge_kernel_size=[2, 2])
TEXT = dict(vocab_size=512, hidden_size=128, intermediate_size=192, moe_intermediate_size=64, num_hidden_layers=3,
            num_attention_heads=4, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            n_routed_experts=8, num_experts_per_tok=2, media_placeholder_token_id=500, max_position_embeddings=4096)


def small(**over):
    cfg = KimiVLConfig(vision_config=VISION, dtype=jnp.float32, param_dtype=jnp.float32, **{**TEXT, **over})
    as_dict = {**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}, "vision_config": VISION}
    model = KimiVLForCausalLM(cfg)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))
    # biases, norm weights and the selection bias away from their initial 0 and 1, so that dropping one shows
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [a + 0.05 * jax.random.normal(k, a.shape) for a, k in zip(leaves, keys)])
    return cfg, as_dict, model, params


@pytest.fixture(scope="module")
def kimi():
    return small()


@pytest.mark.parametrize("grid", [(8, 8), (4, 6), (10, 12)], ids=["square_table_size", "oblong", "interpolated_up"])
def test_tower_merger_and_projector_follow_the_reference(kimi, grid):
    cfg, as_dict, model, params = kimi
    h, w = grid
    pixels = np.random.default_rng(h * w).standard_normal((h * w, 12)).astype(np.float32)
    got = model.apply(params, jnp.asarray(pixels), jnp.asarray(grid), method="encode_images")
    want = ref.image_rows(params, jnp.asarray(pixels), grid, as_dict)
    assert got.shape == (h * w // 4, cfg.hidden_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    for part in ("pos_table", "rope_2d"):   # each part of the tower shows at this size
        without = ref.image_rows(params, jnp.asarray(pixels), grid, as_dict, ablate=(part, ))
        assert np.abs(np.asarray(without) - np.asarray(want)).max() > 1e-2, part


@pytest.mark.parametrize("grid, bucket", [((4, 6), 32), ((6, 4), 64), ((8, 8), 64)])
def test_a_padded_bucket_gives_the_unpadded_images_rows(kimi, grid, bucket):
    _, _, model, params = kimi
    h, w = grid
    rng = np.random.default_rng(7)
    pixels = rng.standard_normal((h * w, 12)).astype(np.float32)
    padded = np.concatenate([pixels, 9.0 * rng.standard_normal((bucket - h * w, 12)).astype(np.float32)])
    alone = model.apply(params, jnp.asarray(pixels), jnp.asarray(grid), method="encode_images")
    in_bucket = model.apply(params, jnp.asarray(padded), jnp.asarray(grid), method="encode_images")
    assert in_bucket.shape[0] == bucket // 4
    np.testing.assert_allclose(np.asarray(in_bucket[:h * w // 4]), np.asarray(alone), atol=2e-5)


def test_bicubic_is_pytorchs_at_minus_three_quarters():
    # 4 -> 8: output 3 reads s = 1.25: taps 0..3 at t = 0.25.  By hand, A = -0.75:
    # w0 = ((A 1.25 - 5A) 1.25 + 8A) 1.25 - 4A = -0.10546875, w1 = ((A + 2) .25 - (A + 3)) .0625 + 1 = 0.87890625,
    # w2 = ((A + 2) .75 - (A + 3)) .5625 + 1 = 0.26171875, w3 = ((A 1.75 - 5A) 1.75 + 8A) 1.75 - 4A = -0.03515625
    taps, weights = bicubic_taps(jnp.arange(8), 4, jnp.asarray(8))
    np.testing.assert_array_equal(np.asarray(taps[3]), [0, 1, 2, 3])
    np.testing.assert_allclose(np.asarray(weights[3]), [-0.10546875, 0.87890625, 0.26171875, -0.03515625], atol=1e-7)
    # output 0 reads s = -0.25: floor -1, t = 0.75, taps -2..1 clamped to 0, 0, 0, 1
    np.testing.assert_array_equal(np.asarray(taps[0]), [0, 0, 0, 1])
    np.testing.assert_allclose(np.asarray(weights[0]), [-0.03515625, 0.26171875, 0.87890625, -0.10546875], atol=1e-7)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-6)
    # jax.image.resize's cubic (A = -0.5) is another function
    ramp = jnp.arange(4.0)
    ours = np.asarray(ref.bicubic_matrix(4, 8) @ np.arange(4.0))
    assert np.abs(ours - np.asarray(jax.image.resize(ramp, (8, ), "cubic"))).max() > 1e-2
    np.testing.assert_allclose(ref.bicubic_matrix(4, 8)[3], [-0.10546875, 0.87890625, 0.26171875, -0.03515625])
    # at the table's own size the interpolation is the table
    table = jax.random.normal(jax.random.PRNGKey(2), (8, 8, 5))
    y, x = jnp.divmod(jnp.arange(64), 8)
    same = interpolated_positions(table, y, x, jnp.asarray(8), jnp.asarray(8))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(table.reshape(64, 5)))


def test_the_merger_takes_two_by_two_blocks_row_major():
    h, w = 4, 6
    z = jnp.arange(32.0)[:, None]                                   # a bucket of 32, 24 of them the image's
    merged = np.asarray(merge_patches(z, jnp.asarray([h, w]), (2, 2)))[:h * w // 4, :, 0]
    assert merged[0].tolist() == [0, 1, 6, 7] and merged[1].tolist() == [2, 3, 8, 9]
    assert merged[3].tolist() == [12, 13, 18, 19] and merged[5].tolist() == [16, 17, 22, 23]


def prompt_with_images(rng, grids, placeholder=500):
    ids, index, images, row = [], [], [], 0
    for i, (h, w) in enumerate(grids):
        text = rng.integers(1, 400, 5 + 3 * i).tolist()
        ids += text + [placeholder] * (h * w // 4)
        index += [-1] * len(text) + list(range(row, row + h * w // 4))
        row += h * w // 4
        images.append((rng.standard_normal((h * w, 12)).astype(np.float32), (h, w)))
    tail = rng.integers(1, 400, 7).tolist()
    return np.asarray(ids + tail), np.asarray(index + [-1] * len(tail)), images


def test_whole_model_follows_the_reference(kimi):
    cfg, as_dict, model, params = kimi
    ids, mm_index, images = prompt_with_images(np.random.default_rng(3), [(4, 6), (8, 6)])
    rows = jnp.concatenate([model.apply(params, jnp.asarray(px), jnp.asarray(g), method="encode_images")
                            for px, g in images])
    got = model.apply(params, jnp.asarray(ids)[None], mm_index=jnp.asarray(mm_index)[None], mm_rows=rows)[0]
    want, margin = ref.forward(params, jnp.asarray(ids), as_dict, images=images)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert margin.shape == ids.shape and float(margin.min()) >= 0
    # without the merge the placeholders' own embeddings stay: another model
    unmerged, _ = ref.forward(params, jnp.asarray(ids), as_dict, images=images, ablate=("merge", ))
    assert np.abs(np.asarray(unmerged) - np.asarray(want)).max() > 1e-2
    # text alone: no tower, the token path of every other model
    text = jnp.asarray(ids[:5])
    np.testing.assert_allclose(np.asarray(model.apply(params, text[None])[0]),
                               np.asarray(ref.forward(params, text, as_dict)[0]), atol=2e-4)


def test_the_int8_control_covers_the_towers_products(kimi):
    _, as_dict, _, params = kimi
    pixels = jnp.asarray(np.random.default_rng(0).standard_normal((16, 12)).astype(np.float32))
    exact = ref.image_rows(params, pixels, (4, 4), as_dict)
    rounded = ref.image_rows(params, pixels, (4, 4), as_dict, mode="int8")
    rel = float(jnp.linalg.norm(rounded - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 0.5, rel


def test_parameter_count_the_configuration_states():
    with open(os.path.join(ROOT, "benchmark", "configs", "kimi-vl-a3b-serve-1chip.json")) as f:
        file = json.load(f)
    names = {f.name for f in dataclasses.fields(KimiVLConfig)}
    cfg = KimiVLConfig(**{k: v for k, v in file.items() if k in names}, param_dtype=jnp.bfloat16)
    assert cfg.q_lora_rank is None and cfg.vision.head_dim == 72 and cfg.vision.num_hidden_layers == 27
    tree = nn.meta.unbox(jax.eval_shape(KimiVLForCausalLM(cfg).init, jax.random.PRNGKey(0),
                                        jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda t: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(t))  # noqa: E731
    assert count(tree) == file["parameters"]["count"] == 5_295_545_264
    assert file["parameters"]["bytes_bfloat16"] == 2 * count(tree)
    assert count(tree["vision_tower"]) == 416_866_032 and count(tree["multi_modal_projector"]) == 30_679_808
    layers = tree["language_model"]["layers"]
    assert count(layers) == 7 * 584_847_936 and count(layers["mlp"]["experts"]) == 7 * 64 * 3 * 2048 * 1408
    assert "q_proj" in layers["self_attn"] and "q_a_proj" not in layers["self_attn"]
    assert file["reduced"].keys() == {"num_hidden_layers"} and file["published"]["num_hidden_layers"] == 27
