"""The control of ``correct`` for ``kimivl_pages``, as ``test_control_xing4.py``
keeps it for ``xing4_longdoc``: at the configuration's ``rehearsal`` size on
the CPU the program's logits pass and the int8 control (the tower's products
rounded too) fails the limit, in both groups, on three seeds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_control_kimi_vl.py -q

The row's two images (8 x 8: the table's own size; 4 x 6: interpolated, a
padded bucket) go through the engine's own encode programs into its row
buffer, its 46 prompt tokens in two chunks of 32 and 14 with ``mm_index``
(both cross text / image borders), then 8 decode steps of one token.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

SEEDS = (0, 1, 2 ** 31 + 5)


def cell_files():
    import run
    cfg = run.load_json("configs", "kimi-vl-a3b-serve-1chip.json")
    traffic = run.load_json("traffic", "image_pages_short_answer.json")
    return run.merge(cfg, cfg["rehearsal"]), run.merge(traffic, traffic["rehearsal"])


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_logits_separate_program_from_int8_control(seed):
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    import harness
    from kinds import serve_open_loop_images as kind
    cfg, traffic = cell_files()
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    assert [g for _, g in rows[0][0].images] == [(8, 8), (4, 6)]
    ref = kind.reference_logits(cfg, params, rows)
    control = [logits for logits, _ in kind.reference_logits(cfg, params, rows, mode="int8")]
    readings = [kind.group_readings(cfg, *kind.position_errors(rows, got, ref))
                for got in (kind.program_logits(eng, rows), control)]
    print("readings", seed, readings)
    assert eng.mm_alloc.free_pages == eng.mm_alloc.num_pages - 1      # the check gives its units back
    limits = cfg["check"]["limits"]
    assert set(limits) == {"long", "decode"}
    for group, limit in limits.items():  # every group of positions separates the two by itself
        assert readings[0][group][0] <= limit < readings[1][group][0], (group, readings)


def test_the_mix_offers_the_same_requests_in_every_seed_and_other_pixels():
    import run
    from kinds import serve_open_loop_images as kind
    cfg = run.load_json("configs", "kimi-vl-a3b-serve-1chip.json")
    traffic = run.load_json("traffic", "image_pages_short_answer.json")
    small = {**traffic, "images": {**traffic["images"], "grids": [{"grid": [4, 4], "weight": g["weight"]}
                                                                  for g in traffic["images"]["grids"]]}}
    shapes, first = set(), set()
    for seed in (0, 7, 2 ** 31 + 7):
        sched = kind.serving_schedule(small, 51, seed, cfg)
        shapes.add(tuple((round(r["due"], 9), r["measured"], len(r["prompt"]), len(r["prompt"].images),
                          r["max_new_tokens"]) for r in sched))
        first.add((tuple(sched[0]["prompt"][:4]), float(sched[0]["prompt"].images[0][0][0, 0, 0, 0])))
        assert all((0 <= r["due"] < 51) == r["measured"] for r in sched)
        assert all(t != cfg["media_placeholder_token_id"] for r in sched for t in r["prompt"][-1:])
    assert len(shapes) == 1 and len(first) == 3
    # the mix as the file has it: counts, grids and lengths (shapes alone: no pixels are drawn here)
    import numpy as np
    import traffic_gen
    n = int(round(traffic["rate_per_s"] * 51))
    counts = traffic_gen.stratified_lengths(traffic["images"]["count"], n)
    assert min(counts) == 1 and max(counts) == 6 and sorted(counts)[n // 2] == 3
    grids = kind._stratified([tuple(g["grid"]) for g in traffic["images"]["grids"]],
                             [g["weight"] for g in traffic["images"]["grids"]], 200, np.random.default_rng(0))
    share = {g: grids.count(g) / 200 for g in set(grids)}
    assert abs(share[(64, 64)] - 0.30) < 0.02 and abs(share[(46, 88)] - 0.25) < 0.02 and len(share) == 6
    assert all(h * w <= 4096 and h % 2 == 0 and w % 2 == 0 for h, w in share)
    lo, hi = traffic["prompt"]["clip"]
    assert lo == 32 + 32 * 32 // 4 and hi == 256 + 6 * 64 * 64 // 4
