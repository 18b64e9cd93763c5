"""On the chip, at the size of the cell ``kimivl_pages``
(``benchmark/configs/kimi-vl-a3b-serve-1chip.json``: the 27-layer tower whole
at heads of 72, one dense and seven expert layers at every published width,
the whole vocabulary, bfloat16): the cell's own check row (a 64 x 64 image,
the table's own size, and a 46 x 88 one, interpolated and padded to its
bucket; SplitFuse chunks of 128 with ``mm_index``; decode through the latent
pages) under weights with which every part of the tower shows, and the
reference with one part left out each time, which has to fail.  Run with:

    DS_TPU_TESTS=1 python -m pytest tests/tpu/test_kimi_vl_on_chip.py -q -s

Under the benchmark's weights rule the position table is a fifteenth of the
patch embedding and the attention's scores are small, so the benchmark's
check would pass a tower without either (PERF.md section 2); here the table is
ten times and ``W_qkv`` twice what the rule gives.  ``DS_CHECK_SEED`` draws
other weights, pixels and tokens.
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def _load(folder, name):
    with open(os.path.join(ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_the_position_table_the_2d_rotary_and_the_merge_are_held_at_the_cells_size():
    import jax
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.utils import compile_cache

    import harness
    from kinds import serve_open_loop_images as kind
    assert jax.devices()[0].platform == "tpu", jax.devices()
    compile_cache.enable()
    cfg, traffic = _load("configs", "kimi-vl-a3b-serve-1chip"), _load("traffic", "image_pages_short_answer")
    seed = int(os.environ.get("DS_CHECK_SEED", 3000041001))
    pcfg = harness.program_config(cfg)
    _, params = harness.seeded_params(cfg, pcfg, seed, jax.devices()[:1])
    tower = params["params"]["vision_tower"]
    tower["pos_emb"] = tower["pos_emb"] * 10
    tower["layers"]["wqkv"]["kernel"] = tower["layers"]["wqkv"]["kernel"] * 2
    eng = InferenceEngineV2(pcfg, params, kind.engine_config(cfg, traffic))
    rows = kind.check_rows(cfg, seed)
    got = kind.program_logits(eng, rows)
    ref = kind.reference_logits(cfg, params, rows)

    def readings(candidate):
        return {g: v[0] for g, v in kind.group_readings(cfg, *kind.position_errors(rows, candidate, ref)).items()}

    program = readings(got)
    changed = {part: readings([logits for logits, _ in kind.reference_logits(cfg, params, rows, ablate=(part, ))])
               for part in ("pos_table", "rope_2d", "merge")}
    print("kimi_vl_on_chip", json.dumps({"seed": seed, "program": program, "changed": changed,
                                         "hbm_peak_bytes": max(harness.hbm_bytes(jax.devices()[:1]))}))
    limits = cfg["check"]["limits"]
    assert all(program[g] <= limits[g] for g in limits), program
    for part, reading in changed.items():       # every mutilated reference is called by the limit the cell holds the program to
        assert all(reading[g] > limits[g] for g in limits), (part, reading)
        assert all(reading[g] > 3 * program[g] for g in limits), (part, reading, program)
    assert np.isfinite(list(program.values())).all()
