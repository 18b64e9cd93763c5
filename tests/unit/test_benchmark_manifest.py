"""The limits of form that ``BENCHMARK.json`` is refused for before any run:
PR 49's first hand-in carried a configuration's ``why`` of 211 characters.
Held here for every entry, so a later cell's texts are counted on the CPU."""

import json
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text):
    return 1 <= len(text) <= 200 and text.isprintable()


@pytest.mark.parametrize("key", ["configs", "workloads"])
def test_names_and_texts_of_the_manifest_are_within_their_limits(key):
    entries = manifest()[key]
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names)) and 1 <= len(entries) <= 24
    for e in entries:
        assert NAME.fullmatch(e["name"]), e["name"]
        assert one_line(e["why"]), (e["name"], len(e["why"]))
        if key == "configs":
            assert one_line(e["source"]) and os.path.exists(os.path.join(ROOT, e["file"])), e["name"]
            assert len(e["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in e["reduced"]), e["name"]
        else:
            assert NAME.fullmatch(e["traffic"]) and e["chips"] in (1, 4), e["name"]
            assert e["config"] in [c["name"] for c in manifest()["configs"]], e["name"]


def test_metrics_of_the_manifest_name_cells_that_exist():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    metrics = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in metrics]
    assert len(names) == len(set(names)) and os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for x in metrics:
        assert NAME.fullmatch(x["name"]) and UNIT.fullmatch(x["unit"]) and x["better"] in ("lower", "higher"), x
        assert set(x.get("workloads", [])) <= cells, x["name"]
    for x in m["per_layer"]:
        assert one_line(x["layer"]) and x["moves"] in names, x["name"]


# ---------------------------------------------------------------- the cell of PR 54

CELL, CONFIG, TRAFFIC = "trinity_mixed_queue", "trinity-large-preview-serve-1chip", "short_long_one_queue"
#: the published ``config.json`` of Trinity-Large-Preview, the numbers of its every key that says something of shape
PUBLISHED = {"global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 3072, "intermediate_size": 12288,
             "load_balance_coeff": 5e-05, "max_position_embeddings": 262144, "moe_intermediate_size": 3072,
             "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
             "num_experts_per_tok": 4, "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
             "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.448,
             "sliding_window": 4096, "topk_group": 1, "vocab_size": 200192}


def _bench_file(folder, name):
    with open(os.path.join(ROOT, "benchmark", folder, name + ".json")) as f:
        return json.load(f)


def test_the_trinity_configuration_keeps_every_published_number_but_the_reduced_ones():
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    cfg = _bench_file("configs", CONFIG)
    assert entry["source"] == cfg["source"] and entry["file"].endswith(CONFIG + ".json")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(cfg["published"])
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys, and the nested group kept whole
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    assert len(cfg["layer_types"]) == 60 and cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["model_type"] == "afmoe" and cfg["score_func"] == "sigmoid" and cfg["mup_enabled"] is True
    assert cfg["engine"]["enable_prefix_cache"] is False and cfg["engine"]["scheduler"]["decode_bucket"] == 32


def test_the_trinity_cell_is_named_wherever_its_metrics_are_read():
    m = manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    e2e = {x["name"] for x in m["end_to_end"] if "workloads" not in x or CELL in x["workloads"]}
    assert e2e == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    mine = {x["name"]: x for x in m["per_layer"] if CELL in x.get("workloads", [])}
    assert {"swa_attn_roofline", "swa_attn_busy_share", "ring_fill_share", "tokens_per_tick", "tick_p50_ms",
            "compiles_in_window.serve", "hbm_peak_gb.serve", "slot_fill_share", "step_host_p50_ms",
            "step_device_wait_p50_ms", "step_excess_share", "expert_kernel_share", "attn_walk_tightness",
            "prefill_ms_per_ktok_mean", "ttft_bypassed_mean_ms", "ttft_wait_mean_ms", "queue_wait_p90_ms",
            "gen_late_p90_ms"} == set(mine)
    for name, x in mine.items():
        assert x["moves"] in e2e, name                         # a metric moves an end-to-end metric the cell reports
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")), name
    for name in ("swa_attn_roofline", "swa_attn_busy_share", "ring_fill_share"):
        assert mine[name]["workloads"] == [CELL] and mine[name]["moves"] == "tpot_p50_ms"
    assert (mine["swa_attn_roofline"]["unit"], mine["swa_attn_roofline"]["source"]) == ("%", "device_trace")
    assert mine["ring_fill_share"]["source"] == "program_counter"
    # every list the cell joined ends with it: nothing before it moved
    for x in m["end_to_end"] + m["per_layer"]:
        if CELL in x.get("workloads", []):
            assert x["workloads"][-1] == CELL, x["name"]


def test_the_trinity_traffic_is_two_modes_in_one_queue_at_four_fifths_of_its_knee():
    traffic = _bench_file("traffic", TRAFFIC)
    assert (traffic["kind"], traffic["block_s"], traffic["mix_seed"]) == ("serve_open_loop", 10, 54)
    short, long_ = traffic["prompt"]["mixture"]
    assert (short["weight"], short["dist"], short["median"], short["sigma"]) == (0.6, "lognormal", 512, 0.8)
    assert (long_["weight"], long_["dist"], long_["lo"], long_["hi"]) == (0.4, "loguniform", 8192, 32768)
    assert traffic["prompt"]["clip"] == [128, 32768]
    answer, = traffic["output"]["mixture"]
    assert (answer["dist"], answer["median"], answer["sigma"]) == ("lognormal", 256, 0.5)
    assert traffic["output"]["clip"] == [64, 512]
    assert traffic["rate_per_s"] == pytest.approx(0.8 * traffic["knee_per_s"])
    assert one_line(traffic["why"]) or len(traffic["why"]) > 200      # a file's why is prose, not the manifest's line
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import traffic_gen
    assert traffic["lead_in_s"] == traffic_gen.lead_in_rule(traffic)
    lengths = traffic_gen.stratified_lengths(traffic["prompt"], 1000)
    assert sum(n >= 8192 for n in lengths) == 400 and min(lengths) == 128 and max(lengths) <= 32768
