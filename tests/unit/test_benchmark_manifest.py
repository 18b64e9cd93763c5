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
    # every list the cell joined ended with it, and only the cell of PR 56 has come behind it: nothing before it moved
    for x in m["end_to_end"] + m["per_layer"]:
        if CELL in x.get("workloads", []):
            assert x["workloads"][x["workloads"].index(CELL) + 1:] in ([], [CELL56]), x["name"]


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


# ---------------------------------------------------------------- the cell of PR 56

CELL56, CONFIG56, TRAFFIC56 = "granite4hs_agent_turns", "granite-4.0-h-small-serve-1chip", "agent_turns_mid_in_short_out"
#: the published ``config.json`` of granite-4.0-h-small, the numbers of its every key that says something of shape
PUBLISHED56 = {"attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_size": 4096,
               "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_d_conv": 4,
               "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
               "max_position_embeddings": 131072, "num_attention_heads": 32, "num_experts_per_tok": 10,
               "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72, "residual_multiplier": 0.22,
               "rms_norm_eps": 1e-05, "rope_theta": 10000, "shared_intermediate_size": 1536, "vocab_size": 100352}
NEW56 = ("expert_bank_busy_share", "expert_bank_roofline", "ssd_update_busy_share")


def test_the_granite_small_configuration_keeps_every_published_number_but_the_reduced_ones():
    entry = next(c for c in manifest()["configs"] if c["name"] == CONFIG56)
    cfg = _bench_file("configs", CONFIG56)
    assert entry["source"] == cfg["source"] and entry["file"].endswith(CONFIG56 + ".json")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(cfg["published"])
    for key, value in PUBLISHED56.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # no width among the reduced keys; the router keeps its published width and the share is stated
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    assert (cfg["router_experts"], cfg["first_expert"], cfg["num_local_experts"], cfg["vocab_size"]) == (72, 0, 36, 50176)
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and cfg["num_hidden_layers"] == 10
    assert cfg["model_type"] == "granitemoehybrid" and cfg["family"] == "granitemoehybrid"
    assert "2 chips that share each layer" in cfg["deployment"] and cfg["precision"]["recurrent_state"] == "float32"
    assert cfg["parameters"]["count"] == 4_757_211_776 and cfg["parameters"]["bytes_bfloat16"] == 2 * 4_757_211_776
    engine = cfg["engine"]
    assert engine["enable_prefix_cache"] is False and engine["scheduler"]["decode_bucket"] == 32
    assert engine["kv"] == {"num_pages": 17440, "page_size": 16} and engine["scheduler"]["max_seqs"] == 32
    assert os.path.exists(os.path.join(ROOT, "benchmark", "refs", cfg["family"] + ".py"))


def test_the_granite_small_cell_is_named_wherever_its_metrics_are_read():
    m = manifest()
    assert len(m["workloads"]) == 12 and sum(w["chips"] == 4 for w in m["workloads"]) == 1
    cell = next(w for w in m["workloads"] if w["name"] == CELL56)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG56, TRAFFIC56, 1) and m["workloads"][-1] is cell
    e2e = {x["name"] for x in m["end_to_end"] if "workloads" not in x or CELL56 in x["workloads"]}
    assert e2e == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    mine = {x["name"]: x for x in m["per_layer"] if CELL56 in x.get("workloads", [])}
    assert {"queue_wait_p90_ms", "gen_late_p90_ms", "tokens_per_tick", "tick_p50_ms", "compiles_in_window.serve",
            "hbm_peak_gb.serve", "slot_fill_share", "step_host_p50_ms", "step_device_wait_p50_ms",
            "step_excess_share", "expert_kernel_share", "attn_walk_tightness", "ssd_update_roofline",
            "prefill_ms_per_ktok_mean", "ttft_bypassed_mean_ms", "ttft_wait_mean_ms", *NEW56} == set(mine)
    for name, x in mine.items():
        assert x["moves"] in e2e, name                         # a metric moves an end-to-end metric the cell reports
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")), name
    # the three this PR brings: last in the list, the cell's alone, read from the device's trace, the layer's name kept
    assert [x["name"] for x in m["per_layer"][-3:]] == list(NEW56)
    for name in NEW56:
        assert mine[name]["workloads"] == [CELL56] and mine[name]["moves"] == "tpot_p50_ms"
        assert (mine[name]["source"], mine[name]["layer"]) == ("device_trace", "Kernels")
    assert (mine["expert_bank_roofline"]["unit"], mine["expert_bank_roofline"]["better"]) == ("%", "higher")
    assert all(mine[n]["unit"] == "share" and mine[n]["better"] == "lower" for n in NEW56 if n.endswith("busy_share"))
    # every list the cell joined ends with it: nothing before it moved
    for x in m["end_to_end"] + m["per_layer"]:
        if CELL56 in x.get("workloads", []):
            assert x["workloads"][-1] == CELL56, x["name"]


def test_the_agent_turns_traffic_is_offered_at_four_fifths_of_its_knee():
    traffic = _bench_file("traffic", TRAFFIC56)
    assert (traffic["kind"], traffic["block_s"], traffic["mix_seed"], traffic["drain_cap_s"]) == \
        ("serve_open_loop", 10, 56, 120)
    prompt, = traffic["prompt"]["mixture"]
    assert (prompt["dist"], prompt["median"], prompt["sigma"]) == ("lognormal", 1024, 0.9)
    assert traffic["prompt"]["clip"] == [128, 8192]
    answer, = traffic["output"]["mixture"]
    assert (answer["dist"], answer["median"], answer["sigma"]) == ("lognormal", 128, 0.6)
    assert traffic["output"]["clip"] == [32, 512]
    assert traffic["rate_per_s"] == pytest.approx(0.8 * traffic["knee_per_s"])
    assert set(traffic["at_rate"]) == {"ttft_mean_ms", "tpot_p50_ms"} and "sweep" in traffic["why"]
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import traffic_gen
    assert traffic["lead_in_s"] == traffic_gen.lead_in_rule(traffic)
    lengths = traffic_gen.stratified_lengths(traffic["prompt"], 1000)
    assert min(lengths) == 128 and max(lengths) == 8192 and 1000 <= sorted(lengths)[500] <= 1050
    # every slot holds the longest request and a fused dispatch of overshoot: 545 pages, of which 32 are 17,440
    assert 32 * -(-(8192 + 512 + 8) // 16) == 17440
