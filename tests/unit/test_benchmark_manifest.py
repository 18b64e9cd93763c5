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
