"""BENCHMARK.json against the contract's limits, and against its own files."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32 and all(_line(w) for w in manifest["command"])
    assert manifest["paths"] == ["benchmark"]


def test_names_units_and_lines(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(REPO, c["file"]))
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_name_has_its_file_and_the_arrows_hold(manifest):
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    reports = {}
    for name, w in cells.items():
        assert w["config"] in configs
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        with open(os.path.join(BENCH, "workloads", name + ".json")) as fh:
            cell = json.load(fh)
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        reports[name] = set(cell["end_to_end"])
        assert "setup_s" in reports[name] and len(reports[name]) >= 2
        for metric in reports[name]:
            assert name in e2e[metric].get("workloads", cells)
    for metric, m in e2e.items():
        for cell in m.get("workloads", cells):
            assert metric in reports[cell], (metric, cell)
    used = set()
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] and m["moves"] in e2e
        assert os.path.exists(os.path.join(BENCH, "reducers", spec["reducer"] + ".py"))
        for cell in m.get("workloads", cells):
            assert m["moves"] in reports[cell], (m["name"], cell)
            used.add(cell)
    assert used == set(cells)       # every cell reports at least one per-layer metric
    assert {c["name"] for c in manifest["configs"]} == {w["config"] for w in cells.values()}


def test_file_names_under_paths_use_name_characters():
    for folder, _, files in os.walk(BENCH):
        if "__pycache__" in folder:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(folder, f)
