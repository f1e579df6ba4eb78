"""BENCHMARK.json against the contract's limits, and every cell's files found
by the names the manifest gives."""

import json
import os
import re

import pytest

from benchmark.harness import reader_path

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


M = manifest()
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in M["paths"])
    four = [w for w in M["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in M["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    e2e = {m["name"]: m for m in M["end_to_end"]}
    if metric["name"] in e2e:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["moves"] in e2e and 1 <= len(metric["layer"]) <= 200
        moved = e2e[metric["moves"]].get("workloads", CELLS)
        assert set(metric.get("workloads", moved)) <= set(moved)
        # the reader is found by the metric's name (a dotted suffix may share its stem's file)
        path = reader_path(metric["name"])
        assert os.path.isfile(path)
        stem = os.path.basename(path)[: -len(".py")]
        assert metric["name"] == stem or metric["name"].rsplit(".", 1)[0] == stem
    assert set(metric.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("cell", M["workloads"], ids=CELLS)
def test_cell_files_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    configs = {c["name"]: c for c in M["configs"]}
    config = configs[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as fh:
        held = json.load(fh)
    assert held["name"] == cell["config"] and held["source"] == config["source"]
    assert held["reduced"] == config["reduced"]
    with open(os.path.join(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
    # every cell reports setup_s, another end-to-end metric and a per-layer metric
    e2e = [m["name"] for m in M["end_to_end"] if cell["name"] in m.get("workloads", CELLS)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(
        cell["name"] in m.get("workloads", CELLS) and m["moves"] in e2e for m in M["per_layer"]
    )


@pytest.mark.parametrize("config", M["configs"], ids=[c["name"] for c in M["configs"]])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and 1 <= len(config["source"]) <= 200
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert any(w["config"] == config["name"] for w in M["workloads"])
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])
    assert len({c["source"] for c in M["configs"]}) == len(M["configs"])


def test_names_are_unique_and_files_named_from_name_characters():
    for group in (M["configs"], M["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in M["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert allowed.match(rel), rel


def test_a_split_metric_shares_its_stems_reader():
    """`name.suffix` without a file of its own is read by `name.py`; a name
    with its own file keeps it."""
    assert os.path.basename(reader_path("cycle_kernel_ms.stream")) == "cycle_kernel_ms.py"
    assert os.path.basename(reader_path("window_device_ms.serve")) == "window_device_ms.serve.py"
    assert os.path.basename(reader_path("hbm_peak_gb.batch")) == "hbm_peak_gb.py"
