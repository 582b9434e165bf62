"""BENCHMARK.json against its contract, and every name resolved to a file."""

import json
import os
import re

import pytest

from benchmark import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["workloads"]) <= 24
    assert len(manifest["command"]) <= 32
    assert any(word.startswith(tuple(manifest["paths"]))
               for word in manifest["command"])
    full_check = (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 2 * 90 + 1200
    assert full_check <= 43200


def test_names_units_and_entry_keys(manifest):
    names = []
    for config in manifest["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(key) for key in config["reduced"])
        assert len(config["reduced"]) <= 16
        names.append(config["name"])
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        names.append(cell["name"])
    for metric in manifest["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in manifest["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert metric["source"] in SOURCES
        assert 1 <= len(metric["layer"]) <= 200
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        names.append(metric["name"])
    assert all(NAME.match(name) for name in names), names
    assert len(set(names)) == len(names)
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    four = sum(cell["chips"] == 4 for cell in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_cell_resolves_to_files(manifest):
    pairs = set()
    for cell in manifest["workloads"]:
        found = run.resolve(cell["name"], rehearse=False)
        config, traffic = found["config"], found["traffic"]
        assert config["source"].startswith("https://")
        assert "reduced" in config and "assumed" in config
        entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
        assert sorted(entry["reduced"]) == sorted(config["reduced"])
        assert entry["file"].startswith(tuple(manifest["paths"]))
        for folder, name in (("drivers", config["driver"]),
                             ("reference", config["reference"]),
                             ("generators", traffic["generator"])):
            assert hasattr(run.load_module(folder, name),
                           {"drivers": "Session", "reference": "check",
                            "generators": "generate"}[folder])
        assert traffic["why"] and traffic["who"]
        pairs.add((cell["config"], cell["traffic"]))
        names = [m["name"] for m in found["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2
        assert found["per_layer"]
    assert len(pairs) == len(manifest["workloads"])
    used = {cell["config"] for cell in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_every_metric_has_a_reader_and_moves_what_its_cells_report(manifest):
    cells = [cell["name"] for cell in manifest["workloads"]]
    end_to_end = {m["name"]: m.get("workloads", cells)
                  for m in manifest["end_to_end"]}
    for metric in manifest["end_to_end"]:
        assert callable(run.load_module("end_to_end", metric["name"]).read)
    for metric in manifest["per_layer"]:
        assert callable(run.load_module("layer_metrics", metric["name"]).read)
        assert metric["moves"] in end_to_end and metric["moves"] != "setup_s"
        for cell in metric["workloads"]:
            assert cell in end_to_end[metric["moves"]], (metric["name"], cell)
    # and no reader waits on disk for a metric that nothing lists
    families = {stem for m in manifest["end_to_end"] + manifest["per_layer"]
                for stem in (m["name"], m["name"].rpartition(".")[0])}
    files = {name[:-3] for folder in ("end_to_end", "layer_metrics")
             for name in os.listdir(os.path.join(ROOT, "benchmark", folder))
             if name.endswith(".py")}
    assert files <= {stem.replace(".", "_") for stem in families}


def test_run_py_holds_no_name_of_a_configuration_cell_or_metric(manifest):
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        source = f.read()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert entry["name"] not in source, entry["name"]


def test_peaks_are_keyed_by_device_kind():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["source"]
    assert "cpu" not in peaks["device_kinds"]
