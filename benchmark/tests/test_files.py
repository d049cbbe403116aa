"""BENCHMARK.json and the files it names: every cell resolves to files
that exist, every name and unit meets the contract's character rules."""

import importlib
import json
import os
import re

import pytest

from benchmark.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$"
                    r"|head|expan|n_embd|n_inner|num_filters|per_tok)")


def line(text, limit=200):
    return (1 <= len(text) <= limit and "\n" not in text
            and "\t" not in text)


def test_top_level(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert 1 <= spec["run_seconds"] <= 51
    assert all(line(word) for word in spec["command"])
    assert os.path.exists(os.path.join(ROOT, spec["command"][1]))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_configs(spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in spec["workloads"]}
    for entry in spec["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(entry["name"]) and entry["name"] in used
        assert line(entry["source"]) and line(entry["why"])
        assert entry["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["name"] == entry["name"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        assert not any(WIDTHS.search(key) for key in entry["reduced"])
        importlib.import_module(f"benchmark.models.{config['model']}")
        # the rehearsal's tiny sizes override keys the file has
        assert set(config["tiny"]) <= set(config)


def test_workloads(spec):
    cells = spec["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    configs = {c["name"] for c in spec["configs"]}
    for entry in cells:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
        assert entry["config"] in configs and entry["chips"] in (1, 4)
        assert line(entry["why"])
        path = os.path.join(ROOT, "benchmark", "workloads",
                            entry["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == entry[key], (entry["name"], key)
        importlib.import_module(f"benchmark.jobs.{cell['job']}")
        assert set(cell["tiny"]) <= set(cell)
        assert not any(k.startswith("HVD_") for k in json.dumps(cell).split('"'))


def test_metrics(spec):
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in spec["workloads"]}
    end_to_end = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in end_to_end
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in end_to_end and line(m["layer"])
        layers.add(m["layer"])
        reader = importlib.import_module(f"benchmark.layers.{m['name']}")
        assert callable(reader.read)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    # every cell reports at least one per-layer metric
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])
    # PERF.md's list of layers has each layer under the same name
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)


def test_file_names_under_paths(spec):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in spec["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert ok.match(rel), rel


@pytest.mark.parametrize("metric", ["collective_ms", "device_idle_share",
                                    "busy_flops_util", "launches_per_step"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    import types

    reader = importlib.import_module(f"benchmark.layers.{metric}")
    run = types.SimpleNamespace(trace=None, traced_steps=16,
                                peak_flops=1e12, program_flops=1e9)
    assert reader.read(run) is None
