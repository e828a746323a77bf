"""BENCHMARK.json against its contract, and the harness finding every file
by name.  No chip, no JAX."""

import json
import os
import re
import shutil

import pytest

from bench_paths import BENCH, ROOT, manifest_data

from benchmark.harness import manifest as mf

DATA = manifest_data()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = DATA["end_to_end"] + DATA["per_layer"]
CELLS = DATA["workloads"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_size():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= DATA["run_seconds"] <= 51
    assert DATA["command"][-1].startswith(DATA["paths"][0] + "/")
    cells = len(CELLS)
    # the check's runs must fit its day with the full 24 cells
    seconds = DATA["run_seconds"]
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24
    assert sum(c["chips"] == 4 for c in CELLS) <= max(1, cells // 4)


@pytest.mark.parametrize("entry", METRICS + CELLS + DATA["configs"],
                         ids=lambda e: e["name"])
def test_names_are_well_formed(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "why" in entry:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_names_are_unique():
    for group in (METRICS, CELLS, DATA["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in DATA["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
        assert 1 <= len(metric["layer"]) <= 200
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {c["name"] for c in CELLS}
    assert set(metric.get("workloads", cells)) <= cells


@pytest.mark.parametrize("metric", DATA["per_layer"], ids=lambda m: m["name"])
def test_moves_names_a_metric_each_listed_cell_reports(metric):
    manifest = mf.Manifest(ROOT)
    end_to_end = {m["name"] for m in DATA["end_to_end"]}
    assert metric["moves"] in end_to_end
    listed = metric.get("workloads")
    for cell in CELLS:
        reported = {m["name"] for m in manifest.end_to_end(cell["name"])}
        if listed is not None and cell["name"] in listed:
            assert metric["moves"] in reported
        if listed is None and metric["moves"] in reported:
            assert metric in manifest.per_layer(cell["name"])
    if "roofline" in metric["name"]:
        assert metric["name"].endswith("_roofline") and metric["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_reports_enough_and_its_files_are_found(cell):
    manifest = mf.Manifest(ROOT)
    reported = [m["name"] for m in manifest.end_to_end(cell["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert manifest.per_layer(cell["name"])
    assert cell["chips"] in (1, 4)
    config = manifest.config(cell["config"])
    assert config["reduced"] == [] and "assumed" in config
    mix = manifest.traffic(cell["traffic"])
    assert callable(mf.driver(mix["kind"]))
    assert manifest.limits(cell["name"])
    kernels = [m for m in manifest.per_layer(cell["name"])
               if m["name"].endswith("_roofline")]
    whole = [m for m in manifest.per_layer(cell["name"])
             if "mfu" in re.split(r"[_.]", m["name"])]
    for kernel in kernels:
        assert any(w["moves"] == kernel["moves"] for w in whole)


@pytest.mark.parametrize("metric", DATA["per_layer"], ids=lambda m: m["name"])
def test_reader_is_found_by_name(metric):
    assert callable(mf.Manifest(ROOT).reader(metric["name"]))


@pytest.mark.parametrize("config", DATA["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    assert config["file"].startswith(DATA["paths"][0] + "/")
    assert any(c["config"] == config["name"] for c in CELLS)
    data = json.load(open(os.path.join(ROOT, config["file"])))
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    files = [c["file"] for c in DATA["configs"]]
    assert files.count(config["file"]) == 1


def test_every_file_under_paths_has_a_contract_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in DATA["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert ok.match(rel), rel


def test_new_cell_mix_config_and_metric_arrive_as_files(tmp_path):
    """A copy of the benchmark's data files plus four NEW files and four NEW
    entries: everything is found, and no file that was there is edited."""
    root = str(tmp_path)
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(root, "benchmark", sub))
    data = manifest_data()
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "gpt2-124m.json")))
    config.update(n_layer=24, n_embd=1024, n_head=16, source="paper X")
    with open(os.path.join(root, "benchmark/configs/new-model.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmark/traffic/new-mix.json"),
              "w") as f:
        json.dump({"kind": "train-steps", "nodes": 2, "per_node_batch": 4}, f)
    with open(os.path.join(root, "benchmark/limits/new-cell.json"),
              "w") as f:
        json.dump({"limits": {"loss1": 1.0}}, f)
    with open(os.path.join(root, "benchmark/metrics/new_metric.py"),
              "w") as f:
        f.write("def read(run):\n    return run.counters.get('x')\n")
    data["configs"].append({"name": "new-model", "source": "paper X",
                            "file": "benchmark/configs/new-model.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": "new-cell", "config": "new-model",
                              "traffic": "new-mix", "chips": 1,
                              "why": "test"})
    for metric in data["end_to_end"]:
        if metric["name"] == "train_tokens_per_s_per_chip":
            metric["workloads"].append("new-cell")
    data["per_layer"].append({
        "name": "new_metric", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "trainer host loop",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = mf.Manifest(root)
    cell = manifest.cell("new-cell")
    assert manifest.config(cell["config"])["n_layer"] == 24
    assert manifest.traffic(cell["traffic"])["nodes"] == 2
    assert manifest.limits("new-cell") == {"loss1": 1.0}
    names = [m["name"] for m in manifest.per_layer("new-cell")]
    assert "new_metric" in names and "compile_s" in names

    class FakeRun:
        counters = {"x": 7.0}

    assert manifest.reader("new_metric")(FakeRun()) == 7.0
    with pytest.raises(mf.ManifestError):
        manifest.cell("no-such-cell")
    with pytest.raises(mf.ManifestError):
        manifest.reader("no_such_metric")
    with pytest.raises(mf.ManifestError):
        mf.driver("no-such-kind")
