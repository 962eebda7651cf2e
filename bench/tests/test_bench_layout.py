"""BENCHMARK.json and the files it names: every cell finds its workload
and configuration file, every per-layer metric its reader, and a new
configuration, cell or metric needs only new files and new entries."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import metrics as metric_readers
from bench import models
from bench import run as bench_run

ROOT = bench_run.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[kind]]
        assert len(names) == len(set(names)), kind
        assert all(NAME.match(n) for n in names), kind


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    _, entry, workload, config = bench_run.load_cell(cell)
    assert workload["name"] == cell and workload["config"] == entry["config"]
    assert config["name"] == entry["config"]
    assert set(workload["limits"]) == {"decision_errors", "norm_gap0", "norm_gap", "acc_gap"}
    assert workload["limits"]["decision_errors"] == 0
    reported = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])]
    assert {m["name"] for m in reported} == {"setup_s", workload["rate_metric"]}
    assert any(cell in m.get("workloads", [cell]) for m in BENCH["per_layer"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_file_matches_its_entry(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"] and config["reduced"] == entry["reduced"]
    assert entry["file"] == f"bench/configs/{entry['name']}.json"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(metric_readers.reader(metric["name"], ROOT))
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells


def test_adding_a_config_a_cell_and_a_metric_needs_only_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = bench["workloads"][0]
    config = json.loads((ROOT / "bench" / "configs" / f"{base['config']}.json").read_text())
    config["name"] = "new-config"
    (root / "bench" / "configs" / "new-config.json").write_text(json.dumps(config))
    workload = json.loads((ROOT / "bench" / "workloads" / f"{base['name']}.json").read_text())
    workload.update(name="new-config.new-cell", config="new-config")
    (root / "bench" / "workloads" / "new-config.new-cell.json").write_text(json.dumps(workload))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(facts):\n    return facts.rounds\n")
    bench["configs"].append({**bench["configs"][0], "name": "new-config",
                             "file": "bench/configs/new-config.json"})
    bench["workloads"].append({**base, "name": "new-config.new-cell", "config": "new-config"})
    bench["per_layer"].append({**bench["per_layer"][0], "name": "new_metric",
                               "workloads": ["new-config.new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    _, entry, wl, conf = bench_run.load_cell("new-config.new-cell", root)
    assert entry["config"] == "new-config" and conf["name"] == "new-config"
    assert wl["recipe"] == workload["recipe"]

    class Facts:
        rounds = 7

    assert metric_readers.read("new_metric", Facts(), root) == 7.0


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_a_model_module(entry):
    config = json.loads((ROOT / entry["file"]).read_text())
    model = models.load(config["model"], ROOT)
    for name in ("make_dataset", "data_facts", "fl_config", "check_widths", "numbers",
                 "candidate"):
        assert callable(getattr(model, name)), name
    assert model.CANDIDATES
