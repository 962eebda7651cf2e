"""A model family enters the benchmark by new files only.

A checkout of the benchmark gets the fixture family ``toy-mlp``
(``bench/tests/toy``): its model module, with a data generator and a
float32 reference of its own, its configuration and workload, a per-layer
metric that reads ``facts.model`` and ``scopes.scope_ms``, and its entries
appended to ``BENCHMARK.json``. No file the checkout shares with the repo
differs from it. The toy cell then runs end to end through
``bench.run.run_cell`` and ``bench.control``, and a configuration whose
model has no module is refused by name.
"""

import argparse
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import control, correct, models, peaks, scopes
from bench import metrics as metric_readers
from bench import run as bench_run

ROOT = bench_run.ROOT
TOY = Path(__file__).parent / "toy"
CELL = "toy.fedavg"
ARGS = argparse.Namespace(workload=CELL, seed=2_147_483_777, seconds=0.5, trace=0)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("models", "configs", "workloads", "metrics"):
        for f in (TOY / kind).iterdir():
            shutil.copy(f, root / "bench" / kind / f.name)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for kind, entries in json.loads((TOY / "benchmark.json").read_text()).items():
        bench[kind] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return root


def _files(root):
    return {p.relative_to(root) for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_the_family_is_added_by_new_files_and_entries_only(toy_root):
    added = _files(toy_root) - _files(ROOT)
    assert added == {Path("bench") / kind / f.name for kind in
                     ("models", "configs", "workloads", "metrics")
                     for f in (TOY / kind).iterdir()}
    for rel in _files(toy_root) & _files(ROOT):
        if rel != Path("BENCHMARK.json"):
            assert (toy_root / rel).read_bytes() == (ROOT / rel).read_bytes(), rel
    ours = json.loads((ROOT / "BENCHMARK.json").read_text())
    theirs = json.loads((toy_root / "BENCHMARK.json").read_text())
    assert set(theirs) == set(ours)
    for key, value in ours.items():
        if isinstance(value, list) and key != "command" and key != "paths":
            assert theirs[key][:len(value)] == value, key
        else:
            assert theirs[key] == value, key


def test_the_toy_cell_runs_correct_through_run_cell(toy_root):
    result = bench_run.run_cell(ARGS, allow_cpu=True, root=toy_root)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "round_ms.toy"}
    assert list(result["compared"]) == ["norm_gap0"]


def test_the_toy_control_and_planted_fault_fail(toy_root):
    _, _, workload, config = bench_run.load_cell(CELL, toy_root)
    found = {r["candidate"]: r for r in control.readings(workload, config, 5, root=toy_root)}
    assert set(found) == {"program", "control", "unchanged"}
    limits = workload["limits"]
    assert correct.judge(found["program"], limits)
    assert not correct.judge(found["control"], limits)
    assert not correct.judge(found["unchanged"], limits)
    assert found["unchanged"]["norm_gap0"] == 1.0


def test_the_toy_metric_reads_the_model_and_a_scope(toy_root, monkeypatch):
    """``toy_train_mfu`` divides the model's work by ``scope_ms("fl.train")``,
    here read from the committed scope fixture: 3000 ns of ``fl.train``
    a chip over 2 rounds."""
    fixture = json.loads((Path(__file__).parent / "scope_fixture.json").read_text())
    trace_dir = toy_root / "traces"
    (trace_dir / "cell").mkdir(parents=True)
    (trace_dir / "cell" / "vm.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "load", lambda path: fixture)
    monkeypatch.setattr(scopes, "TRACES", trace_dir)
    scopes._reduced.cache_clear()
    _, _, workload, config = bench_run.load_cell(CELL, toy_root)
    model = models.load(config["model"], toy_root)
    facts = SimpleNamespace(
        rounds=2, reduced={"window_ns": 10000}, sel=np.ones((2, 4), bool), chips=1,
        config=config, recipe=workload["recipe"], peak=peaks.PEAKS["TPU v5 lite"],
        model=model, n_train_valid=np.full(4, 48),
    )
    try:
        value = metric_readers.read("toy_train_mfu", facts, toy_root)
    finally:
        scopes._reduced.cache_clear()
    weights = 8 * 256 + 256 * 256 * 2 + 256 * 3
    per_round = 6 * weights * 4 * 48
    assert value == pytest.approx(100.0 * per_round / (1.5e-6 * 197e12))


def test_a_model_without_a_module_is_refused_by_name(toy_root, tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(toy_root, root)
    path = root / "bench" / "configs" / "toy.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), "model": "no-such-model"}))
    with pytest.raises(SystemExit, match=r"bench/models/no-such-model\.py not found"):
        bench_run.run_cell(ARGS, allow_cpu=True, root=root)
