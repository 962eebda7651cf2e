"""What decides ``correct``, shown to fail.

- The control (the reference in the program's place, matmuls at ``high``)
  and planted faults fail the uci-har cells' own limits at the cells' size
  (the motionsense cell is too large for a test run).
- A run of a small cell with the program broken underneath (local
  training returning its input; half of each batch left out of the mean;
  a client's accuracy answer altered where it is produced) comes out
  ``correct: false``, and the same run unbroken comes out true.
- The decision laws catch a selection or share depth that breaks them.
"""

import argparse
import json
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, correct, reference
from bench import run as bench_run

CELL = "uci-har.acsp-int8.sync"


@pytest.fixture(scope="module", params=[CELL, "uci-har.acsp-f32.async"])
def uci_readings(request):
    _, _, workload, config = bench_run.load_cell(request.param)
    cands = ["program", "control", "half_batch", "answer", "unchanged"]
    found = {r["candidate"]: r for r in control.readings(workload, config, 11, cands)}
    return workload["limits"], found


def test_program_is_correct_at_the_cell_size(uci_readings):
    limits, found = uci_readings
    assert correct.judge(found["program"], limits)


@pytest.mark.parametrize("candidate", ["control", "half_batch", "answer", "unchanged"])
def test_control_and_faults_fail_at_the_cell_size(uci_readings, candidate):
    limits, found = uci_readings
    assert not correct.judge(found[candidate], limits)


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout whose one cell is the uci-har recipe and limits at a
    size a test run holds."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(bench_run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    config = bench_run.load_json(bench_run.BENCH / "configs" / "uci-har.json")
    config.update(name="small", n_clients=6, n_features=48,
                  samples_per_client_range=[60, 80])
    (root / "bench" / "configs" / "small.json").write_text(json.dumps(config))
    workload = bench_run.load_json(bench_run.BENCH / "workloads" / f"{CELL}.json")
    workload["recipe"]["scan_chunk"] = 4
    workload.update(name="small.cell", config="small")
    (root / "bench" / "workloads" / "small.cell.json").write_text(json.dumps(workload))
    cell = {**bench["workloads"][0], "name": "small.cell", "config": "small"}
    bench["workloads"] = [cell]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [m for m in bench[kind] if CELL in m.get("workloads", [CELL])]
        for m in bench[kind]:
            m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _fault_unchanged(monkeypatch):
    from repro.fl import phases

    monkeypatch.setattr(phases.SGDTrainer, "fit",
                        lambda self, ctx, env: ctx._replace(trained=ctx.train_model))


def _fault_half_batch(monkeypatch):
    from repro.fl import phases

    batched = phases._batched

    def half(x, y, m, batch_size, remainder="drop"):
        xb, yb, mb = batched(x, y, m, batch_size, remainder)
        return xb, yb, mb & (jnp.arange(mb.shape[1]) < mb.shape[1] // 2)

    monkeypatch.setattr(phases, "_batched", half)


def _fault_answer(monkeypatch):
    from repro.fl import phases

    evaluate = phases.DistributedEvaluator.evaluate

    def altered(self, ctx, env, model_fn=None):
        ctx = evaluate(self, ctx, env, model_fn)
        return ctx._replace(accuracy=ctx.accuracy.at[0].set(1.0 - ctx.accuracy[0]))

    monkeypatch.setattr(phases.DistributedEvaluator, "evaluate", altered)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch", "answer"])
def test_a_run_with_the_program_broken_underneath_is_not_correct(
    small_root, monkeypatch, fault
):
    if fault is not None:
        globals()[f"_fault_{fault}"](monkeypatch)
    args = argparse.Namespace(workload="small.cell", seed=2_147_483_711, seconds=0.5, trace=0)
    result = bench_run.run_cell(args, allow_cpu=True, root=small_root)
    assert result["correct"] is (fault is None)
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"decision_errors", "norm_gap0", "norm_gap", "acc_gap"}


def test_decision_laws():
    acc = np.array([[0.9, 0.5, 0.7, 0.3], [0.9, 0.6, 0.8, 0.4]], np.float32)
    sel = np.array([[True] * 4, [False, True, False, True]])
    pms = np.array([[4] * 4, [2, 2, 2, 4]], np.int32)
    assert reference.decision_errors(acc, sel, pms, 0.01, 4) == 0
    bad = sel.copy()
    bad[1, 0] = True
    assert reference.decision_errors(acc, bad, pms, 0.01, 4) == 1
    bad_pms = pms.copy()
    bad_pms[1, 3] = 3
    assert reference.decision_errors(acc, sel, bad_pms, 0.01, 4) == 1
