"""The sharded cell's comparison, shown to fail with the timed path broken
underneath in each way the cell can be: the exchange between chips left
out, local training returning its input, half of each batch left out of
the mean, a client's accuracy answer altered. A small copy of
``motionsense.acsp-f32.shard4`` on four forced host devices, run in a
fresh interpreter because JAX fixes its device count when it starts."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import data as bench_data
from bench import run as bench_run

CELL = "motionsense.acsp-f32.shard4"

BODY = """
import argparse, contextlib, json, sys
root, fault = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, root + "/src"]
from pathlib import Path
from bench import control, run as bench_run
import jax
assert len(jax.devices()) == 4
args = argparse.Namespace(workload="small.shard4", seed=2_147_483_713, seconds=0.5, trace=0)
import jax.numpy as jnp
from repro.fl import phases
if fault == "unchanged":
    phases.SGDTrainer.fit = lambda self, ctx, env: ctx._replace(trained=ctx.train_model)
elif fault == "half_batch":
    batched = phases._batched
    def half(x, y, m, batch_size, remainder="drop"):
        xb, yb, mb = batched(x, y, m, batch_size, remainder)
        return xb, yb, mb & (jnp.arange(mb.shape[1]) < mb.shape[1] // 2)
    phases._batched = half
elif fault == "answer":
    evaluate = phases.DistributedEvaluator.evaluate
    def altered(self, ctx, env, model_fn=None):
        ctx = evaluate(self, ctx, env, model_fn)
        return ctx._replace(accuracy=ctx.accuracy.at[0].set(1.0 - ctx.accuracy[0]))
    phases.DistributedEvaluator.evaluate = altered
ctx = control.no_exchange() if fault == "no_exchange" else contextlib.nullcontext()
with ctx:
    result = bench_run.run_cell(args, allow_cpu=True, root=Path(root))
print(json.dumps(result))
"""


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout whose one cell is the sharded recipe and limits at a
    size a test run holds: 8 clients, 2 lanes a device."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(bench_run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(bench_run.ROOT / "src", root / "src")
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    _, entry, workload, config = bench_run.load_cell(CELL)
    cell_range = config["samples_per_client_range"]
    config.update(name="small", n_clients=8, n_features=48,
                  samples_per_client_range=[60, 80])
    (root / "bench" / "configs" / "small.json").write_text(json.dumps(config))
    workload["recipe"]["scan_chunk"] = 2
    # acc_gap counts test samples: keep the limit's share of a client's test rows
    rows = bench_data.padded_widths(config["samples_per_client_range"])[1]
    workload["limits"]["acc_gap"] *= rows / bench_data.padded_widths(cell_range)[1]
    workload.update(name="small.shard4", config="small")
    (root / "bench" / "workloads" / "small.shard4.json").write_text(json.dumps(workload))
    bench["workloads"] = [{**entry, "name": "small.shard4", "config": "small"}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [m for m in bench[kind] if CELL in m.get("workloads", [CELL])]
        for m in bench[kind]:
            m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("fault", [None, "no_exchange", "unchanged", "half_batch", "answer"])
def test_a_sharded_run_without_the_exchange_between_chips_is_not_correct(small_root, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"{os.environ.get('XLA_FLAGS', '')} "
                        "--xla_force_host_platform_device_count=4".strip()}
    p = subprocess.run([sys.executable, "-c", BODY, str(small_root), str(fault)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["device"]["count"] == 4
    assert result["correct"] is (fault is None), result["compared"]
