"""Shard bench — strong-scaling sweep of the cohort-sharded round step.

The tentpole claim of sharded cohort execution (repro.fl.shard): with the
(K, ...) gathered lanes partitioned K/D per device over the ``cohort``
mesh axis, per-device round compute shrinks to K/D lanes plus one psum
all-reduce of the aggregation partial sums. This bench sweeps
D in {1, 2, 4, 8} x K in {48, 200} at C=5000 (K=48 stands in for the
paper-scale K=50 — lanes must divide every device count in the sweep) and
reports, per cell: steady-state step time through the fused chunk
executor, psum bytes/round read out of the optimized SPMD HLO via
``launch.collectives.collective_bytes`` (the all-reduce entry — the
aggregator's psum is the only all-reduce the step emits), resharding
all-gather bytes, and lanes/device. The D=1 cell is the UNSHARDED step
(``cohort_devices=0``): the baseline is what a user runs today, so the
speedup column charges the sharded path for all of its own overhead.

On the CPU every cell runs in its own subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=D`` — jax locks the
device count at first init, and tests/conftest.py forbids forcing it
in-process for exactly that reason. On an accelerator the cells run
in-process on the first D devices (D beyond the visible count is
skipped): a chip belongs to one process, so a child could not reach it
once this process has touched JAX.

Backend honesty (the loop_bench precedent): forced host devices
TIMESHARE physical cores. On a box with fewer cores than D every
replicated phase (population eval, selection, the (C, ...) scatter) runs
D times serially, so wall-clock *cannot* hold the no-regression bar —
there is no parallel hardware to absorb it. The gates are therefore:

  off-CPU            : scaling efficiency (t1/tD)/D >= 0.7 at every D>1
  CPU, cores >= D    : no-regression — speedup t1/tD >= 0.9
  CPU, cores <  D    : serialization bound — tD <= 1.5 * D * t1 (catches
                       pathological resharding blowups; the honest limit
                       when D virtual devices share fewer cores — measured
                       thread-contention overhead runs ~40% at D=8 on one
                       core, and the pre-fix lane-resharding bug this
                       guard exists for cost an order of magnitude more)

plus, on every backend: the D>1 cells must show nonzero psum (all-reduce)
bytes in their HLO and the D=1 baseline must show none. Measured numbers
and the core count are recorded in BENCH_shard.json either way.

Emits experiments/bench/shard_bench.csv and BENCH_shard.json (repo root,
committed — a trajectory artifact like BENCH_loop.json). Smoke mode
(REPRO_BENCH_SMOKE=1, via ``benchmarks.run --smoke``) runs D in {1, 2},
K=48 at C=500 with the same gates. Run standalone with
``PYTHONPATH=src python -m benchmarks.shard_bench [--smoke]``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"

NO_REGRESSION = 0.90        # CPU with cores >= D: sharded must not lose
SERIAL_OVERHEAD_MAX = 1.5   # CPU with cores < D: tD <= 1.5 * D * t1
EFFICIENCY_FLOOR = 0.7      # off-CPU: (t1/tD)/D >= 0.7
EVAL_EVERY = 5              # thin the O(C) eval so cells time the cohort


def _cell(devices: int, k: int, c: int, rounds: int, reps: int) -> dict:
    """One sweep cell on the first ``devices`` devices of this process."""
    import jax
    import jax.numpy as jnp

    from repro.data import make_har_dataset
    from repro.fl import FLConfig, api
    from repro.launch.collectives import collective_bytes
    from repro.models.mlp import init_mlp

    assert jax.device_count() >= devices, (jax.device_count(), devices)
    ds = make_har_dataset("uci-har", seed=0, scale=0.02, n_clients=c)
    cfg = FLConfig(
        strategy="fedavg", personalization="none", fraction=k / c,
        epochs=1, rounds=rounds, cohort_size=k, eval_every=EVAL_EVERY,
        cohort_devices=devices if devices > 1 else 0,
    )
    env = api.build_env(ds, cfg.seed)
    pipe = api.pipeline_from_config(cfg)
    step = api.build_round_step(env, pipe, cfg.execution)
    g0 = init_mlp(jax.random.PRNGKey(0), ds.n_features, ds.n_classes,
                  hidden=(64, 64))
    state = api.RoundState(
        global_params=jax.tree.map(jnp.array, g0),
        local_params=None,  # NoPersonalizer: no (C, P) carry
        accuracy=jnp.zeros((c,)),
        select=jnp.ones((c,), bool),
        pms=jnp.full((c,), len(g0), jnp.int32),
        rng=jax.random.PRNGKey(1),
        participation=jnp.zeros((c,), jnp.int32),
        loss=jnp.zeros((c,)),
        update_norm=jnp.zeros((c,)),
    )
    chunk = api.build_chunk_step(step, rounds)
    ts = jnp.arange(rounds, dtype=jnp.int32)
    stats = collective_bytes(chunk.lower(state, ts).compile().as_text())

    state, outs = chunk(state, ts)  # warm: compile + first dispatch
    jax.block_until_ready(outs)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        state, outs = chunk(state, ts)  # donated carry, like the real loop
        jax.block_until_ready(outs)
        best = min(best, time.perf_counter() - t0)

    return {
        "D": devices,
        "K": k,
        "C": c,
        "sharded": devices > 1,
        "device_count": jax.device_count(),
        "backend": jax.default_backend(),
        "lanes_per_device": getattr(step, "lanes_per_device", k),
        "step_ms": best / rounds * 1e3,
        # per-round, per-device collective traffic out of the SPMD HLO:
        # psum partial sums lower to all-reduce; GSPMD resharding of the
        # gathered lanes shows up as all-gather
        "psum_bytes_per_round": stats.get("all-reduce", 0) / rounds,
        "allgather_bytes_per_round": stats.get("all-gather", 0) / rounds,
        "collective_ops": stats.get("count", 0),
    }


def _spawn_cell(devices: int, k: int, c: int, rounds: int, reps: int) -> dict:
    """Run one cell in a fresh interpreter with D forced host devices."""
    env = dict(os.environ)
    if devices > 1:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices}".strip()
        )
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.shard_bench", "--worker",
         "--devices", str(devices), "--k", str(k), "--c", str(c),
         "--rounds", str(rounds), "--reps", str(reps)],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"shard_bench cell D={devices} K={k} failed (exit {r.returncode}):\n"
            f"{r.stdout}\n{r.stderr}"
        )
    for line in r.stdout.splitlines():
        if line.startswith("CELL "):
            return json.loads(line[5:])
    raise RuntimeError(f"no CELL line from D={devices} K={k}:\n{r.stdout}")


def run():
    from benchmarks.common import write_bench_json, write_csv

    import jax

    cores = os.cpu_count() or 1
    if SMOKE:
        ds_sweep, ks, c, rounds, reps = [1, 2], [48], 500, 4, 2
    else:
        ds_sweep, ks, c, rounds, reps = [1, 2, 4, 8], [48, 200], 5000, 4, 2
    run_cell = _spawn_cell
    if jax.default_backend() != "cpu":
        run_cell = _cell
        ds_sweep = [d for d in ds_sweep if d <= jax.device_count()]

    cells = []
    for k in ks:
        for d in ds_sweep:
            cell = run_cell(d, k, c, rounds, reps)
            cells.append(cell)
            print(
                f"  D={d} K={k}: {cell['step_ms']:8.2f} ms/round"
                f"  lanes/dev={cell['lanes_per_device']:4d}"
                f"  psum {cell['psum_bytes_per_round'] / 1e6:6.2f} MB/round"
                f"  reshard {cell['allgather_bytes_per_round'] / 1e6:6.2f} MB/round"
            )

    backend = cells[0]["backend"]
    on_cpu = backend == "cpu"
    by_k = {k: {cl["D"]: cl for cl in cells if cl["K"] == k} for k in ks}
    failures = []
    rows = []
    for k in ks:
        base = by_k[k][1]
        for d in ds_sweep:
            cell = by_k[k][d]
            speedup = base["step_ms"] / cell["step_ms"] if d > 1 else 1.0
            cell["speedup"] = speedup
            cell["efficiency"] = speedup / d
            rows.append([
                d, k, c, cell["lanes_per_device"], f"{cell['step_ms']:.2f}",
                f"{speedup:.2f}", f"{speedup / d:.2f}",
                int(cell["psum_bytes_per_round"]),
                int(cell["allgather_bytes_per_round"]),
            ])
            if d == 1:
                if cell["psum_bytes_per_round"] != 0:
                    failures.append(
                        f"K={k}: unsharded baseline emits all-reduce "
                        f"({cell['psum_bytes_per_round']:.0f} B/round)"
                    )
                continue
            if cell["psum_bytes_per_round"] <= 0:
                failures.append(
                    f"D={d} K={k}: no psum all-reduce in the sharded HLO — "
                    "the aggregator is not reducing over the mesh"
                )
            if not on_cpu:
                if cell["efficiency"] < EFFICIENCY_FLOOR:
                    failures.append(
                        f"D={d} K={k}: scaling efficiency "
                        f"{cell['efficiency']:.2f} below the "
                        f"{EFFICIENCY_FLOOR} floor (backend={backend})"
                    )
            elif cores >= d:
                if speedup < NO_REGRESSION:
                    failures.append(
                        f"D={d} K={k}: cpu speedup {speedup:.2f}x below the "
                        f"{NO_REGRESSION}x no-regression bar ({cores} cores)"
                    )
            elif cell["step_ms"] > SERIAL_OVERHEAD_MAX * d * base["step_ms"]:
                failures.append(
                    f"D={d} K={k}: {cell['step_ms']:.1f} ms/round exceeds the "
                    f"serialization bound {SERIAL_OVERHEAD_MAX} * {d} * "
                    f"{base['step_ms']:.1f} ms ({cores} cores < D={d} forced "
                    "devices — resharding overhead is pathological)"
                )

    path = write_csv(
        "shard_bench",
        ["D", "K", "C", "lanes_per_device", "step_ms", "speedup",
         "efficiency", "psum_bytes_per_round", "allgather_bytes_per_round"],
        rows,
    )
    write_bench_json("shard", {
        "smoke": SMOKE,
        "backend": backend,
        "host_cores": cores,
        "C": c,
        "rounds_per_chunk": rounds,
        "eval_every": EVAL_EVERY,
        "cells": cells,
        "gates": {
            "no_regression_cpu": NO_REGRESSION,
            "serial_overhead_max_cpu": SERIAL_OVERHEAD_MAX,
            "efficiency_floor_offcpu": EFFICIENCY_FLOOR,
        },
        "note": (
            "D=1 is the unsharded step (cohort_devices=0); D>1 cells run in "
            "subprocesses with XLA_FLAGS-forced host devices. On CPU, forced "
            "devices timeshare physical cores: with cores >= D the "
            "no-regression bar applies, with cores < D only the "
            "serialization bound does (replicated phases execute D times "
            "serially — there is no hardware to scale on). psum bytes are "
            "the aggregator all-reduce per round per device, read from the "
            "optimized SPMD HLO; all-gather is GSPMD lane resharding."
        ),
    })
    if failures:
        for msg in failures:
            print(f"!! {msg}")
        sys.exit(1)
    return path


if __name__ == "__main__":
    if "--worker" in sys.argv:
        import argparse

        ap = argparse.ArgumentParser()
        ap.add_argument("--worker", action="store_true")
        for name in ("devices", "k", "c", "rounds", "reps"):
            ap.add_argument(f"--{name}", type=int, required=True)
        a = ap.parse_args()
        print("CELL " + json.dumps(_cell(a.devices, a.k, a.c, a.rounds, a.reps)))
    else:
        if "--smoke" in sys.argv:
            os.environ["REPRO_BENCH_SMOKE"] = "1"
            SMOKE = True
        run()
