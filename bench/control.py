"""Readings that set a cell's limits: the program's numbers on many seeds,
the control's, and those of planted faults, in one process.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 \
        [--candidates program,control,half_batch,answer,unchanged]

- ``program``: the timed path's first chunk, as a benchmark run takes it.
- ``no_exchange``: the program itself with the exchange between chips
  left out (every ``lax.psum`` returns its shard-local input), for a cell
  whose cohort is sharded.
- any other name: a candidate of the configuration's model
  (``bench/models/<model>.py``, ``CANDIDATES``), the model's reference put
  in the program's place: ``control`` at the precision below the
  configuration's, or with a fault planted. har-mlp has ``control``
  (matmuls at ``high``: three bfloat16 passes for float32 at
  ``highest``), ``half_batch`` (the second half of each batch left out of
  the loss), ``answer`` (client 0's accuracy answer altered) and
  ``unchanged`` (local training returning its input). The default is
  ``program`` and every candidate of the model.

Under the async scheduler the reference in the program's place follows the
program's own event schedule (landings and dispatches).

Each candidate's numbers (the model's ``numbers``) print as one JSON line.
Not part of a benchmark run; ``--allow-cpu`` runs it off the chip (tests).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from bench import run as bench_run


def program_outs(model, workload, config, data, seed):
    from bench import harness
    from repro.fl.engine import run_federated

    window = harness.Window(0.0, time.perf_counter())
    try:
        run_federated(data, model.fl_config(workload, config, seed, 10 ** 9), recorder=window)
    except harness.WindowClosed:
        pass
    return window.early_outs, window.decisions


@contextlib.contextmanager
def no_exchange():
    """Every ``jax.lax.psum`` returns its input: each chip aggregates only
    its own lanes."""
    import jax

    psum = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: x
    try:
        yield
    finally:
        jax.lax.psum = psum


def readings(workload, config, seed, candidates=None, root=bench_run.ROOT):
    """Each candidate's numbers at ``seed``; by default ``program`` and
    every candidate of the configuration's model."""
    from bench import models

    model = models.load(config["model"], root)
    if candidates is None:
        candidates = ["program", *model.CANDIDATES]
    recipe = workload["recipe"]
    data = model.make_dataset(config)
    sched = None
    if recipe["scheduler"] == "async":  # the program's event schedule
        sched = program_outs(model, workload, config, data, seed)
    for cand in candidates:
        t0 = time.perf_counter()
        if cand == "program":
            outs, decisions = sched or program_outs(model, workload, config, data, seed)
        elif cand == "no_exchange":
            with no_exchange():
                outs, decisions = program_outs(model, workload, config, data, seed)
        else:
            outs, decisions = model.candidate(cand, data, seed, recipe, config, sched)
        found = model.numbers(outs, data, seed, recipe, config, decisions)
        yield {"candidate": cand, "seed": seed, **found,
               "seconds": time.perf_counter() - t0}


def main(argv=None):
    bench_run._environment()
    p = argparse.ArgumentParser(prog="bench.control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--candidates", default=None)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args(argv)
    import jax

    _, entry, workload, config = bench_run.load_cell(args.workload)
    dev = jax.devices()
    if not args.allow_cpu and (dev[0].platform != "tpu" or len(dev) < entry["chips"]):
        raise SystemExit(f"bench.control: needs {entry['chips']} TPU chip(s)")
    bench_run.configure_jax(config)
    candidates = args.candidates.split(",") if args.candidates else None
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(workload, config, seed, candidates):
            print(json.dumps(line), flush=True)
    print(f"device {dev[0].device_kind} x{len(dev)}", file=sys.stderr)


if __name__ == "__main__":
    main()
