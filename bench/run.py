"""Run one cell of the benchmark of record on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell's entry in ``BENCHMARK.json`` names
its workload file ``bench/workloads/<cell>.json``, which names its
configuration ``bench/configs/<config>.json``, whose ``model`` names the
module ``bench/models/<model>.py`` that supplies the data, the program's
configuration, the width check, the comparison with the model's reference
and the work counts (``bench.models``). The run makes its data and weights
from ``--seed``, drives ``repro.fl.run_federated`` unchanged with a
``bench.harness.Window`` as its recorder, checks the first rounds against
the model's reference and prints one JSON line last on standard output. With
``--trace 1`` it traces a steady span of the window and prints the cell's
per-layer metrics (``bench/metrics/<metric>.py``) instead of its
end-to-end ones. It refuses to run without a TPU.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache"
BENCH = ROOT / "bench"


def _environment():
    """The compile cache at a fixed path inside the checkout, uncapped, so a
    run's entries never evict each other; the program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, workload file, configuration file)."""
    bench = load_json(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    workload = load_json(root / "bench" / "workloads" / f"{name}.json")
    config = load_json(root / "bench" / "configs" / f"{entry['config']}.json")
    return bench, entry, workload, config


def configure_jax(config: dict):
    import jax

    # the configuration's stated precision
    jax.config.update("jax_default_matmul_precision", config["matmul_precision"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CacheCounter:
    """Persistent compile cache hits and misses, each with its time on the
    host clock."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
              "/jax/compilation_cache/cache_misses": "miss"}

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        name = self.EVENTS.get(event)
        if name:
            self.events.append((name, time.perf_counter()))

    def count(self, name: str, after: float = float("-inf")) -> int:
        return sum(1 for n, t in self.events if n == name and t > after)


class Tracer:
    """Starts a profiler trace at the window's second hook and stops it at
    the first hook ``seconds`` later; remembers the hooks in between."""

    def __init__(self, directory: Path, seconds: float):
        self.dir, self.seconds = directory, seconds
        self.t0 = self.t1 = None
        self.first_hook = self.last_hook = None
        self._annotation = None

    def __call__(self, window, now):
        import jax

        from bench.harness import OPEN_AT

        n = len(window.hooks)
        if self.t0 is None and n == OPEN_AT + 1:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
            window.profiler.annotate = True
            self.t0, self.first_hook = time.perf_counter(), n
        elif self.t0 is not None and self.t1 is None and now - self.t0 >= self.seconds:
            self.stop(window)

    def stop(self, window):
        import jax

        self._annotation.__exit__(None, None, None)
        window.profiler.annotate = False
        # the span ends here: writing the trace out takes seconds
        self.t1, self.last_hook = time.perf_counter(), len(window.hooks)
        jax.profiler.stop_trace()


def run_cell(args, allow_cpu: bool = False, root: Path = ROOT) -> dict:
    """One run; returns the result line as a dict. Also prints the compared
    numbers and their limits on standard error, last."""
    import jax

    from bench import correct, harness, models, peaks

    bench, entry, workload, config = load_cell(args.workload, root)
    model = models.load(config["model"], root)
    devices = jax.devices()
    chips = entry["chips"]
    if not allow_cpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"bench.run: needs {chips} TPU chip(s), found {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    configure_jax(config)
    cache = CacheCounter()
    peak = peaks.chip_peaks(devices[0].device_kind, allow_unknown=allow_cpu)

    from repro.fl.engine import run_federated

    data = model.make_dataset(config)
    cfg = model.fl_config(workload, config, args.seed, 10 ** 9)
    tracer = None
    if args.trace:
        tracer = Tracer(CACHE / "trace" / args.workload, workload["trace_seconds"])
    window = harness.Window(args.seconds, STARTED, on_hook=tracer)
    try:
        run_federated(data, cfg, recorder=window)
    except harness.WindowClosed:
        pass
    else:
        raise SystemExit("run_federated returned before the window closed")
    if tracer is not None and tracer.t1 is None:
        tracer.stop(window)
    model.check_widths(window.opened, config)
    used = devices[:chips]
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    gc.collect()
    late = sum(1 for name, t0, _ in window.profiler.spans
               if name == "compile" and t0 > window.t_first)
    print(f"bench.run: {args.workload} seed {args.seed} on {devices[0].device_kind} "
          f"x{len(used)}: compile cache hits {cache.count('hit')} misses "
          f"{cache.count('miss')}, setup {window.setup_s:.3f} s, {window.rounds} rounds in "
          f"{window.window_s:.3f} s, {late} compiles and "
          f"{cache.count('miss', window.t_first)} cache misses in the window",
          file=sys.stderr, flush=True)

    recipe = workload["recipe"]
    found = model.numbers(window.early_outs, data, args.seed, recipe, config, window.decisions)
    limits = workload["limits"]
    ok = correct.judge(found, limits)

    result = {
        "correct": ok,
        "attempted": window.rounds,
        "failed": window.failed,
        "metrics": {},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(used),
            "memory_peak_bytes": int(memory_peak),
        },
    }

    def cell_metrics(kind):
        return [m for m in bench[kind] if args.workload in m.get("workloads", [args.workload])]

    if args.trace:
        from bench import metrics as metric_readers, trace_reduce

        facts = trace_reduce.facts(tracer, window, model, data, config, recipe, peak, chips)
        result["device"]["busy_s"] = facts.busy_s
        result["device"]["window_s"] = facts.window_s
        for m in cell_metrics("per_layer"):
            value = metric_readers.read(m["name"], facts, root)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = facts.breakdown
    else:
        values = {"setup_s": window.setup_s,
                  workload["rate_metric"]: 1000.0 * window.window_s / max(window.rounds, 1)}
        for m in cell_metrics("end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["compared"] = {k: {"value": found[k], "limit": limits[k]} for k in limits}
    for line in correct.limit_lines(found, limits):
        print(line, file=sys.stderr)
    return result


def main(argv=None):
    _environment()
    p = argparse.ArgumentParser(prog="bench.run", description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    result = run_cell(args)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
