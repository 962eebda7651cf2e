"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines
``read(facts) -> float | None`` for the metric of that name in
``BENCHMARK.json``, reading a ``bench.trace_reduce.Facts``. A reader that
finds nothing to read returns None, and the metric is left out."""

from __future__ import annotations

import importlib.util
from pathlib import Path


def reader(name: str, root: Path):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read(name: str, facts, root: Path):
    value = reader(name, root)(facts)
    return None if value is None else float(value)
