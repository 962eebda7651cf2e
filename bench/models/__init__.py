"""A configuration's model: ``bench/models/<model>.py`` for the ``model`` a
configuration file names, loaded by path from the run's checkout (as the
metric readers are, so a name with a hyphen works). Everything about a run
that depends on the model comes from that module:

- ``make_dataset(config, seed=None)``: what ``run_federated`` takes;
  ``data_facts(data)``: ``n_train_valid`` and ``n_test_valid`` (per client)
  and ``n_train_rows``, in the model's own unit;
- ``fl_config(workload, config, seed, rounds)``: the program's ``FLConfig``;
- ``check_widths(opened, config)``: the program built the configuration's
  widths (``opened`` is what the run's recorder was opened with);
- ``numbers(outs, data, seed, recipe, config, decisions)``: the numbers
  compared against the workload's limits (``bench.correct``);
- work counts over the rounds of a traced window, read by per-layer
  metrics as ``facts.model.<count>(facts)`` (har-mlp: ``round_flops``,
  ``codec_bytes``, ``codec_leaves``);
- ``CANDIDATES`` and ``candidate(name, data, seed, recipe, config,
  schedule)``: the control and the planted faults of ``bench.control``.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path


def load(name: str, root: Path):
    path = Path(root) / "bench" / "models" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"the configuration's model {name!r} has no module: {path} not found")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module
