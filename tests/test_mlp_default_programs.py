"""The paper's MLP stays the default model, and the one-chip har-mlp cells
build the programs they built before a configuration could name another
model: ``run_federated`` on each cell's ``fl_config`` (at a small size)
lowers the same round-step program, text for text, as pinned here from the
program before ``FLConfig.model`` existed. The compared numbers of those
cells are pinned in ``bench/tests/test_bench_model_parity.py``."""

import hashlib
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import models  # noqa: E402
from bench import run as bench_run  # noqa: E402
from repro.fl import FLConfig  # noqa: E402
from repro.fl.engine import run_federated  # noqa: E402

SMALL = {
    "uci-har": dict(n_clients=6, n_features=48, samples_per_client_range=[60, 80]),
    "motionsense": dict(n_clients=4, n_features=7, samples_per_client_range=[300, 400]),
}
# sha256 of each jitted step's StableHLO text (no debug info), recorded
# from the program before FLConfig.model existed
PINNED = {
    "uci-har.acsp-int8.sync": {
        "chunk_step": "361f2c4397b1382e06f6845663ba0d0c6b25ff0d389eaea2010cb3f71cd31f64"},
    "motionsense.acsp-f32.sync": {
        "chunk_step": "619a8deff4685169e3a4b3a45fda7b5cd504e3d10b163da5d8390ad2f62fa065"},
    "uci-har.acsp-f32.async": {
        "packed_step": "d7bbf60bbc6f9a187e62d9aa1b11a679cdf5b0d7d77eae9e584ff80766209fca"},
}


def lowered_steps(cell: str, monkeypatch) -> dict:
    """``{name: sha256}`` of the steps ``run_federated`` jits and calls for
    the cell's recipe on a small dataset, two chunks (async: events) long."""
    _, _, workload, config = bench_run.load_cell(cell)
    config.update(SMALL[config["name"]])
    model = models.load(config["model"], bench_run.ROOT)
    recipe = workload["recipe"]
    rounds = 2 * recipe["scan_chunk"]
    cfg = model.fl_config(workload, config, 2_147_483_777, rounds)
    texts = {}
    jit = jax.jit

    def recording(fun, **kw):
        jitted = jit(fun, **kw)
        name = getattr(fun, "__name__", repr(fun))

        def call(*args):
            if name not in texts:
                texts[name] = hashlib.sha256(jitted.lower(*args).as_text().encode()).hexdigest()
            return jitted(*args)

        call.lower = jitted.lower
        return call

    monkeypatch.setattr(jax, "jit", recording)
    run_federated(model.make_dataset(config), cfg)
    monkeypatch.setattr(jax, "jit", jit)
    return texts


@pytest.fixture
def default_precision():
    """JAX's default matmul precision, the one the hashes were recorded
    under: a benchmark run in the same process (``bench.run``) sets its
    configuration's precision process-wide, and that changes every dot's
    text."""
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_har_mlp_cells_build_the_programs_they_built_before(cell, monkeypatch, default_precision):
    found = lowered_steps(cell, monkeypatch)
    assert found == PINNED[cell]


def test_the_mlp_is_the_default_model():
    assert FLConfig().model is None
    assert FLConfig(rounds=3, strategy="acsp-fl").model is None
