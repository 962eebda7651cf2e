"""The har-mlp model module, loaded by path as a run loads it, gives what the
benchmark's direct calls give: the generator's data bit for bit, the
``Facts`` data fields, the work counts of ``bench.flops``, and the compared
numbers of the program, the control and a planted fault, sync and async,
at uci-har and MotionSense shapes. The pinned values were recorded with the
comparison as it stood before it moved into the module, so that a later
edit to the module shows here."""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import control, flops, models
from bench import data as bench_data
from bench import run as bench_run

FIELDS = ("x_train", "y_train", "m_train", "x_test", "y_test", "m_test")
SMALL = {
    "uci-har": dict(n_clients=6, n_features=48, samples_per_client_range=[60, 80]),
    "motionsense": dict(n_clients=4, n_features=7, samples_per_client_range=[300, 400]),
}
# (n_train_rows, sum of n_train_valid, sum of n_test_valid) at data_seed 0
FACTS = {"uci-har": (246, 5996, 1984), "motionsense": (43170, 875819, 291928)}
# over the selections of ``_sel``, batch 32, two epochs; three rounds of bytes
WORK = {"uci-har": (28306415616.0, 349734240.0),
        "motionsense": (2399468703744.0, 136669248.0)}
READINGS = {
    "uci-har.acsp-int8.sync": {
        "program": {"decision_errors": 0.0, "norm_gap0": 0.0,
                    "norm_gap": 8.457363386517744e-08, "acc_gap": 8.940696716308594e-07},
        "control": {"decision_errors": 0.0, "norm_gap0": 2.5105977843545914e-06,
                    "norm_gap": 0.000651358474521964, "acc_gap": 0.0},
        "half_batch": {"decision_errors": 0.0, "norm_gap0": 0.29688755681605256,
                       "norm_gap": 0.5945649933420823, "acc_gap": 5.999999642372131},
    },
    "uci-har.acsp-f32.async": {
        "program": {"decision_errors": 0.0, "norm_gap0": 0.0, "norm_gap": 0.0, "acc_gap": 0.0},
        "control": {"decision_errors": 0.0, "norm_gap0": 2.822519945807617e-06,
                    "norm_gap": 6.814845612538705e-06, "acc_gap": 0.0},
        "half_batch": {"decision_errors": 4.0, "norm_gap0": 0.17375901439583577,
                       "norm_gap": 0.3136644787827743, "acc_gap": 6.000000149011612},
    },
    "motionsense.acsp-f32.sync": {
        "program": {"decision_errors": 0.0, "norm_gap0": 5.506103405502932e-08,
                    "norm_gap": 9.680094183444367e-08, "acc_gap": 2.473592758178711e-06},
        "control": {"decision_errors": 0.0, "norm_gap0": 9.635680959630132e-06,
                    "norm_gap": 0.008964268329220844, "acc_gap": 1.0000018179416656},
        "half_batch": {"decision_errors": 0.0, "norm_gap0": 0.5940144030855322,
                       "norm_gap": 0.5940144030855322, "acc_gap": 10.00000086426735},
    },
}


@pytest.fixture(scope="module")
def har():
    return models.load("har-mlp", bench_run.ROOT)


@pytest.fixture(scope="module")
def jax_state():
    """The threefry stream and matmul precision the pinned values were
    recorded with (a benchmark run's), restored afterwards."""
    keys = ("jax_threefry_partitionable", "jax_default_matmul_precision")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_threefry_partitionable", True)
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def _config(name, **shape):
    config = bench_run.load_json(bench_run.BENCH / "configs" / f"{name}.json")
    config.update(shape)
    return config


@pytest.fixture(scope="module", params=["uci-har", "motionsense"])
def full(request, har):
    config = _config(request.param)
    return request.param, config, har.make_dataset(config)


def _sel(c):
    sel = np.zeros((3, c), bool)
    sel[0] = True
    sel[1, ::2] = True
    sel[2, 1] = True
    return sel


@pytest.mark.parametrize("name", ["uci-har", "motionsense"])
@pytest.mark.parametrize("seed", [None, 2_147_483_659])
def test_data_is_the_generators_bit_for_bit(har, name, seed):
    config = _config(name, **SMALL[name])
    ours, theirs = har.make_dataset(config, seed), bench_data.make_dataset(config, seed)
    for f in FIELDS:
        a, b = getattr(ours, f), getattr(theirs, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert ours.n_classes == theirs.n_classes


def test_facts_data_fields(har, full):
    name, _, data = full
    got = har.data_facts(data)
    assert np.array_equal(got["n_train_valid"], np.asarray(data.m_train).sum(axis=1))
    assert np.array_equal(got["n_test_valid"], np.asarray(data.m_test).sum(axis=1))
    assert got["n_train_rows"] == data.x_train.shape[1]
    pinned = (got["n_train_rows"], int(got["n_train_valid"].sum()),
              int(got["n_test_valid"].sum()))
    assert pinned == FACTS[name]


def test_work_counts_are_bench_flops(har, full):
    name, config, data = full
    recipe = {"batch_size": 32, "epochs": 2}
    facts = SimpleNamespace(config=config, recipe=recipe, sel=_sel(config["n_clients"]),
                            rounds=3, **har.data_facts(data))
    sizes = [config["n_features"], *config["hidden"], config["n_classes"]]
    direct = flops.round_flops(sizes, facts.sel, facts.n_train_valid, facts.n_train_rows,
                               facts.n_test_valid, 32, 2)
    assert har.round_flops(facts) == direct == WORK[name][0]
    assert har.codec_bytes(facts) == flops.codec_bytes(sizes, config["n_clients"]) * 3
    assert har.codec_bytes(facts) == WORK[name][1]
    assert har.codec_leaves(facts) == 8


@pytest.mark.parametrize("cell", list(READINGS))
def test_numbers_are_the_parents(jax_state, cell):
    _, _, workload, config = bench_run.load_cell(cell)
    config.update(SMALL[config["name"]])
    if workload["recipe"]["scheduler"] == "sync":
        workload["recipe"].update(scan_chunk=4)
    else:
        workload["recipe"].update(buffer_k=2, max_concurrency=6)
    bench_run.configure_jax(config)
    found = {r.pop("candidate"): r for r in
             control.readings(workload, config, 7, ["program", "control", "half_batch"])}
    for cand, want in READINGS[cell].items():
        got = {k: found[cand][k] for k in want}
        assert got == want, cand
