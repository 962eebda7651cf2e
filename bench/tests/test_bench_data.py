"""The benchmark's generator copy is bitwise the program's generator, and
padding only widens the slabs with masked rows."""

import numpy as np
import pytest

from bench import data as bench_data
from bench import run as bench_run

FIELDS = ("x_train", "y_train", "m_train", "x_test", "y_test", "m_test")


@pytest.mark.parametrize("name", ["uci-har", "motionsense"])
@pytest.mark.parametrize("seed", [0, 2_147_483_659])
def test_bitwise_equal_to_the_program_generator(name, seed):
    from repro.data.har import make_har_dataset

    config = bench_run.load_json(bench_run.BENCH / "configs" / f"{name}.json")
    ours = bench_data.make_dataset(config, seed, pad=False)
    theirs = make_har_dataset(name, seed=seed)
    for f in FIELDS:
        a, b = getattr(ours, f), getattr(theirs, f)
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert np.array_equal(a, b), f
    assert ours.n_classes == theirs.n_classes
    assert np.array_equal(ours.n_samples, theirs.n_samples)

    padded = bench_data.make_dataset(config, seed)
    n_tr, n_te = bench_data.padded_widths(config["samples_per_client_range"])
    assert padded.x_train.shape[1] == n_tr and padded.x_test.shape[1] == n_te
    for f in FIELDS:
        a, b = getattr(padded, f), getattr(theirs, f)
        assert np.array_equal(a[:, : b.shape[1]], b), f
        assert not a[:, b.shape[1]:].any(), f


def test_padded_widths_of_both_configurations():
    assert bench_data.padded_widths((224, 327)) == (246, 81)
    assert bench_data.padded_widths((40804, 57559)) == (43170, 14389)
