"""The benchmark's copy of the HAR stand-in generator.

A copy of ``repro.data.synthetic.make_federated_classification`` (the
per-client loop path) with the dataset rows of ``repro.data.har``, so the
benchmark makes its inputs itself and a change to the program's generator
cannot change what is measured. ``tests/test_data.py`` holds it bitwise
equal to the program's generator.

``pad`` widens the train and test slabs to the widest that the dataset's
sample range can produce, with masked-out rows. Every seed then gives the
same shapes, the same number of local SGD batches and the same compiled
programs; only the values differ.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# dataset rows of repro.data.har.DATASETS (paper Table 2)
DATASETS = {
    "uci-har": dict(
        n_clients=30, n_classes=6, n_features=561,
        samples_per_client_range=(224, 327), dirichlet_alpha=100.0,
        client_shift=0.05,
    ),
    "motionsense": dict(
        n_clients=24, n_classes=6, n_features=7,
        samples_per_client_range=(40804, 57559), dirichlet_alpha=100.0,
        client_shift=0.1, class_sep=1.6,
    ),
}

TEST_FRACTION = 0.25


@dataclasses.dataclass
class Data:
    """Stacked federated dataset; the attributes ``run_federated`` reads."""

    x_train: np.ndarray  # (C, N_tr, F) float32
    y_train: np.ndarray  # (C, N_tr) int32
    m_train: np.ndarray  # (C, N_tr) bool
    x_test: np.ndarray   # (C, N_te, F) float32
    y_test: np.ndarray   # (C, N_te) int32
    m_test: np.ndarray   # (C, N_te) bool
    n_classes: int
    name: str = "synthetic"

    @property
    def n_clients(self) -> int:
        return self.x_train.shape[0]

    @property
    def n_features(self) -> int:
        return self.x_train.shape[-1]

    @property
    def n_samples(self) -> np.ndarray:
        return self.m_train.sum(axis=1).astype(np.int32)


def padded_widths(samples_per_client_range, test_fraction=TEST_FRACTION):
    """(train rows, test rows) of the widest client the range allows."""
    hi = samples_per_client_range[1]
    te = max(1, int(hi * test_fraction))
    return hi - te, te


def make_classification(
    n_clients, n_classes, n_features, samples_per_client_range,
    dirichlet_alpha=100.0, client_shift=0.05, class_sep=6.0,
    test_fraction=TEST_FRACTION, seed=0, name="synthetic", pad=False,
) -> Data:
    rng = np.random.default_rng(seed)
    lo, hi = samples_per_client_range
    means = rng.normal(0.0, class_sep / np.sqrt(n_features), (n_classes, n_features))
    counts = rng.integers(lo, hi + 1, size=n_clients)
    props = rng.dirichlet(np.full(n_classes, dirichlet_alpha), size=n_clients)
    te_counts = np.maximum(1, (counts * test_fraction).astype(int))
    tr_counts = counts - te_counts
    if pad:
        n_tr, n_te = padded_widths(samples_per_client_range, test_fraction)
    else:
        n_tr, n_te = int(tr_counts.max()), int(te_counts.max())

    x_tr = np.zeros((n_clients, n_tr, n_features), np.float32)
    y_tr = np.zeros((n_clients, n_tr), np.int32)
    m_tr = np.zeros((n_clients, n_tr), bool)
    x_te = np.zeros((n_clients, n_te, n_features), np.float32)
    y_te = np.zeros((n_clients, n_te), np.int32)
    m_te = np.zeros((n_clients, n_te), bool)
    for i in range(n_clients):
        n_i = int(counts[i])
        labels = rng.choice(n_classes, size=n_i, p=props[i])
        feats = means[labels] + rng.normal(0.0, 1.0, (n_i, n_features))
        scale = 1.0 + client_shift * rng.normal(0.0, 1.0, (n_features,))
        bias = client_shift * rng.normal(0.0, 1.0, (n_features,))
        mix = np.eye(n_features) + client_shift * 0.2 * rng.normal(
            0.0, 1.0 / np.sqrt(n_features), (n_features, n_features)
        )
        feats = ((feats * scale) @ mix + bias).astype(np.float32)
        t_i, e_i = int(tr_counts[i]), int(te_counts[i])
        x_tr[i, :t_i], y_tr[i, :t_i], m_tr[i, :t_i] = feats[:t_i], labels[:t_i], True
        x_te[i, :e_i], y_te[i, :e_i], m_te[i, :e_i] = feats[t_i:n_i], labels[t_i:n_i], True
    return Data(x_tr, y_tr, m_tr, x_te, y_te, m_te, n_classes, name)


def make_dataset(config: dict, seed: int | None = None, pad: bool = True) -> Data:
    """The dataset a configuration file describes, from ``seed`` or by
    default from the configuration's own ``data_seed``."""
    if seed is None:
        seed = config["data_seed"]
    spec = dict(DATASETS[config["dataset"]])
    for key in ("n_clients", "n_classes", "n_features"):
        spec[key] = config[key]
    spec["samples_per_client_range"] = tuple(config["samples_per_client_range"])
    spec["dirichlet_alpha"] = config["dirichlet_alpha"]
    return make_classification(seed=seed, name=config["dataset"], pad=pad, **spec)
