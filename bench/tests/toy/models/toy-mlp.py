"""A model family of the benchmark's contract test: the program's default
MLP under FedAvg of every client, with no personalization and a float32
uplink, on data and against a reference of its own.

It compares round 0 alone, where every client trains from the same initial
model: ``norm_gap0``, the widest gap between a client's uplink update norm
and the reference's, over the larger of the reference's norm and the
median norm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import Data

CANDIDATES = ("control", "unchanged")


def _sizes(config):
    return [config["n_features"], *config["hidden"], config["n_classes"]]


def make_dataset(config, seed=None):
    """Gaussian features labelled by a random linear map: every row valid."""
    rng = np.random.default_rng(config["data_seed"] if seed is None else seed)
    c, f, k = config["n_clients"], config["n_features"], config["n_classes"]
    w = rng.normal(size=(f, k))

    def rows(n):
        x = rng.normal(size=(c, n, f)).astype(np.float32)
        return x, np.argmax(x @ w, axis=-1).astype(np.int32), np.ones((c, n), bool)

    return Data(*rows(config["train_rows"]), *rows(config["test_rows"]), k, config["name"])


def data_facts(data):
    return {"n_train_valid": data.m_train.sum(axis=1), "n_train_rows": data.x_train.shape[1],
            "n_test_valid": data.m_test.sum(axis=1)}


def fl_config(workload, config, seed, rounds):
    from repro.fl.api import FLConfig

    r = workload["recipe"]
    return FLConfig(strategy="fedavg", fraction=1.0, personalization="none", codec="float32",
                    rounds=rounds, epochs=r["epochs"], batch_size=r["batch_size"], lr=r["lr"],
                    seed=seed, scheduler=r["scheduler"], eval_every=1, scan_chunk=r["scan_chunk"])


def check_widths(opened, config):
    built = [int(x) for x in np.diff(np.asarray(opened["clock"].params_prefix))]
    s = _sizes(config)
    want = [fi * fo + fo for fi, fo in zip(s[:-1], s[1:])]
    if built != want:
        raise SystemExit(f"program built layers of {built} parameters, configuration says {want}")


# -- the reference ---------------------------------------------------------------

def _init(seed, sizes):
    key = jax.random.split(jax.random.PRNGKey(seed))[0]
    params = []
    for fi, fo in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (fi, fo), jnp.float32) * jnp.sqrt(2.0 / fi)
        params.append((w, jnp.zeros((fo,), jnp.float32)))
    return params


def _loss(params, x, y, dtype):
    h = x
    for i, (w, b) in enumerate(params):
        h = jnp.matmul(h.astype(dtype), w.astype(dtype), precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32) + b
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(h), y[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("epochs", "batch", "lr", "dtype"))
def _round0_norms(g0, x, y, *, epochs, batch, lr, dtype):
    """Each client's update norm after local SGD from ``g0``."""

    def client(x, y):
        p = g0
        for _ in range(epochs):
            for i in range(0, x.shape[0] - batch + 1, batch):
                g = jax.grad(_loss)(p, x[i:i + batch], y[i:i + batch], dtype)
                p = jax.tree.map(lambda a, d: a - lr * d, p, g)
        d = jax.tree.map(lambda a, b: jnp.sum((a - b) ** 2), p, g0)
        return jnp.sqrt(sum(jax.tree.leaves(d)))

    return jax.vmap(client)(x, y)


def _reference(data, seed, recipe, config, dtype=jnp.float32):
    return np.asarray(_round0_norms(
        _init(seed, _sizes(config)), jnp.asarray(data.x_train), jnp.asarray(data.y_train),
        epochs=recipe["epochs"], batch=recipe["batch_size"], lr=recipe["lr"], dtype=dtype))


def numbers(outs, data, seed, recipe, config, decisions=None):
    ref = _reference(data, seed, recipe, config).astype(np.float64)
    gap = np.abs(np.asarray(outs["norm"])[0] - ref) / np.maximum(ref, np.median(ref))
    return {"norm_gap0": float(np.max(gap))}


def candidate(name, data, seed, recipe, config, schedule=None):
    """``control``: the reference with bfloat16 matmuls; ``unchanged``:
    local training returning its input."""
    if name == "control":
        norm = _reference(data, seed, recipe, config, jnp.bfloat16)
    elif name == "unchanged":
        norm = np.zeros((config["n_clients"],), np.float32)
    else:
        raise SystemExit(f"toy-mlp has no candidate {name!r}")
    return {"norm": norm[None]}, None


# -- work ------------------------------------------------------------------------

def round_flops(facts):
    """Training FLOPs of the window's rounds: 6 a weight a trained row."""
    s = _sizes(facts.config)
    weights = sum(fi * fo for fi, fo in zip(s[:-1], s[1:]))
    trained = (np.asarray(facts.sel) * np.asarray(facts.n_train_valid)).sum()
    return float(6 * weights * trained * facts.recipe["epochs"])
