"""Sharded cohort execution (repro.fl.shard): D=1 bit-identity with the
unsharded step, D>1 golden parity under forced host devices, fused-chunk
composition with donation, and per-device collective accounting.

In-process tests run at D=1 (the container's single default device) — the
sharded step over a 1-device mesh must be bit-identical to the unsharded
step on every committed golden. Multi-device tests re-exec in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(tests/_subproc.py; conftest.py forbids forcing devices in-process).

Parity contract at D>1: every per-lane number is bit-identical (lanes are
computed by the same code on the same values, just on different devices) —
only the aggregation reduction tree changes, from one flat K-lane sum to D
partial sums combined by psum. The tests assert the committed goldens hold
to <= 1 ulp of float32; on this fixture the regrouping is in fact exact
(asserted too — if XLA's CPU all-reduce ever reorders, the ulp bound is
the documented contract, exactness the current observation).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_forced
from repro.data.synthetic import make_federated_classification
from repro.fl import (
    ExecutionConfig,
    FLConfig,
    build_sharded_round_step,
    pipeline_from_config,
    run_federated,
)
from repro.fl import phases
from repro.fl.api import build_env
from test_fl_api import _GOLDEN


@pytest.fixture(scope="module")
def small_ds():
    return make_federated_classification(
        n_clients=8, n_classes=4, n_features=20,
        samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
        client_shift=0.05, class_sep=5.0, seed=1,
    )


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


def test_cohort_devices_flat_kwarg_and_validation():
    cfg = FLConfig(cohort_devices=2)
    assert cfg.cohort_devices == 2
    assert cfg.execution.cohort_devices == 2
    assert FLConfig().cohort_devices == 0
    with pytest.raises(ValueError, match="cohort_devices"):
        ExecutionConfig(cohort_devices=-2)


def test_cohort_lanes_must_divide_mesh(small_ds):
    from jax.sharding import AbstractMesh

    cfg = FLConfig(rounds=1)
    env = build_env(small_ds, cfg.seed)
    pipe = pipeline_from_config(cfg)
    # 8 lanes over a 3-way cohort axis: rejected before any compute
    mesh3 = AbstractMesh((3,), ("cohort",))
    with pytest.raises(ValueError, match="must divide"):
        build_sharded_round_step(env, pipe, cfg.execution, mesh=mesh3)
    # a mesh without the cohort axis is rejected too
    meshx = AbstractMesh((2,), ("data",))
    with pytest.raises(ValueError, match="cohort"):
        build_sharded_round_step(env, pipe, cfg.execution, mesh=meshx)


def test_custom_aggregator_without_axis_name_rejected(small_ds):
    class Opaque(phases.Aggregator):
        def aggregate(self, ctx, env):
            return ctx

    cfg = FLConfig(rounds=1, cohort_devices=1)
    env = build_env(small_ds, cfg.seed)
    pipe = dataclasses.replace(pipeline_from_config(cfg), aggregator=Opaque())
    with pytest.raises(TypeError, match="axis_name"):
        build_sharded_round_step(env, pipe, cfg.execution)


def test_sharded_step_exposes_mesh(small_ds):
    from repro.fl import api

    cfg = FLConfig(rounds=1, cohort_devices=1)
    env = build_env(small_ds, cfg.seed)
    step = api.build_round_step(env, pipeline_from_config(cfg), cfg.execution)
    assert dict(step.mesh.shape) == {"cohort": 1}
    assert step.lanes_per_device == small_ds.n_clients


def test_manifest_records_cohort_mesh(small_ds, tmp_path):
    from repro.obs import RunRecorder

    rec = RunRecorder(out_dir=str(tmp_path / "run"), echo=False)
    run_federated(small_ds, FLConfig(rounds=2, epochs=1, cohort_devices=1),
                  recorder=rec)
    import json

    m = json.load(open(tmp_path / "run" / "manifest.json"))
    assert m["mesh"] == {"axis_names": ["cohort"], "shape": [1], "devices": 1}
    # unsharded runs record no mesh
    rec2 = RunRecorder(out_dir=str(tmp_path / "run2"), echo=False)
    run_federated(small_ds, FLConfig(rounds=2, epochs=1), recorder=rec2)
    m2 = json.load(open(tmp_path / "run2" / "manifest.json"))
    assert m2["mesh"] is None


# ---------------------------------------------------------------------------
# D=1 bit-identity (in-process, single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_sharded_d1_bit_identical_goldens(small_ds, name):
    """The sharded step over a 1-device cohort mesh reproduces every
    committed golden trajectory bit-for-bit (incl. int8 EF and FT)."""
    gold = _GOLDEN[name]
    h = run_federated(
        small_ds, FLConfig(rounds=5, epochs=1, cohort_devices=1, **gold["cfg"])
    )
    got = np.asarray(h.accuracy_mean, np.float32)
    want = np.frombuffer(bytes.fromhex(gold["acc_hex"]), np.dtype("<f4"))
    np.testing.assert_array_equal(got, want)
    sel = ["".join("1" if b else "0" for b in row) for row in np.asarray(h.selected)]
    assert sel == gold["selected"]


def test_sharded_d1_cohort_k_lt_c_bit_identical(small_ds):
    """K < C gathered execution stays bit-identical under the 1-device
    mesh — the gather/scatter plane is outside the shard_map."""
    base = dict(strategy="poc", fraction=0.5, rounds=4, epochs=1,
                cohort_size=4, codec="int8")
    hs = run_federated(small_ds, FLConfig(cohort_devices=1, **base))
    hu = run_federated(small_ds, FLConfig(**base))
    for f in hs._fields:
        a, b = getattr(hs, f), getattr(hu, f)
        if a is None:
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f)


def test_sharded_d1_full_history_identical(small_ds):
    """Every FLHistory field (not just accuracy) matches the unsharded
    run, chunk-fused and per-round."""
    base = dict(rounds=6, epochs=1, codec="int8")
    hu = run_federated(small_ds, FLConfig(**base))
    for chunk in (1, 3):
        hs = run_federated(
            small_ds, FLConfig(cohort_devices=1, scan_chunk=chunk, **base)
        )
        for f in hs._fields:
            a, b = getattr(hs, f), getattr(hu, f)
            if a is None:
                continue
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"{f} (chunk={chunk})"
            )


# ---------------------------------------------------------------------------
# D > 1: golden parity, chunk fusion, donation, collectives (subprocess)
# ---------------------------------------------------------------------------

_PARITY_BODY = """
import numpy as np
from repro.data.synthetic import make_federated_classification
from repro.fl import FLConfig, run_federated
from test_fl_api import _GOLDEN

D = {d}
ds = make_federated_classification(
    n_clients=8, n_classes=4, n_features=20,
    samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
    client_shift=0.05, class_sep=5.0, seed=1,
)
for name, gold in sorted(_GOLDEN.items()):
    h = run_federated(ds, FLConfig(rounds=5, epochs=1, cohort_devices=D, **gold["cfg"]))
    got = np.asarray(h.accuracy_mean, np.float32)
    want = np.frombuffer(bytes.fromhex(gold["acc_hex"]), np.dtype("<f4")).copy()
    ulp = np.abs(got.view(np.int32).astype(np.int64)
                 - want.view(np.int32).astype(np.int64)).max()
    assert ulp <= 1, (name, ulp)          # documented D>1 contract
    assert np.array_equal(got, want), name  # current observation: exact
    sel = ["".join("1" if b else "0" for b in row) for row in np.asarray(h.selected)]
    assert sel == gold["selected"], name
print("PARITY OK D=", D)
"""


@pytest.mark.multidevice
@pytest.mark.parametrize("d", [2, 4, 8])
def test_golden_parity_forced_devices(d):
    out = run_forced(_PARITY_BODY.format(d=d), n_devices=d)
    assert f"PARITY OK D= {d}" in out


_CHUNK_BODY = """
import numpy as np, jax, jax.numpy as jnp
from repro.data.synthetic import make_federated_classification
from repro.fl import FLConfig, run_federated

ds = make_federated_classification(
    n_clients=8, n_classes=4, n_features=20,
    samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
    client_shift=0.05, class_sep=5.0, seed=1,
)
# fused chunks scan the sharded step unchanged: identical whole-history
base = dict(rounds=6, epochs=1, codec="int8", cohort_devices=2)
h1 = run_federated(ds, FLConfig(**base, scan_chunk=1))
h3 = run_federated(ds, FLConfig(**base, scan_chunk=3))
for f in h1._fields:
    a, b = getattr(h1, f), getattr(h3, f)
    if a is None:
        continue
    assert np.array_equal(np.asarray(a), np.asarray(b)), f
# K < C cohort, sharded D=2 vs unsharded
kc = dict(strategy="poc", fraction=0.5, rounds=4, epochs=1, cohort_size=4)
hs = run_federated(ds, FLConfig(cohort_devices=2, **kc))
hu = run_federated(ds, FLConfig(**kc))
assert np.array_equal(np.asarray(hs.accuracy_mean), np.asarray(hu.accuracy_mean))
print("CHUNK OK")
"""


@pytest.mark.multidevice
def test_chunked_sharded_and_k_lt_c_d2():
    assert "CHUNK OK" in run_forced(_CHUNK_BODY, n_devices=2)


_DONATION_BODY = """
import numpy as np, jax, jax.numpy as jnp
from repro.data.synthetic import make_federated_classification
from repro.fl import FLConfig, api
from repro.fl.sched import _setup_run
from repro.launch.collectives import collective_bytes
from repro.models.mlp import mlp_accuracy, mlp_loss

ds = make_federated_classification(
    n_clients=8, n_classes=4, n_features=20,
    samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
    client_shift=0.05, class_sep=5.0, seed=1,
)
cfg = FLConfig(rounds=4, epochs=1, codec="int8", cohort_devices=2)
su = _setup_run(ds, cfg, None, mlp_loss, mlp_accuracy, None, None, None)
step = api.build_round_step(su.env, su.pipeline, cfg.execution)
assert dict(step.mesh.shape) == {"cohort": 2}
assert step.lanes_per_device == 4

state = su.initial_state()
chunk = api.build_chunk_step(step, 2)
ts = jnp.arange(2, dtype=jnp.int32)
# per-device collective traffic is visible in the optimized SPMD HLO: the
# aggregator's psum lowers to all-reduce ops
stats = collective_bytes(chunk.lower(state, ts).compile().as_text())
assert stats.get("all-reduce", 0) > 0, stats
leaves = jax.tree.leaves(state)
new_state, outs = chunk(state, ts)
jax.block_until_ready(new_state)
# donation: every input slab buffer was consumed in place
assert all(l.is_deleted() for l in leaves)
print("DONATION OK all-reduce", stats["all-reduce"])
"""


@pytest.mark.multidevice
def test_donation_and_collective_bytes_d2():
    out = run_forced(_DONATION_BODY, n_devices=2)
    assert "DONATION OK" in out
