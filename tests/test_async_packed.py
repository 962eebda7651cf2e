"""The async step's packed boundary (``repro.fl.sched.AsyncPacking``): the
event's host inputs go to the device as one int32 vector and the step's
``out`` tree comes back as one uint32 vector. The packing must be exact:
the unpacked values, the step's state and its outputs match the unpacked
step bit for bit, and the loop hands the compiled step ``state`` plus one
host array an event."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_federated_classification
from repro.fl import FLConfig, run_federated, sched
from repro.fl.sched import AsyncPacking

M, C = 5, 7


@pytest.fixture(scope="module")
def har_ds():
    """UCI-HAR's shape (Table 2): 30 clients, 561 features, 6 classes,
    224-327 samples a client."""
    return make_federated_classification(
        n_clients=30, n_classes=6, n_features=561,
        samples_per_client_range=(224, 327), dirichlet_alpha=100.0,
        client_shift=0.05, class_sep=6.0, seed=0,
    )


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _assert_same_bits(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(_bits(x), _bits(y))


def _f32(*words) -> np.ndarray:
    return np.asarray(words, np.uint32).view(np.float32)


I32 = np.iinfo(np.int32)
OUT_CASES = {
    # quiet and signalling NaN payloads of both signs, -0.0, infinities,
    # the smallest subnormal
    "nan_payloads": {
        "acc": _f32(0x7FC00001, 0xFFBFFFFF, 0x7F800001, 0x80000000, 0x7F800000, 0xFF800000, 1),
        "staleness_mean": _f32(0xFFC12345)[0],
    },
    "bools": {
        "selected": np.array([True, False, False, True, True, False, True]),
        "dispatched": np.array([False, True, False, False, True]),
        "flag": np.array(True),
    },
    "int32_extremes": {
        "slot_client": np.array([I32.min, -1, 0, 1, I32.max], np.int32),
        "rejected": np.int32(I32.min),
        "pms": np.array([I32.max] * C, np.int32),
    },
    "mixed": {
        "acc": np.linspace(-1.0, 1.0, C, dtype=np.float32),
        "selected": np.arange(C) % 2 == 0,
        "tx_params": np.float32(-0.0),
        "client_pms": np.arange(C, dtype=np.int32) - 3,
        "rejected": np.int32(I32.max),
    },
}


@pytest.mark.parametrize("case", sorted(OUT_CASES))
def test_pack_out_round_trips_bits(case):
    out = OUT_CASES[case]
    packing = AsyncPacking(M, C, faulty=False)
    flat = jax.jit(packing.pack_out)(jax.tree.map(jnp.asarray, out))
    assert flat.dtype == jnp.uint32 and flat.ndim == 1
    _assert_same_bits(packing.unpack_out(jax.device_get(flat)), out)


def test_pack_out_refuses_other_widths():
    with pytest.raises(TypeError, match="int8"):
        AsyncPacking(M, C, faulty=False).pack_out({"x": jnp.zeros((3,), jnp.int8)})


def test_unpack_out_needs_a_traced_step():
    with pytest.raises(RuntimeError):
        AsyncPacking(M, C, faulty=False).unpack_out(np.zeros((4,), np.uint32))


@pytest.mark.parametrize("faulty", [False, True], ids=["faults_off", "faults_on"])
def test_pack_in_round_trips_the_step_arguments(faulty):
    rng = np.random.default_rng(0)
    host = dict(
        t=123456789, force=True,
        land=rng.random(M) < 0.5, staleness=np.array([0, 3, I32.max, 1, 7], np.int32),
        active=rng.random(M) < 0.5, idle_now=rng.random(C) < 0.5,
        corrupt=np.array([0, 1, 2, 3, 0], np.int32) if faulty else None,
    )
    packing = AsyncPacking(M, C, faulty)
    buf = packing.pack_in(**host)
    assert buf.dtype == np.int32 and buf.shape == (packing.size,)
    assert packing.size == 2 + 3 * M + C + (M if faulty else 0)
    got = jax.jit(packing.unpack_in)(buf)
    # the order and dtypes of build_async_step's arguments after state
    order = ["t", "land", "staleness", "active", "idle_now", "force"]
    want = [jnp.asarray(host[k]) for k in order + (["corrupt"] if faulty else [])]
    _assert_same_bits(list(got), want)


def _run_spied(ds, monkeypatch, **kw):
    """Run the async loop on the uci-har recipe with a spy on the packed
    step: each call's arguments and results, and the raw host inputs that
    were packed."""
    cfg = FLConfig(rounds=8, epochs=2, batch_size=32, lr=0.1, seed=3,
                   strategy="acsp-fl", decay=0.01, personalization="dld",
                   codec="float32", remainder="drop", scheduler="async",
                   buffer_k=10, max_concurrency=30, **kw)
    calls, packed_inputs, built = [], [], {}
    build = sched.build_packed_async_step

    def spied_build(env, pipeline, m, faults=None):
        step, packing = build(env, pipeline, m, faults=faults)
        built.update(env=env, pipeline=pipeline, faults=faults, packing=packing)
        pack_in = packing.pack_in

        def spied_pack_in(*args):
            # copies: the loop updates some of these arrays in place later
            packed_inputs.append(tuple(np.copy(a) if isinstance(a, np.ndarray) else a
                                       for a in args))
            return pack_in(*args)

        def spied_step(*args):
            result = step(*args)
            calls.append((args, result))
            return result

        packing.pack_in = spied_pack_in
        return spied_step, packing

    monkeypatch.setattr(sched, "build_packed_async_step", spied_build)
    run_federated(ds, cfg)
    return calls, packed_inputs, built


@pytest.mark.parametrize(
    "faults", [{}, dict(corrupt_rate=0.3, dropout_rate=0.1)], ids=["faults_off", "faults_on"]
)
def test_packed_step_matches_unpacked_step(har_ds, monkeypatch, faults):
    calls, packed_inputs, built = _run_spied(har_ds, monkeypatch, **faults)
    assert len(calls) >= 6 and len(calls) == len(packed_inputs)
    faulty = bool(faults)
    direct = jax.jit(sched.build_async_step(
        built["env"], built["pipeline"], faults=built["faults"] if faulty else None))
    corrupted = 0
    for ((state, _), (new_state, flat)), host in zip(calls, packed_inputs):
        t, force, land, staleness, active, idle_now, corrupt = host
        # the arguments as the loop staged them before the packing
        args = (state, jnp.asarray(t), jnp.asarray(land), jnp.asarray(staleness),
                jnp.asarray(active), jnp.asarray(idle_now), jnp.asarray(force))
        if faulty:
            args += (jnp.asarray(corrupt),)
            corrupted += int(np.sum(np.asarray(corrupt)[land] != 0))
        else:
            assert corrupt is None
        want_state, want_out = direct(*args)
        _assert_same_bits(new_state, want_state)
        got_out = built["packing"].unpack_out(jax.device_get(flat))
        _assert_same_bits(got_out, jax.device_get(want_out))
    if faulty:
        assert corrupted > 0  # the corruption lane carried live kinds


def test_step_gets_state_and_one_host_array_an_event(har_ds, monkeypatch):
    calls, _, _ = _run_spied(har_ds, monkeypatch)
    assert len(calls) == 8
    for args, _ in calls:
        assert len(args) == 2
        state, buf = args
        assert isinstance(state, sched.AsyncState)
        assert type(buf) is np.ndarray and buf.dtype == np.int32 and buf.ndim == 1
