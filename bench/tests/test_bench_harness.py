"""The window driving a tiny ``run_federated`` on the CPU: set-up ends at
the second hook, the window closes at a hook, and rounds are counted to
that hook."""

import time

import numpy as np
import pytest

from bench import data as bench_data
from bench import harness


@pytest.fixture(scope="module")
def tiny():
    return bench_data.make_classification(
        n_clients=4, n_classes=3, n_features=8, samples_per_client_range=(40, 50), seed=3
    )


def _cfg(**kw):
    from repro.fl.api import FLConfig

    return FLConfig(strategy="acsp-fl", personalization="dld", rounds=10 ** 6,
                    epochs=1, batch_size=8, **kw)


def _run(data, cfg, seconds):
    from repro.fl.engine import run_federated

    started = time.perf_counter()
    window = harness.Window(seconds, started)
    with pytest.raises(harness.WindowClosed):
        run_federated(data, cfg, recorder=window)
    return started, window


def test_sync_window(tiny):
    started, w = _run(tiny, _cfg(scan_chunk=3), 0.5)
    assert w.t_first == w.hooks[1][0] and w.setup_s == w.t_first - started
    assert w.window_s >= 0.5
    # every hook after the second adds a whole chunk to the window
    assert len(w.hooks) >= 3 and w.rounds == 3 * (len(w.hooks) - 2)
    assert w.early_outs["acc"].shape == (6, 4)
    assert w.early_outs["sel"][0].all()
    assert w.t_last == w.hooks[-1][0]
    assert w.failed == 0
    # the scheduler's phases were timed through the recorder's profiler
    names = {s[0] for s in w.profiler.spans}
    assert {"compile", "dispatch", "device_get"} <= names


def test_window_of_zero_closes_once_the_first_rounds_are_kept(tiny):
    _, w = _run(tiny, _cfg(scan_chunk=1), 0.0)
    assert len(w.hooks) == 4 and w.rounds == 2
    assert w.early_outs["acc"].shape == (4, 4)


def test_async_window(tiny):
    _, w = _run(tiny, _cfg(scheduler="async", buffer_k=2, max_concurrency=4), 0.3)
    assert w.rounds == len(w.hooks) - 2 >= 1
    assert w.early_outs["acc"].shape == (4, 4)
    assert w.window_s >= 0.3
    assert np.isfinite(w.early_outs["norm"]).all()
    # warm-start dispatch, then each event's landing and re-dispatch
    kinds = [d[0] for d in w.decisions]
    assert kinds[:3] == ["dispatch", "land", "dispatch"]
    assert kinds.count("land") == 4
