"""Observability layer (repro.obs): recorder stream parity across scan_chunk
sizes and reruns, Perfetto trace schema + simulated-clock exactness,
profiling hooks, bit-identity of recorded vs unrecorded runs (including a
golden config), and the manifest/run-log plumbing."""

import json
import os

import jax
import numpy as np
import pytest

from repro.data import make_federated_classification
from repro.fl import FLConfig, run_federated
from repro.obs import (
    Profiler,
    RunRecorder,
    TraceBuilder,
    environment_snapshot,
    validate_trace,
    validate_trace_file,
)

from test_fl_api import _GOLDEN

SERVER_LATENCY_S = 0.01  # CommModel default the async event clock pays


@pytest.fixture(scope="module")
def small_ds():
    return make_federated_classification(
        n_clients=8, n_classes=4, n_features=20,
        samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
        client_shift=0.05, class_sep=5.0, seed=1,
    )


def _record(ds, cfg, out_dir, **rec_kw):
    rec = RunRecorder(str(out_dir), echo=False, **rec_kw)
    h = run_federated(ds, cfg, recorder=rec)
    return h, str(out_dir)


# ---------------------------------------------------------------------------
# stream parity: identical runs -> identical records
# ---------------------------------------------------------------------------


def test_metrics_stream_identical_across_scan_chunks(small_ds, tmp_path):
    """The recorder consumes stacked chunk leaves, but the emitted JSONL is
    the per-round stream — byte-identical at every scan_chunk size."""
    blobs = {}
    for chunk in (1, 2, 7):
        cfg = FLConfig(rounds=7, epochs=1, scan_chunk=chunk)
        _, out = _record(small_ds, cfg, tmp_path / f"chunk{chunk}")
        with open(os.path.join(out, "metrics.jsonl"), "rb") as f:
            blobs[chunk] = f.read()
    assert blobs[1] == blobs[2] == blobs[7]
    rows = [json.loads(line) for line in blobs[1].splitlines()]
    assert [r["t"] for r in rows] == list(range(7))


def test_rerun_identical_record_including_trace(small_ds, tmp_path):
    """Same config, fresh recorder: metrics AND trace bytes reproduce (the
    record carries no timestamps or other run-local noise)."""
    cfg = FLConfig(rounds=5, epochs=1, scan_chunk=2)
    outs = []
    for tag in ("a", "b"):
        _, out = _record(small_ds, cfg, tmp_path / tag, trace=True)
        outs.append(out)
    for fname in ("metrics.jsonl", "trace.json"):
        with open(os.path.join(outs[0], fname), "rb") as fa, \
             open(os.path.join(outs[1], fname), "rb") as fb:
            assert fa.read() == fb.read(), fname


def test_sync_metrics_match_history(small_ds, tmp_path):
    cfg = FLConfig(rounds=6, epochs=1, scan_chunk=3)
    h, out = _record(small_ds, cfg, tmp_path / "rec")
    rows = [json.loads(line) for line in open(os.path.join(out, "metrics.jsonl"))]
    assert len(rows) == 6
    for t, r in enumerate(rows):
        assert r["acc_mean"] == pytest.approx(float(h.accuracy_mean[t]), abs=0)
        assert r["n_selected"] == int(h.selected[t].sum())
        assert r["sim_clock_s"] == float(h.sim_clock[t])  # exact, == np.cumsum
        assert r["round_time_s"] == float(h.round_time[t])
        assert r["staleness_mean"] == 0.0
        assert r["in_flight"] == int(h.in_flight[t])  # == lanes, always set


# ---------------------------------------------------------------------------
# bit-identity: recording must not perturb the trajectory
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["acsp-fl+dld+float32", "acsp-fl+dld+int8"])
def test_recorded_run_bit_identical_to_golden(small_ds, tmp_path, name):
    """Recording a golden-config run reproduces the committed golden
    trajectory exactly — observation is pure host-side."""
    gold = _GOLDEN[name]
    cfg = FLConfig(rounds=5, epochs=1, **gold["cfg"])
    h, _ = _record(small_ds, cfg, tmp_path / "rec", trace=True)
    got_acc = np.asarray(h.accuracy_mean, np.float32)
    want_acc = np.frombuffer(bytes.fromhex(gold["acc_hex"]), np.dtype("<f4"))
    np.testing.assert_array_equal(got_acc, want_acc)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_recorded_history_equals_unrecorded(small_ds, tmp_path, mode):
    kw = dict(scheduler=mode)
    if mode == "async":
        kw.update(buffer_k=2, heterogeneity=1.0)
    cfg = FLConfig(rounds=6, epochs=1, **kw)
    h_rec, _ = _record(small_ds, cfg, tmp_path / "rec", trace=True, profile=True)
    h = run_federated(small_ds, cfg)
    for a, b in zip(h_rec, h):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# trace: schema validity + simulated-clock exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_trace_schema_valid(small_ds, tmp_path, mode):
    kw = dict(scheduler=mode)
    if mode == "async":
        kw.update(buffer_k=2, heterogeneity=1.0)
    cfg = FLConfig(rounds=5, epochs=1, scan_chunk=2 if mode == "sync" else 1, **kw)
    _, out = _record(small_ds, cfg, tmp_path / mode, trace=True)
    path = os.path.join(out, "trace.json")
    assert validate_trace_file(path, population=small_ds.n_clients) == []
    trace = json.load(open(path))
    phs = {e["ph"] for e in trace["traceEvents"]}
    assert "M" in phs and "B" in phs and "E" in phs and "i" in phs
    # client lanes stay within the population
    client_tids = {e["tid"] for e in trace["traceEvents"]
                   if e["pid"] == 1 and e["ph"] in ("B", "E")}
    assert client_tids <= set(range(small_ds.n_clients))


def test_async_trace_simulated_clock_exact(small_ds, tmp_path):
    """The acceptance contract: under a straggler tail, every aggregation
    instant sits at the exact simulated clock the history reports, and the
    landed clients' upload spans end at the queue's finish times (max
    finish + server latency == sim_clock, bit-equal)."""
    cfg = FLConfig(rounds=10, epochs=1, scheduler="async", buffer_k=2,
                   heterogeneity=1.0)
    h, out = _record(small_ds, cfg, tmp_path / "rec", trace=True)
    trace = json.load(open(os.path.join(out, "trace.json")))
    aggs = [e for e in trace["traceEvents"]
            if e["ph"] == "i" and e["name"] == "aggregate"]
    assert len(aggs) == len(h.sim_clock) == 10
    for a in aggs:
        t = a["args"]["t"]
        assert a["args"]["clock_s"] == float(h.sim_clock[t])
        assert max(a["args"]["finish_s"]) + SERVER_LATENCY_S == float(h.sim_clock[t])
        assert a["args"]["n_landed"] == int(h.selected[t].sum())
    # upload spans close exactly at the finish times the instants report
    ends = {}
    for e in trace["traceEvents"]:
        if e["ph"] == "E" and e["pid"] == 1 and e["name"] == "upload":
            ends.setdefault(e["tid"], []).append(e["ts"] / 1e6)
    for a in aggs:
        for c, f in zip(a["args"]["landed"], a["args"]["finish_s"]):
            assert any(abs(end - f) < 1e-12 for end in ends.get(c, [])), (c, f)


def test_sync_trace_round_spans_cover_sim_clock(small_ds, tmp_path):
    cfg = FLConfig(rounds=6, epochs=1, scan_chunk=3)
    h, out = _record(small_ds, cfg, tmp_path / "rec", trace=True)
    trace = json.load(open(os.path.join(out, "trace.json")))
    rounds = [e for e in trace["traceEvents"]
              if e["pid"] == 0 and e["name"] == "round" and e["ph"] == "E"]
    assert len(rounds) == 6
    # each round span ends at the cumulative simulated clock (in µs)
    for t, e in enumerate(rounds):
        assert e["ts"] == pytest.approx(float(h.sim_clock[t]) * 1e6, rel=1e-12)


def test_validate_trace_catches_malformed():
    assert validate_trace("not a dict") != []
    assert validate_trace({"traceEvents": "nope"}) != []
    # unmatched B, bad phase, ts going backwards, foreign client lane
    tb = TraceBuilder()
    tb.client_lane(3)
    tb.begin("work", 1, 3, 1.0)
    errs = validate_trace(tb.to_obj())
    assert any("unclosed" in e for e in errs)
    tb.end("work", 1, 3, 2.0)
    assert validate_trace(tb.to_obj()) == []
    assert validate_trace(tb.to_obj(), population=3) != []  # lane 3 out of range
    obj = tb.to_obj()
    obj["traceEvents"].append({"ph": "Z", "name": "x", "pid": 0, "tid": 0, "ts": 0})
    assert any("phase" in e for e in errs) or validate_trace(obj) != []


def test_validate_trace_file_missing(tmp_path):
    errs = validate_trace_file(str(tmp_path / "nope.json"))
    assert len(errs) == 1


# ---------------------------------------------------------------------------
# manifest / run.log / profile
# ---------------------------------------------------------------------------


def test_manifest_fields_and_stable_run_id(small_ds, tmp_path):
    cfg = FLConfig(rounds=4, epochs=1)
    h, out_a = _record(small_ds, cfg, tmp_path / "a")
    _, out_b = _record(small_ds, cfg, tmp_path / "b")
    man_a = json.load(open(os.path.join(out_a, "manifest.json")))
    man_b = json.load(open(os.path.join(out_b, "manifest.json")))
    assert man_a["run_id"] == man_b["run_id"]  # content-hash, timestamp-free
    assert man_a["schema_version"] == 1
    assert man_a["mode"] == "sync"
    assert man_a["population"] == small_ds.n_clients
    assert man_a["lanes"] == small_ds.n_clients  # fraction=default cohort
    assert man_a["rounds_recorded"] == 4
    assert man_a["config"]["train"]["rounds"] == 4
    assert man_a["environment"]["backend"]
    assert man_a["summary"]["final_accuracy"] == float(h.accuracy_mean[-1])
    assert man_a["summary"]["sim_clock_s"] == float(h.sim_clock[-1])
    # different config -> different run id
    _, out_c = _record(small_ds, FLConfig(rounds=5, epochs=1), tmp_path / "c")
    man_c = json.load(open(os.path.join(out_c, "manifest.json")))
    assert man_c["run_id"] != man_a["run_id"]


def test_progress_routes_through_run_log(small_ds, tmp_path, capsys):
    cfg = FLConfig(rounds=5, epochs=1)
    rec = RunRecorder(str(tmp_path / "rec"))  # echo=True: print AND log
    run_federated(small_ds, cfg, recorder=rec, progress=True)
    printed = capsys.readouterr().out
    logged = open(str(tmp_path / "rec" / "run.log")).read()
    assert logged.strip()
    for line in logged.splitlines():
        assert line.startswith("  round ")
        assert line in printed


def test_recorder_open_twice_raises(small_ds, tmp_path):
    cfg = FLConfig(rounds=2, epochs=1)
    rec = RunRecorder(str(tmp_path / "rec"), echo=False)
    run_federated(small_ds, cfg, recorder=rec)
    with pytest.raises(ValueError, match="already opened"):
        run_federated(small_ds, cfg, recorder=rec)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_profile_smoke(small_ds, tmp_path, mode):
    kw = dict(scheduler=mode)
    if mode == "async":
        kw.update(buffer_k=2)
    cfg = FLConfig(rounds=4, epochs=1, scan_chunk=2 if mode == "sync" else 1, **kw)
    _, out = _record(small_ds, cfg, tmp_path / mode, profile=True)
    prof = json.load(open(os.path.join(out, "profile.json")))
    assert prof["jit_cache_misses"] >= 1
    assert prof["peak_live_bytes"] > 0
    for phase in ("compile", "dispatch", "device_get"):
        assert prof["totals_s"][phase] > 0
    assert len(prof["chunks"]) >= 1


def test_environment_snapshot_shape():
    env = environment_snapshot()
    assert env["backend"] and env["device_count"] >= 1
    assert env["device_kind"] == jax.devices()[0].device_kind
    assert env["packages"]["jax"]


def test_profiler_raises_when_its_trace_cannot_start(tmp_path):
    """A jax.profiler trace that was asked for and cannot start (here: one
    is already running) is an error, not a run without the trace."""
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        with pytest.raises(RuntimeError):
            Profiler(jax_trace_dir=str(tmp_path / "inner")).start()
    finally:
        jax.profiler.stop_trace()
