"""The deepseek-v2-lite cell's per-layer readers and work counts: the
``mla.*`` and ``moe.*`` scopes read through ``bench.scopes.scope_ms`` from
a hand-made trace, nothing read (and nothing raised) where the program has
no such scope, and the model's FLOPs from its configuration's widths."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import metrics as metric_readers
from bench import models, peaks, scopes
from bench import run as bench_run

CELL = "deepseek-v2-lite.acsp-f32.silo4"
READERS = ("round_mfu.silo4", "mla_device_ms.silo4", "moe_device_ms.silo4",
           "moe_dispatch_ms.silo4", "exposed_collective_share.silo4")


def _trace(paths):
    """One device's ops, one after another: (scope path, ns, opcode)."""
    events, t = [], 1000
    for path, ns, op in paths:
        events.append([f"%x.{t} = f32[8]{{0}} {op}(%p)", t, ns, path])
        t += ns
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "annotations",
                                         "events": [["bench.window", 1000, t - 1000]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": events}]},
    ]}


SILO = [("jit(round_step)/fl.round/fl.train/checkpoint/mla.attn/dot_general", 4000, "fusion"),
        ("jit(round_step)/fl.round/fl.train/checkpoint/moe.route/dot_general", 500, "fusion"),
        ("jit(round_step)/fl.round/fl.train/checkpoint/moe.dispatch/sort", 700, "sort"),
        ("jit(round_step)/fl.round/fl.train/checkpoint/moe.experts/dot_general", 3000, "fusion"),
        ("jit(round_step)/fl.round/fl.train/checkpoint/moe.shared/dot_general", 1000, "fusion"),
        ("jit(round_step)/fl.round/fl.train/checkpoint/moe.combine/scatter", 300, "scatter"),
        ("jit(round_step)/fl.round/fl.eval/mla.attn/dot_general", 1000, "fusion"),
        ("jit(round_step)/fl.round/fl.aggregate/psum", 2000, "all-reduce")]
MLP = [("jit(chunk_step)/fl.chunk/fl.round/fl.train/dot_general", 9000, "fusion")]


@pytest.fixture()
def facts(tmp_path, monkeypatch):
    model = models.load("deepseek-v2-lite", bench_run.ROOT)
    _, _, workload, config = bench_run.load_cell(CELL)
    trace_dir = tmp_path / "trace"
    (trace_dir / CELL).mkdir(parents=True)
    (trace_dir / CELL / "vm.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "TRACES", trace_dir)
    scopes._reduced.cache_clear()

    def make(paths, rounds=2):
        trace = _trace(paths)
        monkeypatch.setattr(scopes, "load", lambda path: trace)
        scopes._reduced.cache_clear()
        red = scopes.reduce(trace)
        coll = sum(ns for _, ns, op in paths if op == "all-reduce")
        return SimpleNamespace(
            rounds=rounds, reduced={"window_ns": red["window_ns"], "collective_ns": [coll],
                                    "exposed_collective_ns": [coll]},
            window_s=red["window_ns"] * 1e-9, sel=np.ones((rounds, 4), bool), chips=4,
            config=config, recipe=workload["recipe"], peak=peaks.PEAKS["TPU v5 lite"],
            model=model, **model.data_facts(model.make_dataset(config)),
        )

    yield make
    scopes._reduced.cache_clear()


def read(name, f):
    return metric_readers.read(name, f, bench_run.ROOT)


def test_the_readers_read_the_scopes_of_the_silo_cell(facts):
    f = facts(SILO)
    assert read("mla_device_ms.silo4", f) == pytest.approx(5000e-6 / 2)
    assert read("moe_device_ms.silo4", f) == pytest.approx(5500e-6 / 2)
    assert read("moe_dispatch_ms.silo4", f) == pytest.approx(1500e-6 / 2)
    assert read("exposed_collective_share.silo4", f) == pytest.approx(100 * 2000 / 12500)
    work = f.model.round_flops(f)
    assert read("round_mfu.silo4", f) == pytest.approx(
        100 * work / (12500e-9 * 197e12 * 4))


def test_a_program_without_the_scopes_reads_nothing(facts):
    f = facts(MLP)
    for name in READERS[1:]:
        assert read(name, f) is None, name


def test_the_silo_work_counts_come_from_the_widths(facts):
    f = facts(SILO, rounds=1)
    model, config = f.model, f.config
    # the share's weights a token multiplies by: head 26.2M, 5 x MLA 13.76M,
    # dense GLU 67.2M, 4 x (router 0.13M + shared 17.3M + 8 held experts of
    # 8.65M at top-6 x 1/64 each)
    assert model.active_weights(config) == 257_949_696
    # causal scores and values, 5 blocks x 16 heads x (192 + 128) a pair
    assert model.attention_flops(config) == 5 * 2 * 16 * 320 * 2048 * 2049 / 2
    per_seq = 2 * 257_949_696 * 2048 + model.attention_flops(config)
    # every silo selected: 4 trained sequences at 3 passes and 1 test sequence each
    assert model.round_flops(f) == pytest.approx(4 * (3 * 4 + 1) * per_seq)
    f.sel = np.asarray([[True, False, False, False]])
    assert model.round_flops(f) == pytest.approx((3 * 4 + 4) * per_seq)
