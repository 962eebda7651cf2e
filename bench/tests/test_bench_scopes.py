"""The scope reduction (bench.scopes) on a committed fixture: each op's
phase is the innermost ``fl.`` phase of its scope, a ``while`` and its
body count once, ops in a round with no phase are ``body`` and ops in no
scope ``none``, the phases add up to the busy time, and a trace whose
window is not the run's is refused. Scopes come from the HLO a trace
keeps (read here from a real CPU trace and from a hand-made module), an
op the compiler added taking its neighbours' scope."""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import metrics as metric_readers
from bench import scopes

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = json.loads((Path(__file__).parent / "scope_fixture.json").read_text())


@pytest.mark.parametrize("scope,phase", [
    ("jit(chunk_step)/fl.chunk/while/body/fl.round/fl.train/while/body/dot", "train"),
    ("jit(step)/fl.round/fl.eval/fl.personalize/mul", "personalize"),
    ("jit(step)/fl.event/fl.transmit/fl.transmit/dot", "transmit"),
    ("jit(chunk_step)/fl.chunk/fl.round/add", "body"),
    ("jit(chunk_step)/fl.chunk/dynamic_update_slice", "body"),
    ("jit(step)/fl.round/shard_map/fl.aggregate/psum", "aggregate"),
    ("jit(add)/add", "none"),
    ("", "none"),
    ("jit(step)/fl.roundabout/fl.trainer/mul", "none"),
])
def test_phase_of_is_the_innermost_phase(scope, phase):
    assert scopes.phase_of(scope) == phase


def test_reduce_counts_a_while_and_its_body_once():
    red = scopes.reduce(FIXTURE)
    ns = {k: v[0] for k, v in red["phase_ns"].items()}
    assert red["window_ns"] == 10000
    # the training while and its two body ops: [1000, 5000] once
    assert ns["train"] == 4000
    assert ns["personalize"] == 1000 and ns["eval"] == 1000
    # an op in a round with no phase; an op in no scope; one clipped at the
    # window's end; one before the window not at all
    assert ns["body"] == 500 and ns["none"] == 500 and ns["select"] == 500
    assert red["busy_ns"][0] == sum(ns.values()) == 7500
    # a chunk-level while enclosing a round: the round's op is train, the
    # rest of the while is body, and nothing counts twice
    assert red["phase_ns"]["train"][1] == 2000 and red["phase_ns"]["body"][1] == 8000
    assert red["busy_ns"][1] == 10000
    assert red["scoped"]


def _facts(rounds=2, window_ns=10000, **host):
    return SimpleNamespace(rounds=rounds, reduced={"window_ns": window_ns},
                           host_phase_s=host)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """The fixture as the newest trace under a trace directory."""
    trace = {"value": FIXTURE}
    (tmp_path / "cell").mkdir()
    (tmp_path / "cell" / "vm.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    monkeypatch.setattr(scopes, "load", lambda path: trace["value"])
    scopes._reduced.cache_clear()
    yield trace
    scopes._reduced.cache_clear()


def test_phase_ms_is_the_mean_over_chips_per_round(traced):
    facts = _facts(rounds=2)
    # train: 4000 ns on chip 0, 2000 on chip 1 -> 3000 ns over 2 rounds
    assert scopes.phase_ms(facts, "train") == pytest.approx(0.0015)
    for name in ("train_device_ms", "train_device_ms.short", "train_device_ms.async"):
        assert metric_readers.read(name, facts, ROOT) == pytest.approx(0.0015)
    for name in ("eval_device_ms", "eval_device_ms.short", "eval_device_ms.async"):
        assert metric_readers.read(name, facts, ROOT) == pytest.approx(0.00025)


def test_a_trace_that_is_not_the_runs_is_refused(traced):
    with pytest.raises(ValueError, match="not this run's trace"):
        scopes.phase_ms(_facts(window_ns=9999), "train")


def test_a_program_without_scopes_reads_nothing(traced):
    bare = copy.deepcopy(FIXTURE)
    for pl in bare["planes"]:
        for ln in pl["lines"]:
            for ev in ln["events"]:
                if len(ev) == 4:
                    ev[3] = ""
    traced["value"] = bare
    assert not scopes.reduce(bare)["scoped"]
    assert metric_readers.read("train_device_ms", _facts(), ROOT) is None
    assert metric_readers.read("eval_device_ms.async", _facts(), ROOT) is None


def test_host_span_metrics_per_event():
    facts = _facts(rounds=4, stage=0.002, queue=0.006)
    assert metric_readers.read("stage_ms.async", facts, ROOT) == pytest.approx(0.5)
    assert metric_readers.read("queue_ms.async", facts, ROOT) == pytest.approx(1.5)
    # a program without the spans has nothing to read
    assert metric_readers.read("stage_ms.async", _facts(rounds=4), ROOT) is None
    assert metric_readers.read("queue_ms.async", _facts(rounds=0, queue=1.0), ROOT) is None


def test_hlo_scopes_come_from_the_traces_metadata_plane(tmp_path):
    """A trace keeps every loaded program's optimized HLO under
    /host:metadata; each instruction's scope is its op_name there."""

    def f(x):
        with jax.named_scope("fl.round"):
            with jax.named_scope("fl.train"):
                y = jnp.sin(x) @ x
            return jnp.cos(y).sum()

    step = jax.jit(f)
    x = jnp.ones((8, 8))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    by_module = scopes.hlo_scopes(path)
    (key,) = [k for k in by_module if k[0] == "jit_f"]
    found = by_module[key]
    assert any(s.endswith("fl.round/fl.train/sin") for s in found.values()), found
    train = next(name for name, s in found.items() if "fl.train" in s)
    module = f"jit_f({key[1]})"
    assert scopes._lookup(by_module, module, train) == found[train]
    assert scopes._lookup(by_module, "jit_f", train) == found[train]
    assert scopes._lookup(by_module, None, train) == found[train]
    assert scopes._lookup(by_module, module, "no-such-op") == ""


def _varint(n) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _inst(iid, name, opcode, op_name="", operands=(), called=()):
    body = _field(1, name.encode()) + _field(2, opcode.encode()) + _field(35, iid)
    if op_name:
        body += _field(7, _field(2, op_name.encode()))
    if operands:
        body += _field(36, b"".join(_varint(o) for o in operands))  # packed
    for c in called:
        body += _field(38, c)  # not packed
    return _field(2, body)


def test_an_op_the_compiler_added_takes_its_neighbours_scope():
    """A copy, prefetch or loop-body op with no op_name counts for the op
    that consumes its result, else the one it reads, else the loop that
    calls its computation; a fusion's inner instructions are never ops."""
    entry = _field(5, 1) + b"".join([
        _inst(10, "p.1", "parameter"),
        _inst(11, "copy.1", "copy", operands=[10]),
        _inst(12, "fusion.1", "fusion", "jit(s)/fl.round/fl.train/dot", [11], called=[3]),
        _inst(13, "copy-start.1", "copy-start", operands=[12]),
        _inst(14, "while.1", "while", "jit(s)/fl.round/fl.eval/while", called=[2]),
        _inst(16, "copy.2", "copy", operands=[12]),
        _inst(15, "add.1", "add", "jit(s)/fl.round/add", [16]),
    ])
    body = _field(5, 2) + _inst(20, "iota.1", "iota")
    fused = _field(5, 3) + _inst(30, "param_0", "parameter") + _inst(
        31, "dot.9", "dot", "jit(s)/fl.round/fl.train/dot", [30])
    module = b"".join(_field(3, c) for c in (entry, body, fused))
    found = scopes.instruction_scopes(_field(1, module))
    assert scopes.phase_of(found["copy.1"]) == "train"        # its consumer
    assert scopes.phase_of(found["copy.2"]) == "body"         # its consumer, before its input
    assert scopes.phase_of(found["copy-start.1"]) == "train"  # no consumer: its input
    assert scopes.phase_of(found["iota.1"]) == "eval"         # the loop that runs it
    assert "p.1" in found and "dot.9" not in found and "param_0" not in found


def test_each_op_is_looked_up_in_the_program_it_ran_in():
    modules = [(0, 100, "jit_a(1)"), (100, 200, "jit_b(2)")]
    events = [["%fusion.1 = f32[8] fusion()", s, 5, ""] for s in (10, 150, 250)]
    assert scopes._enclosing(modules, events) == ["jit_a(1)", "jit_b(2)", None]
    by_module = {("jit_a", 1): {"fusion.1": "fl.round/fl.train/dot"},
                 ("jit_b", 2): {"fusion.1": "fl.round/fl.eval/dot"}}
    assert scopes._lookup(by_module, "jit_b(2)", "fusion.1") == "fl.round/fl.eval/dot"
    # a name two programs share cannot be told apart without the program
    assert scopes._lookup(by_module, None, "fusion.1") == ""


NESTED = json.loads((Path(__file__).parent / "scope_nested_fixture.json").read_text())


def test_scope_ms_reads_a_mechanism_scope_nested_in_a_phase(traced):
    """One op in ``fl.train/moe.dispatch`` (1000 ns), one in ``fl.train``
    alone (2000 ns), one in ``fl.eval`` (500 ns), over 2 rounds."""
    traced["value"] = NESTED
    facts = _facts(rounds=2)
    assert scopes.scope_ms(facts, "moe.dispatch") == pytest.approx(0.0005)
    assert scopes.scope_ms(facts, "fl.train") == pytest.approx(0.0015)
    assert scopes.scope_ms(facts, "fl.round") == pytest.approx(0.00175)
    # the phase split reads as before: train holds both ops
    assert scopes.reduce(NESTED)["phase_ns"]["train"] == [3000]
    assert scopes.phase_ms(facts, "train") == scopes.scope_ms(facts, "fl.train")
    # a whole component only, and nothing for a scope no op is in
    assert scopes.scope_ms(facts, "dispatch") is None
    assert scopes.scope_ms(facts, "moe.combine") is None


def test_scope_ms_of_a_phase_on_the_first_fixture(traced):
    facts = _facts(rounds=2)
    # fl.train holds no other phase's op there, so it reads as the phase
    assert scopes.scope_ms(facts, "fl.train") == scopes.phase_ms(facts, "train")
    # a chunk's while counts for fl.chunk around every op inside it
    assert scopes.reduce(FIXTURE)["scope_ns"]["fl.chunk"] == [7000, 10000]
