"""The trace reduction on a small committed fixture, and ``load`` on a
real (CPU) profiler trace."""

import json
from pathlib import Path

import pytest

from bench import trace_reduce

FIXTURE = Path(__file__).with_name("trace_fixture.json")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(json.loads(FIXTURE.read_text()))


def test_window_is_the_bench_window_annotation(reduced):
    assert reduced["window_ns"] == 10000


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window(reduced):
    # TPU:0: [1000,1500) + [2000,5000) + [8000,10000); TPU:1 busy throughout;
    # the SparseCore plane and the XLA Modules line are not ops of a chip
    assert reduced["busy_ns"] == [5500, 10000]


def test_op_totals_are_clipped_and_averaged_over_chips(reduced):
    ops = reduced["op_ns"]
    assert ops["fusion.7"] == 500 / 2
    assert ops["fusion.1"] == (1000 + 2000 + 10000) / 2
    assert ops["quantize_kernel"] == 1000 / 2
    assert "ignored" not in ops


def test_idle_gaps_are_labelled_by_the_host_phase_they_overlap_most(reduced):
    gaps = reduced["gaps"]
    assert gaps == [("device_get", 3000), ("device_get", 1000), ("dispatch", 500)]


def test_host_loop_labels_a_gap_no_phase_overlaps():
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.window", 0, 100], ["bench.dispatch", 0, 10]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0, 40], ["b", 70, 30]]}]},
    ]}
    assert trace_reduce.reduce(trace)["gaps"] == [("host loop", 30)]


def test_ops_are_counted_and_their_text_kept(reduced):
    assert reduced["op_count"] == {"fusion.7": 1, "fusion.1": 3, "fusion.2": 1,
                                   "quantize_kernel": 1}
    assert reduced["op_text"]["quantize_kernel"] == "quantize_kernel"


def test_opcodes_are_read_from_the_instruction_text():
    assert trace_reduce.opcode("%fusion.12 = f32[30]{0} fusion(f32[30]{0} %custom-call.3)") == "fusion"
    assert trace_reduce.opcode(
        "%all-reduce.19 = (f32[256]{0:T(256)S(1)}, f32[]{:T(128)}) all-reduce(%a, %b)"
    ) == "all-reduce"
    assert trace_reduce.opcode("%vmap_jit_quantize__.1 = (s8[30,128,512]{2,1,0:T(8,128)(4,1)S(1)}, "
                               "f32[30,128,1]{2,1,0}) custom-call(%slice)") == "custom-call"
    assert trace_reduce.opcode("fusion.7") == ""


def test_exposed_collective_time_is_what_no_compute_op_covers():
    ar = "%all-reduce.1 = f32[8]{0} all-reduce(%x)"
    done = "%async-collective-done = f32[24]{0} fusion(%a)"
    loop = "%while.3 = (f32[8]{0}) while(%t), body=%b"
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [["bench.window", 0, 100]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            # the enclosing while is not compute: the all-reduce under it is exposed
            [loop, 0, 50], ["%fusion.1 = f32[8]{0} fusion(%x)", 0, 20], [ar, 20, 10],
            # half of the collective's done overlaps compute
            [done, 60, 20], ["%fusion.2 = f32[8]{0} fusion(%y)", 70, 20]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8]{0} fusion(%x)", 0, 100]]}]},
    ]}
    red = trace_reduce.reduce(trace)
    assert red["collective_ns"] == [30, 0]
    assert red["exposed_collective_ns"] == [20, 0]


def test_op_names_are_the_instruction_names():
    assert trace_reduce.op_name("%fusion.12 = f32[30]{0} fusion(f32[30]{0} %custom-call.3)") == "fusion.12"
    assert trace_reduce.op_name("custom-call.3") == "custom-call.3"


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": []})


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = trace_reduce.load(trace_reduce.find_trace(tmp_path))
    names = [a[0] for a in trace_reduce.annotations(trace)]
    assert "bench.window" in names and "bench.dispatch" in names
    red = trace_reduce.reduce(trace)
    assert red["window_ns"] > 0
    assert red["busy_ns"] == []  # no TPU plane on the CPU

