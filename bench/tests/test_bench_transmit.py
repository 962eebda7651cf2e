"""The uplink's per-layer metric ``transmit_device_ms.short`` on the
committed scope fixture (``scope_fixture.json``): the device time a round
of the ops in the ``fl.transmit`` phase, a mechanism's scope nested in it
(``codec.ef``) included, and nothing where the program has no scopes or
no transmit op."""

import copy

import pytest
from test_bench_scopes import FIXTURE, ROOT, _facts, traced  # noqa: F401

from bench import metrics as metric_readers
from bench import scopes

NAME = "transmit_device_ms.short"
ROUND = "jit(chunk_step)/fl.chunk/while/body/fl.round"


def test_transmit_device_ms_reads_nothing_where_no_op_transmits(traced):
    assert metric_readers.read(NAME, _facts(rounds=2), ROOT) == 0.0


def test_transmit_device_ms_reads_the_transmit_phase(traced):
    """The fixture with one op moved into the fused error-feedback pass
    (``fl.transmit/codec.ef``, 500 ns) and one into the finite guard
    (``fl.transmit``, 1000 ns), on chip 0, over 2 rounds."""
    facts = _facts(rounds=2)
    moved = copy.deepcopy(FIXTURE)
    ops = moved["planes"][1]["lines"][1]["events"]
    ops[3][3] = f"{ROUND}/fl.transmit/codec.ef/jit(quantize)/pallas_call"
    ops[5][3] = f"{ROUND}/fl.transmit/is_finite"
    traced["value"] = moved
    assert metric_readers.read(NAME, facts, ROOT) == pytest.approx(0.000375)
    assert scopes.scope_ms(facts, "codec.ef") == pytest.approx(0.000125)
    assert scopes.reduce(moved)["phase_ns"]["transmit"] == [1500, 0]


def test_transmit_device_ms_reads_none_without_scopes(traced):
    bare = copy.deepcopy(FIXTURE)
    for pl in bare["planes"]:
        for ln in pl["lines"]:
            for ev in ln["events"]:
                if len(ev) == 4:
                    ev[3] = ""
    traced["value"] = bare
    assert metric_readers.read(NAME, _facts(), ROOT) is None
