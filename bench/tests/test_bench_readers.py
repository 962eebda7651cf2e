"""Per-layer readers on hand-made reductions: the codec's roofline finds
its kernels by name and refuses a count that is not 16 a round; the
exposed collective share averages the chips and reads nothing without a
collective; the whole step's mfu is the model's work over the window at
the chips' peak."""

from types import SimpleNamespace
from pathlib import Path

import numpy as np
import pytest

from bench import flops
from bench import metrics as metric_readers
from bench import models, peaks

ROOT = Path(__file__).resolve().parents[2]
HAR = models.load("har-mlp", ROOT)
CONFIG = {"n_features": 561, "hidden": [256, 256, 256], "n_classes": 6, "n_clients": 30}
QUANT = "%vmap_jit_quantize__.{} = (s8[30,128,512]{{2,1,0}}, f32[30,128,1]{{2,1,0}}) custom-call(%slice)"
DEQUANT = "%vmap_jit_dequantize__.{} = f32[30,128,512]{{2,1,0}} custom-call(%q, %s)"
OTHER = "%custom-call.9 = f32[30,256]{1,0} custom-call(%x), custom_call_target=\"tpu_custom_call\""
USER = "%fusion.3 = f32[30,128,512]{2,1,0} fusion(%vmap_jit_quantize__.0)"


def _facts(ops, rounds=2, chips=1, **red):
    reduced = {"op_text": {k: t for k, (t, _, _) in ops.items()},
               "op_count": {k: c for k, (_, c, _) in ops.items()},
               "op_ns": {k: ns for k, (_, _, ns) in ops.items()}, **red}
    return SimpleNamespace(reduced=reduced, rounds=rounds, chips=chips, config=CONFIG,
                           recipe={"codec": "int8"}, peak=peaks.PEAKS["TPU v5 lite"],
                           window_s=1.0, model=HAR)


def _codec_ops(rounds, kernel_ns):
    ops = {}
    for i in range(8):  # 8 leaves, one quantize and one dequantize each
        ops[f"q{i}"] = (QUANT.format(i), rounds, kernel_ns / 16)
        ops[f"d{i}"] = (DEQUANT.format(i), rounds, kernel_ns / 16)
    return ops


def test_quantize_roofline_reads_the_codec_kernels_by_name():
    sizes = [561, 256, 256, 256, 6]
    ideal_ns = 1e9 * flops.codec_bytes(sizes, 30) * 2 / 819e9
    ops = _codec_ops(2, 2 * ideal_ns)
    # a custom call that is not the codec's, and an op that only reads a
    # kernel's output, add nothing
    ops["other"] = (OTHER, 2, 5e6)
    ops["user"] = (USER, 2, 5e6)
    value = metric_readers.read("quantize_roofline", _facts(ops), ROOT)
    assert value == pytest.approx(50.0)


def test_quantize_roofline_refuses_a_count_that_is_not_16_a_round():
    ops = _codec_ops(2, 1e6)
    ops["q8"] = (QUANT.format(8), 1, 1e3)
    with pytest.raises(ValueError, match="expected 16 a round"):
        metric_readers.read("quantize_roofline", _facts(ops), ROOT)


def test_exposed_collective_share_is_the_mean_over_chips():
    facts = _facts({}, chips=4, collective_ns=[4e8, 4e8, 4e8, 4e8],
                   exposed_collective_ns=[1e8, 3e8, 0.0, 0.0])
    assert metric_readers.read("exposed_collective_share", facts, ROOT) == pytest.approx(10.0)
    none = _facts({}, collective_ns=[0.0], exposed_collective_ns=[0.0])
    assert metric_readers.read("exposed_collective_share", none, ROOT) is None


def test_round_mfu_is_the_models_work_over_the_window_at_peak():
    sizes = [561, 256, 256, 256, 6]
    sel = np.array([[True, False, True], [False, True, False]])
    facts = _facts({}, chips=2)
    facts.__dict__.update(sel=sel, n_train_valid=np.array([200, 246, 100]), n_train_rows=246,
                          n_test_valid=np.array([81, 70, 60]),
                          recipe={"batch_size": 32, "epochs": 2})
    work = flops.round_flops(sizes, sel, facts.n_train_valid, 246, facts.n_test_valid, 32, 2)
    value = metric_readers.read("round_mfu", facts, ROOT)
    assert value == pytest.approx(100.0 * work / (1.0 * 197e12 * 2))
    assert metric_readers.read("round_mfu.short", facts, ROOT) == value
    assert metric_readers.read("event_mfu", facts, ROOT) == value
