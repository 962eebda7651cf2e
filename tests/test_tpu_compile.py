"""Compile the main path's TPU programs for a described TPU v5e, no chip.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described and not attached (``jax.experimental.topologies``).
That catches what interpret mode cannot: block shapes the TPU tiling
refuses, kernels that do not lower, programs that do not fit. Nothing runs
here, so these tests say nothing about results or times — ``chip_smoke.py``
does that on the chip.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and the test
workers all import this file.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.codec import QuantizeCodec
from repro.kernels.quantize import dequantize, quantize

# har-mlp leaf sizes: 561x256, 256x256, 256x6 weights; 256 and 6 biases
LEAF_SIZES = (143_616, 65_536, 1_536, 256, 6)
LEAF_SHAPES = ((561, 256), (256, 256), (256, 6), (256,), (6,))
LANES = 30  # uci-har clients
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a), sharding=sharding),
        tree,
    )


@pytest.mark.parametrize("n", LEAF_SIZES)
@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_pair_compiles_for_v5e(one_chip, bits, n):
    """The wire codec's kernel pair at a har-mlp leaf size, vmapped over
    the 30 client lanes, compiles for the chip as Pallas kernels."""

    def roundtrip(x, u):
        q, s = jax.vmap(lambda a, b: quantize(a, b, bits=bits, interpret=False))(x, u)
        return jax.vmap(lambda a, b: dequantize(a, b, interpret=False))(q, s)

    x = jax.ShapeDtypeStruct((LANES, n), jnp.float32, sharding=one_chip)
    text = jax.jit(roundtrip).lower(x, x).compile().as_text()
    assert text.count(KERNEL) >= 2, "quantize and dequantize kernels"


@pytest.mark.parametrize("per_lane_ref", [False, True], ids=["shared", "per_lane"])
@pytest.mark.parametrize("shape", LEAF_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_fused_error_feedback_compiles_for_v5e(one_chip, shape, per_lane_ref):
    """The int8 codec's error-feedback pair for the 30 client lanes of a
    har-mlp leaf, against the server model shared by the lanes (sync) or
    each lane's dispatch snapshot (async), compiles for the chip as two
    Pallas kernels."""

    def step(t, ref, res, u, sent):
        q, s, new_res = quantize(t, u, ref=ref, residual=res, sent=sent, interpret=False)
        recon, sq = dequantize(q, s, ref=ref, shape=t.shape, interpret=False)
        return recon, new_res, sq

    lanes = jax.ShapeDtypeStruct((LANES,) + shape, jnp.float32, sharding=one_chip)
    ref = lanes if per_lane_ref else jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    sent = jax.ShapeDtypeStruct((LANES,), jnp.bool_, sharding=one_chip)
    text = jax.jit(step).lower(lanes, ref, lanes, lanes, sent).compile().as_text()
    assert text.count(f'custom_call_target="{KERNEL}"') == 2


def _int8_round_text(one_chip, fused=True):
    from repro.configs.har_mlp import fl_defaults
    from repro.data import make_har_dataset
    from repro.fl import api
    from repro.fl.sched import _setup_run
    from repro.kernels.quantize import ops
    from repro.models.mlp import mlp_accuracy, mlp_loss

    cfg = fl_defaults()
    cfg = dataclasses.replace(cfg, codec=dataclasses.replace(cfg.codec, spec="int8"))
    ds = make_har_dataset("uci-har", seed=0)
    # the env's data slabs become arguments, so the program holds no array
    # placed on this host's CPU
    slabs = ("x_tr", "y_tr", "m_tr", "x_te", "y_te", "m_te", "n_samples", "delay")
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_default_interpret", lambda: False)
        if not fused:
            mp.setattr(QuantizeCodec, "fused_ef", False)
        su = _setup_run(ds, cfg, None, mlp_loss, mlp_accuracy, None, None, None)
        state = su.initial_state()

        def round_step(state, t, data):
            env = dataclasses.replace(su.env, **dict(zip(slabs, data)))
            return api.build_round_step(env, su.pipeline, cfg.execution)(state, t)

        args = (state, jnp.int32(0), tuple(getattr(su.env, s) for s in slabs))
        text = jax.jit(round_step).lower(*_abstract(args, one_chip)).compile().as_text()
    jax.clear_caches()
    return text


@pytest.fixture(scope="module")
def int8_round_text(one_chip):
    """The optimized program of one round of the paper recipe with the int8
    codec, on the uci-har stand-in at har-mlp's published widths, compiled
    for one chip with the quantize wrappers picking the Pallas kernel as
    they do on a TPU (traces made meanwhile are dropped on both sides)."""
    return _int8_round_text(one_chip)


def test_int8_round_step_compiles_for_v5e(int8_round_text):
    """One int8 round compiles for one chip with the codec's Pallas kernels
    inside."""
    assert int8_round_text.count(KERNEL) >= 2


def test_codec_kernels_keep_the_name_the_benchmark_finds(int8_round_text):
    """The codec's kernels are custom calls named after their jitted
    wrappers, inside the round's ``fl.transmit`` scope: the benchmark's
    ``quantize_roofline`` finds them by that name (its ``CODEC`` pattern),
    one quantize and one dequantize per parameter leaf of a round."""
    from bench.metrics.quantize_roofline import CODEC

    kernels = [line for line in int8_round_text.splitlines()
               if f'custom_call_target="{KERNEL}"' in line]
    leaves = 2 * 4  # har-mlp: (weight, bias) x 4 layers
    assert len(kernels) == 2 * leaves
    for line in kernels:
        name = line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        assert CODEC.search(name), name
        assert "/fl.transmit/" in re.search(r'op_name="([^"]*)"', line).group(1)


def _transmit_ops(text):
    """``(name, opcode, shape, line)`` of each instruction of the optimized
    program in the ``fl.transmit`` scope, outside fusion bodies."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    comp, ops = None, []
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            continue
        scope = re.search(r'op_name="([^"]*)"', line)
        if comp in fused or not scope or "/fl.transmit/" not in scope.group(1):
            continue
        name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
        opcode = re.search(r" ([a-z][a-z0-9\-]*)\(", rest).group(1)
        dims = re.search(r"\[([\d,]*)\]", rest).group(1)
        ops.append((name, opcode, tuple(int(d) for d in dims.split(",") if d), line))
    return ops


def test_int8_transmit_runs_error_feedback_inside_the_kernels(int8_round_text):
    """The int8 round's uplink, as a TPU runs it: each leaf's error
    feedback for all 30 lanes inside the codec's two kernels, under the
    ``codec.ef`` scope. No pad or slice copies a lane stack of a large leaf
    around them (the generic composition pads the 561x256 leaf's values and
    noise and slices codes and values back), and the compiler's estimate
    of the scope's other ops stays under 0.9 M cycles (1.6 M with the
    generic composition)."""
    ops = _transmit_ops(int8_round_text)
    kernels = [line for _, _, _, line in ops if f'custom_call_target="{KERNEL}"' in line]
    assert len(kernels) == 16
    assert all("/fl.transmit/codec.ef/" in line for line in kernels)
    copies = [name for name, opcode, shape, _ in ops if opcode in ("pad", "slice")
              and shape[:1] == (LANES,) and math.prod(shape[1:]) >= 65_536]
    assert copies == []
    cycles = sum(int(c) for _, _, _, line in ops if KERNEL not in line
                 for c in re.findall(r'"estimated_cycles":"(\d+)"', line))
    assert 0 < cycles <= 900_000, cycles


def _lane_sums(text):
    """``(size, where)`` of each lane sum of ``fl.aggregate`` in the
    optimized program: the size of what it reduces to, and where the lane
    axis lies in its operand's layout (the vector lanes, the sublanes, or a
    major axis), which sets the order of the additions."""
    sums = []
    for m in re.finditer(r"= f32\[(\d+),([\d,]+)\]\{([\d,]+):T[^}]*\} multiply\(.*\n"
                         r".*\n.*ROOT %reduce[\w.]* = .*fl\.aggregate/reduce_sum", text):
        minor_to_major = [int(d) for d in m.group(3).split(",")]
        where = {0: "lanes", 1: "sublanes"}.get(minor_to_major.index(0), "major")
        sums.append((math.prod(int(d) for d in m.group(2).split(",")), where))
    return sorted(sums)


def test_int8_aggregation_adds_lanes_in_the_compositions_order(one_chip, int8_round_text):
    """The server's sum over the 30 lanes of each leaf runs over the same
    layout with the fused pass as with the ``ef_step`` composition, so the
    global model the two aggregate is the same to the bit: the 561x256
    leaf in the flat stack the plain wrappers return (lanes on sublanes),
    the 6-wide output bias composed as the composition composes it."""
    fused = _lane_sums(int8_round_text)
    assert fused == _lane_sums(_int8_round_text(one_chip, fused=False))
    assert len(fused) == 8 and (143_616, "sublanes") in fused and (6, "sublanes") in fused
