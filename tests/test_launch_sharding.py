"""Coverage for the launch substrate: path-based PartitionSpec rules
(launch/sharding.py), mesh factories (launch/mesh.py), and the HLO
collective-bytes parser (launch/collectives.py).

Spec-rule tests run against ``jax.sharding.AbstractMesh`` — the rules only
read axis names/sizes, so no real (or forced) devices are needed and the
16x16 production geometry is testable in-process on one CPU device.
Mesh *construction* needs real devices, so ``make_production_mesh`` is
exercised under the ``multidevice`` marker with 256 forced host devices.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from _subproc import run_forced
from repro.launch.collectives import collective_bytes
from repro.launch.mesh import chip_peaks, data_axes, make_cohort_mesh
from repro.launch.sharding import (
    batch_spec,
    lane_spec,
    param_spec,
    tree_lane_pspecs,
    tree_pspecs,
)

PROD = AbstractMesh((16, 16), ("data", "model"))
PODS = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
COHORT4 = AbstractMesh((4,), ("cohort",))


# ---------------------------------------------------------------------------
# param_spec / tree_pspecs (production mesh rules)
# ---------------------------------------------------------------------------


def test_param_spec_generic_2d():
    # last dim -> model, first -> data, both divisible by 16
    assert param_spec("dense/w", (512, 512), PROD, ("data",)) == P("data", "model")
    # bias: 1-D, last dim -> model only
    assert param_spec("dense/b", (512,), PROD, ("data",)) == P("model")
    # scalar: replicated
    assert param_spec("scale", (), PROD, ("data",)) == P()


def test_param_spec_divisibility_fallback():
    # 20 % 16 != 0 on both dims: fully replicated
    assert param_spec("tiny/w", (20, 20), PROD, ("data",)) == P(None, None)
    # only the last dim divides: model-shard it, leave first replicated
    assert param_spec("mix/w", (20, 256), PROD, ("data",)) == P(None, "model")
    # only the first dim divides: data-shard it (ZeRO), last replicated
    assert param_spec("mix2/w", (256, 20), PROD, ("data",)) == P("data", None)


def test_param_spec_multi_pod_dp_axes():
    # with two data axes, the first dim takes the axis *tuple* and the
    # divisibility check uses their product (2*16 = 32)
    assert param_spec("dense/w", (64, 512), PODS, ("pod", "data")) == P(
        ("pod", "data"), "model"
    )
    # 48 % 32 != 0: data fallback, model still fine
    assert param_spec("dense/w", (48, 512), PODS, ("pod", "data")) == P(None, "model")
    assert data_axes() == ("data",)
    assert data_axes(multi_pod=True) == ("pod", "data")


def test_param_spec_stacked_layer_axis_never_sharded():
    # scan-stacked params: leading period axis replicated, rules shift by one
    s = param_spec("stack/dense/w", (8, 512, 512), PROD, ("data",))
    assert s == P(None, "data", "model")


def test_param_spec_mamba_contraction_dim():
    # mixer x_proj is (d_inner, dtr+2ds): the CONTRACTION dim goes to model
    # so it aligns with di-sharded activations (generic last-dim rules would
    # shard the tiny output dim instead)
    assert param_spec("mixer/x_proj", (1024, 96), PROD, ("data",)) == P("model", None)
    assert param_spec("mixer/out_proj", (1024, 512), PROD, ("data",)) == P(
        "model", "data"
    )
    assert param_spec("mixer/D", (1024,), PROD, ("data",)) == P("model")
    # same leaf name outside a mixer path: generic rules apply
    assert param_spec("head/x_proj", (1024, 96), PROD, ("data",)) == P("data", "model")


def test_param_spec_expert_weights():
    # moe (E, d_in, d_out): experts -> model (EP), d_in -> data (ZeRO)
    assert param_spec("moe/wu", (16, 512, 2048), PROD, ("data",)) == P(
        "model", "data", None
    )
    # expert count not divisible by model: E replicated, d_in still data
    assert param_spec("moe/wu", (12, 512, 2048), PROD, ("data",)) == P(
        None, "data", None
    )


def test_tree_pspecs_mirrors_tree():
    tree = {"dense": {"w": jnp.zeros((512, 512)), "b": jnp.zeros((512,))},
            "scale": jnp.zeros(())}
    specs = tree_pspecs(tree, PROD, ("data",))
    assert specs["dense"]["w"] == P("data", "model")
    assert specs["dense"]["b"] == P("model")
    assert specs["scale"] == P()


def test_batch_spec():
    assert batch_spec("x", (32, 128), PROD, ("data",)) == P("data", None)
    # batch not divisible by dp: replicated
    assert batch_spec("x", (20, 128), PROD, ("data",)) == P(None, None)
    assert batch_spec("step", (), PROD, ("data",)) == P()
    # multi-axis dp keeps the tuple
    assert batch_spec("x", (64, 128), PODS, ("pod", "data")) == P(("pod", "data"), None)


# ---------------------------------------------------------------------------
# lane_spec / tree_lane_pspecs (cohort mesh, repro.fl.shard)
# ---------------------------------------------------------------------------


def test_lane_spec_rules():
    assert lane_spec((8, 3, 20), COHORT4) == P("cohort", None, None)
    assert lane_spec((8,), COHORT4) == P("cohort")
    # K not divisible by the cohort axis: replicate (never silently pad)
    assert lane_spec((6, 3), COHORT4) == P(None, None)
    # fewer lanes than devices: replicate
    assert lane_spec((2, 3), COHORT4) == P(None, None)
    assert lane_spec((), COHORT4) == P()


def test_tree_lane_pspecs_and_eval_shape():
    tree = {"w": jnp.zeros((8, 5, 5)), "b": jnp.zeros((8,)), "s": jnp.zeros(())}
    specs = tree_lane_pspecs(tree, COHORT4)
    assert specs == {"w": P("cohort", None, None), "b": P("cohort"), "s": P()}
    # works on abstract leaves too (only .shape is read)
    abstract = jax.eval_shape(lambda: tree)
    assert tree_lane_pspecs(abstract, COHORT4) == specs


# ---------------------------------------------------------------------------
# mesh factories
# ---------------------------------------------------------------------------


def test_chip_peaks_known_kind_and_unknown_raises():
    assert chip_peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")


def test_make_cohort_mesh_single_device():
    # in-process the container sees exactly one device (conftest guards this)
    m = make_cohort_mesh()
    assert dict(m.shape) == {"cohort": 1}
    assert make_cohort_mesh(1).shape == m.shape
    with pytest.raises(ValueError, match="visible"):
        make_cohort_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_cohort_mesh(-1)


@pytest.mark.multidevice
def test_make_production_mesh_forced_256():
    out = run_forced(
        """
        from repro.launch.mesh import make_cohort_mesh, make_production_mesh

        m = make_production_mesh()
        assert dict(m.shape) == {"data": 16, "model": 16}, m.shape
        c = make_cohort_mesh(8)
        assert dict(c.shape) == {"cohort": 8}
        assert dict(make_cohort_mesh().shape) == {"cohort": 256}
        print("MESH OK")
        """,
        n_devices=256,
    )
    assert "MESH OK" in out


# ---------------------------------------------------------------------------
# collective_bytes HLO parsing (launch/collectives.py regression)
# ---------------------------------------------------------------------------

# shapes: f32[16,2048] = 131072 B; tuple member f32[1024] = 4096 B
_SYNC_HLO = """
  %all-reduce.5 = f32[16,2048]{1,0} all-reduce(f32[16,2048]{1,0} %add.3), replica_groups={}, to_apply=%sum
  %all-gather.1 = f32[64,128]{1,0} all-gather(f32[8,128]{1,0} %p0), dimensions={0}
"""

# sync *variadic* all-reduce: tuple lists one result per operand -> summed
_VARIADIC_HLO = """
  %all-reduce.9 = (f32[1024]{0}, f32[2048]{0}) all-reduce(f32[1024]{0} %a, f32[2048]{0} %b), to_apply=%sum
"""

# async pairs: -start carries the shapes (tuple = operand/result/scratch
# wrapper -> charge the largest, the destination); -done is bookkeeping
_ASYNC_HLO = """
  %all-reduce-start.2 = (f32[16,2048]{1,0}, f32[16,2048]{1,0}) all-reduce-start(f32[16,2048]{1,0} %add.3), to_apply=%sum
  %all-reduce-done.2 = f32[16,2048]{1,0} all-reduce-done((f32[16,2048]{1,0}, f32[16,2048]{1,0}) %all-reduce-start.2)
  %all-gather-start.1 = (f32[8,128]{1,0}, f32[64,128]{1,0}) all-gather-start(f32[8,128]{1,0} %p0), dimensions={0}
  %all-gather-done.1 = f32[64,128]{1,0} all-gather-done((f32[8,128]{1,0}, f32[64,128]{1,0}) %all-gather-start.1)
  %collective-permute-start.1 = (f32[256]{0}, f32[256]{0}) collective-permute-start(f32[256]{0} %x), source_target_pairs={{0,1}}
  %collective-permute-done.1 = f32[256]{0} collective-permute-done((f32[256]{0}, f32[256]{0}) %collective-permute-start.1)
"""


def test_collective_bytes_sync_ops():
    stats = collective_bytes(_SYNC_HLO)
    assert stats["count"] == 2
    assert stats["all-reduce"] == 16 * 2048 * 4
    assert stats["all-gather"] == 64 * 128 * 4
    assert stats["total"] == stats["all-reduce"] + stats["all-gather"]


def test_collective_bytes_sync_variadic_tuple_sums():
    stats = collective_bytes(_VARIADIC_HLO)
    assert stats["count"] == 1
    assert stats["all-reduce"] == (1024 + 2048) * 4


def test_collective_bytes_async_counts_start_once():
    """-start/-done pairs count exactly once, under the sync kind name,
    charging the destination buffer (largest tuple member) only."""
    stats = collective_bytes(_ASYNC_HLO)
    assert stats["count"] == 3  # 3 pairs, -done halves never match
    assert stats["all-reduce"] == 16 * 2048 * 4        # not doubled
    assert stats["all-gather"] == 64 * 128 * 4          # dest, not src+dest
    assert stats["collective-permute"] == 256 * 4


# TPU-compiled HLO: tiled layouts put parentheses inside a tuple shape
_TPU_TUPLE_HLO = """
  %all-reduce.19 = (f32[256]{0:T(256)S(1)}, f32[]{:T(128)}, f32[561,256]{1,0:T(8,128)S(1)}) all-reduce(%a, %b, %c), channel_id=1, to_apply=%add
  %collective-permute-start = (s32[16]{0:T(128)}, s32[16]{0:T(128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%x), channel_id=2, source_target_pairs={{0,1}}
"""


def test_collective_bytes_tpu_tiled_tuple_layouts():
    stats = collective_bytes(_TPU_TUPLE_HLO)
    assert stats["count"] == 2
    assert stats["all-reduce"] == (256 + 1 + 561 * 256) * 4
    assert stats["collective-permute"] == 16 * 4


def test_collective_bytes_mixed_and_empty():
    stats = collective_bytes(_SYNC_HLO + _ASYNC_HLO)
    assert stats["count"] == 5
    assert stats["all-reduce"] == 2 * 16 * 2048 * 4
    assert collective_bytes("%add.1 = f32[4]{0} add(%a, %b)") == {"count": 0}
