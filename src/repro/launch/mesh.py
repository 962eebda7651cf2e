"""Mesh definitions: the 16x16 production mesh (TPU v5e pods; 256
chips/pod) and the 1-D dev-scale ``cohort`` mesh the sharded FL round step
runs on (repro.fl.shard).

FUNCTIONS, not module-level constants — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod, or 2x16x16 across two pods.

    Axes:
      pod   — inter-pod data parallelism (DCN-ish; FL silo groups span it)
      data  — intra-pod data parallel / ZeRO / FL silo axis
      model — tensor/expert parallel
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_cohort_mesh(n_devices: int | None = None):
    """1-D dev-scale mesh for sharding the FL cohort axis (repro.fl.shard).

    Axes:
      cohort — data parallelism over the (K, ...) gathered client lanes;
               global params and the (C, ...) server slabs stay replicated.

    ``n_devices`` of None/0 takes every visible device; a positive count
    takes a prefix (dev/test runs force host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in a fresh
    process — see tests/_subproc.py).

    The axis is ``Auto``: the scatter after the cohort ``shard_map`` writes
    lane-sharded results into replicated slabs and leaves the layout to the
    partitioner, which ``make_mesh``'s default ``Explicit`` axes reject with
    a ``ShardingTypeError``.
    """
    devices = jax.devices()
    n = len(devices) if not n_devices else int(n_devices)
    if n < 1:
        raise ValueError(f"make_cohort_mesh: need >= 1 device, got {n_devices!r}")
    if n > len(devices):
        raise ValueError(
            f"make_cohort_mesh: {n} devices requested but only "
            f"{len(devices)} visible (force host devices in a subprocess "
            f"via XLA_FLAGS=--xla_force_host_platform_device_count={n})"
        )
    return jax.make_mesh(
        (n,), ("cohort",), devices=devices[:n], axis_types=(AxisType.Auto,)
    )


def data_axes(multi_pod: bool = False):
    return ("pod", "data") if multi_pod else ("data",)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "peak_ops_int8": 393e12,     # OP/s
        "hbm_bytes": 16e9,           # B
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
    },
}

# the chip the production-mesh dry-run compiles for
V5E = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
