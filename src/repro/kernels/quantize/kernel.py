"""quantize Pallas TPU kernel pair — the comm subsystem's wire-format hot path.

Per parameter block of BP elements:

  scale    = max(|x|) / qmax                       (qmax = 2^(bits-1) - 1)
  q[p]     = clip(floor(x[p] / scale + u[p]), -qmax, qmax)   as int8
  x_hat[p] = q[p] * scale                          (dequantize)

``u`` is uniform noise in [0, 1): with u ~ U[0,1) this is *stochastic
rounding* (unbiased, E[q*scale] = x); with u = 0.5 it degenerates to
round-to-nearest. Noise is generated outside the kernel with jax.random so
the kernel stays deterministic given its inputs and runs identically in
interpret mode on CPU (pltpu.prng_* is TPU-compile only).

Layout: the flat (P,) parameter axis is viewed as (NB, BP) — one row per
quantization block — and the grid walks ROWS rows at a time, so every
block is a 2-D (ROWS, BP) tile whose last dim is the whole row (TPU block
shapes must match the array's trailing dims or the (8, 128) tiling; rank-1
blocks of one scale each are refused by the TPU compiler). Rows are
independent, so a ragged last row-block (NB % ROWS != 0) is safe: its
out-of-range rows are never written back. BlockSpecs:

  x      (NB, BP) -> (ROWS, BP)
  noise  (NB, BP) -> (ROWS, BP)
  q      (NB, BP) -> (ROWS, BP)  int8 out
  scales (NB, 1)  -> (ROWS, 1)   one f32 scale per block (the codec's meta)

The wire format is unchanged: ``(P,)`` int8 codes and ``(NB,)`` scales.
int4 reuses the same int8 storage with qmax=7 — packing is accounted at the
wire level (bits/8 bytes per element) by repro.comm, not materialised here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows per grid step: a multiple of the int8 (32, 128) tile, and at BP=512
# a 512 KiB f32 input tile — x, noise and q double-buffered stay ~2.5 MiB
# of VMEM
ROWS = 256


def _row_blocks(nb: int) -> tuple[int, int]:
    """(rows per block, grid length) for NB quantization blocks."""
    rows = min(nb, ROWS)
    return rows, pl.cdiv(nb, rows)


def _quant_kernel(x_ref, u_ref, q_ref, s_ref, *, qmax: float):
    x = x_ref[...].astype(jnp.float32)          # (ROWS, BP)
    u = u_ref[...].astype(jnp.float32)          # (ROWS, BP)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax     # (ROWS, 1)
    q = jnp.clip(jnp.floor(x / scale + u), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_kernel(
    x: jnp.ndarray,       # (P,) float32, P % block_p == 0
    noise: jnp.ndarray,   # (P,) uniform [0,1) (0.5 everywhere = nearest)
    bits: int = 8,
    block_p: int = 512,
    interpret: bool = True,
):
    p = x.shape[0]
    bp = min(block_p, p)
    assert p % bp == 0, "ops.py pads the param axis"
    nb = p // bp
    rows, steps = _row_blocks(nb)
    qmax = float(2 ** (bits - 1) - 1)
    tile = pl.BlockSpec((rows, bp), lambda i: (i, 0))
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(steps,),
        in_specs=[tile, tile],
        out_specs=[tile, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bp), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(nb, bp), noise.reshape(nb, bp))
    return q.reshape(p), scales.reshape(nb)


def dequantize_kernel(
    q: jnp.ndarray,       # (P,) int8, P % block_p == 0
    scales: jnp.ndarray,  # (NB,) float32
    block_p: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    p = q.shape[0]
    bp = min(block_p, p)
    nb = p // bp
    assert p % bp == 0 and scales.shape[0] == nb
    rows, steps = _row_blocks(nb)
    tile = pl.BlockSpec((rows, bp), lambda i: (i, 0))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(steps,),
        in_specs=[tile, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb, bp), jnp.float32),
        interpret=interpret,
    )(q.reshape(nb, bp), scales.reshape(nb, 1))
    return out.reshape(p)
