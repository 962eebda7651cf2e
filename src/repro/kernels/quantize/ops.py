"""jit'd wrappers: flatten, pad to a whole number of blocks, run the kernel,
slice back. Public entry points for repro.comm's QuantizeCodec.

On a TPU the compiled Pallas kernel runs (kernel.py). Anywhere else — the
CPU backend, or a computation pinned to a CPU device with
``jax.default_device`` — the wrapper dispatches to the vectorized jnp
oracle (ref.py) instead of interpret-mode Pallas: interpret mode unrolls
the grid at trace time, so a 300k-param leaf vmapped over 30 clients would
explode compile times. The two paths compute the same math: the kernel is
checked against ref.py in interpret mode in tests/test_comm_codecs.py,
compiled for a described v5e in tests/test_tpu_compile.py, and run against
ref.py on the chip by chip_smoke.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import dequantize_kernel, quantize_kernel
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref


def _default_interpret() -> bool:
    """True unless this computation lands on a TPU. A ``jax.default_device``
    pin (a device or a platform name) overrides the default backend."""
    pin = jax.config.jax_default_device
    platform = getattr(pin, "platform", pin) if pin is not None else jax.default_backend()
    return platform != "tpu"


def quant_blocks(n: int, block_p: int = 512) -> tuple[int, int]:
    """(block, n_blocks) the wrappers will use for an n-element tensor —
    shared with repro.comm so wire accounting matches the payload layout."""
    bp = min(block_p, max(n, 8))
    return bp, -(-n // bp)


@partial(jax.jit, static_argnames=("bits", "block_p", "interpret"))
def quantize(
    x: jnp.ndarray,               # any shape; flattened internally
    noise: jnp.ndarray | None = None,  # same size, uniform [0,1); None = nearest
    bits: int = 8,
    block_p: int = 512,
    interpret: bool | None = None,
):
    """Returns ``(q, scales)``: int8 codes of shape (x.size,) plus one
    float32 scale per block (the codec payload)."""
    if interpret is None:
        interpret = _default_interpret()
    flat = x.reshape(-1).astype(jnp.float32)
    p = flat.shape[0]
    bp, nb = quant_blocks(p, block_p)
    u = jnp.full((p,), 0.5, jnp.float32) if noise is None else noise.reshape(-1).astype(jnp.float32)
    pad = nb * bp - p
    if pad:
        flat = jnp.pad(flat, (0, pad))
        u = jnp.pad(u, (0, pad))
    if interpret:  # off-TPU fast path: same math, no grid unrolling
        q, scales = quantize_ref(flat, u, bits=bits, block=bp)
    else:
        q, scales = quantize_kernel(flat, u, bits=bits, block_p=bp, interpret=False)
    return q[:p], scales


@partial(jax.jit, static_argnames=("block_p", "interpret"))
def dequantize(
    q: jnp.ndarray,        # (P,) int8
    scales: jnp.ndarray,   # (NB,) float32
    block_p: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = _default_interpret()
    p = q.shape[0]
    bp, nb = quant_blocks(p, block_p)
    pad = nb * bp - p
    if pad:
        q = jnp.pad(q, (0, pad))
    if interpret:
        out = dequantize_ref(q, scales, block=bp)
    else:
        out = dequantize_kernel(q, scales, block_p=bp, interpret=False)
    return out[:p]
