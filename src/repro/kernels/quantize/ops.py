"""jit'd wrappers: flatten, pad to a whole number of blocks, run the kernel,
slice back. Public entry points for repro.comm's QuantizeCodec.

Given ``ref``, ``residual`` and ``sent``, the same two wrappers run one
error-feedback step for a whole stack of lanes at once — ``quantize``
encodes every lane's ``(x - ref) + residual`` and writes the residual to
carry, ``dequantize`` writes ``ref + x_hat`` and each lane's squared update
norm — with the compensation, the residual and the reconstruction inside
the two kernels. A leaf keeps its own layout there: a weight whose rows are
a whole fraction of a block (a last dim of 128 or 256 at block 512, or the
block itself) is read and written where it lies, its ragged last block
masked in the kernel; only other leaves (biases, narrow or odd widths) are
flattened into blocks first. These always run the Pallas kernels
(interpreted off the TPU): ``repro.comm`` takes them on a TPU only.

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

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.quantize import kernel
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


def _lane_view(shape: tuple[int, ...], block_p: int) -> tuple[int, int, int]:
    """``(rows, width, block)`` of the kernels' view of one lane's tensor.

    A tensor whose rows are a whole fraction of a block keeps its layout
    (rows = the leading dims merged), provided the kernels' row tiles fit
    the TPU tiling; any other is flattened: one block of its size when it
    fits in one, else rows of ``block`` elements (padded to a whole number
    of rows when the size is ragged)."""
    if len(shape) >= 2:
        rows, width = math.prod(shape[:-1]), shape[-1]
        g = block_p // width
        if g * width == block_p and (g == 1 or width % 128 == 0):
            bt, _ = kernel.block_rows(-(-rows // g))
            if g == 1 or bt * g % 8 == 0 or bt * g == rows:
                return rows, width, block_p
    n = math.prod(shape)
    if n <= block_p:
        return 1, n, n
    return -(-n // block_p), block_p, block_p


def _to_view(a: jnp.ndarray, rows: int, width: int, lead: int) -> jnp.ndarray:
    """``a`` with ``lead`` leading (lane) axes as ``(*lanes, rows, width)``,
    zero-padded at the end where the view is a ragged flattening."""
    lanes = a.shape[:lead]
    pad = rows * width - math.prod(a.shape[lead:])
    if pad:
        a = jnp.pad(a.reshape(*lanes, -1), [(0, 0)] * lead + [(0, pad)])
    return a.reshape(*lanes, rows, width)


def _from_view(a: jnp.ndarray, shape: tuple[int, ...]) -> jnp.ndarray:
    """The stacked ``(L, rows, width)`` view back as lanes of ``shape``."""
    if a.size != math.prod(shape):
        a = a.reshape(shape[0], -1)[:, :math.prod(shape[1:])]
    return a.reshape(shape)


@partial(jax.jit, static_argnames=("bits", "block_p", "interpret"))
def quantize(
    x: jnp.ndarray,               # any shape; flattened internally
    noise: jnp.ndarray | None = None,  # same size, uniform [0,1); None = nearest
    bits: int = 8,
    block_p: int = 512,
    interpret: bool | None = None,
    *,
    ref: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
    sent: jnp.ndarray | None = None,
):
    """Returns ``(q, scales)``: int8 codes of shape (x.size,) plus one
    float32 scale per block (the codec payload).

    Error feedback: given ``ref``, ``residual`` and ``sent`` — ``x``,
    ``noise`` and ``residual`` stacked lanes ``(L, ...)``, ``ref`` one
    tensor shared by every lane or stacked too, ``sent`` (L,) bool — it
    encodes each lane's
    ``(x - ref) + residual`` and returns ``(q, scales, new_residual)``: the
    payload in the kernels' blocked layout, which ``dequantize`` takes with
    the same ``ref``, and the residual to carry, ``(x - ref) + residual -
    x_hat`` for the lanes that sent the tensor and ``residual`` for the
    others. This mode always runs the Pallas kernel (interpreted off the
    TPU)."""
    if interpret is None:
        interpret = _default_interpret()
    if residual is not None:
        rows, width, block = _lane_view(x.shape[1:], block_p)
        view = partial(_to_view, rows=rows, width=width)
        q, scales, new_res = kernel.encode(
            view(x, lead=1), view(noise, lead=1), bits=bits, block_p=block,
            ref=view(ref, lead=ref.ndim - x.ndim + 1),
            residual=view(residual, lead=1), sent=sent, interpret=interpret)
        return q, scales, _from_view(new_res, x.shape)
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


@partial(jax.jit, static_argnames=("block_p", "interpret", "shape"))
def dequantize(
    q: jnp.ndarray,        # (P,) int8
    scales: jnp.ndarray,   # (NB,) float32
    block_p: int = 512,
    interpret: bool | None = None,
    *,
    ref: jnp.ndarray | None = None,
    shape: tuple[int, ...] | None = None,
):
    """Decodes ``(q, scales)`` to the (P,) values.

    Error feedback: given the blocked payload ``quantize`` returned for a
    stack of lanes of ``shape`` (L, ...) and its ``ref``, returns
    ``(ref + x_hat, sq_norm)``: the values the server aggregates, shaped as
    the lanes, and each lane's sum of ``(ref + x_hat - ref)^2`` (L,)."""
    if interpret is None:
        interpret = _default_interpret()
    if ref is not None:
        rows, width, _ = _lane_view(shape[1:], block_p)
        out, sq = kernel.decode(
            q, scales, rows,
            ref=_to_view(ref, rows, width, lead=ref.ndim - len(shape) + 1),
            interpret=interpret)
        return _from_view(out, shape), jnp.sum(sq.reshape(shape[0], -1), axis=1)
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
