"""quantize Pallas TPU kernel pair — the comm subsystem's wire-format hot path.

Per quantization block of BP consecutive elements:

  scale    = max(|x|) / qmax                       (qmax = 2^(bits-1) - 1)
  q[p]     = clip(floor(x[p] / scale + u[p]), -qmax, qmax)   as int8
  x_hat[p] = q[p] * scale                          (dequantize)

``u`` is uniform noise in [0, 1): with u ~ U[0,1) this is *stochastic
rounding* (unbiased, E[q*scale] = x); with u = 0.5 it degenerates to
round-to-nearest. Noise is generated outside the kernel with jax.random so
the kernel stays deterministic given its inputs and runs identically in
interpret mode on CPU (pltpu.prng_* is TPU-compile only).

Layout. Both kernels take a stack of lanes, each lane's tensor viewed as
rows: values ``(L, R, C)`` with ``G = BP / C`` consecutive rows to a block
(one row when C = BP). Row ``k`` of each group of G is read and written
with a sublane stride of G, which the TPU does on one 128-lane piece at a
time, so a row of several pieces arrives as one operand a piece. The
payload:

  codes  (L, G, NB, C) int8   [l, k, b] is row b*G + k of lane l
  scales (L, NB, 1)    f32    one scale per block (the codec's meta)

NB = ceil(R / G). Rows past R (the ragged last block, or a partial last
tile) are masked to 0 before the block max, so they neither move a scale
nor get a code other than 0, and their writes fall outside the arrays.
TPU block shapes must match an array's trailing dims or the (8, 128)
tiling (int8: (32, 128)), which the wrappers in ops.py guarantee by their
choice of view. The grids put the lane inside the row tile, so an operand
shared by every lane (the server model error feedback measures from) is
fetched once a tile.

Error feedback rides in the same two passes when its operands are given.
The encode, the client's side, quantizes ``c = (x - ref) + residual`` and
also writes the residual to carry, ``c - x_hat`` where the lane sent the
tensor and the old residual where it did not; the decode, the server's
side, writes ``ref + x_hat`` (what the server aggregates) and per-lane
partial sums of ``(ref + x_hat - ref)^2`` (the uplink's squared norm).
int4 reuses the same int8 storage with qmax=7 — packing is accounted at the
wire level (bits/8 bytes per element) by repro.comm, not materialised here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# blocks per grid step: a multiple of the int8 (32, 128) tile, and at BP=512
# a 512 KiB f32 tile — an error-feedback encode's four f32 operands, its
# codes and residual, double-buffered, stay under 6 MiB of VMEM
ROWS = 256

# The kernels' names, which name their custom calls in the compiled program
# and in a device trace whether or not a caller vmaps the wrappers (those of
# ops.py, jitted as ``quantize`` and ``dequantize``): the benchmark's
# quantize_roofline finds the codec's kernels by them.
ENCODE, DECODE = "jit_quantize", "jit_dequantize"


def block_rows(nb: int) -> tuple[int, int]:
    """(blocks per grid step, grid length) for NB quantization blocks."""
    rows = min(nb, ROWS)
    return rows, pl.cdiv(nb, rows)


def _valid(shape, g: int, k: int, rows: int, tile):
    """Which elements of row ``k`` of each group of ``g`` lie inside the
    leaf's R rows, in row tile ``tile``."""
    first = tile * shape[0] * g + k
    return first + g * lax.broadcasted_iota(jnp.int32, shape, 0) < rows


def _load(ref, g: int, k: int):
    """Row ``k`` of each group of ``g`` rows of a tile (a sublane-strided
    load, which the TPU takes from one 128-lane piece, when g > 1)."""
    bt = ref.shape[0] // g
    return ref[pl.ds(k, bt, stride=g), :] if g > 1 else ref[...]


def _store(ref, g: int, k: int, val):
    bt = ref.shape[0] // g
    if g > 1:
        ref[pl.ds(k, bt, stride=g), :] = val
    else:
        ref[...] = val


def _compensated(x_ref, ref_ref, res_ref, g, k):
    c = _load(x_ref, g, k).astype(jnp.float32)
    if ref_ref is not None:
        c = c - _load(ref_ref, g, k)
    if res_ref is not None:
        c = c + _load(res_ref, g, k)
    return c


def _encode_kernel(*refs, qmax, g, pieces, rows, ef):
    """Grid (row tile, lane, piece). Every operand arrives as its ``pieces``
    column pieces of the tile, whose blocks stay put while the piece
    advances: the block scales are taken from all of them at piece 0 and
    kept in scratch; each step then writes its own piece's codes (and
    residual)."""
    refs = list(refs)

    def take():
        return [refs.pop(0) for _ in range(pieces)]

    sent_ref = refs.pop(0) if ef else None
    xs = take()
    rfs = take() if ef else [None] * pieces
    rss = take() if ef else [None] * pieces
    us = take()
    q_ref, s_ref = refs.pop(0), refs.pop(0)
    r_out = refs.pop(0) if ef else None
    (scale_ref,) = refs
    ragged = rows % xs[0].shape[0] != 0
    # read outside the pl.when bodies, where interpret mode cannot
    tile, lane, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def piece(h, k):
        c = _compensated(xs[h], rfs[h], rss[h], g, k)
        u = _load(us[h], g, k).astype(jnp.float32)
        if ragged:
            ok = _valid(c.shape, g, k, rows, tile)
            c, u = jnp.where(ok, c, 0.0), jnp.where(ok, u, 0.0)
        return c, u

    @pl.when(step == 0)
    def _():
        amax = functools.reduce(jnp.maximum, [
            jnp.max(jnp.abs(piece(h, k)[0]), axis=1, keepdims=True)
            for k in range(g) for h in range(pieces)])
        scale_ref[...] = jnp.maximum(amax, 1e-12) / qmax     # (bt, 1)
        s_ref[...] = scale_ref[...]

    for h in range(pieces):
        @pl.when(step == h)
        def _(h=h):
            scale = scale_ref[...]
            for k in range(g):
                c, u = piece(h, k)
                q = jnp.clip(jnp.floor(c / scale + u), -qmax, qmax)
                q_ref[k] = q.astype(jnp.int8)
                if ef:
                    sent = sent_ref[lane] != 0
                    new = jnp.where(sent, c - q * scale, _load(rss[h], g, k))
                    _store(r_out, g, k, new.astype(r_out.dtype))


def _decode_kernel(q_ref, s_ref, *refs, g, rows, ef):
    """Grid (row tile, piece, lane): one piece of one lane's tile."""
    ref_ref = refs[0] if ef else None
    o_ref = refs[1] if ef else refs[0]
    s = s_ref[...]
    sq = jnp.zeros((1, o_ref.shape[1]), jnp.float32)
    for k in range(g):
        dec = q_ref[k].astype(jnp.float32) * s
        out = _load(ref_ref, g, k) + dec if ef else dec
        _store(o_ref, g, k, out.astype(o_ref.dtype))
        if ef:
            d = out - _load(ref_ref, g, k)
            if rows % o_ref.shape[0] != 0:
                d = jnp.where(_valid(d.shape, g, k, rows, pl.program_id(0)), d, 0.0)
            sq = sq + jnp.sum(d * d, axis=0, keepdims=True)
    if ef:
        refs[2][...] = sq


def _split(width: int, g: int) -> tuple[int, int]:
    """(pieces, piece width): a row split into 128-lane pieces when a block
    spans several rows (the TPU's strided access is one 128-lane piece
    wide), else taken whole."""
    return (width // 128, 128) if g > 1 else (1, width)


def encode(
    x: jnp.ndarray,        # (L, R, C) values, G = block_p // C rows a block
    noise: jnp.ndarray,    # (L, R, C) uniform [0,1) (0.5 everywhere = nearest)
    bits: int = 8,
    block_p: int = 512,
    ref: jnp.ndarray | None = None,       # error feedback: (R, C) shared, or (L, R, C) ...
    residual: jnp.ndarray | None = None,  # ... (L, R, C) ...
    sent: jnp.ndarray | None = None,      # ... and (L,) bool
    interpret: bool = True,
):
    """Codes ``(L, G, NB, C)`` int8 (``[l, k, b]``: row ``b*G + k``) and
    scales ``(L, NB, 1)`` of every lane's ``x``; with error feedback, of
    ``c = (x - ref) + residual``, and also the new residual ``(L, R, C)``:
    ``c - x_hat`` where the lane sent, ``residual`` where it did not."""
    lanes, rows, width = x.shape
    g = block_p // width
    assert g * width == block_p and (g == 1 or width % 128 == 0)
    pieces, w = _split(width, g)
    nb = pl.cdiv(rows, g)
    bt, steps = block_rows(nb)
    ef = residual is not None
    operands, specs = [], []
    for a in (x, ref, residual, noise):
        if a is not None:
            operands += [a] * pieces
            specs += [pl.BlockSpec((bt * g, w), lambda i, l, h, *_, p=p: (i, p))
                      if a.ndim == 2 else
                      pl.BlockSpec((None, bt * g, w), lambda i, l, h, *_, p=p: (l, i, p))
                      for p in range(pieces)]
    out_specs = [pl.BlockSpec((None, g, bt, w), lambda i, l, h, *_: (l, 0, i, h)),
                 pl.BlockSpec((None, bt, 1), lambda i, l, h, *_: (l, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((lanes, g, nb, width), jnp.int8),
                 jax.ShapeDtypeStruct((lanes, nb, 1), jnp.float32)]
    if ef:
        operands.insert(0, sent.astype(jnp.int32))
        out_specs.append(pl.BlockSpec((None, bt * g, w), lambda i, l, h, *_: (l, i, h)))
        out_shape.append(jax.ShapeDtypeStruct(x.shape, residual.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=int(ef), grid=(steps, lanes, pieces), in_specs=specs,
        out_specs=out_specs, scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)])
    return tuple(pl.pallas_call(
        functools.partial(_encode_kernel, qmax=float(2 ** (bits - 1) - 1), g=g,
                          pieces=pieces, rows=rows, ef=ef),
        grid_spec=grid_spec, out_shape=out_shape, interpret=interpret, name=ENCODE,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*operands))


def decode(
    q: jnp.ndarray,        # (L, G, NB, C) int8
    scales: jnp.ndarray,   # (L, NB, 1) float32
    rows: int,
    ref: jnp.ndarray | None = None,       # (R, C) shared, or (L, R, C)
    interpret: bool = True,
):
    """``x_hat`` as ``(L, R, C)``; given ``ref``, ``ref + x_hat`` and the
    squared norm of ``x_hat`` over ``ref`` as partial sums
    ``(L, steps, pieces, 1, w)``."""
    lanes, g, nb, width = q.shape
    pieces, w = _split(width, g)
    bt, steps = block_rows(nb)
    # grid (row tile, piece, lane): the lane innermost, so a shared operand
    # keeps its block from lane to lane
    tile = pl.BlockSpec((None, bt * g, w), lambda i, h, l: (l, i, h))
    operands = [q, scales]
    specs = [pl.BlockSpec((None, g, bt, w), lambda i, h, l: (l, 0, i, h)),
             pl.BlockSpec((None, bt, 1), lambda i, h, l: (l, i, 0))]
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((lanes, rows, width), jnp.float32)]
    if ref is not None:
        operands.append(ref)
        specs.append(tile if ref.ndim == 3 else
                     pl.BlockSpec((bt * g, w), lambda i, h, l: (i, h)))
        out_specs.append(pl.BlockSpec((None, None, None, 1, w),
                                      lambda i, h, l: (l, i, h, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((lanes, steps, pieces, 1, w), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, g=g, rows=rows, ef=ref is not None),
        grid=(steps, pieces, lanes), in_specs=specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=DECODE,
    )(*operands)
    return tuple(out) if ref is not None else out[0]


def quantize_kernel(
    x: jnp.ndarray,       # (P,) float32, P % block_p == 0
    noise: jnp.ndarray,   # (P,) uniform [0,1) (0.5 everywhere = nearest)
    bits: int = 8,
    block_p: int = 512,
    interpret: bool = True,
):
    """One flat tensor: ``(q (P,) int8, scales (NB,))``."""
    p = x.shape[0]
    bp = min(block_p, p)
    assert p % bp == 0, "ops.py pads the param axis"
    view = (1, p // bp, bp)
    q, scales = encode(x.reshape(view), noise.reshape(view), bits=bits,
                       block_p=bp, interpret=interpret)
    return q.reshape(p), scales.reshape(-1)


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
    out = decode(q.reshape(1, 1, nb, bp), scales.reshape(1, nb, 1), nb,
                 interpret=interpret)
    return out.reshape(p)
