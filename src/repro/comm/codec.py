"""Wire-format compression codecs for federated uplink traffic.

The seed repo only *counted* float32 parameters analytically; this subsystem
actually transforms updates and reports the bytes the transformed payload
would occupy on the wire. A ``Codec`` maps a flat float32 vector to a
``(payload, carrier)`` pair plus static wire accounting:

  payload  — side information needed to decode (scales, indices); its wire
             cost is ``meta_bytes(n)``;
  carrier  — the dense value array a *downstream* codec may compress
             further (ChainedCodec); if shipped raw it costs
             ``carrier_size(n) * carrier_bits() / 8`` bytes.

Everything is jit-compatible with static shapes: top-k keeps a fixed
``k = ceil(fraction * n)`` per leaf, quantization keeps dense int codes, so
``roundtrip`` runs inside the engine's jitted round step and ``wire_bytes``
is a pure Python function of the (static) element count — exact accounting
with zero traced overhead.

Lossy codecs are meant to be used with *error feedback* (Seide et al. 2014;
SAPS-FL's residual accumulation): the caller keeps a per-client residual
``e``, encodes ``delta + e`` and carries ``(delta + e) - decode(...)``
forward. ``repro.fl.engine`` does exactly this in the round state;
``ef_step`` here is the reusable single-step primitive.

Codecs:
  Float32Identity — raw float32 (the seed's analytic accounting, now real)
  QuantizeCodec   — int8/int4 per-block absmax quantization, stochastic
                    rounding, backed by the Pallas kernel pair in
                    repro.kernels.quantize; int4 packs two nibbles per
                    byte in the wire buffer (physical byte accounting)
  TopKCodec       — magnitude top-k sparsification (values + int32 indices)
  ChainedCodec    — composition, e.g. top-k then int8 on the survivors
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.quantize import dequantize, ops, quant_blocks, quantize


class Codec:
    """Base interface. Subclasses override encode/decode + accounting."""

    name: str = "codec"
    lossy: bool = False
    # whether the carrier is float32 values a downstream codec can compress
    # further (quantize ships integer codes — terminal in a chain)
    float_carrier: bool = True
    # whether ``ef_lanes`` runs this codec's error feedback for a stack of
    # lanes inside its kernels (else the caller composes ``ef_step``)
    fused_ef: bool = False

    # --- wire transform (jit-compatible, static shapes) ---
    def encode(self, flat: jnp.ndarray, rng: jax.Array) -> tuple[Any, jnp.ndarray]:
        """flat (N,) float32 -> (payload, carrier)."""
        raise NotImplementedError

    def decode(self, payload: Any, carrier: jnp.ndarray) -> jnp.ndarray:
        """Inverse of encode: reconstruct the (N,) float32 vector."""
        raise NotImplementedError

    # --- wire accounting (static Python floats) ---
    def meta_bytes(self, n: int) -> float:
        return 0.0

    def carrier_size(self, n: int) -> int:
        return n

    def carrier_bits(self) -> float:
        return 32.0

    def wire_bytes(self, n: int) -> float:
        """One-way wire bytes for an n-element tensor through this codec."""
        if n == 0:
            return 0.0
        return self.meta_bytes(n) + self.carrier_size(n) * self.carrier_bits() / 8.0

    # --- conveniences ---
    def roundtrip(self, x: jnp.ndarray, rng: jax.Array) -> jnp.ndarray:
        """decode(encode(x)) with the original shape restored."""
        flat = x.reshape(-1).astype(jnp.float32)
        payload, carrier = self.encode(flat, rng)
        return self.decode(payload, carrier).reshape(x.shape).astype(x.dtype)

    def compression_ratio(self, n: int) -> float:
        return 4.0 * n / max(self.wire_bytes(n), 1e-12)

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"{type(self).__name__}({self.name})"


class Float32Identity(Codec):
    """Raw float32 on the wire — lossless, 4 bytes/param (the baseline)."""

    name = "float32"
    lossy = False

    def encode(self, flat, rng):
        return None, flat

    def decode(self, payload, carrier):
        return carrier


def _pack_nibbles(q: jnp.ndarray) -> jnp.ndarray:
    """(N,) int8 4-bit codes in [-8, 7] -> (ceil(N/2),) uint8, two per byte
    (low nibble first). The physical int4 wire buffer."""
    n = q.shape[0]
    u = (q.astype(jnp.int32) + 8).astype(jnp.uint8)
    if n % 2:
        u = jnp.pad(u, (0, 1))
    return (u[0::2] | (u[1::2] << 4)).astype(jnp.uint8)


def _unpack_nibbles(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of ``_pack_nibbles``: (ceil(N/2),) uint8 -> (N,) int8."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    u = jnp.stack([lo, hi], axis=1).reshape(-1)[:n]
    return (u - 8).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class QuantizeCodec(Codec):
    """Per-block absmax integer quantization (int8 default, int4 with
    ``bits=4``) with stochastic rounding; one float32 scale per block.

    Backed by the Pallas kernel pair in repro.kernels.quantize (interpret
    mode off-TPU). int4 codes are *physically packed* two nibbles per byte
    in the encoded wire buffer, so ``wire_bytes`` counts the bytes the
    carrier actually occupies (``ceil(n/2)``) rather than charging an
    idealized 0.5 B/param while the codes ride int8 lanes.
    """

    bits: int = 8
    block: int = 512
    stochastic: bool = True

    name = "quantize"
    lossy = True
    float_carrier = False  # ships int codes; nothing can chain after it

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"QuantizeCodec supports bits in (4, 8), got {self.bits}")
        object.__setattr__(self, "name", f"int{self.bits}")

    def encode(self, flat, rng):
        noise = jax.random.uniform(rng, flat.shape) if self.stochastic else None
        q, scales = quantize(flat, noise, bits=self.bits, block_p=self.block)
        if self.bits == 4:
            return (scales, flat.shape[0]), _pack_nibbles(q)
        return scales, q

    def decode(self, payload, carrier):
        if self.bits == 4:
            scales, n = payload
            carrier = _unpack_nibbles(carrier, n)
        else:
            scales = payload
        return dequantize(carrier, scales, block_p=self.block)

    @property
    def fused_ef(self) -> bool:
        """Whether ``ef_lanes`` serves this codec's error feedback: int8
        where the kernels compile for a TPU."""
        return self.bits == 8 and not ops._default_interpret()

    def ef_lanes(self, trained, ref, residual, keys: jax.Array, sent: jnp.ndarray):
        """One error-feedback step of a stack of lanes, the compensation,
        reconstruction and residual inside the codec's two kernels.

        ``trained`` and ``residual`` are pytrees of stacked leaves (L, ...),
        ``ref`` the same pytree shared by every lane or stacked, ``keys``
        (L,) and ``sent`` (L,) bool. Leaf ``i`` of lane ``l`` draws its
        rounding noise from ``fold_in(keys[l], i)`` as ``ef_step`` does, so
        the result is ``ef_step`` on each lane's delta ``trained - ref``:
        returns ``(ref + decoded, new_residual, sq_norm)``, the residual
        updated only where ``sent``, and each lane's squared norm of
        ``decoded`` over ``ref`` (L,).

        The server sums what it receives over the lanes in the order the
        composition's layout gives, so a model aggregated from either is
        the same to the bit: a leaf of one block is composed per lane as
        ``ef_step`` composes it (it has no padding or slicing to save), and
        a leaf the plain wrappers pad reaches the server as the flat
        (L, size) stack they return."""
        leaves, treedef = jax.tree_util.tree_flatten(trained)
        recon, new_res, sq = [], [], 0.0
        for i, (t, g, r) in enumerate(
                zip(leaves, jax.tree.leaves(ref), jax.tree.leaves(residual))):
            n = math.prod(t.shape[1:])
            if n <= self.block:
                o, nr = self._ef_composed(t, g, r, keys, i)
                nr = jnp.where(sent.reshape((-1,) + (1,) * (nr.ndim - 1)), nr, r)
                d = o - g
                recon.append(o)
                new_res.append(nr)
                sq = sq + jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
                continue
            if self.stochastic:
                noise = jax.vmap(lambda k: jax.random.uniform(
                    jax.random.fold_in(k, i), t.shape[1:]))(keys)
            else:
                noise = jnp.full(t.shape, 0.5, jnp.float32)
            q, scales, nr = quantize(t, noise, bits=self.bits, block_p=self.block,
                                     ref=g, residual=r, sent=sent)
            o, n2 = dequantize(q, scales, block_p=self.block, ref=g, shape=t.shape)
            if n % quant_blocks(n, self.block)[0]:
                o = jax.lax.optimization_barrier(o.reshape(o.shape[0], n)).reshape(o.shape)
            recon.append(o)
            new_res.append(nr)
            sq = sq + n2
        unflat = partial(jax.tree_util.tree_unflatten, treedef)
        return unflat(recon), unflat(new_res), sq

    def _ef_composed(self, t, g, r, keys, i):
        """Leaf ``i``'s ``ef_step`` on every lane: ``(ref + decoded,
        compensated - decoded)``."""

        def lane(t_c, r_c, key, g_c):
            comp = (t_c - g_c) + r_c
            dec = self.roundtrip(comp, jax.random.fold_in(key, i))
            return g_c + dec, comp - dec

        return jax.vmap(lane, in_axes=(0, 0, 0, 0 if g.ndim == t.ndim else None))(
            t, r, keys, g)

    def meta_bytes(self, n):
        _, nb = quant_blocks(n, self.block)
        return 4.0 * nb

    def carrier_size(self, n):
        return (n + 1) // 2 if self.bits == 4 else n

    def carrier_bits(self):
        return 8.0  # physical: int8 codes, or a byte of two packed nibbles


@dataclasses.dataclass(frozen=True)
class TopKCodec(Codec):
    """Magnitude top-k sparsification: ship the k = ceil(fraction*n) largest
    entries as (value, int32 index) pairs; the rest are zeros at the decoder
    (and land in the caller's error-feedback residual)."""

    fraction: float = 0.1
    index_bytes: float = 4.0

    name = "topk"
    lossy = True

    def __post_init__(self):
        if not 0.0 < self.fraction <= 1.0:
            raise ValueError(f"topk fraction must be in (0, 1], got {self.fraction}")
        object.__setattr__(self, "name", f"topk{self.fraction:g}")

    def _k(self, n: int) -> int:
        return max(1, min(n, math.ceil(self.fraction * n)))

    def encode(self, flat, rng):
        k = self._k(flat.shape[0])
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        return (idx, flat.shape[0]), flat[idx]

    def decode(self, payload, carrier):
        idx, n = payload
        return jnp.zeros((n,), carrier.dtype).at[idx].set(carrier)

    def meta_bytes(self, n):
        return self.index_bytes * self._k(n)

    def carrier_size(self, n):
        return self._k(n)


class ChainedCodec(Codec):
    """Sequential composition: each stage compresses the previous stage's
    carrier (e.g. top-k picks survivors, int8 quantizes them). Every stage
    except the last must ship a float32 carrier downstream."""

    lossy = True

    def __init__(self, codecs: list[Codec]):
        if len(codecs) < 2:
            raise ValueError("ChainedCodec needs at least two stages")
        for c in codecs[:-1]:
            if not c.float_carrier:
                raise ValueError(
                    f"codec {c.name!r} ships a non-float carrier and can only be "
                    f"the last stage of a chain (got {[x.name for x in codecs]})"
                )
        self.codecs = list(codecs)
        self.name = "+".join(c.name for c in self.codecs)
        self.lossy = any(c.lossy for c in self.codecs)
        self.float_carrier = self.codecs[-1].float_carrier

    def encode(self, flat, rng):
        payloads = []
        carrier = flat
        for i, c in enumerate(self.codecs):
            payload, carrier = c.encode(carrier, jax.random.fold_in(rng, i))
            payloads.append(payload)
        return payloads, carrier

    def decode(self, payloads, carrier):
        for c, payload in zip(reversed(self.codecs), reversed(payloads)):
            carrier = c.decode(payload, carrier)
        return carrier

    def meta_bytes(self, n):
        total, size = 0.0, n
        for c in self.codecs:
            total += c.meta_bytes(size)
            size = c.carrier_size(size)
        return total

    def carrier_size(self, n):
        size = n
        for c in self.codecs:
            size = c.carrier_size(size)
        return size

    def carrier_bits(self):
        return self.codecs[-1].carrier_bits()


# ---------------------------------------------------------------------------
# factory + pytree helpers
# ---------------------------------------------------------------------------


# spec atom -> factory(bits=..., topk_fraction=...) — mirrors the string
# registries of repro.core.selection.get_strategy and repro.fl.phases
_CODEC_ATOMS = {
    "float32": lambda **kw: Float32Identity(),
    "identity": lambda **kw: Float32Identity(),
    "none": lambda **kw: Float32Identity(),
    "fp32": lambda **kw: Float32Identity(),
    "quantize": lambda **kw: QuantizeCodec(bits=kw.get("bits", 8)),
    "int8": lambda **kw: QuantizeCodec(bits=8),
    "int4": lambda **kw: QuantizeCodec(bits=4),
    "topk": lambda **kw: TopKCodec(fraction=kw.get("topk_fraction", 0.1)),
}


def register_codec_atom(name: str, factory) -> None:
    """Register a custom spec atom for ``make_codec``; ``factory`` is called
    with the keyword arguments of ``make_codec`` and returns a Codec."""
    _CODEC_ATOMS[name.lower()] = factory


def make_codec(spec: str, bits: int = 8, topk_fraction: float = 0.1) -> Codec:
    """Build a codec from an FLConfig-style spec string.

    Atoms: ``float32``/``identity``/``none``, ``int8``, ``int4``,
    ``quantize`` (uses ``bits``), ``topk`` (uses ``topk_fraction``).
    ``+``-joined atoms chain left to right, e.g. ``topk+int8``.
    """

    def atom(s: str) -> Codec:
        s = s.strip().lower()
        if s not in _CODEC_ATOMS:
            raise ValueError(
                f"unknown codec atom {s!r} in spec {spec!r}; have {sorted(_CODEC_ATOMS)}"
            )
        return _CODEC_ATOMS[s](bits=bits, topk_fraction=topk_fraction)

    parts = [p for p in spec.split("+") if p.strip()]
    if not parts:
        raise ValueError(f"empty codec spec {spec!r}")
    if len(parts) == 1:
        return atom(parts[0])
    return ChainedCodec([atom(p) for p in parts])


def tree_wire_bytes(codec: Codec, tree) -> float:
    """Static one-way wire bytes for every leaf of a pytree through codec
    (leaf sizes only — no tracing)."""
    return float(sum(codec.wire_bytes(int(l.size)) for l in jax.tree.leaves(tree)))


def roundtrip_tree(codec: Codec, tree, rng: jax.Array):
    """decode(encode(leaf)) for every leaf, each with its own rng fold."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [codec.roundtrip(l, jax.random.fold_in(rng, i)) for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def ef_step(codec: Codec, delta, residual, rng: jax.Array):
    """One error-feedback compression step on a pytree update.

    Encodes ``delta + residual`` leaf-wise; returns the decoded update (what
    the server receives) and the new residual ``(delta + residual) - decoded``
    to carry into the next round. For lossless codecs the residual is zero.
    """
    compensated = jax.tree.map(lambda d, e: d + e, delta, residual)
    decoded = roundtrip_tree(codec, compensated, rng)
    new_residual = jax.tree.map(lambda c, d: c - d, compensated, decoded)
    return decoded, new_residual
