"""Parse collective traffic out of lowered/compiled HLO text.

cost_analysis() has no collective-bytes entry, so we sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute in the (optimized, SPMD-partitioned) HLO. Shapes in the
optimized module are PER-PARTITION, so the sums are per-device bytes —
exactly what the roofline's collective term wants.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")

# e.g.  %all-reduce.5 = bf16[16,2048]{1,0} all-reduce(...)
# Async pairs count once: the `-start` half carries the shapes (matched),
# the `-done` half is bookkeeping (rejected — `-done` can't match
# `(?:-start)?[\.\d]*\(`). TPU layouts nest one level of parentheses inside
# a tuple shape (`(f32[256]{0:T(256)S(1)}, ...)`), so the tuple group
# admits them.
_OP_RE = re.compile(
    r"=\s*(?:\(((?:[^()]|\([^()]*\))*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+("
    + "|".join(_COLLECTIVES)
    + r")(-start)?[\.\d]*\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Returns {'total': bytes, per-op-kind: bytes, 'count': n_ops}.

    Async collectives (``all-reduce-start`` / ``all-gather-start`` / ...)
    count once, under their sync kind name. A sync variadic collective's
    tuple shape lists one result per operand (summed); a ``-start`` tuple is
    the (operand, result[, scratch...]) async wrapper, so only its largest
    shape — the destination buffer — is charged.
    """
    out = defaultdict(int)
    count = 0
    for m in _OP_RE.finditer(hlo_text):
        tuple_part, dtype, dims, kind, start = m.groups()
        if tuple_part is not None:
            shapes = [_shape_bytes(dt, dm) for dt, dm in _SHAPE_RE.findall(tuple_part)]
            size = max(shapes, default=0) if start else sum(shapes)
        else:
            size = _shape_bytes(dtype, dims)
        out[kind] += size
        out["total"] += size
        count += 1
    out["count"] = count
    return dict(out)


def collective_breakdown_str(stats: dict) -> str:
    parts = [f"total={stats.get('total', 0)/1e6:.1f}MB ops={stats.get('count', 0)}"]
    for k in _COLLECTIVES:
        if stats.get(k):
            parts.append(f"{k}={stats[k]/1e6:.1f}MB")
    return " ".join(parts)
