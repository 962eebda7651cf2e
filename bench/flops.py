"""Work counted from shapes: the model FLOPs of a round of har-mlp FL,
and the bytes the int8 wire codec must move.

FLOPs are the matmuls' (2 per multiply-add; biases, activations and the
loss are left out). A trained sample costs the forward pass, the weight
gradients and the input gradients of every layer but the first (nothing
asks for the gradient of the data). Only useful work counts: valid
samples that an executed (selected) client trains, and the valid test
samples that evaluation scores on every client; padding rows and the
lanes of unselected clients do not.
"""

from __future__ import annotations

import numpy as np


def weights(sizes) -> list[int]:
    return [fi * fo for fi, fo in zip(sizes[:-1], sizes[1:])]


def forward_flops(sizes) -> int:
    return 2 * sum(weights(sizes))


def train_flops(sizes) -> int:
    w = weights(sizes)
    return 2 * sum(w) + 2 * sum(w) + 2 * sum(w[1:])


def trained_samples(n_train_valid, n_train_rows, batch, epochs) -> np.ndarray:
    """(C,) valid samples each client trains in one round: whole batches of
    the slab, the tail that does not fill a batch dropped."""
    rows = max(1, n_train_rows // batch) * batch
    return np.minimum(np.asarray(n_train_valid), rows) * epochs


def round_flops(sizes, sel, n_train_valid, n_train_rows, n_test_valid, batch, epochs) -> float:
    """Useful FLOPs of the rounds whose (R, C) selection masks are ``sel``."""
    sel = np.asarray(sel, bool)
    per_client = trained_samples(n_train_valid, n_train_rows, batch, epochs)
    train = float((sel * per_client[None, :]).sum()) * train_flops(sizes)
    evaluate = float(sel.shape[0]) * float(np.sum(n_test_valid)) * forward_flops(sizes)
    return train + evaluate


def codec_bytes(sizes, lanes: int, block: int = 512) -> float:
    """Bytes one round's int8 codec must move for ``lanes`` client lanes:
    quantize reads the f32 values and the f32 noise and writes int8 codes
    and one f32 scale per block; dequantize reads codes and scales and
    writes f32 values."""
    total = 0
    for n in [w for w in weights(sizes)] + list(sizes[1:]):
        bp = min(block, max(n, 8))
        nb = -(-n // bp)
        p = nb * bp
        total += (4 * p + 4 * p + p + 4 * nb) + (p + 4 * nb + 4 * p)
    return float(lanes * total)
