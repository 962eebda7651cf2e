"""``bench.flops`` against hand counts at both har-mlp widths."""

import numpy as np
import pytest

from bench import flops

UCI = [561, 256, 256, 256, 6]
MOTION = [7, 256, 256, 256, 6]


def test_weights_by_hand():
    assert flops.weights(UCI) == [143_616, 65_536, 65_536, 1_536]
    assert flops.weights(MOTION) == [1_792, 65_536, 65_536, 1_536]


@pytest.mark.parametrize("sizes,w,w_after_first", [
    (UCI, 276_224, 132_608),
    (MOTION, 134_400, 132_608),
])
def test_per_sample_flops_by_hand(sizes, w, w_after_first):
    assert flops.forward_flops(sizes) == 2 * w
    # forward + weight gradients + input gradients of layers 2..4
    assert flops.train_flops(sizes) == 2 * w + 2 * w + 2 * w_after_first


def test_trained_samples_drop_the_tail_that_fills_no_batch():
    # 246 rows -> 7 batches of 32 = 224 rows; two epochs
    got = flops.trained_samples(np.array([200, 230, 246]), 246, 32, 2)
    assert got.tolist() == [400, 448, 448]


def test_round_flops_counts_selected_clients_and_all_eval():
    sel = np.array([[True, False], [True, True]])
    got = flops.round_flops(UCI, sel, np.array([100, 50]), 128, np.array([10, 20]), 32, 1)
    train = (100 + 100 + 50) * flops.train_flops(UCI)
    evaluate = 2 * 30 * flops.forward_flops(UCI)
    assert got == train + evaluate


def test_codec_bytes_by_hand():
    # one lane of a (512,) leaf and a (6,) leaf:
    # 512: q reads 8*512, writes 512 + 4; dq reads 512 + 4, writes 4*512
    # 6:   padded to one block of 8
    got = flops.codec_bytes([512, 1], 1)
    one = lambda p, nb: 9 * p + 4 * nb + p + 4 * nb + 4 * p  # noqa: E731
    assert got == one(512, 1) + one(8, 1)


def test_codec_bytes_uci_har_round():
    # about 116 MB for 30 lanes of the 277k-parameter model
    assert 110e6 < flops.codec_bytes(UCI, 30) < 120e6
