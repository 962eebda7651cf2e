"""repro.comm wire-format codecs: round-trip invariants, Pallas kernel
parity, error-feedback convergence, and engine-level wire accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    ChainedCodec,
    Float32Identity,
    QuantizeCodec,
    TopKCodec,
    ef_step,
    make_codec,
    tree_wire_bytes,
)
from repro.kernels.quantize import dequantize, quantize
from repro.kernels.quantize.ref import dequantize_ref, quantize_ref


# ---------------------------------------------------------------------------
# pallas kernel vs ref parity (kernel driven directly in interpret mode —
# the ops wrappers route to ref.py off-TPU, see kernels/quantize/ops.py)
# ---------------------------------------------------------------------------

from repro.kernels.quantize.kernel import dequantize_kernel, quantize_kernel

# 281 blocks of 512 (the har-mlp input layer, padded) spans two row-blocks
# of the kernel grid, the second one ragged
Q_SHAPES = [8, 512, 1024, 4096, 281 * 512]


@pytest.mark.parametrize("n", Q_SHAPES)
@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_kernel_matches_ref(n, bits):
    ks = jax.random.split(jax.random.PRNGKey(n + bits), 2)
    x = jax.random.normal(ks[0], (n,)) * 3.0
    noise = jax.random.uniform(ks[1], (n,))
    bp = min(512, n)
    q, s = quantize_kernel(x, noise, bits=bits, block_p=bp, interpret=True)
    qr, sr = quantize_ref(x, noise, bits=bits, block=bp)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    out = dequantize_kernel(q, s, block_p=bp, interpret=True)
    outr = dequantize_ref(qr, sr, block=bp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), rtol=1e-6)


# har-mlp's leaves: the 561x256 input weight (281 blocks, the last half
# full), the 256x256 hidden weights, the 256x6 output weight, the biases;
# and a 128-wide weight, four rows a block, its last block one row
EF_LEAVES = [(561, 256), (256, 256), (256, 6), (256,), (6,), (301, 128)]


@pytest.mark.parametrize("per_lane_ref", [False, True], ids=["shared", "per_lane"])
@pytest.mark.parametrize("shape", EF_LEAVES, ids=lambda s: "x".join(map(str, s)))
def test_fused_error_feedback_matches_ef_step(shape, per_lane_ref):
    """The int8 codec's error feedback inside its two kernels (interpret
    mode, a few lanes) against today's composition: ``ef_step`` on each
    lane's delta from its reference (the server model shared by the lanes,
    or each lane's dispatch snapshot), the residual kept where the lane did
    not send, one lane holding a NaN. Codes, scales, what the server
    aggregates and the new residual are bit-equal; the update norm agrees
    to float32 summation order."""
    lanes, codec = 3, QuantizeCodec(bits=8)
    ks = jax.random.split(jax.random.PRNGKey(len(shape) + shape[0]), 5)
    trained = jax.random.normal(ks[0], (lanes,) + shape) * 2.0
    trained = trained.at[(1,) + (3,) * len(shape)].set(jnp.nan)
    residual = jax.random.normal(ks[1], trained.shape) * 0.05
    ref = jax.random.normal(ks[2], trained.shape if per_lane_ref else shape)
    keys = jax.random.split(ks[3], lanes)
    sent = jnp.array([True, True, False])

    @jax.jit
    def fused(trained, ref, residual):
        noise = jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, 0), shape))(keys)
        q, s, _ = quantize(trained, noise, ref=ref, residual=residual, sent=sent)
        recon, new_res, sq = codec.ef_lanes([trained], [ref], [residual], keys, sent)
        return q, s, recon[0], new_res[0], sq

    @jax.jit
    def composed(trained, ref, residual):
        def lane(t, r, key, g):
            dec, new_r = ef_step(codec, [t - g], [r], key)
            return g + dec[0], new_r[0], quantize(
                ((t - g) + r).reshape(-1),
                jax.random.uniform(jax.random.fold_in(key, 0), (t.size,)))
        refs = ref if per_lane_ref else jnp.broadcast_to(ref, trained.shape)
        recon, new_res, (q, s) = jax.vmap(lane)(trained, residual, keys, refs)
        new_res = jnp.where(sent.reshape((-1,) + (1,) * len(shape)), new_res, residual)
        d = recon - refs
        return q, s, recon, new_res, jnp.sum(d * d, axis=tuple(range(1, d.ndim)))

    q, s, recon, new_res, sq = fused(trained, ref, residual)
    q_ref, s_ref, recon_ref, new_res_ref, sq_ref = composed(trained, ref, residual)
    # the blocked codes (L, G, NB, C) in wire order: block b holds rows b*G..
    flat = jnp.swapaxes(q, 1, 2).reshape(lanes, -1)[:, :q_ref.shape[1]]
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s[..., 0]), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(recon_ref))
    np.testing.assert_array_equal(np.asarray(new_res), np.asarray(new_res_ref))
    np.testing.assert_allclose(np.asarray(sq), np.asarray(sq_ref), rtol=1e-6)
    assert np.isnan(np.asarray(sq)[1]) and np.isfinite(np.asarray(sq)[[0, 2]]).all()
    np.testing.assert_array_equal(np.asarray(new_res[2]), np.asarray(residual[2]))


def test_fused_error_feedback_only_for_int8_on_a_tpu(monkeypatch):
    """The fused pass is chosen from what the code sees: an int8 codec
    where the kernels compile for a TPU. Off the TPU and for int4 every
    lossy codec keeps ``ef_step``."""
    from repro.kernels.quantize import ops

    assert not QuantizeCodec(bits=8).fused_ef  # the CPU here
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    assert QuantizeCodec(bits=8).fused_ef
    assert not QuantizeCodec(bits=4).fused_ef
    assert not any(getattr(c, "fused_ef", False) for c in (
        Float32Identity(), TopKCodec(0.1), ChainedCodec([TopKCodec(0.1), QuantizeCodec(8)])))


def test_quantize_ops_pad_ragged_sizes():
    """The jit wrappers pad ragged sizes to whole blocks and slice back."""
    for n in (7, 513, 1000):
        x = jax.random.normal(jax.random.PRNGKey(n), (n,))
        q, s = quantize(x, None, bits=8)
        assert q.shape == (n,)
        out = dequantize(q, s)
        assert out.shape == (n,)
        step = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(out - x))) <= step


def test_quantize_deterministic_mode_rounds_to_nearest():
    x = jnp.asarray([0.0, 1.0, -1.0, 0.49, 0.51])
    q, s = quantize(x, None, bits=8)  # noise=None -> u=0.5 = nearest
    out = np.asarray(dequantize(q, s))
    scale = 1.0 / 127.0
    np.testing.assert_allclose(out, np.round(np.asarray(x) / scale) * scale, atol=1e-7)


# ---------------------------------------------------------------------------
# codec round-trip invariants
# ---------------------------------------------------------------------------


def test_identity_codec_lossless():
    x = jax.random.normal(jax.random.PRNGKey(0), (33, 17))
    c = Float32Identity()
    xh = c.roundtrip(x, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(xh), np.asarray(x))
    assert not c.lossy
    assert c.wire_bytes(x.size) == 4.0 * x.size


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_error_bounded_by_step(bits):
    x = jax.random.normal(jax.random.PRNGKey(2), (4096,)) * 2.0
    c = QuantizeCodec(bits=bits)
    xh = c.roundtrip(x, jax.random.PRNGKey(3))
    qmax = 2 ** (bits - 1) - 1
    # per-block scale = absmax/qmax; stochastic floor(x/s + u) errs < 1 step
    xb = np.asarray(x).reshape(-1, 512)
    step = np.abs(xb).max(axis=1, keepdims=True) / qmax
    err = np.abs(np.asarray(xh).reshape(xb.shape) - xb)
    assert np.all(err <= step * (1 + 1e-6))


def test_quantize_stochastic_rounding_unbiased():
    x = jnp.full((20_000,), 0.3)
    c = QuantizeCodec(bits=8)
    xh = np.asarray(c.roundtrip(x, jax.random.PRNGKey(4)))
    # E[decode] == x for stochastic rounding; mean error << one step
    step = 0.3 / 127.0
    assert abs(xh.mean() - 0.3) < 0.05 * step


def test_topk_keeps_largest_and_zeroes_rest():
    x = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 0.0, 1.0, -0.3])
    c = TopKCodec(fraction=0.25)  # k = 2
    xh = np.asarray(c.roundtrip(x, jax.random.PRNGKey(0)))
    np.testing.assert_allclose(xh, [0, -5.0, 0, 3.0, 0, 0, 0, 0])
    assert c.wire_bytes(8) == 2 * (4 + 4)  # 2 values + 2 int32 indices


def test_chained_topk_int8_composes():
    x = jax.random.normal(jax.random.PRNGKey(5), (4096,))
    chain = make_codec("topk+int8", topk_fraction=0.1)
    assert isinstance(chain, ChainedCodec) and chain.lossy
    xh = np.asarray(chain.roundtrip(x, jax.random.PRNGKey(6)))
    # survivors quantized, rest exactly zero
    assert (xh != 0).sum() <= 410
    # chain is cheaper on the wire than top-k with raw f32 values
    assert chain.wire_bytes(4096) < TopKCodec(fraction=0.1).wire_bytes(4096)


@pytest.mark.parametrize("spec,min_ratio", [("int8", 3.5), ("int4", 7.0), ("topk", 4.5)])
def test_compression_ratio_floor(spec, min_ratio):
    c = make_codec(spec, topk_fraction=0.1)
    assert c.compression_ratio(100_000) >= min_ratio


def test_make_codec_rejects_unknown():
    with pytest.raises(ValueError):
        make_codec("gzip")
    with pytest.raises(ValueError):
        TopKCodec(fraction=0.0)


def test_chain_rejects_non_float_carrier_midstage():
    # quantize ships int codes — chaining after it would mis-account bytes
    with pytest.raises(ValueError):
        make_codec("int8+topk")


def test_tree_wire_bytes_sums_leaves():
    tree = {"a": jnp.zeros((10, 10)), "b": jnp.zeros((7,))}
    assert tree_wire_bytes(Float32Identity(), tree) == 4.0 * 107


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


def test_error_feedback_converges_on_quadratic():
    """Compressed-gradient descent with EF reaches the optimum of
    f(w) = 0.5||w - w*||^2 even at aggressive top-k sparsification."""
    w_star = jax.random.normal(jax.random.PRNGKey(7), (64,))
    codec = TopKCodec(fraction=0.1)
    w = jnp.zeros((64,))
    e = jnp.zeros((64,))
    # lr must respect the sparsifier's ~1/fraction update delay — EF replays
    # suppressed coordinates as accumulated bursts, so large steps diverge
    lr = 0.05
    for t in range(500):
        grad = w - w_star
        dec, e = ef_step(codec, -lr * grad, e, jax.random.fold_in(jax.random.PRNGKey(8), t))
        w = w + dec
    assert float(jnp.linalg.norm(w - w_star)) < 1e-3
    # without EF the same codec is stuck far from the optimum
    w2 = jnp.zeros((64,))
    for t in range(500):
        grad = w2 - w_star
        w2 = w2 + codec.roundtrip(-lr * grad, jax.random.fold_in(jax.random.PRNGKey(9), t))
    assert float(jnp.linalg.norm(w - w_star)) < float(jnp.linalg.norm(w2 - w_star))


# ---------------------------------------------------------------------------
# metrics + config satellites
# ---------------------------------------------------------------------------


def test_tx_bytes_exact_beyond_2p24_params():
    from repro.core.metrics import tx_bytes

    n = 2**24 + 1  # float32 would round this to 2**24
    assert float(tx_bytes(n, directions=2)) == n * 4 * 2


def test_flconfig_zero_fraction_raises():
    from repro.fl import FLConfig

    with pytest.raises(ValueError):
        FLConfig(strategy="fedavg", fraction=0.0).strategy_obj()
    with pytest.raises(ValueError):
        FLConfig(strategy="poc", fraction=-0.5).strategy_obj()
    # explicit valid fractions still build
    FLConfig(strategy="fedavg", fraction=1.0).strategy_obj()


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_ds():
    from repro.data import make_federated_classification

    return make_federated_classification(
        n_clients=8, n_classes=4, n_features=20,
        samples_per_client_range=(60, 90), dirichlet_alpha=50.0,
        client_shift=0.05, class_sep=5.0, seed=1,
    )


def test_engine_int8_cuts_wire_bytes_at_equal_selection(small_ds):
    from repro.fl import FLConfig, run_federated

    kw = dict(strategy="fedavg", personalization="none", fraction=1.0, rounds=3, epochs=1)
    f32 = run_federated(small_ds, FLConfig(**kw))
    q8 = run_federated(small_ds, FLConfig(**kw, codec="int8"))
    np.testing.assert_array_equal(f32.selected, q8.selected)  # equal selection
    assert np.all(q8.tx_wire_bytes < f32.tx_wire_bytes)  # strictly less, every round
    assert f32.tx_bytes_cum[-1] / q8.tx_bytes_cum[-1] >= 3.5


def test_engine_acspfl_int8_accuracy_parity(small_ds):
    """Acceptance criterion at test scale: acsp-fl+dld with int8 lands
    >=3.5x fewer cumulative wire bytes within 2 accuracy points of f32."""
    from repro.fl import FLConfig, run_federated

    kw = dict(strategy="acsp-fl", personalization="dld", decay=0.01, rounds=10, epochs=2)
    f32 = run_federated(small_ds, FLConfig(**kw))
    q8 = run_federated(small_ds, FLConfig(**kw, codec="int8"))
    assert f32.tx_bytes_cum[-1] / q8.tx_bytes_cum[-1] >= 3.5
    assert abs(f32.accuracy_mean[-1] - q8.accuracy_mean[-1]) <= 0.02


def test_engine_identity_codec_matches_analytic_accounting(small_ds):
    from repro.core.metrics import BYTES_PER_PARAM
    from repro.fl import FLConfig, run_federated

    h = run_federated(small_ds, FLConfig(strategy="acsp-fl", personalization="dld", rounds=4, epochs=1))
    np.testing.assert_allclose(h.tx_wire_bytes, h.tx_params * BYTES_PER_PARAM, rtol=1e-6)


def test_engine_topk_chain_runs(small_ds):
    from repro.fl import FLConfig, run_federated

    h = run_federated(
        small_ds,
        FLConfig(strategy="acsp-fl", personalization="dld", rounds=6, epochs=2,
                 codec="topk+int8", topk_fraction=0.25),
    )
    assert np.isfinite(h.accuracy_mean).all()
    assert h.accuracy_mean[-1] > 0.5  # still learns through the chain


# ---------------------------------------------------------------------------
# cross-silo quantized all-reduce
# ---------------------------------------------------------------------------


def test_quantized_silo_aggregate_close_to_fp32():
    from repro.fl.cross_silo import _agg_over_silo

    x = jax.random.normal(jax.random.PRNGKey(11), (4, 6, 33))
    w = jnp.asarray([1.0, 2.0, 0.0, 1.0])
    ref = np.asarray(_agg_over_silo(x, w, agg="fp32"))
    q = np.asarray(_agg_over_silo(x, w, agg="int8"))
    step = np.abs(np.asarray(x)).max() / 127.0
    assert np.max(np.abs(ref - q)) <= 2 * step
    # silo axis still broadcast back identically
    for i in range(1, 4):
        np.testing.assert_array_equal(q[i], q[0])


# ---------------------------------------------------------------------------
# int4 physical nibble packing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [7, 512, 1000, 4096])
def test_int4_packed_roundtrip_parity(n):
    """Packing two nibbles per byte is wire-transparent: decode(encode(x))
    equals the unpacked int8-lane reference path exactly."""
    from repro.comm.codec import _pack_nibbles, _unpack_nibbles

    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 2.0
    c = QuantizeCodec(bits=4)
    noise = jax.random.uniform(jax.random.PRNGKey(1), (n,))
    q, s = quantize(x, noise, bits=4)
    payload, carrier = c.encode(x, jax.random.PRNGKey(1))
    assert carrier.dtype == jnp.uint8 and carrier.shape == ((n + 1) // 2,)
    np.testing.assert_array_equal(np.asarray(c.decode(payload, carrier)),
                                  np.asarray(dequantize(q, s)))
    # pack/unpack is an exact bijection on the code lane
    np.testing.assert_array_equal(np.asarray(_unpack_nibbles(_pack_nibbles(q), n)),
                                  np.asarray(q))


def test_int4_wire_accounting_is_physical():
    """wire_bytes charges ceil(n/2) carrier bytes (packed), not 0.5/param."""
    c = QuantizeCodec(bits=4)
    for n in (1000, 1001):
        assert c.wire_bytes(n) == (n + 1) // 2 + c.meta_bytes(n)
    assert c.carrier_bits() == 8.0  # a physical byte of two packed nibbles
    # int4 still compresses ~2x beyond int8 end-to-end
    assert c.compression_ratio(100_000) > 1.9 * QuantizeCodec(bits=8).compression_ratio(100_000) / 2
    assert c.compression_ratio(100_000) >= 7.0


def test_int4_chain_and_engine_path(small_ds):
    """topk+int4 chains (packed carrier is terminal) and runs end-to-end."""
    from repro.fl import FLConfig, run_federated

    chain = make_codec("topk+int4", topk_fraction=0.25)
    x = jax.random.normal(jax.random.PRNGKey(5), (2048,))
    xh = np.asarray(chain.roundtrip(x, jax.random.PRNGKey(6)))
    assert (xh != 0).sum() <= 520
    h = run_federated(
        small_ds,
        FLConfig(strategy="acsp-fl", personalization="dld", rounds=4, epochs=1,
                 codec="int4"),
    )
    assert np.isfinite(h.accuracy_mean).all()
    # physical int4 wire bytes land under the int8 run's
    h8 = run_federated(
        small_ds,
        FLConfig(strategy="acsp-fl", personalization="dld", rounds=4, epochs=1,
                 codec="int8"),
    )
    assert h.tx_bytes_cum[-1] < h8.tx_bytes_cum[-1]
