"""DeepSeek-V2-Lite through the federated path, at a small size on the CPU,
against the one plain reference (``bench/models/deepseek_v2_lite_reference.py``):
the published config, MLA with its latent norm and YaRN, the dropless
held-expert layer, the expert-parallel share identity, the model's loss and
gradients, and ``run_federated`` at one and at four (forced host) devices
against the model module's compared numbers.

Tolerances: program and reference both compute in float32 at ``highest``
and differ only in association (chunked online softmax against a plain
softmax, the held experts batched against one after another, block-wise
backpropagation against one gradient), which leaves
relative gaps of about 1e-6; each tolerance below is set a decade above
what was seen and far below what a missing term (a norm, the YaRN scale, a
gate renormalisation) gives.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, models  # noqa: E402
from bench import run as bench_run  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import deepseek  # noqa: E402
from repro.models import layers as L  # noqa: E402

from _subproc import run_forced  # noqa: E402

CELL = "deepseek-v2-lite.acsp-f32.silo4"
# the catalog's DeepSeek-V2-Lite config.json (model-configs catalog)
CATALOG = {
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "moe_intermediate_size": 1408, "n_routed_experts": 64, "n_shared_experts": 2,
    "num_attention_heads": 16, "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "vocab_size": 102400, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-6, "norm_topk_prob": False, "routed_scaling_factor": 1,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096},
}
# a DeepSeek-shaped model small enough for the CPU: 16 routed experts of
# which a share holds 4, top-6, the published structure otherwise
SMALL = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, kv_lora_rank=32, intermediate_size=96, moe_intermediate_size=32,
             n_routed_experts=4, vocab_size=256, sequence_length=32, num_hidden_layers=3)
SMALL_EXPERTS = 16


@pytest.fixture(scope="module")
def model():
    return models.load("deepseek-v2-lite", bench_run.ROOT)


@pytest.fixture(scope="module")
def ref(model):
    return model.ref


@pytest.fixture(scope="module")
def highest():
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", before)


def small_config():
    config = bench_run.load_json(ROOT / "bench" / "configs" / "deepseek-v2-lite.json")
    config.update(SMALL)
    config["published"] = dict(config["published"], n_routed_experts=SMALL_EXPERTS)
    return config


def small_program_config():
    """The program's published config at SMALL's widths."""
    c = SMALL
    return dataclasses.replace(
        get_config("deepseek-v2-lite-16b"), d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        head_dim=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        kv_lora_rank=c["kv_lora_rank"], d_ff=c["intermediate_size"],
        d_ff_expert=c["moe_intermediate_size"], n_experts=SMALL_EXPERTS, dtype="float32",
    )


def to_reference(layers):
    """The program's layered parameters in the reference's layout."""
    out = [layers[0]]
    for b in layers[1:-1]:
        r = {"attn_norm": b["attn_norm"], "ffn_norm": b["ffn_norm"], **b["attn"]}
        if "ffn" in b:
            r["ffn"] = b["ffn"]
        else:
            m = b["moe"]
            r.update(router=m["router"], shared=m["shared"],
                     experts={k: m[k] for k in ("wg", "wu", "wd")})
        out.append(r)
    return out + [layers[-1]]


def share(n_held=4, n_layers=3):
    return deepseek.share(small_program_config(), n_layers=n_layers, n_held=n_held,
                          vocab=SMALL["vocab_size"])


def test_published_config_pins_the_catalog():
    cfg = get_config("deepseek-v2-lite-16b")
    c = CATALOG
    got = {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "kv_lora_rank": cfg.kv_lora_rank, "moe_intermediate_size": cfg.d_ff_expert,
        "n_routed_experts": cfg.n_experts, "n_shared_experts": cfg.n_shared_experts,
        "num_attention_heads": cfg.n_heads, "num_experts_per_tok": cfg.top_k,
        "num_hidden_layers": cfg.n_layers, "num_key_value_heads": cfg.n_kv_heads,
        "qk_nope_head_dim": cfg.qk_nope_dim, "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim, "vocab_size": cfg.vocab_size,
        "first_k_dense_replace": cfg.first_dense, "rms_norm_eps": cfg.norm_eps,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "rope_scaling": {
            "beta_fast": cfg.rope_scaling.beta_fast, "beta_slow": cfg.rope_scaling.beta_slow,
            "factor": cfg.rope_scaling.factor, "mscale": cfg.rope_scaling.mscale,
            "mscale_all_dim": cfg.rope_scaling.mscale_all_dim,
            "original_max_position_embeddings":
                cfg.rope_scaling.original_max_position_embeddings,
        },
    }
    assert got == c
    assert cfg.head_dim_ == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    assert (cfg.attn_type, cfg.rope_variant, cfg.moe) == ("mla", "yarn", True)


def test_a_federated_dataset_of_sequences_counts_tokens(model):
    from repro.data.synthetic import FederatedDataset

    t = model.make_dataset(small_config())
    data = FederatedDataset(t.x_train, t.y_train, t.m_train, t.x_test, t.y_test, t.m_test,
                            n_classes=t.n_classes)
    s = SMALL["sequence_length"]
    assert data.x_train.shape == (4, 4, s) and data.y_train.shape == (4, 4, s)
    np.testing.assert_array_equal(data.n_samples, np.full(4, 4 * s))
    np.testing.assert_array_equal(data.n_samples, t.n_samples)


def test_the_chip_share_is_535m_parameters_as_the_configuration_states(model):
    config = bench_run.load_json(ROOT / "bench" / "configs" / "deepseek-v2-lite.json")
    sizes = model.layer_sizes(config)
    assert len(sizes) == config["num_hidden_layers"] + 2
    assert sum(sizes) == 535_060_992
    sh = deepseek.share(get_config("deepseek-v2-lite-16b"), n_layers=5, n_held=8, vocab=12800)
    shapes = jax.eval_shape(sh.init, jax.random.PRNGKey(0))
    built = [sum(int(np.prod(a.shape)) for a in jax.tree.leaves(layer)) for layer in shapes]
    assert built == sizes


def test_program_and_reference_draw_the_same_initial_weights(ref):
    config = small_config()
    w = ref.widths(config)
    seed = 2_147_483_901
    r_init, _ = jax.random.split(jax.random.PRNGKey(seed))
    got = to_reference(share().init(r_init))
    want = ref.init_params(seed, w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _block_inputs(ref, seed=3):
    config = small_config()
    w = ref.widths(config)
    layers = ref.init_params(seed, w)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, SMALL["sequence_length"], w.d))
    return w, layers, x


def test_mla_with_latent_norm_and_yarn_matches_the_reference(ref, highest):
    w, layers, x = _block_inputs(ref)
    p = layers[1]
    cfg = small_program_config()
    attn = {k: p[k] for k in ("wq", "wdkv", "wkr", "kv_norm", "wuk", "wuv", "wo")}
    # a latent norm scale and an input that both matter
    attn["kv_norm"] = jnp.linspace(0.5, 1.5, w.rank)
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    got, _ = L.mla_attention(attn, x, pos, cfg)
    want = ref.attention({**attn}, x, w, "highest")
    # float32 at highest, online softmax over two KV chunks against a plain
    # softmax: ~1e-7 relative seen
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
    # without the YaRN softmax scale the outputs differ far beyond that
    plain = dataclasses.replace(cfg, rope_variant="full", rope_scaling=None)
    off, _ = L.mla_attention(attn, x, pos, plain)
    assert float(jnp.max(jnp.abs(off - want))) > 100 * float(jnp.max(jnp.abs(got - want)))


def _held_layer(ref, layers, n_held, first):
    """The program's held-expert parameters for experts [first, first+n_held)
    of a reference MoE block."""
    p = layers[2]
    return {"router": p["router"], "shared": p["shared"],
            **{k: p["experts"][k][first:first + n_held] for k in ("wg", "wu", "wd")}}


def test_dropless_held_experts_under_forced_imbalance(ref, highest):
    w, layers, x = _block_inputs(ref)
    cfg = small_program_config()
    p = dict(layers[2])
    # every token routes one of its top-6 picks to held expert 0 and the
    # other five to absent experts 4-8: all of the share's load on one
    # expert, none of it dropped
    picks = jnp.asarray([0, 4, 5, 6, 7, 8])
    # (about +5 to +2.5 on those logits: larger would underflow the others to
    # exact zeros, whose ties top-k breaks by expert id)
    bias = jnp.zeros((w.d, w.experts)).at[:, picks].set(jnp.linspace(0.10, 0.05, 6))
    p["router"] = p["router"] + bias
    xs = jnp.abs(x)  # a positive input, so the bias lifts those experts for every token
    got, counts = L.moe_apply_held(_held_layer(ref, [None, None, p], w.held, 0), xs, cfg)
    want, held, max_load = ref.experts(p, xs, w, "highest")
    n = xs.shape[0] * xs.shape[1]
    assert int(counts["held"]) == int(held) == n
    assert float(counts["max_load"]) == float(max_load) == w.held  # n over a mean of n / held
    # the held experts batched against one at a time: ~1e-7 relative seen
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_held_experts_zero_the_rows_past_their_last_group(ref, highest, monkeypatch):
    """A grouped matmul on the TPU leaves the buffer's rows past the last
    group unwritten, in its forward and in its transposes: with those rows
    filled with NaN here, the layer's output and every gradient read as
    with the rows written as zeros."""
    w, layers, x = _block_inputs(ref)
    cfg = small_program_config()
    p = _held_layer(ref, layers, w.held, 0)
    ragged_dot = jax.lax.ragged_dot

    def unwritten(rows, sizes):
        return (jnp.arange(rows.shape[0]) < sizes.sum())[:, None]

    @jax.custom_vjp
    def tpu_like(lhs, rhs, sizes):
        return jnp.where(unwritten(lhs, sizes), ragged_dot(lhs, rhs, sizes), jnp.nan)

    def fwd(lhs, rhs, sizes):
        return tpu_like(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: ragged_dot(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return jnp.where(unwritten(lhs, sizes), d_lhs, jnp.nan), d_rhs, None

    tpu_like.defvjp(fwd, bwd)

    def loss(p, x):
        y, _ = L.moe_apply_held(p, x, cfg)
        return jnp.sum(jnp.sin(y))

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda lhs, rhs, sizes, **kw: tpu_like(lhs, rhs, sizes))
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_every_run_fine_tunes_the_configurations_checkpoint(model, ref, monkeypatch):
    """The cell's initial weights are those of the configuration's
    ``init_seed``, whatever a run's ``--seed``: program and reference."""
    config = small_config()
    workload = bench_run.load_json(ROOT / "bench" / "workloads" / f"{CELL}.json")
    get = deepseek.share
    monkeypatch.setattr(deepseek, "share", lambda cfg, **kw: get(small_program_config(), **kw))
    inits = [model.fl_config(workload, config, seed, 3).model.init(jax.random.PRNGKey(seed))
             for seed in (1, 2_147_483_901)]
    want = ref.init_params(config["init_seed"], ref.widths(config))
    for got in inits:
        for a, b in zip(jax.tree.leaves(to_reference(got)), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_shares_parts_add_up_to_the_uncut_layer(ref, highest):
    """Eight shares of 2 of the 16 experts: their routed parts, with the
    shared experts (which every chip computes alike) counted once, sum to
    the reference layer that holds all 16."""
    w, layers, x = _block_inputs(ref, seed=5)
    cfg = small_program_config()
    full = dataclasses.replace(w, held=SMALL_EXPERTS)
    uncut = ref.init_params(5, full)[2]
    want, _, _ = ref.experts(uncut, x, full, "highest")
    shared = ref.glu(uncut["shared"], x.reshape(-1, w.d), "highest").reshape(x.shape)
    total = shared
    for first in range(0, SMALL_EXPERTS, 2):
        part, _ = L.moe_apply_held(_held_layer(ref, [None, None, uncut], 2, first), x, cfg, first)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_model_loss_and_gradients_match_the_reference(ref, highest):
    config = small_config()
    w = ref.widths(config)
    data = ref.make_tokens(config)
    x, y, m = (jnp.asarray(a[0, :2]) for a in (data.x_train, data.y_train, data.m_train))
    sh = share()
    seed = 2_147_483_777
    params = sh.init(jax.random.split(jax.random.PRNGKey(seed))[0])
    loss, grads = jax.value_and_grad(sh.loss)(params, x, y, m)
    ref_loss, ref_grads = ref.loss_and_grads(ref.init_params(seed, w), x, y, m, w=w,
                                             precision="highest")
    # the loss: one float32 mean over 64 tokens' log-probabilities
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    # each gradient leaf, relative to its own scale: ~1e-6 seen
    for a, b in zip(jax.tree.leaves(to_reference(grads)), jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale
    acc, loss2, counts = sh.evaluate(params, x, y, m)
    assert float(loss2) == pytest.approx(float(loss), rel=1e-7)
    assert int(counts["moe_dropped"]) == 0
    assert 0 < int(counts["moe_routed_held"]) <= 2 * 6 * x.size


def test_counts_are_kept_a_round_and_skipped_rounds_count_nothing(model, highest):
    from repro.fl import FLConfig, run_federated

    data = model.make_dataset(small_config())
    cfg = FLConfig(strategy="acsp-fl", personalization="dld", rounds=3, epochs=1, batch_size=2,
                   lr=0.05, eval_every=2, model=share().fl_model())
    h = run_federated(data, cfg)
    n_pairs = 4 * SMALL["sequence_length"] * 6 * (SMALL["num_hidden_layers"] - 1)
    held = h.counts["moe_routed_held"]
    assert held.shape == (3,) and 0 < held[0] <= n_pairs and held[2] > 0
    assert held[1] == 0 and h.counts["moe_max_load"][1] == 0  # round 1: no eval
    assert not h.counts["moe_dropped"].any()


RUN_BODY = """
import dataclasses, json, sys, time
from pathlib import Path
ROOT = Path({root!r})
sys.path.insert(0, str(ROOT))
import contextlib
import numpy as np, jax
jax.config.update("jax_default_matmul_precision", "highest")
import repro.configs
import test_deepseek_fl as T
from bench import control, harness, models, run as bench_run
from repro.fl.engine import run_federated

model = models.load("deepseek-v2-lite", ROOT)
config = T.small_config()
small = T.small_program_config()
get = repro.configs.get_config
repro.configs.get_config = lambda name: small if name == "deepseek-v2-lite-16b" else get(name)
workload = bench_run.load_json(ROOT / "bench" / "workloads" / (T.CELL + ".json"))
workload["recipe"]["cohort_devices"] = {devices}
data = model.make_dataset(config)
seed = 2_147_483_777
window = harness.Window(0.0, time.perf_counter())
exchange = control.no_exchange() if {no_exchange} else contextlib.nullcontext()
try:
    with exchange:
        run_federated(data, model.fl_config(workload, config, seed, 10 ** 9), recorder=window)
except harness.WindowClosed:
    pass
model.check_widths(window.opened, config)
found = model.numbers(window.early_outs, data, seed, workload["recipe"], config)
print("FOUND", json.dumps(found))
"""

# the small model's readings from the configuration's init_seed: the
# program against the reference, norm_gap0 4.7e-8 (four devices) and 5.7e-8
# (one), norm_gap 1.0e-7, acc_gap 0; the reference at "high" in the
# program's place, norm_gap0 7.5e-7; the planted faults, 0.07 and more, or
# 11 test tokens. norm_gap0 is the number the control fails: three times
# the program's reading, a quarter of the control's.
SMALL_LIMITS = {"decision_errors": 0.0, "norm_gap0": 2e-7, "norm_gap": 1e-4, "acc_gap": 1.5}


def _found(out: str) -> dict:
    import json

    return json.loads(out.split("FOUND ", 1)[1].splitlines()[0])


@pytest.mark.parametrize("devices", [0, 4])
def test_run_federated_small_deepseek_matches_the_reference(devices):
    code = RUN_BODY.format(root=str(ROOT), devices=devices, no_exchange=False)
    found = _found(run_forced(code, n_devices=max(devices, 1)))
    assert all(found[k] <= v for k, v in SMALL_LIMITS.items()), found


def test_the_small_silo_cell_without_the_exchange_between_chips_fails():
    """Four silos one a device, every ``lax.psum`` returning its own
    shard: each device aggregates its own silo alone, which the compared
    numbers catch from round 1 on."""
    code = RUN_BODY.format(root=str(ROOT), devices=4, no_exchange=True)
    found = _found(run_forced(code, n_devices=4))
    assert any(found[k] > v for k, v in SMALL_LIMITS.items()), found


def test_the_small_control_and_faults_fail(model, highest):
    config = small_config()
    workload = bench_run.load_json(ROOT / "bench" / "workloads" / f"{CELL}.json")
    recipe = workload["recipe"]
    data = model.make_dataset(config)
    seed = 2_147_483_777
    for name in model.CANDIDATES:
        outs, _ = model.candidate(name, data, seed, recipe, config)
        found = model.numbers(outs, data, seed, recipe, config)
        assert any(found[k] > v for k, v in SMALL_LIMITS.items()), (name, found)
