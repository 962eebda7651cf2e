"""DeepSeek-V2-Lite at one chip's share, fine-tuned by cross-silo ACSP-FL:
the model of the ``deepseek-v2-lite`` configuration (see ``bench.models``
for what a model module supplies). The silos' text and the plain float32
reference are in ``deepseek_v2_lite_reference.py`` beside this file, which
imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np

from bench import reference as laws
from bench.correct import COMPARED_ROUNDS


def _reference():
    name = "bench_model_deepseek_v2_lite_reference"
    if name not in sys.modules:
        path = Path(__file__).with_name("deepseek_v2_lite_reference.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


ref = _reference()

# the reference at the precision below the configuration's float32 at
# "highest", and the reference with a fault planted
CANDIDATES = ("control", "half_batch", "answer", "unchanged")


# -- data ------------------------------------------------------------------------

def make_dataset(config: dict, seed: int | None = None):
    return ref.make_tokens(config, seed)


def data_facts(data) -> dict:
    """Valid train and test tokens per silo, and the train slab's tokens."""
    s = data.y_train.shape[-1]
    return {
        "n_train_valid": np.asarray(data.m_train).sum(axis=1) * s,
        "n_train_rows": int(data.x_train.shape[1]) * s,
        "n_test_valid": np.asarray(data.m_test).sum(axis=1) * s,
    }


# -- the program -----------------------------------------------------------------

def fl_config(workload: dict, config: dict, seed: int, rounds: int):
    """The program's FLConfig for the workload's recipe: the program's own
    DeepSeek-V2-Lite config (``repro.configs``) cut to the configuration's
    share (``repro.models.deepseek.share``), starting from the weights of
    the configuration's ``init_seed`` whatever the run's ``seed``."""
    from repro.configs import get_config
    from repro.configs.base import (
        CodecConfig, ExecutionConfig, PersonalizationConfig, SchedulerConfig,
        SelectionConfig, TrainConfig,
    )
    from repro.fl.api import FLConfig
    from repro.models import deepseek

    import jax

    r = workload["recipe"]
    model = deepseek.share(
        get_config("deepseek-v2-lite-16b"), n_layers=config["num_hidden_layers"],
        n_held=config["n_routed_experts"], vocab=config["vocab_size"],
    )
    # the silos fine-tune one checkpoint: the weights of init_seed, drawn as
    # a run draws its own from PRNGKey(seed) -> (init, loop)
    start = jax.random.split(jax.random.PRNGKey(config["init_seed"]))[0]
    fl_model = dataclasses.replace(model.fl_model(), init=lambda _rng: model.init(start))
    return FLConfig(
        selection=SelectionConfig(strategy=r["strategy"], decay=r["decay"]),
        personalization=PersonalizationConfig(mode=r["personalization"]),
        codec=CodecConfig(spec=r["codec"]),
        train=TrainConfig(rounds=rounds, epochs=r["epochs"], batch_size=r["batch_size"],
                          lr=r["lr"], seed=seed, remainder=r["remainder"]),
        scheduler=SchedulerConfig(mode=r["scheduler"]),
        execution=ExecutionConfig(cohort_size=r["cohort_size"], eval_every=r["eval_every"],
                                  scan_chunk=r["scan_chunk"],
                                  cohort_devices=r["cohort_devices"]),
        model=fl_model,
    )


def layer_sizes(config: dict) -> list[int]:
    """Parameters of each layer at the configuration's widths and cut."""
    return ref.layer_sizes(ref.widths(config))


def check_widths(opened: dict, config: dict):
    """The parameter count of each layer the program built against the
    configuration's widths at its cut."""
    prefix = np.asarray(opened["clock"].params_prefix)
    built = [int(x) for x in np.diff(prefix)]
    want = layer_sizes(config)
    if built != want:
        raise SystemExit(f"program built layers of {built} parameters, configuration says {want}")


# -- what decides correct --------------------------------------------------------

def numbers(outs: dict, data, seed: int, recipe: dict, config: dict, decisions=None) -> dict:
    """The candidate's first rounds against the reference.

    ``outs`` holds (T, C) arrays ``acc``, ``sel``, ``pms``, ``norm`` of the
    candidate's first T >= 4 rounds.

    - ``decision_errors``: silos whose selection or share depth breaks the
      ACSP-FL / DLD laws applied to the candidate's own accuracies, over
      the first rounds. An exact check: limit 0.
    - ``norm_gap0``: round 0, every silo, the gap between the candidate's
      uplink update norm and the reference's, over the larger of the
      reference's norm and the round's median: every silo starts from the
      same initial model; covers the model's forward and backward passes
      (MLA, the routed and shared experts, the head) and local SGD.
    - ``norm_gap``: the same over rounds 0..3 and the silos each round
      selected (what an unselected silo trains is thrown away, so the
      reference does not train it): from round 1 on, models a rounding
      apart, the DLD personalisation and the aggregation.
    - ``acc_gap``: over rounds 0..3 and every silo, the gap between the
      candidate's and the reference's next-token accuracy, in test tokens.
    """
    acc, sel, pms, norm = (np.asarray(outs[k]) for k in ("acc", "sel", "pms", "norm"))
    w = ref.widths(config)
    n_layers = w.blocks + 2
    r = COMPARED_ROUNDS
    errors = laws.decision_errors(acc, sel, pms, recipe["decay"], n_layers)
    ref_acc, ref_norm = ref.run(data, recipe, config, sel[:r], pms[:r])
    compared = sel[:r].astype(bool)
    median = np.asarray([np.median(row[m]) if m.any() else 0.0
                         for row, m in zip(ref_norm, compared)])
    floor = np.maximum(np.nan_to_num(ref_norm), median[:, None])
    norm_gap = np.abs(norm[:r].astype(np.float64) - ref_norm) / floor
    norm_gap = np.where(compared, np.nan_to_num(norm_gap, nan=np.inf), 0.0)
    n_test = data_facts(data)["n_test_valid"]
    acc_gap = np.abs(acc[:r].astype(np.float64) - ref_acc) * n_test[None, :]
    return {
        "decision_errors": float(errors),
        "norm_gap0": float(np.max(norm_gap[0])),
        "norm_gap": float(np.max(norm_gap)),
        "acc_gap": float(np.max(np.nan_to_num(acc_gap, nan=np.inf))),
    }


def candidate(name: str, data, seed: int, recipe: dict, config: dict, schedule=None):
    """(outs, None) of the reference put in the program's place, making its
    own decisions by the laws: ``control`` with its matmuls at ``high``
    (three bfloat16 passes), or with the fault ``name`` planted."""
    if name not in CANDIDATES:
        raise SystemExit(f"deepseek-v2-lite has no candidate {name!r}; "
                         f"it has {', '.join(CANDIDATES)}")
    precision = "high" if name == "control" else "highest"
    fault = None if name == "control" else name
    w = ref.widths(config)
    n_layers = w.blocks + 2
    run = ref.Run(data, recipe, config, precision, fault)
    c = data.n_clients
    sel, pms = np.ones((c,), bool), np.full((c,), n_layers, np.int32)
    out = {"acc": [], "sel": [], "pms": [], "norm": []}
    for t in range(COMPARED_ROUNDS):
        acc, norm = run.step(sel, pms)
        for key, value in zip(out, (acc, sel, pms, norm)):
            out[key].append(value)
        sel, pms = laws.acsp_select(acc, t, recipe["decay"]), laws.dld_layers(acc, n_layers)
    return {k: np.stack(v) for k, v in out.items()}, None


# -- work over the rounds of a traced window -------------------------------------

def active_weights(config: dict) -> float:
    """Weights a token multiplies by, at the share: the head, MLA, the dense
    GLU, the router, the shared experts, and the routed experts at
    top-k x held / routed of the experts held (the embedding is a lookup)."""
    w = ref.widths(config)
    mla = (w.d * w.heads * (w.nope + w.rope) + w.d * (w.rank + w.rope)
           + w.rank * w.heads * (w.nope + w.v) + w.heads * w.v * w.d)
    routed = w.top_k * 3 * w.d * w.expert / w.experts  # an expected share
    total = w.d * w.vocab + w.blocks * mla
    for i in range(w.blocks):
        if i < w.first_dense:
            total += 3 * w.d * w.dense
        else:
            total += w.d * w.experts + 3 * w.d * w.shared + routed * w.held
    return float(total)


def attention_flops(config: dict) -> float:
    """Forward FLOPs of the attention scores and their values for one
    sequence of ``sequence_length`` tokens, causal, over every block."""
    w = ref.widths(config)
    s = config["sequence_length"]
    return float(w.blocks * 2 * w.heads * (w.nope + w.rope + w.v) * s * (s + 1) / 2)


def round_flops(facts) -> float:
    """Useful model FLOPs of the window's rounds: each executed (selected)
    silo's trained tokens (whole batches of the slab, ``epochs`` passes) at
    3 x the forward pass, and every silo's test tokens at one forward pass;
    the forward pass is 2 FLOPs a weight a token plus the attention scores.
    Recomputed activations do not count."""
    config, r = facts.config, facts.recipe
    s = config["sequence_length"]
    fwd_token = 2.0 * active_weights(config)
    fwd_seq = fwd_token * s + attention_flops(config)
    rows = facts.n_train_rows // s
    trained = np.minimum(np.asarray(facts.n_train_valid) // s,
                         max(1, rows // r["batch_size"]) * r["batch_size"]) * r["epochs"]
    sel = np.asarray(facts.sel, bool)
    train = 3.0 * fwd_seq * float((sel * trained[None, :]).sum())
    evaluate = float(sel.shape[0]) * fwd_seq * float(np.sum(np.asarray(facts.n_test_valid) // s))
    return train + evaluate
