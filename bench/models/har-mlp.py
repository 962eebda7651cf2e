"""The paper's har-mlp (3 x 256 ReLU, softmax head, local SGD) on the HAR
stand-in data: the model of the ``uci-har`` and ``motionsense``
configurations (see ``bench.models`` for what a model module supplies).
An adapter over ``bench.data`` (the generator), ``bench.reference`` (the
plain float32 reference) and ``bench.flops`` (work counted from shapes).
"""

from __future__ import annotations

import numpy as np

from bench import data as bench_data
from bench import flops, reference
from bench.correct import COMPARED_ROUNDS

# the reference at the precision below the configuration's float32 at
# "highest", and the reference with a fault planted
CANDIDATES = ("control", "half_batch", "answer", "unchanged")


def layer_sizes(config: dict) -> list[int]:
    return [config["n_features"], *config["hidden"], config["n_classes"]]


# -- data ------------------------------------------------------------------------

def make_dataset(config: dict, seed: int | None = None) -> bench_data.Data:
    return bench_data.make_dataset(config, seed)


def data_facts(data) -> dict:
    """Valid train and test samples per client, and the train slab's rows."""
    return {
        "n_train_valid": np.asarray(data.m_train).sum(axis=1),
        "n_train_rows": int(data.x_train.shape[1]),
        "n_test_valid": np.asarray(data.m_test).sum(axis=1),
    }


# -- the program -----------------------------------------------------------------

def fl_config(workload: dict, config: dict, seed: int, rounds: int):
    """The program's FLConfig for a workload's recipe; the program builds
    its default model, the paper's MLP, for the data's widths."""
    from repro.configs.base import (
        CodecConfig, ExecutionConfig, PersonalizationConfig, SchedulerConfig,
        SelectionConfig, TrainConfig,
    )
    from repro.fl.api import FLConfig

    r = workload["recipe"]
    sched = {k: r[k] for k in ("buffer_k", "max_concurrency", "staleness_fn",
                               "staleness_exponent") if k in r}
    return FLConfig(
        selection=SelectionConfig(strategy=r["strategy"], decay=r["decay"]),
        personalization=PersonalizationConfig(mode=r["personalization"]),
        codec=CodecConfig(spec=r["codec"]),
        train=TrainConfig(rounds=rounds, epochs=r["epochs"], batch_size=r["batch_size"],
                          lr=r["lr"], seed=seed, remainder=r["remainder"]),
        scheduler=SchedulerConfig(mode=r["scheduler"], **sched),
        execution=ExecutionConfig(cohort_size=r["cohort_size"], eval_every=r["eval_every"],
                                  scan_chunk=r["scan_chunk"],
                                  cohort_devices=r["cohort_devices"]),
    )


def check_widths(opened: dict, config: dict):
    """The parameter count of each layer the program built against the
    configuration's widths."""
    prefix = np.asarray(opened["clock"].params_prefix)
    built = [int(x) for x in np.diff(prefix)]
    s = layer_sizes(config)
    want = [fi * fo + fo for fi, fo in zip(s[:-1], s[1:])]
    if built != want:
        raise SystemExit(f"program built layers of {built} parameters, configuration says {want}")


# -- what decides correct --------------------------------------------------------

def numbers(outs: dict, data, seed: int, recipe: dict, config: dict, decisions=None) -> dict:
    """The candidate's first rounds against ``bench.reference``.

    ``outs`` holds (T, C) arrays ``acc``, ``sel``, ``pms``, ``norm`` of the
    candidate's first T >= 4 rounds; under the async scheduler
    ``decisions`` holds its landings and dispatches (``harness.Window``),
    which the reference follows, and ``norm`` is compared for the clients
    that landed.

    - ``decision_errors``: lanes whose selection or share depth breaks the
      ACSP-FL / DLD laws applied to the candidate's own accuracies, over
      every round of the first chunk (async: every dispatch of the first
      events). An exact check: limit 0.
    - ``norm_gap``: over rounds 0..3 and every client (async: every client
      that landed), the gap between the candidate's uplink update norm and
      the reference's, over the larger of the reference's norm and that
      round's median norm. Covers the personalizer's model build, local
      SGD, the codec with error feedback, the finite guard and (from round
      1) the aggregation. From round 1 on, two runs that differ by
      rounding start a round from models one ulp apart, and a ReLU whose
      input sits within that of zero flips a gradient term, so this number
      swings from seed to seed.
    - ``norm_gap0``: the same in round 0 alone, where every client starts
      from the same initial model: steady from seed to seed, and what a
      lower precision fails.
    - ``acc_gap``: over rounds 0..3 and every client, the gap between the
      candidate's and the reference's evaluation accuracy, in test samples.
    """
    sizes = layer_sizes(config)
    acc, sel, pms, norm = (np.asarray(outs[k]) for k in ("acc", "sel", "pms", "norm"))
    n_layers = len(sizes) - 1
    r = COMPARED_ROUNDS
    compared = np.ones(acc[:r].shape, bool)
    if recipe["scheduler"] == "async":
        ref_acc, ref_norm, errors = reference.run_async(
            data, seed, recipe, sizes, {"acc": acc[:r], "norm": norm[:r]}, decisions,
            recipe["max_concurrency"],
        )
        compared = sel[:r].astype(bool)  # the clients that landed
    else:
        errors = reference.decision_errors(acc, sel, pms, recipe["decay"], n_layers)
        ref_acc, ref_norm = reference.run(data, seed, recipe, sizes, sel[:r], pms[:r])
    median = np.asarray([np.median(row[m]) if m.any() else 0.0
                         for row, m in zip(ref_norm, compared)])
    floor = np.maximum(ref_norm, median[:, None])
    norm_gap = np.abs(norm[:r].astype(np.float64) - ref_norm) / floor
    norm_gap = np.where(compared, np.nan_to_num(norm_gap, nan=np.inf), 0.0)
    n_test = np.asarray(data.m_test).sum(axis=1)
    acc_gap = np.abs(acc[:r].astype(np.float64) - ref_acc) * n_test[None, :]
    return {
        "decision_errors": float(errors),
        "norm_gap0": float(np.max(norm_gap[0])),
        "norm_gap": float(np.max(norm_gap)),
        "acc_gap": float(np.max(np.nan_to_num(acc_gap, nan=np.inf))),
    }


def candidate(name: str, data, seed: int, recipe: dict, config: dict, schedule=None):
    """(outs, decisions) of the reference put in the program's place:
    ``control`` with its matmuls at ``high`` (three bfloat16 passes), or
    with the fault ``name`` planted (``bench.reference``). Under the async
    scheduler it follows the program's own event ``schedule`` (outs,
    decisions), which the simulated clock fixes, not the values."""
    if name not in CANDIDATES:
        raise SystemExit(f"har-mlp has no candidate {name!r}; it has {', '.join(CANDIDATES)}")
    sizes = layer_sizes(config)
    precision = "high" if name == "control" else "highest"
    fault = None if name == "control" else name
    if schedule is not None:
        outs, decisions = dict(schedule[0]), schedule[1]
        acc, norm, _ = reference.run_async(
            data, seed, recipe, sizes, outs, decisions, recipe["max_concurrency"],
            precision=precision, fault=fault,
        )
        outs.update(acc=acc, norm=norm)
        return outs, decisions
    acc, sel, pms, norm = reference.run_free(
        data, seed, recipe, sizes, COMPARED_ROUNDS, precision=precision, fault=fault
    )
    return {"acc": acc, "sel": sel, "pms": pms, "norm": norm}, None


# -- work over the rounds of a traced window -------------------------------------

def round_flops(facts) -> float:
    """Useful model FLOPs of the window's rounds (``bench.flops``)."""
    r = facts.recipe
    return flops.round_flops(
        layer_sizes(facts.config), facts.sel, facts.n_train_valid, facts.n_train_rows,
        facts.n_test_valid, r["batch_size"], r["epochs"],
    )


def codec_bytes(facts) -> float:
    """Bytes the int8 codec must move for every client lane over the
    window's rounds."""
    return flops.codec_bytes(layer_sizes(facts.config), facts.config["n_clients"]) * facts.rounds


def codec_leaves(facts) -> int:
    """Parameter leaves, each one quantize and one dequantize kernel a
    round on each chip: a weight and a bias a layer."""
    return 2 * (len(layer_sizes(facts.config)) - 1)
