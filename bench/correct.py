"""What decides ``correct``: the candidate's first rounds against the
plain reference (``bench.reference``).

The candidate is whatever produced the per-round outputs that the window's
first hook received: the program in a benchmark run, or the reference put
in its place for the control and the planted faults (``bench.control``).
Three numbers are compared, each with a limit from the workload file:

- ``decision_errors``: lanes whose selection or share depth breaks the
  ACSP-FL / DLD laws applied to the candidate's own accuracies, over every
  round of the first chunk (async: every dispatch of the first events).
  An exact check: limit 0.
- ``norm_gap``: over rounds 0..3 and every client (async: every client
  that landed), the gap between the candidate's uplink update norm and the
  reference's, over the larger of the reference's norm and that round's
  median norm. Covers the personalizer's model build, local SGD, the codec
  with error feedback, the finite guard and (from round 1) the
  aggregation. From round 1 on, two runs that differ by rounding start a
  round from models one ulp apart, and a ReLU whose input sits within that
  of zero flips a gradient term, so this number swings from seed to seed.
- ``norm_gap0``: the same in round 0 alone, where every client starts from
  the same initial model: steady from seed to seed, and what a lower
  precision fails.
- ``acc_gap``: over rounds 0..3 and every client, the gap between the
  candidate's and the reference's evaluation accuracy, in test samples.
"""

from __future__ import annotations

import numpy as np

from bench import reference

COMPARED_ROUNDS = 4


def numbers(outs: dict, data, seed: int, recipe: dict, sizes, decisions=None) -> dict:
    """``outs`` holds (T, C) arrays ``acc``, ``sel``, ``pms``, ``norm`` of
    the candidate's first T >= 4 rounds; under the async scheduler
    ``decisions`` holds its landings and dispatches (``harness.Window``),
    which the reference follows, and ``norm`` is compared for the clients
    that landed."""
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


def judge(found: dict, limits: dict) -> bool:
    return all(found[k] <= limits[k] for k in limits)


def limit_lines(found: dict, limits: dict) -> list[str]:
    return [f"{k} {found[k]!r} limit {limits[k]!r}" for k in limits]
