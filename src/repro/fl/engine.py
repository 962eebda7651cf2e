"""Federated simulation entry point — config plumbing + host-side history.

The round itself is the composable phase pipeline (repro.fl.api /
repro.fl.phases):

  Personalizer -> LocalTrainer -> TransmitPhase (wire codec + EF)
               -> Aggregator -> Evaluator -> SelectorPhase -> LayerPolicy

executed through the **cohort runtime** (repro.fl.cohort): selection
resolves to a fixed-size index set of at most
``ExecutionConfig.cohort_size`` client ids, the engine gathers exactly
those clients' data shards, local/personalized params, and EF residuals
into ``(K, ...)`` lanes with ``jnp.take``, runs the compute phases on
them, and scatters the results back into the ``(C, ...)`` server state
with ``.at[idx].set`` — per-round training compute and trained-state
memory are O(cohort), not O(population), which is what lets adaptive
selection's shrinking cohorts (the paper's §4 headline) translate into
real step-time and memory wins at large C (see benchmarks/scale_bench.py).
``cohort_size=0`` (default) executes the full population and is
bit-identical to the dense pre-cohort engine. Full-population evaluation
can be thinned with ``ExecutionConfig.eval_every`` (last-known
accuracy/loss carried between evals).

The server loop that drives the step lives in the scheduler layer
(repro.fl.sched): ``cfg.scheduler.mode`` picks between the paper's
synchronous barrier (``SyncScheduler`` — Algorithm 1, round time = slowest
selected client) and FedBuff-style event-driven buffered execution
(``AsyncScheduler`` — aggregate as soon as ``buffer_k`` updates land, with
staleness-weighted merging, over at most
``SchedulerConfig.max_concurrency`` in-flight dispatch slots).

The synchronous loop is **round-fused**: ``ExecutionConfig.scan_chunk``
rounds run as one ``lax.scan`` entirely on device (``api.build_chunk_step``),
so the host pays one dispatch, one blocking ``device_get`` of the stacked
``(T_chunk, ...)`` history leaves, and one vectorized numpy accounting
pass per *chunk* instead of per round — at large chunk sizes wall-clock
tracks device compute, not Python dispatch overhead (see
benchmarks/loop_bench.py + BENCH_loop.json). The fused step donates the
carried round state, updating the ``(C, ...)`` server slabs in place;
donation invalidates the previous chunk's state buffers, so anything that
drives chunk steps directly must treat its input state as consumed.
``scan_chunk=1`` (default) keeps per-round host sync; every chunk size is
bit-identical to it. ``run_federated`` is the stable entry point that
builds the default pipeline from an ``FLConfig`` and delegates to the
configured scheduler; ``make_round_step`` exposes the (un-jitted)
synchronous round step for callers that drive it themselves.

Uplink traffic goes through a wire codec (repro.comm): each selected
client's shared delta is encode/decode round-tripped (with per-client
error-feedback residuals carried in the round state for lossy codecs), and
``FLHistory.tx_bytes_cum`` / ``round_time`` account codec-reported wire
bytes. Under the async scheduler the same codec path carries each landing
client's delta, so async + compression + cost-aware selection compose.

Variant map (paper §4.4 naming):
  ND    — strategy selection, NO personalization, NO decay, full model shared
  FT    — fine-tuning personalization (Eq. 8), full model shared
  PMS k — first k layers shared, rest personalized locally
  DLD   — per-client dynamic layer count (Eq. 9)
Baselines (FedAvg / POC / Oort / DEEV) use personalization='none',
share all layers, and their own selection strategy.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

from repro.core.metrics import CommModel
from repro.data.synthetic import FederatedDataset
from repro.fl.api import (
    FLConfig,
    RoundPipeline,
    build_env,
    build_round_step,
    model_functions,
    pipeline_from_config,
)
from repro.models.mlp import mlp_accuracy, mlp_loss

__all__ = ["FLConfig", "FLHistory", "make_round_step", "run_federated"]


class FLHistory(NamedTuple):
    """Per-round records (numpy, host-side). Under the async scheduler a
    "round" is one aggregation event (``buffer_k`` landed updates)."""

    accuracy_mean: np.ndarray      # (T,)
    accuracy_per_client: np.ndarray  # (T, C)
    selected: np.ndarray           # (T, C) bool — sync: cohort; async: landers
    tx_params: np.ndarray          # (T,) uplink parameter count
    tx_bytes_cum: np.ndarray       # (T,) cumulative uplink *wire* bytes
    round_time: np.ndarray         # (T,) simulated seconds per round/event
    pms: np.ndarray                # (T, C) layers shared per client
    tx_wire_bytes: np.ndarray      # (T,) per-round uplink wire bytes (codec)
    sim_clock: np.ndarray          # (T,) simulated clock at each aggregation
    staleness_mean: np.ndarray     # (T,) mean staleness of merged updates
                                   # (identically 0 under the sync barrier)
    in_flight: np.ndarray          # (T,) executing client lanes — ALWAYS
                                   # populated: the cohort size K under the
                                   # sync barrier, clients in flight after
                                   # dispatch under async (never exceeds
                                   # max_concurrency)
    tx_edge_bytes: np.ndarray | None = None
                                   # (T, E) edge->server hop bytes when
                                   # two-level aggregation is on
                                   # (ExecutionConfig.edge_groups >= 1);
                                   # None on flat runs. The client uplink
                                   # (hop 1) stays in tx_bytes_cum /
                                   # tx_wire_bytes, so flat accounting is
                                   # unchanged by the extra tier.
    rejected_updates: np.ndarray | None = None
                                   # (T,) client updates zero-masked by the
                                   # finite-delta guard (NaN/Inf or norm
                                   # explosion past faults.max_update_norm)
                                   # before aggregation; None only on
                                   # history producers that predate the
                                   # guard (identically 0 on healthy runs).
    counts: dict | None = None
                                   # {name: (T,)} the model's per-round counts
                                   # (``FLModel.counts``, from the evaluation
                                   # pass, folded over the clients); None for
                                   # a model without counts, such as the MLP.


def make_round_step(
    data: FederatedDataset,
    cfg: FLConfig,
    loss_fn: Callable = mlp_loss,
    acc_fn: Callable = mlp_accuracy,
    pipeline: RoundPipeline | None = None,
):
    """Build the synchronous round step (un-jitted — wrap in ``jax.jit``
    or fuse with ``api.build_chunk_step``): the cfg's default pipeline (or
    a custom one) composed over the static data/config environment,
    executing on ``cfg.execution.cohort_size`` gathered lanes. With
    ``cfg.execution.cohort_devices != 0`` the returned step is the
    cohort-sharded variant (repro.fl.shard): same signature, compute
    phases shard_mapped K/D lanes per device over a ``cohort`` mesh."""
    pipeline = pipeline or pipeline_from_config(cfg)
    _, loss_fn, acc_fn, model = model_functions(cfg, None, loss_fn, acc_fn)
    env = build_env(data, cfg.seed, loss_fn=loss_fn, acc_fn=acc_fn, model=model)
    return build_round_step(env, pipeline, cfg.execution)


def run_federated(
    data: FederatedDataset,
    cfg: FLConfig,
    init_fn: Callable | None = None,
    loss_fn: Callable = mlp_loss,
    acc_fn: Callable = mlp_accuracy,
    comm: CommModel | None = None,
    progress: bool = False,
    pipeline: RoundPipeline | None = None,
    client_delay: np.ndarray | None = None,
    recorder=None,
    checkpoint_every: int = 0,
    resume_from: str | None = None,
    checkpoint_dir: str | None = None,
) -> FLHistory:
    """Run ``cfg.rounds`` federated rounds (sync) or aggregation events
    (async) under the configured scheduler; returns host-side history.

    The model is ``cfg.model`` (an ``FLModel``) where the config names
    one; otherwise ``init_fn``/``loss_fn``/``acc_fn``, by default the
    paper's MLP built for the data's widths.

    ``client_delay`` is an optional (C,) multiplicative heterogeneity lane
    for the simulated clock (stragglers); by default it is derived from
    ``cfg.scheduler.heterogeneity`` (0 = uniform clocks, the seed
    behaviour).

    ``recorder`` is an optional ``repro.obs.RunRecorder``: the scheduler
    feeds it per-round metric streams, optional simulated-clock trace
    events, and wall-clock profiling, and closes it with the returned
    history. Observation is pure host-side — a recorded run's device
    trajectory (and the committed goldens) is bit-identical to an
    unrecorded one — and ``recorder=None`` (default) costs nothing.

    ``checkpoint_every=n`` snapshots the full resumable run state (round
    state with its rng chain, host accounting history, and — on the host
    population plane — the ``PopulationStore`` lanes) into
    ``checkpoint_dir`` every n rounds through ``repro.checkpoint``;
    ``resume_from=dir`` restarts from the latest snapshot there and
    continues to ``cfg.rounds``, bit-identical to the uninterrupted run.
    ``resume_from`` doubles as the write directory when ``checkpoint_dir``
    is unset, so an interrupted run resumes AND keeps checkpointing with
    one flag.
    """
    from repro.fl.sched import make_scheduler

    return make_scheduler(cfg).run(
        data,
        cfg,
        init_fn=init_fn,
        loss_fn=loss_fn,
        acc_fn=acc_fn,
        comm=comm,
        progress=progress,
        pipeline=pipeline,
        client_delay=client_delay,
        recorder=recorder,
        checkpoint_every=checkpoint_every,
        checkpoint_dir=checkpoint_dir,
        resume_from=resume_from,
    )
