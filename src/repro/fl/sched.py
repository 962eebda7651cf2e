"""Event-driven round schedulers — the host-side server loops.

The round *pipeline* (repro.fl.api / repro.fl.phases) defines what one
aggregation does; this module decides *when* aggregations happen on a
simulated clock whose per-client completion times come from
``CommModel.client_times`` (codec-compressed uplink + training flops,
optionally scaled by a per-client heterogeneity lane):

- ``SyncScheduler`` — the paper's Algorithm 1 barrier: every selected
  client finishes before the server aggregates, so each round costs the
  slowest straggler. Reproduces the pre-scheduler engine loop
  bit-identically (guarded by the golden trajectories in
  tests/test_fl_api.py and tests/test_sched.py).

  Execution is **round-fused**: the server loop runs ``lax.scan`` over
  chunks of ``ExecutionConfig.scan_chunk`` rounds entirely on device
  (``api.build_chunk_step``). The host syncs ONCE per chunk — one
  executable dispatch, one blocking ``device_get`` of the stacked
  ``(T_chunk, ...)`` out leaves, one vectorized numpy pass for all
  accounting (wire bytes, FLOPs, ``CommModel.round_times``) — instead of
  paying Python dispatch + blocking fetch + numpy<->jnp churn every round.
  The chunk step donates the carried ``RoundState``, so the ``(C, ...)``
  server slabs (local params, EF residuals, per-client vectors) are
  updated in place; donation invalidates the *previous* chunk's state
  buffers, which is safe because the scheduler reassigns ``state`` and
  only ever reads history from the fetched out stack. ``scan_chunk=1``
  (default) dispatches the plain jitted round step — the pre-fusion
  device execution bit-for-bit (round-time accounting runs through the
  vectorized float64 pass on every path); any fused chunk size is
  bit-identical to it
  (golden-guarded, including non-divisor tail chunks; the one carve-out
  is the ``eval_every > 1`` cond branch, within 1 ulp — see
  ``api.build_chunk_step``). ``progress=True`` prints at chunk
  boundaries — rounds inside a chunk are not host-visible until the
  chunk completes.

- ``AsyncScheduler`` — FedBuff-style buffered execution (Nguyen et al.
  2022) over a fixed pool of ``M = SchedulerConfig.max_concurrency``
  dispatch slots (0 -> M = C): each slot holds one in-flight client's id,
  model snapshot, and share depth, so dispatch state and per-event compute
  are O(M) regardless of the population. Clients finish after their
  simulated completion time; the server aggregates as soon as ``buffer_k``
  updates land, merging each delta with a staleness discount
  (``phases.StalenessAggregator``), then assigns freed slots to the idle
  clients the selector wants next — at most M clients are ever in flight
  (the FedBuff concurrency cap), decoupled from how many clients selection
  scores. Wire traffic rides the same codec path (per-client EF residuals
  included), so async + compression + cost-aware selection compose.

Both schedulers execute rounds through the cohort runtime (repro.fl.cohort
gather/scatter): the sync step gathers the ``cohort_size`` selected
clients' lanes per round, the async step's cohort lanes *are* the M
dispatch slots. Both expose ``run(data, cfg, ...) -> FLHistory`` and are
picked by ``make_scheduler(cfg)`` from ``cfg.scheduler.mode``;
``repro.fl.engine.run_federated`` is the stable entry point that delegates
here.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import (
    load_fl_state,
    load_host_arrays,
    save_fl_state,
    save_host_arrays,
)
from repro.comm import Codec, tree_wire_bytes
from repro.core.aggregation import finite_update_guard, transmitted_parameters
from repro.core.layersharing import layer_param_sizes, layer_share_mask
from repro.core.metrics import (
    BYTES_PER_PARAM,
    CommModel,
    edge_hop_bytes,
    edge_partition,
)
from repro.data.synthetic import FederatedDataset
from repro.fl import phases
from repro.fl.api import (
    FLConfig,
    RoundPipeline,
    RoundState,
    build_chunk_step,
    build_env,
    build_round_step,
    pipeline_from_config,
)
from repro.fl.cohort import tree_scatter, tree_take
from repro.fl.faults import compile_fault_plan
from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss
from repro.obs.profile import phase_timer
from repro.obs.record import format_async_progress, format_sync_progress

__all__ = [
    "AsyncScheduler",
    "AsyncState",
    "ClientClock",
    "EventQueue",
    "SyncScheduler",
    "build_async_step",
    "make_scheduler",
]


# ---------------------------------------------------------------------------
# simulated event clock
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ClientClock:
    """Per-client completion-time sampler for the simulated event clock.

    Durations are static per (codec, model, pms): cumulative per-layer
    parameter and wire-byte prefixes turn the per-round
    ``(pms > arange) @ sizes`` matmul the seed loop recomputed every round
    into a single prefix lookup, computed once per experiment.

    The (C,) delay lane is **lazy**: on the homogeneous default
    (``heterogeneity=0``) nothing per-client is ever materialized, so a
    C=10^6 clock constructs in O(1) and ``durations`` over a slot subset
    (``cids``) touches O(|subset|) — the population tier's event clock
    never pays O(C) per event.
    """

    comm: CommModel
    n_samples: np.ndarray      # (C,) float64 — |d_i|
    epochs: int
    params_prefix: np.ndarray  # (L+1,) — params in the first k layers
    wire_prefix: np.ndarray    # (L+1,) float64 — codec uplink wire bytes
    heterogeneity: float = 0.0  # lognormal sigma; 0 = uniform clocks
    delay_seed: int = 0
    n_clients: int = 0
    _delay: np.ndarray | None = dataclasses.field(default=None, repr=False)

    @classmethod
    def build(
        cls,
        global_params,
        codec: Codec,
        data: FederatedDataset,
        cfg: FLConfig,
        comm: CommModel,
        client_delay: np.ndarray | None = None,
    ) -> "ClientClock":
        sizes = np.asarray(jax.device_get(layer_param_sizes(global_params)))
        layer_wire = np.asarray(
            [tree_wire_bytes(codec, layer) for layer in global_params], np.float64
        )
        return cls(
            comm=comm,
            n_samples=np.asarray(data.n_samples, np.float64),
            epochs=cfg.epochs,
            params_prefix=np.concatenate([[0], np.cumsum(sizes)]),
            wire_prefix=np.concatenate([[0.0], np.cumsum(layer_wire)]),
            heterogeneity=cfg.scheduler.heterogeneity if client_delay is None else 0.0,
            delay_seed=cfg.seed,
            n_clients=data.n_clients,
            _delay=(
                np.asarray(client_delay, np.float64)
                if client_delay is not None
                else None
            ),
        )

    @property
    def delay(self) -> np.ndarray:
        """(C,) multiplicative heterogeneity lane, sampled on first use
        (same stream as always: ``default_rng(seed + 4242)``)."""
        if self._delay is None:
            if self.heterogeneity > 0.0:
                self._delay = np.random.default_rng(
                    self.delay_seed + 4242
                ).lognormal(0.0, self.heterogeneity, self.n_clients)
            else:
                self._delay = np.ones((self.n_clients,))
        return self._delay

    @property
    def uniform(self) -> bool:
        if self._delay is None:
            return self.heterogeneity == 0.0
        return bool(np.all(self._delay == 1.0))

    def shared_params(self, pms: np.ndarray) -> np.ndarray:
        """Parameter count each client shares at depth ``pms`` (any shape —
        the prefix lookup broadcasts, so a chunk's (T, C) depths batch)."""
        return self.params_prefix[np.asarray(pms)]

    def round_flops(self, pms: np.ndarray, cids: np.ndarray | None = None) -> np.ndarray:
        """Local-training FLOPs per client at share depth ``pms`` — the one
        place the compute model (fwd+bwd ~ 6 * params * samples * epochs)
        lives; ``durations`` and the schedulers' accounting both use it.
        Broadcasts like ``shared_params`` (``(T, C)`` chunk batches).
        ``cids`` restricts to a client subset: ``pms`` then carries those
        clients' depths and the sample lane is row-gathered to match."""
        n_samples = self.n_samples if cids is None else self.n_samples[np.asarray(cids)]
        return 6.0 * self.shared_params(pms) * n_samples * self.epochs

    def durations(self, pms: np.ndarray, cids: np.ndarray | None = None) -> np.ndarray:
        """Simulated seconds for one dispatch at share depth ``pms``:
        uncompressed float32 downlink + local epochs + codec-compressed
        uplink, scaled by the per-client delay lane. ``cids=None`` covers
        the whole population ((C,) result); a client-id subset computes
        only those rows — every per-client term is elementwise, so the
        subset rows are bitwise the full-lane rows."""
        params = self.shared_params(pms)
        delay = None
        if not self.uniform:
            delay = self.delay if cids is None else self.delay[np.asarray(cids)]
        return np.asarray(
            self.comm.client_times(
                self.wire_prefix[np.asarray(pms)],
                self.round_flops(pms, cids=cids),
                rx_bytes_per_client=params * float(BYTES_PER_PARAM),
                delay=delay,
            ),
            np.float64,
        )

    def component_times(self, pms: np.ndarray, cids: np.ndarray | None = None):
        """``durations`` split into ``(rx, train, total)`` per client —
        downlink, local-training, and the full dispatch->upload-done time
        (broadcasts like ``shared_params``: a chunk's (T, C) depths batch).

        The trace exporter (repro.obs) tiles each dispatch as
        ``[t, t+rx) [t+rx, t+rx+train) [t+rx+train, t+total)``: the upload
        span absorbs the float64 rounding remainder, so the triple ends
        bit-identically at the ``durations`` value the event queue used —
        per-client spans sum to the exact simulated clock the history
        reports."""
        total = self.durations(pms, cids=cids)
        rx = (
            self.shared_params(pms) * float(BYTES_PER_PARAM)
            / self.comm.bandwidth_bytes_per_s
        )
        train = self.round_flops(pms, cids=cids) / self.comm.client_flops_per_s
        if not self.uniform:
            delay = self.delay if cids is None else self.delay[np.asarray(cids)]
            rx = rx * delay
            train = train * delay
        return rx, train, total


class EventQueue:
    """Heap-backed simulated event clock over M dispatch slots.

    Replaces the per-event ``np.lexsort`` over every slot (O(M log M) per
    aggregation event, ~all of it wasted re-sorting slots that didn't
    change) with a lazily-invalidated binary heap: ``push`` on dispatch,
    ``pop_k`` the k earliest arrivals per event in O(k log M). Entries
    order by ``(finish, client id)`` — exactly the lexsort's tie-break,
    and a total order over live entries because in-flight slots always
    hold distinct clients. Re-pushing a slot bumps its generation counter,
    so a stale heap entry (from a superseded dispatch) is skipped on pop
    instead of eagerly removed. ``finish`` keeps the per-slot finish times
    current — the recorder reads the popped slots' exact queue times from
    it. Heap-vs-lexsort identity is regression-tested on randomized event
    sequences (tests/test_population.py).
    """

    def __init__(self, n_slots: int):
        self.finish = np.full((n_slots,), np.inf, np.float64)
        self._gen = np.zeros((n_slots,), np.int64)
        self._live = np.zeros((n_slots,), bool)
        self._heap: list[tuple[float, int, int, int]] = []

    def push(self, slot: int, finish: float, client: int) -> None:
        """(Re-)arm ``slot``: ``client`` finishes at simulated ``finish``."""
        self._gen[slot] += 1
        self.finish[slot] = finish
        self._live[slot] = True
        heapq.heappush(
            self._heap, (float(finish), int(client), int(slot), int(self._gen[slot]))
        )

    def pop_k(self, k: int) -> np.ndarray:
        """Slots of the k earliest live entries, in (finish, client id)
        order — the popped slots leave the queue (their clients landed)."""
        out = []
        while len(out) < k:
            _, _, slot, gen = heapq.heappop(self._heap)
            if gen == self._gen[slot] and self._live[slot]:
                self._live[slot] = False
                out.append(slot)
        return np.asarray(out, np.int64)


# ---------------------------------------------------------------------------
# shared scheduler initialization
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _RunSetup:
    """Everything both schedulers need before their first event."""

    pipeline: RoundPipeline
    comm: CommModel
    env: phases.RoundEnv
    clock: ClientClock
    g0: Any
    loc0: Any          # g0 broadcast to every client lane; None when the
                       # personalizer is stateless (no per-client model carry)
    residual0: Any     # EF residuals (lossy codec) or None
    pms0: int
    n_layers: int
    r_loop: jax.Array

    def initial_state(self) -> RoundState:
        """Round 0's carried state: every client selected, nothing
        observed yet."""
        c = self.env.n_clients
        return RoundState(
            global_params=self.g0,
            local_params=self.loc0,
            accuracy=jnp.zeros((c,)),
            select=jnp.ones((c,), bool),
            pms=jnp.full((c,), self.pms0, jnp.int32),
            rng=self.r_loop,
            residual=self.residual0,
            participation=jnp.zeros((c,), jnp.int32),
            loss=jnp.zeros((c,), jnp.float32),
            update_norm=jnp.zeros((c,), jnp.float32),
        )


def _setup_run(
    data: FederatedDataset,
    cfg: FLConfig,
    init_fn: Callable | None,
    loss_fn: Callable,
    acc_fn: Callable,
    comm: CommModel | None,
    pipeline: RoundPipeline | None,
    client_delay: np.ndarray | None,
) -> _RunSetup:
    """Shared scheduler initialization. The rng split order matches the
    pre-scheduler engine loop exactly (bit-identity depends on it)."""
    pipeline = pipeline or pipeline_from_config(cfg)
    comm = comm or CommModel()
    rng = jax.random.PRNGKey(cfg.seed)
    r_init, r_loop = jax.random.split(rng)
    if init_fn is None:
        init_fn = lambda r: init_mlp(r, data.n_features, data.n_classes)
    g0 = init_fn(r_init)
    n_layers = len(g0)
    # every client starts from the same init (paper: server broadcasts w(0));
    # stateless personalizers never read per-client locals, so the O(C)
    # model carry is skipped entirely
    loc0 = (
        jax.tree.map(
            lambda gl: jnp.broadcast_to(gl, (data.n_clients,) + gl.shape), g0
        )
        if pipeline.personalizer.stateful
        else None
    )
    residual0 = (
        jax.tree.map(
            lambda gl: jnp.zeros((data.n_clients,) + gl.shape, gl.dtype), g0
        )
        if pipeline.transmit.lossy
        else None
    )
    # Algorithm 1: round 1 selects ALL clients; the shared piece is cut from
    # the first round in PMS mode (DLD starts full: A=0 <= 0.25 -> all layers)
    pms0 = cfg.pms_layers if cfg.personalization.mode == "pms" else n_layers
    return _RunSetup(
        pipeline=pipeline,
        comm=comm,
        env=build_env(data, cfg.seed, loss_fn=loss_fn, acc_fn=acc_fn),
        clock=ClientClock.build(g0, pipeline.transmit.codec, data, cfg, comm, client_delay),
        g0=g0,
        loc0=loc0,
        residual0=residual0,
        pms0=pms0,
        n_layers=n_layers,
        r_loop=r_loop,
    )


# ---------------------------------------------------------------------------
# checkpoint/resume plumbing shared by the schedulers and host runners
# ---------------------------------------------------------------------------


def resolve_checkpoint_dir(
    checkpoint_every: int,
    checkpoint_dir: str | None,
    resume_from: str | None,
) -> str | None:
    """Where snapshots go: ``checkpoint_dir``, falling back to
    ``resume_from`` (resuming keeps appending snapshots to the same run
    directory). ``checkpoint_every > 0`` with nowhere to write is an
    error — silently not checkpointing would defeat the point."""
    directory = checkpoint_dir or resume_from
    if checkpoint_every and not directory:
        raise ValueError(
            "checkpoint_every > 0 needs checkpoint_dir= (or resume_from=, "
            "which doubles as the save directory)"
        )
    return directory


def _sync_fault_inputs(faults, seed: int, t: int, clock: ClientClock, pms_host):
    """Host-side fault resolution for one sync round: the round's compiled
    plan, the (C,) survivor mask (not crashed AND inside the deadline at
    the fault-slowed duration), and the slowed durations themselves."""
    plan = compile_fault_plan(faults, seed, t, pms_host.shape[0])
    dur = clock.durations(pms_host) * plan.slow
    alive = ~plan.crash
    if faults.deadline_s > 0.0:
        alive = alive & (dur <= faults.deadline_s)
    return plan, alive, dur


# ---------------------------------------------------------------------------
# SyncScheduler — Algorithm 1's barrier loop (bit-identical to the seed)
# ---------------------------------------------------------------------------


def _progress_rows(t0: int, n: int, chunk: int, rounds: int) -> list[int]:
    """Which rows of a fetched ``[t0, t0+n)`` chunk to print under
    ``progress=True``. At ``scan_chunk=1`` this is the legacy cadence
    (every 10th round + the final one); fused chunks print at chunk
    boundaries instead — always round 0 (first chunk) and each chunk's
    last round (which covers the final round) — so progress never silently
    disappears when 10 doesn't align with the chunk grid."""
    if chunk <= 1:
        return [i for i in range(n) if (t0 + i) % 10 == 0 or t0 + i == rounds - 1]
    rows = [0] if t0 == 0 else []
    if n - 1 not in rows:
        rows.append(n - 1)
    return rows


@dataclasses.dataclass
class SyncScheduler:
    """The synchronous barrier loop, round-fused on device: ``lax.scan``
    chunks of ``ExecutionConfig.scan_chunk`` cohort-gathered round steps
    per dispatch (``api.build_chunk_step``), round time = slowest selected
    client. The host syncs once per chunk — a single ``device_get`` of the
    stacked ``(T_chunk, ...)`` out leaves — and all per-round accounting
    (shared-param prefix lookups, FLOPs, ``CommModel.round_times``) runs as
    one vectorized numpy pass over the chunk. The chunk step donates the
    carried ``RoundState``: the ``(C, ...)`` server slabs are updated in
    place, and the previous chunk's state buffers are invalid afterwards
    (the loop below never touches them again).

    The rng chain matches the pre-scheduler engine loop, and at
    ``cohort_size=0`` (K = C) the gathered step computes the dense path's
    numbers exactly, so the committed golden trajectories (model state,
    accuracy, selection, wire/tx accounting) stay bit-identical — at every
    ``scan_chunk``, including non-divisor tail chunks (the tail compiles
    its own, shorter fused step once); with ``cohort_size=K`` the round's
    training compute and trained-state memory drop to O(K). The one
    history field computed host-side, the simulated ``round_time``, is now
    accounted in one float64 numpy pass (``CommModel.round_times``) on
    every path — values can differ from the old per-round float32
    ``round_time`` history in the low bits (~1e-7 relative)."""

    def run(
        self,
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
        checkpoint_dir: str | None = None,
        resume_from: str | None = None,
    ):
        from repro.fl.engine import FLHistory

        if cfg.execution.resolved_host_population(data.n_clients) or not hasattr(
            data, "x_train"
        ):
            # population tier: (C, ...) slabs stay host-resident, only the
            # cohort is staged on device (sharded/lazy datasets have no
            # x_train slab to build a device env from at all)
            from repro.fl.population import run_host_sync

            return run_host_sync(
                data, cfg, init_fn=init_fn, loss_fn=loss_fn, acc_fn=acc_fn,
                comm=comm, progress=progress, pipeline=pipeline,
                client_delay=client_delay, recorder=recorder,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume_from=resume_from,
            )
        faults = cfg.faults
        faulty = faults.enabled
        if faulty and cfg.execution.edge_groups >= 1:
            raise ValueError(
                "fault injection with an edge_groups topology is not "
                "supported yet; set edge_groups=0 or disable FaultConfig"
            )
        ckpt_dir = resolve_checkpoint_dir(checkpoint_every, checkpoint_dir, resume_from)
        su = _setup_run(data, cfg, init_fn, loss_fn, acc_fn, comm, pipeline, client_delay)
        comm, clock = su.comm, su.clock
        state = su.initial_state()
        round_step = build_round_step(
            su.env, su.pipeline, cfg.execution, faults=faults if faulty else None
        )
        # fault mode needs the host in the loop every round (the compiled
        # plan feeds the step's alive/corrupt lanes), so the fused chunk
        # collapses to per-round dispatch
        chunk = 1 if faulty else cfg.execution.resolved_chunk(cfg.rounds)
        # scan_chunk=1 dispatches the plain jitted round step — the exact
        # pre-fusion compilation, not a length-1 scan: XLA may fuse a
        # lax.cond branch (eval_every thinning) differently inside a scan
        # body, and the default path's DEVICE trajectory must stay
        # bit-for-bit the seed loop (host round-time accounting is the
        # float64 vectorized pass on every path — see the class docstring)
        per_round = jax.jit(round_step) if chunk <= 1 else None
        chunk_steps: dict[int, Callable] = {}  # length -> fused executable
        lanes = cfg.execution.resolved_cohort(data.n_clients)
        delay = None if clock.uniform else clock.delay
        if recorder is not None:
            recorder.open_run(mode="sync", cfg=cfg, data=data, comm=comm,
                              clock=clock, lanes=lanes,
                              # sharded steps expose their cohort mesh —
                              # run records distinguish D=1 from D=8
                              mesh=getattr(round_step, "mesh", None))
        prof = recorder.profiler if recorder is not None else None
        emit = recorder.log if recorder is not None else print
        # two-level (edge-server) topology accounting: static id partition +
        # per-layer sizes feed the (T, E) edge->server hop-byte lane
        n_edges = cfg.execution.edge_groups
        edge_ids = edge_partition(data.n_clients, n_edges) if n_edges >= 1 else None
        layer_sizes = np.diff(clock.params_prefix)
        edge_hist: list[np.ndarray] = []
        accs, sel_hist, tx_hist, pms_hist, times, wire_hist = [], [], [], [], [], []
        rejected_hist: list[np.ndarray] = []
        start = 0
        if resume_from is not None:
            # latest snapshot: RoundState through repro.checkpoint (rng
            # included), accumulated history lanes verbatim — the resumed
            # loop continues bitwise where the interrupted run stopped
            trees, meta = load_fl_state({"state": state}, resume_from)
            state = jax.tree.map(jnp.asarray, trees["state"])
            start = int(meta["round"])
            hist = load_host_arrays(resume_from, f"hist_{start:05d}")
            accs = [hist["acc"]]
            sel_hist = [hist["selected"]]
            tx_hist = [hist["tx_params"]]
            pms_hist = [hist["pms"]]
            times = [hist["round_time"]]
            wire_hist = [hist["wire"]]
            rejected_hist = [hist["rejected"]]
            if "tx_edge_bytes" in hist:
                edge_hist = [hist["tx_edge_bytes"]]
        for t0 in range(start, cfg.rounds, chunk):
            n = min(chunk, cfg.rounds - t0)
            if prof is not None:
                prof.begin_chunk(t0, n)
            if per_round is not None:
                if faulty:
                    # the fault plan is resolved host-side each round: crash
                    # + deadline survivors feed the step's alive mask, the
                    # corruption kinds ride along, and the slowed durations
                    # drive the deadline-capped round-time accounting below
                    pms_host = np.asarray(jax.device_get(state.pms))
                    sel_pre = np.asarray(jax.device_get(state.select))
                    plan, alive_np, dur_t = _sync_fault_inputs(
                        faults, cfg.seed, t0, clock, pms_host
                    )
                    if not (sel_pre & alive_np).any():
                        # a storm killed every selected client: the server
                        # re-dispatches until someone answers — run the
                        # round fault-free rather than aggregate nothing
                        alive_np = np.ones_like(alive_np)
                    extra = (
                        jnp.asarray(alive_np),
                        jnp.asarray(plan.corrupt.astype(np.int32)),
                    )
                else:
                    extra = ()
                if prof is not None and not isinstance(per_round, jax.stages.Compiled):
                    # AOT-split so compile time is attributed, not folded
                    # into the first dispatch (same executable bit-for-bit)
                    with prof.phase("compile"):
                        per_round = per_round.lower(
                            state, jnp.asarray(t0), *extra
                        ).compile()
                with phase_timer(prof, "dispatch"):
                    state, out = per_round(state, jnp.asarray(t0), *extra)
                with phase_timer(prof, "device_get"):
                    outs = jax.device_get(out)
                outs = {k: np.asarray(v)[None] for k, v in outs.items()}
            else:
                step = chunk_steps.get(n)
                if step is None:  # one trace per distinct length (body + tail)
                    if prof is not None:
                        with prof.phase("compile"):
                            step = build_chunk_step(round_step, n).lower(
                                state, jnp.arange(t0, t0 + n, dtype=jnp.int32)
                            ).compile()
                    else:
                        step = build_chunk_step(round_step, n)
                    chunk_steps[n] = step
                with phase_timer(prof, "dispatch"):
                    state, outs = step(state, jnp.arange(t0, t0 + n, dtype=jnp.int32))
                with phase_timer(prof, "device_get"):
                    outs = jax.device_get(outs)  # the ONE host sync this chunk pays
            if prof is not None:
                prof.end_chunk()
            acc = np.asarray(outs["acc"])                            # (n, C)
            sel = np.asarray(outs["selected"])                       # (n, C)
            pms = np.asarray(outs["pms"])                            # (n, C)
            wire = np.asarray(outs["wire_per_client"], np.float64)   # (n, C)
            # simulated round times, whole chunk at once: slowest selected
            # client per round — codec-compressed uplink, uncompressed
            # float32 downlink (the server broadcasts the exact global
            # model); the prefix lookup + FLOPs + round_times are a single
            # numpy pass over (n, C), no per-round numpy<->jnp churn
            per_client_params = clock.shared_params(pms)             # (n, C)
            if n_edges >= 1:
                e_bytes = edge_hop_bytes(sel, pms, layer_sizes, edge_ids, n_edges)
                edge_hist.append(e_bytes)
                rt = comm.edge_round_times(
                    wire, clock.round_flops(pms), sel, edge_ids, e_bytes,
                    rx_bytes=per_client_params * float(BYTES_PER_PARAM),
                    delay=delay,
                )
            else:
                rt = comm.round_times(
                    wire, clock.round_flops(pms), sel,
                    rx_bytes=per_client_params * float(BYTES_PER_PARAM),
                    # None on the homogeneous default: no delay lane to pay
                    delay=delay,
                )
            n_dropped = None
            if faulty:
                # the server waits on everyone it dispatched, but only up
                # to the deadline: round time = slowest *dispatched* client
                # at its fault-slowed duration, deadline-capped
                wait = dur_t[sel_pre]
                rt_t = float(wait.max()) if wait.size else 0.0
                if faults.deadline_s > 0.0:
                    rt_t = min(rt_t, faults.deadline_s)
                rt = np.asarray([rt_t + comm.server_latency_s], np.float64)
                n_dropped = int((sel_pre & ~alive_np).sum())
            rej = (
                np.asarray(outs["rejected"], np.int64)
                if "rejected" in outs
                else np.zeros((n,), np.int64)  # sharded step: no guard leaf
            )
            rejected_hist.append(rej)
            times.append(rt)
            accs.append(acc)
            sel_hist.append(sel)
            pms_hist.append(pms)
            tx_hist.append(np.asarray(outs["tx_params"], np.float64))
            wire_hist.append(wire.sum(axis=1))
            if recorder is not None:
                # one vectorized append per chunk, straight off the stacked
                # out leaves the device_get above already fetched
                recorder.on_sync_chunk(
                    t0=t0, acc=acc, sel=sel, pms=pms, wire=wire,
                    tx=tx_hist[-1], times=rt,
                    update_norm=np.asarray(outs["update_norm"]), lanes=lanes,
                    rejected=rej,
                    dropped=(
                        np.asarray([n_dropped], np.int64)
                        if n_dropped is not None
                        else None
                    ),
                )
            if progress:
                for i in _progress_rows(t0, n, chunk, cfg.rounds):
                    emit(format_sync_progress(
                        t0 + i, float(acc[i].mean()), int(sel[i].sum())
                    ))
            r = t0 + n
            if (
                ckpt_dir
                and checkpoint_every
                and r // checkpoint_every > t0 // checkpoint_every
            ):
                # snapshot at the first chunk boundary past each multiple
                # of checkpoint_every: RoundState (rng chain included) via
                # repro.checkpoint + the accumulated history lanes verbatim
                save_fl_state({"state": jax.device_get(state)}, ckpt_dir, r)
                hist_arrays = {
                    "acc": np.concatenate(accs),
                    "selected": np.concatenate(sel_hist),
                    "tx_params": np.concatenate(tx_hist),
                    "pms": np.concatenate(pms_hist),
                    "round_time": np.concatenate(times),
                    "wire": np.concatenate(wire_hist),
                    "rejected": np.concatenate(rejected_hist),
                }
                if edge_hist:
                    hist_arrays["tx_edge_bytes"] = np.concatenate(edge_hist)
                save_host_arrays(hist_arrays, ckpt_dir, f"hist_{r:05d}")

        acc_pc = np.concatenate(accs)
        wire = np.concatenate(wire_hist)
        times = np.concatenate(times)
        h = FLHistory(
            accuracy_mean=acc_pc.mean(axis=1),
            accuracy_per_client=acc_pc,
            selected=np.concatenate(sel_hist),
            tx_params=np.concatenate(tx_hist),
            tx_bytes_cum=np.cumsum(wire),
            round_time=times,
            pms=np.concatenate(pms_hist),
            tx_wire_bytes=wire,
            sim_clock=np.cumsum(times),
            staleness_mean=np.zeros_like(times),
            in_flight=np.full(times.shape, lanes, np.int64),
            tx_edge_bytes=np.concatenate(edge_hist) if n_edges >= 1 else None,
            rejected_updates=np.concatenate(rejected_hist),
        )
        if recorder is not None:
            recorder.close(h)
        return h


# ---------------------------------------------------------------------------
# AsyncScheduler — buffered staleness-weighted execution over dispatch slots
# ---------------------------------------------------------------------------


class AsyncState(NamedTuple):
    """Carried async server state (a pytree; async-step input/output).

    In-flight work lives in ``M`` fixed dispatch *slots* keyed by client id
    (``slot_client``): each slot carries the model snapshot and share depth
    its client was dispatched with, so dispatch state is O(M) — the
    population only pays for the cheap per-client vectors (plus the
    personalized-model / EF-residual carries when those features are on).
    """

    global_params: Any        # layered list, leaves (...) — current server model
    slot_params: Any          # layered list, leaves (M, ...) — the snapshot
                              # each in-flight slot's client trains from
    slot_client: jnp.ndarray  # (M,) int32 — client id occupying each slot
    slot_pms: jnp.ndarray     # (M,) int32 — share depth frozen at dispatch
    client_pms: jnp.ndarray   # (C,) int32 — share depth each client was last
                              # dispatched with (accounting + wire signals)
    local_params: Any         # layered list, leaves (C, ...); None when the
                              # personalizer is stateless
    accuracy: jnp.ndarray     # (C,) last-known distributed-eval accuracy
    loss: jnp.ndarray         # (C,) last-known eval loss
    update_norm: jnp.ndarray  # (C,) last-known compressed-delta norm
    rng: jax.Array
    residual: Any = None      # EF residuals (lossy codec only), (C, ...)
    participation: Any = None  # (C,) int32 — cumulative landings


def _lane(mask: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


def build_async_step(env: phases.RoundEnv, pipeline: RoundPipeline, faults=None):
    """Compose a RoundPipeline into the jitted buffered-aggregation step.

    The step maps ``(AsyncState, t, land, staleness, active, idle_now,
    force) -> (AsyncState, out)``. Its cohort lanes are the M dispatch
    slots: every slot trains its client's gathered data shard from the
    slot's snapshot (in-flight lanes recompute the same deterministic
    result each event; only ``land`` lanes commit), the landing deltas ride
    the wire codec with EF and merge into the global model with staleness
    weights, the population is evaluated (thinned by ``eval_every``), and
    the selector's pick among ``idle_now`` clients is assigned to the freed
    slots in ascending client-id order — at most ``min(free slots, wanted
    clients)`` dispatches, so in-flight work never exceeds M. ``force``
    guards the event queue against draining: when nothing else is in
    flight and the selector wants none of the idle clients, the landing
    slots re-dispatch their own clients.

    Every step carries the always-on finite-delta guard: landing slots
    whose transmitted ``update_norm`` is non-finite are masked out of the
    buffered merge, their local/residual state reverted, and counted in
    ``out["rejected"]``. When ``faults`` is an enabled ``FaultConfig`` the
    returned step takes one extra ``corrupt (M,) int32`` argument — the
    landing slots' corruption kinds (compiled host-side at dispatch),
    applied to the trained params before transmit so the guard is what
    rejects them; fault-off steps compile with no fault ops at all.
    """

    c = env.n_clients
    stateful = pipeline.personalizer.stateful
    faulty = faults is not None and faults.enabled
    max_norm = float(faults.max_update_norm) if faulty else 0.0
    corrupt_scale = float(faults.corrupt_scale) if faulty else 0.0

    def _async_body(
        state: AsyncState,
        t: jnp.ndarray,
        land: jnp.ndarray,        # (M,) bool — slots whose updates land now
        staleness: jnp.ndarray,   # (M,) int32 — events since slot dispatch
        active: jnp.ndarray,      # (M,) bool — slot holds an in-flight client
        idle_now: jnp.ndarray,    # (C,) bool — clients idle after landing
        force: jnp.ndarray,       # () bool — re-dispatch landers if no one else
        corrupt,                  # (M,) int32 corruption kinds or None
    ):
        g = state.global_params
        n_layers = len(g)
        cids = state.slot_client
        land = land & active
        share_m = layer_share_mask(n_layers, state.slot_pms)  # (M, L)

        if pipeline.transmit.lossy:
            rng, r_fit, r_sel, r_codec = jax.random.split(state.rng, 4)
        else:
            rng, r_fit, r_sel = jax.random.split(state.rng, 3)
            r_codec = None

        prev_part = (
            state.participation
            if state.participation is not None
            else jnp.zeros((c,), jnp.int32)
        )
        # scatter via an out-of-range sentinel so non-landing (and inactive,
        # possibly duplicate-id) slots touch nothing
        land_cid = jnp.where(land, cids, c)
        participation = prev_part.at[land_cid].add(1, mode="drop")

        menv = env.take(cids)
        cctx = phases.RoundContext(
            t=t,
            global_params=g,
            local_params=tree_take(state.local_params, cids) if stateful else None,
            select=land,
            pms=state.slot_pms,
            share=share_m,
            residual=tree_take(state.residual, cids),
            participation=jnp.take(participation, cids),
            cohort_idx=cids,
            cohort_mask=land,
            dispatch_params=state.slot_params,
            staleness=staleness,
            rng_fit=r_fit,
            rng_codec=r_codec,
            rng_sel=r_sel,
        )

        # --- each slot lane trains from its own dispatch snapshot ---
        cctx = cctx._replace(train_model=pipeline.personalizer.train_model(cctx, menv))
        cctx = pipeline.trainer.fit(cctx, menv)
        if corrupt is not None:
            # corrupt the trained params BEFORE transmit so the uploaded
            # update_norm carries the garbage — the finite guard below is
            # what rejects it (corrupt slots still land and pay wire)
            from repro.fl.faults import apply_corruption

            kinds_m = jnp.where(land, corrupt, 0)
            cctx = cctx._replace(
                trained=apply_corruption(cctx.trained, kinds_m, corrupt_scale)
            )
        if stateful:
            cctx = cctx._replace(
                new_local=jax.tree.map(
                    lambda new, old: jnp.where(_lane(land, new), new, old),
                    cctx.trained,
                    pipeline.personalizer.local_fallback(cctx, menv),
                )
            )
        # --- wire codec: landing slots' deltas vs their snapshots ---
        local_before = cctx.local_params if stateful else None
        res_before = cctx.residual
        cctx = pipeline.transmit.transmit(cctx, menv)
        # --- finite-delta guard (always on): non-finite / norm-exploded
        # landings are masked out of the merge and their state reverted ---
        ok, n_rejected = finite_update_guard(land, cctx.update_norm, max_norm)
        cctx = cctx._replace(
            select=land & ok,
            update_norm=jnp.where(ok, cctx.update_norm, jnp.take(state.update_norm, cids)),
        )
        if res_before is not None:
            cctx = cctx._replace(
                residual=jax.tree.map(
                    lambda new, old: jnp.where(_lane(ok, new), new, old),
                    cctx.residual,
                    res_before,
                )
            )
        if stateful:
            cctx = cctx._replace(
                new_local=jax.tree.map(
                    lambda new, old: jnp.where(_lane(ok, new), new, old),
                    cctx.new_local,
                    local_before,
                )
            )
        # --- staleness-weighted buffered merge into the current model ---
        cctx = pipeline.aggregator.aggregate(cctx, menv)

        # --- scatter landing lanes into the (C, ...) client state ---
        new_local = (
            tree_scatter(state.local_params, land_cid, cctx.new_local, mode="drop")
            if stateful
            else None
        )
        new_residual = tree_scatter(state.residual, land_cid, cctx.residual, mode="drop")
        update_norm = state.update_norm.at[land_cid].set(cctx.update_norm, mode="drop")
        land_c = jnp.zeros((c,), bool).at[land_cid].set(True, mode="drop")
        wire_paid_c = (
            jnp.zeros((c,), jnp.float32).at[land_cid].set(cctx.wire_paid, mode="drop")
        )
        share_c = layer_share_mask(n_layers, state.client_pms)  # (C, L)
        wire_prospective, _ = pipeline.transmit.wire_costs(g, share_c, land_c)

        # --- population phases: eval (eval_every-thinned), selection ---
        pctx = cctx._replace(
            local_params=state.local_params,
            select=land_c,
            pms=state.client_pms,
            share=share_c,
            residual=new_residual,
            participation=participation,
            cohort_idx=None,
            cohort_mask=None,
            dispatch_params=None,
            staleness=None,
            new_local=new_local,
            wire_bytes=wire_prospective,
            wire_paid=wire_paid_c,
            update_norm=update_norm,
            prev_accuracy=state.accuracy,
            prev_loss=state.loss,
        )
        if getattr(pipeline.evaluator, "eval_every", 1) == 1:
            pctx = pctx._replace(eval_model=pipeline.personalizer.eval_model(pctx, env))
            pctx = pipeline.evaluator.evaluate(pctx, env)
        else:  # thinned: the O(C) composed-model build runs inside the cond
            pctx = pipeline.evaluator.evaluate(
                pctx, env,
                model_fn=lambda ctx=pctx: pipeline.personalizer.eval_model(ctx, env),
            )
        pctx = pipeline.selector.select(pctx, env)
        pctx = pctx._replace(next_pms=pipeline.layer_policy.next_pms(pctx, env, n_layers))

        # --- slot assignment: wanted idle clients -> freed slots, ascending
        # ids on both sides; never let the queue drain ---
        want = pctx.next_select & idle_now         # (C,)
        free = land | ~active                      # (M,)
        n_assign = jnp.minimum(jnp.sum(want), jnp.sum(free))
        slot_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        cand_order = jnp.argsort(~want, stable=True)  # wanted ids first, ascending
        assigned = free & (slot_rank < n_assign)
        new_cid = jnp.take(cand_order, jnp.clip(slot_rank, 0, c - 1))
        need_force = force & (n_assign == 0)
        dispatched = jnp.where(need_force, land, assigned)
        new_slot_client = jnp.where(assigned, new_cid, cids)
        # pms is frozen at dispatch (like the snapshot): the share mask a
        # client lands with is the one its completion time was charged for
        disp_pms = jnp.take(pctx.next_pms, new_slot_client)
        new_slot_pms = jnp.where(dispatched, disp_pms, state.slot_pms)
        disp_cid = jnp.where(dispatched, new_slot_client, c)
        new_client_pms = state.client_pms.at[disp_cid].set(disp_pms, mode="drop")
        new_slot_params = jax.tree.map(
            lambda s, gl: jnp.where(_lane(dispatched, s), jnp.broadcast_to(gl, s.shape), s),
            state.slot_params,
            pctx.new_global,
        )

        land_f = land.astype(jnp.float32)
        new_state = AsyncState(
            global_params=pctx.new_global,
            slot_params=new_slot_params,
            slot_client=new_slot_client,
            slot_pms=new_slot_pms,
            client_pms=new_client_pms,
            local_params=new_local,
            accuracy=pctx.accuracy,
            loss=pctx.loss,
            update_norm=update_norm,
            rng=rng,
            residual=new_residual,
            participation=participation,
        )
        n_land = jnp.maximum(jnp.sum(land_f), 1.0)
        merge_w = (
            cctx.merge_weight
            if cctx.merge_weight is not None
            else jnp.ones_like(land_f)
        )
        out = {
            "acc": pctx.accuracy,
            "selected": land_c,
            "tx_params": transmitted_parameters(land, share_m, layer_param_sizes(g)),
            "pms": state.client_pms,
            "wire_per_client": wire_paid_c,
            "update_norm": update_norm,
            "dispatched": dispatched,
            "slot_client": new_slot_client,
            "client_pms": new_client_pms,
            "staleness_mean": jnp.sum(land_f * staleness.astype(jnp.float32)) / n_land,
            "merge_discount_mean": jnp.sum(land_f * merge_w) / n_land,
            # finite-guard rejections this event (landed slots whose
            # transmitted update failed validation)
            "rejected": n_rejected,
        }
        return new_state, out

    def async_step(state, t, land, staleness, active, idle_now, force):
        return _async_body(state, t, land, staleness, active, idle_now, force, None)

    if not faulty:
        return async_step

    def fault_async_step(state, t, land, staleness, active, idle_now, force, corrupt):
        return _async_body(state, t, land, staleness, active, idle_now, force, corrupt)

    return fault_async_step


@dataclasses.dataclass
class AsyncScheduler:
    """FedBuff-style event-driven server loop over M dispatch slots.

    A host-side event queue tracks each slot's simulated finish time
    (``ClientClock``). Each of ``cfg.rounds`` aggregation events pops the
    ``buffer_k`` earliest arrivals (fewer only if fewer are in flight),
    advances the clock to the last of them plus server latency, and runs
    the jitted async step: staleness-weighted merge, eval, selection, slot
    re-assignment. ``buffer_k=0`` (the config default) resolves to
    ``C // 2``; ``max_concurrency=0`` resolves to M = C (every client can
    be in flight, the pre-slot behaviour). With ``max_concurrency=M_c`` at
    most ``M_c`` clients are ever in flight — FedBuff's concurrency cap,
    tunable independently of how many clients the selector scores.

    The trajectory is a pure function of (data, cfg, pipeline, delays):
    device work is deterministic, and the queue breaks finish-time ties by
    (finish, client id) — ``EventQueue``'s heap order, identical to the
    original lexsort — so same seed + config => identical FLHistory.
    """

    buffer_k: int | None = None  # override; None -> cfg.scheduler.buffer_k

    def run(
        self,
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
        checkpoint_dir: str | None = None,
        resume_from: str | None = None,
    ):
        from repro.fl.engine import FLHistory

        if cfg.execution.resolved_host_population(data.n_clients) or not hasattr(
            data, "x_train"
        ):
            from repro.fl.population import run_host_async

            return run_host_async(
                data, cfg, init_fn=init_fn, loss_fn=loss_fn, acc_fn=acc_fn,
                comm=comm, progress=progress, pipeline=pipeline,
                client_delay=client_delay, recorder=recorder,
                buffer_k=self.buffer_k,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume_from=resume_from,
            )
        faults = cfg.faults
        faulty = faults.enabled
        if faulty and cfg.execution.edge_groups >= 1:
            raise ValueError(
                "fault injection with an edge_groups topology is not "
                "supported yet; set edge_groups=0 or disable FaultConfig"
            )
        ckpt_dir = resolve_checkpoint_dir(checkpoint_every, checkpoint_dir, resume_from)
        su = _setup_run(data, cfg, init_fn, loss_fn, acc_fn, comm, pipeline, client_delay)
        comm, clock_fn = su.comm, su.clock
        # fail fast on a sync-built pipeline: the barrier aggregators average
        # absolute parameters and would silently mis-merge stale snapshots
        if isinstance(
            su.pipeline.aggregator,
            (phases.FedAvgAggregator, phases.MaskedPartialAggregator),
        ):
            raise ValueError(
                "AsyncScheduler needs an aggregator that merges deltas against "
                "dispatch snapshots, got "
                f"{type(su.pipeline.aggregator).__name__}; build the pipeline "
                "from an async-mode config (scheduler.mode='async') or swap in "
                "phases.StalenessAggregator"
            )
        c = data.n_clients
        # slot count: max_concurrency is the async-specific knob; when unset,
        # ExecutionConfig.cohort_size bounds the lanes here too (the cohort
        # promise — O(K) compute — holds in both scheduler modes)
        m = min(
            cfg.scheduler.max_concurrency or cfg.execution.cohort_size or c, c
        )
        slot_client0 = np.arange(m, dtype=np.int32)
        state = AsyncState(
            global_params=su.g0,
            # Algorithm 1: the warm start dispatches w(0) — to the first M
            # clients (everyone when max_concurrency=0)
            slot_params=jax.tree.map(
                lambda gl: jnp.broadcast_to(gl, (m,) + gl.shape), su.g0
            ),
            slot_client=jnp.asarray(slot_client0),
            slot_pms=jnp.full((m,), su.pms0, jnp.int32),
            client_pms=jnp.full((c,), su.pms0, jnp.int32),
            local_params=su.loc0,
            accuracy=jnp.zeros((c,), jnp.float32),
            loss=jnp.zeros((c,), jnp.float32),
            update_norm=jnp.zeros((c,), jnp.float32),
            rng=su.r_loop,
            residual=su.residual0,
            participation=jnp.zeros((c,), jnp.int32),
        )
        step = jax.jit(
            build_async_step(su.env, su.pipeline, faults=faults if faulty else None)
        )
        buffer_k = self.buffer_k or cfg.scheduler.buffer_k or max(1, c // 2)
        deadline = float(faults.deadline_s)

        def _arm_faults(cids_arr, durations, at_version):
            """Fault-arm a dispatch batch: fault-slowed notice times,
            failure codes (0 ok / 1 crash / 2 deadline timeout), and
            corruption kinds — drawn from the plan at the dispatching
            model version, so the whole schedule is a pure function of
            (cfg, seed). Failed dispatches are noticed at
            ``min(duration, deadline)`` (an upload that never comes is
            only detectable by the deadline; without one, the crash
            surfaces when the upload attempt fails at its finish time)."""
            plan = compile_fault_plan(faults, cfg.seed, at_version, c)
            cids_arr = np.asarray(cids_arr)
            dur = durations * plan.slow[cids_arr]
            code = np.where(plan.crash[cids_arr], 1, 0).astype(np.int8)
            if deadline > 0.0:
                code = np.where((code == 0) & (dur > deadline), 2, code)
                dur = np.where(code != 0, np.minimum(dur, deadline), dur)
            kind = np.where(code == 0, plan.corrupt[cids_arr], 0).astype(np.int32)
            return dur, code, kind
        if recorder is not None:
            recorder.open_run(mode="async", cfg=cfg, data=data, comm=comm,
                              clock=clock_fn, lanes=m, buffer_k=buffer_k)
        prof = recorder.profiler if recorder is not None else None
        emit = recorder.log if recorder is not None else print

        # --- host event queue over the M slots (finish-time heap) ---
        slot_client = slot_client0.copy()
        client_pms = np.full((c,), su.pms0, np.int32)
        queue = EventQueue(m)
        slot_fail = np.zeros((m,), np.int8)
        slot_kind = np.zeros((m,), np.int32)
        retries = np.zeros((m,), np.int64)
        d0 = clock_fn.durations(client_pms[slot_client0], cids=slot_client0)
        if faulty:  # warm-start dispatches draw from the version-0 plan
            d0, slot_fail, slot_kind = _arm_faults(slot_client0, d0, 0)
        for s in range(m):
            queue.push(s, d0[s], int(slot_client0[s]))
        if recorder is not None:  # warm start: w(0) cut at simulated t=0
            recorder.on_async_dispatch(slot_client0, 0.0, client_pms)
        active = np.ones((m,), bool)
        in_flight_clients = np.zeros((c,), bool)
        in_flight_clients[slot_client0] = True
        dispatch_version = np.zeros((m,), np.int64)
        sim_clock = 0.0
        version = 0

        n_edges = cfg.execution.edge_groups
        edge_ids = edge_partition(c, n_edges) if n_edges >= 1 else None
        layer_sizes = np.diff(clock_fn.params_prefix)
        edge_hist: list[np.ndarray] = []
        accs, sel_hist, tx_hist, pms_hist = [], [], [], []
        times, wire_hist, clock_hist, stale_hist, flight_hist = [], [], [], [], []
        rejected_hist: list[int] = []
        pend_retried = pend_timeout = pend_dropped = 0
        start_t = 0
        if resume_from is not None:
            # latest snapshot: AsyncState through repro.checkpoint, every
            # host lane verbatim, and the event queue rebuilt by re-pushing
            # the in-flight slots at their saved finish times (heap order
            # is a total order over live entries, so replay is exact)
            trees, meta = load_fl_state({"state": state}, resume_from)
            state = jax.tree.map(jnp.asarray, trees["state"])
            start_t = int(meta["round"])
            sim_clock = float(meta["sim_clock"])
            version = int(meta["version"])
            host = load_host_arrays(resume_from, f"hist_{start_t:05d}")
            slot_client = host["slot_client"].astype(np.int32)
            client_pms = host["client_pms"].astype(np.int32)
            active = host["active"].astype(bool)
            in_flight_clients = host["in_flight_clients"].astype(bool)
            dispatch_version = host["dispatch_version"].astype(np.int64)
            slot_fail = host["slot_fail"].astype(np.int8)
            slot_kind = host["slot_kind"].astype(np.int32)
            retries = host["retries"].astype(np.int64)
            queue = EventQueue(m)
            for s in range(m):
                if active[s]:
                    queue.push(s, float(host["queue_finish"][s]), int(slot_client[s]))
            accs = [row for row in host["acc"]]
            sel_hist = [row for row in host["selected"]]
            tx_hist = [float(x) for x in host["tx_params"]]
            pms_hist = [row for row in host["pms"]]
            times = [float(x) for x in host["round_time"]]
            wire_hist = [float(x) for x in host["wire"]]
            clock_hist = [float(x) for x in host["sim_clock_hist"]]
            stale_hist = [float(x) for x in host["staleness"]]
            flight_hist = [int(x) for x in host["in_flight_hist"]]
            rejected_hist = [int(x) for x in host["rejected"]]
            if "tx_edge_bytes" in host:
                edge_hist = [row for row in host["tx_edge_bytes"]]
        t = start_t
        while t < cfg.rounds:
            n_active = int(active.sum())
            if n_active == 0:
                # the whole population dropped out (every slot's retries
                # exhausted): degrade gracefully — end the run with the
                # history accumulated so far instead of deadlocking
                break
            k = max(1, min(buffer_k, n_active))
            # earliest finishers land; ties break by client id (deterministic)
            landers = queue.pop_k(k)
            if faulty:
                codes = slot_fail[landers]
                ok_l = landers[codes == 0]
                bad = landers[codes != 0]
                pend_timeout += int((codes == 2).sum())
                # capture notice times BEFORE retry pushes overwrite them
                notice_max = float(queue.finish[landers].max())
                can_retry = retries[bad] < faults.max_retries
                retry_slots = bad[can_retry]
                drop_slots = bad[~can_retry]
                for s in retry_slots:
                    # exponential-backoff re-dispatch of the SAME client on
                    # the same slot and snapshot: the failure is noticed at
                    # the popped finish time, the retry starts after the
                    # backoff, with fresh fault draws at the current model
                    # version (transient slowness / crashes clear on retry)
                    retries[s] += 1
                    cid = int(slot_client[s])
                    backoff = faults.backoff_s * (2.0 ** float(retries[s] - 1))
                    d_r, code_r, kind_r = _arm_faults(
                        [cid], clock_fn.durations(client_pms[[cid]], cids=[cid]),
                        version,
                    )
                    slot_fail[s] = code_r[0]
                    slot_kind[s] = kind_r[0]
                    queue.push(s, float(queue.finish[s]) + backoff + float(d_r[0]), cid)
                pend_retried += int(retry_slots.size)
                if drop_slots.size:
                    # retries exhausted: free the slot and the client — the
                    # step's idle-assignment path backfills from selection
                    pend_dropped += int(drop_slots.size)
                    active[drop_slots] = False
                    in_flight_clients[slot_client[drop_slots]] = False
                if ok_l.size == 0 and drop_slots.size == 0:
                    continue  # pure-retry event: no aggregation happens
                landers = ok_l
                land = np.zeros((m,), bool)
                land[landers] = True
                land_finish = queue.finish[landers].copy()
                new_clock = notice_max + comm.server_latency_s
                force = bool(int((active & ~land).sum()) == 0)
            else:
                land = np.zeros((m,), bool)
                land[landers] = True
                land_finish = queue.finish[landers].copy()
                new_clock = float(land_finish.max()) + comm.server_latency_s
                force = bool(n_active - k == 0)
            staleness = np.where(land, version - dispatch_version, 0).astype(np.int32)
            landed_clients = slot_client[landers]
            idle_now = ~in_flight_clients
            idle_now[landed_clients] = True

            args = (
                state,
                jnp.asarray(t),
                jnp.asarray(land),
                jnp.asarray(staleness),
                jnp.asarray(active),
                jnp.asarray(idle_now),
                jnp.asarray(force),
            )
            if faulty:
                args = args + (jnp.asarray(slot_kind),)
            if prof is not None:
                prof.begin_chunk(t, 1)
                if not isinstance(step, jax.stages.Compiled):
                    # AOT-split so compile time is attributed, not folded
                    # into the first event's dispatch
                    with prof.phase("compile"):
                        step = step.lower(*args).compile()
            with phase_timer(prof, "dispatch"):
                state, out = step(*args)
            with phase_timer(prof, "device_get"):
                out = jax.device_get(out)
            if prof is not None:
                prof.end_chunk()

            dispatched = np.asarray(out["dispatched"])
            slot_client = np.asarray(out["slot_client"], np.int32)
            client_pms = np.asarray(out["client_pms"], np.int32)
            active = (active & ~land) | dispatched
            in_flight_clients[landed_clients] = False
            in_flight_clients[slot_client[dispatched]] = True
            # re-arm only the dispatched slots: subset-duration rows are
            # bitwise the full-lane rows (elementwise model), so the event
            # clock never materializes a (C,) vector per event
            disp_slots = np.nonzero(dispatched)[0]
            if disp_slots.size:
                disp_cids = slot_client[disp_slots]
                d_disp = clock_fn.durations(client_pms[disp_cids], cids=disp_cids)
                if faulty:
                    # fresh fault draws at the version these slots train from
                    d_disp, code_d, kind_d = _arm_faults(
                        disp_cids, d_disp, version + 1
                    )
                    slot_fail[disp_slots] = code_d
                    slot_kind[disp_slots] = kind_d
                    retries[disp_slots] = 0
                for s, f, cid in zip(disp_slots, new_clock + d_disp, disp_cids):
                    queue.push(int(s), float(f), int(cid))
            dispatch_version = np.where(dispatched, version + 1, dispatch_version)

            accs.append(out["acc"])
            sel_hist.append(np.asarray(out["selected"]))
            tx_hist.append(float(out["tx_params"]))
            pms_hist.append(out["pms"])
            if n_edges >= 1:
                # hop-2 bytes for this event's landers; the event clock
                # itself stays flat (the edge forward leg is modeled in the
                # sync barrier's round time only)
                edge_hist.append(
                    edge_hop_bytes(
                        sel_hist[-1][None], np.asarray(out["pms"])[None],
                        layer_sizes, edge_ids, n_edges,
                    )[0]
                )
            wire_hist.append(np.asarray(out["wire_per_client"], np.float64).sum())
            times.append(new_clock - sim_clock)
            clock_hist.append(new_clock)
            stale_hist.append(float(out["staleness_mean"]))
            flight_hist.append(int(in_flight_clients.sum()))
            rejected_hist.append(int(out["rejected"]) if "rejected" in out else 0)
            if recorder is not None:
                fault_kw = {}
                if faulty:
                    fault_kw = dict(
                        retried=pend_retried, timed_out=pend_timeout,
                        dropped=pend_dropped,
                    )
                recorder.on_async_event(
                    t=t, acc=np.asarray(out["acc"]), sel=sel_hist[-1],
                    tx=tx_hist[-1], pms=pms_hist[-1], wire=wire_hist[-1],
                    dt=times[-1], new_clock=new_clock,
                    staleness_mean=stale_hist[-1], in_flight=flight_hist[-1],
                    buffer_k=k, update_norm=np.asarray(out["update_norm"]),
                    merge_discount=float(out["merge_discount_mean"]),
                    landed_clients=landed_clients, landed_finish=land_finish,
                    landed_staleness=staleness[landers],
                    rejected=rejected_hist[-1], **fault_kw,
                )
                if dispatched.any():  # re-dispatches cut at the new clock
                    recorder.on_async_dispatch(
                        slot_client[dispatched], new_clock, client_pms
                    )
            pend_retried = pend_timeout = pend_dropped = 0
            sim_clock = new_clock
            version += 1
            if progress and (t % 10 == 0 or t == cfg.rounds - 1):
                emit(format_async_progress(
                    t, float(np.mean(out["acc"])), int(land.sum()),
                    new_clock, stale_hist[-1],
                ))
            t += 1
            if ckpt_dir and checkpoint_every and t % checkpoint_every == 0:
                # full resume state: AsyncState + scalars via repro.checkpoint,
                # the host dispatch plane + accumulated history verbatim
                save_fl_state(
                    {
                        "state": jax.device_get(state),
                        "sim_clock": float(sim_clock),
                        "version": int(version),
                    },
                    ckpt_dir, t,
                )
                host_arrays = {
                    "slot_client": slot_client,
                    "client_pms": client_pms,
                    "active": active,
                    "in_flight_clients": in_flight_clients,
                    "dispatch_version": dispatch_version,
                    "slot_fail": slot_fail,
                    "slot_kind": slot_kind,
                    "retries": retries,
                    "queue_finish": np.asarray(queue.finish, np.float64),
                    "acc": np.stack(accs),
                    "selected": np.stack(sel_hist),
                    "tx_params": np.asarray(tx_hist),
                    "pms": np.stack(pms_hist),
                    "round_time": np.asarray(times),
                    "wire": np.asarray(wire_hist),
                    "sim_clock_hist": np.asarray(clock_hist),
                    "staleness": np.asarray(stale_hist),
                    "in_flight_hist": np.asarray(flight_hist, np.int64),
                    "rejected": np.asarray(rejected_hist, np.int64),
                }
                if n_edges >= 1:
                    host_arrays["tx_edge_bytes"] = np.stack(edge_hist)
                save_host_arrays(host_arrays, ckpt_dir, f"hist_{t:05d}")

        acc_pc = np.stack(accs)
        wire = np.asarray(wire_hist)
        h = FLHistory(
            accuracy_mean=acc_pc.mean(axis=1),
            accuracy_per_client=acc_pc,
            selected=np.stack(sel_hist),
            tx_params=np.asarray(tx_hist),
            tx_bytes_cum=np.cumsum(wire),
            round_time=np.asarray(times),
            pms=np.stack(pms_hist),
            tx_wire_bytes=wire,
            sim_clock=np.asarray(clock_hist),
            staleness_mean=np.asarray(stale_hist),
            in_flight=np.asarray(flight_hist, np.int64),
            tx_edge_bytes=np.stack(edge_hist) if n_edges >= 1 else None,
            rejected_updates=np.asarray(rejected_hist, np.int64),
        )
        if recorder is not None:
            recorder.close(h)
        return h


def make_scheduler(cfg: FLConfig):
    """Scheduler for ``cfg.scheduler.mode`` (the engine's dispatch point)."""
    return AsyncScheduler() if cfg.scheduler.mode == "async" else SyncScheduler()
