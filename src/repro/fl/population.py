"""Host-resident population plane: million-client federated populations.

The device-resident schedulers (repro.fl.sched) carry every ``(C, ...)``
per-client slab — data shards, personalized models, EF residuals, the
cheap per-client vectors — as jit-carried device state. That is the right
call up to a few tens of thousands of clients; past it the device (and the
XLA donation story) becomes the population bottleneck even though each
round only ever *touches* K cohort lanes.

This module splits the population plane from the compute plane:

- ``PopulationStore`` holds all ``(C, ...)`` per-client server state in
  host numpy (optionally memory-mapped under ``backing_dir``), exposing
  ``gather(idx) -> (K, ...)`` row slabs and ``scatter(idx, rows)``
  write-back;
- ``run_host_sync`` / ``run_host_async`` mirror ``SyncScheduler.run`` /
  ``AsyncScheduler.run`` with the store as the source of truth: each
  round/event stages exactly the cohort's rows onto device (data shard,
  local params, residuals, lanes), runs the same phase pipeline inside a
  cohort-sized jit, and scatters the results back — the only *persistent*
  device arrays are the global model and the rng key, so the device
  live-array watermark is O(K + model), not O(C)
  (benchmarks/pop_bench.py measures it via ``jax.live_arrays()``).

Bit-identity: at the same (data, cfg, pipeline) the host-plane trajectory
is bit-identical to the device-resident path — the cohort jit replays the
device round step's exact phase composition and rng splits on the staged
rows, population-wide evaluation defaults to one whole-``C`` call
(``eval_chunk=0``), and selection/layer-policy run on the same device
expressions over the staged lanes (golden-guarded with
``host_population=1`` in tests/test_population.py). ``eval_chunk=n``
streams evaluation through n-lane windows for populations whose test
slabs don't fit on device; rows are vmap-independent, so chunking changes
batch shape only.

The scheduler entry points (``SyncScheduler.run`` / ``AsyncScheduler.run``)
delegate here when ``cfg.execution.resolved_host_population(C)`` is true
(forced, or C at/above the auto threshold) or when the dataset is sharded/
lazy (``repro.data.synthetic.ShardedFederatedData``) and has no eager
``x_train`` slab to build a device env from.
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import (
    load_fl_state,
    load_host_arrays,
    load_pytree,
    save_fl_state,
    save_host_arrays,
    save_pytree,
)
from repro.core.aggregation import finite_update_guard, transmitted_parameters
from repro.core.layersharing import layer_param_sizes, layer_share_mask
from repro.core.metrics import (
    BYTES_PER_PARAM,
    CommModel,
    edge_hop_bytes,
    edge_partition,
)
from repro.fl import phases
from repro.fl.api import (
    FLConfig,
    RoundPipeline,
    _tree_where,
    model_functions,
    pipeline_from_config,
)
from repro.fl.faults import apply_corruption, compile_fault_plan
from repro.fl.sched import (
    ClientClock,
    EventQueue,
    _progress_rows,
    _sync_fault_inputs,
    resolve_checkpoint_dir,
)
from repro.models.mlp import init_mlp, mlp_accuracy, mlp_loss
from repro.obs.profile import phase_timer
from repro.obs.record import format_async_progress, format_sync_progress

__all__ = ["PopulationStore", "run_host_sync", "run_host_async"]


# ---------------------------------------------------------------------------
# PopulationStore — the host-resident (C, ...) population plane
# ---------------------------------------------------------------------------


class PopulationStore:
    """All per-client server state, host-resident, gather/scatter by rows.

    Two kinds of entries:

    - ``lanes``: cheap ``(C,)`` vectors (accuracy, loss, selection, share
      depth, participation, update norms) — always plain RAM;
    - ``trees``: layered pytrees with ``(C, ...)`` leaves (personalized
      local params, EF residuals) — the heavy slabs, optionally backed by
      ``np.memmap`` files under ``backing_dir`` so a population larger
      than RAM pages from disk.

    ``gather`` returns *copies* of the requested rows (safe to mutate, safe
    to feed to jit); ``scatter`` writes rows back in place.
    ``scatter(idx, gather(idx))`` is the identity (property-tested).
    """

    def __init__(self, n_clients: int, backing_dir: str | None = None):
        self.n_clients = int(n_clients)
        self.backing_dir = backing_dir
        self.lanes: dict[str, np.ndarray] = {}
        self.trees: dict[str, Any] = {}

    # -- construction ------------------------------------------------------
    def add_lane(self, name: str, values: np.ndarray) -> None:
        values = np.asarray(values)
        if values.shape[0] != self.n_clients:
            raise ValueError(
                f"lane {name!r}: leading dim {values.shape[0]} != C={self.n_clients}"
            )
        self.lanes[name] = values

    def add_tree(self, name: str, template, init: str) -> None:
        """Allocate a (C, ...)-leaved pytree from a per-client template.

        ``init='broadcast'`` fills every row with the template leaf (the
        server's w(0) broadcast); ``init='zeros'`` zero-fills (EF
        residuals). With ``backing_dir`` set, each leaf is an
        ``open_memmap``'d ``.npy`` file — a normal array to numpy, loadable
        back with ``np.load(..., mmap_mode='r+')``.
        """
        counter = itertools.count()

        def alloc(leaf):
            leaf = np.asarray(leaf)
            shape = (self.n_clients,) + leaf.shape
            if self.backing_dir is None:
                arr = np.empty(shape, leaf.dtype)
            else:
                os.makedirs(self.backing_dir, exist_ok=True)
                arr = np.lib.format.open_memmap(
                    os.path.join(self.backing_dir, f"{name}_{next(counter)}.npy"),
                    mode="w+", dtype=leaf.dtype, shape=shape,
                )
            if init == "broadcast":
                arr[...] = leaf[None]
            else:
                arr[...] = 0
            return arr

        self.trees[name] = jax.tree.map(alloc, template)

    @classmethod
    def build(
        cls,
        n_clients: int,
        lanes: dict[str, np.ndarray],
        g0=None,
        stateful: bool = False,
        lossy: bool = False,
        backing_dir: str | None = None,
    ) -> "PopulationStore":
        """The FL server's population plane: the scheduler lanes plus the
        heavy model/residual slabs the active features need."""
        store = cls(n_clients, backing_dir=backing_dir)
        for name, values in lanes.items():
            store.add_lane(name, values)
        if g0 is not None and (stateful or lossy):
            g_np = jax.tree.map(np.asarray, jax.device_get(g0))
            if stateful:
                store.add_tree("local", g_np, init="broadcast")
            if lossy:
                store.add_tree("residual", g_np, init="zeros")
        return store

    # -- row access --------------------------------------------------------
    def gather(self, idx: np.ndarray, names: tuple[str, ...] | list[str]):
        """``{name: (K, ...) rows}`` for the cohort ``idx`` — lane rows and
        tree rows alike, copied contiguous (device staging feeds on them)."""
        idx = np.asarray(idx)
        out: dict[str, Any] = {}
        for name in names:
            if name in self.lanes:
                out[name] = self.lanes[name][idx]
            elif name in self.trees:
                out[name] = jax.tree.map(
                    lambda leaf: np.ascontiguousarray(leaf[idx]), self.trees[name]
                )
            else:
                raise KeyError(name)
        return out

    def scatter(self, idx: np.ndarray, values: dict[str, Any]) -> None:
        """Write ``(K, ...)`` rows back at ``idx`` (the cohort's results)."""
        idx = np.asarray(idx)
        for name, val in values.items():
            if name in self.lanes:
                self.lanes[name][idx] = np.asarray(val)
            elif name in self.trees:
                def put(leaf, rows):
                    leaf[idx] = np.asarray(rows)
                    return leaf

                jax.tree.map(put, self.trees[name], val)
            else:
                raise KeyError(name)

    def flush(self) -> None:
        """Flush memmap-backed slabs to disk (no-op for RAM backing)."""
        for tree in self.trees.values():
            jax.tree.map(
                lambda leaf: leaf.flush() if isinstance(leaf, np.memmap) else None,
                tree,
            )

    def nbytes(self) -> int:
        total = sum(a.nbytes for a in self.lanes.values())
        for tree in self.trees.values():
            total += sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        return total


# ---------------------------------------------------------------------------
# shared host-runner setup
# ---------------------------------------------------------------------------


def _tree_nbytes(tree) -> int:
    return sum(np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree))


def _data_shard(data, idx: np.ndarray):
    """(K, ...) data rows for client ids ``idx`` — ``shard`` is the staging
    interface both the eager and the lazy/sharded datasets expose."""
    return data.shard(np.asarray(idx))


def _delay_lane(n_clients: int, seed: int) -> np.ndarray:
    """The env's per-client analytic delay lane (Oort's systemic term),
    fetched to host once — the exact bits ``api.build_env`` would put on
    device, so selection strategies read identical values."""
    return np.asarray(
        jax.device_get(
            jax.random.uniform(
                jax.random.PRNGKey(seed + 99), (n_clients,), minval=0.5, maxval=2.0
            )
        )
    )


class _HostSetup:
    """Everything both host runners need before their first event."""

    def __init__(self, data, cfg: FLConfig, init_fn, loss_fn, acc_fn, comm,
                 pipeline, client_delay):
        self.pipeline = pipeline or pipeline_from_config(cfg)
        self.comm = comm or CommModel()
        rng = jax.random.PRNGKey(cfg.seed)
        r_init, self.r_loop = jax.random.split(rng)
        init_fn, loss_fn, acc_fn, _ = model_functions(cfg, init_fn, loss_fn, acc_fn)
        if init_fn is None:
            init_fn = lambda r: init_mlp(r, data.n_features, data.n_classes)
        self.g0 = init_fn(r_init)
        self.n_layers = len(self.g0)
        self.pms0 = (
            cfg.pms_layers if cfg.personalization.mode == "pms" else self.n_layers
        )
        self.clock = ClientClock.build(
            self.g0, self.pipeline.transmit.codec, data, cfg, self.comm, client_delay
        )
        self.loss_fn = loss_fn
        self.acc_fn = acc_fn
        # static per-layer costs, fetched once: the codec's wire bytes per
        # layer and the parameter sizes (both shape-only functions of g0)
        self.lw = np.asarray(
            jax.device_get(self.pipeline.transmit.layer_wire(self.g0)), np.float32
        )
        self.sizes = np.asarray(jax.device_get(layer_param_sizes(self.g0)))
        self.n_samples32 = np.asarray(data.n_samples, np.float32)
        self.delay_env = _delay_lane(data.n_clients, cfg.seed)

    def default_lanes(self, c: int) -> dict[str, np.ndarray]:
        return {
            "accuracy": np.zeros((c,), np.float32),
            "loss": np.zeros((c,), np.float32),
            "update_norm": np.zeros((c,), np.float32),
            "participation": np.zeros((c,), np.int32),
        }


def _restore_rows(dst, src):
    """Copy a loaded leaf back into a live store leaf in place — memmap
    leaves stay memmaps (the restored rows page straight to the backing
    files on ``flush``)."""
    dst[...] = np.asarray(src)
    return dst


def _population_plane_manifest(cfg: FLConfig, store: PopulationStore) -> dict:
    return {
        "host_population": True,
        "edge_groups": int(cfg.execution.edge_groups),
        "store_backing": (
            None if store.backing_dir is None else f"memmap:{store.backing_dir}"
        ),
    }


# ---------------------------------------------------------------------------
# jitted step builders (cohort-sized compute, population-sized signals)
# ---------------------------------------------------------------------------


def _build_cohort_step(pipeline: RoundPipeline, n_layers: int, k: int,
                       population: int, loss_fn, acc_fn, faults=None):
    """The staged-cohort compute step: the device round step's
    personalize -> fit -> transmit -> aggregate segment, replayed on the
    gathered ``(K, ...)`` rows with the same rng-lane splits. Returns the
    merged global, the cohort's new local/residual/update-norm rows, the
    finite-guard rejection count, the carried rng, and the selection key
    the population step consumes.

    Mirrors ``api.build_round_step``'s failure semantics exactly: the
    finite-delta guard is always on (same ops in the same order, so
    healthy rows stay bit-identical to the device-resident path), and an
    enabled ``faults`` adds one trailing ``corrupt_k (K,) int32`` argument
    whose kinds rewrite the trained params post-trainer."""
    stateful = pipeline.personalizer.stateful
    lossy = pipeline.transmit.lossy
    faulty = faults is not None and faults.enabled
    max_norm = float(faults.max_update_norm) if faulty else 0.0
    corrupt_scale = float(faults.corrupt_scale) if faulty else 0.0

    @phases.scoped("fl.round")
    def _cohort_body(g, rng, t, idx, cmask, pms_k, participation_k,
                     local_k, residual_k, data_k, n_samples_k, delay_k,
                     prev_un_k, corrupt_k):
        share_k = layer_share_mask(n_layers, pms_k)
        if lossy:
            rng, r_fit, r_sel, r_codec = jax.random.split(rng, 4)
        else:
            rng, r_fit, r_sel = jax.random.split(rng, 3)
            r_codec = None
        x_tr, y_tr, m_tr, x_te, y_te, m_te = data_k
        cenv = phases.RoundEnv(
            x_tr=x_tr, y_tr=y_tr, m_tr=m_tr, x_te=x_te, y_te=y_te, m_te=m_te,
            n_samples=n_samples_k, delay=delay_k, n_clients=k,
            loss_fn=loss_fn, acc_fn=acc_fn, population=population,
        )
        cctx = phases.RoundContext(
            t=t,
            global_params=g,
            local_params=local_k if stateful else None,
            select=cmask,
            pms=pms_k,
            share=share_k,
            residual=residual_k,
            participation=participation_k,
            cohort_idx=idx,
            cohort_mask=cmask,
            rng_fit=r_fit,
            rng_codec=r_codec,
            rng_sel=r_sel,
        )
        cctx = cctx._replace(train_model=pipeline.personalizer.train_model(cctx, cenv))
        cctx = pipeline.trainer.fit(cctx, cenv)
        if corrupt_k is not None:
            # corrupt the trained params BEFORE transmit so the uploaded
            # update_norm reflects the garbage and the finite guard below
            # is what rejects it — corrupt clients still pay wire
            kinds_k = jnp.where(cmask, corrupt_k, 0)
            cctx = cctx._replace(
                trained=apply_corruption(cctx.trained, kinds_k, corrupt_scale)
            )
        with jax.named_scope("fl.personalize"):
            if stateful:
                cctx = cctx._replace(
                    new_local=jax.tree.map(
                        lambda new, old: jnp.where(
                            cmask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
                        ),
                        cctx.trained,
                        pipeline.personalizer.local_fallback(cctx, cenv),
                    )
                )
        local_before = cctx.local_params if stateful else None
        res_before = cctx.residual
        cctx = pipeline.transmit.transmit(cctx, cenv)
        # finite-delta guard (always on) — same expressions as the device
        # round step, so all-finite rounds are bit-identical to it
        with jax.named_scope("fl.transmit"):
            ok, n_rejected = finite_update_guard(cmask, cctx.update_norm, max_norm)
            cctx = cctx._replace(
                select=cmask & ok,
                residual=_tree_where(ok, cctx.residual, res_before),
                update_norm=jnp.where(ok, cctx.update_norm, prev_un_k),
            )
            if stateful:
                cctx = cctx._replace(new_local=_tree_where(ok, cctx.new_local, local_before))
        cctx = pipeline.aggregator.aggregate(cctx, cenv)
        return (cctx.new_global, cctx.new_local, cctx.residual,
                cctx.update_norm, n_rejected, rng, r_sel)

    def cohort_step(g, rng, t, idx, cmask, pms_k, participation_k,
                    local_k, residual_k, data_k, n_samples_k, delay_k,
                    prev_un_k):
        return _cohort_body(g, rng, t, idx, cmask, pms_k, participation_k,
                            local_k, residual_k, data_k, n_samples_k, delay_k,
                            prev_un_k, None)

    if not faulty:
        return jax.jit(cohort_step)

    def fault_cohort_step(g, rng, t, idx, cmask, pms_k, participation_k,
                          local_k, residual_k, data_k, n_samples_k, delay_k,
                          prev_un_k, corrupt_k):
        return _cohort_body(g, rng, t, idx, cmask, pms_k, participation_k,
                            local_k, residual_k, data_k, n_samples_k, delay_k,
                            prev_un_k, corrupt_k)

    return jax.jit(fault_cohort_step)


def _build_eval_step(pipeline: RoundPipeline, n_layers: int, population: int,
                     loss_fn, acc_fn, chunk: int):
    """Streamed population evaluation over a ``chunk``-lane window: the
    window's test slab rides in as jit arguments, so device memory per call
    is O(chunk). Rows are vmap-independent — each window computes the
    device evaluator's per-row values up to fusion (arg slabs block the
    constant folding the device jit applies to its closed-over data, which
    can move the masked-mean division by 1 ulp; use ``eval_chunk=0`` when
    exact bits matter and the test slab fits)."""

    @phases.scoped("fl.round")
    @phases.scoped("fl.eval")
    def eval_step(new_global, local_rows, pms_rows, x_te, y_te, m_te):
        env_c = phases.RoundEnv(
            x_tr=None, y_tr=None, m_tr=None, x_te=x_te, y_te=y_te, m_te=m_te,
            n_samples=None, delay=None, n_clients=chunk,
            loss_fn=loss_fn, acc_fn=acc_fn, population=population,
        )
        ctx = phases.RoundContext(
            new_global=new_global,
            new_local=local_rows,
            share=layer_share_mask(n_layers, pms_rows),
        )
        model = pipeline.personalizer.eval_model(ctx, env_c)
        acc = jax.vmap(acc_fn)(model, x_te, y_te, m_te)
        loss = jax.vmap(loss_fn)(model, x_te, y_te, m_te)
        return acc, loss

    return jax.jit(eval_step)


def _build_eval_full(pipeline: RoundPipeline, n_layers: int, data, c: int,
                     loss_fn, acc_fn):
    """Whole-population evaluation with the test slabs closed over as jit
    constants — byte-for-byte the device evaluator's program (``build_env``
    bakes the data into the round step's closure the same way), so XLA
    constant-folds the per-client mask totals identically and the
    accuracy/loss lanes are bit-identical to the device-resident path.
    This is the ``eval_chunk=0`` default; it stages the full test slab on
    device, so populations past device memory set ``eval_chunk`` and
    stream instead."""
    _, _, _, x_te, y_te, m_te = _data_shard(data, np.arange(c))
    env_f = phases.RoundEnv(
        x_tr=None, y_tr=None, m_tr=None, x_te=jnp.asarray(x_te),
        y_te=jnp.asarray(y_te), m_te=jnp.asarray(m_te),
        n_samples=None, delay=None, n_clients=c,
        loss_fn=loss_fn, acc_fn=acc_fn, population=c,
    )

    @phases.scoped("fl.round")
    @phases.scoped("fl.eval")
    def eval_full(new_global, local_full, pms_lane):
        ctx = phases.RoundContext(
            new_global=new_global,
            new_local=local_full,
            share=layer_share_mask(n_layers, pms_lane),
        )
        model = pipeline.personalizer.eval_model(ctx, env_f)
        acc = jax.vmap(acc_fn)(model, env_f.x_te, env_f.y_te, env_f.m_te)
        loss = jax.vmap(loss_fn)(model, env_f.x_te, env_f.y_te, env_f.m_te)
        return acc, loss

    return jax.jit(eval_full)


def _build_pop_step(pipeline: RoundPipeline, n_layers: int, population: int,
                    lw: np.ndarray, sizes: np.ndarray):
    """The population-signal step for the sync runner: wire accounting,
    selection, and layer policy over the staged ``(C,)`` lanes — the same
    device expressions the fused round step runs, minus the data slabs
    (selection reads only the cheap lanes)."""
    lw_j = jnp.asarray(lw, jnp.float32)
    sizes_j = jnp.asarray(sizes, jnp.int32)

    @phases.scoped("fl.round")
    def pop_step(t, r_sel, pms, executed, accuracy, loss, update_norm,
                 participation, n_samples, delay):
        share = layer_share_mask(n_layers, pms)
        share_f = share.astype(jnp.float32)
        wire_prospective = share_f @ lw_j
        wire_paid = (share_f * executed.astype(jnp.float32)[:, None]) @ lw_j
        env_p = phases.RoundEnv(
            x_tr=None, y_tr=None, m_tr=None, x_te=None, y_te=None, m_te=None,
            n_samples=n_samples, delay=delay, n_clients=population,
            loss_fn=None, acc_fn=None, population=population,
        )
        pctx = phases.RoundContext(
            t=t,
            select=executed,
            pms=pms,
            share=share,
            participation=participation,
            accuracy=accuracy,
            loss=loss,
            wire_bytes=wire_prospective,
            wire_paid=wire_paid,
            update_norm=update_norm,
            rng_sel=r_sel,
        )
        pctx = pipeline.selector.select(pctx, env_p)
        next_pms = pipeline.layer_policy.next_pms(pctx, env_p, n_layers)
        tx = transmitted_parameters(executed, share, sizes_j)
        return pctx.next_select, next_pms, wire_paid, tx

    return jax.jit(pop_step)


def _eval_windows(c: int, eval_chunk: int):
    chunk = eval_chunk or c
    return [(lo, min(lo + chunk, c)) for lo in range(0, c, chunk)]


def _run_eval_stream(su: _HostSetup, store: PopulationStore, data, g,
                     pms_lane: np.ndarray, eval_steps: dict, eval_chunk: int,
                     c: int):
    """Stream population evaluation through ``eval_chunk`` windows, writing
    the accuracy/loss lanes in place. ``eval_chunk=0`` runs the one
    whole-population constants-baked step (bit-identical to the device
    evaluator); otherwise one jit per distinct window length (body + tail)."""
    stateful = su.pipeline.personalizer.stateful
    if eval_chunk == 0:
        step = eval_steps.get("full")
        if step is None:
            step = _build_eval_full(
                su.pipeline, su.n_layers, data, c, su.loss_fn, su.acc_fn
            )
            eval_steps["full"] = step
        local_full = store.trees["local"] if stateful else None
        acc, loss = step(g, local_full, pms_lane)
        store.lanes["accuracy"][:] = np.asarray(jax.device_get(acc))
        store.lanes["loss"][:] = np.asarray(jax.device_get(loss))
        return
    for lo, hi in _eval_windows(c, eval_chunk):
        n = hi - lo
        step = eval_steps.get(n)
        if step is None:
            step = _build_eval_step(
                su.pipeline, su.n_layers, c, su.loss_fn, su.acc_fn, n
            )
            eval_steps[n] = step
        rows = np.arange(lo, hi)
        local_rows = (
            jax.tree.map(lambda leaf: leaf[lo:hi], store.trees["local"])
            if stateful
            else None
        )
        _, _, _, x_te, y_te, m_te = _data_shard(data, rows)
        acc, loss = step(g, local_rows, pms_lane[lo:hi], x_te, y_te, m_te)
        store.lanes["accuracy"][lo:hi] = np.asarray(jax.device_get(acc))
        store.lanes["loss"][lo:hi] = np.asarray(jax.device_get(loss))


# ---------------------------------------------------------------------------
# host-plane synchronous runner (mirrors SyncScheduler.run)
# ---------------------------------------------------------------------------


def run_host_sync(
    data,
    cfg: FLConfig,
    init_fn: Callable | None = None,
    loss_fn: Callable = mlp_loss,
    acc_fn: Callable = mlp_accuracy,
    comm: CommModel | None = None,
    progress: bool = False,
    pipeline: RoundPipeline | None = None,
    client_delay: np.ndarray | None = None,
    recorder=None,
    backing_dir: str | None = None,
    stats: dict | None = None,
    checkpoint_every: int = 0,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
):
    """The synchronous barrier loop with a host-resident population plane.

    Per round: resolve the cohort from the host selection lane, gather its
    rows from the ``PopulationStore`` + data shard, run the cohort jit,
    scatter results back, stream evaluation, then run the population-signal
    jit (selection + layer policy) over the staged lanes. History and
    accounting are identical to ``SyncScheduler.run``; ``stats`` (optional
    dict) additionally collects per-round ``round_ms`` / ``host_gather_ms``
    / ``staged_bytes`` for the population benchmark.

    Failure semantics and checkpoint/resume mirror ``SyncScheduler.run``:
    an enabled ``cfg.faults`` masks crashed / past-deadline clients out of
    the round before cohort resolution and deadline-caps the simulated
    round time; ``checkpoint_every``/``resume_from`` snapshot and restore
    the full run — global model, rng chain, every ``PopulationStore`` lane
    and tree (memmap-backed included), and the accumulated history — so a
    resumed run is bit-identical to an uninterrupted one.
    """
    from repro.fl.engine import FLHistory

    su = _HostSetup(data, cfg, init_fn, loss_fn, acc_fn, comm, pipeline, client_delay)
    comm, clock = su.comm, su.clock
    faults = cfg.faults
    faulty = faults.enabled
    if faulty and cfg.execution.edge_groups >= 1:
        raise ValueError(
            "fault injection with an edge_groups topology is not "
            "supported yet; set edge_groups=0 or disable FaultConfig"
        )
    ckpt_dir = resolve_checkpoint_dir(checkpoint_every, checkpoint_dir, resume_from)
    c = data.n_clients
    k = cfg.execution.resolved_cohort(c)
    eval_every = cfg.execution.eval_every
    eval_chunk = cfg.execution.eval_chunk
    n_edges = cfg.execution.edge_groups
    edge_ids = edge_partition(c, n_edges) if n_edges >= 1 else None
    layer_sizes = np.diff(clock.params_prefix)
    stateful = su.pipeline.personalizer.stateful
    lossy = su.pipeline.transmit.lossy

    lanes = su.default_lanes(c)
    lanes["select"] = np.ones((c,), bool)
    lanes["pms"] = np.full((c,), su.pms0, np.int32)
    store = PopulationStore.build(
        c, lanes, g0=su.g0, stateful=stateful, lossy=lossy, backing_dir=backing_dir
    )
    tree_names = [n for n in ("local", "residual") if n in store.trees]

    g = su.g0
    rng = su.r_loop
    cohort_step = _build_cohort_step(
        su.pipeline, su.n_layers, k, c, loss_fn, acc_fn,
        faults=faults if faulty else None,
    )
    pop_step = _build_pop_step(su.pipeline, su.n_layers, c, su.lw, su.sizes)
    eval_steps: dict = {}
    delay_acct = None if clock.uniform else clock.delay

    if recorder is not None:
        recorder.open_run(
            mode="sync", cfg=cfg, data=data, comm=comm, clock=clock, lanes=k,
            population_plane=_population_plane_manifest(cfg, store),
        )
    prof = recorder.profiler if recorder is not None else None
    emit = recorder.log if recorder is not None else print

    accs, sel_hist, tx_hist, pms_hist, times, wire_hist = [], [], [], [], [], []
    edge_hist: list[np.ndarray] = []
    rejected_hist: list[int] = []
    start = 0
    if resume_from is not None:
        # latest snapshot: global model + rng via repro.checkpoint, the
        # store's heavy trees restored row-for-row in place (memmap leaves
        # stay memmaps), every lane + the history lanes verbatim
        trees, meta = load_fl_state({"g": g, "rng": rng}, resume_from)
        g = jax.tree.map(jnp.asarray, trees["g"])
        rng = jnp.asarray(trees["rng"])
        start = int(meta["round"])
        if store.trees:
            loaded = load_pytree(store.trees, resume_from, f"store_{start:05d}")
            jax.tree.map(_restore_rows, store.trees, loaded)
        host = load_host_arrays(resume_from, f"hist_{start:05d}")
        for name in store.lanes:
            store.lanes[name][...] = host[f"lane_{name}"]
        store.flush()
        accs = [row for row in host["acc"]]
        sel_hist = [row for row in host["selected"]]
        tx_hist = [float(x) for x in host["tx_params"]]
        pms_hist = [row for row in host["pms"]]
        times = [float(x) for x in host["round_time"]]
        wire_hist = [float(x) for x in host["wire"]]
        rejected_hist = [int(x) for x in host["rejected"]]
        if "tx_edge_bytes" in host:
            edge_hist = [host["tx_edge_bytes"]]
    for t in range(start, cfg.rounds):
        t_round0 = time.perf_counter()
        if prof is not None:
            prof.begin_chunk(t, 1)
        # --- cohort resolution on the host lanes (== cohort_indices) ---
        select = store.lanes["select"]
        if faulty:
            # crash + deadline survivors resolved host-side, intersected
            # into the selection before cohort resolution — exactly the
            # device scheduler's alive-mask semantics
            sel_pre = select.copy()
            plan, alive_np, dur_t = _sync_fault_inputs(
                faults, cfg.seed, t, clock, store.lanes["pms"]
            )
            if not (sel_pre & alive_np).any():
                # a storm killed every selected client: the server
                # re-dispatches until someone answers — run the round
                # fault-free rather than aggregate nothing
                alive_np = np.ones_like(alive_np)
            select = select & alive_np
        idx = np.argsort(~select, kind="stable")[:k].astype(np.int32)
        cmask = select[idx]
        executed = np.zeros((c,), bool)
        executed[idx] = cmask
        store.lanes["participation"][idx] += cmask
        # --- stage the cohort: store rows + data shard -> device args ---
        t_gather0 = time.perf_counter()
        gathered = store.gather(
            idx, ["pms", "participation", "update_norm", *tree_names]
        )
        data_k = _data_shard(data, idx)
        local_k = gathered.get("local")
        residual_k = gathered.get("residual")
        staged_bytes = float(
            sum(a.nbytes for a in data_k)
            + gathered["pms"].nbytes + gathered["participation"].nbytes
            + gathered["update_norm"].nbytes
            + sum(_tree_nbytes(gathered[n]) for n in tree_names)
        )
        gather_ms = (time.perf_counter() - t_gather0) * 1e3
        step_args = (
            g, rng, jnp.asarray(t), idx, cmask, gathered["pms"],
            gathered["participation"], local_k, residual_k, data_k,
            su.n_samples32[idx], su.delay_env[idx], gathered["update_norm"],
        )
        if faulty:
            step_args = step_args + (
                jnp.asarray(plan.corrupt[idx].astype(np.int32)),
            )
        with phase_timer(prof, "dispatch"):
            g, new_local_k, new_residual_k, un_k, rej_d, rng, r_sel = (
                cohort_step(*step_args)
            )
        # --- scatter the cohort's results back into the store ---
        with phase_timer(prof, "device_get"):
            back: dict[str, Any] = {}
            if stateful:
                back["local"] = jax.device_get(new_local_k)
            if lossy:
                back["residual"] = jax.device_get(new_residual_k)
            store.scatter(idx, back)
            store.lanes["update_norm"][idx] = np.asarray(jax.device_get(un_k))
        # --- population evaluation, streamed (thinned by eval_every) ---
        if t % eval_every == 0:
            _run_eval_stream(su, store, data, g, store.lanes["pms"], eval_steps,
                             eval_chunk, c)
        # --- population signals: wire accounting, selection, next pms ---
        pms_row = store.lanes["pms"].copy()  # pre-update, like out["pms"]
        next_select_d, next_pms_d, wire_paid_d, tx_d = pop_step(
            jnp.asarray(t), r_sel, pms_row, executed, store.lanes["accuracy"],
            store.lanes["loss"], store.lanes["update_norm"],
            store.lanes["participation"], su.n_samples32, su.delay_env,
        )
        store.lanes["select"] = np.asarray(jax.device_get(next_select_d), bool)
        store.lanes["pms"] = np.asarray(jax.device_get(next_pms_d), np.int32)
        wire_row = np.asarray(jax.device_get(wire_paid_d), np.float64)
        tx_row = float(jax.device_get(tx_d))
        if prof is not None:
            prof.end_chunk()
        # --- simulated-clock accounting (identical to SyncScheduler) ---
        per_client_params = clock.shared_params(pms_row)
        flops = clock.round_flops(pms_row)
        if n_edges >= 1:
            e_bytes = edge_hop_bytes(
                executed[None], pms_row[None], layer_sizes, edge_ids, n_edges
            )
            edge_hist.append(e_bytes)
            rt = comm.edge_round_times(
                wire_row[None], flops[None], executed[None], edge_ids, e_bytes,
                rx_bytes=per_client_params[None] * float(BYTES_PER_PARAM),
                delay=delay_acct,
            )
        else:
            rt = comm.round_times(
                wire_row[None], flops[None], executed[None],
                rx_bytes=per_client_params[None] * float(BYTES_PER_PARAM),
                delay=delay_acct,
            )
        n_dropped = None
        if faulty:
            # the server waits on everyone it dispatched, but only up to
            # the deadline: round time = slowest dispatched client at its
            # fault-slowed duration, deadline-capped
            wait = dur_t[sel_pre]
            rt_t = float(wait.max()) if wait.size else 0.0
            if faults.deadline_s > 0.0:
                rt_t = min(rt_t, faults.deadline_s)
            rt = np.asarray([rt_t + comm.server_latency_s], np.float64)
            n_dropped = int((sel_pre & ~alive_np).sum())
        acc_row = store.lanes["accuracy"].copy()
        accs.append(acc_row)
        sel_hist.append(executed)
        pms_hist.append(pms_row)
        tx_hist.append(tx_row)
        wire_hist.append(float(wire_row.sum()))
        times.append(float(rt[0]))
        rejected_hist.append(int(jax.device_get(rej_d)))
        if stats is not None:
            stats.setdefault("round_ms", []).append(
                (time.perf_counter() - t_round0) * 1e3
            )
            stats.setdefault("host_gather_ms", []).append(gather_ms)
            stats.setdefault("staged_bytes", []).append(staged_bytes)
        if recorder is not None:
            recorder.on_sync_chunk(
                t0=t, acc=acc_row[None], sel=executed[None], pms=pms_row[None],
                wire=wire_row[None], tx=np.asarray([tx_row]), times=rt,
                update_norm=store.lanes["update_norm"][None], lanes=k,
                host_gather_ms=[gather_ms], staged_bytes=[staged_bytes],
                rejected=np.asarray([rejected_hist[-1]], np.int64),
                dropped=(
                    np.asarray([n_dropped], np.int64)
                    if n_dropped is not None
                    else None
                ),
            )
        if progress:
            for i in _progress_rows(t, 1, 1, cfg.rounds):
                emit(format_sync_progress(
                    t, float(acc_row.mean()), int(executed.sum())
                ))
        r = t + 1
        if ckpt_dir and checkpoint_every and r % checkpoint_every == 0:
            # full resume state: model + rng via repro.checkpoint, the
            # store's trees path-keyed (memmap leaves flushed first), every
            # lane + accumulated history verbatim
            store.flush()
            save_fl_state(
                {"g": jax.device_get(g), "rng": jax.device_get(rng)},
                ckpt_dir, r,
            )
            if store.trees:
                save_pytree(store.trees, ckpt_dir, f"store_{r:05d}")
            hist_arrays = {
                f"lane_{name}": v for name, v in store.lanes.items()
            }
            hist_arrays.update({
                "acc": np.stack(accs),
                "selected": np.stack(sel_hist),
                "tx_params": np.asarray(tx_hist),
                "pms": np.stack(pms_hist),
                "round_time": np.asarray(times),
                "wire": np.asarray(wire_hist),
                "rejected": np.asarray(rejected_hist, np.int64),
            })
            if edge_hist:
                hist_arrays["tx_edge_bytes"] = np.concatenate(edge_hist)
            save_host_arrays(hist_arrays, ckpt_dir, f"hist_{r:05d}")

    store.flush()
    times_np = np.asarray(times)
    wire = np.asarray(wire_hist)
    acc_pc = np.stack(accs)
    h = FLHistory(
        accuracy_mean=acc_pc.mean(axis=1),
        accuracy_per_client=acc_pc,
        selected=np.stack(sel_hist),
        tx_params=np.asarray(tx_hist),
        tx_bytes_cum=np.cumsum(wire),
        round_time=times_np,
        pms=np.stack(pms_hist),
        tx_wire_bytes=wire,
        sim_clock=np.cumsum(times_np),
        staleness_mean=np.zeros_like(times_np),
        in_flight=np.full(times_np.shape, k, np.int64),
        tx_edge_bytes=np.concatenate(edge_hist) if n_edges >= 1 else None,
        rejected_updates=np.asarray(rejected_hist, np.int64),
    )
    if recorder is not None:
        recorder.close(h)
    return h


# ---------------------------------------------------------------------------
# host-plane async runner (mirrors AsyncScheduler.run)
# ---------------------------------------------------------------------------


def _build_async_host_step(pipeline: RoundPipeline, n_layers: int, m: int,
                           population: int, loss_fn, acc_fn, sizes: np.ndarray,
                           faults=None):
    """The slot-lane compute step of ``sched.build_async_step``, on staged
    ``(M, ...)`` rows: every slot trains its client from the slot snapshot,
    landing deltas ride the codec and merge with staleness weights.

    Carries the same always-on finite-delta guard (and, with an enabled
    ``faults``, the same trailing ``corrupt_m (M,) int32`` argument) as the
    device async step — same ops in the same order, so all-finite events
    stay bit-identical to the device-resident path."""
    stateful = pipeline.personalizer.stateful
    lossy = pipeline.transmit.lossy
    sizes_j = jnp.asarray(sizes, jnp.int32)
    faulty = faults is not None and faults.enabled
    max_norm = float(faults.max_update_norm) if faulty else 0.0
    corrupt_scale = float(faults.corrupt_scale) if faulty else 0.0

    @phases.scoped("fl.event")
    def _step_body(g, slot_params, rng, t, cids, slot_pms, land, staleness,
                   local_m, residual_m, participation_m, data_m, n_samples_m,
                   delay_m, prev_un_m, corrupt_m):
        share_m = layer_share_mask(n_layers, slot_pms)
        if lossy:
            rng, r_fit, r_sel, r_codec = jax.random.split(rng, 4)
        else:
            rng, r_fit, r_sel = jax.random.split(rng, 3)
            r_codec = None
        x_tr, y_tr, m_tr, x_te, y_te, m_te = data_m
        menv = phases.RoundEnv(
            x_tr=x_tr, y_tr=y_tr, m_tr=m_tr, x_te=x_te, y_te=y_te, m_te=m_te,
            n_samples=n_samples_m, delay=delay_m, n_clients=m,
            loss_fn=loss_fn, acc_fn=acc_fn, population=population,
        )
        cctx = phases.RoundContext(
            t=t,
            global_params=g,
            local_params=local_m if stateful else None,
            select=land,
            pms=slot_pms,
            share=share_m,
            residual=residual_m,
            participation=participation_m,
            cohort_idx=cids,
            cohort_mask=land,
            dispatch_params=slot_params,
            staleness=staleness,
            rng_fit=r_fit,
            rng_codec=r_codec,
            rng_sel=r_sel,
        )
        cctx = cctx._replace(train_model=pipeline.personalizer.train_model(cctx, menv))
        cctx = pipeline.trainer.fit(cctx, menv)
        if corrupt_m is not None:
            # corrupt the trained params BEFORE transmit so the uploaded
            # update_norm carries the garbage — the finite guard below is
            # what rejects it (corrupt slots still land and pay wire)
            kinds_m = jnp.where(land, corrupt_m, 0)
            cctx = cctx._replace(
                trained=apply_corruption(cctx.trained, kinds_m, corrupt_scale)
            )
        with jax.named_scope("fl.personalize"):
            if stateful:
                cctx = cctx._replace(
                    new_local=jax.tree.map(
                        lambda new, old: jnp.where(
                            land.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
                        ),
                        cctx.trained,
                        pipeline.personalizer.local_fallback(cctx, menv),
                    )
                )
        local_before = cctx.local_params if stateful else None
        res_before = cctx.residual
        cctx = pipeline.transmit.transmit(cctx, menv)
        # finite-delta guard (always on) — same expressions as the device
        # async step, so all-finite events are bit-identical to it
        with jax.named_scope("fl.transmit"):
            ok, n_rejected = finite_update_guard(land, cctx.update_norm, max_norm)
            cctx = cctx._replace(
                select=land & ok,
                update_norm=jnp.where(ok, cctx.update_norm, prev_un_m),
            )
            if res_before is not None:
                cctx = cctx._replace(residual=_tree_where(ok, cctx.residual, res_before))
            if stateful:
                cctx = cctx._replace(new_local=_tree_where(ok, cctx.new_local, local_before))
        cctx = pipeline.aggregator.aggregate(cctx, menv)
        land_f = land.astype(jnp.float32)
        n_land = jnp.maximum(jnp.sum(land_f), 1.0)
        merge_w = (
            cctx.merge_weight if cctx.merge_weight is not None
            else jnp.ones_like(land_f)
        )
        tx = transmitted_parameters(land, share_m, sizes_j)
        return (cctx.new_global, cctx.new_local, cctx.residual, cctx.update_norm,
                cctx.wire_paid, tx,
                jnp.sum(land_f * staleness.astype(jnp.float32)) / n_land,
                jnp.sum(land_f * merge_w) / n_land,
                n_rejected, rng, r_sel)

    def step(g, slot_params, rng, t, cids, slot_pms, land, staleness,
             local_m, residual_m, participation_m, data_m, n_samples_m,
             delay_m, prev_un_m):
        return _step_body(g, slot_params, rng, t, cids, slot_pms, land,
                          staleness, local_m, residual_m, participation_m,
                          data_m, n_samples_m, delay_m, prev_un_m, None)

    if not faulty:
        return jax.jit(step)

    def fault_step(g, slot_params, rng, t, cids, slot_pms, land, staleness,
                   local_m, residual_m, participation_m, data_m, n_samples_m,
                   delay_m, prev_un_m, corrupt_m):
        return _step_body(g, slot_params, rng, t, cids, slot_pms, land,
                          staleness, local_m, residual_m, participation_m,
                          data_m, n_samples_m, delay_m, prev_un_m, corrupt_m)

    return jax.jit(fault_step)


def _build_async_pop_step(pipeline: RoundPipeline, n_layers: int,
                          population: int, lw: np.ndarray):
    """Selection + slot assignment over the staged ``(C,)`` lanes — the
    population segment of ``sched.build_async_step``, same expressions."""
    c = population
    lw_j = jnp.asarray(lw, jnp.float32)

    @phases.scoped("fl.event")
    def pop_step(t, r_sel, client_pms, land_c, accuracy, loss, update_norm,
                 participation, n_samples, delay, idle_now, cids, land,
                 active, slot_pms, force):
        share_c = layer_share_mask(n_layers, client_pms)
        wire_prospective = share_c.astype(jnp.float32) @ lw_j
        env_p = phases.RoundEnv(
            x_tr=None, y_tr=None, m_tr=None, x_te=None, y_te=None, m_te=None,
            n_samples=n_samples, delay=delay, n_clients=c,
            loss_fn=None, acc_fn=None, population=c,
        )
        pctx = phases.RoundContext(
            t=t,
            select=land_c,
            pms=client_pms,
            share=share_c,
            participation=participation,
            accuracy=accuracy,
            loss=loss,
            wire_bytes=wire_prospective,
            update_norm=update_norm,
            rng_sel=r_sel,
        )
        pctx = pipeline.selector.select(pctx, env_p)
        next_pms = pipeline.layer_policy.next_pms(pctx, env_p, n_layers)
        # slot assignment: wanted idle clients -> freed slots, ascending ids
        want = pctx.next_select & idle_now
        free = land | ~active
        n_assign = jnp.minimum(jnp.sum(want), jnp.sum(free))
        slot_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
        cand_order = jnp.argsort(~want, stable=True)
        assigned = free & (slot_rank < n_assign)
        new_cid = jnp.take(cand_order, jnp.clip(slot_rank, 0, c - 1))
        need_force = force & (n_assign == 0)
        dispatched = jnp.where(need_force, land, assigned)
        new_slot_client = jnp.where(assigned, new_cid, cids)
        disp_pms = jnp.take(next_pms, new_slot_client)
        new_slot_pms = jnp.where(dispatched, disp_pms, slot_pms)
        return dispatched, new_slot_client, new_slot_pms, disp_pms

    return jax.jit(pop_step)


def _build_slot_update(pipeline: RoundPipeline):
    @phases.scoped("fl.event")
    @phases.scoped("fl.scatter")
    def upd(slot_params, new_global, dispatched):
        return jax.tree.map(
            lambda s, gl: jnp.where(
                dispatched.reshape((-1,) + (1,) * (s.ndim - 1)),
                jnp.broadcast_to(gl, s.shape), s,
            ),
            slot_params, new_global,
        )

    return jax.jit(upd)


def run_host_async(
    data,
    cfg: FLConfig,
    init_fn: Callable | None = None,
    loss_fn: Callable = mlp_loss,
    acc_fn: Callable = mlp_accuracy,
    comm: CommModel | None = None,
    progress: bool = False,
    pipeline: RoundPipeline | None = None,
    client_delay: np.ndarray | None = None,
    recorder=None,
    buffer_k: int | None = None,
    backing_dir: str | None = None,
    stats: dict | None = None,
    checkpoint_every: int = 0,
    checkpoint_dir: str | None = None,
    resume_from: str | None = None,
):
    """FedBuff-style buffered execution with a host-resident population
    plane: the M dispatch slots stage their clients' rows per event, only
    landing rows scatter back (non-landing lanes recompute the same
    deterministic result next event, exactly like the device path), and
    the heap-backed ``EventQueue`` samples completion times lazily over
    the dispatched subset — no O(C) work per event beyond the population
    selection pass itself.

    Failure semantics and checkpoint/resume mirror ``AsyncScheduler.run``:
    an enabled ``cfg.faults`` arms each dispatch with crash/timeout codes
    and corruption kinds from the deterministic fault plan, failed slots
    re-dispatch with exponential backoff up to ``max_retries`` then free
    their slot; ``checkpoint_every``/``resume_from`` snapshot and restore
    the full run (model, rng, slot plane, event queue, every
    ``PopulationStore`` lane and tree, history) bit-identically.
    """
    from repro.fl.engine import FLHistory

    su = _HostSetup(data, cfg, init_fn, loss_fn, acc_fn, comm, pipeline, client_delay)
    comm, clock = su.comm, su.clock
    faults = cfg.faults
    faulty = faults.enabled
    if faulty and cfg.execution.edge_groups >= 1:
        raise ValueError(
            "fault injection with an edge_groups topology is not "
            "supported yet; set edge_groups=0 or disable FaultConfig"
        )
    ckpt_dir = resolve_checkpoint_dir(checkpoint_every, checkpoint_dir, resume_from)
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
    m = min(cfg.scheduler.max_concurrency or cfg.execution.cohort_size or c, c)
    eval_every = cfg.execution.eval_every
    eval_chunk = cfg.execution.eval_chunk
    n_edges = cfg.execution.edge_groups
    edge_ids = edge_partition(c, n_edges) if n_edges >= 1 else None
    layer_sizes = np.diff(clock.params_prefix)
    stateful = su.pipeline.personalizer.stateful
    lossy = su.pipeline.transmit.lossy

    lanes = su.default_lanes(c)
    lanes["client_pms"] = np.full((c,), su.pms0, np.int32)
    store = PopulationStore.build(
        c, lanes, g0=su.g0, stateful=stateful, lossy=lossy, backing_dir=backing_dir
    )
    tree_names = [n for n in ("local", "residual") if n in store.trees]

    g = su.g0
    rng = su.r_loop
    slot_params = jax.tree.map(
        lambda gl: jnp.broadcast_to(gl, (m,) + gl.shape), su.g0
    )
    step = _build_async_host_step(
        su.pipeline, su.n_layers, m, c, loss_fn, acc_fn, su.sizes,
        faults=faults if faulty else None,
    )
    pop_step = _build_async_pop_step(su.pipeline, su.n_layers, c, su.lw)
    slot_update = _build_slot_update(su.pipeline)
    eval_steps: dict = {}
    deadline = float(faults.deadline_s)

    def _arm_faults(cids_arr, durations, at_version):
        """Fault-arm a dispatch batch (same semantics as the device
        scheduler): fault-slowed notice times, failure codes (0 ok /
        1 crash / 2 deadline timeout), and corruption kinds, all drawn
        from the plan at the dispatching model version."""
        plan = compile_fault_plan(faults, cfg.seed, at_version, c)
        cids_arr = np.asarray(cids_arr)
        dur = durations * plan.slow[cids_arr]
        code = np.where(plan.crash[cids_arr], 1, 0).astype(np.int8)
        if deadline > 0.0:
            code = np.where((code == 0) & (dur > deadline), 2, code)
            dur = np.where(code != 0, np.minimum(dur, deadline), dur)
        kind = np.where(code == 0, plan.corrupt[cids_arr], 0).astype(np.int32)
        return dur, code, kind

    resolved_buffer_k = buffer_k or cfg.scheduler.buffer_k or max(1, c // 2)
    if recorder is not None:
        recorder.open_run(
            mode="async", cfg=cfg, data=data, comm=comm, clock=clock,
            lanes=m, buffer_k=resolved_buffer_k,
            population_plane=_population_plane_manifest(cfg, store),
        )
    prof = recorder.profiler if recorder is not None else None
    emit = recorder.log if recorder is not None else print

    # --- host event queue over the M slots ---
    slot_client = np.arange(m, dtype=np.int32)
    slot_pms = np.full((m,), su.pms0, np.int32)
    client_pms = store.lanes["client_pms"]
    queue = EventQueue(m)
    slot_fail = np.zeros((m,), np.int8)
    slot_kind = np.zeros((m,), np.int32)
    retries = np.zeros((m,), np.int64)
    d0 = clock.durations(client_pms[slot_client], cids=slot_client)
    if faulty:  # warm-start dispatches draw from the version-0 plan
        d0, slot_fail, slot_kind = _arm_faults(slot_client, d0, 0)
    for s in range(m):
        queue.push(s, d0[s], int(slot_client[s]))
    if recorder is not None:
        recorder.on_async_dispatch(slot_client, 0.0, client_pms)
    active = np.ones((m,), bool)
    in_flight_clients = np.zeros((c,), bool)
    in_flight_clients[slot_client] = True
    dispatch_version = np.zeros((m,), np.int64)
    sim_clock = 0.0
    version = 0

    accs, sel_hist, tx_hist, pms_hist = [], [], [], []
    times, wire_hist, clock_hist, stale_hist, flight_hist = [], [], [], [], []
    edge_hist: list[np.ndarray] = []
    rejected_hist: list[int] = []
    pend_retried = pend_timeout = pend_dropped = 0
    start_t = 0
    if resume_from is not None:
        # latest snapshot: model/rng/slot snapshots via repro.checkpoint,
        # store trees restored row-for-row in place, lanes + slot plane +
        # history verbatim, and the event queue rebuilt by re-pushing the
        # in-flight slots at their saved finish times
        trees, meta = load_fl_state(
            {"g": g, "rng": rng, "slot_params": slot_params}, resume_from
        )
        g = jax.tree.map(jnp.asarray, trees["g"])
        rng = jnp.asarray(trees["rng"])
        slot_params = jax.tree.map(jnp.asarray, trees["slot_params"])
        start_t = int(meta["round"])
        sim_clock = float(meta["sim_clock"])
        version = int(meta["version"])
        if store.trees:
            loaded = load_pytree(store.trees, resume_from, f"store_{start_t:05d}")
            jax.tree.map(_restore_rows, store.trees, loaded)
        host = load_host_arrays(resume_from, f"hist_{start_t:05d}")
        for name in store.lanes:
            store.lanes[name][...] = host[f"lane_{name}"]
        store.flush()
        slot_client = host["slot_client"].astype(np.int32)
        slot_pms = host["slot_pms"].astype(np.int32)
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
        t_round0 = time.perf_counter()
        n_active = int(active.sum())
        if n_active == 0:
            # the whole population dropped out (every slot's retries
            # exhausted): degrade gracefully — end the run with the
            # history accumulated so far instead of deadlocking
            break
        k_ev = max(1, min(resolved_buffer_k, n_active))
        landers = queue.pop_k(k_ev)
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
                # exponential-backoff re-dispatch of the SAME client on the
                # same slot and snapshot, with fresh fault draws at the
                # current model version
                retries[s] += 1
                cid = int(slot_client[s])
                backoff = faults.backoff_s * (2.0 ** float(retries[s] - 1))
                d_r, code_r, kind_r = _arm_faults(
                    [cid], clock.durations(client_pms[[cid]], cids=[cid]),
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
            force = bool(n_active - k_ev == 0)
        staleness = np.where(land, version - dispatch_version, 0).astype(np.int32)
        landed_clients = slot_client[landers]
        idle_now = ~in_flight_clients
        idle_now[landed_clients] = True
        if prof is not None:
            prof.begin_chunk(t, 1)

        # --- stage the slot lanes (duplicate ids in inactive slots are
        # fine — they are row reads, and only landing rows write back) ---
        t_gather0 = time.perf_counter()
        store.lanes["participation"][landed_clients] += 1
        gathered = store.gather(slot_client, tree_names)
        data_m = _data_shard(data, slot_client)
        part_m = store.lanes["participation"][slot_client]
        staged_bytes = float(
            sum(a.nbytes for a in data_m)
            + sum(_tree_nbytes(gathered[n]) for n in tree_names)
        )
        gather_ms = (time.perf_counter() - t_gather0) * 1e3
        step_args = (
            g, slot_params, rng, jnp.asarray(t), slot_client, slot_pms,
            land, staleness, gathered.get("local"), gathered.get("residual"),
            part_m, data_m, su.n_samples32[slot_client],
            su.delay_env[slot_client], store.lanes["update_norm"][slot_client],
        )
        if faulty:
            step_args = step_args + (jnp.asarray(slot_kind),)
        with phase_timer(prof, "dispatch"):
            (g, new_local_m, new_residual_m, un_m, wire_m, tx_d,
             stale_mean_d, merge_mean_d, rej_d, rng, r_sel) = step(*step_args)
        # --- scatter landing rows only (others provably unchanged) ---
        with phase_timer(prof, "device_get"):
            back: dict[str, Any] = {}
            if stateful:
                back["local"] = jax.tree.map(
                    lambda leaf: np.asarray(jax.device_get(leaf))[landers],
                    new_local_m,
                )
            if lossy:
                back["residual"] = jax.tree.map(
                    lambda leaf: np.asarray(jax.device_get(leaf))[landers],
                    new_residual_m,
                )
            store.scatter(landed_clients, back)
            un_rows = np.asarray(jax.device_get(un_m))
            wire_rows = np.asarray(jax.device_get(wire_m), np.float64)
        store.lanes["update_norm"][landed_clients] = un_rows[landers]
        land_c = np.zeros((c,), bool)
        land_c[landed_clients] = True
        wire_paid_c = np.zeros((c,), np.float64)
        wire_paid_c[landed_clients] = wire_rows[landers]
        # --- population evaluation, streamed ---
        if t % eval_every == 0:
            _run_eval_stream(su, store, data, g, client_pms, eval_steps,
                             eval_chunk, c)
        # --- selection + slot assignment over the staged lanes ---
        pms_pre = client_pms.copy()  # pre-dispatch-update, like out["pms"]
        disp_d, new_slot_client_d, new_slot_pms_d, disp_pms_d = pop_step(
            jnp.asarray(t), r_sel, pms_pre, land_c, store.lanes["accuracy"],
            store.lanes["loss"], store.lanes["update_norm"],
            store.lanes["participation"], su.n_samples32, su.delay_env,
            idle_now, slot_client, land, active, slot_pms, jnp.asarray(force),
        )
        dispatched = np.asarray(jax.device_get(disp_d))
        new_slot_client = np.asarray(jax.device_get(new_slot_client_d), np.int32)
        slot_pms = np.asarray(jax.device_get(new_slot_pms_d), np.int32)
        disp_pms = np.asarray(jax.device_get(disp_pms_d), np.int32)
        slot_params = slot_update(slot_params, g, disp_d)
        if prof is not None:
            prof.end_chunk()

        # --- host queue/lane updates ---
        active = (active & ~land) | dispatched
        in_flight_clients[landed_clients] = False
        in_flight_clients[new_slot_client[dispatched]] = True
        client_pms[new_slot_client[dispatched]] = disp_pms[dispatched]
        disp_slots = np.nonzero(dispatched)[0]
        if disp_slots.size:
            disp_cids = new_slot_client[disp_slots]
            d_disp = clock.durations(client_pms[disp_cids], cids=disp_cids)
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
        slot_client = new_slot_client

        accs.append(store.lanes["accuracy"].copy())
        sel_hist.append(land_c)
        tx_hist.append(float(jax.device_get(tx_d)))
        pms_hist.append(pms_pre)
        wire_hist.append(float(wire_paid_c.sum()))
        times.append(new_clock - sim_clock)
        clock_hist.append(new_clock)
        stale_hist.append(float(jax.device_get(stale_mean_d)))
        flight_hist.append(int(in_flight_clients.sum()))
        rejected_hist.append(int(jax.device_get(rej_d)))
        if n_edges >= 1:
            edge_hist.append(
                edge_hop_bytes(
                    land_c[None], pms_pre[None], layer_sizes, edge_ids, n_edges
                )[0]
            )
        if stats is not None:
            stats.setdefault("round_ms", []).append(
                (time.perf_counter() - t_round0) * 1e3
            )
            stats.setdefault("host_gather_ms", []).append(gather_ms)
            stats.setdefault("staged_bytes", []).append(staged_bytes)
        if recorder is not None:
            fault_kw = {}
            if faulty:
                fault_kw = dict(
                    retried=pend_retried, timed_out=pend_timeout,
                    dropped=pend_dropped,
                )
            recorder.on_async_event(
                t=t, acc=accs[-1], sel=land_c, tx=tx_hist[-1], pms=pms_pre,
                wire=wire_hist[-1], dt=times[-1], new_clock=new_clock,
                staleness_mean=stale_hist[-1], in_flight=flight_hist[-1],
                buffer_k=k_ev, update_norm=store.lanes["update_norm"],
                merge_discount=float(jax.device_get(merge_mean_d)),
                landed_clients=landed_clients, landed_finish=land_finish,
                landed_staleness=staleness[landers],
                rejected=rejected_hist[-1], **fault_kw,
            )
            if dispatched.any():
                recorder.on_async_dispatch(
                    new_slot_client[dispatched], new_clock, client_pms
                )
        pend_retried = pend_timeout = pend_dropped = 0
        sim_clock = new_clock
        version += 1
        if progress and (t % 10 == 0 or t == cfg.rounds - 1):
            emit(format_async_progress(
                t, float(accs[-1].mean()), int(land.sum()),
                new_clock, stale_hist[-1],
            ))
        t += 1
        if ckpt_dir and checkpoint_every and t % checkpoint_every == 0:
            # full resume state: model/rng/slot snapshots via
            # repro.checkpoint, store trees path-keyed, lanes + slot plane
            # + event queue + accumulated history verbatim
            store.flush()
            save_fl_state(
                {
                    "g": jax.device_get(g),
                    "rng": jax.device_get(rng),
                    "slot_params": jax.device_get(slot_params),
                    "sim_clock": float(sim_clock),
                    "version": int(version),
                },
                ckpt_dir, t,
            )
            if store.trees:
                save_pytree(store.trees, ckpt_dir, f"store_{t:05d}")
            host_arrays = {
                f"lane_{name}": v for name, v in store.lanes.items()
            }
            host_arrays.update({
                "slot_client": slot_client,
                "slot_pms": slot_pms,
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
            })
            if edge_hist:
                host_arrays["tx_edge_bytes"] = np.stack(edge_hist)
            save_host_arrays(host_arrays, ckpt_dir, f"hist_{t:05d}")

    store.flush()
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
