"""Swappable round phases — the building blocks of the FL round pipeline.

A federated round is an explicit sequence of small frozen-dataclass phase
components, each transforming a shared ``RoundContext``:

  Personalizer -> LocalTrainer -> TransmitPhase (wire codec + EF)
               -> Aggregator -> Evaluator -> SelectorPhase -> LayerPolicy

``RoundContext`` is a NamedTuple (a pytree) carrying the per-round dynamic
values: parameters, masks, rng lanes, and the per-client observations each
phase deposits for the ones downstream. ``RoundEnv`` is the static
per-experiment environment (data shards, sample counts, loss/acc fns)
closed over by the jitted round step — phases read it, never mutate it.

**Lane convention (cohort execution).** Phases are written against *lanes*,
not the population: every stacked leaf they touch has a leading axis of
``env.n_clients`` lanes, and the engine decides what a lane is. The compute
phases (Personalizer.train_model, LocalTrainer, TransmitPhase, Aggregator)
receive a *cohort* context/env — ``env.take(idx)``-gathered ``(K, ...)``
slabs of the K clients selection picked, with ``ctx.cohort_idx`` naming
which client each lane is and ``ctx.cohort_mask`` its validity — while the
population phases (Personalizer.eval_model, Evaluator, SelectorPhase,
LayerPolicy) see the full ``(C, ...)`` state. Per-client randomness is
derived from ``env.population`` and gathered by ``ctx.cohort_idx``
(``client_keys``), so a client's rng stream does not depend on which lane
it lands in. This is what makes rounds O(K) in compute and trained-state
memory: the engine (repro.fl.api.build_round_step) gathers the cohort with
``jnp.take``, runs the phases on K lanes, and scatters results back into
the ``(C, ...)`` server state with ``.at[idx].set``.

Every phase kind has a string registry mirroring ``get_strategy`` /
``make_codec`` (``get_phase('aggregator', 'fedavg')``), so configs address
phases by name and custom components drop in via ``register_phase``.
``repro.fl.api`` composes phases into a ``RoundPipeline`` and builds the
jitted round step; ``repro.fl.cross_silo`` reuses ``TransmitPhase`` for its
quantized all-reduce so both runtimes share one wire-format definition.

Phases are scheduler-agnostic: ``repro.fl.sched.SyncScheduler`` drives them
with the broadcast global model (``ctx.dispatch_params is None``), while
``AsyncScheduler`` supplies per-slot dispatch snapshots plus the
``staleness`` lane (its cohort lanes are the (M,) in-flight dispatch slots,
``cohort_idx`` the client id each slot holds), and swaps the aggregator for
``StalenessAggregator`` (registry name ``'staleness'``) — a FedBuff-style
buffered delta merge discounted by ``staleness_weight``.

Phases must also stay **scan-fusable**: the sync scheduler runs the round
step as the body of a ``lax.scan`` over ``scan_chunk`` rounds, so ``ctx.t``
is always a traced scalar (never a Python int — branch with ``lax.cond``,
as ``DistributedEvaluator``'s ``eval_every`` thinning does) and everything
a phase deposits into the round's ``out`` dict must be a fixed-shape array
so the chunk can stack it to ``(T_chunk, ...)`` leaves fetched in one
``device_get``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comm import Codec, ef_step, tree_wire_bytes
from repro.core import (
    compose_model,
    dynamic_layer_definition,
    fedavg_aggregate,
    masked_partial_aggregate,
    personalize_ft,
)
from repro.core.aggregation import staleness_weighted_merge
from repro.core.selection import ClientObservations, SelectionStrategy

# Device scopes. Every op a round emits carries, in its HLO ``op_name``
# metadata, the body it runs in (``fl.round``, ``fl.event`` for the async
# step, ``fl.chunk`` around the fused scan) and the phase inside it
# (``fl.personalize``, ``fl.train``, ``fl.transmit``, ``fl.aggregate``,
# ``fl.eval``, ``fl.select``, plus ``fl.gather``/``fl.scatter`` at the
# steps' cohort gather and scatter sites). The steps also scope their own
# glue: the lanes' new local models (trained where selected, the
# personalizer's fallback elsewhere) as ``fl.personalize``, and the finite
# guard that rejects a non-finite uplink and reverts its lane as
# ``fl.transmit``. A device trace then attributes each op's time to a
# phase. Scopes are trace-time metadata only: they change no number the
# step computes.


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        run.fl_scope = name
        return run

    return wrap


class _Phase:
    """Base of the phase classes: each method named in ``_SCOPED`` that a
    class (or any subclass) defines runs under its ``fl.<phase>`` scope."""

    _SCOPED: dict[str, str] = {}

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        for method, phase in cls._SCOPED.items():
            fn = cls.__dict__.get(method)
            if callable(fn) and not hasattr(fn, "fl_scope"):
                setattr(cls, method, scoped(f"fl.{phase}")(fn))


@dataclasses.dataclass(frozen=True)
class RoundEnv:
    """Static per-experiment environment every phase can read.

    Held by the round-step closure (not traced): data shards stacked on the
    lane axis, per-lane sample counts, the analytic delay lane for Oort's
    systemic term, and the model's loss/accuracy functions. ``n_clients``
    is the number of *lanes* this env carries — the population C for the
    env ``build_env`` returns, the cohort size K for the gathered view
    ``take`` returns; ``population`` always names the true population so
    per-client rng streams stay lane-independent.
    """

    x_tr: jnp.ndarray
    y_tr: jnp.ndarray
    m_tr: jnp.ndarray
    x_te: jnp.ndarray
    y_te: jnp.ndarray
    m_te: jnp.ndarray
    n_samples: jnp.ndarray   # (lanes,) float — |d_i|
    delay: jnp.ndarray       # (lanes,) float — analytic systemic delay (Oort)
    n_clients: int           # number of lanes (C, or K after .take)
    loss_fn: Callable
    acc_fn: Callable
    population: int = 0      # true population C; 0 -> n_clients
    eval_fn: Callable | None = None  # (params, x, y, m) -> (acc, loss, counts)
    count_ops: tuple = ()    # ((count name, "sum" | "max"), ...) of eval_fn

    @property
    def pop(self) -> int:
        return self.population or self.n_clients

    def take(self, idx: jnp.ndarray) -> "RoundEnv":
        """Cohort view: gather the ``idx`` client lanes of every data slab.

        The result has ``n_clients == len(idx)`` lanes but remembers the
        original ``population``, so rng derivation and wire accounting stay
        anchored to true client ids.
        """
        k = int(idx.shape[0])
        return dataclasses.replace(
            self,
            x_tr=jnp.take(self.x_tr, idx, axis=0),
            y_tr=jnp.take(self.y_tr, idx, axis=0),
            m_tr=jnp.take(self.m_tr, idx, axis=0),
            x_te=jnp.take(self.x_te, idx, axis=0),
            y_te=jnp.take(self.y_te, idx, axis=0),
            m_te=jnp.take(self.m_te, idx, axis=0),
            n_samples=jnp.take(self.n_samples, idx),
            delay=jnp.take(self.delay, idx),
            n_clients=k,
            population=self.pop,
        )


def client_keys(rng: jax.Array, ctx: "RoundContext", env: RoundEnv) -> jax.Array:
    """(lanes,) per-client rng keys, stable under cohort gathering.

    Keys are split over the *population* and gathered by ``ctx.cohort_idx``,
    so client i consumes the same stream whether it runs in a dense lane or
    a gathered cohort lane (bit-identity of the cohort runtime depends on
    this).
    """
    keys = jax.random.split(rng, env.pop)
    if ctx.cohort_idx is not None:
        keys = jnp.take(keys, ctx.cohort_idx, axis=0)
    return keys


class RoundContext(NamedTuple):
    """Dynamic state threaded through the phase pipeline (a pytree).

    The first block comes from the carried round state; later fields start
    as ``None`` and are filled by the phase that owns them (``_replace``
    returns an updated copy — phases never mutate in place). Stacked fields
    are *lane*-shaped (see the module docstring): during the compute phases
    a lane is one gathered cohort member (K lanes, or M dispatch slots
    under the async scheduler), during eval/selection a lane is one client
    of the population (C lanes).
    """

    t: Any = None                 # round index (traced scalar)
    global_params: Any = None     # layered list, leaves (...)
    local_params: Any = None      # layered list, leaves (lanes, ...)
    select: Any = None            # (lanes,) bool — cohort: validity mask;
                                  # population: THIS round's selection
    pms: Any = None               # (lanes,) int32 — layers each client shares
    share: Any = None             # (lanes, L) bool — layer_share_mask(pms)
    residual: Any = None          # EF residuals (lossy codec), leaves (lanes, ...)
    participation: Any = None     # (lanes,) int32 — selections so far (incl. now)
    # cohort lane (set while the compute phases run on gathered lanes):
    cohort_idx: Any = None        # (lanes,) int32 — client id behind each lane
    cohort_mask: Any = None       # (lanes,) bool — lane holds a selected client
    # scheduler lane (async mode; None under the synchronous barrier):
    dispatch_params: Any = None   # per-slot model snapshot each client
                                  # trained from, leaves (lanes, ...) — deltas
                                  # and EF are computed against it, not the
                                  # (newer) server model
    staleness: Any = None         # (lanes,) int32 — aggregation events since
                                  # each client's snapshot was cut
    rng_fit: Any = None
    rng_codec: Any = None
    rng_sel: Any = None
    # last-known eval results carried in (population phases; eval_every > 1
    # reuses them on skipped rounds):
    prev_accuracy: Any = None     # (C,)
    prev_loss: Any = None         # (C,)
    # filled by phases, in pipeline order:
    train_model: Any = None       # Personalizer
    trained: Any = None           # LocalTrainer
    new_local: Any = None         # engine (selected lanes keep training)
    agg_src: Any = None           # TransmitPhase — what the server receives
    wire_bytes: Any = None        # (lanes,) prospective uplink cost (codec)
    wire_paid: Any = None         # (lanes,) wire bytes actually paid this round
    update_norm: Any = None       # (lanes,) l2 norm of the compressed delta
    new_global: Any = None        # Aggregator
    eval_model: Any = None        # Personalizer.eval_model
    accuracy: Any = None          # Evaluator
    loss: Any = None              # Evaluator
    next_select: Any = None       # SelectorPhase
    next_pms: Any = None          # LayerPolicy
    merge_weight: Any = None      # Aggregator — (lanes,) staleness discount
                                  # each landing update was merged with
                                  # (observability signal; no phase reads it)
    counts: Any = None            # Evaluator — {name: (lanes,)} the model's
                                  # counts (env.eval_fn), None without them


def fold_counts(counts: dict, ops) -> dict:
    """Fold per-lane counts ``{name: (lanes,)}`` into one value each, by
    the model's ``ops`` (``((name, "sum" | "max"), ...)``)."""
    fold = {"sum": jnp.sum, "max": jnp.max}
    return {name: fold[op](counts[name]) for name, op in ops}


def _stack_clients(params, n_clients: int):
    """Broadcast an unstacked layered model to every client lane."""
    return jax.tree.map(
        lambda gl: jnp.broadcast_to(gl, (n_clients,) + gl.shape), params
    )


def _client_global(ctx: RoundContext, env: RoundEnv):
    """Each client's view of the global model at training time.

    Under the synchronous barrier that is the broadcast server model; under
    the async scheduler each client trains from the (possibly stale)
    snapshot it was dispatched with, carried stacked in
    ``ctx.dispatch_params``.
    """
    if ctx.dispatch_params is not None:
        return ctx.dispatch_params
    return _stack_clients(ctx.global_params, env.n_clients)


# ---------------------------------------------------------------------------
# Personalizer — builds train-time and eval-time per-client models
# ---------------------------------------------------------------------------


class Personalizer(_Phase):
    """Decides what model each client trains and is evaluated on.

    ``stateful`` declares whether the personalizer reads/writes per-client
    local parameters: stateless personalizers let the engine drop the
    ``(C, ...)`` local-params carry entirely, so the only model state that
    scales with the population is the cheap per-client vectors.
    """

    stateful: bool = True
    _SCOPED = {"train_model": "personalize", "eval_model": "personalize",
               "local_fallback": "personalize"}

    def train_model(self, ctx: RoundContext, env: RoundEnv):
        raise NotImplementedError

    def eval_model(self, ctx: RoundContext, env: RoundEnv):
        raise NotImplementedError

    def local_fallback(self, ctx: RoundContext, env: RoundEnv):
        """What unselected cohort lanes keep as their local model this round."""
        return ctx.local_params


@dataclasses.dataclass(frozen=True)
class NoPersonalizer(Personalizer):
    """Everyone trains and evaluates the broadcast global model (under the
    async scheduler: the dispatch-time snapshot). Reads no local params, so
    the engine skips the per-client model carry (``stateful = False``)."""

    stateful: bool = False

    def train_model(self, ctx, env):
        return _client_global(ctx, env)

    def eval_model(self, ctx, env):
        return _stack_clients(ctx.new_global, env.n_clients)

    def local_fallback(self, ctx, env):
        return ctx.train_model


@dataclasses.dataclass(frozen=True)
class FTPersonalizer(Personalizer):
    """Fine-tuning choice (Eq. 8): each client keeps whichever whole model
    (local vs global) has lower loss on its test shard."""

    def _pick(self, local, global_, env, stacked=False):
        loss_loc = jax.vmap(lambda p, x, y, m: env.loss_fn(p, x, y, m))(
            local, env.x_te, env.y_te, env.m_te
        )
        if stacked:  # async: per-client dispatch snapshots, leaves (C, ...)
            loss_glob = jax.vmap(lambda p, x, y, m: env.loss_fn(p, x, y, m))(
                global_, env.x_te, env.y_te, env.m_te
            )
        else:
            loss_glob = jax.vmap(lambda x, y, m: env.loss_fn(global_, x, y, m))(
                env.x_te, env.y_te, env.m_te
            )
        return personalize_ft(local, global_, loss_loc, loss_glob)

    def train_model(self, ctx, env):
        if ctx.dispatch_params is not None:
            return self._pick(ctx.local_params, ctx.dispatch_params, env, stacked=True)
        return self._pick(ctx.local_params, ctx.global_params, env)

    def eval_model(self, ctx, env):
        return self._pick(ctx.new_local, ctx.new_global, env)


@dataclasses.dataclass(frozen=True)
class ComposePersonalizer(Personalizer):
    """PMS/DLD: compose shared global layers with personalized local ones
    along the (C, L) share mask. ``compose_model`` broadcasts the global
    side per leaf, so the async scheduler's stacked dispatch snapshots
    compose exactly like the broadcast server model."""

    def train_model(self, ctx, env):
        if ctx.dispatch_params is not None:
            return compose_model(ctx.dispatch_params, ctx.local_params, ctx.share)
        return compose_model(ctx.global_params, ctx.local_params, ctx.share)

    def eval_model(self, ctx, env):
        return compose_model(ctx.new_global, ctx.new_local, ctx.share)


# ---------------------------------------------------------------------------
# LocalTrainer — Algorithm 2
# ---------------------------------------------------------------------------


def _batched(x, y, m, batch_size: int, remainder: str = "drop"):
    """Reshape a client's data slab to (nb, B, ...) minibatches.

    ``remainder='drop'`` trims to a whole number of batches (the seed
    behaviour — any *valid* samples in the trimmed tail are silently never
    trained on); ``remainder='pad'`` appends a masked tail batch instead so
    every valid sample is seen (the padding rows carry ``mask=False`` and
    contribute nothing to the masked loss).
    """
    n = x.shape[0]
    if remainder == "pad":
        nb = -(-n // batch_size)
        take = nb * batch_size
        if take > n:
            pad = take - n
            x = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])
            y = jnp.concatenate([y, jnp.zeros((pad, *y.shape[1:]), y.dtype)])
            m = jnp.concatenate([m, jnp.zeros((pad,), m.dtype)])
    else:
        nb = max(1, n // batch_size)
        take = nb * batch_size
        if take > n:  # dataset smaller than one batch: single ragged batch
            nb, take, batch_size = 1, n, n
        x, y, m = x[:take], y[:take], m[:take]
    return (
        x.reshape(nb, batch_size, *x.shape[1:]),
        y.reshape(nb, batch_size, *y.shape[1:]),
        m.reshape(nb, batch_size),
    )


class LocalTrainer(_Phase):
    """Produces ``ctx.trained`` from ``ctx.train_model`` (Algorithm 2)."""

    _SCOPED = {"fit": "train"}

    def fit(self, ctx: RoundContext, env: RoundEnv) -> RoundContext:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class SGDTrainer(LocalTrainer):
    """Algorithm 2 LocalTrain: tau epochs of minibatch SGD, vmapped over
    the lane axis — the gathered (K, ...) cohort under the cohort runtime,
    so training compute is O(K) not O(C); any invalid lanes' results are
    discarded by the engine's cohort mask.

    ``remainder`` controls what happens when the data slab is not a whole
    number of batches: ``'drop'`` truncates (seed behaviour — tail samples
    of large clients are silently never trained), ``'pad'`` adds a masked
    tail batch so every valid sample is seen. Padded/masked-out batches
    rely on the loss masking its mean (``mlp_loss`` guards the all-padded
    denominator); custom ``loss_fn``s must do the same.
    """

    epochs: int = 1
    batch_size: int = 32
    lr: float = 0.1
    remainder: str = "drop"

    def fit(self, ctx: RoundContext, env: RoundEnv) -> RoundContext:
        def local_fit(params, x, y, m, rng):
            xb, yb, mb = _batched(x, y, m, self.batch_size, self.remainder)

            def epoch(params, _):
                def step(params, batch):
                    bx, by, bm = batch
                    grads = jax.grad(env.loss_fn)(params, bx, by, bm)
                    new = jax.tree.map(lambda p, g: p - self.lr * g, params, grads)
                    return new, ()

                params, _ = jax.lax.scan(step, params, (xb, yb, mb))
                return params, ()

            params, _ = jax.lax.scan(epoch, params, None, length=self.epochs)
            return params

        fit_rngs = client_keys(ctx.rng_fit, ctx, env)
        trained = jax.vmap(local_fit)(
            ctx.train_model, env.x_tr, env.y_tr, env.m_tr, fit_rngs
        )
        return ctx._replace(trained=trained)


# ---------------------------------------------------------------------------
# TransmitPhase — the wire codec with error feedback
# ---------------------------------------------------------------------------


def _client_sq_norms(stacked, reference):
    """(C,) sum of squared differences between stacked leaves (C, ...) and
    the reference (unstacked, or stacked per client), reduced over every
    non-client axis."""
    total = 0.0
    for lc, lg in zip(jax.tree.leaves(stacked), jax.tree.leaves(reference)):
        d = lc - lg
        total = total + jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
    return total


@dataclasses.dataclass(frozen=True)
class TransmitPhase(_Phase):
    """Wire-codec phase: the uplink every selected client's shared delta
    takes to the server.

    Lossy codecs run an error-feedback step per client and layer (residuals
    carried in the round state, touched only for layers actually sent);
    lossless codecs pass the exact update through. Besides ``agg_src`` (what
    the server aggregates) this phase deposits the cost-aware selection
    signals: per-client prospective wire bytes, paid wire bytes, and the l2
    norm of the compressed uplink delta.

    The uplink delta is measured against each client's view of the global
    model: the broadcast server model under the synchronous barrier, or the
    per-client dispatch snapshot (``ctx.dispatch_params``) under the async
    scheduler — a stale client compresses and ships *its own* delta, and
    the staleness-weighted aggregator replays it onto the newer server
    model.
    """

    codec: Codec
    _SCOPED = {"transmit": "transmit", "wire_costs": "transmit"}

    @property
    def lossy(self) -> bool:
        return self.codec.lossy

    def transmit(self, ctx: RoundContext, env: RoundEnv) -> RoundContext:
        g, trained = ctx.global_params, ctx.trained
        base = ctx.dispatch_params  # None under the synchronous barrier
        if self.codec.lossy and ctx.residual is None:
            raise ValueError(
                "lossy codec requires RoundState.residual; initialize it with "
                "jax.tree.map(jnp.zeros_like, local_params) (run_federated does)"
            )
        agg_src, new_residual, sq_norms = trained, ctx.residual, [None] * len(g)
        if self.codec.lossy:
            # The server aggregates decode(encode(delta + residual)); the new
            # residual absorbs what the codec dropped, but only for clients
            # that actually transmitted the layer (selected AND sharing it) —
            # personalized layers never hit the wire, so their residuals stay.
            # An int8 codec on a TPU runs each leaf's step for every lane as
            # its two kernels (the ``codec.ef`` scope), which also return the
            # lanes' squared update norms.
            agg_src, new_residual = [], []
            for j, (tr_j, g_j, res_j) in enumerate(zip(trained, g, ctx.residual)):
                sent_j = ctx.select & ctx.share[:, j]  # (lanes,)
                keys = client_keys(jax.random.fold_in(ctx.rng_codec, j), ctx, env)
                # async: each client's delta vs its dispatch snapshot
                ref_j = g_j if base is None else base[j]
                if self.codec.fused_ef:
                    with jax.named_scope("codec.ef"):
                        recon_j, res_j, sq_norms[j] = self.codec.ef_lanes(
                            tr_j, ref_j, res_j, keys, sent_j)
                else:

                    def client_ef(tr_c, res_c, key, ref_c):
                        delta = jax.tree.map(lambda t, gl: t - gl, tr_c, ref_c)
                        dec, new_r = ef_step(self.codec, delta, res_c, key)
                        recon = jax.tree.map(lambda gl, d: gl + d, ref_c, dec)
                        return recon, new_r

                    recon_j, new_r_j = jax.vmap(
                        client_ef, in_axes=(0, 0, 0, None if base is None else 0)
                    )(tr_j, res_j, keys, ref_j)
                    res_j = jax.tree.map(
                        lambda n, o: jnp.where(
                            sent_j.reshape((-1,) + (1,) * (n.ndim - 1)), n, o
                        ),
                        new_r_j,
                        res_j,
                    )
                agg_src.append(recon_j)
                new_residual.append(res_j)

        # --- cost signals for selection + accounting ------------------------
        # lane-level (cohort) versions; the engine computes the population
        # (C,) views via wire_costs and scatters update_norm back into the
        # carried per-client lane
        wire_prospective, wire_paid = self.wire_costs(g, ctx.share, ctx.select)
        share_f = ctx.share.astype(jnp.float32)
        norm_sq = 0.0
        for j, sq_j in enumerate(sq_norms):
            ref_j = base[j] if base is not None else g[j]
            norm_sq = norm_sq + share_f[:, j] * (
                _client_sq_norms(agg_src[j], ref_j) if sq_j is None else sq_j)
        return ctx._replace(
            agg_src=agg_src,
            residual=new_residual,
            wire_bytes=wire_prospective,
            wire_paid=wire_paid,
            update_norm=jnp.sqrt(norm_sq),
        )

    def layer_wire(self, global_params) -> jnp.ndarray:
        """(L,) static wire bytes one client pays per layer through the codec."""
        return jnp.asarray(
            [tree_wire_bytes(self.codec, layer) for layer in global_params],
            jnp.float32,
        )

    def wire_costs(self, global_params, share: jnp.ndarray, select: jnp.ndarray):
        """Population wire-cost signals: ``(prospective, paid)`` per-client
        bytes from the (C, L) share mask and (C,) selection — prospective
        counts every shared layer, paid only those a selected client
        actually shipped this round."""
        lw = self.layer_wire(global_params)
        share_f = share.astype(jnp.float32)
        return share_f @ lw, (share_f * select.astype(jnp.float32)[:, None]) @ lw

    def silo_transmit(self, x: jnp.ndarray, residual: jnp.ndarray, rng: jax.Array):
        """Cross-silo lane: EF-compress each silo's stacked contribution.

        ``x``/``residual`` are single leaves with a leading silo axis
        (S, ...); each silo's slice is encoded independently (per-silo codec
        blocks/scales). Returns ``(decoded, new_residual)``, both (S, ...).
        """
        keys = jax.random.split(rng, x.shape[0])
        return jax.vmap(lambda v, e, k: ef_step(self.codec, v, e, k))(
            x, residual, keys
        )


# ---------------------------------------------------------------------------
# Aggregator — Eq. 1
# ---------------------------------------------------------------------------


class Aggregator(_Phase):
    """Reduces the lane axis into the new global model.

    All three implementations express the reduction as weighted partial
    sums over their local lanes; setting ``axis_name`` (a shard_map mesh
    axis — ``"cohort"`` under repro.fl.shard) finishes each sum with one
    ``lax.psum`` over that axis, so the same phase aggregates a cohort
    partitioned K/D per device. ``axis_name=None`` (default) is the
    single-device reduction, bit-identical to the pre-sharding code.

    ``edge_groups`` routes the reduction through two-level hierarchical
    (edge-server) aggregation: the population is partitioned into E
    contiguous client-id blocks, each edge partial-sums its members, and
    the server merges the E edge partials. ``edge_groups <= 1`` keeps the
    flat sum exactly (E=1 is one edge whose partial IS the server sum —
    trajectory bit-identical); E > 1 reassociates the reduction tree
    (~1 ulp, like ``axis_name`` sharding). Composes with ``axis_name``:
    edge partials are shard-local, the psum finishes them.
    """

    edge_groups = 0   # subclasses declare the dataclass field
    axis_name = None  # subclasses declare the dataclass field (kept last)
    _SCOPED = {"aggregate": "aggregate"}

    def _edges(self, ctx: RoundContext, env: RoundEnv):
        """``(edge_ids, n_edges)`` for the current lanes, or ``(None, 0)``
        when hierarchical aggregation is off. Edge membership is by true
        client id (``ctx.cohort_idx``), so a client aggregates through the
        same edge whichever lane/slot it lands in."""
        if self.edge_groups <= 1:
            return None, 0
        group = -(-env.pop // self.edge_groups)
        cid = (
            ctx.cohort_idx
            if ctx.cohort_idx is not None
            else jnp.arange(env.n_clients)
        )
        ids = jnp.clip(cid // group, 0, self.edge_groups - 1).astype(jnp.int32)
        return ids, self.edge_groups

    def aggregate(self, ctx: RoundContext, env: RoundEnv) -> RoundContext:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FedAvgAggregator(Aggregator):
    """Plain Eq. 1 over selected clients, full model."""

    edge_groups: int = 0
    axis_name: str | None = None

    def aggregate(self, ctx, env):
        edge_ids, n_edges = self._edges(ctx, env)
        return ctx._replace(
            new_global=fedavg_aggregate(
                ctx.agg_src, ctx.select, env.n_samples, axis_name=self.axis_name,
                edge_ids=edge_ids, n_edges=n_edges,
            )
        )


@dataclasses.dataclass(frozen=True)
class MaskedPartialAggregator(Aggregator):
    """ACSP-FL masked aggregation: only layers a client shares contribute;
    layers nobody shared keep the previous global value."""

    edge_groups: int = 0
    axis_name: str | None = None

    def aggregate(self, ctx, env):
        edge_ids, n_edges = self._edges(ctx, env)
        return ctx._replace(
            new_global=masked_partial_aggregate(
                ctx.agg_src, ctx.global_params, ctx.select, env.n_samples,
                ctx.share, axis_name=self.axis_name,
                edge_ids=edge_ids, n_edges=n_edges,
            )
        )


# --- staleness weighting (FedBuff, Nguyen et al. 2022) ----------------------

def _stale_constant(s, exponent, threshold):
    return jnp.ones_like(s)


def _stale_polynomial(s, exponent, threshold):
    return (1.0 + s) ** (-exponent)


def _stale_hinge(s, exponent, threshold):
    return jnp.where(s <= threshold, 1.0, 1.0 / (exponent * (s - threshold) + 1.0))


STALENESS_FNS = {
    "constant": _stale_constant,
    "polynomial": _stale_polynomial,
    "hinge": _stale_hinge,
}


def staleness_weight(
    fn: str, staleness: jnp.ndarray, exponent: float = 0.5, threshold: float = 4.0
) -> jnp.ndarray:
    """(C,) merge discount for updates ``staleness`` aggregation events old.

    ``constant`` ignores staleness (plain FedAvg weighting); ``polynomial``
    is FedBuff's ``(1+s)^-a``; ``hinge`` is flat up to ``threshold`` then
    decays as ``1/(a(s-b)+1)``. All return 1.0 at s=0.
    """
    if fn not in STALENESS_FNS:
        raise KeyError(f"unknown staleness_fn {fn!r}; have {sorted(STALENESS_FNS)}")
    return STALENESS_FNS[fn](jnp.asarray(staleness, jnp.float32), exponent, threshold)


@dataclasses.dataclass(frozen=True)
class StalenessAggregator(Aggregator):
    """Buffered staleness-weighted merge (FedBuff-style): the server folds
    each landing client's *delta* (vs its dispatch snapshot) into the
    current global model, discounted by how many aggregation events passed
    since that snapshot was cut.

    ``new_g = g + sum_i v_i d_i / sum_i v_i`` per shared layer, with
    ``v_i = select_i * |d_i| * s(staleness_i)``. With ``constant`` weights,
    zero staleness, and full participation this reduces to FedAvg (the
    sync-equivalence acceptance criterion). Works under the synchronous
    barrier too (staleness defaults to zero there).
    """

    staleness_fn: str = "polynomial"
    exponent: float = 0.5
    threshold: float = 4.0
    edge_groups: int = 0
    axis_name: str | None = None

    def aggregate(self, ctx, env):
        if self.staleness_fn not in STALENESS_FNS:  # fail at trace time
            raise KeyError(
                f"unknown staleness_fn {self.staleness_fn!r}; have {sorted(STALENESS_FNS)}"
            )
        base = ctx.dispatch_params
        n_layers = len(ctx.agg_src)
        deltas = []
        for j in range(n_layers):
            ref_j = base[j] if base is not None else ctx.global_params[j]
            deltas.append(
                jax.tree.map(lambda a, r: a - r, ctx.agg_src[j], ref_j)
            )
        stale = (
            ctx.staleness
            if ctx.staleness is not None
            else jnp.zeros(ctx.select.shape, jnp.int32)
        )
        discount = staleness_weight(
            self.staleness_fn, stale, self.exponent, self.threshold
        )
        w = (
            ctx.select.astype(jnp.float32)
            * env.n_samples.astype(jnp.float32)
            * discount
        )
        edge_ids, n_edges = self._edges(ctx, env)
        return ctx._replace(
            new_global=staleness_weighted_merge(
                deltas, ctx.global_params, w, ctx.share, axis_name=self.axis_name,
                edge_ids=edge_ids, n_edges=n_edges,
            ),
            # the per-lane discount factor alone (sample weighting excluded)
            # — the scheduler surfaces its landed mean to the run recorder
            merge_weight=discount,
        )


# ---------------------------------------------------------------------------
# Evaluator
# ---------------------------------------------------------------------------


class Evaluator(_Phase):
    _SCOPED = {"evaluate": "eval"}

    def evaluate(self, ctx: RoundContext, env: RoundEnv, model_fn=None) -> RoundContext:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class DistributedEvaluator(Evaluator):
    """Distributed eval (paper §4.3): each client scores its composed model
    on its own test shard; accuracy and loss feed the selector.

    Full-population eval is itself O(C) every round; ``eval_every=n``
    recomputes it only on rounds (aggregation events) where
    ``t % n == 0`` and carries the last-known accuracy/loss
    (``ctx.prev_accuracy``/``prev_loss``) in between, so large-population
    async runs are not eval-bound. Selection reads the carried values on
    skipped rounds. ``eval_every=1`` (default) keeps the seed's
    every-round eval with no conditional in the traced step.

    ``model_fn`` (when given) builds the per-client eval models *inside*
    the fresh branch, so the personalizer's O(C) composed-model work is
    also skipped on carried rounds — the engine passes it on the thinned
    path instead of pre-filling ``ctx.eval_model``.
    """

    eval_every: int = 1

    def __post_init__(self):
        if self.eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {self.eval_every!r}")

    def evaluate(self, ctx, env, model_fn=None):
        def fresh(_):
            model = model_fn() if model_fn is not None else ctx.eval_model
            if env.eval_fn is not None:  # the model's one pass; counts as float32
                acc, loss, counts = jax.vmap(env.eval_fn)(model, env.x_te, env.y_te, env.m_te)
                return acc, loss, {k: v.astype(jnp.float32) for k, v in counts.items()}
            acc = jax.vmap(lambda p, x, y, m: env.acc_fn(p, x, y, m))(
                model, env.x_te, env.y_te, env.m_te
            )
            loss = jax.vmap(lambda p, x, y, m: env.loss_fn(p, x, y, m))(
                model, env.x_te, env.y_te, env.m_te
            )
            return acc, loss, None

        if self.eval_every == 1:
            acc, loss, counts = fresh(None)
        else:
            zeros = jnp.zeros((env.n_clients,), jnp.float32)
            prev_acc = ctx.prev_accuracy if ctx.prev_accuracy is not None else zeros
            prev_loss = ctx.prev_loss if ctx.prev_loss is not None else zeros

            def carried(_):  # a skipped round counts nothing
                counts = None
                if env.eval_fn is not None:
                    counts = {name: zeros for name, _ in env.count_ops}
                return prev_acc, prev_loss, counts

            acc, loss, counts = jax.lax.cond(
                (ctx.t % self.eval_every) == 0, fresh, carried, None
            )
        return ctx._replace(accuracy=acc, loss=loss, counts=counts)


# ---------------------------------------------------------------------------
# SelectorPhase — Algorithm 1 l.12
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SelectorPhase(_Phase):
    """Wraps a SelectionStrategy; assembles the full ClientObservations
    (including the codec-phase cost signals) and picks next round's cohort."""

    strategy: SelectionStrategy
    _SCOPED = {"select": "select"}

    def select(self, ctx: RoundContext, env: RoundEnv) -> RoundContext:
        obs = ClientObservations(
            accuracy=ctx.accuracy,
            loss=ctx.loss,
            n_samples=env.n_samples,
            delay=env.delay,
            wire_bytes=ctx.wire_bytes,
            update_norm=ctx.update_norm,
            participation_count=ctx.participation,
        )
        return ctx._replace(next_select=self.strategy.select(obs, ctx.t, ctx.rng_sel))


# ---------------------------------------------------------------------------
# LayerPolicy — how many layers each client shares next round
# ---------------------------------------------------------------------------


class LayerPolicy(_Phase):
    _SCOPED = {"next_pms": "select"}

    def next_pms(self, ctx: RoundContext, env: RoundEnv, n_layers: int):
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FullShare(LayerPolicy):
    """Everyone always shares the whole model."""

    def next_pms(self, ctx, env, n_layers):
        return jnp.full((env.n_clients,), n_layers, jnp.int32)


@dataclasses.dataclass(frozen=True)
class StaticPMS(LayerPolicy):
    """Fixed shared-prefix length (the paper's PMS k variants)."""

    layers: int = 2

    def next_pms(self, ctx, env, n_layers):
        return jnp.full((env.n_clients,), self.layers, jnp.int32)


@dataclasses.dataclass(frozen=True)
class DLDPolicy(LayerPolicy):
    """Dynamic layer definition (Eq. 9): per-client PMS from accuracy."""

    def next_pms(self, ctx, env, n_layers):
        return dynamic_layer_definition(ctx.accuracy, n_layers)


# ---------------------------------------------------------------------------
# registries (mirror get_strategy / make_codec)
# ---------------------------------------------------------------------------

_PHASE_REGISTRY: dict[str, dict[str, Callable]] = {
    "personalizer": {
        "none": NoPersonalizer,
        "ft": FTPersonalizer,
        "compose": ComposePersonalizer,
    },
    "trainer": {"sgd": SGDTrainer},
    "aggregator": {
        "fedavg": FedAvgAggregator,
        "masked-partial": MaskedPartialAggregator,
        "staleness": StalenessAggregator,
    },
    "evaluator": {"distributed": DistributedEvaluator},
    "layer-policy": {"full": FullShare, "static": StaticPMS, "dld": DLDPolicy},
}


def get_phase(kind: str, name: str, **kwargs):
    """Build a phase component by (kind, name), e.g.
    ``get_phase('aggregator', 'fedavg')``. Unknown kinds/names raise
    ``KeyError`` listing what is available."""
    if kind not in _PHASE_REGISTRY:
        raise KeyError(f"unknown phase kind {kind!r}; have {sorted(_PHASE_REGISTRY)}")
    reg = _PHASE_REGISTRY[kind]
    key = name.lower()
    if key not in reg:
        raise KeyError(f"unknown {kind} {name!r}; have {sorted(reg)}")
    return reg[key](**kwargs)


def register_phase(kind: str, name: str, factory: Callable) -> None:
    """Register a custom phase factory under (kind, name); ``factory`` is
    called with the keyword arguments passed to ``get_phase``."""
    if kind not in _PHASE_REGISTRY:
        raise KeyError(f"unknown phase kind {kind!r}; have {sorted(_PHASE_REGISTRY)}")
    _PHASE_REGISTRY[kind][name.lower()] = factory
