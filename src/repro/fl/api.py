"""Composable round-pipeline API for the federated engine.

A federated round is a ``RoundPipeline`` — an explicit, swappable sequence
of phase components (see ``repro.fl.phases``):

  Personalizer -> LocalTrainer -> TransmitPhase (wire codec + EF)
               -> Aggregator -> Evaluator -> SelectorPhase -> LayerPolicy

``FLConfig`` is the declarative form: five nested validated sub-configs
(``SelectionConfig``, ``PersonalizationConfig``, ``CodecConfig``,
``TrainConfig``, ``SchedulerConfig``) with a flat-kwargs backward-compat
constructor, so both

    FLConfig(strategy="acsp-fl", personalization="dld", rounds=30)   # flat
    FLConfig(selection=SelectionConfig("acsp-fl"), train=TrainConfig(rounds=30))

build the same config. ``pipeline_from_config`` maps a config onto phase
objects via the string registries; ``build_round_step`` composes any
pipeline into the jitted round step, and ``build_chunk_step`` fuses
``scan_chunk`` consecutive round steps into a single donated on-device
executable (the round-fused sync loop). The server loop that drives the
step lives in ``repro.fl.sched``: ``SchedulerConfig.mode`` picks between the
synchronous barrier (``SyncScheduler``, the paper's Algorithm 1) and
event-driven buffered execution (``AsyncScheduler``, FedBuff-style) —
``run_federated`` dispatches on it.

Composing a custom round::

    from repro.fl import api, phases

    pipe = api.pipeline_from_config(cfg)                       # the default
    pipe = dataclasses.replace(                                 # swap a phase
        pipe, selector=phases.SelectorPhase(get_strategy("oort-wire", fraction=0.3))
    )
    hist = run_federated(data, cfg, pipeline=pipe)

The default pipeline reproduces the pre-refactor monolithic round step
bit-identically (guarded by tests/test_fl_api.py golden trajectories).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    CodecConfig,
    ExecutionConfig,
    FaultConfig,
    PersonalizationConfig,
    SchedulerConfig,
    SelectionConfig,
    TrainConfig,
)
from repro.core.aggregation import finite_update_guard, transmitted_parameters
from repro.core.layersharing import layer_param_sizes, layer_share_mask
from repro.data.synthetic import FederatedDataset
from repro.fl import phases
from repro.fl.cohort import cohort_indices, tree_scatter, tree_take
from repro.models.mlp import mlp_accuracy, mlp_loss

__all__ = [
    "FLConfig",
    "FLModel",
    "SelectionConfig",
    "PersonalizationConfig",
    "CodecConfig",
    "SchedulerConfig",
    "ExecutionConfig",
    "FaultConfig",
    "TrainConfig",
    "RoundPipeline",
    "RoundState",
    "pipeline_from_config",
    "build_round_step",
    "build_chunk_step",
]


# ---------------------------------------------------------------------------
# FLConfig — nested sub-configs + flat-kwargs backward compat
# ---------------------------------------------------------------------------

# flat kwarg -> (group field, sub-config attribute)
_FLAT_KEYS = {
    "strategy": ("selection", "strategy"),
    "fraction": ("selection", "fraction"),
    "decay": ("selection", "decay"),
    "personalization": ("personalization", "mode"),
    "pms_layers": ("personalization", "pms_layers"),
    "codec": ("codec", "spec"),
    "codec_bits": ("codec", "bits"),
    "topk_fraction": ("codec", "topk_fraction"),
    "rounds": ("train", "rounds"),
    "epochs": ("train", "epochs"),
    "batch_size": ("train", "batch_size"),
    "lr": ("train", "lr"),
    "momentum": ("train", "momentum"),
    "seed": ("train", "seed"),
    "remainder": ("train", "remainder"),
    "scheduler": ("scheduler", "mode"),
    "buffer_k": ("scheduler", "buffer_k"),
    "max_concurrency": ("scheduler", "max_concurrency"),
    "staleness_fn": ("scheduler", "staleness_fn"),
    "heterogeneity": ("scheduler", "heterogeneity"),
    "cohort_size": ("execution", "cohort_size"),
    "eval_every": ("execution", "eval_every"),
    "scan_chunk": ("execution", "scan_chunk"),
    "cohort_devices": ("execution", "cohort_devices"),
    "host_population": ("execution", "host_population"),
    "eval_chunk": ("execution", "eval_chunk"),
    "edge_groups": ("execution", "edge_groups"),
    "dropout_rate": ("faults", "dropout_rate"),
    "deadline_s": ("faults", "deadline_s"),
    "corrupt_rate": ("faults", "corrupt_rate"),
    "max_retries": ("faults", "max_retries"),
}

_GROUP_TYPES = {
    "selection": SelectionConfig,
    "personalization": PersonalizationConfig,
    "codec": CodecConfig,
    "train": TrainConfig,
    "scheduler": SchedulerConfig,
    "execution": ExecutionConfig,
    "faults": FaultConfig,
}


@dataclasses.dataclass(frozen=True)
class FLModel:
    """The model a federated run trains, when it is not the paper's MLP.

    ``init(rng)`` builds the layered model (a list of per-layer pytrees:
    what layer sharing and DLD count); ``loss(params, x, y, m)`` and
    ``accuracy(params, x, y, m)`` score one client's batch of rows (``m``
    the rows' validity mask). ``evaluate(params, x, y, m)``, where given,
    returns ``(accuracy, loss, counts)`` from one pass, and the evaluator
    uses it in their place; ``counts`` names each count it returns with how
    the round folds it over the clients, ``"sum"`` or ``"max"``. Each count
    is a per-round record of the history (``FLHistory.counts``).
    """

    init: Callable
    loss: Callable
    accuracy: Callable
    evaluate: Callable | None = None
    counts: tuple = ()            # ((name, "sum" | "max"), ...)


def model_functions(cfg: "FLConfig", init_fn, loss_fn, acc_fn):
    """``(init_fn, loss_fn, acc_fn, model)`` of a run: the config's
    ``model`` where it names one, else the functions given (the MLP's by
    default; ``init_fn=None`` builds it from the data's widths)."""
    m = cfg.model
    if m is None:
        return init_fn, loss_fn, acc_fn, None
    return m.init, m.loss, m.accuracy, m


@dataclasses.dataclass(frozen=True, init=False)
class FLConfig:
    """Federated experiment config: seven nested validated sub-configs.

    Accepts either the nested objects (``selection=SelectionConfig(...)``)
    or the seed's flat kwargs (``strategy="oort", fraction=0.5, rounds=30,
    codec="int8", cohort_size=64, dropout_rate=0.3``) — but not both forms
    for the same group. The seed's flat attributes (``cfg.strategy``,
    ``cfg.rounds``, ...) remain readable. ``model`` (an ``FLModel``) names
    the model the run trains; None (default) is the paper's MLP.
    """

    selection: SelectionConfig
    personalization: PersonalizationConfig
    codec: CodecConfig
    train: TrainConfig
    scheduler: SchedulerConfig
    execution: ExecutionConfig
    faults: FaultConfig
    model: FLModel | None

    def __init__(self, selection=None, personalization=None, codec=None,
                 train=None, scheduler=None, execution=None, faults=None,
                 model=None, **flat):
        # string conveniences on the group params themselves: the seed's
        # FLConfig(personalization="dld", codec="int8") spelled the mode/spec
        # directly, so route strings into the flat namespace
        if isinstance(personalization, str):
            flat["personalization"], personalization = personalization, None
        if isinstance(codec, str):
            flat["codec"], codec = codec, None
        if isinstance(selection, str):
            flat["strategy"], selection = selection, None
        if isinstance(scheduler, str):
            flat["scheduler"], scheduler = scheduler, None

        unknown = set(flat) - set(_FLAT_KEYS)
        if unknown:
            raise TypeError(
                f"unknown FLConfig kwargs {sorted(unknown)}; flat kwargs are "
                f"{sorted(_FLAT_KEYS)} (or pass nested "
                f"{sorted(_GROUP_TYPES)} sub-configs)"
            )
        given = {"selection": selection, "personalization": personalization,
                 "codec": codec, "train": train, "scheduler": scheduler,
                 "execution": execution, "faults": faults}
        grouped: dict[str, dict[str, Any]] = {g: {} for g in _GROUP_TYPES}
        for key, value in flat.items():
            group, attr = _FLAT_KEYS[key]
            grouped[group][attr] = value
        for group, cls in _GROUP_TYPES.items():
            if given[group] is not None:
                if grouped[group]:
                    raise ValueError(
                        f"pass either {group}={cls.__name__}(...) or its flat "
                        f"kwargs, not both (got both for {sorted(grouped[group])})"
                    )
                if not isinstance(given[group], cls):
                    raise TypeError(
                        f"{group} must be a {cls.__name__}, got {type(given[group]).__name__}"
                    )
                object.__setattr__(self, group, given[group])
            else:
                object.__setattr__(self, group, cls(**grouped[group]))
        if model is not None and not isinstance(model, FLModel):
            raise TypeError(f"model must be an FLModel, got {type(model).__name__}")
        object.__setattr__(self, "model", model)

    # --- flat read access (seed compatibility) -----------------------------
    @property
    def strategy(self) -> str:
        return self.selection.strategy

    @property
    def fraction(self) -> float:
        return self.selection.fraction

    @property
    def decay(self) -> float:
        return self.selection.decay

    @property
    def pms_layers(self) -> int:
        return self.personalization.pms_layers

    @property
    def codec_bits(self) -> int:
        return self.codec.bits

    @property
    def topk_fraction(self) -> float:
        return self.codec.topk_fraction

    @property
    def rounds(self) -> int:
        return self.train.rounds

    @property
    def epochs(self) -> int:
        return self.train.epochs

    @property
    def batch_size(self) -> int:
        return self.train.batch_size

    @property
    def lr(self) -> float:
        return self.train.lr

    @property
    def momentum(self) -> float:
        return self.train.momentum

    @property
    def seed(self) -> int:
        return self.train.seed

    @property
    def buffer_k(self) -> int:
        return self.scheduler.buffer_k

    @property
    def max_concurrency(self) -> int:
        return self.scheduler.max_concurrency

    @property
    def cohort_size(self) -> int:
        return self.execution.cohort_size

    @property
    def eval_every(self) -> int:
        return self.execution.eval_every

    @property
    def scan_chunk(self) -> int:
        return self.execution.scan_chunk

    @property
    def cohort_devices(self) -> int:
        return self.execution.cohort_devices

    @property
    def host_population(self) -> int:
        return self.execution.host_population

    @property
    def eval_chunk(self) -> int:
        return self.execution.eval_chunk

    @property
    def edge_groups(self) -> int:
        return self.execution.edge_groups

    @property
    def dropout_rate(self) -> float:
        return self.faults.dropout_rate

    @property
    def deadline_s(self) -> float:
        return self.faults.deadline_s

    @property
    def corrupt_rate(self) -> float:
        return self.faults.corrupt_rate

    @property
    def max_retries(self) -> int:
        return self.faults.max_retries

    def strategy_obj(self):
        return self.selection.strategy_obj()

    def codec_obj(self):
        return self.codec.codec_obj()


# ---------------------------------------------------------------------------
# RoundPipeline — the composed phases
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RoundPipeline:
    """One federated round as an explicit phase sequence. Swap any field
    (``dataclasses.replace``) to compose a custom round."""

    personalizer: phases.Personalizer
    trainer: phases.LocalTrainer
    transmit: phases.TransmitPhase
    aggregator: phases.Aggregator
    evaluator: phases.Evaluator
    selector: phases.SelectorPhase
    layer_policy: phases.LayerPolicy


def pipeline_from_config(cfg: FLConfig) -> RoundPipeline:
    """Map a (nested) FLConfig onto phase objects via the registries.

    The scheduler group picks the aggregator family: async mode always
    merges through the staleness-weighted buffered aggregator (which
    honours the share mask, so it composes with PMS/DLD partial sharing);
    sync mode keeps the paper's FedAvg / masked-partial aggregation.
    """
    mode = cfg.personalization.mode
    personalizer = phases.get_phase(
        "personalizer", mode if mode in ("none", "ft") else "compose"
    )
    if mode == "dld":
        layer_policy = phases.get_phase("layer-policy", "dld")
    elif mode == "pms":
        layer_policy = phases.get_phase("layer-policy", "static", layers=cfg.personalization.pms_layers)
    else:
        layer_policy = phases.get_phase("layer-policy", "full")
    sched = cfg.scheduler
    edge_e = cfg.execution.edge_groups
    if sched.mode == "async":
        aggregator = phases.get_phase(
            "aggregator", "staleness",
            staleness_fn=sched.staleness_fn,
            exponent=sched.staleness_exponent,
            threshold=sched.staleness_threshold,
            edge_groups=edge_e,
        )
    else:
        aggregator = phases.get_phase(
            "aggregator", "masked-partial" if mode in ("pms", "dld") else "fedavg",
            edge_groups=edge_e,
        )
    return RoundPipeline(
        personalizer=personalizer,
        trainer=phases.get_phase(
            "trainer", "sgd",
            epochs=cfg.train.epochs, batch_size=cfg.train.batch_size,
            lr=cfg.train.lr, remainder=cfg.train.remainder,
        ),
        transmit=phases.TransmitPhase(cfg.codec_obj()),
        aggregator=aggregator,
        evaluator=phases.get_phase(
            "evaluator", "distributed", eval_every=cfg.execution.eval_every
        ),
        selector=phases.SelectorPhase(cfg.strategy_obj()),
        layer_policy=layer_policy,
    )


# ---------------------------------------------------------------------------
# round-step composition
# ---------------------------------------------------------------------------


class RoundState(NamedTuple):
    """Carried server-loop state (a pytree; jit round-step input/output)."""

    global_params: Any            # layered list, leaves (...)
    local_params: Any             # layered list, leaves (C, ...); None when
                                  # the personalizer is stateless
    accuracy: jnp.ndarray         # (C,)
    select: jnp.ndarray           # (C,) bool
    pms: jnp.ndarray              # (C,) int32 — layers each client will share
    rng: jax.Array
    residual: Any = None          # EF residuals (lossy codec only), (C, ...)
    participation: Any = None     # (C,) int32 — cumulative selection counts
    loss: Any = None              # (C,) last-known eval loss (eval_every)
    update_norm: Any = None       # (C,) last-known compressed-delta norm


def build_env(
    data: FederatedDataset,
    seed: int,
    loss_fn: Callable = mlp_loss,
    acc_fn: Callable = mlp_accuracy,
    model: FLModel | None = None,
) -> phases.RoundEnv:
    """Device-resident static environment for the round phases; ``model``
    adds its one-pass ``evaluate`` and its counts."""
    return phases.RoundEnv(
        x_tr=jnp.asarray(data.x_train),
        y_tr=jnp.asarray(data.y_train),
        m_tr=jnp.asarray(data.m_train),
        x_te=jnp.asarray(data.x_test),
        y_te=jnp.asarray(data.y_test),
        m_te=jnp.asarray(data.m_test),
        n_samples=jnp.asarray(data.n_samples, jnp.float32),
        # Oort's systemic term: per-client delay, fixed per experiment
        delay=jax.random.uniform(
            jax.random.PRNGKey(seed + 99), (data.n_clients,), minval=0.5, maxval=2.0
        ),
        n_clients=data.n_clients,
        loss_fn=loss_fn,
        acc_fn=acc_fn,
        population=data.n_clients,
        eval_fn=model.evaluate if model is not None else None,
        count_ops=model.counts if model is not None else (),
    )


def _tree_where(mask: jnp.ndarray, new, old):
    """Per-lane select over ``(lanes, ...)`` trees; ``None`` passes through."""
    if new is None:
        return None
    return jax.tree.map(
        lambda n, o: jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
        new,
        old,
    )


def build_round_step(
    env: phases.RoundEnv,
    pipeline: RoundPipeline,
    execution: ExecutionConfig | None = None,
    faults: FaultConfig | None = None,
):
    """Compose a RoundPipeline into the jitted cohort-gathered round step.

    The step maps ``(RoundState, t) -> (RoundState, out)`` where ``out``
    holds the host-side history records. Execution is gather -> compute ->
    scatter: the (C,) selection mask resolves to a fixed-size index set
    ``idx (K,)`` (``execution.cohort_size``; 0 -> K = C), the cohort's data
    slabs, local params, and EF residuals are gathered with ``jnp.take``,
    the compute phases (personalize/train/transmit/aggregate) run on
    ``(K, ...)`` lanes, and results scatter back into the ``(C, ...)``
    server state with ``.at[idx].set`` — so per-round training compute and
    trained-state memory are O(K). Evaluation and selection stay
    population-wide (thinned by ``DistributedEvaluator(eval_every=n)``).

    Bit-identity: at K = C the gathered lanes compute exactly the numbers
    the dense pre-refactor engine computed — per-client rng keys are
    population-anchored (``phases.client_keys``), cohort lanes keep
    ascending client-id order so every masked-aggregation sum reduces its
    nonzero terms in the dense order, and phase order / rng-lane splits are
    unchanged (guarded by the committed golden trajectories).

    ``execution.cohort_devices != 0`` delegates to
    ``repro.fl.shard.build_sharded_round_step``: the same step with the
    compute phases shard_mapped over a ``cohort`` device mesh (K/D lanes
    per device, aggregation as shard-local partial sums + one psum).

    Failure semantics: every step carries the always-on finite-delta guard
    (``repro.core.aggregation.finite_update_guard``) — cohort lanes whose
    transmitted ``update_norm`` is non-finite are zero-masked out of
    aggregation, their local/residual state reverted, and counted in the
    ``out["rejected"]`` leaf. When ``faults`` is an *enabled*
    ``FaultConfig`` the returned step instead maps
    ``(state, t, alive (C,) bool, corrupt (C,) int8) -> (state, out)``:
    ``alive`` (crash/deadline survivors, computed host-side from the
    round's ``repro.fl.faults.compile_fault_plan``) is intersected into
    the selection before cohort resolution, and ``corrupt`` kinds rewrite
    the trained params post-trainer so the guard rejects them. Fault-off
    steps contain no fault ops at all — bit-identity with the committed
    goldens is untouched.
    """
    execution = execution or ExecutionConfig()
    faulty = faults is not None and faults.enabled
    if execution.cohort_devices != 0:
        if faulty:
            raise ValueError(
                "fault injection composes with the cohort runtime and host "
                "population plane but not with cohort_devices sharding; set "
                "cohort_devices=0 or disable FaultConfig"
            )
        from repro.fl.shard import build_sharded_round_step

        return build_sharded_round_step(env, pipeline, execution)
    cohort_k = execution.resolved_cohort(env.n_clients)
    stateful = pipeline.personalizer.stateful
    max_norm = float(faults.max_update_norm) if faulty else 0.0
    corrupt_scale = float(faults.corrupt_scale) if faulty else 0.0

    @phases.scoped("fl.round")
    def _round_body(state: RoundState, t: jnp.ndarray, alive, corrupt):
        g = state.global_params
        n_layers = len(g)
        share = layer_share_mask(n_layers, state.pms)  # (C, L)

        if pipeline.transmit.lossy:
            rng, r_fit, r_sel, r_codec = jax.random.split(state.rng, 4)
        else:
            rng, r_fit, r_sel = jax.random.split(state.rng, 3)
            r_codec = None

        # --- gather: selection mask -> fixed-size cohort (K,) ---
        # crashed / past-deadline clients (fault mode) never enter the
        # cohort: they trained nothing the server sees, pay no wire, and
        # their lanes backfill from the remaining selected clients
        with jax.named_scope("fl.gather"):
            select_in = state.select if alive is None else state.select & alive
            idx = cohort_indices(select_in, cohort_k)
            cmask = jnp.take(select_in, idx)
            # executed = selected AND inside the cohort bound; when the strategy
            # selects more than K clients the overflow neither trains nor pays
            # wire (at K = C executed == select exactly)
            executed = (
                jnp.zeros(state.select.shape, bool).at[idx].set(cmask)
            )
            # participation defaults to None on hand-built states (the exported
            # RoundState mirrors the old _RoundState shape) — treat as zeros
            prev_part = (
                state.participation
                if state.participation is not None
                else jnp.zeros(state.select.shape, jnp.int32)
            )
            participation = prev_part + executed.astype(jnp.int32)
            cenv = env.take(idx)
            cctx = phases.RoundContext(
                t=t,
                global_params=g,
                local_params=tree_take(state.local_params, idx) if stateful else None,
                select=cmask,
                pms=jnp.take(state.pms, idx),
                share=jnp.take(share, idx, axis=0),
                residual=tree_take(state.residual, idx),
                participation=jnp.take(participation, idx),
                cohort_idx=idx,
                cohort_mask=cmask,
                rng_fit=r_fit,
                rng_codec=r_codec,
                rng_sel=r_sel,
            )

        # --- personalization: build each cohort lane's training model ---
        cctx = cctx._replace(train_model=pipeline.personalizer.train_model(cctx, cenv))
        # --- local training on K lanes (invalid lanes discarded below) ---
        cctx = pipeline.trainer.fit(cctx, cenv)
        if corrupt is not None:
            # corrupt the trained params BEFORE transmit so the uploaded
            # update_norm reflects the garbage and the finite guard below
            # is what rejects it — corrupt clients still pay wire
            from repro.fl.faults import apply_corruption

            kinds_k = jnp.where(cmask, jnp.take(corrupt, idx), 0)
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
        # --- wire codec: compress each cohort lane's shared delta (uplink) ---
        local_before = cctx.local_params if stateful else None
        res_before = cctx.residual
        cctx = pipeline.transmit.transmit(cctx, cenv)
        # --- finite-delta guard (always on): lanes whose transmitted norm
        # is non-finite (or past max_update_norm in fault mode) are masked
        # out of aggregation and their local/residual/norm state reverted —
        # one bad client can no longer poison the global model ---
        prev_norm = (
            state.update_norm
            if state.update_norm is not None
            else jnp.zeros(state.select.shape, jnp.float32)
        )
        with jax.named_scope("fl.transmit"):
            ok, n_rejected = finite_update_guard(cmask, cctx.update_norm, max_norm)
            cctx = cctx._replace(
                select=cmask & ok,
                residual=_tree_where(ok, cctx.residual, res_before),
                update_norm=jnp.where(ok, cctx.update_norm, jnp.take(prev_norm, idx)),
            )
            if stateful:
                cctx = cctx._replace(new_local=_tree_where(ok, cctx.new_local, local_before))
        # --- aggregation of shared pieces (Eq. 1, masked/partial), K lanes ---
        cctx = pipeline.aggregator.aggregate(cctx, cenv)

        # --- scatter: cohort results back into the (C, ...) server state ---
        with jax.named_scope("fl.scatter"):
            new_local = (
                tree_scatter(state.local_params, idx, cctx.new_local) if stateful else None
            )
            new_residual = tree_scatter(state.residual, idx, cctx.residual)
            update_norm = prev_norm.at[idx].set(cctx.update_norm)
        wire_prospective, wire_paid = pipeline.transmit.wire_costs(
            g, share, executed
        )

        # --- population phases: eval, selection, layer policy on (C,) ---
        pctx = cctx._replace(
            local_params=state.local_params,
            select=executed,
            pms=state.pms,
            share=share,
            residual=new_residual,
            participation=participation,
            cohort_idx=None,
            cohort_mask=None,
            new_local=new_local,
            wire_bytes=wire_prospective,
            wire_paid=wire_paid,
            update_norm=update_norm,
            prev_accuracy=state.accuracy,
            prev_loss=state.loss,
        )
        # --- evaluation: distributed accuracy on composed models; on the
        # eval_every-thinned path the personalizer's O(C) model build runs
        # inside the evaluator's cond, so skipped rounds pay nothing ---
        if getattr(pipeline.evaluator, "eval_every", 1) == 1:
            pctx = pctx._replace(eval_model=pipeline.personalizer.eval_model(pctx, env))
            pctx = pipeline.evaluator.evaluate(pctx, env)
        else:
            pctx = pipeline.evaluator.evaluate(
                pctx, env,
                model_fn=lambda ctx=pctx: pipeline.personalizer.eval_model(ctx, env),
            )
        # --- client selection for next round (Algorithm 1 l.12) ---
        pctx = pipeline.selector.select(pctx, env)
        # --- next round's PMS (layers to share) ---
        pctx = pctx._replace(next_pms=pipeline.layer_policy.next_pms(pctx, env, n_layers))

        # --- communication accounting for THIS round (uplink) ---
        tx = transmitted_parameters(executed, share, layer_param_sizes(g))

        new_state = RoundState(
            global_params=pctx.new_global,
            local_params=new_local,
            accuracy=pctx.accuracy,
            select=pctx.next_select,
            pms=pctx.next_pms,
            rng=rng,
            residual=new_residual,
            participation=participation,
            loss=pctx.loss,
            update_norm=update_norm,
        )
        out = {
            "acc": pctx.accuracy,
            "selected": executed,
            "tx_params": tx,
            "pms": state.pms,
            "wire_per_client": wire_paid,
            # phase cost signal surfaced for observability (repro.obs): the
            # last-known compressed-delta norm per client, already carried
            # in the round state — an extra out leaf, no extra compute
            "update_norm": update_norm,
            # finite-guard rejections this round (selected lanes whose
            # transmitted update failed validation)
            "rejected": n_rejected,
        }
        if pctx.counts is not None:
            out["counts"] = phases.fold_counts(pctx.counts, env.count_ops)
        return new_state, out

    def round_step(state: RoundState, t: jnp.ndarray):
        return _round_body(state, t, None, None)

    if not faulty:
        return round_step

    def fault_round_step(state: RoundState, t: jnp.ndarray, alive, corrupt):
        return _round_body(state, t, alive, corrupt)

    return fault_round_step


def build_chunk_step(round_step, length: int):
    """Fuse ``length`` consecutive rounds into one donated on-device step.

    The scanned body is a ``build_round_step`` round step; the carry is its
    ``RoundState``, and the per-round ``out`` dicts come back stacked to
    ``(length, ...)`` leaves, so the host dispatches once and fetches the
    whole chunk's history with a single ``device_get``. The returned
    callable maps ``(RoundState, ts (length,) int32) -> (RoundState, outs)``
    and is jitted with ``donate_argnums=0``: the carried ``(C, ...)`` server
    slabs (local params, EF residuals, per-client vectors) are updated in
    place instead of double-allocated — the caller's input state buffers are
    INVALID after the call (``x.is_deleted()``), exactly like the scheduler
    reassigning ``state`` every chunk.

    Bit-identity with per-round dispatch is load-bearing and relies on two
    choices here: the scan is fully unrolled (``unroll=length``) and each
    iteration ends in ``lax.optimization_barrier``, so every round's
    subgraph compiles with the same fusion boundaries as the standalone
    jitted round step (a rolled ``while`` loop lets XLA fuse the peeled
    first iteration differently, which showed up as 1-ulp accuracy
    drift on tie-sensitive lanes). Compile cost therefore grows linearly
    with ``length`` — chunk sizes in the tens are the sweet spot.

    One carve-out: a ``lax.cond`` in the round body (the
    ``eval_every > 1``-thinned evaluator) may still be fused differently
    inside the scan than in the plain jit, shifting eval outputs by 1 ulp
    of float32 on tie-sensitive lanes. Fused execution stays bit-identical
    across ALL chunk sizes (tails included); exact equality with per-round
    dispatch is guaranteed for cond-free bodies (``eval_every=1``, the
    golden-guarded default) and holds to float32 resolution otherwise —
    see tests/test_loop_fused.py.
    """
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length!r}")

    def body(state, t):
        state, out = round_step(state, t)
        # materialize each round's outputs at the iteration boundary — the
        # same numerics contract a per-round jit dispatch provides
        return jax.lax.optimization_barrier((state, out))

    @phases.scoped("fl.chunk")
    def chunk_step(state: RoundState, ts: jnp.ndarray):
        return jax.lax.scan(body, state, ts, unroll=length)

    return jax.jit(chunk_step, donate_argnums=0)
