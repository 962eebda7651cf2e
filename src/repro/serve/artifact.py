"""Servable artifact: the frozen output of a federated run that the
serving engine loads.

ACSP-FL's Personalizer phase produces three things worth deploying: the
shared global model, each client's personalized local layers, and the
per-client share structure (FT pick / PMS depth / DLD depth). Training
carries them in ``RoundState``; this module freezes them into an on-disk
artifact (``repro.checkpoint`` npz + a serve manifest) that
``repro.serve.engine`` serves from.

The unifying representation is the **(C, L) share mask**: for every client
and layer, True means "use the shared global layer", False "use my
personalized local layer". All four personalization modes project onto it:

- ``none``  -> all-True rows (no local slab is stored at all);
- ``ft``    -> the Eq. 8 pick, frozen at export time by comparing each
  client's local-model vs global-model loss on its own shard — an all-False
  row (keep my whole model) or an all-True row (take the global);
- ``pms``/``dld`` -> the prefix mask ``layer_share_mask`` training used.

Because the mask is per-client, one artifact can hold clients in different
effective modes, and a single batched ``compose_model`` forward serves a
mode-heterogeneous batch bit-identically to per-client composition
(tested in tests/test_serve.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_pytree_auto, save_pytree
from repro.core.layersharing import layer_share_mask
from repro.fl.api import FLConfig, RoundState, build_round_step
from repro.models.mlp import mlp_accuracy, mlp_loss

SERVE_MANIFEST = "servable.meta.json"
SERVE_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class ServableArtifact:
    """Everything the serving engine needs, device-ready.

    ``local_params`` is None for artifacts without personalization state
    (mode 'none'); ``share_mask`` is always present and fully describes
    each client's composition. ``meta`` carries provenance (mode, rounds
    trained, config hash) for the serve manifest.
    """

    global_params: Any          # layered list, leaves (...)
    local_params: Any           # layered list, leaves (C, ...); or None
    share_mask: jnp.ndarray     # (C, L) bool — True: use the global layer
    meta: dict

    @property
    def n_clients(self) -> int:
        return int(self.share_mask.shape[0])

    @property
    def n_layers(self) -> int:
        return int(self.share_mask.shape[1])


def _ft_pick(global_params, local_params, data) -> jnp.ndarray:
    """(C,) Eq. 8 pick frozen at export: True -> client keeps its local
    model (its loss on the client's own test shard is <= the global's)."""
    x, y, m = (
        jnp.asarray(data.x_test),
        jnp.asarray(data.y_test),
        jnp.asarray(data.m_test),
    )
    loss_loc = jax.vmap(lambda p, xx, yy, mm: mlp_loss(p, xx, yy, mm))(
        local_params, x, y, m
    )
    loss_glob = jax.vmap(lambda xx, yy, mm: mlp_loss(global_params, xx, yy, mm))(
        x, y, m
    )
    return loss_loc <= loss_glob


def servable_from_state(
    state: RoundState, mode: str, data=None, extra_meta: dict | None = None
) -> ServableArtifact:
    """Project a trained ``RoundState`` onto the serve representation.

    ``mode`` is the run's personalization mode; ``data`` is required for
    ``ft`` (the pick is frozen against each client's test shard, exactly
    the comparison ``FTPersonalizer.eval_model`` makes every round).
    """
    n_layers = len(state.global_params)
    c = int(state.select.shape[0])
    if mode == "none" or state.local_params is None:
        share = jnp.ones((c, n_layers), bool)
        local = None
        mode = "none"
    elif mode == "ft":
        if data is None:
            raise ValueError("mode 'ft' needs the dataset to freeze the Eq. 8 pick")
        use_local = _ft_pick(state.global_params, state.local_params, data)
        share = jnp.broadcast_to(~use_local[:, None], (c, n_layers))
        local = state.local_params
    elif mode in ("pms", "dld"):
        share = layer_share_mask(n_layers, state.pms)
        local = state.local_params
    else:
        raise ValueError(f"unknown personalization mode {mode!r}")
    meta = {
        "schema_version": SERVE_SCHEMA_VERSION,
        "mode": mode,
        "n_clients": c,
        "n_layers": n_layers,
        "stateful": local is not None,
        "personalized_clients": int(jnp.sum(~share.all(axis=1))),
    }
    meta.update(extra_meta or {})
    return ServableArtifact(
        global_params=state.global_params,
        local_params=local,
        share_mask=share,
        meta=meta,
    )


def save_servable(artifact: ServableArtifact, directory: str) -> str:
    """Write the artifact: one ``servable.npz`` checkpoint (global params +
    local slabs + share mask) plus ``servable.meta.json``."""
    tree: dict[str, Any] = {
        "global": artifact.global_params,
        "share": artifact.share_mask,
    }
    if artifact.local_params is not None:
        tree["local"] = artifact.local_params
    path = save_pytree(tree, directory, "servable")
    with open(os.path.join(directory, SERVE_MANIFEST), "w") as f:
        json.dump(artifact.meta, f, indent=1, default=str)
        f.write("\n")
    return path


def load_servable(directory: str) -> ServableArtifact:
    """Load an artifact saved by ``save_servable`` (no template needed)."""
    with open(os.path.join(directory, SERVE_MANIFEST)) as f:
        meta = json.load(f)
    tree = load_pytree_auto(directory, "servable")
    return ServableArtifact(
        global_params=tree["global"],
        local_params=tree.get("local"),
        share_mask=jnp.asarray(tree["share"], bool),
        meta=meta,
    )


def fit_servable(
    data, cfg: FLConfig, progress: bool = False
) -> tuple[ServableArtifact, RoundState]:
    """Train ``cfg.rounds`` synchronous rounds and freeze the final state
    into a servable artifact.

    Drives the same jitted round step ``SyncScheduler`` runs (same rng
    chain, same initial state), but keeps the final ``RoundState`` — the
    scheduler's ``run`` only returns host-side history, and the serving
    path needs the trained slabs themselves.
    """
    from repro.fl.sched import _setup_run

    su = _setup_run(data, cfg, None, mlp_loss, mlp_accuracy, None, None, None)
    state = su.initial_state()
    step = jax.jit(build_round_step(su.env, su.pipeline, cfg.execution))
    for t in range(cfg.rounds):
        state, out = step(state, jnp.asarray(t))
        if progress and (t % 10 == 0 or t == cfg.rounds - 1):
            print(f"  round {t:3d}  acc={float(np.asarray(out['acc']).mean()):.4f}")
    artifact = servable_from_state(
        state,
        cfg.personalization.mode,
        data=data,
        extra_meta={"rounds": cfg.rounds, "strategy": cfg.strategy,
                    "dataset": getattr(data, "name", "?"), "seed": cfg.seed},
    )
    return artifact, state
