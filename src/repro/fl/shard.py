"""Sharded cohort execution: ``shard_map`` the (K, ...) round step over a
1-D ``cohort`` device mesh, composed with the round-fused executor.

The cohort runtime (repro.fl.api.build_round_step) already shapes a round
as gather -> per-lane compute on (K, ...) slabs -> aggregate -> scatter,
which makes the cohort axis a ready-made data-parallel axis: every compute
phase (Personalizer.train_model, LocalTrainer, TransmitPhase) is
lane-local, and only the Aggregator reduces across lanes.
``build_sharded_round_step`` exploits exactly that split:

- the compute block runs under ``shard_map`` over ``make_cohort_mesh``'s
  ``cohort`` axis, with every (K, ...) gathered slab — client data, local
  params, EF residuals, per-lane ids/masks — partitioned K/D per device
  (``launch.sharding.tree_lane_pspecs``), while the global model, the rng
  lanes, and the traced round index stay replicated;
- the Aggregator runs with ``axis_name="cohort"``: each device reduces its
  own lanes to weighted partial sums in lane order, then ONE ``lax.psum``
  per numerator/denominator combines the shards in fixed axis order
  (repro.core.aggregation), so the aggregated global model lands
  replicated on every device;
- everything population-shaped — selection bookkeeping, the (C, ...)
  scatter, wire accounting, evaluation, the selector and layer policy —
  stays outside the shard_map exactly as the unsharded step computes it,
  so host accounting is unchanged.

Contracts (tests/test_shard.py):

- the sharded step is still a ``(RoundState, t) -> (RoundState, out)``
  function, so ``api.build_chunk_step`` scans it unchanged with donation
  intact — one dispatch covers ``scan_chunk`` multi-device rounds;
- at D=1 it is bit-identical to the unsharded step (all golden
  trajectories hold); at D>1 the per-lane numbers are bit-identical and
  only the aggregation reduction tree changes (D partial sums + psum
  instead of one flat sum), which stays within 1 ulp of float32 per
  reduced element — golden parity at D in {2, 4, 8} is asserted at that
  tolerance in subprocess-spawned tests (forced host devices; see
  tests/_subproc.py and the conftest.py device-count constraint);
- per-device collective traffic is observable: lower the jitted step and
  run ``launch.collectives.collective_bytes`` over the optimized HLO — the
  psum all-reduces are the only collectives the compute block emits
  (benchmarks/shard_bench.py accounts them per round).

Per-client rng streams need no special handling: keys are split over the
*population* and gathered by the lane's client id (``phases.client_keys``),
so a device holding lanes [d*K/D, (d+1)*K/D) derives exactly the keys those
clients would consume anywhere else — lane placement never changes a
client's randomness.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ExecutionConfig
from repro.core.aggregation import transmitted_parameters
from repro.core.layersharing import layer_param_sizes, layer_share_mask
from repro.fl import phases
from repro.fl.api import RoundPipeline, RoundState
from repro.fl.cohort import cohort_indices, tree_scatter, tree_take
from repro.launch.mesh import make_cohort_mesh
from repro.launch.sharding import lane_spec, tree_lane_pspecs

__all__ = ["build_sharded_round_step"]


def _sharded_aggregator(aggregator: phases.Aggregator) -> phases.Aggregator:
    """The same aggregator phase, reducing over the ``cohort`` mesh axis."""
    if getattr(aggregator, "axis_name", "missing") == "cohort":
        return aggregator
    try:
        return dataclasses.replace(aggregator, axis_name="cohort")
    except (TypeError, ValueError) as e:
        raise TypeError(
            f"sharded execution needs an Aggregator with an `axis_name` "
            f"field (shard-local partial sums + lax.psum); "
            f"{type(aggregator).__name__} has none"
        ) from e


def build_sharded_round_step(
    env: phases.RoundEnv,
    pipeline: RoundPipeline,
    execution: ExecutionConfig | None = None,
    mesh=None,
):
    """Compose a RoundPipeline into a cohort-sharded round step.

    Maps ``(RoundState, t) -> (RoundState, out)`` exactly like
    ``api.build_round_step`` — same phase order, same rng-lane splits, same
    ``out`` dict — but the compute phases run under ``shard_map`` with the
    K cohort lanes partitioned K/D over ``mesh``'s ``cohort`` axis.

    ``mesh`` defaults to ``make_cohort_mesh(execution.cohort_devices)``
    (``cohort_devices=0`` takes every visible device). K must divide the
    device count — raise early rather than silently padding lanes. The
    returned function exposes the mesh as ``round_step.mesh`` (the
    scheduler records its shape in the run manifest) and can be jitted
    directly or fused through ``api.build_chunk_step``; XLA compiles one
    SPMD program over the mesh either way, with the (C, ...) server slabs
    replicated.
    """
    execution = execution or ExecutionConfig()
    if mesh is None:
        n = execution.cohort_devices
        mesh = make_cohort_mesh(None if n in (0, -1) else n)
    if "cohort" not in mesh.shape:
        raise ValueError(f"mesh has no 'cohort' axis: {mesh!r}")
    n_shards = mesh.shape["cohort"]
    cohort_k = execution.resolved_cohort(env.n_clients)
    if cohort_k % n_shards != 0:
        raise ValueError(
            f"cohort lanes must divide the mesh: K={cohort_k} over "
            f"{n_shards} 'cohort' devices leaves a remainder — pick "
            f"cohort_size (or population) a multiple of the device count"
        )
    lanes_local = cohort_k // n_shards
    stateful = pipeline.personalizer.stateful
    aggregator = _sharded_aggregator(pipeline.aggregator)
    lane = P("cohort")
    rep = P()

    def cohort_compute(g, t, r_fit, r_codec, idx, cmask, pms_c, share_c,
                       part_c, loc_c, res_c, slabs):
        """The per-device compute block: ``lanes_local`` cohort lanes.

        Runs the exact phase sequence of the unsharded step on this
        device's shard of the gathered lanes; the aggregator's psum is the
        only cross-device communication. Per-client rng keys come from the
        replicated rng lane gathered by the shard's ``idx``.
        """
        xtr, ytr, mtr, xte, yte, mte, ns, dl = slabs
        cenv = dataclasses.replace(
            env, x_tr=xtr, y_tr=ytr, m_tr=mtr, x_te=xte, y_te=yte, m_te=mte,
            n_samples=ns, delay=dl, n_clients=lanes_local, population=env.pop,
        )
        cctx = phases.RoundContext(
            t=t,
            global_params=g,
            local_params=loc_c,
            select=cmask,
            pms=pms_c,
            share=share_c,
            residual=res_c,
            participation=part_c,
            cohort_idx=idx,
            cohort_mask=cmask,
            rng_fit=r_fit,
            rng_codec=r_codec,
        )
        cctx = cctx._replace(train_model=pipeline.personalizer.train_model(cctx, cenv))
        cctx = pipeline.trainer.fit(cctx, cenv)
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
        cctx = pipeline.transmit.transmit(cctx, cenv)
        # shard-local weighted partial sums + one psum over 'cohort' — the
        # new global model is identical (replicated) on every device
        cctx = aggregator.aggregate(cctx, cenv)
        return cctx.new_global, cctx.new_local, cctx.residual, cctx.update_norm

    @phases.scoped("fl.round")
    def round_step(state: RoundState, t: jnp.ndarray):
        g = state.global_params
        n_layers = len(g)
        share = layer_share_mask(n_layers, state.pms)  # (C, L)

        if pipeline.transmit.lossy:
            rng, r_fit, r_sel, r_codec = jax.random.split(state.rng, 4)
        else:
            rng, r_fit, r_sel = jax.random.split(state.rng, 3)
            r_codec = None

        # --- gather: selection mask -> fixed-size cohort (K,) ---
        with jax.named_scope("fl.gather"):
            idx = cohort_indices(state.select, cohort_k)
            cmask = jnp.take(state.select, idx)
            executed = jnp.zeros(state.select.shape, bool).at[idx].set(cmask)
            prev_part = (
                state.participation
                if state.participation is not None
                else jnp.zeros(state.select.shape, jnp.int32)
            )
            participation = prev_part + executed.astype(jnp.int32)
            cenv = env.take(idx)
            loc_c = tree_take(state.local_params, idx) if stateful else None
            res_c = tree_take(state.residual, idx)
            slabs = (cenv.x_tr, cenv.y_tr, cenv.m_tr, cenv.x_te, cenv.y_te,
                     cenv.m_te, cenv.n_samples, cenv.delay)
            args = (g, t, r_fit, r_codec, idx, cmask, jnp.take(state.pms, idx),
                    jnp.take(share, idx, axis=0), jnp.take(participation, idx),
                    loc_c, res_c, slabs)

        # --- compute phases on K/D lanes per device ---
        in_specs = (rep, rep, rep, rep, lane, lane, lane, lane, lane,
                    tree_lane_pspecs(loc_c, mesh),
                    tree_lane_pspecs(res_c, mesh),
                    tuple(lane_spec(s.shape, mesh) for s in slabs))
        # outputs mirror the input trees' structures (new_local <- loc_c,
        # residual <- res_c), so their lane specs transfer directly
        out_specs = (rep,
                     tree_lane_pspecs(loc_c, mesh) if stateful else rep,
                     tree_lane_pspecs(res_c, mesh),
                     lane)
        new_g, new_local_c, new_res_c, unorm_c = jax.shard_map(
            cohort_compute, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )(*args)

        # --- scatter: cohort results back into the (C, ...) server state
        # (the all-gather of the lane-sharded results lands here) ---
        with jax.named_scope("fl.scatter"):
            new_local = (
                tree_scatter(state.local_params, idx, new_local_c) if stateful else None
            )
            new_residual = tree_scatter(state.residual, idx, new_res_c)
            prev_norm = (
                state.update_norm
                if state.update_norm is not None
                else jnp.zeros(state.select.shape, jnp.float32)
            )
            update_norm = prev_norm.at[idx].set(unorm_c)
        wire_prospective, wire_paid = pipeline.transmit.wire_costs(
            g, share, executed
        )

        # --- population phases: eval, selection, layer policy on (C,) ---
        pctx = phases.RoundContext(
            t=t,
            global_params=g,
            local_params=state.local_params,
            select=executed,
            pms=state.pms,
            share=share,
            residual=new_residual,
            participation=participation,
            rng_fit=r_fit,
            rng_codec=r_codec,
            rng_sel=r_sel,
            prev_accuracy=state.accuracy,
            prev_loss=state.loss,
            new_local=new_local,
            new_global=new_g,
            wire_bytes=wire_prospective,
            wire_paid=wire_paid,
            update_norm=update_norm,
        )
        if getattr(pipeline.evaluator, "eval_every", 1) == 1:
            pctx = pctx._replace(eval_model=pipeline.personalizer.eval_model(pctx, env))
            pctx = pipeline.evaluator.evaluate(pctx, env)
        else:
            pctx = pipeline.evaluator.evaluate(
                pctx, env,
                model_fn=lambda ctx=pctx: pipeline.personalizer.eval_model(ctx, env),
            )
        pctx = pipeline.selector.select(pctx, env)
        pctx = pctx._replace(next_pms=pipeline.layer_policy.next_pms(pctx, env, n_layers))

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
            "update_norm": update_norm,
        }
        # pin the carried state replicated: sharding propagation would
        # otherwise leave scatter outputs lane-sharded over 'cohort', and a
        # donated input (replicated) can't alias an output with a different
        # layout — without this, build_chunk_step's donation silently stops
        # freeing the (C, ...) slabs (tests assert .is_deleted())
        replicated = jax.sharding.NamedSharding(mesh, rep)
        new_state, out = jax.tree.map(
            lambda l: jax.lax.with_sharding_constraint(l, replicated),
            (new_state, out),
        )
        return new_state, out

    round_step.mesh = mesh
    round_step.lanes_per_device = lanes_local
    return round_step
