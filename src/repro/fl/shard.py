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

When every device runs one client (K = C = D, ``cohort_size=0``: a silo
a device) lane i is client i, so the same step keeps every per-client slab
where its lane runs: the (C, ...) local models and EF residuals stay
partitioned over the devices from round to round (``lane_placement`` puts
the initial state there), the evaluation of each client's composed model
runs on its own device inside the ``shard_map``, and only the (C,)
accuracy, loss and norm vectors leave it for selection and the layer
policy. The global model is the only replicated model, so a device holds
its own client's model and not the population's. Nothing is gathered or
scattered: a round's cohort is every client, selected or not, in
client-id order. With several clients a device the population's slabs
stay replicated and the evaluation outside, as above: on a TPU v5e,
MotionSense's 24 clients 6 a device evaluated inside read accuracies
5,351 test samples off the float32 reference, against at most 46 with
the population's evaluation (cause not found).

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

__all__ = ["build_sharded_round_step", "lane_placement"]


def cohort_mesh(execution: ExecutionConfig):
    """The ``cohort`` mesh ``execution.cohort_devices`` asks for (0 and -1
    take every visible device)."""
    n = execution.cohort_devices
    return make_cohort_mesh(None if n in (0, -1) else n)


def lane_placement(execution: ExecutionConfig, n_clients: int):
    """Where the initial state of a sharded run with one client a device
    (K = C = D) lives, or None when the run is not one: ``place(g0,
    stateful, lossy)`` returns the global model replicated over the cohort
    mesh, and the (C, ...) local models (g0 on every lane) and EF residuals
    (zeros) built on the device that runs each lane, so no device ever
    holds the population's models."""
    if execution.cohort_devices == 0 or execution.resolved_cohort(n_clients) != n_clients:
        return None
    mesh = cohort_mesh(execution)
    if n_clients != mesh.shape["cohort"]:
        return None
    rep = jax.sharding.NamedSharding(mesh, P())
    lane = jax.sharding.NamedSharding(mesh, P("cohort"))

    def place(g0, stateful: bool, lossy: bool):
        g = jax.device_put(g0, rep)

        def lanes(fill):
            return jax.jit(
                lambda g: jax.tree.map(
                    lambda gl: jnp.broadcast_to(fill(gl), (n_clients,) + gl.shape), g
                ),
                out_shardings=lane,
            )(g)

        loc = lanes(lambda gl: gl) if stateful else None
        res = lanes(jnp.zeros_like) if lossy else None
        return g, loc, res

    place.replicated = rep  # where the (C,) vectors of the state live
    return place


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
    SPMD program over the mesh either way. The (C, ...) server slabs are
    replicated, and the cohort is gathered from them and scattered back;
    with one client a device (K = C = D) lane i is client i, and they stay
    partitioned over the mesh with the evaluation inside the ``shard_map``
    (module docstring): the placement is the only difference.
    """
    execution = execution or ExecutionConfig()
    if mesh is None:
        mesh = cohort_mesh(execution)
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
    # one client a device: its slabs stay on the device that runs its lane
    local = cohort_k == env.n_clients == n_shards
    stateful = pipeline.personalizer.stateful
    aggregator = _sharded_aggregator(pipeline.aggregator)
    lane = P("cohort")
    rep = P()
    replicated = jax.sharding.NamedSharding(mesh, rep)
    lanes_sh = jax.sharding.NamedSharding(mesh, lane)

    def pin(tree, sharding):
        return jax.tree.map(lambda l: jax.lax.with_sharding_constraint(l, sharding), tree)

    def evaluate(ctx, env):
        if getattr(pipeline.evaluator, "eval_every", 1) == 1:
            ctx = ctx._replace(eval_model=pipeline.personalizer.eval_model(ctx, env))
            return pipeline.evaluator.evaluate(ctx, env)
        return pipeline.evaluator.evaluate(
            ctx, env, model_fn=lambda ctx=ctx: pipeline.personalizer.eval_model(ctx, env),
        )

    def cohort_compute(g, t, r_fit, r_codec, idx, cmask, pms_c, share_c,
                       part_c, loc_c, res_c, slabs, seen):
        """The per-device compute block: ``lanes_local`` cohort lanes.

        Runs the exact phase sequence of the unsharded step on this
        device's shard of the lanes; the aggregator's psum is the only
        cross-device communication. Per-client rng keys come from the
        replicated rng lane gathered by the shard's ``idx``. With one client
        a device it also evaluates its client (``seen``: its previous
        accuracy and loss), on the new global model composed with its own.
        """
        if local:  # the (C,) vectors come in whole: take this device's rows
            first = jax.lax.axis_index("cohort") * lanes_local
            idx, cmask, pms_c, share_c, part_c, *seen = (
                jax.lax.dynamic_slice_in_dim(v, first, lanes_local)
                for v in (idx, cmask, pms_c, share_c, part_c, *seen)
            )
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
        new = (cctx.new_global, cctx.new_local, cctx.residual, cctx.update_norm)
        if not local:
            return new
        prev_acc, prev_loss = seen
        ectx = evaluate(cctx._replace(cohort_idx=None, cohort_mask=None,
                                      prev_accuracy=prev_acc, prev_loss=prev_loss), cenv)
        return new + (ectx.accuracy, ectx.loss, ectx.counts)

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

        # --- gather: selection mask -> fixed-size cohort (K,); with one
        # client a device every client is a lane, in client-id order, and
        # nothing moves ---
        with jax.named_scope("fl.gather"):
            if local:
                idx = jnp.arange(cohort_k, dtype=jnp.int32)
                cmask = executed = state.select
            else:
                idx = cohort_indices(state.select, cohort_k)
                cmask = jnp.take(state.select, idx)
                executed = jnp.zeros(state.select.shape, bool).at[idx].set(cmask)
            prev_part = (
                state.participation
                if state.participation is not None
                else jnp.zeros(state.select.shape, jnp.int32)
            )
            participation = prev_part + executed.astype(jnp.int32)
            if local:
                cenv, seen = env, (state.accuracy, state.loss if state.loss is not None
                                   else jnp.zeros(state.select.shape, jnp.float32))
                loc_c, res_c = state.local_params, state.residual
                lanes = (state.pms, share, participation)
            else:
                cenv, seen = env.take(idx), None
                loc_c = tree_take(state.local_params, idx) if stateful else None
                res_c = tree_take(state.residual, idx)
                lanes = (jnp.take(state.pms, idx), jnp.take(share, idx, axis=0),
                         jnp.take(participation, idx))
            slabs = (cenv.x_tr, cenv.y_tr, cenv.m_tr, cenv.x_te, cenv.y_te,
                     cenv.m_te, cenv.n_samples, cenv.delay)
            args = (g, t, r_fit, r_codec, idx, cmask, *lanes, loc_c, res_c, slabs, seen)

        # --- compute phases on K/D lanes per device (with one client a
        # device the (C,) vectors enter replicated: a lane-sharded input
        # would draw the sharding of their uses outside back over 'cohort') ---
        vec = rep if local else lane
        in_specs = (rep, rep, rep, rep, vec, vec, vec, vec, vec,
                    tree_lane_pspecs(loc_c, mesh),
                    tree_lane_pspecs(res_c, mesh),
                    tuple(lane_spec(s.shape, mesh) for s in slabs),
                    (rep, rep) if local else None)
        # outputs mirror the input trees' structures (new_local <- loc_c,
        # residual <- res_c), so their lane specs transfer directly
        out_specs = (rep,
                     tree_lane_pspecs(loc_c, mesh) if stateful else rep,
                     tree_lane_pspecs(res_c, mesh),
                     lane)
        if local:
            counts = {name: lane for name, _ in env.count_ops} if env.eval_fn else None
            out_specs += (lane, lane, counts)
        new_g, new_local_c, new_res_c, unorm_c, *evaluated = jax.shard_map(
            cohort_compute, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )(*args)

        # --- scatter: cohort results back into the (C, ...) server state
        # (the all-gather of the lane-sharded results lands here); with one
        # client a device only the (C,) vectors are gathered, for selection ---
        with jax.named_scope("fl.scatter"):
            prev_norm = (
                state.update_norm
                if state.update_norm is not None
                else jnp.zeros(state.select.shape, jnp.float32)
            )
            if local:
                new_local, new_residual = new_local_c, new_res_c
                update_norm, evaluated = pin((unorm_c, evaluated), replicated)
            else:
                new_local = (
                    tree_scatter(state.local_params, idx, new_local_c) if stateful else None
                )
                new_residual = tree_scatter(state.residual, idx, new_res_c)
                update_norm = prev_norm.at[idx].set(unorm_c)
        wire_prospective, wire_paid = pipeline.transmit.wire_costs(
            g, share, executed
        )

        # --- population phases: eval (K < C), selection, layer policy on (C,) ---
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
        if local:
            acc, loss, counts = evaluated
            pctx = pctx._replace(accuracy=acc, loss=loss, counts=counts)
        else:
            pctx = evaluate(pctx, env)
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
        if pctx.counts is not None:
            out["counts"] = phases.fold_counts(pctx.counts, env.count_ops)
        # pin the carried state where the next round expects it: replicated,
        # but for the (C, ...) slabs of a run with one client a device, which
        # stay over 'cohort' as lane_placement put them. Sharding propagation would
        # otherwise leave scatter outputs lane-sharded, and a donated input
        # can't alias an output with a different layout — without this,
        # build_chunk_step's donation silently stops freeing the (C, ...)
        # slabs (tests assert .is_deleted())
        slab = lanes_sh if local else replicated
        new_state = RoundState(**{
            f: pin(v, slab if f in ("local_params", "residual") else replicated)
            for f, v in new_state._asdict().items()
        })
        return new_state, pin(out, replicated)

    round_step.mesh = mesh
    round_step.lanes_per_device = lanes_local
    return round_step
