"""On-chip smoke check: drive the main path once on a TPU and check it.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded cohort only

One chip runs, in one process on ``jax.devices()[0]``:

- kernel: the int8/int4 wire codec (``repro.kernels.quantize``) at the
  har-mlp leaf sizes, vmapped over 30 client lanes. The compiled program
  must hold the Pallas kernel (``tpu_custom_call``), and its codes and
  decoded values must match the jnp reference on the same noise;
- ef: the int8 codec's error feedback inside its two kernels
  (``QuantizeCodec.ef_lanes``) for 30 lanes of each har-mlp leaf, against
  the server model shared by the lanes and against per-lane snapshots,
  must give what ``ef_step`` on each lane gives on the same chip: the same
  aggregated values and residuals, and the update norms to 1e-6;
- sync: ``run_federated`` on the uci-har stand-in (30 clients, har-mlp at
  its published widths 561-256-256-256-6) for a FedAvg float32 baseline
  and the paper recipe with the int8 codec in fused 5-round chunks. The
  same two runs pinned to the host CPU are the reference;
- async + host plane: a few aggregation events of the async scheduler, then
  a few rounds of the host-resident population plane at C=2000, K=64;
- serve: the int8 run frozen into a servable artifact, saved, loaded and
  answering 64 requests through the continuous batcher at batch 8.

``--chips 4`` runs only the cohort-sharded round step (C=2000, K=64, 16
lanes per chip) and the same configuration unsharded on one chip.

Every phase prints one line with its compile seconds (XLA backend
compiles, persistent-cache reads included), its other wall seconds, its
persistent compilation cache hits and misses, and what it checked. A failed check raises, so the script exits non-zero and
never prints the last line: ``{"ok": true, "device": {...}}``. There is no
CPU fallback: without a TPU the script stops before any work.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# the CPU reference runs need the host backend beside the TPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# har-mlp leaf sizes: 561x256, 256x256 (twice), 256x6 weights; 256 and 6 biases
LEAF_SIZES = (143_616, 65_536, 1_536, 256, 6)
LEAF_SHAPES = ((561, 256), (256, 256), (256, 6), (256,), (6,))
LANES = 30                # uci-har clients
ROUNDS = 5
CHANCE = 1.0 / 6.0        # six activity classes
# final mean accuracy must beat chance by this much; the 2000-client runs
# see 64 clients a round and reach ~0.45 after 5 rounds on the host CPU
ACC_MARGIN = 0.15
# TPU f32 matmuls take bf16 passes at the default precision, the CPU's are
# full f32: the trajectories differ, so they agree to a tolerance only
CPU_ACC_TOL = 0.05        # per-round mean accuracy, TPU vs host CPU
SHARD_ACC_TOL = 0.01      # per-round mean accuracy, 4-chip vs 1-chip
POP_CLIENTS = 2000
POP_COHORT = 64
# per-client sample counts of the 2000-client population are cut to a tenth
# (22..32 instead of 224..327): generating the full (C, N, 561) slab with
# its per-client 561x561 shift takes minutes of host time
POP_SCALE = 0.1
KERNEL_MARK = "tpu_custom_call"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
# process-wide: XLA compile seconds (persistent-cache reads included) and
# persistent-cache hits and misses, fed by jax.monitoring listeners
_counts = collections.Counter()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _counts["compile_s"] += duration


def _on_event(event: str, **_) -> None:
    if event in _CACHE_EVENTS:
        _counts[_CACHE_EVENTS[event]] += 1


@contextlib.contextmanager
def phase(name: str, checks: list[str]):
    """Time a phase; print its line once its body (and checks) passed."""
    c0, t0 = dict(_counts), time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    d = {k: _counts[k] - c0.get(k, 0) for k in
         ("compile_s", "cache_hits", "cache_misses")}
    print(f"phase {name}: compile_s={d['compile_s']:.3f} "
          f"run_s={wall - d['compile_s']:.3f} cache_hits={d['cache_hits']} "
          f"cache_misses={d['cache_misses']} | " + "; ".join(checks), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def check_history(h, label: str) -> float:
    """Every float lane finite, accuracy rising and above chance; returns
    the final accuracy."""
    for name, lane in h._asdict().items():
        if lane is None:
            continue
        a = np.asarray(lane)
        if a.dtype.kind == "f":
            check(bool(np.isfinite(a).all()), f"{label}: non-finite {name}")
    first, final = float(h.accuracy_mean[0]), float(h.accuracy_mean[-1])
    check(final > first, f"{label}: accuracy fell from {first:.4f} to {final:.4f}")
    check(final > CHANCE + ACC_MARGIN,
          f"{label}: final accuracy {final:.4f} not above chance "
          f"{CHANCE:.4f} + {ACC_MARGIN}")
    return final


def fmt(xs) -> str:
    return "[" + ", ".join(f"{float(x):.4f}" for x in xs) + "]"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernel(sizes=LEAF_SIZES, lanes=LANES) -> None:
    from repro.kernels.quantize import dequantize, quant_blocks, quantize
    from repro.kernels.quantize.ref import dequantize_ref, quantize_ref

    checks = []
    with phase("kernel", checks):
        worst_code = worst_decode = 0.0
        for bits in (8, 4):
            for n in sizes:
                kx, ku = jax.random.split(jax.random.PRNGKey(10 * n + bits))
                x = jax.random.normal(kx, (lanes, n)) * 0.05
                u = jax.random.uniform(ku, (lanes, n))

                def roundtrip(x, u):
                    q, s = jax.vmap(lambda a, b: quantize(a, b, bits=bits))(x, u)
                    return q, s, jax.vmap(lambda a, b: dequantize(a, b))(q, s)

                bp, nb = quant_blocks(n)

                def reference(x, u):
                    pad = ((0, 0), (0, nb * bp - n))
                    xp, up = jnp.pad(x, pad), jnp.pad(u, pad)
                    q, s = jax.vmap(
                        lambda a, b: quantize_ref(a, b, bits=bits, block=bp))(xp, up)
                    out = jax.vmap(lambda a, b: dequantize_ref(a, b, block=bp))(q, s)
                    return q[:, :n], s, out[:, :n]

                compiled = jax.jit(roundtrip).lower(x, u).compile()
                check(KERNEL_MARK in compiled.as_text(),
                      f"int{bits} n={n}: no Pallas kernel in the compiled program")
                q, s, out = jax.device_get(compiled(x, u))
                qr, sr, outr = jax.device_get(jax.jit(reference)(x, u))
                check(s.shape == (lanes, nb), f"int{bits} n={n}: scales {s.shape}")
                np.testing.assert_allclose(s, sr, rtol=1e-6)
                dcode = np.abs(q.astype(np.int32) - qr.astype(np.int32)).max()
                check(dcode <= 1, f"int{bits} n={n}: codes differ by {dcode}")
                step = np.repeat(sr, bp, axis=1)[:, :n]
                ddec = (np.abs(out - outr) / step).max()
                check(ddec <= 1.0 + 1e-5,
                      f"int{bits} n={n}: decoded values differ by {ddec} steps")
                worst_code = max(worst_code, float(dcode))
                worst_decode = max(worst_decode, float(ddec))
        checks += [f"{KERNEL_MARK} in all {2 * len(sizes)} programs",
                   f"max |code - ref| = {worst_code:.0f}",
                   f"max |decoded - ref| = {worst_decode:.4f} scale steps"]


def phase_ef(shapes=LEAF_SHAPES, lanes=LANES) -> None:
    from repro.comm.codec import QuantizeCodec, ef_step

    codec = QuantizeCodec(bits=8)
    check(codec.fused_ef, "the int8 codec does not take the fused pass on the chip")
    checks = []
    with phase("ef", checks):
        worst = {"values": 0.0, "residual": 0.0, "norm": 0.0}
        for per_lane in (False, True):
            for shape in shapes:
                ks = jax.random.split(jax.random.PRNGKey(len(shape) + shape[0]), 4)
                t = jax.random.normal(ks[0], (lanes,) + shape) * 0.05
                r = jax.random.normal(ks[1], t.shape) * 1e-3
                g = jax.random.normal(ks[2], t.shape if per_lane else shape) * 0.05
                keys = jax.random.split(ks[3], lanes)
                sent = jnp.arange(lanes) % 3 != 2

                def fused(t, g, r):
                    return codec.ef_lanes([t], [g], [r], keys, sent)

                def composed(t, g, r):
                    def lane(t, r, key, g):
                        dec, new_r = ef_step(codec, [t - g], [r], key)
                        return g + dec[0], new_r[0]
                    gs = g if per_lane else jnp.broadcast_to(g, t.shape)
                    recon, new_r = jax.vmap(lane)(t, r, keys, gs)
                    keep = sent.reshape((-1,) + (1,) * len(shape))
                    d = recon - gs
                    return recon, jnp.where(keep, new_r, r), jnp.sum(
                        d * d, axis=tuple(range(1, d.ndim)))

                compiled = jax.jit(fused).lower(t, g, r).compile()
                check(compiled.as_text().count(KERNEL_MARK) >= 2,
                      f"ef {shape}: no Pallas kernels in the compiled program")
                (recon,), (new_r,), sq = jax.device_get(compiled(t, g, r))
                recon_c, new_r_c, sq_c = jax.device_get(jax.jit(composed)(t, g, r))
                worst["values"] = max(worst["values"], float(np.abs(recon - recon_c).max()))
                worst["residual"] = max(worst["residual"], float(np.abs(new_r - new_r_c).max()))
                worst["norm"] = max(worst["norm"], float((np.abs(sq - sq_c) / sq_c).max()))
        check(worst["values"] == 0.0 and worst["residual"] == 0.0,
              f"ef: fused and composed differ: {worst}")
        check(worst["norm"] <= 1e-6, f"ef: squared norms differ by {worst['norm']}")
        checks += [f"{2 * len(shapes)} leaf programs", "values and residuals equal",
                   f"max rel. squared-norm gap {worst['norm']:.2e}"]


def _sync_configs(rounds=ROUNDS):
    from repro.configs.har_mlp import fl_defaults
    from repro.fl import ExecutionConfig, FLConfig

    fedavg = FLConfig(strategy="fedavg", personalization="none", fraction=1.0,
                      rounds=rounds, epochs=2)
    cfg = fl_defaults()
    int8 = dataclasses.replace(
        cfg,
        codec=dataclasses.replace(cfg.codec, spec="int8"),
        train=dataclasses.replace(cfg.train, rounds=rounds),
        execution=ExecutionConfig(scan_chunk=rounds),
    )
    return fedavg, int8


def phase_sync(make_ds, rounds=ROUNDS):
    from repro.fl import run_federated

    fedavg_cfg, int8_cfg = _sync_configs(rounds)
    ds = make_ds()
    checks = []
    with phase("sync", checks):
        h_f = run_federated(ds, fedavg_cfg)
        h_q = run_federated(ds, int8_cfg)
        acc_f = check_history(h_f, "fedavg")
        acc_q = check_history(h_q, "acsp-fl int8")
        up_f, up_q = float(h_f.tx_bytes_cum[-1]), float(h_q.tx_bytes_cum[-1])
        check(up_q < up_f, f"ACSP-FL uplink {up_q} not below FedAvg {up_f}")
        checks += [f"fedavg acc {fmt(h_f.accuracy_mean)}",
                   f"acsp-fl int8 acc {fmt(h_q.accuracy_mean)}",
                   f"uplink MB fedavg {up_f / 1e6:.3f} > acsp-fl int8 {up_q / 1e6:.3f}",
                   f"final acc {acc_f:.4f} / {acc_q:.4f} > chance + {ACC_MARGIN}"]

    checks = []
    with phase("sync-cpu-reference", checks), jax.default_device(jax.devices("cpu")[0]):
        r_f = run_federated(make_ds(), fedavg_cfg)
        r_q = run_federated(make_ds(), int8_cfg)
        for label, h, r in (("fedavg", h_f, r_f), ("acsp-fl int8", h_q, r_q)):
            check_history(r, f"{label} cpu")
            gap = float(np.abs(h.accuracy_mean - r.accuracy_mean).max())
            check(gap <= CPU_ACC_TOL,
                  f"{label}: TPU vs CPU accuracy differ by {gap:.4f} > {CPU_ACC_TOL}")
            checks.append(f"{label} tpu {fmt(h.accuracy_mean)} cpu "
                          f"{fmt(r.accuracy_mean)} max gap {gap:.4f} <= {CPU_ACC_TOL}")
    return ds, int8_cfg


def phase_async_host(ds, make_pop, rounds=ROUNDS):
    from repro.configs.har_mlp import fl_defaults
    from repro.fl import ExecutionConfig, FLConfig, SchedulerConfig, run_federated

    checks = []
    with phase("async", checks):
        cfg = fl_defaults()
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, rounds=rounds),
            scheduler=SchedulerConfig(mode="async"),
        )
        h = run_federated(ds, cfg)
        acc = check_history(h, "async")
        checks.append(f"{len(h.accuracy_mean)} aggregation events, "
                      f"acc {fmt(h.accuracy_mean)}, final {acc:.4f}")

    pop = make_pop()
    checks = []
    with phase("host-plane", checks):
        cfg = FLConfig(strategy="fedavg", personalization="none",
                       fraction=POP_COHORT / POP_CLIENTS, rounds=rounds, epochs=2,
                       execution=ExecutionConfig(cohort_size=POP_COHORT,
                                                 host_population=1))
        h = run_federated(pop, cfg)
        acc = check_history(h, "host plane")
        lanes = int(np.asarray(h.in_flight).max())
        check(lanes == POP_COHORT, f"host plane ran {lanes} lanes, not {POP_COHORT}")
        checks.append(f"C={pop.n_clients} K={lanes} acc {fmt(h.accuracy_mean)}, "
                      f"final {acc:.4f}")


def phase_serve(ds, cfg, n_requests=64, batch=8) -> None:
    from repro.serve import (
        ClassifyProgram,
        ContinuousBatcher,
        PersonalizedEngine,
        ServeRequest,
        fit_servable,
        load_servable,
        save_servable,
    )

    checks = []
    with phase("serve", checks), tempfile.TemporaryDirectory(
        dir=ROOT, prefix=".smoke-servable-"
    ) as out_dir:
        artifact, _ = fit_servable(ds, cfg)
        save_servable(artifact, out_dir)
        engine = PersonalizedEngine(load_servable(out_dir))
        rng = np.random.default_rng(0)
        cids = rng.integers(0, ds.n_clients, size=n_requests)
        x_test = np.asarray(ds.x_test)
        reqs = [ServeRequest(rid=i, client_id=int(c),
                             inputs=x_test[int(c), i % x_test.shape[1]])
                for i, c in enumerate(cids)]
        results = ContinuousBatcher(ClassifyProgram(engine, batch), batch).run(reqs)
        check(len(results) == n_requests, f"{len(results)} of {n_requests} answered")
        bitwise = 0
        for res in results:
            out = np.asarray(res.output)
            check(bool(np.isfinite(out).all()), f"request {res.rid}: non-finite")
            ref = np.asarray(engine.forward_unbatched(
                res.client_id, jnp.asarray(reqs[res.rid].inputs)))
            check(int(out.argmax()) == int(ref.argmax()),
                  f"request {res.rid}: batched argmax != per-client forward")
            bitwise += int(np.array_equal(out, ref))
        checks += [f"{n_requests} requests at batch {batch}: argmax == "
                   f"forward_unbatched on all",
                   f"bitwise equal on {bitwise}/{n_requests}"]


def phase_sharded(make_pop, devices=4, rounds=ROUNDS) -> None:
    from repro.configs.har_mlp import fl_defaults
    from repro.fl import ExecutionConfig, api, run_federated
    from repro.fl.sched import _setup_run
    from repro.launch.collectives import collective_bytes
    from repro.models.mlp import mlp_accuracy, mlp_loss

    check(len(jax.devices()) >= devices,
          f"{devices} chips asked, {len(jax.devices())} visible")
    pop = make_pop()
    base = fl_defaults()
    base = dataclasses.replace(base, train=dataclasses.replace(base.train, rounds=rounds))

    def cfg_for(d):
        return dataclasses.replace(
            base, execution=ExecutionConfig(cohort_size=POP_COHORT, cohort_devices=d))

    checks = []
    with phase("sharded-4chip", checks):
        cfg = cfg_for(devices)
        su = _setup_run(pop, cfg, None, mlp_loss, mlp_accuracy, None, None, None)
        step = api.build_round_step(su.env, su.pipeline, cfg.execution)
        ids = {d.id for d in step.mesh.devices.flat}
        check(len(ids) == devices, f"mesh spans devices {sorted(ids)}")
        state = su.initial_state()
        hlo = jax.jit(step).lower(state, jnp.int32(0)).compile().as_text()
        stats = collective_bytes(hlo)
        # the aggregator psums one weighted sum per parameter: at least the
        # model's f32 bytes
        model_bytes = 4 * sum(leaf.size for leaf in jax.tree.leaves(su.g0))
        check(stats.get("all-reduce", 0) >= model_bytes,
              f"all-reduce below the model's {model_bytes} B in the HLO: {stats}")
        h_s = run_federated(pop, cfg)
        acc_s = check_history(h_s, "sharded")
        checks += [f"mesh devices {sorted(ids)}",
                   f"{step.lanes_per_device} lanes per chip",
                   f"all-reduce {stats['all-reduce']} B/round in the HLO",
                   f"final acc {acc_s:.4f}"]

    checks = []
    with phase("unsharded-1chip", checks):
        h_u = run_federated(pop, cfg_for(0))
        check_history(h_u, "unsharded")
        gap = float(np.abs(h_s.accuracy_mean - h_u.accuracy_mean).max())
        check(gap <= SHARD_ACC_TOL,
              f"sharded vs unsharded accuracy differ by {gap:.6f} > {SHARD_ACC_TOL}")
        same_sel = bool(np.array_equal(h_s.selected, h_u.selected))
        checks += [f"sharded {fmt(h_s.accuracy_mean)}",
                   f"unsharded {fmt(h_u.accuracy_mean)}",
                   f"max gap {gap:.6f} <= {SHARD_ACC_TOL}",
                   f"same selections {same_sel}"]


# ---------------------------------------------------------------------------


def make_uci_har():
    from repro.data import make_har_dataset

    return make_har_dataset("uci-har", seed=0)


def make_population():
    from repro.data import make_har_dataset

    return make_har_dataset("uci-har", seed=0, n_clients=POP_CLIENTS, scale=POP_SCALE)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-cohort phase and its "
                         "one-chip comparison")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {dev.platform}); "
                 "this check runs on the chip only")
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
          f"default matmul precision {jax.config.jax_default_matmul_precision}, "
          f"compile cache {cache}", flush=True)

    if args.chips == 4:
        phase_sharded(make_population, devices=4)
    else:
        phase_kernel()
        phase_ef()
        ds, int8_cfg = phase_sync(make_uci_har)
        phase_async_host(ds, make_population)
        phase_serve(ds, int8_cfg)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
