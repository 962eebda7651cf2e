"""Quickstart: ACSP-FL on the UCI-HAR stand-in, 30 clients, 30 rounds.

    PYTHONPATH=src python examples/quickstart.py [--codec int8] [--strategy oort-wire]
                                                 [--mode async --buffer-k 8]
                                                 [--n-clients 2000 --cohort-size 50]

Reproduces the paper's headline behaviour in ~a minute on CPU: adaptive
selection shrinks the cohort, DLD shrinks the shared piece, accuracy stays
on par with full FedAvg at a fraction of the bytes. ``--codec`` stacks a
wire codec (repro.comm) on the adaptive run: int8 / int4 quantization,
top-k sparsification, or a chain like topk+int8. ``--strategy`` swaps the
selector — including the cost-aware ``grad-importance`` / ``oort-wire``
and the participation-fair ``oort-fair``. ``--mode async`` swaps the
barrier loop for the event-driven FedBuff-style scheduler
(repro.fl.sched): the server merges as soon as ``--buffer-k`` updates
land, weighting stale updates down, so a straggler no longer pins the
simulated round clock. ``--n-clients`` scales the population up and
``--cohort-size`` bounds how many client lanes a round physically
gathers/trains (cohort execution: compute is O(K), not O(C)).
"""

import argparse
import dataclasses
import os
import sys

# --devices N (dev only) forces N host devices for the cohort-sharded run.
# XLA locks the device count at first backend init, so the flag has to land
# in the environment before anything below touches jax — peek at argv here,
# let argparse own the real parsing/help later.
if "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1 :][:1]
    if _n and _n[0].isdigit() and int(_n[0]) > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={int(_n[0])}"
        ).strip()

import numpy as np

from repro.configs.har_mlp import fl_defaults
from repro.core.metrics import overhead_reduction
from repro.data import make_har_dataset
from repro.fl import FLConfig, SchedulerConfig, run_federated
from repro.launch.compile_cache import enable_compile_cache

CUSTOM_ROUND_HELP = """
cohort execution (O(K) rounds):
  The round step executes as gather -> compute -> scatter: selection
  resolves to at most --cohort-size client ids, only those clients' data
  shards / local params / EF residuals are gathered into (K, ...) lanes
  with jnp.take, the compute phases run on K lanes, and results scatter
  back into the (C, ...) server state with .at[idx].set. Per-round compute
  and trained-state memory are O(K) regardless of the population, so

    PYTHONPATH=src python examples/quickstart.py --n-clients 2000 --cohort-size 50

  trains at most 50 lanes per round against a 2000-client population (>=5x
  step time vs dense; see benchmarks/scale_bench.py + BENCH_scale.json).
  --cohort-size 0 (default) executes the full population, bit-identical to
  the dense engine. ExecutionConfig(eval_every=n) additionally thins the
  O(C) distributed eval to every n-th round; SchedulerConfig
  (max_concurrency=M) caps async in-flight dispatch slots at M.

round-fused execution (--scan-chunk):
  The sync server loop can fuse S rounds into one on-device lax.scan
  (ExecutionConfig.scan_chunk): the host dispatches once, blocks once, and
  accounts once per S-round chunk, with the carried server state donated
  and updated in place. Bit-identical to per-round execution at ANY chunk
  size (tail chunks included) — only the host-sync cadence changes:
  progress prints at chunk boundaries, and wall-clock stops being
  dominated by Python dispatch (>=3x rounds/sec on the paper's small MLP
  at C=100; see benchmarks/loop_bench.py + BENCH_loop.json). Compile time
  grows with S (the chunk body is unrolled), so chunk sizes in the tens
  are the sweet spot:

    PYTHONPATH=src python examples/quickstart.py --scan-chunk 10

sharding the cohort (--devices):
  The gathered (K, ...) cohort lanes are a ready-made data-parallel axis:
  with --devices D the adaptive run's compute phases run under shard_map
  over a 1-D 'cohort' device mesh (repro.fl.shard), K/D lanes per device,
  with the FedAvg reduction as shard-local partial sums + one lax.psum.
  Global params and the (C, ...) server state stay replicated, the fused
  scan/donation path is unchanged, and the trajectory matches the
  unsharded run (bit-identical at D=1, <=1-ulp documented at D>1):

    PYTHONPATH=src python examples/quickstart.py --n-clients 2000 \\
        --cohort-size 48 --devices 2

  K must divide D. On CPU, --devices forces D *host* devices that
  timeshare your cores (dev-only; real speedups need real devices — see
  benchmarks/shard_bench.py + BENCH_shard.json for the D-scaling sweep
  and per-device psum traffic).

scaling the population (--host-population / --edge-groups):
  Cohort execution makes per-round *compute* O(K); the population tier
  (repro.fl.population) makes per-round *device memory* O(K) too. With
  --host-population 1 every (C, ...) per-client slab — local params, EF
  residuals, selection/accuracy/participation lanes — lives host-side in
  a numpy PopulationStore (optionally memory-mapped), and each round
  stages only the gathered (K, ...) cohort onto device:

    PYTHONPATH=src python examples/quickstart.py --n-clients 2000 \\
        --cohort-size 50 --host-population 1

  The trajectory is bit-identical to the device-resident path (goldens
  enforced); --host-population 0 (default) picks the host plane
  automatically at >= 50k clients, -1 forces device-resident. At C=10^5+
  pair it with the lazy sharded data generator
  (repro.data.synthetic.make_sharded_population — O(K) host data memory)
  and ExecutionConfig.eval_chunk to stream the O(C) evaluation through
  fixed-size device slabs; see benchmarks/pop_bench.py + BENCH_pop.json
  for the C-sweep (step time sublinear in C at fixed K, zero
  population-sized device slabs).

  --edge-groups E adds two-level hierarchical aggregation on top:
  clients partial-aggregate at E edge servers, the server merges the E
  partials, and FLHistory.tx_edge_bytes accounts the edge->server hop
  (client->edge uplink stays in tx_bytes_cum, so flat accounting is
  unchanged). E=1 is bit-identical to flat aggregation; E>1 changes only
  the reduction tree (~1-ulp, like --devices):

    PYTHONPATH=src python examples/quickstart.py --n-clients 2000 \\
        --cohort-size 50 --host-population 1 --edge-groups 8

composing a custom round:
  A federated round is a pipeline of swappable phases (repro.fl.phases):

    Personalizer -> LocalTrainer -> TransmitPhase (wire codec + EF)
                 -> Aggregator -> Evaluator -> SelectorPhase -> LayerPolicy

  Build the default pipeline from a config, swap any phase, and hand it to
  run_federated:

    import dataclasses
    from repro.core.selection import get_strategy
    from repro.fl import api, phases, run_federated

    cfg = api.FLConfig(strategy="acsp-fl", personalization="dld", rounds=30,
                       cohort_size=16)
    pipe = api.pipeline_from_config(cfg)
    pipe = dataclasses.replace(
        pipe,
        selector=phases.SelectorPhase(get_strategy("oort-wire", fraction=0.3)),
        layer_policy=phases.get_phase("layer-policy", "static", layers=2),
    )
    hist = run_federated(ds, cfg, pipeline=pipe)

  Phase names live in string registries (phases.get_phase, get_strategy,
  repro.comm.make_codec); register_phase / register_strategy /
  register_codec_atom add custom components without touching the engine.

observing a run (--record-dir):
  Attach a structured run record (repro.obs) to the adaptive run:

    PYTHONPATH=src python examples/quickstart.py --record-dir experiments/run0 \\
        --trace --mode async --heterogeneity 1.0

  writes experiments/run0/:
    manifest.json  config snapshot + sha256 hash, backend/devices, git rev,
                   package versions, seed, and final summary stats
    metrics.jsonl  one JSON object per round (sync) or aggregation event
                   (async): accuracy, cohort size, wire bytes, simulated
                   round time/clock, update norms, staleness, in-flight
    run.log        the progress lines (progress printing routes through
                   the recorder — same text, also persisted)
    trace.json     (--trace) Chrome/Perfetto trace on the SIMULATED clock:
                   per-client dispatch->train->upload lanes, aggregation
                   instants, sync round/chunk spans. Open it at
                   https://ui.perfetto.dev (or chrome://tracing).
    profile.json   (--profile) wall-clock profile of the real loop:
                   compile vs dispatch vs device_get per chunk, jit cache
                   misses, live-array memory watermark

  Recording is pure host-side observation: the run's trajectory is
  bit-identical with or without a recorder (goldens enforced), and
  overhead at the default off state is zero.

surviving failures (--dropout-rate / --deadline / --resume):
  Real federations lose clients. --dropout-rate p crashes each dispatched
  client with probability p per round (seeded, deterministic — repro
  repro.fl.faults); --deadline s bounds the simulated round: under the
  sync barrier, clients past the deadline are dropped from aggregation
  (the round degrades to K_effective < K through the masked partial-
  aggregation path instead of stalling), while under --mode async the
  deadline is the per-slot timeout after which the dispatch is retried
  with exponential backoff (at most FaultConfig.max_retries times, never
  exceeding max_concurrency in-flight). Independently of injection, a
  finite-delta guard zero-masks NaN/Inf client updates before any
  aggregator sees them (FLHistory.rejected_updates counts them):

    PYTHONPATH=src python examples/quickstart.py --dropout-rate 0.3 \\
        --deadline 60 --heterogeneity 1.0

  converges to the fault-free target within <=2x the rounds at 30%
  dropout (gate enforced in benchmarks/fault_bench.py -> BENCH_fault.json).
  Long runs can snapshot and resume: --checkpoint-every n writes the full
  resumable state (round state + rng chain + host accounting, and the
  PopulationStore on --host-population 1 runs) into --resume DIR every n
  rounds through repro.checkpoint, and a rerun with the same --resume DIR
  restarts from the latest snapshot, bit-identical to the uninterrupted
  run:

    PYTHONPATH=src python examples/quickstart.py --rounds 100 \\
        --checkpoint-every 10 --resume experiments/quickstart_ckpt
    # ... interrupt it, then rerun the same command to continue

serving a personalized run (--serve):
  Training's output is not one model — it is a shared global model plus
  every client's personalization state (FT picks, DLD layer depths).
  --serve freezes exactly that into a servable artifact (repro.serve):

    PYTHONPATH=src python examples/quickstart.py --serve

  re-derives the adaptive run's final state (same rng chain, bit-identical
  trajectory), exports global params + per-client local slabs + per-client
  (C, L) share masks to experiments/quickstart_servable/, loads it back,
  and serves a mixed batch of clients through the continuous-batching
  engine: each request is (client_id, x); the engine gathers that client's
  personalized layers into its batch lane (the trainer's cohort jnp.take)
  and composes global-vs-local per layer, so ONE jitted forward answers a
  batch of different client models — bit-identical per lane to composing
  and running each client alone. Throughput/latency numbers for this path:
  benchmarks/serve_bench.py -> BENCH_serve.json (QPS, p50/p99 vs batch
  size x personalization mode).
"""


def main():
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=CUSTOM_ROUND_HELP,
    )
    ap.add_argument("--codec", default="float32",
                    help="wire codec for the adaptive run: float32 | int8 | int4 | topk | topk+int8")
    ap.add_argument("--strategy", default="acsp-fl",
                    help="selection strategy: acsp-fl | deev | poc | oort | grad-importance | oort-wire | oort-fair")
    ap.add_argument("--topk-fraction", type=float, default=0.1)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--mode", default="sync", choices=["sync", "async"],
                    help="server loop: sync barrier or async buffered aggregation")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="async: aggregate once this many updates land (0 = C//2)")
    ap.add_argument("--heterogeneity", type=float, default=0.0,
                    help="lognormal sigma of per-client delay multipliers (stragglers)")
    ap.add_argument("--n-clients", type=int, default=0,
                    help="override the dataset's population size (0 = paper's 30; "
                         ">=2000 uses the vectorized population generator)")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="max client lanes a round gathers/trains (0 = full "
                         "population, the dense-equivalent path)")
    ap.add_argument("--scan-chunk", type=int, default=1,
                    help="rounds fused per on-device scan chunk (sync loop; "
                         "1 = per-round host sync, 0 = whole run in one chunk)")
    ap.add_argument("--host-population", type=int, default=0, choices=[-1, 0, 1],
                    help="population plane placement: 0 = auto (host-resident "
                         "at >= 50k clients), 1 = force the host-resident "
                         "PopulationStore + per-round cohort staging, -1 = "
                         "force device-resident (see epilog)")
    ap.add_argument("--edge-groups", type=int, default=0,
                    help="two-level hierarchical aggregation over this many "
                         "edge groups (0 = flat client->server; edge->server "
                         "hop bytes land in FLHistory.tx_edge_bytes)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the adaptive run's cohort lanes over this many "
                         "devices (forces host devices on CPU, dev only; 0 = "
                         "unsharded; K must divide it — see epilog)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round P(dispatched client crashes before "
                         "upload) for the adaptive run (seeded fault "
                         "injection; see 'surviving failures' in the epilog)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="simulated round deadline in seconds: sync drops "
                         "late clients from aggregation, async retries the "
                         "slot with backoff (0 = no deadline)")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume the adaptive run from the latest snapshot "
                         "in DIR (also where --checkpoint-every writes)")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="snapshot the adaptive run's resumable state into "
                         "the --resume DIR every N rounds (0 = off)")
    ap.add_argument("--record-dir", default=None,
                    help="write a structured run record (manifest.json + "
                         "metrics.jsonl + run.log) for the adaptive run here")
    ap.add_argument("--trace", action="store_true",
                    help="with --record-dir: also export a Chrome/Perfetto "
                         "trace.json on the simulated clock")
    ap.add_argument("--profile", action="store_true",
                    help="with --record-dir: also profile the real loop "
                         "(compile/dispatch/device_get, jit cache misses, "
                         "memory watermark) into profile.json")
    ap.add_argument("--serve", action="store_true",
                    help="after training: export the adaptive run's global + "
                         "per-client state as a servable artifact "
                         "(experiments/quickstart_servable/) and demo batched "
                         "personalized inference on it (see epilog)")
    args = ap.parse_args()
    if (args.trace or args.profile) and not args.record_dir:
        ap.error("--trace/--profile require --record-dir")
    if args.checkpoint_every and not args.resume:
        ap.error("--checkpoint-every needs --resume DIR to write into")
    enable_compile_cache()
    # fail fast on a bad codec spec or strategy name before the
    # (minutes-long) baseline runs
    from repro.comm import make_codec
    from repro.core.selection import get_strategy
    make_codec(args.codec, topk_fraction=args.topk_fraction)
    get_strategy(args.strategy)

    ds = make_har_dataset("uci-har", seed=0, n_clients=args.n_clients or None)
    print(f"dataset: {ds.name} — {ds.n_clients} clients, {ds.n_features} features, {ds.n_classes} classes"
          + (f" (cohort_size={args.cohort_size})" if args.cohort_size else ""))

    print("\n[1/2] FedAvg baseline (100% participation, full model, float32 wire)")
    # same heterogeneity lane as the adaptive run (seed-derived), so the
    # simulated-clock comparison sees identical stragglers on both sides;
    # the baseline shares the cohort bound so both runs pay comparable compute
    fedavg = run_federated(
        ds, FLConfig(strategy="fedavg", personalization="none", fraction=1.0,
                     rounds=args.rounds, epochs=2, heterogeneity=args.heterogeneity,
                     cohort_size=args.cohort_size, scan_chunk=args.scan_chunk,
                     host_population=args.host_population,
                     edge_groups=args.edge_groups),
        progress=True,
    )

    print(f"\n[2/2] {args.strategy} (adaptive selection + DLD partial sharing + codec={args.codec}"
          + (f" + async buffer_k={args.buffer_k or ds.n_clients // 2}" if args.mode == "async" else "")
          + ")")
    cfg = fl_defaults()  # the paper's recipe (configs.har_mlp), tailored by flags
    from repro.fl import ExecutionConfig, FaultConfig
    cfg = dataclasses.replace(
        cfg,
        selection=dataclasses.replace(cfg.selection, strategy=args.strategy),
        codec=dataclasses.replace(cfg.codec, spec=args.codec, topk_fraction=args.topk_fraction),
        train=dataclasses.replace(cfg.train, rounds=args.rounds),
        scheduler=SchedulerConfig(mode=args.mode, buffer_k=args.buffer_k,
                                  heterogeneity=args.heterogeneity),
        execution=ExecutionConfig(cohort_size=args.cohort_size,
                                  scan_chunk=args.scan_chunk,
                                  cohort_devices=args.devices if args.devices > 1 else 0,
                                  host_population=args.host_population,
                                  edge_groups=args.edge_groups),
        faults=FaultConfig(dropout_rate=args.dropout_rate,
                           deadline_s=args.deadline),
    )
    recorder = None
    if args.record_dir:
        from repro.obs import RunRecorder
        recorder = RunRecorder(args.record_dir, trace=args.trace,
                               profile=args.profile)
    # first run with --resume DIR has nothing to resume yet: start fresh
    # but still checkpoint into DIR, so rerunning the command continues
    resume = args.resume
    if resume and not (os.path.isdir(resume)
                       and any(f.endswith("_meta.json") for f in os.listdir(resume))):
        resume = None
    acsp = run_federated(ds, cfg, progress=True, recorder=recorder,
                         checkpoint_every=args.checkpoint_every,
                         checkpoint_dir=args.resume, resume_from=resume)
    if recorder is not None:
        print(f"\nrun record -> {args.record_dir}/ (manifest.json, metrics.jsonl"
              + (", trace.json — open at https://ui.perfetto.dev" if args.trace else "")
              + (", profile.json" if args.profile else "") + ")")

    red = overhead_reduction(acsp.tx_bytes_cum[-1], fedavg.tx_bytes_cum[-1])
    name = args.strategy
    print("\n=== summary ===")
    print(f"accuracy      : FedAvg {fedavg.accuracy_mean[-1]:.3f} | {name} {acsp.accuracy_mean[-1]:.3f}")
    print(f"worst client  : FedAvg {fedavg.accuracy_per_client[-1].min():.3f} | {name} {acsp.accuracy_per_client[-1].min():.3f}")
    print(f"uplink bytes  : FedAvg {fedavg.tx_bytes_cum[-1]/1e6:.1f}MB | {name} {acsp.tx_bytes_cum[-1]/1e6:.1f}MB")
    print(f"communication reduction: {red:.1%} (paper reports up to 95% at 100 rounds)")
    print(f"avg clients/round: FedAvg {fedavg.selected.sum(1).mean():.1f} | {name} {acsp.selected.sum(1).mean():.1f}")
    print(f"simulated clock : FedAvg {fedavg.sim_clock[-1]:.1f}s | {name} {acsp.sim_clock[-1]:.1f}s"
          + (f" (mean staleness {acsp.staleness_mean.mean():.2f})" if args.mode == "async" else ""))
    assert acsp.tx_bytes_cum[-1] < fedavg.tx_bytes_cum[-1]

    if args.serve:
        serve_demo(ds, cfg)


def serve_demo(ds, cfg, out_dir="experiments/quickstart_servable", n_requests=64):
    """--serve: freeze the adaptive run into a servable artifact and serve a
    mixed batch of personalized requests from it (epilog: 'serving a
    personalized run')."""
    from repro.serve import (
        ClassifyProgram,
        ContinuousBatcher,
        PersonalizedEngine,
        ServeRequest,
        fit_servable,
        latency_stats,
        load_servable,
        save_servable,
    )

    print("\n[serve] re-deriving the adaptive run's final state "
          f"({cfg.rounds} rounds, mode={cfg.personalization.mode})")
    artifact, _ = fit_servable(ds, cfg)
    save_servable(artifact, out_dir)
    print(f"[serve] servable -> {out_dir}/ "
          f"({artifact.n_clients} clients, {artifact.n_layers} layers, "
          f"{artifact.meta['personalized_clients']} personalized)")

    engine = PersonalizedEngine(load_servable(out_dir))
    rng = np.random.default_rng(0)
    cids = rng.integers(0, ds.n_clients, size=n_requests)
    reqs = [
        ServeRequest(rid=i, client_id=int(c),
                     inputs=np.asarray(ds.x_test[int(c), i % ds.x_test.shape[1]]))
        for i, c in enumerate(cids)
    ]
    batch = 8
    results = ContinuousBatcher(ClassifyProgram(engine, batch), batch).run(reqs)
    stats = latency_stats(results)

    # every lane of the batched forward must equal that client's own
    # individually composed model — spot-check a few served requests
    for res in results[:4]:
        ref = np.asarray(engine.forward_unbatched(
            res.client_id, np.asarray(next(r.inputs for r in reqs if r.rid == res.rid))))
        assert np.array_equal(np.asarray(res.output), ref)
    print(f"[serve] {stats['n_requests']} requests @ batch {batch}: "
          f"{stats['qps']:.0f} req/s, p50 {stats['latency_p50_ms']:.2f}ms, "
          f"p99 {stats['latency_p99_ms']:.2f}ms "
          f"(batched == per-client compose, checked)")


if __name__ == "__main__":
    main()
