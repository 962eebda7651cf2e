import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct inputs — no allocation — and report
memory_analysis / cost_analysis / HLO collective bytes for §Dry-run and
§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --arch ... --shape train_4k --fl-shared 4  # cross-silo FL mode
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, get_shape, list_archs
from repro.launch import context as ctxmod
from repro.launch.collectives import collective_breakdown_str, collective_bytes
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import V5E, chip_peaks, data_axes, make_production_mesh
from repro.launch.sharding import batch_spec, cache_pspecs, tree_pspecs
from repro.models.api import get_model, make_batch_specs
from repro.optim import adamw

SLIDING_WINDOW = 8192


def _sds_tree(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _named(mesh, spec_tree):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P))


def build_lowerable(arch: str, shape_name: str, mesh, multi_pod: bool, fl_shared: int | None = None):
    """Returns (fn, example_args (ShapeDtypeStructs), in_shardings, out_shardings, meta)."""
    from jax.sharding import PartitionSpec as P

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    dp = data_axes(multi_pod)

    window = 0
    if shape.needs_subquadratic and cfg.attn_type != "none":
        # jamba's 4 attn layers keep the native full 500k cache (hybrid is
        # sub-quadratic overall); pure-attention archs take the SW variant
        window = 0 if cfg.ssm else SLIDING_WINDOW
    bundle = get_model(cfg)

    params_sds = jax.eval_shape(bundle.init, jax.random.key(0))
    param_specs = tree_pspecs(params_sds, mesh, dp)

    meta = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "window": window, "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }

    if fl_shared is not None:
        from repro.fl.cross_silo import build_fl_dryrun

        return build_fl_dryrun(cfg, bundle, shape, mesh, dp, fl_shared, meta)

    if shape.kind == "train":
        opt = adamw(3e-4)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_specs = tree_pspecs(opt_sds, mesh, dp)
        bspecs = make_batch_specs(cfg, "train", shape.global_batch, shape.seq_len)
        batch_sds = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in bspecs.items()}
        batch_specs = {k: batch_spec(k, s, mesh, dp) for k, (s, d) in bspecs.items()}
        fn = bundle.make_train_step(opt, window=window)
        return (
            fn,
            (params_sds, opt_sds, batch_sds),
            (param_specs, opt_specs, batch_specs),
            (param_specs, opt_specs, P()),
            meta,
        )

    if shape.kind == "prefill":
        bspecs = make_batch_specs(cfg, "prefill", shape.global_batch, shape.seq_len)
        batch_sds = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in bspecs.items()}
        batch_specs = {k: batch_spec(k, s, mesh, dp) for k, (s, d) in bspecs.items()}
        fn = bundle.make_prefill_step(window=window)
        return fn, (params_sds, batch_sds), (param_specs, batch_specs), None, meta

    # decode
    cache_sds = jax.eval_shape(lambda: bundle.init_cache(shape.global_batch, shape.seq_len, window))
    cache_specs = cache_pspecs(cache_sds, mesh, dp)
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_spec = batch_spec("tokens", (shape.global_batch, 1), mesh, dp)
    fn = bundle.make_decode_step(window=window)
    return fn, (params_sds, cache_sds, tok_sds), (param_specs, cache_specs, tok_spec), None, meta


def run_one(arch: str, shape_name: str, multi_pod: bool = False, fl_shared: int | None = None, verbose: bool = True, seq_parallel: bool = False):
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    dp = data_axes(multi_pod)
    t0 = time.time()
    with ctxmod.mesh_context(mesh, dp_axes=dp, moe_ep=(fl_shared is None), seq_parallel=seq_parallel):
        fn, args, in_sh, out_sh, meta = build_lowerable(arch, shape_name, mesh, multi_pod, fl_shared)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware analysis: XLA's cost_analysis counts while bodies ONCE;
    # our models scan over layer periods, so flops/collectives must be
    # multiplied by known_trip_count (repro.launch.hlo_analysis).
    la = hlo_analyze(hlo)
    coll_flat = collective_bytes(hlo)  # flat (loop-unaware) for reference

    flops_dev = float(la["flops"])
    bytes_dev = float(la["bytes"])
    peaks = chip_peaks(V5E)

    result = {
        **meta,
        "fl_shared": fl_shared,
        "seq_parallel": seq_parallel,
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": float(la["collective_bytes"]),
        "collectives": la["collectives"],
        "xla_flat_flops": float(cost.get("flops", 0.0)),
        "xla_flat_bytes": float(cost.get("bytes accessed", 0.0)),
        "flat_collective_bytes": coll_flat.get("total", 0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        # roofline terms (seconds) — per-device quantities over per-chip rates
        "t_compute": flops_dev / peaks["peak_flops_bf16"],
        "t_memory": bytes_dev / peaks["hbm_bw"],
        "t_collective": float(la["collective_bytes"]) / peaks["ici_bw"],
    }
    terms = {k: result[k] for k in ("t_compute", "t_memory", "t_collective")}
    result["bottleneck"] = max(terms, key=terms.get)

    if verbose:
        mb = lambda x: f"{(x or 0)/2**30:.2f}GiB"
        print(f"[{arch} x {shape_name} x {'2pod' if multi_pod else '1pod'}"
              + (f" fl_shared={fl_shared}" if fl_shared is not None else "") + "]")
        print(f"  lower {t_lower:.0f}s compile {t_compile:.0f}s  chips={n_chips}")
        print(f"  memory: args={mb(result['memory']['argument_bytes'])} temp={mb(result['memory']['temp_bytes'])} out={mb(result['memory']['output_bytes'])}")
        print(f"  cost (loop-aware): flops/dev={flops_dev:.3e} bytes/dev={bytes_dev:.3e}")
        coll_str = " ".join(f"{k}={v/1e6:.1f}MB" for k, v in sorted(la["collectives"].items()))
        print(f"  collectives/dev: total={la['collective_bytes']/1e6:.1f}MB {coll_str}")
        print(f"  roofline: compute={result['t_compute']*1e3:.2f}ms memory={result['t_memory']*1e3:.2f}ms collective={result['t_collective']*1e3:.2f}ms -> {result['bottleneck']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--fl-shared", type=int, default=None,
                    help="cross-silo FL round step sharing the first N stack periods")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="§Perf: sequence-parallel residual stream (train)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    enable_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    combos = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                combos.append((a, s, mp))

    failures = []
    for a, s, mp in combos:
        tag = (f"{a}_{s}_{'2pod' if mp else '1pod'}"
               + (f"_fl{args.fl_shared}" if args.fl_shared is not None else "")
               + ("_sp" if args.seq_parallel else ""))
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path):
            print(f"skip {tag} (exists)")
            continue
        try:
            res = run_one(a, s, multi_pod=mp, fl_shared=args.fl_shared, seq_parallel=args.seq_parallel)
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
        except Exception as e:
            traceback.print_exc()
            failures.append((tag, str(e)))
            with open(os.path.join(args.out, tag + ".FAILED"), "w") as f:
                f.write(traceback.format_exc())
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print(f"\nall {len(combos)} combos passed")


if __name__ == "__main__":
    main()
