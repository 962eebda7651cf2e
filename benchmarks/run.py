"""Benchmark harness entry point — one function per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick]
Emits CSVs to experiments/bench/ and prints name,us_per_call,derived lines.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer rounds / datasets")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny sizes, perf entry points only (kernel + codec)")
    args = ap.parse_args()
    if args.quick:
        os.environ["REPRO_BENCH_ROUNDS"] = "10"
    if args.smoke:
        os.environ["REPRO_BENCH_ROUNDS"] = "5"
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        async_bench,
        client_distribution,
        codec_bench,
        comm_overhead,
        fault_bench,
        kernel_bench,
        loop_bench,
        obs_smoke,
        pop_bench,
        roofline,
        scale_bench,
        selection_bench,
        selection_frequency,
        serve_bench,
        shard_bench,
        table3_variants,
        table4_literature,
    )

    suites = [
        ("table3_variants (paper Table 3 / Fig 6)", table3_variants.run),
        ("table4_literature (paper Table 4 / Fig 8)", table4_literature.run),
        ("comm_overhead (paper Fig 7)", comm_overhead.run),
        ("client_distribution (paper Fig 10)", client_distribution.run),
        ("selection_frequency (paper Fig 11)", selection_frequency.run),
        ("kernel_bench", kernel_bench.run),
        ("codec_bench (comm subsystem)", codec_bench.run),
        ("selection_bench (strategy x codec grid)", selection_bench.run),
        ("async_bench (sync vs async scheduler grid)", async_bench.run),
        ("fault_bench (dropout/deadline robustness, resume-safe grid)", fault_bench.run),
        ("scale_bench (cohort O(K) vs dense O(C) rounds)", scale_bench.run),
        ("loop_bench (round-fused executor vs per-round dispatch)", loop_bench.run),
        ("shard_bench (cohort-sharded step, D-device strong scaling)", shard_bench.run),
        ("pop_bench (host-resident population plane, C-sweep)", pop_bench.run),
        ("serve_bench (personalized serving QPS/p99 x batch x mode)", serve_bench.run),
        ("obs_smoke (recorded + traced run, artifacts validated)", obs_smoke.run),
        ("roofline (deliverable g)", roofline.run),
    ]
    if args.smoke:  # CI smoke: the perf + pipeline entry points, tiny sizes
        suites = [
            s for s in suites
            if s[0].split(" ")[0]
            in ("kernel_bench", "codec_bench", "selection_bench", "async_bench",
                "fault_bench", "scale_bench", "loop_bench", "shard_bench",
                "serve_bench", "pop_bench", "obs_smoke")
        ]
    t00 = time.time()
    for name, fn in suites:
        print(f"\n=== {name} ===")
        t0 = time.time()
        try:
            path = fn()
            print(f"-> {path} ({time.time()-t0:.0f}s)")
        except Exception as e:  # keep the harness going; report at the end
            import traceback

            traceback.print_exc()
            print(f"!! {name} FAILED: {e}")
            sys.exit(1)
    print(f"\nall benchmarks done in {time.time()-t00:.0f}s")


if __name__ == "__main__":
    main()
