"""Roofline report from the dry-run JSONs (deliverable g).

Per (arch x shape x mesh): the three roofline terms (compute / memory /
collective, in seconds), the dominant bottleneck, MODEL_FLOPS = 6·N·D
(6·N_active·D for MoE), and the useful-compute ratio MODEL_FLOPS/HLO_FLOPS.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.common import write_csv
from repro.configs import SHAPES, get_config

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def model_flops(meta: dict) -> float:
    """6·N_active·D for the step's token count (train: fwd+bwd; decode: 2·N·D_tokens)."""
    cfg = get_config(meta["arch"])
    shape = SHAPES[meta["shape"]]
    n_active = cfg.active_param_count()
    seq = shape.seq_len
    if cfg.encoder_decoder:
        # whisper: decoder capped at max_decoder_seq; encoder frames fixed
        seq = min(seq, cfg.max_decoder_seq or seq) + cfg.encoder_seq
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * seq
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * seq
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def load_rows():
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if r.get("fl_shared") is not None:
            continue  # FL-mode runs reported separately in EXPERIMENTS.md §Perf
        mf = model_flops(r)
        hlo_total = r["flops_per_device"] * r["n_chips"]
        rows.append({
            **r,
            "model_flops": mf,
            "useful_ratio": mf / hlo_total if hlo_total else 0.0,
        })
    return rows


def run():
    rows = load_rows()
    header = ["arch", "shape", "mesh", "t_compute_ms", "t_memory_ms", "t_collective_ms",
              "bottleneck", "model_tflops", "useful_ratio", "temp_gib"]
    out = []
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"], x["multi_pod"])):
        mesh = "2x16x16" if r["multi_pod"] else "16x16"
        out.append([
            r["arch"], r["shape"], mesh,
            f"{r['t_compute']*1e3:.2f}", f"{r['t_memory']*1e3:.2f}", f"{r['t_collective']*1e3:.2f}",
            r["bottleneck"].replace("t_", ""),
            f"{r['model_flops']/1e12:.1f}", f"{r['useful_ratio']:.3f}",
            f"{(r['memory']['temp_bytes'] or 0)/2**30:.1f}",
        ])
        print("  " + " ".join(f"{c:>14s}" if i > 2 else f"{c:<22s}" for i, c in enumerate(out[-1])))
    return write_csv("roofline", header, out)


if __name__ == "__main__":
    run()
