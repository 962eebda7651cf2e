"""Batched serving driver: prefill queue + decode loop for any assigned
architecture (reduced configs on CPU; the same code path serves full configs
on a TPU slice — cache shardings per repro.launch.sharding.cache_spec).

    PYTHONPATH=src python -m repro.launch.serve --arch falcon-mamba-7b \
        --requests 8 --batch 4 --prompt-len 64 --max-new 32

The decode loop itself lives in ``repro.serve.decode`` (shared with
``examples/serve_decode.py`` and the continuous-batching serve loop).
Token-only architectures run true continuous batching — finished lanes
(EOS or max-new) retire and are back-filled from the queue in the same
iteration by re-prefilling the joined batch — while architectures with
richer prefill inputs fall back to static waves via ``greedy_decode``.
Either way the EOS id comes from the model config (``cfg.eos_token_id``)
and generated tokens are accounted per lane: a re-prefilled survivor's
history is never re-counted in the tok/s number.

``--record DIR`` writes a structured serve record (manifest +
requests.jsonl + Perfetto trace) through ``repro.serve.record``.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model, make_concrete_batch
from repro.serve import (
    ContinuousBatcher,
    DecodeProgram,
    ServeRecorder,
    ServeRequest,
    ServeResult,
    greedy_decode,
    latency_stats,
    token_only_prefill,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="falcon-mamba-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--record", default=None, metavar="DIR",
                    help="write a serve record (manifest/requests/trace) here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(args.seed))
    prefill = jax.jit(bundle.make_prefill_step(window=args.window))
    decode = jax.jit(bundle.make_decode_step(window=args.window))
    eos = cfg.eos_token_id

    recorder = None
    if args.record:
        recorder = ServeRecorder(args.record, trace=True)
        recorder.open_session(
            artifact_meta={"arch": args.arch, "kind": "lm-decode",
                           "eos_token_id": eos},
            engine="decode",
            batch_size=args.batch,
            extra={"prompt_len": args.prompt_len, "max_new": args.max_new},
        )

    t0 = time.time()
    if token_only_prefill(cfg):
        # continuous batching: every request is an independent lane tenant
        proto = make_concrete_batch(
            cfg, "prefill", args.requests, args.prompt_len,
            jax.random.PRNGKey(args.seed + 1),
        )
        prompts = np.asarray(proto["tokens"])
        program = DecodeProgram(
            prefill, decode, params, args.batch, args.prompt_len,
            eos_id=eos, temperature=args.temperature,
            rng=jax.random.PRNGKey(args.seed + 2),
        )
        reqs = [
            ServeRequest(rid=i, client_id=i, inputs=prompts[i], steps=args.max_new)
            for i in range(args.requests)
        ]
        results = ContinuousBatcher(program, args.batch, recorder=recorder).run(reqs)
        n_served = len(results)
        n_tok = program.tokens_out
        lens = [r.steps for r in sorted(results, key=lambda r: r.rid)]
        print(f"continuous: {n_served} requests, lens {lens}, "
              f"{program.prefill_calls} prefills")
        stats = latency_stats(results)
    else:
        # wave fallback: prefill inputs beyond raw tokens can't be rebuilt
        # lane-wise mid-flight, so waves retire together
        rng = jax.random.PRNGKey(args.seed + 2)
        queue = list(range(args.requests))
        n_served = n_tok = 0
        wave_results = []
        while queue:
            wave, queue = queue[: args.batch], queue[args.batch:]
            rng, sub, s_dec = jax.random.split(rng, 3)
            batch = make_concrete_batch(cfg, "prefill", len(wave), args.prompt_len, sub)
            t_wave = time.time() - t0
            seqs, n_gen = greedy_decode(
                prefill, decode, params, batch, args.max_new,
                eos_id=eos, temperature=args.temperature, rng=s_dec,
            )
            t_fin = time.time() - t0
            n_served += len(wave)
            n_tok += int(n_gen.sum())
            for rid, s in zip(wave, seqs):
                res = ServeResult(rid=rid, client_id=rid, output=s,
                                  enqueue_s=0.0, start_s=t_wave,
                                  finish_s=t_fin, steps=len(s))
                wave_results.append(res)
                if recorder is not None:
                    recorder.on_request(res)
            print(f"wave of {len(wave)}: {[len(s) for s in seqs]} tokens each "
                  f"({n_tok / (time.time() - t0 + 1e-9):.1f} tok/s cumulative)")
        stats = latency_stats(wave_results)

    dt = time.time() - t0
    stats["tokens"] = int(n_tok)
    stats["tok_per_s"] = n_tok / max(dt, 1e-9)
    if recorder is not None:
        print("serve record:", recorder.close(stats))
    print(f"\nserved {n_served} requests, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tok/s, CPU interpret path; TPU is the target)")
    assert n_served == args.requests


if __name__ == "__main__":
    main()
