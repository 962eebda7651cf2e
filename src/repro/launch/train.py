"""Single-host training driver for any assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-vl-2b --reduced \
        --steps 50 --batch 4 --seq 128

On this CPU container only --reduced configs are runnable; the full configs
train through the same code path on a real TPU slice (the mesh/sharding
setup mirrors repro.launch.dryrun).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model, make_concrete_batch
from repro.optim import adamw, chain, clip_by_global_norm, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = get_model(cfg)

    rng = jax.random.PRNGKey(args.seed)
    params = bundle.init(rng)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"{args.arch}{' (reduced)' if args.reduced else ''}: {n_params/1e6:.1f}M params")

    opt = chain(
        clip_by_global_norm(1.0),
        adamw(cosine_schedule(args.lr, warmup_steps=max(2, args.steps // 10), total_steps=args.steps)),
    )
    opt_state = opt.init(params)
    step_fn = jax.jit(bundle.make_train_step(opt))

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        rng, sub = jax.random.split(rng)
        batch = make_concrete_batch(cfg, "train", args.batch, args.seq, sub)
        params, opt_state, loss = step_fn(params, opt_state, batch)
        losses.append(float(loss))
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(f"  step {step:4d}  loss {losses[-1]:.4f}  ({(time.time()-t0)/(step+1):.2f}s/step)")

    assert np.isfinite(losses).all(), "NaN/inf loss"
    print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} in {args.steps} steps")
    if args.ckpt:
        path = save_pytree(params, args.ckpt, f"{args.arch.replace('/', '_')}")
        print(f"saved {path}")


if __name__ == "__main__":
    main()
