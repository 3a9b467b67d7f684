"""End-to-end training driver with fault tolerance.

Features exercised even at CPU scale (reduced configs): deterministic
resume-exact data pipeline, checkpoint/restart (crash-safe, elastic across
mesh changes), straggler-aware microbatch planning hooks, and the jitted
train step with the production sharding rules on whatever mesh is
available.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 200 --seq-len 256 --global-batch 16 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import ckpt as CKPT
from repro import configs
from repro.data import Prefetcher, SyntheticLM
from repro.dist import sharding as SH
from repro.dist import steps as ST
from repro.models import api
from repro.optim import adamw


def make_mesh_auto():
    auto = (jax.sharding.AxisType.Auto,) * 2
    n = len(jax.devices())
    if n == 1:
        return jax.make_mesh((1, 1), ("data", "model"), axis_types=auto)
    model = 1
    for m in (8, 4, 2):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=auto)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-sync", default="auto", choices=["auto", "int8"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    args = ap.parse_args(argv)

    cfg = configs.get_config(args.arch)
    if args.reduced:
        over = {}
        if args.n_layers:
            over["n_layers"] = args.n_layers
        if args.d_model:
            over["d_model"] = args.d_model
        cfg = configs.reduced(cfg, **over)
    mesh = make_mesh_auto()
    ctx = SH.make_ctx(mesh)
    print(f"[train] arch={cfg.arch} family={cfg.family} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    key = jax.random.PRNGKey(args.seed)
    params = api.init_params(cfg, key)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 1))
    opt_state = adamw.init(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"[train] {n_params/1e6:.1f}M params")

    # --- fault tolerance: resume from the latest checkpoint ---------------
    start_step = 0
    if args.ckpt_dir:
        latest = CKPT.latest_step(args.ckpt_dir)
        if latest is not None:
            (params, opt_state), manifest = CKPT.restore(
                args.ckpt_dir, (params, opt_state)
            )
            start_step = manifest["step"]
            print(f"[train] resumed from step {start_step}")

    data = SyntheticLM(cfg.vocab, args.seq_len, args.global_batch, seed=args.seed)
    prefetch = Prefetcher(data, start_step=start_step)

    step_fn = ST.make_train_step(
        cfg, ctx, opt_cfg, microbatches=args.microbatches, grad_sync=args.grad_sync
    )
    pspecs = SH.param_specs(cfg, ctx, params)
    ospecs_leaf = SH.opt_state_specs(cfg, ctx, pspecs, params)
    ospecs = adamw.AdamWState(master=ospecs_leaf, m=ospecs_leaf, v=ospecs_leaf, count=P())
    isP = lambda x: isinstance(x, P)
    nt = lambda t: jax.tree.map(ctx.ns, t, is_leaf=isP)
    jit_step = jax.jit(
        step_fn,
        in_shardings=(nt(pspecs), nt(ospecs), None, None),
        out_shardings=(nt(pspecs), nt(ospecs), None),
        donate_argnums=(0, 1),
    )

    losses = []
    t0 = time.time()
    for i in range(start_step, args.steps):
        step_i, batch = next(prefetch)
        assert step_i == i, f"data pipeline desync: {step_i} != {i}"
        batch = jax.tree.map(jnp.asarray, batch)
        rng = jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), i)
        params, opt_state, metrics = jit_step(params, opt_state, batch, rng)
        losses.append(float(metrics["loss"]))
        if (i + 1) % args.log_every == 0:
            dt = time.time() - t0
            print(f"[train] step {i+1}: loss={losses[-1]:.4f} "
                  f"lr={float(metrics['lr']):.2e} gnorm={float(metrics['grad_norm']):.3f} "
                  f"({dt/args.log_every:.2f}s/step)")
            t0 = time.time()
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, i + 1, (params, opt_state),
                      extra={"loss": losses[-1]})
            print(f"[train] checkpointed step {i+1}")
    prefetch.close()

    out = {"final_loss": losses[-1], "first_loss": losses[0],
           "steps": args.steps, "params_m": n_params / 1e6}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
