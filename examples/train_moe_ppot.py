"""Train a reduced MoE (moonshot family wiring) with the beyond-paper PPoT
expert router vs standard top-k, on the real train step (AdamW, remat,
chunked loss). Shows loss parity + the load-balancing win.

Run:  PYTHONPATH=src python examples/train_moe_ppot.py [--steps 60]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import SyntheticLM
from repro.dist import sharding as SH, steps as ST
from repro.models import api, moe as MOE
from repro.optim import adamw


def train(router: str, steps: int, seed: int = 0):
    cfg = configs.reduced(
        configs.get_config("moonshot-v1-16b-a3b"),
        n_layers=3, d_model=128, n_experts=8, top_k=2, moe_dff=128,
        vocab=512, router=router,
    )
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ctx = SH.make_ctx(mesh)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=1e-3, total_steps=steps, warmup_steps=5)
    step = jax.jit(ST.make_train_step(cfg, ctx, ocfg))
    data = SyntheticLM(cfg.vocab, 128, 8, seed=seed)
    losses = []
    t0 = time.time()
    for i in range(steps):
        batch = jax.tree.map(jnp.asarray, data.batch_at(i))
        params, opt, m = step(params, opt, batch, jax.random.fold_in(jax.random.PRNGKey(1), i))
        losses.append(float(m["loss"]))
    return losses, time.time() - t0, cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    print("router   first-10-loss  last-10-loss   wall")
    for router in ("topk", "ppot"):
        losses, wall, cfg = train(router, args.steps)
        print(f"{router:8s} {np.mean(losses[:10]):12.4f} {np.mean(losses[-10:]):13.4f} {wall:6.1f}s")

    # load-balance comparison on identical gates
    cfg = configs.reduced(configs.get_config("moonshot-v1-16b-a3b"),
                          n_experts=16, top_k=4, moe_dff=64)
    gates = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(2), (4096, 16)) * 1.5
        + jnp.linspace(2, 0, 16)[None, :])
    i1, _ = MOE.topk_route(cfg, gates)
    i2, _ = MOE.ppot_route(cfg, gates, jax.random.PRNGKey(3))
    s1 = MOE.expert_load_stats(cfg, gates, i1)
    s2 = MOE.expert_load_stats(cfg, gates, i2)
    print(f"\nexpert overflow @cf=1.25:  topk={float(s1['overflow_frac']):.3f}  "
          f"ppot={float(s2['overflow_frac']):.3f}  "
          f"(max load {float(s1['max_load']):.0f} → {float(s2['max_load']):.0f})")


if __name__ == "__main__":
    main()
