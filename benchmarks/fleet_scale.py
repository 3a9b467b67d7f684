"""§Fleet — scaling and staleness cost of S parallel frontends.

Measurements, one JSON (``BENCH_fleet.json``):

0. **scan_fleet: the one-program fleet** (``run_fleet_simulation_scan``) —
   aggregate routing decisions/s vs S ∈ {1, 2, 4, 8} at the SAME total
   arrival rate (B_tot_scan requests per turn, each frontend handling
   B_tot_scan/S), the whole closed loop (S frontends × environment ×
   shared pool) as one compiled scan. Three numbers per S, the PR-3
   methodology keys: ``modeled_aggregate`` (B_tot / isolated-S=1-turn
   latency at batch B_tot/S — one machine per frontend, the paper's
   deployment), ``measured_stacked`` (all S frontends vmapped on this
   one device), ``measured_hostmesh`` (shard_map over S devices: S chips
   of a TPU host in this process, or S forced host devices in a child
   process on a CPU-only box — a lower bound there). Plus an
   arrival_batch-k sweep of the fleet scan under the ``cotenant_shock``
   scenario (latency percentiles + req/s vs batching granularity).

Plus the PR-3 baseline sections (preserved under ``pr3_baseline``):

1. **decisions/s vs S ∈ {1, 2, 4, 8, 16}** under the SAME total arrival
   rate (B_tot decisions per fleet step; each frontend handles B_tot/S).
   Two numbers per S, honestly labeled:

     * ``modeled_aggregate``: B_tot / t(B_tot/S) where t is the ISOLATED
       per-frontend engine latency measured on this host — the fleet's
       capacity when every frontend has its own machine (the deployment
       the paper describes). Scaling above 1× comes from real sub-linear
       per-frontend cost, not from pretending this container has S cores.
     * ``measured_hostmesh``: wall-clock of the shard_map fleet step over
       S devices (S TPU chips in this process; on a CPU-only box a child
       with ``--xla_force_host_platform_device_count=S``), sync
       fired every ``sync_every`` steps — S time-shared shards on THIS
       host's cores, so it lower-bounds true fleet parallelism (this box
       has few cores; the modeled number is the capacity claim).

2. **p50/p99 response-time inflation vs staleness bound** on the Fig-8
   workload (30 TPC-H-speed workers, load 0.8): S = 4 frontends, sync
   cadence swept over {1, 4, 16, 64, 256} chain rounds, each setting
   reporting response percentiles + ``metrics.fleet_summary`` (λ̂
   calibration, staleness histogram, herd-collision rate) — the p99 price
   of reduced coordination, with and without the herd-conflict correction
   at the widest bound.

3. **S = 1 parity**: the serving fleet harness (``run_fleet_simulation``,
   S = 1) against the single-frontend ``run_simulation`` on a Fig-8-style
   serving workload — must agree to 0.0% (bit-equal streams).

  PYTHONPATH=src:. python benchmarks/fleet_scale.py [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import csv_row, write_bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

S_SWEEP = (1, 2, 4, 8, 16)
SYNC_SWEEP = (1, 4, 16, 64, 256)
N_WORKERS = 64  # decisions/s shape (matches BENCH_dispatch.json)
B_TOT = 32768  # fleet-step decision batch at the same total arrival rate
SCAN_S_SWEEP = (1, 2, 4, 8)
B_TOT_SCAN = 2048  # per-turn request batch for the one-program fleet scan
K_SWEEP_COTENANT = (8, 32, 128)

# Child of a CPU-only parent: S forced host devices exist only in a process
# that sets the flag before JAX starts.
_MESH_CHILD = """
import json, sys
import numpy as np, jax
from jax.sharding import Mesh
from benchmarks import fleet_scale as fs
kw = json.loads(sys.argv[1])
fn = getattr(fs, kw.pop("fn"))
print(json.dumps(fn(mesh=Mesh(np.array(jax.devices()), ("sched",)), **kw)))
"""


def _on_mesh(S: int, fn, **kw) -> dict | None:
    """``fn(mesh=<S-device mesh>, **kw)``. On a TPU host the mesh is built
    in this process from ``jax.devices()``: this process holds the chips,
    so a child could not reach them. Returns None when the host has fewer
    than S chips. Elsewhere ``fn`` runs in a child over S forced host
    devices; a failed child raises."""
    import jax

    if jax.default_backend() == "tpu":
        if len(jax.devices()) < S:
            return None
        from jax.sharding import Mesh

        return fn(mesh=Mesh(np.array(jax.devices()[:S]), ("sched",)), **kw)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={S}"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + REPO
    out = subprocess.run(
        [sys.executable, "-c", _MESH_CHILD,
         json.dumps({"fn": fn.__name__, **kw})],
        capture_output=True, text=True, env=env, timeout=900, cwd=REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"{fn.__name__} over {S} host devices failed "
            f"(rc={out.returncode}):\n{out.stderr[-3000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _fleet_step_rate(*, mesh, m: int, iters: int, sync_every: int) -> dict:
    """Wall-clock of the shard_map fleet step over ``mesh`` (one frontend
    per device), sync fired every ``sync_every`` steps."""
    import jax
    import jax.numpy as jnp

    from repro.core import learner as lrn
    from repro.fleet import (init_fleet_frontends, make_fleet_step,
                             make_fleet_sync)

    S, n = mesh.devices.size, N_WORKERS
    lcfg = lrn.default_learner_config(mu_bar=float(n))
    ffs = init_fleet_frontends(S, n, lcfg)
    step = make_fleet_step(mesh, m=m)
    sync = make_fleet_sync(mesh)

    def keys(i):
        return jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(0), i), S)

    nows = jnp.arange(1, S + 1, dtype=jnp.float32)
    w, ffs = step(ffs, keys(0), nows)  # compile
    ffs = sync(ffs, jnp.float32(0.0))
    jax.block_until_ready(w)
    t0 = time.time()
    for i in range(iters):
        w, ffs = step(ffs, keys(i + 1), nows * (i + 2))
        if (i + 1) % sync_every == 0:
            ffs = sync(ffs, jnp.float32(i))
    jax.block_until_ready(w)
    wall = time.time() - t0
    return {"wall_s": wall, "dec_per_s": S * m * iters / wall}


def _fleet_scan_mesh_rate(*, mesh, k: int, turns: int,
                          sync_every: int) -> dict:
    dec_per_s, wall = _fleet_scan_rate(
        mesh.devices.size, k, turns, sync_every=sync_every, mesh=mesh)
    return {"wall_s": wall, "dec_per_s": dec_per_s}


def _fleet_scan_rate(S: int, k: int, turns: int, *, sync_every: int = 8,
                     mesh=None, repeats: int = 3) -> tuple[float, float]:
    """Aggregate routed-requests/s of the one-program fleet scan: S
    frontends × Poisson environment × shared pool, arrival batch ``k``
    per turn, production config (async μ̂ flips + frozen per-sync alias
    tables). First driver call compiles (the scan program is lru-cached on
    its shape), the best of ``repeats`` warm calls is reported — the whole
    host driver including workload precompute and state writeback, i.e.
    the rate the serving pipeline actually delivers."""
    from repro.serving import (
        FleetRouter,
        SimulatedPool,
        run_fleet_simulation_scan,
    )

    speeds = np.ones(N_WORKERS)
    rate = 0.8 * float(speeds.sum())
    horizon = turns * k / rate

    def once():
        r = FleetRouter(S, N_WORKERS, mu_bar=float(speeds.sum()), seed=0)
        p = SimulatedPool(speeds)
        t0 = time.time()
        resp, _, info = run_fleet_simulation_scan(
            r, p, arrival_rate=rate, horizon=horizon, seed=0,
            arrival_batch=k, sync_every=sync_every, frozen_mu=True,
            pend_cap=4 * k, mesh=mesh,
        )
        return time.time() - t0, len(resp)

    once()  # compile
    best, routed = min(
        (once() for _ in range(repeats)), key=lambda t: t[0]
    )
    return routed / best, best


def _scan_fleet(smoke: bool) -> tuple[list[str], dict]:
    """scan_fleet section: aggregate dec/s vs S at the same total arrival
    rate, PR-3 methodology keys (modeled = isolated per-frontend latency,
    measured = this container), for the ONE-PROGRAM fleet."""
    turns = 8 if smoke else 16
    b_tot = 512 if smoke else B_TOT_SCAN
    per_s, rows = {}, []
    for S in SCAN_S_SWEEP:
        k_f = b_tot // S
        # isolated frontend: an S=1 program at this frontend's share —
        # per-turn latency t(B/S); modeled aggregate = B / t
        iso_rate, iso_wall = _fleet_scan_rate(1, k_f, turns)
        iso_turn_s = iso_wall / turns
        modeled = b_tot / iso_turn_s
        # stacked: all S frontends vmapped in one program on this device
        stacked_rate, _ = _fleet_scan_rate(S, b_tot, turns)
        mesh = (
            _on_mesh(S, _fleet_scan_mesh_rate, k=b_tot, turns=turns,
                     sync_every=8) if S > 1 else None
        )
        per_s[S] = {
            "per_frontend_batch": k_f,
            "isolated_frontend_turn_ms": iso_turn_s * 1e3,
            "modeled_aggregate_dec_per_s": modeled,
            "measured_stacked_dec_per_s": stacked_rate,
            "measured_hostmesh_dec_per_s": (
                mesh["dec_per_s"] if mesh else None
            ),
        }
        rows.append(csv_row(
            f"scan_fleet_S{S}", iso_turn_s / k_f * 1e6,
            f"modeled={modeled/1e6:.2f}M/s;"
            f"stacked={stacked_rate/1e6:.2f}M/s",
        ))
    scale8 = (per_s[8]["modeled_aggregate_dec_per_s"]
              / per_s[1]["modeled_aggregate_dec_per_s"])
    rows.append(csv_row(
        "scan_fleet_scaling_claim", 0.0,
        f"S8_vs_S1={scale8:.2f}x;meets_3x={scale8 >= 3.0}",
    ))
    return rows, {
        "b_tot": b_tot,
        "turns": turns,
        "by_S": per_s,
        "scaling_S8_vs_S1_modeled": scale8,
        "meets_3x_bar": bool(scale8 >= 3.0),
        "methodology": (
            "same total arrival rate: b_tot=%d requests per turn, "
            "per-frontend share b_tot/S; modeled aggregate = b_tot / "
            "isolated-S=1-scan turn latency t(b_tot/S) (one machine per "
            "frontend, the paper's deployment); measured_stacked = the "
            "S-frontend one-program scan on this single device; "
            "measured_hostmesh = the same program shard_mapped over S "
            "forced host devices time-sharing this container's cores "
            "(lower bound)" % b_tot
        ),
    }


def _batch_sweep_cotenant(smoke: bool) -> tuple[list[str], dict]:
    """arrival_batch-k sweep of the S=4 fleet scan under the
    ``cotenant_shock`` scenario: batching granularity vs latency
    percentiles and delivered req/s on an interference workload."""
    from repro import env as envmod
    from repro.env.serving import run_scenario

    scn = envmod.make("cotenant_shock")
    ks = K_SWEEP_COTENANT[:2] if smoke else K_SWEEP_COTENANT
    S = 4
    sweep, rows = {}, []
    for k in ks:
        def once():
            t0 = time.time()
            out = run_scenario(
                scn, use_scan=True, arrival_batch=k, seed=0,
                n_frontends=S, sync_every=4, frozen_mu=True,
            )
            return time.time() - t0, out
        once()  # compile (shape changes with k)
        wall, out = min((once() for _ in range(2)), key=lambda t: t[0])
        resp = out["responses"]
        sweep[f"k{k}"] = {
            "arrival_batch": k,
            "turns": out["info"]["turns"],
            "p50": float(np.percentile(resp, 50)),
            "p99": float(np.percentile(resp, 99)),
            "req_per_s": len(resp) / wall,
        }
        rows.append(csv_row(
            f"scan_fleet_cotenant_k{k}", wall / max(out["info"]["turns"], 1) * 1e6,
            f"p50={sweep[f'k{k}']['p50']:.2f};p99={sweep[f'k{k}']['p99']:.2f};"
            f"rps={sweep[f'k{k}']['req_per_s']:.0f}",
        ))
    return rows, {
        "scenario": "cotenant_shock", "S": S, "sync_every": 4,
        "frozen_mu": True, "sweep": sweep,
    }


def _smoke_point() -> dict:
    """The fixed reduced shape ci.sh tracks: S=4 stacked one-program fleet
    at k=256. Recorded as ``smoke_reference`` by full runs (the committed
    BENCH_fleet.json) and as ``scan_fleet.smoke_point`` by --smoke runs,
    so CI can compare fresh-vs-committed on identical shapes."""
    rate, _ = _fleet_scan_rate(4, 256, 8)
    return {"S": 4, "arrival_batch": 256, "turns": 8, "dec_per_s": rate}


def _isolated_frontend_latency(m: int, n: int, iters: int = 30) -> float:
    """Warm per-call latency of ONE frontend routing its share of ``m``
    decisions through the batched engine (the serving route_view shape)."""
    import jax
    import jax.numpy as jnp

    from repro.core import dispatch as dsp
    from repro.core import policies as pol

    cfg = pol.default_policy_config()
    q = jnp.zeros((n,), jnp.int32)
    mu = jnp.ones((n,), jnp.float32)
    key = jax.random.PRNGKey(0)
    out = dsp.dispatch(pol.PPOT_SQ2, key, q, mu, mu, cfg, m)  # compile
    jax.block_until_ready(out.workers)
    best = float("inf")
    for _ in range(5):  # best-of-5 timed blocks (throttling de-noise)
        t0 = time.time()
        for i in range(iters):
            out = dsp.dispatch(
                pol.PPOT_SQ2, jax.random.fold_in(key, i), q, mu, mu, cfg, m
            )
        jax.block_until_ready(out.workers)
        best = min(best, (time.time() - t0) / iters)
    return best


def _decisions_per_s(smoke: bool) -> tuple[list[str], dict]:
    rows, per_s = [], {}
    iters = 10 if smoke else 30
    for S in S_SWEEP:
        m = B_TOT // S
        t_f = _isolated_frontend_latency(m, N_WORKERS, iters=iters)
        modeled = B_TOT / t_f
        mesh = _on_mesh(S, _fleet_step_rate, m=m,
                        iters=max(iters // 2, 5), sync_every=8)
        per_s[S] = {
            "per_frontend_batch": m,
            "isolated_frontend_latency_ms": t_f * 1e3,
            "modeled_aggregate_dec_per_s": modeled,
            "measured_hostmesh_dec_per_s": (
                mesh["dec_per_s"] if mesh else None
            ),
        }
        rows.append(csv_row(
            f"fleet_decisions_S{S}", t_f / m * 1e6,
            f"modeled={modeled/1e6:.2f}M/s;"
            f"hostmesh={(mesh['dec_per_s']/1e6 if mesh else float('nan')):.2f}M/s",
        ))
    scale8 = per_s[8]["modeled_aggregate_dec_per_s"] / per_s[1]["modeled_aggregate_dec_per_s"]
    rows.append(csv_row(
        "fleet_scaling_claim", 0.0,
        f"S8_vs_S1={scale8:.2f}x;meets_3x={scale8 >= 3.0}",
    ))
    return rows, {
        "by_S": per_s,
        "scaling_S8_vs_S1_modeled": scale8,
        "meets_3x_bar": bool(scale8 >= 3.0),
        "methodology": (
            "same total arrival rate: B_tot=%d decisions per fleet step, "
            "per-frontend share B_tot/S; modeled aggregate = B_tot / "
            "isolated-frontend latency t(B_tot/S) (one machine per frontend, "
            "the paper's deployment); measured_hostmesh = shard_map on S "
            "forced host devices time-sharing this container's cores "
            "(lower bound)" % B_TOT
        ),
    }


def _staleness_sweep(smoke: bool, seed: int = 0) -> tuple[list[str], dict]:
    import jax

    from repro.configs import rosella_sim as RS
    from repro.core import metrics as M
    from repro.core import policies as pol
    from repro.fleet import fleet_lam_hats

    rounds = 12_000 if smoke else 60_000
    speeds = RS.tpch_speed_set(30, seed=seed)
    lam = 0.8 * float(speeds.sum())
    S = 4
    sweep: dict = {}
    rows = []
    base_p99 = base_p50 = None
    settings = [(se, False) for se in SYNC_SWEEP] + [(SYNC_SWEEP[-1], True)]
    for sync_every, herd in settings:
        cfg, params = RS.make_sim(
            pol.PPOT_SQ2, speeds, load=0.8, rounds=rounds, seed=seed,
            n_frontends=S, fleet_sync_every=sync_every,
            fleet_herd_correction=herd,
        )
        import repro.core.simulator as sim

        t0 = time.time()
        final, trace = sim.simulate(cfg, params, jax.random.PRNGKey(seed))
        jax.block_until_ready(trace["now"])
        wall = time.time() - t0
        m = M.analyze(trace, n=cfg.n, warmup_frac=0.3)
        fs = M.fleet_summary_from_trace(
            trace, n_frontends=S, sync_every=sync_every,
            lam_hat_frontends=np.asarray(fleet_lam_hats(final.fleet)),
            lam_true=lam,
        )
        p50 = float(np.percentile(m.response_times, 50))
        p99 = float(np.percentile(m.response_times, 99))
        if sync_every == 1 and not herd:
            base_p50, base_p99 = p50, p99
        key = f"sync{sync_every}" + ("_herd" if herd else "")
        sweep[key] = {
            "sync_every_rounds": sync_every,
            "herd_correction": herd,
            "p50": p50, "p99": p99,
            "p50_inflation": p50 / base_p50 if base_p50 else None,
            "p99_inflation": p99 / base_p99 if base_p99 else None,
            "censored": m.censored,
            "collision_rate": fs["collision_rate"],
            "staleness_gap_mean": fs.get("staleness", {}).get("gap_mean"),
            "lam_calibration_mean_rel_err": fs.get(
                "lam_calibration_rel_err", {}
            ).get("mean"),
        }
        rows.append(csv_row(
            f"fleet_staleness_{key}", wall / rounds * 1e6,
            f"p50={p50:.2f};p99={p99:.2f};collide={fs['collision_rate']:.3f}",
        ))
    return rows, {"S": S, "workload": "fig8 tpch n=30 load=0.8",
                  "rounds": rounds, "lam": lam, "sweep": sweep}


def _s1_parity(smoke: bool, seed: int = 0) -> tuple[list[str], dict]:
    from repro.configs import rosella_sim as RS
    from repro.serving import (
        FleetRouter,
        RosellaRouter,
        SimulatedPool,
        run_fleet_simulation,
        run_simulation,
    )

    speeds = RS.tpch_speed_set(30, seed=seed)
    rate = 0.8 * float(speeds.sum())
    horizon = 200.0 if smoke else 600.0
    batch = 32
    r1 = RosellaRouter(len(speeds), mu_bar=float(speeds.sum()), seed=seed,
                       async_mu=False)
    resp1, _ = run_simulation(
        r1, SimulatedPool(speeds), arrival_rate=rate, horizon=horizon,
        seed=seed, arrival_batch=batch,
    )
    rf = FleetRouter(1, len(speeds), mu_bar=float(speeds.sum()), seed=seed,
                     async_mu=False)
    respf, _, _ = run_fleet_simulation(
        rf, SimulatedPool(speeds), arrival_rate=rate, horizon=horizon,
        seed=seed, arrival_batch=batch, sync_every=1,
    )
    p50_1, p99_1 = np.percentile(resp1, [50, 99])
    p50_f, p99_f = np.percentile(respf, [50, 99])
    d50 = abs(p50_f - p50_1) / p50_1
    d99 = abs(p99_f - p99_1) / p99_1
    bit_equal = bool(np.array_equal(resp1, respf))
    rows = [csv_row(
        "fleet_s1_parity", 0.0,
        f"p50_rel={d50*100:.3f}%;p99_rel={d99*100:.3f}%;bit_equal={bit_equal}",
    )]
    return rows, {
        "workload": "fig8-style serving: tpch n=30 load=0.8",
        "horizon": horizon, "arrival_batch": batch,
        "p50_single": float(p50_1), "p99_single": float(p99_1),
        "p50_fleet": float(p50_f), "p99_fleet": float(p99_f),
        "p50_rel_err": float(d50), "p99_rel_err": float(d99),
        "bit_equal": bit_equal,
        "within_0p5pct": bool(d50 < 0.005 and d99 < 0.005),
    }


def run(smoke: bool = False, json_path: str | None = None):
    rows: list[str] = []
    r0, scan = _scan_fleet(smoke)
    rows += r0
    rb, bsweep = _batch_sweep_cotenant(smoke)
    rows += rb
    smoke_point = _smoke_point()
    if smoke:
        # --smoke runs carry the point for ci.sh to diff against the
        # committed smoke_reference; they skip the PR-3 baseline sections
        # (full-shape measurements, minutes each)
        scan["smoke_point"] = smoke_point
        summary = {
            "config": {
                "smoke": True, "n_workers": N_WORKERS,
                "b_tot_scan": 512, "scan_S_sweep": list(SCAN_S_SWEEP),
            },
            "scan_fleet": scan,
            "batch_sweep_cotenant": bsweep,
        }
    else:
        r1, dec = _decisions_per_s(smoke)
        rows += r1
        r2, stale = _staleness_sweep(smoke)
        rows += r2
        r3, parity = _s1_parity(smoke)
        rows += r3
        summary = {
            "config": {
                "smoke": False, "n_workers": N_WORKERS, "B_tot": B_TOT,
                "b_tot_scan": B_TOT_SCAN,
                "S_sweep": list(S_SWEEP),
                "scan_S_sweep": list(SCAN_S_SWEEP),
                "sync_sweep": list(SYNC_SWEEP),
            },
            "scan_fleet": scan,
            "batch_sweep_cotenant": bsweep,
            "pr3_baseline": {
                "decisions_per_s": dec,
                "staleness_sweep": stale,
                "s1_parity": parity,
            },
        }
    if json_path:
        write_bench("fleet", summary, smoke=smoke,
                    smoke_reference=None if smoke else smoke_point,
                    path=json_path)
        rows.append(csv_row("fleet_bench_json", 0.0, f"wrote={json_path}"))
    return rows, summary


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.out is None:  # smoke runs must not clobber the full-shape record
        name = "BENCH_fleet_smoke.json" if args.smoke else "BENCH_fleet.json"
        args.out = os.path.join(os.path.dirname(__file__), "..", name)
    for r in run(smoke=args.smoke, json_path=os.path.abspath(args.out))[0]:
        print(r)
