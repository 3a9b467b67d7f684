"""Trace-scale streaming load harness → BENCH_loadtest.json.

Pushes a million-request, multi-simulated-hour cluster-trace workload
through the one-program serving scan in bounded memory and publishes the
paper-facing throughput evidence: sustained dec/s as a per-chunk time
series (warmup excluded), whole-horizon p50/p99/p999 from the folded
window histograms, λ̂-calibration over the horizon, and the RSS
high-water series whose flatness demonstrates the streaming memory model.

Composition (everything landed in PRs 6–8, composed here):
  * ``repro.load.ScenarioStream`` lazily materializes an Azure-shaped
    trace (``repro.load.traces.AzureLikeTrace``: diurnal × burst-overlay
    arrivals, lognormal costs) chunk by chunk — the host never holds the
    full trace;
  * ``repro.load.run_stream_scan`` drives the chunks through the scan
    with the donated carry (router, pending set, telemetry) crossing
    chunk boundaries device-side;
  * stream-only telemetry (``ObserveConfig(emit_responses=False)``) +
    ``JsonlSink`` keep the live set to one chunk of xs plus the window
    records (``loadtest_windows.jsonl``, gitignored);
  * ``benchmarks.common.sustained_series`` + ``core.metrics
    .calibration_report`` reduce the chunk records and window stream.

Also includes the arrival_batch-k sweep under volatility (k ∈ {8…512} ×
{cotenant_shock, flash_crowd}) — the granularity/latency frontier of the
batched router, completing PR 6's partial sweep.

Usage:
  PYTHONPATH=src:. python benchmarks/loadtest.py            # full, ≥1M req
  PYTHONPATH=src:. python benchmarks/loadtest.py --smoke    # ~100k req
"""
from __future__ import annotations

import argparse
import itertools

import numpy as np

from benchmarks import common
from repro import obs
from repro.core import metrics as M
from repro.env.scenario import Scenario
from repro.load import AzureLikeTrace, ScenarioStream, run_stream_scan
from repro.serving import router as rt

#: 64 heterogeneous workers: 8 tiles of a fast/medium/slow pattern
#: (total capacity 76 cost-units/s — the BASE_SPEEDS idea at 12.8× scale).
SPEED_TILE = (2.0, 2.0, 1.0, 1.0, 0.5, 1.5, 1.0, 0.5)
N_TILES = 8
RATE = 40.0  # base arrival rate; the Azure shape averages ~1.22× this
# (burst overlay duty cycle), so realized λ̄ ≈ 49 req/s — utilization 0.64
# mean and ~0.90 at the diurnal peak (40 × 1.4 × 1.22 ≈ 68 vs capacity
# 76): heavily loaded but stable, with 3× burst epochs as transient
# overload the pending set absorbs
ARRIVAL_BATCH = 128
CHUNK_TURNS = 512  # ×128 req/turn = 65,536 requests per compiled chunk
PEND_CAP = 8192  # in-flight bound: burst epochs (3× the diurnal-peak rate
# ≈ 168 req/s vs capacity 76) backlog thousands of requests over their
# ~15s dwell before the calm epoch drains them; 8k slots absorb the
# worst observed burst-on-peak backlog with ~2× headroom
COMP_CAP = 512  # post-burst drains complete > 256 requests per turn
HORIZON_FULL = 20_600.0  # ≈ 5.7 simulated hours ⇒ ≥ 1.0M requests
HORIZON_SMOKE = 2_060.0  # ≈ 100k requests
WINDOW_TURNS = 64  # 8,192 requests per telemetry window


def _speeds() -> np.ndarray:
    return np.tile(np.asarray(SPEED_TILE, float), N_TILES)


def make_scenario(horizon: float) -> Scenario:
    return Scenario(
        name="azure_like_load",
        speeds=tuple(_speeds()),
        rate=RATE,
        horizon=horizon,
        arrivals=AzureLikeTrace(period=3600.0, depth=0.4, burst_factor=3.0,
                                dwell=(120.0, 15.0), cost_sigma=1.2),
        description="Azure-shaped streaming load (diurnal × bursts, "
                    "lognormal costs) on 64 heterogeneous workers",
    )


def make_router(seed: int = 0) -> rt.RosellaRouter:
    """The harness's router: PPoT-SQ(2) on the alias probe stream, μ̂
    flipped at every completion flush (deterministic, ``async_mu=False``)."""
    speeds = _speeds()
    return rt.RosellaRouter(
        len(speeds), mu_bar=float(speeds.sum()), policy="ppot_sq2",
        seed=seed, async_mu=False, use_alias=True, c_window=10.0,
    )


def run_stream(horizon: float, *, seed: int = 0,
               windows_path: str | None = None,
               max_chunks: int | None = None):
    """One streamed load run; returns (info, ocfg, scn). ``max_chunks``
    stops after that many full chunks of ``CHUNK_TURNS`` turns (a short
    run without the horizon's partial tail chunk, which would compile a
    program of its own)."""
    scn = make_scenario(horizon)
    router = make_router(seed)
    pool = rt.SimulatedPool(_speeds())
    stream = ScenarioStream(scn, seed=seed, arrival_batch=ARRIVAL_BATCH)
    chunks = (stream if max_chunks is None else
              itertools.islice(stream.chunks(CHUNK_TURNS), max_chunks))
    ocfg = obs.ObserveConfig(window_turns=WINDOW_TURNS,
                             emit_responses=False)
    sink = obs.JsonlSink(windows_path) if windows_path else None
    try:
        _, _, info = run_stream_scan(
            router, pool, chunks, chunk_turns=CHUNK_TURNS,
            fake_cost=scn.request_cost * 0.25, pend_cap=PEND_CAP,
            comp_cap=COMP_CAP, observe=ocfg, obs_sink=sink, timing=True,
        )
    finally:
        if sink is not None:
            sink.close()
    return info, ocfg, scn


def _window_series(windows: "list[dict]") -> dict:
    """Compact per-window series for the committed artifact (full hists
    live in the JSONL sink, not the BENCH json)."""
    def col(k, nd=4):
        return [round(float(w[k]), nd) for w in windows]

    return {
        "t_end": col("t_end", 2),
        "p50": col("p50"),
        "p99": col("p99"),
        "p999": col("p999"),
        "lam_calibration": col("lam_calibration"),
        "throughput": col("throughput", 2),
        "q_mean": col("q_mean", 2),
    }


def batch_sweep(*, smoke: bool = False, seed: int = 0) -> "list[dict]":
    """arrival_batch-k sweep under volatility: the batched router amortizes
    per-turn dispatch over k requests (throughput ↑) but reacts to the
    environment once per turn (granularity ↓) — this records that frontier
    on the two volatile scenarios PR 6 left uncovered."""
    import time as _time

    from repro import env
    from repro.env.serving import run_scenario

    ks = (8, 32, 128, 512) if not smoke else (8, 128)
    rows = []
    for name in ("cotenant_shock", "flash_crowd"):
        for k in ks:
            scn = env.make(name, rate=RATE, speeds=tuple(_speeds()))
            t0 = _time.time()
            out = run_scenario(
                scn, use_scan=True, arrival_batch=k, seed=seed,
                chunk_turns=None,  # auto
                comp_cap=max(512, 4 * k),  # post-burst drains complete more
                # than SERVE_COMP_CAP=256 requests in one turn at this rate
                # (flash_crowd at k=512 drains >2·k in the first calm turn)
            )
            wall = _time.time() - t0
            r = np.asarray(out["responses"], float)
            rows.append({
                "scenario": name,
                "arrival_batch": k,
                "requests": int(r.size),
                "turns": int(out["info"]["turns"]),
                "decs_warm_excl": float(r.size / wall),
                "wall_s": wall,
                "p50": float(np.percentile(r, 50)) if r.size else None,
                "p99": float(np.percentile(r, 99)) if r.size else None,
                "mean": float(r.mean()) if r.size else None,
            })
            print(f"  sweep {name} k={k}: {r.size} req, "
                  f"p99={rows[-1]['p99']:.2f}, {wall:.1f}s")
    return rows


def seed_sweep(n_seeds: int, *, horizon: float = HORIZON_SMOKE) -> dict:
    """Variance bands across seeds: the streamed harness re-run at
    seeds 0..n-1 (smoke horizon — the full million-request shape is a
    single pinned-seed headline; the spread question is answered at the
    ~100k-request shape where n runs are tractable).  Publishes
    mean ± spread for sustained dec/s and the folded-histogram
    p50/p99, closing ROADMAP item 4(c)'s 'sweep seeds and publish
    variance bands'."""
    per_seed = []
    for s in range(n_seeds):
        info, ocfg, _ = run_stream(horizon, seed=s)
        sus = common.sustained_series(info["chunks"], warmup=1)
        calib = M.calibration_report(ocfg, info["windows"],
                                     warmup_windows=2)
        row = {
            "seed": s,
            "requests_total": sus["requests_total"],
            "decs_sustained": round(sus["decs_sustained"], 1),
            "p50": round(calib["p50"], 4),
            "p99": round(calib["p99"], 4),
        }
        per_seed.append(row)
        print(f"  seed {s}: {row['requests_total']} req, "
              f"{row['decs_sustained']:.0f} dec/s, p50={row['p50']:.3f}, "
              f"p99={row['p99']:.3f}")

    def band(key):
        v = np.asarray([r[key] for r in per_seed], float)
        return {
            "mean": round(float(v.mean()), 4),
            "std": round(float(v.std(ddof=1)) if len(v) > 1 else 0.0, 4),
            "min": round(float(v.min()), 4),
            "max": round(float(v.max()), 4),
        }

    return {
        "n_seeds": n_seeds,
        "horizon_s": horizon,
        "per_seed": per_seed,
        "bands": {k: band(k) for k in ("decs_sustained", "p50", "p99")},
    }


def run(*, smoke: bool = False, seed: int = 0, sweep: bool = True,
        windows_path: str | None = None,
        smoke_reference: dict | None = None) -> dict:
    horizon = HORIZON_SMOKE if smoke else HORIZON_FULL
    print(f"loadtest: streaming {'smoke' if smoke else 'full'} horizon "
          f"{horizon:.0f}s (n=64, k={ARRIVAL_BATCH}, "
          f"chunk_turns={CHUNK_TURNS})")
    info, ocfg, scn = run_stream(horizon, seed=seed,
                                 windows_path=windows_path)
    windows = info["windows"]
    sustained = common.sustained_series(info["chunks"], warmup=1)
    calib = M.calibration_report(ocfg, windows, warmup_windows=2)
    payload = {
        "workload": {
            "shape": "azure_like",
            "n_workers": scn.n,
            "capacity": float(_speeds().sum()),
            "base_rate": RATE,
            "horizon_s": horizon,
            "arrival_batch": ARRIVAL_BATCH,
            "chunk_turns": CHUNK_TURNS,
            "pend_cap": PEND_CAP,
            "comp_cap": COMP_CAP,
            "window_turns": ocfg.window_turns,
            "stream_only": True,
            "trace_dropped": info.get("trace_dropped", 0),
        },
        "requests_total": sustained["requests_total"],
        "sustained": sustained,
        "calibration": calib,
        "windows": _window_series(windows),
        "peak_rss_mb": obs.peak_rss_mb(),
    }
    print(f"  {sustained['requests_total']} requests, sustained "
          f"{sustained['decs_sustained']:.0f} dec/s, p99={calib['p99']:.2f}, "
          f"peak RSS {payload['peak_rss_mb']:.0f} MB "
          f"(growth {sustained['rss_mb_growth']:.1f} MB)")
    if sweep:
        payload["batch_sweep"] = batch_sweep(smoke=smoke, seed=seed)
    common.write_bench("loadtest", payload, smoke=smoke,
                       smoke_reference=smoke_reference)
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="~100k-request run (gitignored artifact)")
    ap.add_argument("--no-sweep", action="store_true",
                    help="skip the arrival_batch sweep")
    ap.add_argument("--windows-out", default="loadtest_windows.jsonl",
                    help="JSONL window-stream sink path ('' to disable)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=0, metavar="N",
                    help="run the seed-variance sweep at seeds 0..N-1 and "
                         "merge it into the committed BENCH_loadtest.json "
                         "(other keys untouched); skips the single-seed run")
    args = ap.parse_args()
    if args.seeds:
        # standalone mode: update only the seed_sweep section of the
        # committed artifact — the million-request headline keys stay as
        # measured by the last full run
        import json as _json

        print(f"loadtest: seed sweep x{args.seeds} at smoke horizon")
        sweep_doc = seed_sweep(args.seeds)
        b = sweep_doc["bands"]
        print(f"  bands: dec/s {b['decs_sustained']['mean']:.0f}"
              f"±{b['decs_sustained']['std']:.0f}, "
              f"p99 {b['p99']['mean']:.3f}±{b['p99']['std']:.3f}")
        try:
            with open("BENCH_loadtest.json") as f:
                doc = _json.load(f)
        except FileNotFoundError:
            doc = {"schema_version": common.BENCH_SCHEMA_VERSION}
        doc["seed_sweep"] = sweep_doc
        doc["provenance"] = common.bench_provenance()
        with open("BENCH_loadtest.json", "w") as f:
            _json.dump(doc, f, indent=1)
        print("wrote BENCH_loadtest.json (seed_sweep merged)")
        raise SystemExit(0)
    smoke_ref = None
    if not args.smoke:
        # full runs embed a reduced-shape reference measured on the same
        # host so ci.sh's non-gating smoke can compare like for like
        print("loadtest: measuring smoke_reference first")
        ref_info, _, _ = run_stream(HORIZON_SMOKE, seed=args.seed)
        ref = common.sustained_series(ref_info["chunks"], warmup=1)
        smoke_ref = {
            "decs_sustained": ref["decs_sustained"],
            "requests_total": ref["requests_total"],
        }
    run(smoke=args.smoke, seed=args.seed, sweep=not args.no_sweep,
        windows_path=args.windows_out or None,
        smoke_reference=smoke_ref)
