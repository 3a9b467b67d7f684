"""Shared helpers for the figure benchmarks: run a sim config, time it, and
emit ``name,us_per_call,derived`` CSV rows (one per paper table/figure) —
plus the uniform ``BENCH_*.json`` writer (schema version + host/jax/device
provenance) all the suite benchmarks emit through."""
from __future__ import annotations

import json
import platform
import time

import jax
import numpy as np

from repro.core import metrics as M
from repro.core import simulator as sim
from repro.utils.cache import enable_compile_cache

enable_compile_cache()

#: Bump when the shared BENCH envelope changes shape (suite payloads keep
#: their own top-level keys — readers like ci.sh's smoke comparisons are
#: unaffected by the envelope).
BENCH_SCHEMA_VERSION = 1


def bench_provenance() -> dict:
    """Where this artifact was measured: host, python, jax, devices."""
    return {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "hostname": platform.node(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
    }


def write_bench(stem: str, payload: dict, *, smoke: bool = False,
                smoke_reference: dict | None = None,
                path: str | None = None) -> str:
    """Write ``BENCH_<stem>.json`` (committed) or ``BENCH_<stem>_smoke.json``
    (gitignored) with the shared envelope: the suite's payload keys stay
    top-level (existing readers — ci.sh's non-gating smoke comparisons —
    keep working), plus ``schema_version`` + ``provenance``; smoke runs get
    ``smoke: true``, full runs record their reduced-shape
    ``smoke_reference`` for those comparisons."""
    out = dict(payload)
    out["schema_version"] = BENCH_SCHEMA_VERSION
    out["provenance"] = bench_provenance()
    if smoke:
        out["smoke"] = True
    elif smoke_reference is not None:
        out["smoke_reference"] = smoke_reference
    if path is None:
        path = f"BENCH_{stem}_smoke.json" if smoke else f"BENCH_{stem}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    return path


def bench_main(stem: str, run, *, smoke_kw: dict | None = None) -> None:
    """Shared ``__main__`` for the ``(csv_rows, derived)`` figure/table
    benchmarks: print the CSV rows (the historical stdout contract) and
    ALSO publish the uniform ``BENCH_<stem>.json`` envelope. ``--smoke``
    runs the reduced shapes in ``smoke_kw`` and writes the gitignored
    ``BENCH_<stem>_smoke.json`` instead of clobbering the full record."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes; gitignored artifact")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    kw = dict(smoke_kw or {}) if args.smoke else {}
    rows, derived = run(seed=args.seed, **kw)
    for r in rows:
        print(r)
    write_bench(stem, {"csv_rows": list(rows), "derived": derived},
                smoke=args.smoke)


def sustained_series(chunks: "list[dict]", *, warmup: int = 1) -> dict:
    """Sustained-throughput report from the chunk driver's per-chunk
    wall-clock records (``info["chunks"]`` of a ``timing=True`` run):
    dec/s as a TIME SERIES (one point per chunk, jit warmup excluded from
    the sustained figure but kept in the series — the first chunk pays
    compilation), plus the memory high-water samples whose flatness is
    the bounded-memory evidence."""
    chunks = list(chunks)
    out: dict = {
        "n_chunks": len(chunks),
        "warmup_chunks_excluded": min(warmup, max(len(chunks) - 1, 0)),
    }
    if not chunks:
        return out
    body = chunks[out["warmup_chunks_excluded"]:]
    run_s = sum(c["run_s"] for c in body)
    reqs = sum(c["requests"] for c in body)
    decs = [c["requests"] / c["run_s"] for c in chunks if c["run_s"] > 0]
    rss = [c["rss_mb"] for c in chunks]
    out.update(
        requests_total=int(sum(c["requests"] for c in chunks)),
        turns_total=int(sum(c["turns"] for c in chunks)),
        decs_series=[round(d, 1) for d in decs],
        decs_sustained=(reqs / run_s) if run_s > 0 else float("nan"),
        decs_min=min(decs) if decs else float("nan"),
        decs_max=max(decs) if decs else float("nan"),
        wall_s_total=sum(c["gen_s"] + c["run_s"] for c in chunks),
        gen_s_total=sum(c["gen_s"] for c in chunks),
        run_s_total=sum(c["run_s"] for c in chunks),
        rss_mb_series=[round(r, 1) for r in rss],
        rss_mb_peak=max(rss) if rss else float("nan"),
        # growth across the post-warmup chunks: ~0 ⇔ streaming is truly
        # bounded-memory (the committed acceptance check reads this)
        rss_mb_growth=(rss[-1] - rss[out["warmup_chunks_excluded"]]
                       if len(rss) > 1 else 0.0),
    )
    return out


def run_sim(cfg, params, seed: int = 0, warmup_frac: float = 0.3):
    t0 = time.time()
    final, trace = sim.simulate(cfg, params, jax.random.PRNGKey(seed))
    jax.block_until_ready(trace["now"])
    wall = time.time() - t0
    m = M.analyze(trace, n=cfg.n, warmup_frac=warmup_frac)
    return m, trace, wall


def response_stats(m, censor_penalty: float | None = None):
    """Mean/percentiles; censored jobs (never finished in-sim — unbounded
    queues) get reported separately and, if censor_penalty is set, folded in
    at that value (the paper's '>2000ms' bucket)."""
    r = m.response_times
    out = {
        "n": int(m.num_jobs),
        "censored_frac": m.censored / max(m.num_jobs, 1),
    }
    if censor_penalty is not None and m.censored:
        r = np.concatenate([r, np.full(m.censored, censor_penalty)])
    if r.size:
        out.update(
            mean=float(np.mean(r)),
            p5=float(np.percentile(r, 5)),
            p25=float(np.percentile(r, 25)),
            p50=float(np.percentile(r, 50)),
            p75=float(np.percentile(r, 75)),
            p95=float(np.percentile(r, 95)),
        )
    else:
        out.update(mean=float("inf"), p5=0, p25=0, p50=0, p75=0, p95=float("inf"))
    return out


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.2f},{derived}"
