"""Chip smoke test: drive Rosella's main path once on a TPU and check it.

    python chip_smoke.py [--seed N]     # one chip: phases 1-4
    python chip_smoke.py --chips 4      # four chips: the sharded fleet only

Phases (one chip, all in this process):

1. Engine. ``core.dispatch.dispatch`` with policy ``ppot_sq2`` and the
   alias table, no mask, at (n, B) = (64, 4096) and (1024, 4096). The
   compiled program must hold the Pallas kernel (``tpu_custom_call``), and
   ``workers``/``q_after`` must be bit-identical to ``use_kernel=False``.
2. Streamed scan. The load harness's own scenario, router, pool and
   constants (``benchmarks/loadtest.py``: n=64, 128 requests per turn,
   512-turn chunks, pend_cap 8192) through ``repro.load.run_stream_scan``
   for 4 chunks = 262,144 requests, stream-only telemetry. Both overflow
   counters must be 0; the first chunk's time includes compilation.
3. Reference. The first chunk of the same stream through the scan (with
   per-request responses) and through the host loop
   ``env.serving.run_workload`` with ``SequentialPool`` and
   ``async_mu=False``: placements must be equal, responses equal or within
   ``RESP_ATOL``.
4. Failure path. ``crash_storm`` with a ``RecoveryConfig`` on the faulty
   scan and on the host loop: the conservation ledger must balance and the
   two runs must agree under phase 3's rule.

``--chips 4``: S=4 frontends of the one-program fleet scan sharded over
``Mesh(jax.devices()[:4], ("sched",))`` against the same run stacked on
one device (``mesh=None``): responses and placements must be bit-equal,
the μ̂ trace equal within ``FLEET_MU_RTOL``.

Traffic is generated from ``--seed``. The script fails, printing no
result, when JAX finds no TPU. Its last line is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

ENGINE_SHAPES = ((64, 4096), (1024, 4096))  # (n workers, B tasks per call)
STREAM_CHUNKS = 4
FLEET_S = 4
FLEET_TURNS = 64
FLEET_PEND_CAP = 2048  # in-flight bound at 0.8 load (512 overflowed)
#: The sharded fleet compiles each frontend's learner fold for one row per
#: chip, the stacked one for S rows on one chip; XLA may order their f32
#: sums differently, so μ̂ may differ by a few f32 ulps (measured on a
#: four-chip v5e: up to 1.9e-6 on a trace whose largest μ̂ is about 73).
#: The bound, relative to that largest value, is about 10 ulps of it.
#: Placements and responses must still be equal.
FLEET_MU_RTOL = 1e-6
#: Largest response difference (simulated seconds) accepted between the
#: scan and the host loop when they are not bit-equal. The scan's f64 event
#: clock is emulated by XLA on the TPU, with about 2^-44 relative error per
#: operation (measured on a v5e: 6.5e-11 s on clocks near 1.3e3 s), while
#: the host's runs in IEEE f64 numpy. A microsecond of simulated time is
#: far below any response the scheduler produces (tenths of a second and
#: up) and about 1000x the emulation error on clocks up to 2e4 s.
RESP_ATOL = 1e-6


class Checks:
    """Collects failed checks so every phase still reports."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failed.append(what)
            print(f"  FAILED: {what}", flush=True)
        return ok


def _max_diff(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, float), np.asarray(b, float)
    if a.shape != b.shape or not np.array_equal(np.isnan(a), np.isnan(b)):
        return float("inf")
    ok = ~np.isnan(a)
    return float(np.max(np.abs(a[ok] - b[ok]), initial=0.0))


def compare_runs(check, tag, scan, host) -> None:
    """Phase 3/4 rule: equal placements; responses bit-equal or within
    RESP_ATOL; μ̂ traces reported."""
    import numpy as np

    (resp_s, mu_s, info_s), (resp_h, mu_h, info_h) = scan, host
    w_eq = np.array_equal(info_s["workers"], info_h["workers"])
    r_bit = np.array_equal(resp_s, resp_h, equal_nan=True)
    r_diff = _max_diff(resp_s, resp_h)
    mu_bit = np.array_equal(mu_s, mu_h)
    print(f"  {tag}: {resp_s.size} requests, placements equal={w_eq}, "
          f"responses bit-equal={r_bit} max|diff|={r_diff!r} "
          f"(atol {RESP_ATOL}), mu_hat trace bit-equal={mu_bit} "
          f"max|diff|={_max_diff(mu_s, mu_h)!r}", flush=True)
    check(info_s["workers"].size == resp_s.size > 0,
          f"{tag}: placements cover every request")
    check(w_eq, f"{tag}: placements equal")
    check(r_bit or r_diff <= RESP_ATOL, f"{tag}: responses within atol")


def phase_engine(check, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import dispatch as dsp
    from repro.core import policies as pol

    cfg = pol.default_policy_config()
    for n, B in ENGINE_SHAPES:
        k_mu, k_q, k_draw = jax.random.split(jax.random.PRNGKey(seed + n), 3)
        mu = jax.random.uniform(k_mu, (n,), minval=0.1, maxval=4.0)
        q = jax.random.randint(k_q, (n,), 0, 64, dtype=jnp.int32)
        table = dsp.build_alias_table(mu)
        args = (pol.PPOT_SQ2, k_draw, q, mu, mu, cfg, B)
        t0 = time.perf_counter()
        text = dsp.dispatch.lower(*args, table=table).compile().as_text()
        t_compile = time.perf_counter() - t0
        kernel = "tpu_custom_call" in text
        res = dsp.dispatch(*args, table=table)
        ref = dsp.dispatch(*args, table=table, use_kernel=False)
        w_eq = np.array_equal(res.workers, ref.workers)
        q_eq = np.array_equal(res.q_after, ref.q_after)
        w = np.asarray(res.workers)
        print(f"engine n={n} B={B}: tpu_custom_call={kernel} "
              f"compile_s={t_compile!r} workers bit-equal={w_eq} "
              f"q_after bit-equal={q_eq} placed={int((w >= 0).sum())}",
              flush=True)
        check(kernel, f"engine n={n}: kernel in the compiled program")
        check(w_eq and q_eq, f"engine n={n}: kernel == use_kernel=False")
        check(bool(((w >= 0) & (w < n)).all())
              and int(np.asarray(res.q_after).sum())
              == int(np.asarray(q).sum()) + B,
              f"engine n={n}: every task placed and folded back")


def phase_stream(check, seed: int) -> None:
    import numpy as np

    from benchmarks import loadtest as lt

    info, _, _ = lt.run_stream(lt.HORIZON_FULL, seed=seed,
                               max_chunks=STREAM_CHUNKS)
    chunks = info["chunks"]
    requests = sum(c["requests"] for c in chunks)
    run_s = [c["run_s"] for c in chunks]
    windows = info["windows"]
    print(f"stream: {requests} requests in {len(chunks)} chunks of "
          f"{lt.CHUNK_TURNS} turns x {lt.ARRIVAL_BATCH}, flush_overflow="
          f"{info['flush_overflow']} pend_overflow={info['pend_overflow']} "
          f"windows={len(windows)}", flush=True)
    print(f"  chunk run_s={run_s!r} (chunk 0 includes compilation: "
          f"compile_plus_run_s={run_s[0]!r}, warm chunks total "
          f"{sum(run_s[1:])!r})", flush=True)
    check(len(chunks) == STREAM_CHUNKS
          and requests == STREAM_CHUNKS * lt.CHUNK_TURNS * lt.ARRIVAL_BATCH,
          "stream: every chunk ran")
    check(info["flush_overflow"] == 0 and info["pend_overflow"] == 0,
          "stream: no overflow")
    p99 = np.array([w["p99"] for w in windows], float)
    thr = np.array([w["throughput"] for w in windows], float)
    check(len(windows) == info["turns"] // lt.WINDOW_TURNS
          and bool(np.isfinite(p99).all()) and bool((thr > 0).all()),
          "stream: every telemetry window finite")


def phase_reference(check, seed: int) -> None:
    from benchmarks import loadtest as lt
    from repro import obs
    from repro.env.serving import run_workload
    from repro.load import ScenarioStream, run_stream_scan
    from repro.serving import router as rt

    scn = lt.make_scenario(lt.HORIZON_FULL)
    stream = ScenarioStream(scn, seed=seed, arrival_batch=lt.ARRIVAL_BATCH)
    wl = next(stream.chunks(lt.CHUNK_TURNS))
    fake_cost = scn.request_cost * 0.25
    t0 = time.perf_counter()
    scan = run_stream_scan(
        lt.make_router(seed), rt.SimulatedPool(lt._speeds()), [wl],
        fake_cost=fake_cost, pend_cap=lt.PEND_CAP, comp_cap=lt.COMP_CAP,
        observe=obs.ObserveConfig(window_turns=lt.WINDOW_TURNS,
                                  emit_responses=True),
    )
    t1 = time.perf_counter()
    host = run_workload(lt.make_router(seed), rt.SequentialPool(lt._speeds()),
                        wl, fake_cost=fake_cost)
    t2 = time.perf_counter()
    print(f"reference: first {wl.turns} turns, scan {t1 - t0!r} s, "
          f"host loop {t2 - t1!r} s", flush=True)
    check(scan[2]["flush_overflow"] == 0 and scan[2]["pend_overflow"] == 0,
          "reference: no overflow")
    compare_runs(check, "reference", scan, host)


def phase_failure(check, seed: int) -> None:
    from repro import env
    from repro.core import metrics
    from repro.env.serving import run_scenario
    from repro.serving import RecoveryConfig

    rc = RecoveryConfig(timeout_mult=8, retry_budget=2, retry_cap=4,
                        spec_cap=2)
    out = {}
    for use_scan in (True, False):
        t0 = time.perf_counter()
        o = run_scenario(env.make("crash_storm"), use_scan=use_scan,
                         recovery=rc, sequential_pool=True, seed=seed)
        out[use_scan] = (o["responses"], o["mu_trace"], o["info"])
        print(f"crash_storm use_scan={use_scan}: {time.perf_counter() - t0!r}"
              f" s, ledger={o['info']['ledger']}", flush=True)
    led, led_h = out[True][2]["ledger"], out[False][2]["ledger"]
    ok, residuals = metrics.check_conservation(led)
    check(led["conserved"] and ok, f"crash_storm: ledger balanced {residuals}")
    # counts must match exactly; the one clock-derived entry
    # (max_clean_service) under the responses' rule
    check(led.keys() == led_h.keys() and all(
        abs(v - led_h[key]) <= RESP_ATOL if isinstance(v, float)
        else v == led_h[key] for key, v in led.items()),
        "crash_storm: ledger == host")
    compare_runs(check, "crash_storm", out[True], out[False])


def phase_fleet(check, seed: int, chips: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmarks import loadtest as lt
    from repro.serving import (FleetRouter, SequentialPool,
                               run_fleet_simulation_scan)

    speeds = lt._speeds()
    rate = 0.8 * float(speeds.sum())
    k = lt.ARRIVAL_BATCH
    runs = {}
    for name, mesh in (("sharded", Mesh(np.array(jax.devices()[:chips]),
                                        ("sched",))),
                       ("stacked", None)):
        router = FleetRouter(FLEET_S, len(speeds), mu_bar=float(speeds.sum()),
                             seed=seed, async_mu=False)
        t0 = time.perf_counter()
        runs[name] = run_fleet_simulation_scan(
            router, SequentialPool(speeds), arrival_rate=rate,
            horizon=FLEET_TURNS * k / rate, seed=seed, arrival_batch=k,
            sync_every=8, frozen_mu=True, pend_cap=FLEET_PEND_CAP,
            mesh=mesh,
        )
        info = runs[name][2]
        print(f"fleet S={FLEET_S} {name} over {chips if mesh else 1} "
              f"device(s): {info['turns']} turns x {k}, "
              f"{time.perf_counter() - t0!r} s (compile included), "
              f"overflow={info['flush_overflow']}/{info['pend_overflow']}",
              flush=True)
    (rs, ms, is_), (rn, mn, in_) = runs["sharded"], runs["stacked"]
    eq = {"responses": np.array_equal(rs, rn),
          "workers": np.array_equal(is_["workers"], in_["workers"]),
          "mu_trace": np.array_equal(ms, mn)}
    mu_diff = _max_diff(ms, mn)
    mu_scale = float(np.max(np.abs(mn), initial=0.0))
    turns = np.nonzero((ms != mn).any(axis=1))[0]
    print(f"  sharded vs stacked bit-equal: {eq} max|resp diff|="
          f"{_max_diff(rs, rn)!r}; mu_hat max|diff|={mu_diff!r} of "
          f"max|mu_hat| {mu_scale!r}, differs on {turns.size} of "
          f"{ms.shape[0]} turns, first {turns[:8].tolist()}", flush=True)
    check(rs.size > 0 and bool(np.isfinite(rs).all()),
          "fleet: every request served")
    check(eq["responses"] and eq["workers"],
          "fleet: sharded responses and placements == stacked")
    check(mu_diff <= FLEET_MU_RTOL * mu_scale,
          "fleet: sharded mu_hat == stacked within f32 rounding")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the S=4 fleet sharded over four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.utils.cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache} "
          f"({n_cached} entries)", flush=True)

    check = Checks()
    t_start = time.perf_counter()
    if args.chips == 4:
        phase_fleet(check, args.seed, args.chips)
    else:
        for phase in (phase_engine, phase_stream, phase_reference,
                      phase_failure):
            t0 = time.perf_counter()
            phase(check, args.seed)
            print(f"  [{phase.__name__} {time.perf_counter() - t0!r} s]",
                  flush=True)
    print(f"total {time.perf_counter() - t_start!r} s", flush=True)
    if check.failed:
        print("chip_smoke: FAILED: " + "; ".join(check.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
