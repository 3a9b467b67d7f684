"""Serving-layer execution of compiled scenarios.

``run_workload`` is the HOST loop over a ``ServingWorkload`` — the same
per-turn structure as ``serving/router.run_simulation`` (flush due
completions → one fused ``serve_turn`` → submit fakes/reals → one μ̂
sample), consuming the scenario's pre-materialized arrays instead of
drawing lazily, plus the two membership hooks of churn scenarios:
``router.set_membership`` at mask-change turns (masked table rebuild +
learner cold-start) and the fake-job probe burst at rejoined replicas.

Because the null scenario's workload arrays replay ``run_simulation``'s
exact RandomState sequence and this loop issues the identical router and
pool calls in the identical order, ``run_workload(null)`` is bit-exact to
``run_simulation`` — and for EVERY scenario it is float-for-float equal
to the one-program scan (``serving/scanloop.run_workload_scan``) when
driven with a deterministic (``async_mu=False``) router and a
``SequentialPool`` (tests/test_env.py pins Poisson, MMPP and churn).

``run_scenario`` is the convenience harness the benchmark suite and the
examples drive: build router+pool, run host or scan, return responses +
μ̂ trace + the workload (whose speed/membership trajectories feed the
adaptation-time metric).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import estimator as est
from repro.core import policies as pol
from repro.env.scenario import Scenario, ServingWorkload
from repro.obs import windows as obw
from repro.serving import recovery as rcv
from repro.serving import router as rt
from repro.serving import scanloop


def run_workload(
    router: rt.RosellaRouter,
    pool: rt.SimulatedPool,
    wl: ServingWorkload,
    *,
    fake_cost: float,
    burst_cost: float | None = None,
    recovery: rcv.RecoveryConfig | None = None,
    observe: "obw.ObserveConfig | None" = None,
    decisions=None,  # obs.DecisionTrace — lifecycle event ring (host only)
):
    """Drive the host serving loop over a compiled workload.

    Rejoin probe bursts submit at ``burst_cost`` (default 4×fake_cost =
    the full request cost): they dominate a rejoined worker's fresh
    sample ring, so they must be cost-calibrated with real traffic —
    cheap fake-cost probes would rebuild its μ̂ ~4× high and herd the
    router onto the worker that just came back.

    ``observe`` (an ``obs.ObserveConfig``) folds the SAME jitted
    telemetry step as the scan body once per turn — the window stream in
    ``info["windows"]`` is float-for-float equal to the scan's.
    ``decisions`` (an ``obs.DecisionTrace``) records per-task lifecycle
    events (arrive → place → complete) into the bounded ring.

    Returns ``(response_times, mu_trace, info)`` — the scan loop's
    contract (``info`` carries the turn count and each request's worker
    in ``info["workers"]``; overflow accounting is a scan-only concern,
    reported as zeros here for symmetry).
    """
    if wl.has_faults or recovery is not None:
        # the failure-semantics loop subsumes this one (fault-free +
        # inert recovery reduces to it bit-exactly); keep the fast plain
        # path for the overwhelmingly common fault-free case
        return rcv.run_workload_recovery(
            router, pool, wl, fake_cost=fake_cost, burst_cost=burst_cost,
            recovery=recovery, observe=observe, decisions=decisions,
        )
    if burst_cost is None:
        burst_cost = 4.0 * fake_cost
    T = wl.turns
    k = wl.times.shape[1] if T else 0
    responses: list[np.ndarray] = []
    mu_trace: list[np.ndarray] = []
    placed: list[np.ndarray] = []
    p_done = np.empty(0)
    p_rep = np.empty(0, np.int32)
    p_start = np.empty(0)
    tc = obw.init_carry(observe) if observe is not None else None
    windows: list = []

    for turn in range(T):
        times = wl.times[turn]
        t = float(times[-1])
        pool.set_speeds(wl.speeds[turn])

        # gather completions that happened before this batch, oldest first
        # (identical to run_simulation)
        due = p_done <= t
        comp_w = comp_t = None
        comp_now = t
        if due.any():
            order = np.argsort(p_done[due], kind="stable")
            comp_w = p_rep[due][order]
            comp_t = (p_done - p_start)[due][order]
            comp_now = float(p_done[due].max())
            keep = ~due
            p_done, p_rep, p_start = p_done[keep], p_rep[keep], p_start[keep]

        # membership hook: apply the mask at turn 0 and at change turns —
        # rejoins cold-start the learner BEFORE this turn's completion
        # fold, the same ordering as the scan body
        burst_js = np.empty(0, np.int64)
        if wl.active is not None:
            changed = turn == 0 or not np.array_equal(
                wl.active[turn], wl.active[turn - 1]
            )
            if changed:
                router.set_membership(
                    wl.active[turn], t, rejoin=wl.rejoin[turn]
                )
            if wl.burst is not None and wl.burst.shape[1]:
                bt = wl.burst[turn]
                burst_js = bt[bt >= 0].astype(np.int64)

        # completion flush + benchmark requests + batch route: ONE jit call
        fake_js, js = router.serve_turn(t, k, comp_w, comp_t, comp_now)

        # submissions in fakes → probe burst → reals order (the scan
        # body's concatenation order; insertion sequence must match)
        for sub_js, sub_cost in ((fake_js, fake_cost),
                                 (burst_js, burst_cost)):
            if len(sub_js):
                fs, fd = pool.submit_batch(
                    sub_js, np.full(len(sub_js), t),
                    np.full(len(sub_js), sub_cost),
                )
                p_done = np.concatenate([p_done, fd])
                p_rep = np.concatenate([p_rep, sub_js.astype(np.int32)])
                p_start = np.concatenate([p_start, fs])
        ss, dd = pool.submit_batch(js, times, wl.costs[turn])
        responses.append(dd - times)
        placed.append(js)
        p_done = np.concatenate([p_done, dd])
        p_rep = np.concatenate([p_rep, js.astype(np.int32)])
        p_start = np.concatenate([p_start, ss])
        mu_trace.append(np.asarray(router.mu_front))

        if decisions is not None:
            for i in range(k):
                task = turn * k + i
                decisions.arrive(times[i], task)
                decisions.place(times[i], task, int(js[i]))
                decisions.complete(dd[i], task, int(js[i]))
        if observe is not None:
            tob = obw.plain_turn_obs(
                observe, t=np.float32(times[-1]), resp=dd - times,
                arrivals_k=k, q_view=router.q_view,
                lam_hat=est.lam_hat_ema(router.arr),
                mu_hat=router.learner.mu_hat,
                mu_true=wl.speeds[turn],
                active=(None if wl.active is None
                        else jnp.asarray(wl.active[turn])),
            )
            tc, row, flag = obw.observe_turn_host(observe, tc, tob)
            if bool(flag):
                windows.append(obw.record_from_state(observe, row))

    resp = np.concatenate(responses) if responses else np.empty(0)
    info = {"turns": T, "flush_overflow": 0, "pend_overflow": 0,
            "workers": (np.concatenate(placed).astype(np.int64) if placed
                        else np.empty(0, np.int64))}
    if observe is not None:
        tail = obw.final_partial_record(observe, tc)
        if tail is not None:
            windows.append(tail)
        info["windows"] = windows
    return resp, np.asarray(mu_trace), info


def run_scenario(
    scn: Scenario,
    *,
    policy: str = pol.PPOT_SQ2,
    seed: int = 0,
    arrival_batch: int = 8,
    use_scan: bool = False,
    async_mu: bool = False,
    use_alias: bool = True,
    sequential_pool: bool = False,
    c_window: float = 10.0,
    router: rt.RosellaRouter | None = None,
    pool: rt.SimulatedPool | None = None,
    n_frontends: int = 1,
    sync_every: int = 1,
    herd_correction=False,
    frozen_mu: bool = False,
    recovery: rcv.RecoveryConfig | None = None,
    observe: "obw.ObserveConfig | None" = None,
    obs_sink=None,
    decisions=None,
    chunk_turns: int | None = None,
    pend_cap: int | None = None,
    comp_cap: int | None = None,
):
    """One scenario end to end on the serving layer.

    Builds a ``RosellaRouter`` (μ̄ = baseline capacity) and a pool at the
    baseline speeds, compiles the workload, runs the host loop (or the
    one-program scan with ``use_scan``) and returns a dict with the
    responses, the μ̂ trace, the workload (for adaptation-time analysis)
    and the router/pool (final states). ``async_mu=False`` is the
    deterministic default so scenario runs are reproducible artifacts;
    pass ``sequential_pool=True`` for the exact-parity pool chain.

    ``n_frontends > 1`` composes the scenario with the frontend FLEET on
    the one-program scan (``scanloop.run_fleet_workload_scan``): S
    frontends with stale views, sync cadence ``sync_every`` (in turns),
    per-frontend ``herd_correction`` gains and optionally the frozen-μ̂
    amortized views (``frozen_mu``). Requires ``use_scan=True`` (the fleet
    × env composition is a scan-path program; the host fleet loop has no
    env hooks) and S | arrival_batch.
    """
    speeds0 = np.asarray(scn.speeds, float)
    if n_frontends > 1:
        if recovery is not None:
            raise ValueError(
                "recovery (timeout/retry/speculation) is single-frontend "
                "only for now: the fleet scan carries fault loss "
                "accounting but no re-dispatch machinery"
            )
        if not use_scan:
            raise ValueError(
                "n_frontends > 1 requires use_scan=True: the fleet × env "
                "composition runs on the one-program scan path"
            )
        if router is not None and not isinstance(router, rt.FleetRouter):
            raise ValueError("n_frontends > 1 needs a FleetRouter")
        if router is None:
            router = rt.FleetRouter(
                n_frontends, scn.n, mu_bar=float(speeds0.sum()),
                policy=policy, seed=seed, async_mu=async_mu,
                use_alias=use_alias, c_window=c_window,
                herd_correction=herd_correction,
            )
        if pool is None:
            pool_cls = (
                rt.SequentialPool if sequential_pool else rt.SimulatedPool
            )
            pool = pool_cls(speeds0)
        wl = scn.compile_serving(seed=seed, arrival_batch=arrival_batch)
        wl.partition(n_frontends)  # validate the S | k split up front
        fake_cost = scn.request_cost * 0.25
        resp, mu_trace, info = scanloop.run_fleet_workload_scan(
            router, pool, wl.times, wl.costs, wl.speeds,
            active_np=wl.active, rejoin_np=wl.rejoin, burst_np=wl.burst,
            fake_cost=fake_cost, sync_every=sync_every,
            frozen_mu=frozen_mu, kill_np=wl.kill_at, stall_np=wl.stall_at,
            stall_dur_np=wl.stall_dur, chunk_turns=chunk_turns,
            observe=observe, obs_sink=obs_sink,
        )
        return {
            "responses": resp,
            "mu_trace": mu_trace,
            "info": info,
            "workload": wl,
            "router": router,
            "pool": pool,
        }
    if router is None:
        router = rt.RosellaRouter(
            scn.n, mu_bar=float(speeds0.sum()), policy=policy, seed=seed,
            async_mu=async_mu, use_alias=use_alias, c_window=c_window,
        )
    if pool is None:
        pool_cls = rt.SequentialPool if sequential_pool else rt.SimulatedPool
        pool = pool_cls(speeds0)
    wl = scn.compile_serving(seed=seed, arrival_batch=arrival_batch)
    fake_cost = scn.request_cost * 0.25
    if use_scan:
        resp, mu_trace, info = scanloop.run_workload_scan(
            router, pool, wl.times, wl.costs, wl.speeds,
            active_np=wl.active, rejoin_np=wl.rejoin, burst_np=wl.burst,
            fake_cost=fake_cost, kill_np=wl.kill_at, stall_np=wl.stall_at,
            stall_dur_np=wl.stall_dur, recovery=recovery,
            chunk_turns=chunk_turns, pend_cap=pend_cap, comp_cap=comp_cap,
            observe=observe, obs_sink=obs_sink,
        )
    else:
        resp, mu_trace, info = run_workload(
            router, pool, wl, fake_cost=fake_cost, recovery=recovery,
            observe=observe, decisions=decisions,
        )
    return {
        "responses": resp,
        "mu_trace": mu_trace,
        "info": info,
        "workload": wl,
        "router": router,
        "pool": pool,
    }
