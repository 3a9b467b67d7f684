"""Failure semantics across the three execution layers: fault injection
(crash / blackout / grey degradation), timeout + retry re-dispatch,
speculative re-execution, and the task-conservation ledger.

The contract under test (README "Failure semantics"):

* zero-fault runs with an inert ``RecoveryConfig`` are BIT-exact to the
  plain paths on host and scan — the recovery loop is a strict superset;
* every fault scenario is float-for-float identical between the host
  recovery loop and the one-program faulty scan (responses, μ̂ trace,
  and the full conservation ledger);
* the ledger CONSERVES under arbitrary fault schedules and retry
  budgets: every task completes or is lost, every launched copy
  completes or is killed;
* dirty completions (stall-stretched, timed-out, killed-adjacent) never
  reach the μ̂ learner;
* graceful churn departures DRAIN (nothing lost), crashes KILL — on the
  chain simulator and the serving layers alike;
* pending-set overflow is never silent: the scan raises by default and
  auto-sizes ``pend_cap`` from the workload bound.
"""
import numpy as np
import pytest

import jax

from repro import env
from repro.core import metrics
from repro.core import simulator as sim
from repro.env import scenario as scn_mod
from repro.env.serving import run_scenario
from repro.serving import (
    INERT_RECOVERY,
    RecoveryConfig,
    RosellaRouter,
    SequentialPool,
    run_workload_scan,
)

RECOVERY = RecoveryConfig(
    timeout_mult=8.0, retry_budget=2, retry_cap=4, spec_cap=2,
    spec_ratio=3.0,
)
FAULT_SCENARIOS = ["crash_storm", "blackout", "grey_failure"]


def _run(name, *, use_scan, recovery=None, n_frontends=1, seed=0, **mk):
    return run_scenario(
        env.make(name, **mk), use_scan=use_scan, sequential_pool=True,
        arrival_batch=8, seed=seed, recovery=recovery,
        n_frontends=n_frontends,
    )


# ---------------------------------------------------------------------------
# Zero-fault parity: recovery machinery must cost nothing when unused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["null", "churn"])
def test_inert_recovery_bit_exact_host(name):
    """The host recovery loop with an inert config (no timeouts, no
    retries, no speculation, no faults) replays the plain host loop
    bit-for-bit — responses and μ̂ trace."""
    a = _run(name, use_scan=False)
    b = _run(name, use_scan=False, recovery=INERT_RECOVERY)
    np.testing.assert_array_equal(a["responses"], b["responses"])
    np.testing.assert_array_equal(a["mu_trace"], b["mu_trace"])
    led = b["info"]["ledger"]
    assert led["lost_tasks"] == 0 and led["conserved"]


@pytest.mark.parametrize("name", ["null", "churn"])
def test_inert_recovery_bit_exact_scan(name):
    """Same inert-superset property on the one-program scan, plus
    host-vs-scan equality of the faulty path itself."""
    a = _run(name, use_scan=True)
    b = _run(name, use_scan=True, recovery=INERT_RECOVERY)
    h = _run(name, use_scan=False, recovery=INERT_RECOVERY)
    np.testing.assert_array_equal(a["responses"], b["responses"])
    np.testing.assert_array_equal(a["mu_trace"], b["mu_trace"])
    np.testing.assert_array_equal(h["responses"], b["responses"])
    np.testing.assert_array_equal(h["mu_trace"], b["mu_trace"])
    assert h["info"]["ledger"] == b["info"]["ledger"]


# ---------------------------------------------------------------------------
# Host vs scan parity on every fault scenario, recovery fully armed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", FAULT_SCENARIOS)
def test_fault_host_scan_parity(name):
    """Crash storms, blackouts and grey failures with timeouts, retries
    AND speculation enabled: the host recovery loop and the faulty scan
    agree float-for-float on responses (NaN = lost), the μ̂ trace and
    every ledger entry — and the books balance."""
    h = _run(name, use_scan=False, recovery=RECOVERY)
    s = _run(name, use_scan=True, recovery=RECOVERY)
    np.testing.assert_array_equal(h["responses"], s["responses"])
    np.testing.assert_array_equal(h["mu_trace"], s["mu_trace"])
    assert h["info"]["workers"].size == h["responses"].size
    np.testing.assert_array_equal(h["info"]["workers"], s["info"]["workers"])
    lh, ls = h["info"]["ledger"], s["info"]["ledger"]
    assert lh == ls
    ok, residuals = metrics.check_conservation(ls)
    assert ok, residuals
    assert s["info"]["flush_overflow"] == 0
    assert s["info"]["pend_overflow"] == 0


def test_retry_and_backup_placements_host_scan_parity():
    """The faulty scan hands back each turn's retry and backup copies,
    their workers and tasks (-1 where a slot launched nothing), equal to
    the host recovery loop's on a crash storm; one per launched copy in the
    ledger.
    With the inert config both are zero-width and the scan stays
    bit-equal to the plain one."""
    h = _run("crash_storm", use_scan=False, recovery=RECOVERY, seed=1)
    s = _run("crash_storm", use_scan=True, recovery=RECOVERY, seed=1)
    led = s["info"]["ledger"]
    n = s["mu_trace"].shape[1]
    for kind, launched in (("retry", led["n_retries"]),
                           ("spec", led["n_spec"])):
        cap = getattr(RECOVERY, kind + "_cap")
        for key, bound in ((kind + "_workers", n),
                           (kind + "_tasks", led["n_tasks"])):
            hw, sw = h["info"][key], s["info"][key]
            assert sw.shape == hw.shape == (s["info"]["turns"], cap)
            np.testing.assert_array_equal(hw, sw)
            assert (sw >= 0).sum() == launched > 0
            assert sw.max() < bound  # a worker of the cluster, a task
    plain = _run("crash_storm", use_scan=True, seed=1)
    inert = _run("crash_storm", use_scan=True, recovery=INERT_RECOVERY,
                 seed=1)
    for key in ("retry_workers", "spec_workers", "retry_tasks",
                "spec_tasks"):
        assert inert["info"][key].shape[1] == 0
    np.testing.assert_array_equal(plain["responses"], inert["responses"])
    np.testing.assert_array_equal(plain["mu_trace"], inert["mu_trace"])


def test_retry_rescues_crash_losses():
    """The point of re-dispatch: without recovery a crash storm loses
    every killed in-flight task; with timeout+retry nearly all of them
    complete (a copy killed in the horizon's last turns can stay lost —
    there is no turn left to re-place it)."""
    bare = _run("crash_storm", use_scan=True)
    armed = _run("crash_storm", use_scan=True, recovery=RECOVERY)
    lb, la = bare["info"]["ledger"], armed["info"]["ledger"]
    assert lb["lost_tasks"] > 0 and lb["copies_real_killed"] > 0
    assert la["lost_tasks"] < lb["lost_tasks"]
    assert la["lost_tasks"] <= 1
    assert la["n_retries"] > 0
    rep = metrics.fault_report(armed["responses"], la, horizon=360.0)
    assert rep["conserved"]
    assert rep["retry_amplification"] > 1.0
    assert rep["throughput"] >= rep["goodput"]


# ---------------------------------------------------------------------------
# Conservation under random fault schedules and retry budgets
# ---------------------------------------------------------------------------


def test_conservation_random_fault_schedules():
    """Property sweep: random crash/blackout schedules × random retry
    budgets/timeout multipliers — the ledger conserves on every draw and
    matches between host and scan (the scan keeps a fixed retry_cap so
    all draws share one compiled program)."""
    rng = np.random.RandomState(7)
    for trial in range(6):
        events = tuple(
            (float(rng.uniform(5.0, 70.0)), int(rng.randint(5)),
             float(rng.uniform(4.0, 25.0)),
             "crash" if rng.rand() < 0.5 else "blackout")
            for _ in range(rng.randint(2, 5))
        )
        rc = RecoveryConfig(
            timeout_mult=float(rng.choice([4.0, 8.0, 16.0, np.inf])),
            retry_budget=int(rng.randint(0, 4)),
            retry_cap=4,
            spec_cap=int(rng.randint(0, 3)),
        )
        scn = scn_mod.Scenario(
            f"prop{trial}", speeds=(0.25, 0.5, 1.0, 2.0, 1.0), rate=3.0,
            horizon=90.0, faults=env.FaultSchedule(events=events),
        )
        h = run_scenario(scn, use_scan=False, sequential_pool=True,
                         arrival_batch=8, seed=trial, recovery=rc)
        s = run_scenario(scn, use_scan=True, sequential_pool=True,
                         arrival_batch=8, seed=trial, recovery=rc)
        lh, ls = h["info"]["ledger"], s["info"]["ledger"]
        assert lh == ls, (trial, events)
        ok, residuals = metrics.check_conservation(ls)
        assert ok, (trial, events, residuals)
        np.testing.assert_array_equal(h["responses"], s["responses"])
        comp = np.isfinite(s["responses"]).sum()
        assert comp == ls["completed_tasks"]


# ---------------------------------------------------------------------------
# Learner hygiene: dirty completions never reach μ̂
# ---------------------------------------------------------------------------


def test_learner_not_contaminated_by_stalled_completions():
    """A 45 s blackout stretches in-flight service by the full window.
    Those completions are DIRTY — they drain the queue view but never
    feed the learner: the maximum service time folded into μ̂ stays an
    order of magnitude below the outage length."""
    out = _run("blackout", use_scan=True, recovery=RECOVERY)
    led = out["info"]["ledger"]
    assert led["n_dirty_completions"] > 0
    assert led["n_stalled"] > 0
    # static speeds ≥ 0.25 and unit-scale costs: clean service is a few
    # seconds; a stall-stretched sample would be ≥ 45 s
    assert led["max_clean_service"] < 45.0
    assert led["max_clean_service"] > 0.0


# ---------------------------------------------------------------------------
# Churn drains, crashes kill — simulator and serving layers agree
# ---------------------------------------------------------------------------


def test_sim_crash_kills_churn_drains():
    """Chain simulator: a crash storm reports killed jobs through the
    trace's killed column; graceful churn (same membership dynamics,
    no violence) kills nothing — departures drain."""
    cfg, params, e = env.make("crash_storm").to_sim("ppot_sq2", rounds=9000)
    _, trace = sim.simulate(cfg, params, jax.random.PRNGKey(0), e)
    m = metrics.analyze(trace, cfg.n)
    assert m.killed_jobs > 0

    cfg, params, e = env.make("churn").to_sim("ppot_sq2", rounds=9000)
    _, trace = sim.simulate(cfg, params, jax.random.PRNGKey(0), e)
    m = metrics.analyze(trace, cfg.n)
    assert m.killed_jobs == 0


def test_serving_churn_departure_drains_in_flight():
    """Serving layers: graceful churn must not lose in-flight work —
    every task completes (ledger: zero lost, zero killed) on host and
    scan, while the same membership trajectory delivered as crashes
    kills in-flight copies."""
    for use_scan in (False, True):
        out = _run("churn", use_scan=use_scan, recovery=INERT_RECOVERY)
        led = out["info"]["ledger"]
        assert led["lost_tasks"] == 0, use_scan
        assert led["copies_real_killed"] == 0, use_scan
        assert np.isfinite(out["responses"]).all()
    out = _run("crash_storm", use_scan=True)
    assert out["info"]["ledger"]["copies_real_killed"] > 0


# ---------------------------------------------------------------------------
# Overflow is loud
# ---------------------------------------------------------------------------


def _tiny_workload():
    T, k, n = 8, 4, 3
    times = (np.arange(T * k, dtype=np.float64).reshape(T, k) + 1) * 0.01
    costs = np.full((T, k), 5.0)  # slow tasks pile up the pending set
    speeds = np.ones((T, n))
    return times, costs, speeds, n


def test_pend_overflow_raises_by_default():
    times, costs, speeds, n = _tiny_workload()
    router = RosellaRouter(n, mu_bar=float(n), async_mu=False)
    pool = SequentialPool(np.ones(n))
    with pytest.raises(RuntimeError, match="pend_cap"):
        run_workload_scan(router, pool, times, costs, speeds,
                          fake_cost=0.25, pend_cap=8)


def test_pend_overflow_reported_when_opted_out():
    times, costs, speeds, n = _tiny_workload()
    router = RosellaRouter(n, mu_bar=float(n), async_mu=False)
    pool = SequentialPool(np.ones(n))
    _, _, info = run_workload_scan(router, pool, times, costs, speeds,
                                   fake_cost=0.25, pend_cap=8,
                                   strict_overflow=False)
    assert info["pend_overflow"] > 0


def test_pend_cap_autosizes_from_workload_bound():
    """``pend_cap=None`` sizes the pending buffer from the total
    submission bound — the same piled-up workload that overflows a tiny
    cap runs clean, faults and retries included."""
    times, costs, speeds, n = _tiny_workload()
    kill = np.full((times.shape[0], n), np.inf)
    kill[4, 0] = 0.3  # one crash mid-run, to take the faulty path too
    router = RosellaRouter(n, mu_bar=float(n), async_mu=False)
    pool = SequentialPool(np.ones(n))
    _, _, info = run_workload_scan(
        router, pool, times, costs, speeds, fake_cost=0.25,
        kill_np=kill, recovery=RECOVERY,
    )
    assert info["pend_overflow"] == 0 and info["flush_overflow"] == 0
    assert metrics.check_conservation(info["ledger"])[0]


# ---------------------------------------------------------------------------
# Fleet: fault subset (kill/stall + ledger), no re-dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["blackout", "crash_storm"])
def test_fleet_s1_faulty_bit_equal_single_scan(name):
    single = _run(name, use_scan=True)
    fleet = _run(name, use_scan=True, n_frontends=1)
    np.testing.assert_array_equal(single["responses"], fleet["responses"])
    np.testing.assert_array_equal(single["mu_trace"], fleet["mu_trace"])
    assert single["info"]["ledger"] == fleet["info"]["ledger"]


def test_fleet_s2_faulty_ledger_conserves():
    out = _run("crash_storm", use_scan=True, n_frontends=2)
    led = out["info"]["ledger"]
    assert metrics.check_conservation(led)[0]
    assert led["copies_real_killed"] > 0


def test_fleet_rejects_recovery():
    with pytest.raises(ValueError, match="single-frontend"):
        _run("crash_storm", use_scan=True, n_frontends=2,
             recovery=RECOVERY)


# ---------------------------------------------------------------------------
# Catalog
# ---------------------------------------------------------------------------


def test_fault_scenarios_registered():
    names = set(env.names())
    assert {"crash_storm", "blackout", "grey_failure"} <= names
    assert len(names) >= 12
