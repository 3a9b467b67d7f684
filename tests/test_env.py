"""Environment engine (repro.env): masked dispatch, scenario determinism,
cross-layer parity (host loop vs. scan, null vs. pre-env machinery),
churn cold-start, adaptation-time metric, LB partitioning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import env
from repro.core import dispatch as dsp
from repro.core import learner as lrn
from repro.core import metrics as M
from repro.core import policies as pol
from repro.core import scheduler as rs
from repro.core import simulator as sim
from repro.serving import (
    RosellaRouter,
    SequentialPool,
    SimulatedPool,
    run_simulation,
)

N = 8
MU = jnp.asarray(np.linspace(0.5, 2.0, N), jnp.float32)
MASK = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], bool)
CFG = pol.default_policy_config()


# ---------------------------------------------------------------------------
# Masked alias table + masked dispatch
# ---------------------------------------------------------------------------


def _table_mass(table, n):
    """Reconstruct the categorical each (u, v) draw samples from."""
    prob = np.asarray(table.prob)
    alias = np.asarray(table.alias)
    mass = np.zeros(n)
    for b in range(n):
        mass[b] += prob[b]
        mass[alias[b]] += 1.0 - prob[b]
    return mass / n


def test_masked_alias_table_zero_inactive_mass_exact():
    t = dsp.build_alias_table(MU, MASK)
    mass = _table_mass(t, N)
    m = np.asarray(MASK)
    # inactive bins: EXACT zero (prob forced to 0.0, no alias edge lands)
    assert (mass[~m] == 0.0).all()
    w = np.where(m, np.asarray(MU), 0.0)
    np.testing.assert_allclose(mass[m], (w / w.sum())[m], atol=1e-6)


def test_masked_alias_table_degenerate_mu():
    # all active workers at mu=0 → uniform over the ACTIVE set
    t = dsp.build_alias_table(jnp.zeros((N,), jnp.float32), MASK)
    mass = _table_mass(t, N)
    m = np.asarray(MASK)
    assert (mass[~m] == 0.0).all()
    np.testing.assert_allclose(mass[m], 1.0 / m.sum(), atol=1e-6)


def test_masked_alias_never_selects_inactive():
    t = dsp.build_alias_table(MU, MASK)
    u, _, v, _ = dsp._uniform_quad(jax.random.PRNGKey(3), 4096)
    js = np.asarray(dsp.alias_sample(t, u, v))
    assert np.asarray(MASK)[js].all()


@pytest.mark.parametrize("policy", pol.ALL_POLICIES)
def test_masked_dispatch_never_selects_inactive(policy):
    q = jnp.zeros((N,), jnp.int32)
    table = (
        dsp.build_alias_table(MU, MASK)
        if policy in dsp.ALIAS_POLICIES else None
    )
    res = dsp.dispatch(policy, jax.random.PRNGKey(0), q, MU, MU, CFG, 512,
                       use_kernel=False, mask=MASK, table=table)
    ws = np.asarray(res.workers)
    assert (ws >= 0).all()
    assert np.asarray(MASK)[ws].all()
    # fold-back accounting intact
    np.testing.assert_array_equal(
        np.asarray(res.q_after), np.bincount(ws, minlength=N)
    )


@pytest.mark.parametrize("policy", [pol.PPOT_SQ2, pol.PSS, pol.POT])
def test_masked_dispatch_sequential_oracle_parity(policy):
    """Batched masked dispatch vs. the per-task sequential oracle on the
    same draw streams: identical workers on a balanced queue snapshot is
    too strong (fold-back differs within the batch), but the oracle must
    consume the same probes — check via fold_chunks=1 vs =B on a queue
    that never changes selection (all-zero queue, B small relative to n
    spread is not guaranteed) → instead: same mask invariants + exact
    parity of the probe-only policies (PSS: selection == probe)."""
    q = jnp.zeros((N,), jnp.int32)
    table = (
        dsp.build_alias_table(MU, MASK)
        if policy in dsp.ALIAS_POLICIES else None
    )
    key = jax.random.PRNGKey(7)
    a = dsp.dispatch(policy, key, q, MU, MU, CFG, 64, use_kernel=False,
                     mask=MASK, table=table)
    b = dsp.dispatch_sequential(policy, key, q, MU, MU, CFG, 64,
                                mask=MASK, table=table)
    if policy == pol.PSS:  # probe-only: fold-back can't change selection
        np.testing.assert_array_equal(np.asarray(a.workers),
                                      np.asarray(b.workers))
    assert np.asarray(MASK)[np.asarray(b.workers)].all()
    np.testing.assert_array_equal(
        np.asarray(b.q_after),
        np.bincount(np.asarray(b.workers), minlength=N),
    )


def test_masked_alias_vs_masked_searchsorted_distribution():
    """The masked alias draw and the masked inverse-CDF draw sample the
    SAME distribution (different streams): total-variation distance of
    empirical histograms within the sampling-noise bound."""
    B = 20_000
    key = jax.random.PRNGKey(11)
    table = dsp.build_alias_table(MU, MASK)
    u1, _, v1, _ = dsp._uniform_quad(key, B)
    j_alias = np.asarray(dsp.alias_sample(table, u1, v1))
    cdf = dsp.masked_cdf(MU, MASK)
    u = jax.random.uniform(jax.random.PRNGKey(12), (B,))
    j_cdf = np.asarray(jnp.clip(dsp.inverse_cdf_sample(cdf, u), 0, N - 1))
    m = np.asarray(MASK)
    assert m[j_alias].all() and m[j_cdf].all()
    ha = np.bincount(j_alias, minlength=N) / B
    hc = np.bincount(j_cdf, minlength=N) / B
    assert 0.5 * np.abs(ha - hc).sum() < 0.02


def test_fake_jobs_from_masked():
    lcfg = lrn.default_learner_config(10.0)
    js = rs.fake_jobs_from(lcfg, jax.random.PRNGKey(1), jnp.float32(1.0),
                           jnp.float32(50.0), 8, N, mask=MASK)
    js = np.asarray(js)
    live = js[js >= 0]
    assert len(live) > 0 and np.asarray(MASK)[live].all()


def test_reset_workers_cold_start():
    lcfg = lrn.default_learner_config(10.0)
    st = lrn.init_learner(4, lcfg, 1.0)
    st = st.replace(
        mu_hat=jnp.asarray([2.0, 9.0, 4.0, 1.0]),
        count=jnp.asarray([5, 7, 3, 2], jnp.int32),
        samples=jnp.ones_like(st.samples),
    )
    reset = jnp.asarray([False, True, False, False])
    active = jnp.asarray([True, True, True, False])  # worker 3 offline
    out = lrn.reset_workers(st, reset, jnp.float32(100.0), active)
    # cold μ̂ = mean over active & ~reset = mean(2, 4) = 3
    np.testing.assert_allclose(np.asarray(out.mu_hat),
                               [2.0, 3.0, 4.0, 1.0])
    assert int(out.count[1]) == 0 and float(out.epoch_start[1]) == 100.0
    assert float(out.samples[1].sum()) == 0.0
    # untouched workers keep their rings
    assert int(out.count[0]) == 5 and float(out.samples[0].sum()) > 0


# ---------------------------------------------------------------------------
# Scenario engine: determinism, null bit-exactness, cross-layer parity
# ---------------------------------------------------------------------------


def test_scenario_registry():
    assert set(env.names()) >= {
        "null", "reshuffle", "flash_crowd", "diurnal", "cotenant_shock",
        "speed_drift", "churn", "churn_heavy", "trace_replay",
    }
    with pytest.raises(KeyError):
        env.make("no_such_scenario")


def test_null_scenario_bit_exact_vs_run_simulation():
    scn = env.make("null", horizon=120.0)
    sp = np.asarray(scn.speeds)
    ra = RosellaRouter(scn.n, mu_bar=sp.sum(), seed=0, async_mu=False)
    pa = SimulatedPool(sp)
    resp_ref, mu_ref = run_simulation(
        ra, pa, arrival_rate=scn.rate, horizon=scn.horizon, seed=0,
        arrival_batch=8,
    )
    out = env.run_scenario(scn, seed=0, arrival_batch=8)
    np.testing.assert_array_equal(resp_ref, out["responses"])
    np.testing.assert_array_equal(mu_ref, out["mu_trace"])


@pytest.mark.parametrize("name", ["flash_crowd", "churn"])
def test_scenario_deterministic_repeat(name):
    scn = env.make(name, horizon=100.0)
    a = env.run_scenario(scn, seed=3, arrival_batch=8)
    b = env.run_scenario(scn, seed=3, arrival_batch=8)
    np.testing.assert_array_equal(a["responses"], b["responses"])
    np.testing.assert_array_equal(a["mu_trace"], b["mu_trace"])


@pytest.mark.parametrize("name", ["null", "flash_crowd", "churn",
                                  "churn_heavy"])
def test_host_vs_scan_parity(name):
    """Host loop vs. the one-program scan, float-for-float, on the
    Poisson, MMPP and churn scenarios (SequentialPool + deterministic
    router — the documented exactness regime)."""
    scn = env.make(name, horizon=100.0)
    h = env.run_scenario(scn, seed=1, arrival_batch=8, sequential_pool=True)
    s = env.run_scenario(scn, seed=1, arrival_batch=8, sequential_pool=True,
                         use_scan=True)
    assert s["info"]["flush_overflow"] == 0
    assert s["info"]["pend_overflow"] == 0
    np.testing.assert_array_equal(h["responses"], s["responses"])
    np.testing.assert_array_equal(h["mu_trace"], s["mu_trace"])
    np.testing.assert_array_equal(h["pool"].free_at, s["pool"].free_at)
    assert h["info"]["workers"].size == h["responses"].size
    np.testing.assert_array_equal(h["info"]["workers"], s["info"]["workers"])


def test_churn_serving_never_routes_offline():
    """During the offline window no request (real or benchmark) may land
    on the churned replica — checked via the pool's busy clock: replica 1
    accrues NO new work between its leave and rejoin."""
    scn = env.make("churn", horizon=300.0)
    out = env.run_scenario(scn, seed=0, arrival_batch=8,
                           sequential_pool=True)
    wl = out["workload"]
    t = wl.times[:, -1]
    # free_at[1] just before rejoin must predate the leave + max in-flight
    # work: replay the run, snapshotting the pool at the leave/rejoin turns
    router = RosellaRouter(scn.n, mu_bar=float(np.sum(scn.speeds)), seed=0,
                           async_mu=False)
    pool = SequentialPool(np.asarray(scn.speeds))
    off_turns = np.nonzero(~wl.active[:, 1])[0]
    from repro.env.serving import run_workload

    # run only the offline prefix, then check replica 1's clock is frozen
    cut = off_turns[-1] + 1
    import dataclasses as _dc

    wl_cut = _dc.replace(
        wl, times=wl.times[:cut], costs=wl.costs[:cut],
        speeds=wl.speeds[:cut], active=wl.active[:cut],
        rejoin=wl.rejoin[:cut], burst=wl.burst[:cut],
    )
    run_workload(router, pool, wl_cut, fake_cost=scn.request_cost * 0.25)
    t_leave = t[off_turns[0]]
    # any work replica 1 still owes was submitted BEFORE it left (bounded
    # by its pre-departure backlog); nothing new arrived while offline
    assert pool.free_at[1] <= t_leave + 40.0
    assert np.asarray(router.active, bool)[1] == False  # noqa: E712


def test_churn_rejoin_cold_start_and_relearn():
    scn = env.make("churn")
    out = env.run_scenario(scn, seed=0, arrival_batch=8)
    wl, mu = out["workload"], out["mu_trace"]
    t = wl.times[:, -1]
    rejoin_turn = int(np.nonzero(wl.rejoin[:, 1])[0][0])
    # the probe burst targets the rejoined worker
    assert (wl.burst[rejoin_turn] == 1).sum() == scn.probe_burst
    # by the end μ̂ ranks replica 1 (speed 2.0) above replica 2 (speed 1.0)
    assert mu[-1][1] > mu[-1][2]


def test_onoff_overlapping_windows_rejected():
    """period ≤ window length would emit non-monotonic breakpoints and
    corrupt every searchsorted lookup — must raise, not run wrong."""
    from repro.env.processes import OnOffInterference

    bad = OnOffInterference(affected=(0,), t_on=10.0, t_off=50.0, period=30.0)
    with pytest.raises(ValueError, match="period"):
        bad.compile(np.ones(4), 200.0, np.random.RandomState(0))
    ok = OnOffInterference(affected=(0,), t_on=10.0, t_off=50.0, period=60.0)
    bp, _ = ok.compile(np.ones(4), 200.0, np.random.RandomState(0))
    assert (np.diff(bp) > 0).all()


def test_trace_partial_tail_counted():
    tr = env.TraceArrivals.from_arrays(np.arange(10) * 1.0)
    scn = env.Scenario(name="t", speeds=(1.0, 1.0), rate=1.0, horizon=100.0,
                       arrivals=tr)
    wl = scn.compile_serving(seed=0, arrival_batch=4)
    assert wl.turns == 2 and wl.trace_dropped == 2  # 10 = 2 full batches + 2


def test_scan_honors_preset_router_mask():
    """A static membership mask set via set_membership BEFORE a scan run
    must mask the scan too (host/scan drop-in contract): no placement on
    the offline replica, and host-vs-scan stays float-for-float."""
    from repro.serving import run_simulation_scan

    sp = np.array([2.0, 2.0, 1.0, 1.0, 0.5])
    act = np.array([True, False, True, True, True])
    kw = dict(arrival_rate=3.0, horizon=80.0, seed=0, arrival_batch=8)
    ra = RosellaRouter(5, mu_bar=sp.sum(), seed=0, async_mu=False)
    ra.set_membership(act, 0.0)
    pa = SequentialPool(sp)
    from repro.serving import run_simulation

    resp_h, mu_h = run_simulation(ra, pa, **kw)
    rb = RosellaRouter(5, mu_bar=sp.sum(), seed=0, async_mu=False)
    rb.set_membership(act, 0.0)
    pb = SequentialPool(sp)
    resp_s, mu_s, info = run_simulation_scan(rb, pb, **kw)
    assert info["pend_overflow"] == 0
    np.testing.assert_array_equal(resp_h, resp_s)
    np.testing.assert_array_equal(mu_h, mu_s)
    assert pb.free_at[1] == 0.0  # offline replica never received work


def test_mesh_fleet_sync_masked_tables():
    """The masked mesh sync form: every shard's frozen alias table zeroes
    offline workers' probe mass (single-device mesh, axis size 1)."""
    from repro.fleet import init_fleet_frontends, make_fleet_sync
    from repro.core import learner as lrn
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("sched",), axis_types=(AxisType.Auto,))
    lcfg = lrn.default_learner_config(4.0)
    ffs = init_fleet_frontends(1, 4, lcfg, mu_init=1.0)
    sync = make_fleet_sync(mesh, masked=True)
    active = jnp.asarray([True, True, False, True])
    out = sync(ffs, jnp.float32(1.0), active)
    prob = np.asarray(out.alias_p)[0]
    alias = np.asarray(out.alias_a)[0]
    assert prob[2] == 0.0
    assert alias[2] != 2  # every draw in the dead bin escapes to a live one
    mass = _table_mass(dsp.AliasTable(prob=prob, alias=alias), 4)
    assert mass[2] == 0.0


def test_fleet_sync_reports_rejoined():
    from repro.serving import FleetRouter

    fl = FleetRouter(2, 4, mu_bar=4.0, seed=0, async_mu=False)
    info = fl.sync(1.0, active=np.array([True, True, False, True]))
    assert len(info["rejoined"]) == 0  # first mask: nothing rejoins
    info = fl.sync(2.0, active=np.array([True, True, True, True]))
    np.testing.assert_array_equal(info["rejoined"], [2])
    for fr in fl.frontends:  # masked table adopted fleet-wide
        assert np.asarray(fr.active, bool).all()


def test_trace_replay_times_verbatim():
    scn = env.make("trace_replay", horizon=60.0)
    wl = scn.compile_serving(seed=0, arrival_batch=4)
    tr = np.asarray(scn.arrivals.times)
    flat = wl.times.reshape(-1)
    np.testing.assert_array_equal(flat, tr[: len(flat)])


def test_simulate_env_churn_masks_placements():
    scn = env.make("churn", horizon=200.0)
    cfg, params, e = scn.to_sim("ppot_sq2", rounds=4000)
    assert e is not None
    final, trace = sim.simulate(cfg, params, jax.random.PRNGKey(0), e)
    code = np.asarray(trace["code"])
    now = np.asarray(trace["now"])
    tw = np.asarray(trace["task_workers"])
    arr = code == sim.EV_ARRIVAL
    off = arr & (now >= 120.0) & (now < 240.0)
    assert off.sum() > 0
    assert (tw[off] != 1).all()  # replica 1 never placed while offline


def test_simulate_null_scenario_is_plain_simulate():
    scn = env.make("null")
    cfg, params, e = scn.to_sim("ppot_sq2", rounds=1500)
    assert e is None
    f1, tr1 = sim.simulate(cfg, params, jax.random.PRNGKey(0))
    f2, tr2 = sim.simulate(cfg, params, jax.random.PRNGKey(0), None)
    np.testing.assert_array_equal(np.asarray(tr1["now"]),
                                  np.asarray(tr2["now"]))


def test_simulate_env_mmpp_rate_modulation():
    """Arrival counts track the piecewise rate: the burst regime must see
    a higher arrival rate than the calm regime."""
    scn = env.make("flash_crowd", horizon=400.0)
    cfg, params, e = scn.to_sim("ppot_sq2", rounds=20_000)
    final, trace = sim.simulate(cfg, params, jax.random.PRNGKey(0), e)
    code = np.asarray(trace["code"])
    now = np.asarray(trace["now"])
    lam_bp = np.asarray(e.lam_bp)
    lam_val = np.asarray(e.lam_val)
    arr_t = now[code == sim.EV_ARRIVAL]
    hi = lam_val > lam_val.min()
    # empirical rate in burst segments vs calm segments
    def rate_in(mask_seg):
        tot_t, tot_n = 0.0, 0
        for i in np.nonzero(mask_seg)[0]:
            t0 = lam_bp[i]
            t1 = lam_bp[i + 1] if i + 1 < len(lam_bp) else float(now[-1])
            t1 = min(t1, float(now[-1]))
            if t1 <= t0:
                continue
            tot_t += t1 - t0
            tot_n += int(((arr_t >= t0) & (arr_t < t1)).sum())
        return tot_n / max(tot_t, 1e-9)

    assert rate_in(hi) > 1.8 * rate_in(~hi)


# ---------------------------------------------------------------------------
# Load-balancer partitioning (simulator fleet)
# ---------------------------------------------------------------------------


def _fleet_shares(cfg, params, seed=0):
    final, trace = sim.simulate(cfg, params, jax.random.PRNGKey(seed))
    code = np.asarray(trace["code"])
    fr = np.asarray(trace["frontend"])[code == sim.EV_ARRIVAL]
    return np.bincount(fr, minlength=cfg.n_frontends)


def test_lb_sticky_round_robin_exact():
    cfg = sim.SimConfig(n=4, policy="ppot_sq2", rounds=3000, n_frontends=4,
                        fleet_sync_every=4, frontend_lb="sticky")
    params = sim.make_params(lam=3.0, mu=[1.0, 1.0, 2.0, 0.5])
    shares = _fleet_shares(cfg, params)
    assert shares.max() - shares.min() <= 1  # perfect round-robin


def test_lb_weighted_shares():
    cfg = sim.SimConfig(n=4, policy="ppot_sq2", rounds=4000, n_frontends=4,
                        fleet_sync_every=4, frontend_lb="weighted")
    params = sim.make_params(lam=3.0, mu=[1.0, 1.0, 2.0, 0.5],
                             lb_weights=[6.0, 1.0, 1.0, 1.0])
    shares = _fleet_shares(cfg, params)
    frac = shares / shares.sum()
    assert abs(frac[0] - 6.0 / 9.0) < 0.08
    assert (frac[1:] < 0.25).all()


def test_lb_uniform_default_unchanged():
    """frontend_lb defaults to 'uniform' — the PR-3 stream: the same run
    with the field explicitly set must be bit-identical."""
    params = sim.make_params(lam=3.0, mu=[1.0, 1.0, 2.0, 0.5])
    cfg_a = sim.SimConfig(n=4, policy="ppot_sq2", rounds=1200, n_frontends=2,
                          fleet_sync_every=4)
    cfg_b = sim.SimConfig(n=4, policy="ppot_sq2", rounds=1200, n_frontends=2,
                          fleet_sync_every=4, frontend_lb="uniform")
    _, tr_a = sim.simulate(cfg_a, params, jax.random.PRNGKey(0))
    _, tr_b = sim.simulate(cfg_b, params, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(tr_a["frontend"]),
                                  np.asarray(tr_b["frontend"]))
    np.testing.assert_array_equal(np.asarray(tr_a["q_real"]),
                                  np.asarray(tr_b["q_real"]))


# ---------------------------------------------------------------------------
# Adaptation-time metric
# ---------------------------------------------------------------------------


def test_adaptation_time_synthetic():
    """Constructed trajectory: error sits at 0.05, jumps to 0.5 at the
    shift, decays back under the pre-shift band at a known time."""
    times = np.arange(0.0, 100.0, 1.0)
    err = np.full_like(times, 0.05)
    shift = 40.0
    post = times >= shift
    err[post] = 0.05 + 0.45 * np.exp(-(times[post] - shift) / 8.0)
    at = M.adaptation_time(times, err, shift, pre_window=20.0)
    # err re-enters band ≈ 0.05·(1+small) when exp term < band−0.05...
    # band = quantile(0.9) of flat 0.05 = 0.05 → floored at min_band 0.02
    # → band 0.05; re-entry when 0.45·exp(−dt/8) ≤ 0 → never exactly;
    # with fp, exp decays under 1e-17 by dt≈320 > horizon → NaN guard:
    assert np.isnan(at) or at > 0
    # more discriminating: band with headroom
    err2 = np.full_like(times, 0.05)
    err2[post] = np.where(times[post] < 60.0, 0.5, 0.04)
    at2 = M.adaptation_time(times, err2, shift, pre_window=20.0)
    assert at2 == pytest.approx(20.0)
    # a shift that never moves the error: adaptation time 0
    at3 = M.adaptation_time(times, np.full_like(times, 0.01), shift,
                            pre_window=20.0)
    assert at3 == 0.0


def test_adaptation_report_on_cotenant():
    scn = env.make("cotenant_shock")
    out = env.run_scenario(scn, seed=0, arrival_batch=8)
    wl, mu = out["workload"], out["mu_trace"]
    rep = M.adaptation_report(wl.times[:, -1], mu, wl.speeds, wl.shift_times)
    assert rep["n_shifts"] == 2
    # at least one shift measurably adapted
    assert rep["n_unadapted"] < rep["n_shifts"]
    assert np.isfinite(rep["mean"]) and rep["mean"] >= 0.0
