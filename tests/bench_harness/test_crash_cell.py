"""Tests of the crash cell (``s2x4-crash-bulk``) on the CPU, at a small size:
one tile of S2 (15 workers), 16 arrivals a turn, 300 turns, machines that
crash after 60 and come back after 10 simulated seconds on average, so that
crashes, timeouts, retries and backup copies all occur.

* the program, through the cell's driver, against the plain recovery
  reference (``bench/recovery_reference.py``) on three seeds;
* the float32-clock control comes out not correct;
* a run with the timed path broken underneath comes out not correct, once
  for each fault the recovery layer can have;
* the cell and its files are found by name;
* the recovery stage reader on a synthetic trace.

Each case runs under its own time limit.
"""
from __future__ import annotations

import contextlib
import functools
import signal
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import faults as bfl
from bench import recovery_stages as rst
from bench import run as br
from bench import trace as btr

CELL = "s2x4-crash-bulk"
SMALL = {"n": 15, "tiles": 1, "arrival_batch": 16, "pend_cap": 4096,
         "comp_cap": 256, "mttf": 60.0, "mean_down": 10.0,
         "task_cap": {"window_s": 40, "requests_per_s": 1000}}
TURNS, CALLS = 300, 3
#: The plain body's stages, read in the crash cell by their own files.
STAGE_READERS = ("flush", "learner_fold", "alias_build", "dispatch",
                 "pool_chain", "pending_append", "telemetry_fold")


def limited(seconds: int):
    """Fail the test if it runs longer than ``seconds``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            def stop(*_):
                raise TimeoutError(f"{fn.__name__} ran past {seconds} s")

            old = signal.signal(signal.SIGALRM, stop)
            signal.alarm(seconds)
            try:
                return fn(*a, **kw)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)
        return run
    return wrap


class _NoTrace:
    """A tracer that records nothing: the window then ends after the
    driver's ``trace_calls`` calls."""

    def start(self):
        pass

    def stop(self):
        pass

    def gen(self):
        return contextlib.nullcontext()


def _small_cell(turns_per_call=TURNS // CALLS):
    cell = br.Cell(CELL)
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = dict(cell.traffic, turns_per_call=turns_per_call)
    return cell


def _fresh_programs():
    import jax

    from repro.serving import scanloop

    getattr(scanloop._build_scan_faulty, "cache_clear", lambda: None)()
    jax.clear_caches()


def _judged_run(seed, turns_per_call=TURNS // CALLS):
    """The program over ``TURNS`` turns through the cell's driver, judged
    against the reference: (compared numbers, correct, the driver)."""
    cell = _small_cell(turns_per_call)
    drv = cell.driver.Driver(cell, seed)
    drv.trace_calls = TURNS // turns_per_call
    _fresh_programs()
    try:
        drv.setup()
        host = drv.window(30.0, _NoTrace())
    finally:
        _fresh_programs()
    assert host["turns"] == TURNS
    nums = drv.check()
    ok, _ = br.judge(nums, cell.limits)
    return nums, ok, drv


@limited(120)
def test_program_matches_the_reference():
    for seed in (3, 2**31 + 7, 987654321):
        nums, ok, drv = _judged_run(seed)
        assert ok, nums
        assert nums["ledger_off"] == 0 and nums["placement_mismatch"] == 0
        led = drv._info["ledger"]
        assert led["conserved"]
        # crashes, timeouts, retries and backup copies all occurred
        assert nums["crashes"] > 20 and led["n_timeouts"] > 100
        assert led["n_retries"] > 100 and led["n_spec"] > 100
        assert (drv._info["retry_workers"] >= 0).sum() == led["n_retries"]
        assert (drv._info["spec_workers"] >= 0).sum() == led["n_spec"]


@limited(90)
def test_control_is_not_correct():
    from bench import recovery_control

    cell = _small_cell()
    for seed in (11, 2**31 + 5):
        nums = recovery_control.readings(cell, seed, TURNS)
        ok, _ = br.judge(nums, cell.limits)
        assert not ok, nums
        assert nums["resp_off"] > 50 * cell.limits["resp_off"]


@limited(60)
def test_deadlines_read_no_learner_the_reference_disowns(monkeypatch):
    """A system whose learner is 2% off on one worker for 30 turns, which its
    alias table and deadlines read: too few entries for ``mu_off``, but the
    reference keeps its own mu_hat for those deadlines, so the timeouts and
    the retries they queue come out apart."""
    from bench import recovery_reference as rref

    cell = _small_cell()
    drv = cell.driver.Driver(cell, 4242)
    times, costs, speeds = drv.stream().turns(TURNS)
    cols = rref.Columns(*drv.faults.columns(times[:, -1]))
    lo, hi = times[100, -1], times[130, -1]

    class OffLearner(rref.Learner):
        def refresh(self, lam, now32):
            super().refresh(lam, now32)
            if lo <= now32 < hi:
                self.mu[3] *= np.float32(1.02)

    args = (drv.sem, drv.rc, drv.router_seed, times, costs, speeds, cols)
    with monkeypatch.context() as m:
        m.setattr(rref, "Learner", OffLearner)
        system = rref.simulate(*args, burst_cost=drv.burst_cost).answers
    nums = rref.check(*args, system, burst_cost=drv.burst_cost)
    ok, _ = br.judge(dict(nums, overflow=0), cell.limits)
    assert 0 < nums["mu_off"] < cell.limits["mu_off"]
    assert not ok, nums
    assert rref.adopt_mu(np.float32([1.0, 2.0]), np.float32([1.00001, 2.1]))[
        1] == np.float32(2.0)


def _kill_skipped(mp):
    """One worker's crashes never reach the program."""
    import jax.numpy as jnp

    from repro.serving import scanloop

    real = scanloop._build_scan_faulty

    def build(*a, **kw):
        run = real(*a, **kw)

        def no_kill(lcfg, carry, xs):
            kill = xs[6].at[:, 1].set(jnp.inf)
            return run(lcfg, carry, xs[:6] + (kill,) + xs[7:])
        return no_kill
    mp.setattr(scanloop, "_build_scan_faulty", build)


def _retry_elsewhere(mp):
    """Each retry copy goes to the next worker."""
    import jax.numpy as jnp

    from repro.core import scheduler

    real = scheduler._serve_step_math

    def shifted(*a, **kw):
        out = real(*a, **kw)
        if len(a) <= 16:  # no retry slots
            return out
        k, n, w = a[9], a[0].shape[0], out[1]
        tail = jnp.where(w[k:] >= 0, (w[k:] + 1) % n, -1)
        return (out[0], jnp.concatenate([w[:k], tail])) + tuple(out[2:])
    mp.setattr(scheduler, "_serve_step_math", shifted)


def _backup_dropped(mp):
    """Backup copies are counted and kept but never run: the chain leaves
    the last ``spec_cap`` slots out and they never finish."""
    import jax.numpy as jnp

    from repro.serving import scanloop

    real = scanloop.pool_chain
    spec_cap = br.Cell(CELL).config["recovery"]["spec_cap"]

    def dropped(free_at, sub_w, sub_arr, sub_cost, act, speeds64):
        last = jnp.arange(act.shape[0]) >= act.shape[0] - spec_cap
        fa, start, done, steps = real(free_at, sub_w, sub_arr, sub_cost,
                                      act & ~last, speeds64)
        return fa, start, jnp.where(last, jnp.inf, done), steps
    mp.setattr(scanloop, "pool_chain", dropped)


def _dirty_learned(mp):
    """Every call starts with all in-flight copies marked clean, so a
    timed-out copy's completion feeds the learner."""
    import jax.numpy as jnp

    from repro.serving import scanloop

    real = scanloop._build_scan_faulty

    def build(*a, **kw):
        run = real(*a, **kw)

        def clean(lcfg, carry, xs):
            p_learn = 21  # the carry's learner-eligibility column
            carry = (carry[:p_learn] + (jnp.ones_like(carry[p_learn]),)
                     + carry[p_learn + 1:])
            return run(lcfg, carry, xs)
        return clean
    mp.setattr(scanloop, "_build_scan_faulty", build)


def _down_worker_placed(mp):
    """The dispatch is not told which workers are down."""
    from repro.core import scheduler

    real = scheduler._serve_step_math

    def unmasked(*a, **kw):
        return real(*(a[:15] + (None,) + a[16:]), **kw)
    mp.setattr(scheduler, "_serve_step_math", unmasked)


@pytest.mark.parametrize("fault,turns_per_call", [
    (_kill_skipped, 100), (_retry_elsewhere, 100), (_backup_dropped, 100),
    (_dirty_learned, 1), (_down_worker_placed, 100)])
@limited(90)
def test_broken_timed_path_is_not_correct(monkeypatch, fault, turns_per_call):
    fault(monkeypatch)
    nums, ok, _ = _judged_run(4242, turns_per_call)
    assert not ok, nums


@limited(30)
def test_crash_cell_is_found_by_name():
    cell = br.Cell(CELL)
    assert cell.config_name == "rosella-s2x4-crash"
    assert cell.traffic_name == "poisson-a085-bulk" and cell.chips == 1
    assert set(cell.limits) == {"misplaced", "overflow", "unconserved",
                                "placement_mismatch", "resp_off", "mu_off",
                                "ledger_off"}
    bench = br.load_json(br.ROOT / "BENCHMARK.json")
    assert [m["name"] for m in br.cell_metrics(bench, CELL, False)] == [
        "decisions_per_s", "setup_s"]
    per_layer = [m["name"] for m in br.cell_metrics(bench, CELL, True)]
    assert per_layer == ["gen_share", "idle_share", "turn_device_us",
                         "fault_apply_us", "retry_select_us",
                         "spec_select_us"] + [
        f"{s}_us.crash" for s in STAGE_READERS]
    for name in per_layer:
        assert br.load_module(br.BENCH / "metrics" / f"{name}.py").read
    drv = cell.driver.Driver(cell, 1)
    # the response buffer is sized before set-up, never from a rate
    per_call = drv.T * drv.sem.k
    assert drv.task_cap % per_call == 0
    assert drv.task_cap >= 40 * 1_000_000
    with pytest.raises(ValueError):
        drv.window(drv.window_cap_s + 1)


@limited(30)
def test_fault_columns():
    params = {"mttf": 60.0, "mean_down": 10.0, "anchor": 0,
              "probe_burst": 4}
    t_end = np.cumsum(np.full(400, 2.0))
    whole = bfl.Faults(params, 15, 77).columns(t_end)
    cut = bfl.Faults(params, 15, 77)
    parts = [cut.columns(t_end[s:s + 70]) for s in range(0, 400, 70)]
    for a, b in zip(whole, zip(*parts)):
        assert np.array_equal(a, np.concatenate(b))
    active, rejoin, burst, kill = whole
    assert active[:, 0].all() and np.isinf(kill[:, 0]).all()  # the anchor
    assert 0.05 < 1 - active[:, 1:].mean() < 0.25  # 10 / 70 down
    crashed = np.isfinite(kill)
    assert crashed.sum() > 50
    # a crash's instant lies in its turn
    t, w = np.nonzero(crashed)
    assert (kill[t, w] <= t_end[t]).all()
    assert (kill[t, w] > np.concatenate([[-np.inf], t_end])[t]).all()
    assert not rejoin[0].any() and (rejoin <= active).all()
    assert ((burst >= 0).sum(axis=1) == 4 * rejoin.sum(axis=1)).all()


# -- the recovery stage reader ------------------------------------------------


def _op(name, scope):
    return (f'%{name} = f64[8] {name.split(".")[0]}(%p), '
            f'metadata={{op_name="{scope}" source_file="scanloop.py"}}')


def _trace(turns, sorts_kept):
    ops = []
    for t in range(turns):
        base = 1000 * t
        ops.append((_op("fusion.1", "jit(run)/while/body/rosella.fault_apply/"
                        "and"), base + 10, 20))
        if t < sorts_kept:
            ops.append((_op("sort.2", "jit(run)/while/body/"
                            "rosella.retry_select/sort"), base + 40, 30))
        ops.append((_op("sort.3", "jit(run)/while/body/rosella.spec_select/"
                        "sort"), base + 80, 50))
        ops.append((_op("sort.4", "jit(run)/while/body/rosella.flush/sort"),
                    base + 200, 7))
    dev = NS(name="/device:TPU:0", lines=[NS(name=btr.OPS_LINE, events=[
        NS(name=n, start_ns=s, duration_ns=d) for n, s, d in ops])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        NS(name=btr.WINDOW_SPAN, start_ns=0, duration_ns=1000 * turns)])])
    return btr.reduce_planes([host, dev])


@limited(30)
def test_recovery_stage_reader():
    assert rst.scope_label("a/rosella.retry_select/b/rosella.flush/c") == (
        "flush")
    assert rst.scope_label("a/rosella.spec_select/sort") == "spec_select"
    assert rst.scope_label("jit(run)/while") is None
    ctx = {"trace": _trace(20, 20), "host": {"turns": 20}}
    assert rst.stage_us(ctx, "fault_apply") == pytest.approx(0.020)
    assert rst.stage_us(ctx, "retry_select") == pytest.approx(0.030)
    assert rst.stage_us(ctx, "spec_select") == pytest.approx(0.050)
    # the plain stages' readers of the crash cell read the same labels
    for stage in STAGE_READERS:
        mod = br.load_module(br.BENCH / "metrics" / f"{stage}_us.crash.py")
        assert mod.read(ctx) == rst.stage_us(ctx, stage)
    assert rst.stage_us(ctx, "flush") == pytest.approx(0.007)
    assert rst.stage_us(ctx, "pending_append") == 0.0
    # a trace that dropped the retry sort of 2 turns in 20 vouches for nothing
    ctx = {"trace": _trace(20, 18), "host": {"turns": 20}}
    assert rst.stage_us(ctx, "fault_apply") is None
    assert rst.stage_us(ctx, "flush") is None
