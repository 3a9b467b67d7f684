"""Tests of the benchmark harness, on the CPU.

* the trace reduction on a synthetic trace;
* a cell file added under ``workloads/`` is found with no other edit;
* the correctness comparison's control (the reference with a float32
  event clock in the system's place) comes out not correct;
* a run driven with the timed path broken underneath comes out not
  correct, once for each fault a single-frontend cell can have;
* the window's delay covers each call's device run even where the
  program leaves its per-call read-back out.
"""
from __future__ import annotations

import json
import shutil
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import reference as ref
from bench import run as br
from bench import trace as btr
from bench import traffic as trf

# -- trace reduction ----------------------------------------------------------


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in ev]) for ln, ev in lines.items()])


def _synthetic_trace():
    host = _plane("/host:CPU", {"python": [
        (btr.WINDOW_SPAN, 100, 1000),  # window [100, 1100)
        (btr.GEN_SPAN, 100, 50),
        ("serve_scan_chunk", 150, 20),
        (btr.GEN_SPAN, 700, 300),
    ]})
    dev0 = _plane("/device:TPU:0", {
        "XLA Modules": [("jit_run", 150, 500)],
        btr.OPS_LINE: [
            ("while.3", 150, 400),              # [150, 550)
            ("sort.12", 200, 100),              # nested in the while
            ("%custom-call.4 = s32[1,256] custom-call(), custom_call_target=\"tpu_custom_call\", metadata={op_name=\"jit(ppot_dispatch_fused_alias)\"}", 400, 20),
            ("%bitcast.9 = s32[128] bitcast(%custom-call.4), metadata={op_name=\"jit(ppot_dispatch_fused_alias)\"}", 420, 5),
            ("fusion.7", 560, 40),              # [560, 600)
            ("copy.1", 0, 120),                 # clipped to [100, 120)
            ("fusion.8", 1090, 100),            # clipped to [1090, 1100)
        ]})
    dev1 = _plane("/device:TPU:1", {btr.OPS_LINE: [("sort.2", 100, 500)]})
    return [host, dev1, dev0]


def test_union_and_gaps():
    s = np.array([0.0, 5.0, 2.0, 20.0])
    e = np.array([4.0, 6.0, 3.0, 30.0])
    assert btr.union_length(s, e) == 4.0 + 1.0 + 10.0
    gs, ge = btr.gaps(s, e, 0.0, 40.0)
    assert list(zip(gs, ge)) == [(4.0, 5.0), (6.0, 20.0), (30.0, 40.0)]
    assert btr.op_kind("fusion.12") == "fusion"
    assert btr.op_kind("sort-1.2") == "sort"
    hlo = "%while.42 = (u32[]{:T(128)}, f32[64]{0}) while(%tuple.3), body=%b"
    assert btr.op_name(hlo) == "while.42" and btr.op_kind(hlo) == "while"


def test_reduce_synthetic_trace():
    sm = btr.reduce_planes(_synthetic_trace())
    assert [d.name for d in sm.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert sm.window_s == pytest.approx(1000e-9)
    busy0 = 20 + 400 + 40 + 10  # nested ops count once
    assert sm.busy_s == pytest.approx(0.5 * (busy0 + 500) * 1e-9)
    sort = sm.op_seconds(lambda n: "sort" in btr.op_kind(n))
    assert sort == pytest.approx(0.5 * (100 + 500) * 1e-9)
    from bench import kernels

    # the kernel's own event, not the bitcast that carries its name
    assert sm.op_count(kernels.is_ppot_alias_kernel) == 0.5
    assert sm.op_seconds(kernels.is_ppot_alias_kernel) == pytest.approx(10e-9)
    bd = sm.breakdown()
    assert bd["device_ops"][0] == ["while.3", pytest.approx(400e-9)]
    # longest gap on device 0: [600, 1090), the host generating then idle
    label, length = bd["idle_gaps"][0]
    assert length == pytest.approx(490e-9)
    assert label == btr.GEN_SPAN  # midpoint 845 lies in the second gen span
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


def test_reduce_requires_window_and_device():
    with pytest.raises(ValueError):
        btr.reduce_planes([_plane("/host:CPU", {"python": []})])
    with pytest.raises(ValueError):
        btr.reduce_planes([_plane("/host:CPU", {"python": [
            (btr.WINDOW_SPAN, 0, 10)]})])


def test_metric_readers_on_synthetic_trace():
    sm = btr.reduce_planes(_synthetic_trace())
    cell = br.Cell("s2x4-poisson-bulk")
    ctx = {"trace": sm, "host": {"turns": 1, "gen_s": 1.0, "window_s": 4.0},
           "cell": cell, "device_kind": "TPU v5 lite"}
    bench = br.load_json(br.ROOT / "BENCHMARK.json")
    for m in bench["per_layer"]:
        reader = br.load_module(br.BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        assert v is None or np.isfinite(v), m["name"]
        if m["unit"] == "%" and v is not None:
            assert 0.0 <= v <= 100.0, m["name"]
    gen = br.load_module(br.BENCH / "metrics" / "gen_share.py")
    assert gen.read(ctx) == 25.0
    # device 0 holds the one turn's kernel event, device 1 none: half of
    # the events the turns run should leave (mean over the devices)
    kern = br.load_module(br.BENCH / "metrics" / "kernel_us_per_turn.py")
    assert kern.read(ctx) is None
    sm.devices = sm.devices[:1]  # device 0 alone: one event for one turn
    assert kern.read(ctx) == pytest.approx(0.02)
    with pytest.raises(KeyError):
        ctx["device_kind"] = "not a chip"
        from bench import kernels
        kernels.least_time_s(1.0, 1.0, "not a chip")


# -- discovery of cells by name ----------------------------------------------


def _bench_copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(br.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(br.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_cell_file_is_found_without_edits(tmp_path):
    root = _bench_copy(tmp_path)
    (root / "bench" / "workloads" / "s2x4-poisson-new.json").write_text(
        json.dumps({"config": "rosella-s2x4", "traffic": "poisson-a085-online",
                    "driver": "stream", "chips": 1, "trace_calls": 5}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "s2x4-poisson-new",
                               "config": "rosella-s2x4",
                               "traffic": "poisson-a085-online", "chips": 1,
                               "why": "test"})
    cell = br.Cell("s2x4-poisson-new", root / "bench")
    assert cell.config["n"] == 60 and cell.traffic["turns_per_call"] == 1
    assert cell.driver.Driver(cell, 3).T == 1
    e2e = [m["name"] for m in br.cell_metrics(bench, "s2x4-poisson-new", False)]
    assert e2e == ["setup_s"]
    bench["end_to_end"][1]["workloads"].append("s2x4-poisson-new")
    e2e = [m["name"] for m in br.cell_metrics(bench, "s2x4-poisson-new", False)]
    assert e2e == ["delay_p95_ms", "setup_s"]
    with pytest.raises(FileNotFoundError):
        br.Cell("no-such-cell", root / "bench")


# -- the correctness comparison ------------------------------------------------


def _sem():
    cell = br.Cell("s2x4-poisson-bulk")
    return cell, cell.driver.semantics(cell.config)


def test_control_is_not_correct():
    """The reference with a float32 event clock, put in the system's place,
    fails the limits on three seeds (400 turns: about 1,000 simulated s)."""
    cell, sem = _sem()
    from bench import control

    for seed in (11, 2**31 + 5, 987654321):
        nums = control.readings(cell, seed, 400)
        ok, _ = br.judge(nums, cell.limits)
        assert not ok, nums
        assert nums["resp_off"] > 0.5 >= 50 * cell.limits["resp_off"]


def test_reference_judges_itself_correct():
    cell, sem = _sem()
    times, costs, speeds = cell.driver.Driver(cell, 5).stream().turns(200)
    assert np.array_equal(speeds, np.broadcast_to(sem.speeds, speeds.shape))
    # a mix that gives ``permute_every_s`` permutes the speeds (sec. 6.2)
    vol = dict(cell.traffic, request_cost=1.0, permute_every_s=60.0)
    _, _, moved = trf.Stream(vol, sem.speeds, 5, sem.k).turns(200)
    assert len({tuple(x) for x in moved}) > 10
    assert np.allclose(np.sort(moved, axis=1), np.sort(sem.speeds))
    own = ref.simulate(sem, 77, times, costs, speeds)
    nums = ref.check(sem, 77, times, costs, speeds, own.workers, own.resp,
                     own.mu_trace)
    nums["overflow"] = own.pend_overflow + own.flush_overflow
    ok, _ = br.judge(nums, cell.limits)
    assert ok, nums
    assert nums["placement_mismatch"] == 0 and nums["resp_gap_s"] == 0


# -- a run with the timed path broken underneath -------------------------------

TINY = {"n": 8, "speed_set": [2.0, 1.0, 0.5, 1.5], "tiles": 2,
        "arrival_batch": 16, "pend_cap": 1024, "comp_cap": 256}


def _tiny_checkout(tmp_path):
    root = _bench_copy(tmp_path)
    cfg = json.loads((root / "bench/configs/rosella-s2x4.json").read_text())
    cfg.update(TINY)
    (root / "bench/configs/rosella-s2x4.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/poisson-a085-bulk.json").read_text())
    tr.update(turns_per_call=16)
    (root / "bench/traffic/poisson-a085-bulk.json").write_text(json.dumps(tr))
    return root


def _fresh_programs():
    import jax

    from repro.serving import scanloop

    getattr(scanloop._build_scan, "cache_clear", lambda: None)()
    jax.clear_caches()


def _run_tiny(root, seconds=0.5, workload="s2x4-poisson-bulk"):
    _fresh_programs()
    try:
        return br.run(["--workload", workload, "--seed", "4242",
                       "--seconds", str(seconds), "--trace", "0"],
                      require_chip=False, bench_dir=root / "bench",
                      compile_cache=False)
    finally:
        _fresh_programs()


def _fault_state_unchanged(mp):
    """Each call returns the state it was given (its outputs still come)."""
    import jax
    import jax.numpy as jnp

    from repro.serving import scanloop

    real = scanloop._build_scan

    def build(*a, **kw):
        run = real(*a, **kw)

        def stuck(lcfg, carry, xs):
            keep = jax.tree.map(jnp.copy, carry)
            _, ys = run(lcfg, carry, xs)
            return keep, ys
        return stuck
    mp.setattr(scanloop, "_build_scan", build)


def _fault_half_batch(mp):
    """Half of each arrival batch is left unplaced."""
    import jax.numpy as jnp

    from repro.core import scheduler

    real = scheduler._serve_step_math

    def half(*a, **kw):
        out = real(*a, **kw)
        w = out[1]
        w = jnp.where(jnp.arange(w.shape[0]) < w.shape[0] // 2, w, -1)
        return (out[0], w) + tuple(out[2:])
    mp.setattr(scheduler, "_serve_step_math", half)


def _fault_answer_altered(mp):
    """The dispatch engine's placements are shifted to the next worker."""
    from repro.core import dispatch, scheduler

    real = dispatch.dispatch

    def shifted(*a, **kw):
        res = real(*a, **kw)
        n = res.q_after.shape[0]
        return res._replace(workers=(res.workers + 1) % n)
    mp.setattr(scheduler.dsp, "dispatch", shifted)


def test_sound_tiny_run_is_correct(tmp_path):
    res = _run_tiny(_tiny_checkout(tmp_path))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"decisions_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", [
    _fault_state_unchanged, _fault_half_batch, _fault_answer_altered])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    """The exchange between chips is a fault no single-frontend cell can
    have; the other three are planted here."""
    root = _tiny_checkout(tmp_path)
    fault(monkeypatch)
    res = _run_tiny(root)
    assert res["correct"] is False, res["checks"]


# -- the window's fence ----------------------------------------------------------


class _NoTrace:
    """A tracer that records nothing: the window then ends after the
    driver's ``trace_calls`` calls."""

    def start(self):
        pass

    def stop(self):
        pass

    def gen(self):
        import contextlib

        return contextlib.nullcontext()


def test_delay_covers_device_run_without_readback(tmp_path, monkeypatch):
    """The last call of a three-call window is lengthened by a loop of
    matrix products that its outputs wait for, and the program's per-call
    read-back of the telemetry rows is left out: the window and the delay
    of that call's requests still hold its whole device run (with nothing
    to wait for but the next chunk, an unfenced window would close as soon
    as the call was launched)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.obs import windows
    from repro.serving import scanloop

    @jax.jit
    def lengthen(y, iters):
        one = jnp.ones((256, 256), jnp.float32)
        m = jnp.full((256, 256), y.ravel()[0].astype(jnp.float32))
        m = jax.lax.fori_loop(0, iters, lambda i, a: jnp.sin(a) @ one, m)
        return y + (jnp.sum(m) * 0.0).astype(y.dtype)

    cell = br.Cell("s2x4-poisson-online", _tiny_checkout(tmp_path) / "bench")
    drv = cell.driver.Driver(cell, 4242)
    drv.trace_calls = 3
    real = scanloop._build_scan
    calls = []

    def build(*a, **kw):
        run = real(*a, **kw)

        def slow_last(lcfg, carry, xs):
            carry, ys = run(lcfg, carry, xs)
            calls.append(1)
            iters = 120 if len(calls) == drv.trace_calls else 0
            return carry, (lengthen(ys[0], iters),) + tuple(ys[1:])
        return slow_last
    monkeypatch.setattr(scanloop, "_build_scan", build)
    monkeypatch.setattr(windows, "records_from_rows", lambda *a, **kw: [])
    _fresh_programs()
    try:
        drv.setup()  # compiles the call and the lengthening, unlengthened
        with jax.enable_x64(True):
            y = jnp.zeros((1, drv.sem.k), jnp.float64)
            lengthen(y, 120).block_until_ready()
            run_s = []
            for _ in range(3):
                t0 = time.perf_counter()
                lengthen(y, 120).block_until_ready()
                run_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            pending = lengthen(y, 120)
            dispatch_s = time.perf_counter() - t0
            pending.block_until_ready()
        assert dispatch_s < 0.1 * min(run_s)  # the loop runs after its launch
        calls.clear()
        host = drv.window(60.0, _NoTrace())
    finally:
        _fresh_programs()
    assert host["calls"] == len(calls) == 3
    assert host["window_s"] >= 0.5 * min(run_s)
    assert host["metrics"]["delay_p95_ms"] >= 0.5e3 * min(run_s)
    drv.release()
    ok, checks = br.judge(drv.check(), cell.limits)
    assert ok, checks
