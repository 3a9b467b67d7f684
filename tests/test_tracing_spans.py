"""Profiler names of the program (``repro.obs.tracing``): the stage scopes
every scan body carries into its HLO metadata, and the phase spans of the
chunk driver's calls, recorded by a CPU profiler session."""
from __future__ import annotations

import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.env.scenario import ServingWorkload
from repro.load import run_stream_scan
from repro.obs import tracing as obt
from repro.serving import router as rt
from repro.serving import scanloop
from repro.serving.recovery import RecoveryConfig

N, K, PEND_CAP, TURNS = 8, 4, 64, 4
SPEEDS = np.linspace(0.5, 2.0, N)
OCFG = obs.ObserveConfig(window_turns=4)
LOC = re.compile(r'loc\("([^"]*)"')
SCOPE = re.compile(r"rosella\.[a-z_]+")


class _Lowered(Exception):
    """Raised in place of running a scan once its program is lowered."""


def _scopes_of(monkeypatch, builder: str, call) -> set:
    """The ``rosella.*`` scope names in the location metadata (the source of
    each HLO instruction's ``op_name``) of the program ``builder`` makes
    for ``call``; the program is lowered, never compiled or run."""
    orig = getattr(scanloop, builder)
    texts = []

    def spy(*a, **kw):
        run = orig(*a, **kw)

        def lowered(*args):
            texts.append(run.lower(*args).as_text(debug_info=True))
            raise _Lowered

        return lowered

    monkeypatch.setattr(scanloop, builder, spy)
    with pytest.raises(_Lowered):
        call()
    return {scope for loc in LOC.findall(texts[0])
            for scope in SCOPE.findall(loc)}


def _workload(turns=TURNS, seed=0):
    rng = np.random.default_rng(seed)
    times = np.cumsum(rng.exponential(0.1, (turns * K,))).reshape(turns, K)
    costs = rng.exponential(1.0, (turns, K))
    speeds = np.tile(SPEEDS, (turns, 1))
    return times, costs, speeds


def _router():
    return rt.RosellaRouter(N, mu_bar=float(SPEEDS.sum()), policy="ppot_sq2",
                            seed=0, async_mu=False, use_alias=True)


ALL_STAGES = {obt.PREFIX + s for s in obt.STAGES}
RECOVERY_SCOPES = {obt.PREFIX + s for s in obt.RECOVERY_STAGES}


def test_plain_scan_carries_every_stage_scope(monkeypatch):
    times, costs, speeds = _workload()
    got = _scopes_of(monkeypatch, "_build_scan", lambda: (
        scanloop.run_workload_scan(
            _router(), rt.SimulatedPool(SPEEDS), times, costs, speeds,
            pend_cap=PEND_CAP, observe=OCFG)))
    assert got == ALL_STAGES


def test_faulty_scan_carries_the_stage_scopes_it_shares(monkeypatch):
    """The plain body's stages and the three recovery stages."""
    times, costs, speeds = _workload()
    kill = np.full((TURNS, N), np.inf)
    rc = RecoveryConfig(timeout_mult=8.0, retry_budget=2, retry_cap=4,
                        spec_cap=2)
    got = _scopes_of(monkeypatch, "_build_scan_faulty", lambda: (
        scanloop.run_workload_scan(
            _router(), rt.SimulatedPool(SPEEDS), times, costs, speeds,
            kill_np=kill, recovery=rc, pend_cap=PEND_CAP, observe=OCFG)))
    assert got == ALL_STAGES | RECOVERY_SCOPES


@pytest.mark.parametrize("frozen_mu", [False, True])
def test_fleet_scan_carries_every_stage_scope(monkeypatch, frozen_mu):
    times, costs, speeds = _workload()
    router = rt.FleetRouter(2, N, mu_bar=float(SPEEDS.sum()), seed=0,
                            async_mu=False)
    got = _scopes_of(monkeypatch, "_build_fleet_scan", lambda: (
        scanloop.run_fleet_workload_scan(
            router, rt.SimulatedPool(SPEEDS), times, costs, speeds,
            pend_cap=PEND_CAP, frozen_mu=frozen_mu, observe=OCFG)))
    assert got == ALL_STAGES


def test_stage_rejects_unknown_names():
    with pytest.raises(ValueError):
        obt.stage("sort")
    with pytest.raises(ValueError):
        obt.recovery_stage("flush")
    with obt.DriverCall(0) as call, pytest.raises(ValueError):
        call.phase("compile")


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(obt.PREFIX)]
    return spans


def test_driver_calls_hold_disjoint_phases_and_timings(tmp_path):
    """Three chunks through the streamed scan under a CPU profiler session:
    one ``rosella.call`` span per chunk, each holding the five phases in
    order, nested and disjoint; the pull that finds the stream's end opens
    a call that holds only ``rosella.next_chunk``.  ``info["chunks"]``
    carries the phase seconds and counts."""
    chunk_turns, n_chunks = 8, 3
    times, costs, speeds = _workload(chunk_turns * n_chunks)
    chunks = [ServingWorkload(times[s:s + chunk_turns],
                              costs[s:s + chunk_turns],
                              speeds[s:s + chunk_turns], None, None, None,
                              np.empty(0), 0)
              for s in range(0, chunk_turns * n_chunks, chunk_turns)]
    cfg = obs.ObserveConfig(window_turns=4)
    run_stream_scan(_router(), rt.SimulatedPool(SPEEDS), chunks[:1],
                    pend_cap=PEND_CAP, observe=cfg)  # compile outside
    with jax.profiler.trace(str(tmp_path)):
        _, _, info = run_stream_scan(
            _router(), rt.SimulatedPool(SPEEDS), chunks, pend_cap=PEND_CAP,
            observe=cfg, timing=True)
    spans = _host_spans(tmp_path)
    calls = sorted((s, e, int(st["step_num"])) for n, s, e, st in spans
                   if n == obt.CALL)
    phases = [(n[len(obt.PREFIX):], s, e) for n, s, e, _ in spans
              if n != obt.CALL]
    assert [c[2] for c in calls] == list(range(n_chunks + 1))
    for c_start, c_end, step in calls:
        inside = sorted((s, e, n) for n, s, e in phases
                        if c_start <= s and e <= c_end)
        names = [n for _, _, n in inside]
        if step == n_chunks:
            assert names == ["next_chunk"]
            continue
        assert names == list(obt.PHASES)
        for (_, e0, _), (s1, _, _) in zip(inside, inside[1:]):
            assert e0 <= s1  # disjoint
    assert len(phases) == 5 * n_chunks + 1  # every phase lies in a call

    meta = info["chunks"]
    assert len(meta) == n_chunks
    for m in meta:
        for key in ("gen_s", "h2d_s", "launch_s", "fence_s", "readback_s"):
            assert m[key] >= 0.0, key
        assert m["launch_s"] + m["fence_s"] <= m["run_s"]
        assert m["bytes_in"] == 8 * chunk_turns * (2 * K + N)
        assert m["rss_mb"] > 0
    assert sum(m["windows"] for m in meta) == len(info["windows"])
    assert len(info["windows"]) == chunk_turns * n_chunks // 4


def test_bench_names_are_the_programs():
    """The benchmark's reader names the stages and phases the program
    emits (it imports nothing of the program, so it lists them itself)."""
    from bench import stages

    assert stages.PREFIX == obt.PREFIX
    assert stages.STAGES == obt.STAGES
    assert stages.CALL == obt.CALL
    assert stages.PHASES == obt.PHASES


def test_bench_recovery_names_are_the_programs():
    """The crash cell's reader names the recovery stages the faulty body
    emits."""
    from bench import recovery_stages

    assert recovery_stages.RECOVERY_STAGES == obt.RECOVERY_STAGES
    assert set(recovery_stages.SCOPES) == set(obt.STAGES + obt.RECOVERY_STAGES)
