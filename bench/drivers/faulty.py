"""Driver of the single-frontend stream under worker crashes: the
benchmark's arrival stream (``bench/traffic.py``) and its fault columns
(``bench/faults.py``) fed through ``repro.load.run_stream_scan`` with the
recovery layer on (timeouts, retries, backup copies), ``turns_per_call``
turns of ``arrival_batch`` requests per device call.

The window is the stream driver's (``bench/drivers/stream.py``), and so is
``decisions_per_s``: the arrivals placed in the window over its wall
seconds; retry and backup copies do not count.  ``check`` judges every
arrival, retry and backup placement, every task's response, mu_hat and the
conservation ledger against ``bench/recovery_reference.py``.
"""
from __future__ import annotations

import math

import numpy as np

from bench import faults as bfl
from bench import recovery_reference as rref
from bench.drivers import stream


class Driver(stream.Driver):
    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        cfg = self.cfg
        self.rc = rref.Recovery.from_config(cfg["recovery"])
        self.fault_params = {
            "mttf": cfg["mttf"], "mean_down": cfg["mean_down"],
            "anchor": cfg["fault_anchor"], "probe_burst": cfg["probe_burst"]}
        self.burst_cost = cfg["burst_cost_share"] * cfg["request_cost"]
        # the task-indexed response buffer rides the program's carry, so its
        # size is part of the program: fixed before set-up, for a window of
        # up to ``window_s`` at ``requests_per_s``, in whole calls (plus the
        # call that ends the window)
        cap = cfg["task_cap"]
        per_call = self.T * self.sem.k
        self.window_cap_s = float(cap["window_s"])
        self.task_cap = per_call * (math.ceil(
            self.window_cap_s * cap["requests_per_s"] / per_call) + 1)
        self._cols = []

    def stream(self):
        self.faults = bfl.Faults(self.fault_params, self.sem.n, self.seed)
        self._cols = []
        return super().stream()

    def _workload(self, times, costs, speeds):
        from repro.env.scenario import ServingWorkload

        active, rejoin, burst, kill = self.faults.columns(times[:, -1])
        self._cols.append((active, rejoin, burst, kill))
        return ServingWorkload(times, costs, speeds, active, rejoin, burst,
                               np.empty(0), 0, kill_at=kill)

    def _run(self, chunks):
        from repro.load import run_stream_scan
        from repro.serving.recovery import RecoveryConfig

        router, pool, ocfg = self._system()
        resp, mu_trace, info = run_stream_scan(
            router, pool, chunks, fake_cost=self.sem.fake_cost,
            burst_cost=self.burst_cost,
            recovery=RecoveryConfig(**vars(self.rc)),
            pend_cap=self.sem.pend_cap, comp_cap=self.sem.comp_cap,
            task_cap=self.task_cap, observe=ocfg, strict_overflow=False,
            timing=True)
        self._info = info
        return resp, mu_trace, info

    def setup(self) -> None:
        super().setup()
        if "retry_tasks" not in self._info:
            raise RuntimeError("the program hands back no retry and backup "
                               "placements: the crash cell cannot be judged")

    def window(self, seconds: float, tracer=None) -> dict:
        if seconds > self.window_cap_s:
            raise ValueError(f"a window of {seconds} s exceeds the "
                             f"{self.window_cap_s} s the task buffer holds")
        return super().window(seconds, tracer)

    def check(self) -> dict:
        resp, mu_trace, workers, overflow = self._out
        times, costs, speeds = self._drawn
        info = self._info
        cols = rref.Columns(*(np.concatenate(x) for x in zip(*self._cols)))
        answers = rref.Answers(workers, info["retry_workers"],
                               info["spec_workers"], info["retry_tasks"],
                               info["spec_tasks"], resp, mu_trace,
                               info["ledger"])
        out = rref.check(self.sem, self.rc, self.router_seed, times, costs,
                         speeds, cols, answers, burst_cost=self.burst_cost)
        out["overflow"] = overflow
        return out
