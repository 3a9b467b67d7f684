"""Driver of the single-frontend stream: the benchmark's arrival stream fed
through ``repro.load.run_stream_scan`` (the one-program scan and its chunk
driver), ``turns_per_call`` turns of ``arrival_batch`` requests per device
call.

The chunk driver runs with ``timing=True``, which blocks on every output of
a call (placements, responses, mu_hat, telemetry) before it asks for the
next chunk, and it reads each call's telemetry rows back to the host.  The
window starts when the chunk driver asks for its first timed chunk and ends
when it asks for the first chunk after ``seconds`` have passed, so it holds
generation, copy, launch, the whole device run and the read-back of every
call in it.  A request's scheduling delay runs from the moment its chunk is
generated to the moment the driver asks for the next chunk: its call has
finished on the device by then.  (The placements themselves are copied to
the host once, after the window: the program keeps them on the device
until the stream ends.)
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as ref
from bench import traffic as trf


def semantics(cfg: dict) -> ref.Semantics:
    speeds = np.tile(np.asarray(cfg["speed_set"], float), cfg["tiles"])
    if speeds.size != cfg["n"]:
        raise ValueError("n must equal len(speed_set) * tiles")
    return ref.Semantics(
        n=cfg["n"], k=cfg["arrival_batch"], speeds=speeds,
        pend_cap=cfg["pend_cap"], comp_cap=cfg["comp_cap"],
        fake_cost=cfg["fake_cost_share"] * cfg["request_cost"],
        c0=cfg["c0"], c_window=cfg["c_window"], mu_init=cfg["mu_init"])


class Driver:
    def __init__(self, cell, seed: int):
        self.cfg = cfg = cell.config
        self.traffic = dict(cell.traffic, request_cost=cfg["request_cost"])
        self.seed = int(seed)
        self.router_seed = trf.seed_words(seed)[0]
        self.sem = semantics(cfg)
        self.T = int(cell.traffic["turns_per_call"])
        self.trace_calls = int(cell.spec["trace_calls"])

    def stream(self) -> trf.Stream:
        return trf.Stream(self.traffic, self.sem.speeds, self.seed, self.sem.k)

    # -- the system under test -------------------------------------------
    def _system(self):
        from repro import obs
        from repro.serving import router as rt

        cfg, sem = self.cfg, self.sem
        router = rt.RosellaRouter(
            sem.n, mu_bar=sem.mu_bar, policy=cfg["policy"],
            seed=self.router_seed, c0=cfg["c0"], c_window=cfg["c_window"],
            async_mu=False, use_alias=True)
        pool = rt.SimulatedPool(sem.speeds)
        ocfg = obs.ObserveConfig(window_turns=cfg["window_turns"],
                                 emit_responses=True)
        return router, pool, ocfg

    def _run(self, chunks):
        from repro.load import run_stream_scan

        router, pool, ocfg = self._system()
        return run_stream_scan(
            router, pool, chunks, fake_cost=self.sem.fake_cost,
            pend_cap=self.sem.pend_cap, comp_cap=self.sem.comp_cap,
            observe=ocfg, strict_overflow=False, timing=True)

    @staticmethod
    def _workload(times, costs, speeds):
        from repro.env.scenario import ServingWorkload

        return ServingWorkload(times, costs, speeds, None, None, None,
                               np.empty(0), 0)

    def setup(self) -> None:
        """Compile and run one call at the cell's shape, from a stream of
        this seed (the window starts a fresh stream and a fresh router)."""
        resp, _, _ = self._run([self._workload(*self.stream().turns(self.T))])
        np.asarray(resp)

    # -- the timed window ------------------------------------------------
    def window(self, seconds: float, tracer=None) -> dict:
        stream = self.stream()
        log = []  # per call: (t_enter, t_ready, t_back)
        drawn = []
        limit = self.trace_calls if tracer is not None else None
        state = {"t0": None, "end": None}

        def chunks():
            while True:
                t_enter = time.perf_counter()
                if state["t0"] is None:
                    state["t0"] = t_enter
                    if tracer is not None:
                        tracer.start()
                if log:
                    log[-1][2] = t_enter
                    done = (len(log) >= limit if limit is not None
                            else t_enter - state["t0"] >= seconds)
                    if done:
                        state["end"] = t_enter
                        if tracer is not None:
                            tracer.stop()
                        return
                if tracer is not None:
                    with tracer.gen():
                        turn = stream.turns(self.T)
                else:
                    turn = stream.turns(self.T)
                drawn.append(turn)
                log.append([t_enter, time.perf_counter(), None])
                yield self._workload(*turn)

        resp, mu_trace, info = self._run(chunks())
        self._out = (np.asarray(resp), np.asarray(mu_trace),
                     np.asarray(info["workers"]),
                     int(info["flush_overflow"]) + int(info["pend_overflow"]))
        self._drawn = tuple(np.concatenate(x) for x in zip(*drawn))
        log = np.asarray(log, float)
        window_s = state["end"] - state["t0"]
        requests = len(log) * self.T * self.sem.k
        delay = np.repeat(log[:, 2] - log[:, 1], self.T * self.sem.k)
        return {
            "attempted": requests,
            "calls": len(log),
            "turns": len(log) * self.T,
            "window_s": window_s,
            "gen_s": float(np.sum(log[:, 1] - log[:, 0])),
            "metrics": {
                "decisions_per_s": requests / window_s,
                "delay_p95_ms": 1e3 * float(np.percentile(delay, 95)),
            },
        }

    def release(self) -> None:
        """Drop the system's device state before the reference runs."""
        import gc

        gc.collect()

    # -- correctness -----------------------------------------------------
    def check(self) -> dict:
        resp, mu_trace, workers, overflow = self._out
        times, costs, speeds = self._drawn
        out = ref.check(self.sem, self.router_seed, times, costs, speeds,
                        workers, resp, mu_trace)
        out["overflow"] = overflow
        return out
