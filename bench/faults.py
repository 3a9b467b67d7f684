"""Fault columns of the crash cell: whole-machine fail-stop crashes with
exponential up and down times, drawn from ``--seed``.

Every worker but the anchor alternates up and down: it crashes after an
Exp(``mttf``) up time (simulated seconds) and comes back after an
Exp(``mean_down``) down time, then starts over.  The anchor never crashes,
so the cluster always keeps a live worker.  Each worker draws from its own
stream (spawned from the seed), so the outages do not depend on how the
turns are cut into calls.

Per turn the columns say what the scheduler is told (the turn's time is
its last arrival):

* ``active[t, w]``: worker w is up at turn t, i.e. no outage holds
  ``t0 <= time[t] < t1``;
* ``kill[t, w]``: the instant of the earliest crash of w that the turn is
  the first to reach (``time[t - 1] < t0 <= time[t]``), +inf where none;
  every copy on w that would finish after it is lost;
* ``rejoin[t, w]``: w is up at turn t and was down at turn t - 1 (the
  stream's first turn has no rejoin);
* ``burst[t]``: ``probe_burst`` probe jobs for each rejoining worker, in
  worker order, padded with -1 to ``n * probe_burst`` slots.

This file imports nothing of the system under test.
"""
from __future__ import annotations

import numpy as np


def fault_seed(seed: int) -> int:
    """The fault stream's word of ``--seed`` (beside the three words
    ``bench/traffic.seed_words`` draws for router, speeds and arrivals)."""
    w = np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)
    return int(w[3]) & 0x7FFFFFFF


class Faults:
    """Lazy fault columns for a stream of turns: ``columns(t_end)`` takes
    the turns' times (f64[T], nondecreasing, continuing the stream) and
    returns ``(active, rejoin, burst, kill)``."""

    def __init__(self, params: dict, n: int, seed: int):
        self.n = int(n)
        self.mttf = float(params["mttf"])
        self.mean_down = float(params["mean_down"])
        self.anchor = int(params["anchor"])
        self.probe_burst = int(params["probe_burst"])
        self.burst_cap = self.n * self.probe_burst
        kids = np.random.SeedSequence(fault_seed(seed)).spawn(self.n)
        self.rngs = [np.random.default_rng(s) for s in kids]
        # per worker: outages [t0, t1) drawn so far, and the next crash
        self.outages = [[] for _ in range(self.n)]
        self.next_up = [float(r.exponential(self.mttf)) for r in self.rngs]
        self.prev_time = -np.inf
        self.prev_active = None

    def _draw_until(self, horizon: float) -> None:
        for w in range(self.n):
            if w == self.anchor:
                continue
            rng, t = self.rngs[w], self.next_up[w]
            while t <= horizon:
                t1 = t + float(rng.exponential(self.mean_down))
                self.outages[w].append((t, t1))
                t = t1 + float(rng.exponential(self.mttf))
            self.next_up[w] = t

    def columns(self, t_end):
        t_end = np.asarray(t_end, np.float64)
        T, n = t_end.size, self.n
        self._draw_until(float(t_end[-1]))
        active = np.ones((T, n), bool)
        kill = np.full((T, n), np.inf)
        for w in range(n):
            live = []  # outages that can still touch a later turn
            for t0, t1 in self.outages[w]:
                if t1 <= self.prev_time:
                    continue
                live.append((t0, t1))
                active[:, w] &= ~((t0 <= t_end) & (t_end < t1))
                if self.prev_time < t0 <= t_end[-1]:
                    ti = int(np.searchsorted(t_end, t0, side="left"))
                    kill[ti, w] = min(kill[ti, w], t0)
            self.outages[w] = live
        prev = np.concatenate([
            (active[:1] if self.prev_active is None
             else self.prev_active[None, :]), active[:-1]])
        rejoin = active & ~prev
        burst = np.full((T, self.burst_cap), -1, np.int32)
        for ti in np.nonzero(rejoin.any(axis=1))[0]:
            ids = np.repeat(np.nonzero(rejoin[ti])[0], self.probe_burst)
            burst[ti, :ids.size] = ids
        self.prev_time = float(t_end[-1])
        self.prev_active = active[-1].copy()
        return active, rejoin, burst, kill
