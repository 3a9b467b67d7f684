"""Traffic generator: the Rosella paper's synthetic open arrival stream.

One general generator reads every traffic mix (``bench/traffic/<name>.json``).
The process is the one of the paper's synthetic experiments (arXiv
2010.15206, section 6.2): Poisson arrivals at ``load`` (the load ratio
alpha) times the cluster's total speed, and exponential request costs of
mean ``request_cost``, so that a worker of speed mu serves a request in an
exponential time of mean ``request_cost / mu``.  Where the mix gives
``permute_every_s``, the cluster is volatile as in section 6.2: every so
many simulated seconds the workers' speeds are permuted at random (the
total stays constant), from the first turn whose last arrival reaches the
instant.  Times are simulated seconds.

Everything is drawn from two numpy streams seeded from ``--seed``: the same
seed gives the same arrival times, costs and speed permutations.  The
system under test receives only the generated arrays.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> tuple[int, int, int]:
    """Three 31-bit words from any whole-number seed (the router's PRNG
    seed, the permutations' stream, the arrival stream)."""
    w = np.random.SeedSequence(int(seed)).generate_state(3, np.uint32)
    return tuple(int(x) & 0x7FFFFFFF for x in w)


class Stream:
    """Turns of ``k`` arrivals each, drawn lazily: ``turns(T)`` returns
    ``(times f64[T, k], costs f64[T, k], speeds f64[T, n])`` continuing
    the stream; ``speeds[t]`` are the workers' speeds entering turn t."""

    def __init__(self, params: dict, speeds, seed: int, k: int):
        if params.get("shape") != "poisson" or params.get("cost") != "exponential":
            raise ValueError("the generator draws Poisson arrivals and "
                             "exponential costs only")
        _, s_env, s_arr = seed_words(seed)
        self.speeds = np.asarray(speeds, np.float64)
        self.rate = float(params["load"]) * float(self.speeds.sum())
        self.cost = float(params["request_cost"])
        self.period = float(params.get("permute_every_s") or np.inf)
        self.k = int(k)
        self.rng = np.random.RandomState(s_arr)
        self.env = np.random.RandomState(s_env)
        self.t = 0.0
        self.phase = 0
        self.current = (self.speeds if np.isinf(self.period) else
                        self.speeds[self.env.permutation(self.speeds.size)])

    def _speeds_at(self, t_last: float) -> np.ndarray:
        while (self.phase + 1) * self.period <= t_last:
            self.phase += 1
            self.current = self.speeds[self.env.permutation(self.speeds.size)]
        return self.current

    def turns(self, T: int):
        gaps = self.rng.exponential(1.0 / self.rate, size=(T, self.k))
        times = self.t + np.cumsum(gaps.reshape(-1)).reshape(T, self.k)
        self.t = float(times[-1, -1])
        costs = self.cost * self.rng.exponential(1.0, size=(T, self.k))
        speeds = np.stack([self._speeds_at(float(x)) for x in times[:, -1]])
        return times, costs, speeds
