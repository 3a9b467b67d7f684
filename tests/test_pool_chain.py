"""The replica-pool chain (``scanloop.pool_chain``), run one rank within
worker at a time across all workers: bit-equal to ``SequentialPool``'s
per-submission recurrence on the gated submissions, in as many steps as
the busiest worker has active submissions; and its counter in the scan's
``info["pool_chain_steps"]``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import (
    RosellaRouter,
    SequentialPool,
    run_simulation,
    run_simulation_scan,
)
from repro.serving.scanloop import pool_chain

N_FAKE = 8  # the scan's max_fake


def _turn(rng, n, m, *, p=None, fake_on=0.5, t=10.0):
    """One turn's submissions as the scan lays them out: ``N_FAKE`` fakes
    at the turn's end time ``t`` (each active with probability
    ``fake_on``), then the reals in arrival order."""
    speeds = rng.uniform(0.15, 2.0, n)
    w = rng.choice(n, m, p=p(speeds) if p else None)
    arr = np.concatenate([np.full(N_FAKE, t),
                          np.sort(rng.uniform(0.0, t, m - N_FAKE))])
    cost = np.concatenate([np.full(N_FAKE, 0.25),
                           rng.exponential(1.0, m - N_FAKE)])
    act = np.concatenate([rng.random(N_FAKE) < fake_on,
                          np.ones(m - N_FAKE, bool)])
    free_at = rng.uniform(0.0, 1.5 * t, n)
    return dict(free_at=free_at, w=w, arr=arr, cost=cost, act=act,
                speeds=speeds)


def _cell(rng):  # the benchmark cell's shape, placements weighted by speed
    return _turn(rng, 60, 136, p=lambda s: s / s.sum())


def _one_worker(rng):
    c = _turn(rng, 8, 40, fake_on=1.0)
    c["w"][:] = 3
    return c


def _inactive_fakes(rng):
    c = _turn(rng, 6, 30, fake_on=1.0)
    c["act"][[0, 2, 5, 7]] = False
    c["w"][:N_FAKE] = c["w"][N_FAKE]  # the dropped fakes share a real's worker
    return c


def _fakes_ahead(rng):
    """Every fake is active, at the turn's end, on the workers the reals go
    to: the reals queue behind work that arrived after them."""
    c = _turn(rng, 4, 24, fake_on=1.0, t=5.0)
    c["w"][:N_FAKE] = c["w"][N_FAKE:2 * N_FAKE]
    c["free_at"][:] = 0.0
    return c


def _idle_workers(rng):
    c = _turn(rng, 16, 40)
    c["w"] = c["w"] % 5  # workers 5..15 get nothing
    return c


def _busy_pool(rng):
    c = _turn(rng, 10, 50)
    c["free_at"] = c["arr"].max() + rng.uniform(1.0, 3.0, 10)
    return c


def _nothing_active(rng):
    c = _turn(rng, 10, 20)
    c["act"][:] = False
    return c


CASES = {
    "cell_shape": _cell,
    "one_worker": _one_worker,
    "inactive_fakes": _inactive_fakes,
    "fakes_ahead_of_reals": _fakes_ahead,
    "idle_workers": _idle_workers,
    "busy_pool": _busy_pool,
    "nothing_active": _nothing_active,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_pool_chain_matches_sequential_pool(name):
    c = CASES[name](np.random.default_rng(sorted(CASES).index(name)))
    act = c["act"]
    host = SequentialPool(c["speeds"])
    host.free_at = c["free_at"].copy()
    start_h, done_h = host.submit_batch(c["w"][act], c["arr"][act],
                                        c["cost"][act])
    with jax.enable_x64(True):
        fa, start, done, steps = jax.jit(pool_chain)(
            jnp.asarray(c["free_at"]), jnp.asarray(c["w"], jnp.int32),
            jnp.asarray(c["arr"]), jnp.asarray(c["cost"]), jnp.asarray(act),
            jnp.asarray(c["speeds"]))
    np.testing.assert_array_equal(np.asarray(fa), host.free_at)
    np.testing.assert_array_equal(np.asarray(start)[act], start_h)
    np.testing.assert_array_equal(np.asarray(done)[act], done_h)
    most = np.bincount(c["w"][act], minlength=len(c["speeds"])).max()
    assert int(steps) == most
    assert steps.dtype == jnp.int32
    if name == "one_worker":
        assert int(steps) == len(act)
    if name == "nothing_active":
        assert int(steps) == 0
        np.testing.assert_array_equal(np.asarray(fa), c["free_at"])


class _Recording(SequentialPool):
    """Keeps each turn's submitted workers: the host loop submits a turn's
    fakes (when any, at most ``N_FAKE`` < k), then its k reals."""

    def __init__(self, speeds, k):
        super().__init__(speeds)
        self.k, self.turns, self._fakes = k, [], np.empty(0, np.int64)

    def submit_batch(self, replicas, arrivals, costs):
        replicas = np.asarray(replicas, np.int64)
        if len(replicas) < self.k:
            self._fakes = replicas
        else:
            self.turns.append((self._fakes, replicas))
            self._fakes = np.empty(0, np.int64)
        return super().submit_batch(replicas, arrivals, costs)


def test_scan_counts_pool_chain_steps():
    """``info["pool_chain_steps"]`` is the sum over turns of the most jobs
    any one worker got in the turn, fakes included, recomputed from the
    host loop's submissions (bit-equal to the scan's placements)."""
    speeds = np.array([0.25, 0.5, 1.0, 2.0])
    kw = dict(arrival_rate=3.0, horizon=60.0, seed=3, arrival_batch=16)
    host = _Recording(speeds, kw["arrival_batch"])
    run_simulation(RosellaRouter(4, mu_bar=speeds.sum(), seed=0,
                                 async_mu=False), host, **kw)
    _, _, info = run_simulation_scan(
        RosellaRouter(4, mu_bar=speeds.sum(), seed=0, async_mu=False),
        SequentialPool(speeds), **kw)
    assert info["turns"] == len(host.turns)
    reals = np.concatenate([r for _, r in host.turns])
    np.testing.assert_array_equal(info["workers"], reals)
    want = sum(np.bincount(np.concatenate([f, r]), minlength=4).max()
               for f, r in host.turns)
    assert info["pool_chain_steps"] == want
    assert any(len(f) for f, _ in host.turns)  # fakes took part
