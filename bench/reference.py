"""Plain reference of Rosella's single-frontend serving semantics.

A straightforward numpy implementation, turn by turn, of what the system
under test computes for one frontend in front of ``n`` heterogeneous
workers (the Rosella paper, arXiv 2010.15206, sections 3-5):

* completion flush: every in-flight job whose done time has passed by the
  turn's last arrival leaves, oldest done first (ties in submission order),
  at most ``comp_cap`` a turn;
* learner (Fig. 6): each completion's service time enters its worker's
  ring; mu_hat = (1 - eps) / mean of the last min(2.25 L, ring) samples,
  zero when the last L samples span too long; L = c / (1 - alpha);
* arrival estimator: an EMA of inter-arrival gaps (window 64);
* benchmark ("fake") jobs: min(Poisson(c0 (mu_bar - lam_hat) dt), 8) jobs
  at uniform workers;
* PPoT-SQ(2): two probes drawn in proportion to mu_hat through a Walker
  alias table, the shorter queue of the two wins (ties to the first), all
  probes of a turn against one queue snapshot;
* worker pool: FIFO per worker, ``start = max(arrival, free_at)``,
  ``done = start + cost / speed``, at the speeds entering the turn in which
  the job is placed; the fake jobs of a turn arrive at its last arrival
  time, before its requests.

The random draws follow the stream the semantics fixes: the router's
threefry key splits twice a turn (fake draw, probe draw), and the uniforms
are the murmur3 counter hash of the key words.  Nothing here imports the
system under test or takes its state; ``check`` takes only its answers.

Scheduling decisions depend on every earlier decision, so the reference
judges each turn's answers given the system's earlier placements (the
answers), never its internal state: the pool runs the system's placements,
and the reference's own PPoT decision for each request is compared with
the system's.  The event clock is float64 as the configuration states;
``clock=np.float32`` gives the lower-precision control.
"""
from __future__ import annotations

import dataclasses
import struct

import numpy as np

F32 = np.float32
RING_CAP = 128
AVG_WINDOW_MULT = 2.25
EMA_WINDOW = 64
MAX_FAKE = 8
#: A response is "off" when it differs from the reference's by more than
#: this many simulated seconds: about 1e4 times the error of the float64
#: event clock that XLA emulates on the TPU (6.5e-11 s on clocks near
#: 1.3e3 s), and about 1e-3 of the float32 clock's rounding at 1e4 s.
RESP_TOL = 1e-6
#: A mu_hat entry is "off" when it differs by more than this share of the
#: largest mu_hat of its turn: about 100 float32 ulps.
MU_RTOL = 1e-5
#: float32 sums over the learner's rings and the alias normalisation are
#: not ordered by the semantics, so mu_hat, and the alias thresholds built
#: from it, may differ by a few ulps between two sound implementations; the
#: pairing loop carries each residual's error into the next bin (measured:
#: up to 8.6e-6 apart).  A probe whose acceptance draw lies within this
#: distance of its bin's threshold may resolve either way (the draws lie on
#: a 2^-16 grid, so about 2 draws in 65,536 are judged so).
PROBE_TOL = 3e-5


@dataclasses.dataclass
class Semantics:
    n: int
    k: int
    speeds: np.ndarray  # f64[n] the speed set (volatility permutes it)
    pend_cap: int
    comp_cap: int
    fake_cost: float
    c0: float = 0.1
    c_window: float = 10.0
    mu_init: float = 1.0

    @property
    def mu_bar(self) -> float:
        return float(np.sum(self.speeds))


def _fmix32(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _weyl(kd, B):
    return np.arange(B, dtype=np.uint32) * np.uint32(0x9E3779B9) + kd[0]


def uniform_pair(kd, B):
    with np.errstate(over="ignore"):
        h = _fmix32(_weyl(kd, B) ^ (kd[1] * np.uint32(0x85EBCA6B)))
    sc = F32(1.0 / 65536.0)
    return ((h >> np.uint32(16)).astype(F32) * sc,
            (h & np.uint32(0xFFFF)).astype(F32) * sc)


def uniform_quad(kd, B):
    with np.errstate(over="ignore"):
        x = _weyl(kd, B)
        h1 = _fmix32(x ^ (kd[1] * np.uint32(0x85EBCA6B)))
        h2 = _fmix32((x + np.uint32(0x7F4A7C15))
                     ^ (kd[1] * np.uint32(0xC2B2AE35)))
    sc = F32(1.0 / 65536.0)
    return ((h1 >> np.uint32(16)).astype(F32) * sc,
            (h1 & np.uint32(0xFFFF)).astype(F32) * sc,
            (h2 >> np.uint32(16)).astype(F32) * sc,
            (h2 & np.uint32(0xFFFF)).astype(F32) * sc)


_F32 = struct.Struct("f")


def _f32(x: float) -> float:
    """``x`` rounded to float32 (the difference of two float32 numbers of
    like size is exact in float64, so this is float32 arithmetic)."""
    return _F32.unpack(_F32.pack(x))[0]


def alias_table(mu):
    """Vose's alias construction: smalls paired from the top of their
    stack with the current large, one bin finalised per step."""
    n = mu.shape[0]
    total = np.sum(mu, dtype=F32)
    w = mu if total > 0 else np.ones_like(mu)
    p = (w * (F32(n) / np.sum(w, dtype=F32))).astype(F32)
    idx = np.arange(n)
    small = p < 1.0
    stack = idx[small].tolist() + idx[~small].tolist()
    ns, nl = int(small.sum()), n - int(small.sum())
    p = p.tolist()
    prob = [1.0] * n
    alias = list(range(n))
    for _ in range(n):
        has_s, has_l = ns > 0, nl > 0
        s = stack[max(ns - 1, 0)]
        l = stack[n - max(nl, 1)]
        if has_s and has_l:
            prob[s] = p[s]
            alias[s] = l
            pl = _f32(p[l] - _f32(1.0 - p[s]))
            p[l] = pl
            if pl < 1.0:
                stack[ns - 1] = l
                nl -= 1
            else:
                ns -= 1
        elif has_s:
            prob[s], alias[s] = 1.0, s
            ns -= 1
        else:
            prob[l], alias[l] = 1.0, l
            nl -= 1
    return np.asarray(prob, F32), np.asarray(alias, np.int64)


def alias_draw(prob, alias, u, v):
    """(probe, other) per draw: the probe, and the worker it resolves to if
    its acceptance draw lies within PROBE_TOL of the threshold on the other
    side (else the probe again)."""
    n = prob.shape[0]
    i = np.minimum((u * F32(n)).astype(np.int64), n - 1)
    j = np.where(v < prob[i], i, alias[i])
    near = np.abs(v.astype(np.float64) - prob[i]) < PROBE_TOL
    return j, np.where(near, np.where(j == i, alias[i], i), j)


class Learner:
    def __init__(self, sem: Semantics):
        n = sem.n
        self.samples = np.zeros((n, RING_CAP), F32)
        self.stamps = np.zeros((n, RING_CAP), F32)
        self.widx = np.zeros(n, np.int64)
        self.count = np.zeros(n, np.int64)
        self.epoch = np.zeros(n, F32)
        self.mu = np.full(n, F32(sem.mu_init), F32)
        self.mu_bar = F32(sem.mu_bar)
        self.c_window = F32(sem.c_window)
        self.n = n

    def record(self, workers, service, now32):
        """Append each completion to its worker's ring, in flush order (a
        later write to the same slot wins, as one at a time would)."""
        order = np.argsort(workers, kind="stable")
        w = workers[order]
        first = np.searchsorted(w, w, side="left")
        rank = np.empty_like(order)
        rank[order] = np.arange(w.size) - first
        slot = (self.widx[workers] + rank) % RING_CAP
        self.samples[workers, slot] = service
        self.stamps[workers, slot] = now32
        counts = np.bincount(workers, minlength=self.n)
        self.widx = (self.widx + counts) % RING_CAP
        self.count = self.count + counts

    def refresh(self, lam, now32):
        n, cap = self.n, RING_CAP
        alpha = np.clip(F32(lam / np.maximum(self.mu_bar, F32(1e-9))),
                        F32(0.0), F32(0.999))
        eps = F32(F32(0.3) * F32(F32(1.0) - alpha))
        avg_rate = F32(self.mu_bar / F32(n))
        mu_star = F32(F32(F32(F32(1.0) - alpha) / F32(10.0)) * avg_rate)
        L_f = F32(self.c_window / np.maximum(F32(F32(1.0) - alpha), F32(1e-3)))
        L = int(min(max(int(np.ceil(L_f)), 1), cap))
        lanes = np.arange(cap)[None, :]
        age = (self.widx[:, None] - 1 - lanes) % cap
        L_avg = min(int(AVG_WINDOW_MULT * L), cap)
        kk = np.minimum(self.count, L_avg)[:, None]
        valid = (age < kk) & (lanes < np.minimum(self.count, cap)[:, None])
        sums = np.sum(np.where(valid, self.samples, F32(0.0)), axis=1,
                      dtype=F32)
        nval = np.maximum(np.sum(valid, axis=1), 1).astype(F32)
        q_hat = (sums / nval).astype(F32)
        mu_new = np.where(
            self.count > 0,
            F32(F32(1.0) - eps) / np.maximum(q_hat, F32(1e-9)),
            self.mu).astype(F32)
        t_lth = self.stamps[np.arange(n), (self.widx - L) % cap]
        t_ref = np.where(self.count >= L, t_lth, self.epoch)
        horizon = F32(F32(F32(F32(1.0) + eps) * F32(L))
                      / np.maximum(mu_star, F32(1e-9)))
        too_slow = (F32(now32) - t_ref).astype(F32) > horizon
        self.mu = np.where(too_slow, F32(0.0), mu_new).astype(F32)


_LOGFACT = np.concatenate([
    np.zeros(1, F32),
    np.cumsum(np.log(np.arange(1, MAX_FAKE + 1, dtype=F32)), dtype=F32),
]).astype(F32)


def fake_jobs(sem: Semantics, kd, lam, dt):
    nu = F32(F32(sem.c0) * np.maximum(F32(F32(sem.mu_bar) - lam), F32(0.0)))
    lam_p = F32(nu * np.maximum(dt, F32(0.0)))
    u1, u2 = uniform_pair(kd, MAX_FAKE)
    ks = np.arange(MAX_FAKE + 1, dtype=F32)
    logp = (ks * np.log(np.maximum(lam_p, F32(1e-30)))
            - lam_p - _LOGFACT).astype(F32)
    cdf = np.cumsum(np.exp(logp), dtype=F32)
    cnt = int(np.sum(cdf <= u1[0]))
    js = (u2 * F32(sem.n)).astype(np.int64)
    return js[:cnt]


def key_chain(seed: int, turns: int):
    """uint32 words of (k_fake, k_route) for each turn, from the router's
    threefry key ``PRNGKey(seed)`` split twice a turn."""
    import jax

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        def step(key, _):
            key1, k_fake = jax.random.split(key)
            key2, k_route = jax.random.split(key1)
            return key2, (k_fake, k_route)

        _, (kf, kr) = jax.jit(lambda k: jax.lax.scan(
            step, k, None, length=turns))(jax.random.PRNGKey(seed))
        return np.asarray(kf, np.uint32), np.asarray(kr, np.uint32)


def _chain(free_at, workers, arrive, cost, speeds, clock):
    """FIFO service, one job after another: ``start = max(arrival,
    free_at[w])``, ``done = start + cost / speed[w]``.  ``free_at`` is
    updated in place.  Python floats are IEEE float64, so the float64
    clock runs on them; the float32 control runs on numpy scalars."""
    if clock is np.float64:
        fa, sp = free_at.tolist(), speeds.tolist()
        ws, starts, dones = workers.tolist(), [], []
        for w, a, c in zip(ws, arrive.tolist(), cost.tolist()):
            st = a if a > fa[w] else fa[w]
            dn = st + c / sp[w]
            fa[w] = dn
            starts.append(st)
            dones.append(dn)
        free_at[:] = fa
        return np.asarray(starts), np.asarray(dones)
    starts = np.empty(workers.size, clock)
    dones = np.empty(workers.size, clock)
    for i, w in enumerate(workers):
        st = max(arrive[i], free_at[w])
        dn = clock(st + cost[i] / speeds[w])
        free_at[w] = dn
        starts[i], dones[i] = st, dn
    return starts, dones


@dataclasses.dataclass
class Result:
    workers: np.ndarray  # i64[T, k] the reference's own PPoT decisions
    allowed: np.ndarray  # bool[T, k] the placement run is one PPoT allows
    resp: np.ndarray  # f64[T, k] responses under the placements it ran
    mu_trace: np.ndarray  # f32[T, n] mu_hat entering each turn
    pend_overflow: int
    flush_overflow: int
    pend_max: int  # most jobs in flight at once


def simulate(sem: Semantics, seed: int, times, costs, speeds, *,
             forced=None, clock=np.float64) -> Result:
    """Run the semantics over ``times``/``costs`` (f64[T, k]) with the
    workers' speeds ``speeds`` (f64[T, n]) entering each turn.  With
    ``forced`` (i64[T, k], the system's placements) the pool runs those
    placements; without it the reference acts on its own decisions (the
    control, put in the system's place)."""
    T, k = times.shape
    n = sem.n
    kf, kr = key_chain(seed, T)
    speeds = np.asarray(speeds).astype(clock)
    times_c = times.astype(clock)
    costs_c = costs.astype(clock)
    fake_cost = clock(sem.fake_cost)
    lrn = Learner(sem)
    q = np.zeros(n, np.int64)
    free_at = np.zeros(n, clock)
    # in-flight jobs as parallel lists (done, start, worker, seq)
    p_done = np.empty(0, clock)
    p_start = np.empty(0, clock)
    p_rep = np.empty(0, np.int64)
    p_seq = np.empty(0, np.int64)
    seq_ctr = 0
    mean_gap, last_time, arr_count = F32(0.0), F32(0.0), 0
    r = (1.0 - 1.0 / EMA_WINDOW) ** k
    r32, one_r32 = F32(r), F32(1.0 - r)
    last_fake = F32(0.0)
    pend_over = flush_over = pend_max = 0
    out_w = np.empty((T, k), np.int64)
    out_r = np.empty((T, k), np.float64)
    out_mu = np.empty((T, n), F32)
    out_ok = np.empty((T, k), bool)
    for t in range(T):
        t64 = times_c[t, -1]
        t32 = F32(t64)
        # -- flush
        due = np.nonzero(p_done <= t64)[0]
        if due.size:
            order = due[np.lexsort((p_seq[due], p_done[due]))]
            if order.size > sem.comp_cap:
                flush_over += order.size - sem.comp_cap
                order = order[:sem.comp_cap]
            comp_w = p_rep[order]
            comp_t = (p_done[order] - p_start[order]).astype(F32)
            comp_now = F32(np.max(p_done[order]))
            keep = np.ones(p_done.size, bool)
            keep[order] = False
            p_done, p_start = p_done[keep], p_start[keep]
            p_rep, p_seq = p_rep[keep], p_seq[keep]
        else:
            comp_w = np.empty(0, np.int64)
        out_mu[t] = lrn.mu
        q = np.maximum(q - np.bincount(comp_w, minlength=n), 0)
        lam0 = F32(np.where(mean_gap > 0,
                            F32(1.0) / np.maximum(mean_gap, F32(1e-9)),
                            F32(0.0)))
        if comp_w.size:
            lrn.record(comp_w, comp_t, comp_now)
            lrn.refresh(lam0, comp_now)
        fakes = fake_jobs(sem, kf[t], lam0, F32(t32 - last_fake))
        gap = F32(F32(t32 - last_time) / F32(k))
        mean_gap = gap if arr_count == 0 else F32(
            F32(r32 * mean_gap) + F32(one_r32 * gap))
        last_time, arr_count = t32, arr_count + k
        prob, alias = alias_table(lrn.mu)
        u1, u2, v1, v2 = uniform_quad(kr[t], k)
        j1, j1b = alias_draw(prob, alias, u1, v1)
        j2, j2b = alias_draw(prob, alias, u2, v2)
        mine = np.where(q[j1] <= q[j2], j1, j2)
        out_w[t] = mine
        placed = mine if forced is None else forced[t]
        ok = placed == mine
        for a, b in ((j1, j2b), (j1b, j2), (j1b, j2b)):
            ok |= placed == np.where(q[a] <= q[b], a, b)
        out_ok[t] = ok
        q = q + np.bincount(placed, minlength=n)
        last_fake = t32
        # -- pool chain: fakes at the turn's last arrival, then requests
        sub_w = np.concatenate([fakes, placed])
        sub_a = np.concatenate([np.full(fakes.size, t64, clock), times_c[t]])
        sub_c = np.concatenate([np.full(fakes.size, fake_cost, clock),
                                costs_c[t]])
        sub_s, sub_d = _chain(free_at, sub_w, sub_a, sub_c, speeds[t], clock)
        out_r[t] = (sub_d[fakes.size:] - times_c[t]).astype(np.float64)
        room = sem.pend_cap - p_done.size
        m = sub_w.size
        if m > room:
            pend_over += m - room
            m = max(room, 0)
        p_done = np.concatenate([p_done, sub_d[:m]])
        p_start = np.concatenate([p_start, sub_s[:m]])
        p_rep = np.concatenate([p_rep, sub_w[:m]])
        p_seq = np.concatenate([p_seq, seq_ctr + np.arange(m)])
        seq_ctr += sub_w.size
        pend_max = max(pend_max, p_done.size)
    return Result(out_w, out_ok, out_r, out_mu, pend_over, flush_over,
                  pend_max)


def check(sem: Semantics, seed: int, times, costs, speeds, workers, resp,
          mu_trace) -> dict:
    """Judge the system's answers for the turns of ``times`` (f64[T, k])
    at ``speeds`` (f64[T, n]):
    its placements ``workers`` (int[T*k]), responses ``resp`` (f64[T*k])
    and mu_hat trace (f32[T, n]).  Returns the compared numbers."""
    T, k = times.shape
    workers = np.asarray(workers).reshape(-1)
    resp = np.asarray(resp, np.float64).reshape(-1)
    mu_trace = np.asarray(mu_trace, np.float32)
    shape_ok = (workers.size == T * k and resp.size == T * k
                and mu_trace.shape == (T, sem.n))
    if not shape_ok:
        return {"misplaced": T * k, "placement_mismatch": 1.0,
                "resp_off": 1.0, "mu_off": 1.0, "resp_gap_s": float("inf"),
                "mu_gap": float("inf")}
    bad = (workers < 0) | (workers >= sem.n)
    forced = np.where(bad, 0, workers).reshape(T, k).astype(np.int64)
    ref = simulate(sem, seed, times, costs, speeds, forced=forced)
    gap = np.abs(resp - ref.resp.reshape(-1))
    gap = np.where(np.isnan(gap), np.inf, gap)
    scale = np.maximum(np.max(np.abs(ref.mu_trace), axis=1, keepdims=True),
                       F32(1e-9))
    mu_gap = np.abs(mu_trace.astype(np.float64) - ref.mu_trace) / scale
    mu_gap = np.where(np.isnan(mu_gap), np.inf, mu_gap)
    return {
        "misplaced": int(bad.sum()),
        "placement_mismatch": float(np.mean(bad | ~ref.allowed.reshape(-1))),
        "resp_off": float(np.mean(gap > RESP_TOL)),
        "mu_off": float(np.mean(mu_gap > MU_RTOL)),
        "resp_gap_s": float(np.max(gap)),
        "mu_gap": float(np.max(mu_gap)),
    }
