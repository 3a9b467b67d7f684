"""Rosella runtime scheduler — the deployable composition of the three
components (arrival estimator + scheduling policy + performance learner),
paper Fig. 1, as a jittable state machine.

Unlike ``simulator.py`` (which owns the event clock for reproducing the
paper's experiments), the runtime is *driven by the caller*: the serving
router / training straggler-mitigator feed it arrivals and completion
telemetry and ask it to place batches of jobs. Placement goes through the
unified batched dispatch engine (``core/dispatch.py``): ``schedule`` places
a whole batch of ``m`` jobs in ONE engine call — every job probes against
the frontend's queue snapshot and the batch's own assignments fold back via
a single scatter-add — which is what lets one frontend make millions of
decisions per second (paper §1) instead of scanning job-by-job. All methods
are pure ``state → state`` functions so they compose with jit/shard_map;
the ``RosellaScheduler`` class is a thin convenience wrapper.

Distributed mode (paper §5): each scheduler shard keeps its own state;
``schedule_shard``/``make_sharded_schedule`` run the same engine per shard
inside ``shard_map`` and ``pmean`` the μ̂/q̂ estimates over the scheduler
axis after every batch — "they need only synchronize the estimates of
worker speeds regularly".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch as dsp
from repro.core import estimator as est
from repro.core import learner as lrn
from repro.core import policies as pol
from repro.obs import tracing as obt
from repro.utils.struct import pytree_dataclass


@pytree_dataclass
class RosellaState:
    q_view: jax.Array  # i32[n] scheduler's view of outstanding work
    arr: est.EmaArrivalState
    learner: lrn.LearnerState
    last_fake_time: jax.Array  # f32 — fake-job Poisson bookkeeping


def init_rosella(
    n: int, lcfg: lrn.LearnerConfig, mu_init: float | jax.Array = 1.0
) -> RosellaState:
    return RosellaState(
        q_view=jnp.zeros((n,), jnp.int32),
        arr=est.init_ema_arrival(),
        learner=lrn.init_learner(n, lcfg, mu_init),
        last_fake_time=jnp.float32(0.0),
    )


def _schedule_impl(
    state: RosellaState,
    key: jax.Array,
    now: jax.Array,
    m: int,
    policy: str = pol.PPOT_SQ2,
    table: dsp.AliasTable | None = None,
) -> tuple[jax.Array, RosellaState]:
    """Place ``m`` jobs arriving at ``now``; returns (workers[m], state').

    One batched engine call: all m jobs probe the frontend's queue snapshot
    and the batch folds back into the view with one histogram fold (the
    paper's probe sees the queue including in-flight assignments from this
    frontend). ``table`` (optional) is an amortized alias table for the
    μ̂-proportional probe draw — callers that refresh μ̂ on a cadence (the
    fleet's frozen views) build it once per refresh."""
    arr = est.observe_arrivals_ema(state.arr, now, m, window=est.EMA_ARR_WINDOW)
    mu_true = state.learner.mu_hat  # runtime has no oracle speeds
    res = dsp.dispatch(
        policy, key, state.q_view, state.learner.mu_hat, mu_true,
        pol.default_policy_config(), m, table=table,
    )
    return res.workers, state.replace(q_view=res.q_after, arr=arr)


schedule = functools.partial(jax.jit, static_argnums=(3, 4))(_schedule_impl)

#: ``schedule`` with the state donated: the caller hands over its state
#: buffers (q_view et al. are rewritten in place on device). Host-driven
#: loops that rebind ``state = schedule_donated(state, ...)`` — the
#: ``RosellaScheduler`` wrapper, the serving router — use this variant; do
#: NOT reuse the old state object after calling it.
schedule_donated = functools.partial(
    jax.jit, static_argnums=(3, 4), donate_argnums=(0,)
)(_schedule_impl)


# ---------------------------------------------------------------------------
# Double-buffered serving primitives (route() must never block on a learner
# refresh — ROADMAP async-completion item). The router splits the state:
# ``route_view`` touches only (q_view, arrival estimator) plus a μ̂ SNAPSHOT
# it is handed, while ``fold_telemetry`` folds completions into the learner
# on the side; the router flips its μ̂ snapshot to the refreshed one only
# once that computation has actually materialized (jax async dispatch), so
# the routing hot path never waits on LEARNER-AGGREGATE.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0,))
def route_view(
    q_view: jax.Array,  # i32[n] — donated, rewritten in place
    arr: est.EmaArrivalState,
    mu_hat: jax.Array,  # f32[n] μ̂ snapshot (front buffer)
    key: jax.Array,
    now: jax.Array,
    m: int,
    policy: str = pol.PPOT_SQ2,
    table: dsp.AliasTable | None = None,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, est.EmaArrivalState]:
    """Route ``m`` requests against a queue view + μ̂ snapshot; no learner
    state in the dependency chain. Returns (workers[m], q_view', arr').
    ``table`` is the amortized alias table matching THIS μ̂ snapshot — the
    router rebuilds it only when the front buffer flips. ``mask`` is the
    membership mask (worker churn): requests never route to an inactive
    replica; the table must have been built with the same mask."""
    arr2 = est.observe_arrivals_ema(arr, now, m, window=est.EMA_ARR_WINDOW)
    res = dsp.dispatch(
        policy, key, q_view, mu_hat, mu_hat, pol.default_policy_config(), m,
        table=table, mask=mask,
    )
    return res.workers, res.q_after, arr2


def absorb_completions(q_view: jax.Array, workers: jax.Array) -> jax.Array:
    """Drain a completion batch (pad with -1) from the queue view — the
    cheap half of completion handling; the learner half runs separately.
    (Plain traced function: composed into ``complete_step``/``serve_step``.)
    """
    valid = workers >= 0
    wc = jnp.where(valid, workers, 0)
    dec = jnp.zeros_like(q_view).at[wc].add(-valid.astype(q_view.dtype))
    return jnp.maximum(q_view + dec, 0)


def fold_telemetry(
    learner: lrn.LearnerState,
    lcfg: lrn.LearnerConfig,
    workers: jax.Array,  # i32[B] worker ids (pad with -1)
    service_times: jax.Array,  # f32[B]
    lam_hat: jax.Array,
    now: jax.Array,
) -> lrn.LearnerState:
    """LEARNER-AGGREGATE for a completion batch + estimate refresh — the
    expensive half of completion handling, kept off the routing path. The
    whole batch lands in the sample rings via ONE vectorized scatter
    (``learner.record_completions``), not a per-completion scan. (Plain
    traced function: composed into ``complete_step``/``serve_step``.)"""
    learner = lrn.record_completions(learner, workers, service_times, now)
    return lrn.refresh_estimates(learner, lcfg, lam_hat, now)


@functools.partial(jax.jit, donate_argnums=(0,))
def complete_step(
    q_view: jax.Array,  # i32[n] — donated
    learner: lrn.LearnerState,  # NOT donated: mu_hat may be aliased by the
    # router's μ̂ front/pending buffers (see serve_step)
    lcfg: lrn.LearnerConfig,
    arr: est.EmaArrivalState,
    workers: jax.Array,  # i32[B] worker ids (pad with -1)
    service_times: jax.Array,  # f32[B]
    now: jax.Array,
):
    """Fused completion fold: queue-view drain + LEARNER-AGGREGATE +
    estimate refresh in one jit dispatch. Returns (q_view', learner')."""
    q2 = absorb_completions(q_view, workers)
    learner2 = fold_telemetry(
        learner, lcfg, workers, service_times, est.lam_hat_ema(arr), now
    )
    return q2, learner2


def _serve_step_math(
    q_view, learner, arr, mu_hat, lcfg, key, comp_workers, comp_times,
    scalars, m, policy, max_fake, use_fresh_mu,
    table: dsp.AliasTable | None = None, use_alias: bool = False,
    mask: jax.Array | None = None,
    m_route: int | None = None, slots: jax.Array | None = None,
):
    """The traced body of ``serve_step`` — shared verbatim with the
    scan-compiled serving loop (``serving/scanloop.py``) so both consume
    bit-identical key streams and f32 math. See ``serve_step`` for the
    contract; keep every array here explicitly dtyped (the scan loop
    traces this under an x64 context for its f64 event clock).

    ``mask`` (bool[n], optional) is the membership mask of the churn
    scenarios: routing and benchmark draws target only active replicas
    (inactive workers get exactly-zero probe mass; the fresh-μ̂ alias
    rebuild is masked). ``mask=None`` is bit-identical to before.

    ``m_route``/``slots`` (recovery layer): route ``m_route ≥ m`` slots
    in the one dispatch call — the first ``m`` are the arrival batch, the
    tail is the turn's retry re-dispatch quota, gated per-slot by the
    ``slots`` bool[m_route] mask (inactive slots place nothing and return
    worker −1). The arrival estimator still observes exactly ``m``
    arrivals (retries are re-executions, not new arrivals).
    ``m_route=None`` is bit-identical to before."""
    now, last_fake, comp_now = scalars
    q1 = absorb_completions(q_view, comp_workers)
    lam0 = est.lam_hat_ema(arr)

    def fold(l):
        l2 = lrn.record_completions(l, comp_workers, comp_times, comp_now)
        return lrn.refresh_estimates(l2, lcfg, lam0, comp_now)

    with obt.stage("learner_fold"):
        learner2 = jax.lax.cond(
            jnp.any(comp_workers >= 0), fold, lambda l: l, learner
        )
    key1, k_fake = jax.random.split(key)
    key2, k_route = jax.random.split(key1)
    n = q1.shape[0]
    fake_js = fake_jobs_from(lcfg, k_fake, lam0, now - last_fake, max_fake, n,
                             mask=mask)
    arr2 = est.observe_arrivals_ema(arr, now, m, window=est.EMA_ARR_WINDOW)
    if use_fresh_mu:
        mu_route = learner2.mu_hat
        # blocking semantics route on THIS flush's μ̂ — the amortized front
        # table would be stale, so rebuild from the fresh estimates (still
        # one build per completion flush, not per request).
        tbl = None
        if use_alias:
            with obt.stage("alias_build"):
                tbl = dsp.build_alias_table(mu_route, mask)
    else:
        mu_route = mu_hat
        tbl = table if use_alias else None
    with obt.stage("dispatch"):
        res = dsp.dispatch(
            policy, k_route, q1, mu_route, mu_route,
            pol.default_policy_config(), m if m_route is None else m_route,
            active=slots, table=tbl, mask=mask,
        )
    return fake_js, res.workers, res.q_after, learner2, arr2, key2


@functools.partial(
    jax.jit, static_argnums=(9, 10, 11, 12, 14), donate_argnums=(0,)
)
def serve_step(
    q_view: jax.Array,  # i32[n] — donated
    learner: lrn.LearnerState,  # NOT donated: the μ̂ front buffer may alias
    # learner.mu_hat (at init, and whenever a flip adopted it) — donating
    # would invalidate the routing snapshot
    arr: est.EmaArrivalState,
    mu_hat: jax.Array,  # f32[n] μ̂ snapshot (front buffer)
    lcfg: lrn.LearnerConfig,
    key: jax.Array,
    comp_workers: jax.Array,  # i32[P] due completions (pad with -1)
    comp_times: jax.Array,  # f32[P]
    scalars,  # (now, last_fake_time, comp_now)
    m: int,
    policy: str = pol.PPOT_SQ2,
    max_fake: int = 8,
    use_fresh_mu: bool = False,
    table: dsp.AliasTable | None = None,  # amortized front-buffer table
    use_alias: bool = False,
    mask: jax.Array | None = None,  # bool[n] membership mask (churn)
):
    """One whole serving turn in ONE jit dispatch: flush the due completion
    batch, draw benchmark requests, route the arrival batch.

    The three stages keep the double-buffer seam inside the executable:
    the route subgraph depends only on (q_view drained of completions, the
    μ̂ SNAPSHOT argument, arrival estimator), never on the learner fold /
    refresh subgraph — XLA can run LEARNER-AGGREGATE concurrently on
    another thread while the route computes. ``use_fresh_mu=True`` instead
    routes on THIS flush's refreshed μ̂ (PR-1's blocking semantics,
    bit-deterministic — the router's ``async_mu=False`` mode). Key
    consumption and update ordering are bit-identical to
    ``complete_arrays`` + ``benchmark_requests`` + ``route``; an
    all-padding completion batch skips the learner fold exactly like the
    host loop skips ``complete_arrays``.

    ``use_alias=True`` draws the μ̂-proportional probes through the
    amortized alias ``table`` (rebuilt by the router only on a front-buffer
    flip; rebuilt in-step from the fresh μ̂ under ``use_fresh_mu``).

    Returns (fake_js[max_fake], workers[m], q_view', learner', arr', key').
    """
    return _serve_step_math(
        q_view, learner, arr, mu_hat, lcfg, key, comp_workers, comp_times,
        scalars, m, policy, max_fake, use_fresh_mu, table, use_alias, mask
    )


@functools.partial(
    jax.jit, static_argnums=(9, 10, 11, 12, 14, 16), donate_argnums=(0,)
)
def serve_step_recovery(
    q_view: jax.Array,  # i32[n] — donated
    learner: lrn.LearnerState,
    arr: est.EmaArrivalState,
    mu_hat: jax.Array,
    lcfg: lrn.LearnerConfig,
    key: jax.Array,
    comp_workers: jax.Array,  # i32[P] CLEAN due completions (pad with -1)
    comp_times: jax.Array,  # f32[P]
    scalars,  # (now, last_fake_time, comp_now)
    m: int,
    policy: str = pol.PPOT_SQ2,
    max_fake: int = 8,
    use_fresh_mu: bool = False,
    table: dsp.AliasTable | None = None,
    use_alias: bool = False,
    mask: jax.Array | None = None,
    m_route: int | None = None,
    slots: jax.Array | None = None,  # bool[m_route] slot gate (retry tail)
):
    """``serve_step`` with the recovery layer's widened dispatch: one call
    routes the ``m`` arrivals AND up to ``m_route − m`` retry re-dispatch
    slots (``slots`` gates the tail; see ``_serve_step_math``). With
    ``m_route=None``/``slots=None`` this is ``serve_step`` exactly —
    zero-fault recovery configs compile to the identical program."""
    return _serve_step_math(
        q_view, learner, arr, mu_hat, lcfg, key, comp_workers, comp_times,
        scalars, m, policy, max_fake, use_fresh_mu, table, use_alias, mask,
        m_route, slots,
    )


def serve_step_fleet(
    q_views: jax.Array,  # i32[S, n] per-frontend stale queue views
    learners: lrn.LearnerState,  # stacked per-frontend learners ([S, ...])
    arrs: est.EmaArrivalState,  # stacked per-frontend λ̂ EMAs ([S])
    mu_fronts: jax.Array,  # f32[S, n] per-frontend μ̂ routing snapshots
    lcfg: lrn.LearnerConfig,
    keys: jax.Array,  # u32[S, 2] per-frontend PRNG keys
    comp_workers: jax.Array,  # i32[S, P] per-frontend due completions
    comp_times: jax.Array,  # f32[S, P]
    scalars,  # (now, last_fakes[S], comp_nows[S])
    m: int,  # per-frontend batch size
    policy: str,
    max_fake: int = 8,
    use_fresh_mu: bool = False,
    tables: dsp.AliasTable | None = None,  # frozen tables, leaves [S, n]
    use_alias: bool = False,
    mask: jax.Array | None = None,  # bool[n] shared membership mask
):
    """S serving turns at once: ``_serve_step_math`` vmapped over the
    frontend axis. Each frontend flushes ITS completions, draws ITS
    benchmark jobs and routes ITS arrival chunk against its own stale
    view/μ̂/key — the membership mask and the clock are fleet-shared.
    vmap of the step math is bit-identical per row to S unbatched calls
    (pinned by tests/test_fleet_scan.py), which is what lets the
    one-program fleet scan meet its host-parity obligations.

    Returns ``(fake_js[S, max_fake], workers[S, m], q_views', learners',
    arrs', keys')``.
    """
    now, last_fakes, comp_nows = scalars

    def one(q, l, a, mu, k, cw, ct, lf, cn, tb):
        return _serve_step_math(
            q, l, a, mu, lcfg, k, cw, ct, (now, lf, cn),
            m, policy, max_fake, use_fresh_mu, tb, use_alias, mask,
        )

    if tables is None:
        return jax.vmap(
            lambda q, l, a, mu, k, cw, ct, lf, cn:
            one(q, l, a, mu, k, cw, ct, lf, cn, None)
        )(q_views, learners, arrs, mu_fronts, keys, comp_workers,
          comp_times, last_fakes, comp_nows)
    return jax.vmap(one)(
        q_views, learners, arrs, mu_fronts, keys, comp_workers,
        comp_times, last_fakes, comp_nows, tables,
    )


@functools.partial(jax.jit, static_argnums=(4, 5))
def fake_jobs_from(
    lcfg: lrn.LearnerConfig,
    key: jax.Array,
    lam_hat: jax.Array,
    dt: jax.Array,
    max_fake: int,
    n: int,
    mask: jax.Array | None = None,
) -> jax.Array:
    """LEARNER-DISPATCHER tick from raw estimates: Poisson(ν·dt) benchmark
    jobs at uniform workers (uniform over the ACTIVE workers when the
    membership ``mask`` is given — offline workers can't run benchmarks);
    returns workers[max_fake] padded with -1.

    The count is drawn by inverse-CDF over the max_fake+1 truncated Poisson
    pmf terms and workers by scaled counter-hash uniforms — exactly the
    ``min(Poisson(ν·dt), max_fake)`` / uniform-worker distribution, but
    without jax.random's rejection-sampler and threefry lowerings, which
    dominated this fn's (and the serving serve_step's) compile time.
    """
    nu = lrn.fake_job_rate(lcfg, lam_hat)
    lam = nu * jnp.maximum(dt, 0.0)
    u1, u2 = dsp._uniform_pair(key, max_fake)
    ks = jnp.arange(max_fake + 1, dtype=jnp.float32)
    logfact = jnp.concatenate([
        # explicitly f32: this fn must trace identically under an enabled
        # x64 context (the scan-compiled serving loop) and without one
        jnp.zeros((1,), jnp.float32),
        jnp.cumsum(jnp.log(jnp.arange(1, max_fake + 1, dtype=jnp.float32))),
    ])
    logp = ks * jnp.log(jnp.maximum(lam, 1e-30)) - lam - logfact
    cdf = jnp.cumsum(jnp.exp(logp))
    k = jnp.sum((cdf <= u1[0]).astype(jnp.int32))
    if mask is None:
        js = (u2 * n).astype(jnp.int32)
    else:
        js = dsp._active_choice(mask, u2)
    return jnp.where(jnp.arange(max_fake) < k, js, -1)


@jax.jit
def report_completions(
    state: RosellaState,
    workers: jax.Array,  # i32[B] worker ids (pad with -1)
    service_times: jax.Array,  # f32[B]
    now: jax.Array,
) -> RosellaState:
    """Feed completion telemetry (LEARNER-AGGREGATE input) for a batch."""

    def body(s, wt):
        w, t = wt
        valid = w >= 0
        wc = jnp.maximum(w, 0)

        def upd(s):
            learner = lrn.record_completion(s.learner, wc, t, now)
            return s.replace(
                learner=learner,
                q_view=s.q_view.at[wc].add(-1),
            )

        return jax.lax.cond(valid, upd, lambda s: s, s), None

    state, _ = jax.lax.scan(body, state, (workers, service_times))
    return state.replace(q_view=jnp.maximum(state.q_view, 0))


@jax.jit
def refresh(state: RosellaState, lcfg: lrn.LearnerConfig, now: jax.Array) -> RosellaState:
    lam_hat = est.lam_hat_ema(state.arr)
    return state.replace(
        learner=lrn.refresh_estimates(state.learner, lcfg, lam_hat, now)
    )


@functools.partial(jax.jit, static_argnums=(4,))
def fake_jobs_due(
    state: RosellaState,
    lcfg: lrn.LearnerConfig,
    key: jax.Array,
    now: jax.Array,
    max_fake: int = 8,
) -> tuple[jax.Array, RosellaState]:
    """LEARNER-DISPATCHER tick: Poisson(ν·Δt) benchmark jobs since the last
    tick, each aimed at a uniform worker. Returns (workers[max_fake] padded
    with -1, state')."""
    lam_hat = est.lam_hat_ema(state.arr)
    dt = now - state.last_fake_time
    js = fake_jobs_from(lcfg, key, lam_hat, dt, max_fake, state.q_view.shape[0])
    return js, state.replace(last_fake_time=now)


def sync_shard_estimates(state: RosellaState, axis_name: str) -> RosellaState:
    """Inside shard_map: average μ̂ across scheduler shards (paper §5)."""
    mu = jax.lax.pmean(state.learner.mu_hat, axis_name)
    q = jax.lax.pmean(state.q_view.astype(jnp.float32), axis_name)
    return state.replace(
        learner=state.learner.replace(mu_hat=mu),
        q_view=jnp.round(q).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# Multi-frontend scheduling (paper §5) — S scheduler shards, one engine each
# ---------------------------------------------------------------------------


def schedule_shard(
    state: RosellaState,
    key: jax.Array,
    now: jax.Array,
    m: int,
    policy: str,
    axis_name: str,
) -> tuple[jax.Array, RosellaState]:
    """One frontend step inside ``shard_map``: place a local batch of ``m``
    jobs through the dispatch engine, then pmean-sync μ̂/q̂ across the
    scheduler axis ("synchronize the estimates … regularly")."""
    workers, state = schedule(state, key, now, m, policy)
    return workers, sync_shard_estimates(state, axis_name)


def init_rosella_shards(
    num_shards: int, n: int, lcfg: lrn.LearnerConfig, mu_init: float | jax.Array = 1.0
) -> RosellaState:
    """Stack ``num_shards`` fresh states on a leading axis for shard_map."""
    one = init_rosella(n, lcfg, mu_init)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_shards,) + x.shape), one
    )


def make_sharded_schedule(mesh, m: int, policy: str = pol.PPOT_SQ2,
                          axis_name: str = "sched"):
    """Build a jitted multi-frontend scheduler over ``mesh[axis_name]``.

    Returns ``fn(states, keys, now) -> (workers[S, m], states')`` where
    every pytree leaf of ``states`` (and ``keys``) carries a leading shard
    axis of size S = mesh.shape[axis_name]. Each shard runs the batched
    engine against its own queue view, then estimates sync via pmean —
    the paper's distributed frontends.
    """

    def shard_fn(st, k, now):
        st1 = jax.tree.map(lambda x: x[0], st)
        w, st2 = schedule_shard(st1, k[0], now, m, policy, axis_name)
        return w[None], jax.tree.map(lambda x: x[None], st2)

    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=(P(axis_name), P(axis_name)),
    )
    return jax.jit(mapped)


class RosellaScheduler:
    """Convenience OO wrapper holding (state, config) for host-side drivers."""

    def __init__(self, n: int, mu_bar: float, *, c0: float = 0.1,
                 c_window: float = 10.0, window_mode: str = "practical",
                 mu_init: float = 1.0, seed: int = 0):
        self.n = n
        self.lcfg = lrn.default_learner_config(
            mu_bar, c0=c0, c_window=c_window, window_mode=window_mode
        )
        self.state = init_rosella(n, self.lcfg, mu_init)
        self.key = jax.random.PRNGKey(seed)

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def schedule(self, now: float, m: int, policy: str = pol.PPOT_SQ2):
        # Donating variant: self.state is rebound, so the old buffers are
        # free to be rewritten in place on device.
        workers, self.state = schedule_donated(
            self.state, self._next_key(), jnp.float32(now), m, policy
        )
        return workers

    def report(self, workers, service_times, now: float):
        self.state = report_completions(
            self.state,
            jnp.asarray(workers, jnp.int32),
            jnp.asarray(service_times, jnp.float32),
            jnp.float32(now),
        )
        self.state = refresh(self.state, self.lcfg, jnp.float32(now))

    def fake_jobs(self, now: float, max_fake: int = 8):
        js, self.state = fake_jobs_due(
            self.state, self.lcfg, self._next_key(), jnp.float32(now), max_fake
        )
        return js

    @property
    def mu_hat(self):
        return self.state.learner.mu_hat
