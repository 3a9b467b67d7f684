"""Bounded-staleness sync layer — reconciling S stale frontend views.

The paper's frontends "need only synchronize the estimates of worker speeds
regularly" (§5). This module is that synchronization, at a configurable
cadence (the staleness bound), in two implementations with one semantics:

  * **pure-jnp round-based fold** (``sync_sim_views``) for the simulator,
    where true worker state is directly available: every frontend's queue
    snapshot reconciles to the true queues, its own-placement delta clears,
    its μ̂ view adopts the current central estimate, and the per-frontend
    λ̂ streams merge into a fleet-wide ``lam_global = Σ_f λ̂_f`` (each
    frontend sees ~λ/S of the arrivals, so the SUM estimates total λ);

  * **collective form** (``sync_frontend_shard`` inside ``shard_map``) for
    real meshes, where no one holds true state: the global queue view is
    reconstructed from per-frontend deltas — each shard contributes
    ``q_view − q_snap`` (its placements/drains since the last agreement)
    via ``psum`` on top of the previously agreed snapshot — μ̂ merges via
    ``pmean``, and the per-frontend λ̂ scalars are ``all_gather``-ed so
    every frontend knows the whole fleet's streams (kept per-frontend;
    only the merged total is adopted).

Between syncs, frontends run coordination-free: ``make_fleet_step`` builds
a jitted shard_map step that ONLY schedules (one batched-engine call per
frontend, all frontends in one device program, no collectives); the caller
invokes ``make_fleet_sync``'s function every ``sync_every`` steps — the
bounded-staleness cadence is driver-controlled, so reduced coordination
actually removes the collectives from the hot path instead of masking them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch as dsp
from repro.core import estimator as est
from repro.core import policies as pol
from repro.core import scheduler as rs
from repro.fleet.state import (
    FleetFrontend,
    FleetSimState,
    fleet_lam_hats,
    frontend_shard_table,
)


# ---------------------------------------------------------------------------
# Pure-jnp round-based fold (simulator)
# ---------------------------------------------------------------------------


def sync_sim_views(
    fleet: FleetSimState,
    q_true: jax.Array,  # i32[n] true worker queues (the simulator knows them)
    mu_central: jax.Array,  # f32[n] current central μ̂ (or true μ in oracle mode)
    now: jax.Array,
    active: jax.Array | None = None,  # bool[n] membership mask (churn envs)
) -> FleetSimState:
    """Reconcile every frontend's view at true worker state (one fold, no
    collectives — the simulator's round-based form of the sync layer).
    The frozen alias table is part of the view: ONE build from the newly
    adopted μ̂, broadcast to every frontend, amortized until the next
    sync. Under churn the table is MASKED (``active``): offline workers
    carry exactly zero probe mass in every frontend's frozen view until
    the sync that readmits them (membership flips force a sync — see
    ``simulator.round_fn``)."""
    S = fleet.q_snap.shape[0]
    lam_f = fleet_lam_hats(fleet)
    table = dsp.build_alias_table(mu_central, active)
    return fleet.replace(
        q_snap=jnp.broadcast_to(q_true[None], fleet.q_snap.shape),
        q_delta=jnp.zeros_like(fleet.q_delta),
        mu_view=jnp.broadcast_to(mu_central[None], fleet.mu_view.shape),
        alias_p=jnp.broadcast_to(table.prob[None], fleet.alias_p.shape),
        alias_a=jnp.broadcast_to(table.alias[None], fleet.alias_a.shape),
        t_sync=jnp.full((S,), now, jnp.float32),
        lam_global=jnp.sum(lam_f),
    )


# ---------------------------------------------------------------------------
# Collective form (shard_map over a scheduler mesh axis)
# ---------------------------------------------------------------------------


def _sync_collective_core(q_local, q_snap, mu_local, lam_local, axis_name):
    """The sync round's three collectives, over a shard's LOCAL frontend
    rows (``[Sl, ...]`` where Sl = S / mesh size; Sl = 1 when every
    frontend owns a device). Shared by ``sync_frontend_shard`` (the mesh
    fleet) and ``make_fleet_scan_sync`` (the one-program fleet scan), so
    both paths reconcile with the SAME psum/psum-mean/all_gather pattern:

      * global queues  = snapshot + psum of per-frontend deltas,
      * merged μ̂      = psum of local μ̂ sums / psum of local counts
        (≡ pmean over frontends, any shard split),
      * λ̂ streams     = all_gather'd into frontend order ``[S]``.

    Returns ``(total_q i32[n], mu_merged f32[n], lam_all f32[S])``."""
    # explicit dtype: the fleet scan traces this under an x64 context,
    # where default integer sums widen to i64
    delta = (q_local - q_snap[None, :]).sum(axis=0, dtype=q_snap.dtype)
    total = jnp.maximum(q_snap + jax.lax.psum(delta, axis_name), 0)
    cnt = jax.lax.psum(jnp.float32(q_local.shape[0]), axis_name)
    mu_merged = jax.lax.psum(mu_local.sum(axis=0), axis_name) / cnt
    lam_all = jax.lax.all_gather(lam_local, axis_name).reshape(-1)
    return total, mu_merged, lam_all


def sync_frontend_shard(ff: FleetFrontend, now: jax.Array, axis_name: str,
                        active: jax.Array | None = None) -> FleetFrontend:
    """One frontend's half of the fleet sync, inside ``shard_map``.

    Global queue view = previously agreed snapshot + Σ_f (own view − own
    snapshot): each frontend's delta is exactly what it did since the last
    agreement, so the psum reconstructs true outstanding work without any
    frontend observing the workers directly. μ̂ merges by pmean (paper §5);
    λ̂ streams stay per-frontend — only their all_gather'd SUM is adopted
    as the fleet arrival-rate estimate. ``active`` (replicated bool[n],
    optional) is the membership mask of a churn environment: the frozen
    alias table every shard rebuilds is masked, so no frontend probes an
    offline worker between syncs."""
    total, mu, lam_all = _sync_collective_core(
        ff.core.q_view[None], ff.q_snap, ff.core.learner.mu_hat[None],
        est.lam_hat_ema(ff.core.arr)[None], axis_name,
    )  # lam_all: [S]
    core = ff.core.replace(
        q_view=total, learner=ff.core.learner.replace(mu_hat=mu)
    )
    # the frozen alias table rides the sync: every shard rebuilds from the
    # SAME pmean'd μ̂ (identical tables, no extra collective) and samples
    # through it coordination-free until the next sync
    table = dsp.build_alias_table(mu, active)
    return ff.replace(
        core=core, q_snap=total, alias_p=table.prob, alias_a=table.alias,
        lam_global=jnp.sum(lam_all), t_sync=jnp.asarray(now, jnp.float32),
    )


def make_fleet_step(mesh, m: int, policy: str = pol.PPOT_SQ2,
                    axis_name: str = "sched", use_alias: bool = True):
    """Build the coordination-FREE fleet scheduling step over
    ``mesh[axis_name]``: ``fn(frontends, keys, nows) -> (workers[S, m],
    frontends')``. Every pytree leaf of ``frontends`` (and ``keys``,
    ``nows``) carries a leading frontend axis of size S. Each frontend
    places its batch through the batched dispatch engine against its own
    stale view and clock (``nows[f]`` — frontends run on independent
    machines with independent arrival streams); NO collective runs here —
    staleness accrues until the caller fires ``make_fleet_sync``'s fn.
    With ``use_alias`` (default) the μ̂-proportional probes draw through
    the shard's FROZEN alias table (rebuilt by the sync collective), so
    the between-sync hot path does O(1) sampling work per probe."""

    def shard_fn(ff, k, now):
        f1 = jax.tree.map(lambda x: x[0], ff)
        tbl = frontend_shard_table(f1) if use_alias else None
        w, core = rs._schedule_impl(f1.core, k[0], now[0], m, policy, tbl)
        f2 = f1.replace(core=core)
        return w[None], jax.tree.map(lambda x: x[None], f2)

    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)),
    )
    return jax.jit(mapped)


def make_fleet_sync(mesh, axis_name: str = "sched", masked: bool = False):
    """Build the jitted fleet sync: ``fn(frontends, now) -> frontends'``
    (psum delta-reconciled queue views, pmean μ̂, all_gather'd λ̂ merge).
    Fire it every ``sync_every`` steps — that cadence IS the staleness
    bound. ``masked=True`` builds the churn form instead:
    ``fn(frontends, now, active)`` with a replicated bool[n] membership
    mask — every shard's frozen alias table rebuilds MASKED, so no
    frontend probes an offline worker until the next sync."""

    if masked:
        def shard_fn(ff, now, active):
            f1 = jax.tree.map(lambda x: x[0], ff)
            f2 = sync_frontend_shard(f1, now, axis_name, active)
            return jax.tree.map(lambda x: x[None], f2)

        in_specs = (P(axis_name), P(), P())
    else:
        def shard_fn(ff, now):
            f1 = jax.tree.map(lambda x: x[0], ff)
            f2 = sync_frontend_shard(f1, now, axis_name)
            return jax.tree.map(lambda x: x[None], f2)

        in_specs = (P(axis_name), P())

    mapped = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(axis_name),
    )
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# One-program fleet scan stages (serving/scanloop fleet mode over a mesh)
# ---------------------------------------------------------------------------


def make_fleet_serve_stage(mesh, m: int, policy: str, *, max_fake: int = 8,
                           use_fresh_mu: bool = True, use_alias: bool = True,
                           churn: bool = False, axis_name: str = "sched"):
    """The fleet scan's SERVE stage as a ``shard_map`` over the frontend
    axis — the coordination-free half of the loop: each shard runs
    ``scheduler.serve_step_fleet`` on its LOCAL frontend rows (vmap, so
    any mesh size dividing S works), NO collectives. Pair with
    ``make_fleet_scan_sync`` — sync rounds are then the only collectives
    in the compiled loop. Returns an UNJITTED fn (it is traced inside the
    scan body): ``fn(q, learner, arr, mu_front, keys, comp_w, comp_t,
    last_fake, comp_now, now, lcfg, table_p, table_a, mask) -> (fake_js,
    workers, q', learner', arr', keys')``. ``table_p``/``table_a`` and
    ``mask`` are always passed (dummies when unused — shard_map wants a
    fixed arity); the static flags decide whether they are read."""

    def shard_fn(q, l, a, mu, keys, cw, ct, lf, cn, now, lcfg, tbp, tba,
                 mask):
        tb = (
            dsp.AliasTable(prob=tbp, alias=tba)
            if (use_alias and not use_fresh_mu) else None
        )
        return rs.serve_step_fleet(
            q, l, a, mu, lcfg, keys, cw, ct, (now, lf, cn),
            m, policy, max_fake, use_fresh_mu, tb, use_alias,
            mask if churn else None,
        )

    per_f, shared = P(axis_name), P()
    # no collectives here, so nothing for the varying-axes check to guard;
    # it would reject the learner's mix of replicated (lcfg, now) and
    # per-frontend operands inside the completion-fold ``lax.cond``
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(per_f, per_f, per_f, per_f, per_f, per_f, per_f, per_f,
                  per_f, shared, shared, per_f, per_f, shared),
        out_specs=(per_f, per_f, per_f, per_f, per_f, per_f),
        check_vma=False,
    )


def make_fleet_scan_sync(mesh, axis_name: str = "sched"):
    """The fleet scan's SYNC stage as a ``shard_map``: reconcile the
    per-frontend stale views through ``_sync_collective_core`` — the SAME
    psum/pmean/all_gather pattern as ``sync_frontend_shard`` — plus the
    herd-correction unwind (corrections are a routing bias, not state) and
    the staleness-gap telemetry. Unjitted; traced inside the scan body
    under the sync-round ``lax.cond``, so the collectives run ONLY on sync
    turns. ``fn(q_view, herd_applied, q_snap, mu_hat, lam_hat) ->
    (q_view'[S,n] (global, broadcast), mu_merged'[S,n], gaps i32[S],
    global_q i32[n], lam_sum f32)``."""

    def shard_fn(q_view, herd_applied, q_snap, mu_hat, lam_hat):
        qs = q_view - herd_applied
        total, mu_merged, _ = _sync_collective_core(
            qs, q_snap, mu_hat, lam_hat, axis_name,
        )
        gaps = jnp.abs(qs - total[None, :]).sum(
            axis=1, dtype=jnp.int32
        )
        # psum (not sum-of-all_gather): statically replicated, so the
        # P() out_spec passes shard_map's replication check
        lam_sum = jax.lax.psum(lam_hat.sum(dtype=jnp.float32), axis_name)
        return (
            jnp.broadcast_to(total[None], q_view.shape),
            jnp.broadcast_to(mu_merged[None], mu_hat.shape),
            gaps, total, lam_sum,
        )

    per_f, shared = P(axis_name), P()
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(per_f, per_f, shared, per_f, per_f),
        out_specs=(per_f, per_f, per_f, shared, shared),
    )
