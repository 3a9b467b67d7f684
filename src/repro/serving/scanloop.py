"""Scan-compiled closed-loop serving simulation — the whole run is ONE
compiled program.

``run_simulation`` (serving/router.py) already moves each arrival batch as
arrays, but the LOOP is still Python: every turn pays a host→device
dispatch of ``serve_step``, a host-side pending-completion bookkeeping
pass, and a device→host μ̂ sample. This module compiles the entire
Fig-8/Fig-11 run into a single ``lax.scan`` whose carry holds everything
the host loop kept in Python state, with fixed capacities:

  * the router state (queue view, learner sample rings, arrival EMA, PRNG
    key, fake-job clock) — the ``serve_step`` carry,
  * the in-flight completion set (``pend_cap`` slots: done/start times,
    replica, insertion sequence, validity) replacing the host's growing
    numpy arrays. Slots are unordered: each turn's new copies take free
    slots (``pending_slots``) and survivors never move, so the order lives
    in the insertion sequence ``p_seq``; each turn flushes the ≤
    ``SERVE_COMP_CAP`` oldest due completions in (done-time, ``p_seq``)
    order — exactly the host's stable sort,
  * the replica pool (``free_at`` per replica): the per-turn submission
    chain (``pool_chain``) runs ``SimulatedPool.submit``'s recurrence
    ``start = max(arrival, free_at); done = start + cost/μ`` one rank
    within worker at a time across all replicas, each replica's jobs in
    submission order through the same float64 operations (pair with
    ``SequentialPool`` on the host side for exact-parity tests).

The numpy side of the workload (arrival gaps, request costs, the speed
schedule) is pre-drawn on the host with the SAME ``RandomState`` call
sequence as ``run_simulation``, so both loops see identical workloads; the
jax key stream is consumed by the shared ``scheduler._serve_step_math``,
so routing decisions are bit-identical to a ``RosellaRouter`` in its
deterministic ``async_mu=False`` mode. Event times ride the carry in
f64 (the loop traces under a scoped ``jax.enable_x64`` context — every
scheduler-side array is explicitly f32/i32, so the f32 math is unchanged)
and only cross to f32 at the same points the host loop crosses the jit
boundary.

Parity contract (tests/test_scanloop.py):
  * ``use_alias=False`` + ``SequentialPool`` host loop → EXACT: the
    response arrays are equal float-for-float (inverse-CDF RNG stream);
  * ``use_alias=True`` (the production alias stream) → statistical: p50/
    p99 response times agree within a few % (different probe draws, same
    distribution).

Capacity overflows (a turn with more due completions than the flush cap,
or more in-flight work than ``pend_cap``) are counted and returned in
``info`` — they void exactness (the host loop pre-folds overflow instead),
so parity tests assert both counters are zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator as est
from repro.core import learner as lrn
from repro.core import scheduler as rs
from repro.obs import export as oex
from repro.obs import tracing as obt
from repro.obs import windows as obw
from repro.serving import router as rt

#: In-flight completion capacity of the scan carry. Bounded by the total
#: outstanding work the workload can accumulate; overflows are counted in
#: ``info["pend_overflow"]`` (excess submissions are dropped — never
#: silently: parity tests require the counter to be 0). 1024 clears the
#: Fig-8/Fig-11 workloads with ~2× headroom. Slots are unordered (order
#: lives in ``p_seq``), and the flush sort and the free-slot search pass
#: over all ``pend_cap`` slots every turn, so oversizing it costs real
#: wall-clock; ``info["pend_peak"]`` reports how many slots a run used.
PEND_CAP = 1024


def _precompute_workload(arrival_rate, horizon, request_cost, speed_schedule,
                         seed, arrival_batch, speeds0):
    """Replay ``run_simulation``'s numpy RandomState call sequence up
    front: per turn, arrival gaps then request costs — identical draws,
    identical workload."""
    rng = np.random.RandomState(seed)
    t = 0.0
    sched_i = 0
    speeds = np.asarray(speeds0, float).copy()
    times_l, costs_l, speeds_l = [], [], []
    while t < horizon:
        gaps = rng.exponential(1.0 / arrival_rate, size=arrival_batch)
        times = t + np.cumsum(gaps)
        t = float(times[-1])
        if speed_schedule is not None:
            while sched_i < len(speed_schedule) and speed_schedule[sched_i][0] <= t:
                speeds = np.asarray(speed_schedule[sched_i][1], float).copy()
                sched_i += 1
        times_l.append(times)
        costs_l.append(request_cost * rng.exponential(1.0, size=arrival_batch))
        speeds_l.append(speeds.copy())
    if not times_l:
        return None
    return (np.stack(times_l), np.stack(costs_l), np.stack(speeds_l))


def pool_chain(free_at, sub_w, sub_arr, sub_cost, act, speeds64):
    """One turn of the replica-pool chain: submit ``m`` jobs in order, each
    active one (``act``) to worker ``sub_w`` by ``SequentialPool.submit``'s
    recurrence ``start = max(arrival, free_at[w]); done = start + cost/speed;
    free_at[w] = done``.

    The chain is sequential only within a worker, so it runs one RANK at a
    time across all workers: step ``r`` advances every worker that has an
    ``r``-th active submission. Each worker sees its own jobs in submission
    order through the same float64 operations, so the result is bit-equal
    to the per-submission recurrence, in ``steps`` = (most active jobs on
    one worker) sequential steps instead of ``m``. Inactive slots never
    touch ``free_at``; their ``start``/``done`` are not defined.

    Returns ``(free_at', start[m], done[m], steps)``; every index is int32
    (the sharded fleet's partitioner rejects 64-bit scan offsets)."""
    i32 = jnp.int32
    m, n = sub_w.shape[0], free_at.shape[0]
    with obt.stage("pool_chain"):
        sub_w = sub_w.astype(i32)
        dur = sub_cost / speeds64[sub_w]
        # rank within worker: the earlier active submissions on its worker
        idx = jnp.arange(m, dtype=i32)
        earlier = ((sub_w[None, :] == sub_w[:, None]) & act[None, :]
                   & (idx[None, :] < idx[:, None]))
        rank = jnp.sum(earlier, axis=1, dtype=i32)
        steps = jnp.max(jnp.where(act, rank + 1, 0), initial=0).astype(i32)
        row = jnp.where(act, rank, m)  # inactive slots drop off the grid
        grid_a = jnp.zeros((m, n), free_at.dtype).at[row, sub_w].set(
            sub_arr, mode="drop")
        grid_d = jnp.zeros((m, n), free_at.dtype).at[row, sub_w].set(
            dur, mode="drop")
        grid_v = jnp.zeros((m, n), bool).at[row, sub_w].set(True, mode="drop")

        def step(r, st):
            fa, grid_s = st
            a = jax.lax.dynamic_index_in_dim(grid_a, r, keepdims=False)
            d = jax.lax.dynamic_index_in_dim(grid_d, r, keepdims=False)
            v = jax.lax.dynamic_index_in_dim(grid_v, r, keepdims=False)
            start = jnp.maximum(a, fa)
            fa = jnp.where(v, start + d, fa)
            return fa, jax.lax.dynamic_update_index_in_dim(grid_s, start, r, 0)

        free_at, grid_s = jax.lax.fori_loop(
            i32(0), steps, step, (free_at, jnp.zeros((m, n), free_at.dtype)))
        start = grid_s[jnp.minimum(row, m - 1), sub_w]
        done = start + dur
    return free_at, start, done, steps


def pending_slots(p_valid, act, pend_cap):
    """Where one turn's new in-flight copies go: the ``i``-th active
    submission (``act``, counted in submission order) takes the ``i``-th
    free slot of ``p_valid`` in index order. Survivors stay where they are:
    slots are unordered, and every pass that needs an order sorts by
    ``p_seq``.

    The rank search is a compare-and-reduce of the ``m`` ranks against the
    running count of free slots (``pend_cap`` x ``m`` compares), never a
    scatter: a TPU scatter costs by its updates, and ``jnp.nonzero`` builds
    one of ``pend_cap`` updates.

    Active submissions beyond the free count get slot ``pend_cap`` (a
    ``mode="drop"`` scatter skips them) and count in ``n_over``: the last
    ones in ``act`` order, those with ``nv + pos >= pend_cap`` for ``nv``
    live slots. Inactive submissions get ``pend_cap`` too and count
    nowhere.

    Returns ``(slot[m], pos[m], n_over, n_live)``: ``pos`` is each
    submission's rank among the active ones (its ``p_seq`` offset),
    ``n_live`` the live slots once the new copies are in; all int32."""
    i32 = jnp.int32
    pos = jnp.cumsum(act, dtype=i32) - 1
    free_rank = jnp.cumsum(~p_valid, dtype=i32)  # free slots in [0, j]
    n_free = free_rank[-1]
    found = jnp.searchsorted(free_rank, pos, side="right",
                             method="compare_all").astype(i32)
    slot = jnp.where(act, found, i32(pend_cap))
    n_act = jnp.sum(act, dtype=i32)
    placed = jnp.minimum(n_act, n_free)
    return slot, pos, n_act - placed, i32(pend_cap) - n_free + placed


@functools.lru_cache(maxsize=8)
def _build_scan(n, k, comp_cap, pend_cap, policy, max_fake, use_alias,
                fake_cost, churn=False, burst_cap=0, burst_cost=0.0,
                observe=None):
    """Compile-once factory for the whole-run scan program (cached on the
    static shape/config tuple; the scan length T is carried by the xs
    shapes, so a new horizon recompiles — one compile per workload shape;
    the learner config rides as a jit pytree arg, not a baked closure).

    ``churn=True`` is the environment engine's membership axis: the xs
    gain per-turn ``(active[n], rejoin[n], burst_w[burst_cap])`` columns —
    the membership mask joins the traced state (every routing/benchmark
    draw is masked), rejoining workers cold-start the learner IN-CARRY
    (``learner.reset_workers``, the same fold the host router applies in
    ``set_membership``), and the probe burst submits alongside the fake
    jobs — no host callbacks anywhere in the run. ``churn=False`` compiles
    the exact pre-churn program.

    ``observe`` (an ``obs.ObserveConfig``) appends a ``TelemetryCarry``
    to the carry and folds the window metrics per turn (read-only w.r.t.
    the routing math — responses stay bit-equal to ``observe=None``).
    The ys gain ``(row, flag)``; with ``observe.emit_responses=False``
    the per-request response and μ̂ ys drop from the program entirely
    (stream-only mode for long horizons). ``observe=None`` compiles the
    exact pre-telemetry program."""

    def body(lcfg, carry, xs):
        if observe is not None:
            carry, tc = carry[:-1], carry[-1]
        (q_view, learner, arr, key, last_fake, free_at,
         p_done, p_start, p_rep, p_seq, p_valid, seq_ctr,
         over_flush, over_pend, chain_steps, pend_peak) = carry
        if churn:
            times64, costs64, speeds64, active_t, rejoin_t, burst_t = xs
        else:
            times64, costs64, speeds64 = xs
            active_t = rejoin_t = None
            burst_t = jnp.zeros((0,), jnp.int32)
        t64 = times64[-1]
        t32 = t64.astype(jnp.float32)

        # -- flush due completions, oldest done first (stable by insertion,
        #    the host loop's np.argsort(..., kind="stable") semantics)
        with obt.stage("flush"):
            due = p_valid & (p_done <= t64)
            n_due = jnp.sum(due)
            keydone = jnp.where(due, p_done, jnp.inf)
            order = jnp.lexsort((p_seq, keydone))
            sel = order[:comp_cap]
            rank_ok = jnp.arange(comp_cap) < n_due
            comp_w = jnp.where(rank_ok, p_rep[sel], -1).astype(jnp.int32)
            comp_t = jnp.where(
                rank_ok, (p_done[sel] - p_start[sel]).astype(jnp.float32), 0.0
            ).astype(jnp.float32)
            comp_now64 = jnp.max(jnp.where(rank_ok, p_done[sel], -jnp.inf))
            comp_now32 = jnp.where(n_due > 0, comp_now64, t64).astype(
                jnp.float32)
            flushed = jnp.zeros_like(p_valid).at[sel].set(rank_ok)
            p_valid = p_valid & ~flushed
            over_flush = over_flush + jnp.maximum(
                n_due - comp_cap, 0).astype(jnp.int32)

        # -- membership transition (churn only): rejoining workers
        #    cold-start the learner BEFORE this turn's completion fold —
        #    the same ordering as the host router's set_membership call
        if churn:
            learner = jax.lax.cond(
                jnp.any(rejoin_t),
                lambda l: lrn.reset_workers(l, rejoin_t, t32, active_t),
                lambda l: l,
                learner,
            )

        # -- μ̂ trace sample: the front buffer entering this turn (the value
        #    run_simulation appends — learner μ̂ as of the last flush,
        #    post-membership-reset on a churn turn)
        mu_tr = learner.mu_hat

        # -- the serving turn: same traced math as scheduler.serve_step in
        #    use_fresh_mu mode (async_mu=False), same key consumption
        fake_js, workers, q_view, learner, arr, key = rs._serve_step_math(
            q_view, learner, arr, learner.mu_hat, lcfg, key,
            comp_w, comp_t, (t32, last_fake, comp_now32),
            k, policy, max_fake, True, None, use_alias, active_t,
        )
        last_fake = t32

        # -- replica-pool chain, fakes then probe bursts then reals (the
        #    host's submit_batch calls in order), as the exact sequential
        #    recurrence
        act = jnp.concatenate(
            [fake_js >= 0, burst_t >= 0, jnp.ones((k,), bool)]
        )
        sub_w = jnp.concatenate(
            [jnp.maximum(fake_js, 0), jnp.maximum(burst_t, 0), workers]
        )
        sub_arr = jnp.concatenate(
            [jnp.full((max_fake + burst_cap,), t64), times64]
        )
        # probe bursts run at burst_cost (representative full-request cost
        # — their service times must be CALIBRATED with real traffic,
        # since they dominate a rejoined worker's fresh sample ring; the
        # cheap fake_cost there would bias its μ̂ ~4× high)
        sub_cost = jnp.concatenate(
            [jnp.full((max_fake,), fake_cost),
             jnp.full((burst_cap,), burst_cost), costs64]
        )

        free_at, sub_start, sub_done, steps = pool_chain(
            free_at, sub_w, sub_arr, sub_cost, act, speeds64)
        chain_steps = chain_steps + steps
        resp = sub_done[max_fake + burst_cap:] - times64  # f64[k]

        # -- append the new in-flight work: fakes-then-reals into free
        #    slots (slots are unordered; p_seq carries insertion order)
        with obt.stage("pending_append"):
            slot, pos, n_over, n_live = pending_slots(p_valid, act, pend_cap)
            p_done = p_done.at[slot].set(sub_done, mode="drop")
            p_start = p_start.at[slot].set(sub_start, mode="drop")
            p_rep = p_rep.at[slot].set(sub_w.astype(jnp.int32), mode="drop")
            p_seq = p_seq.at[slot].set(seq_ctr + pos, mode="drop")
            p_valid = p_valid.at[slot].set(True, mode="drop")
            over_pend = over_pend + n_over
            seq_ctr = seq_ctr + jnp.sum(act).astype(jnp.int32)
            pend_peak = jnp.maximum(pend_peak, n_live)

        carry = (q_view, learner, arr, key, last_fake, free_at,
                 p_done, p_start, p_rep, p_seq, p_valid, seq_ctr,
                 over_flush, over_pend, chain_steps, pend_peak)
        if observe is None:
            return carry, (resp, mu_tr, workers)
        with obt.stage("telemetry_fold"):
            tob = obw.plain_turn_obs(
                observe, t=t32, resp=resp, arrivals_k=k, q_view=q_view,
                lam_hat=est.lam_hat_ema(arr), mu_hat=learner.mu_hat,
                mu_true=speeds64, active=active_t,
            )
            tc, row, flag = obw.observe_turn(observe, tc, tob)
        if observe.emit_responses:
            return carry + (tc,), (resp, mu_tr, workers, row, flag)
        return carry + (tc,), (row, flag)

    # carry buffers are DONATED: the output carry reuses the input's
    # storage, so a chunked driver streams a long horizon through repeated
    # invocations with no host round-trip and no per-chunk reallocation —
    # the previous chunk's carry is consumed in place (its buffers read
    # back .is_deleted(); callers must not touch a donated carry again)
    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(lcfg, carry0, xs):
        return jax.lax.scan(functools.partial(body, lcfg), carry0, xs)

    return run


@functools.lru_cache(maxsize=8)
def _build_scan_faulty(n, k, comp_cap, pend_cap, policy, max_fake, use_alias,
                       fake_cost, churn, burst_cap, burst_cost, rc,
                       observe=None):
    """The failure-semantics variant of ``_build_scan``: the xs gain
    per-turn fault columns ``(kill_t[n], stall_t[n], stall_d[n])`` (+inf =
    no event) and the carry gains the copy-lifecycle columns of
    ``serving/recovery.run_workload_recovery`` — original task id/arrival/
    cost, deadline, attempt, duplicate/learn/timed-out/retry flags — plus
    the response min-fold array, the conservation counters, and the
    max-clean-service watermark. ``rc`` (a hashable ``RecoveryConfig``)
    is part of the compile key: retry/timeout/speculation stages are
    STATICALLY elided when their knobs are off, so an inert config
    compiles the plain per-turn math plus masked no-op fault arithmetic —
    float-identical to ``_build_scan`` (pinned by tests/test_faults.py).

    The per-turn order is the host loop's, step for step (see
    ``run_workload_recovery``); every float expression is written in the
    same operand order so host and scan agree float-for-float. Steps
    (2)-(4), (8)-(9) and (11) run under the recovery scopes
    (``obs.tracing.recovery_stage``). Beside the arrival placements the
    per-request ys carry each turn's retry copies' workers and tasks
    (i32[retry_cap] each) and backup copies' workers and tasks
    (i32[spec_cap] each), -1 where a slot launched nothing."""
    from repro.dist import straggler as strg
    from repro.serving import recovery as rcv

    retry_cap = int(rc.retry_cap)
    spec_cap = int(rc.spec_cap)
    retry_on = retry_cap > 0
    timeout_on = bool(np.isfinite(rc.timeout_mult))
    mult = float(rc.timeout_mult)
    lut = rcv.backoff_lut(rc)  # numpy f64 on BOTH layers (no XLA pow)
    budget = int(rc.retry_budget)
    mu_floor = float(rc.mu_floor)
    spec_ratio = float(rc.spec_ratio)

    def body(lcfg, carry, xs):
        if observe is not None:
            carry, tc = carry[:-1], carry[-1]
        (q_view, learner, arr, key, last_fake, free_at,
         p_done, p_start, p_rep, p_seq, p_valid, seq_ctr,
         over_flush, over_pend, chain_steps,
         p_task, p_arrv, p_cost, p_dead, p_att, p_dup, p_learn, p_to,
         p_retry, resp, ctr, max_clean, turn, pend_peak) = carry
        ctr_in = ctr  # window ledger deltas = end-of-turn ctr - ctr_in
        if churn:
            (times64, costs64, speeds64, active_t, rejoin_t, burst_t,
             kill_t, stall_t, stall_d) = xs
        else:
            times64, costs64, speeds64, kill_t, stall_t, stall_d = xs
            active_t = rejoin_t = None
            burst_t = jnp.zeros((0,), jnp.int32)
        t64 = times64[-1]
        t32 = t64.astype(jnp.float32)
        is_real = p_task >= 0
        n_pad = resp.shape[0] - 1  # pad slot of the response min-fold
        drain = jnp.zeros((n,), jnp.int32)

        with obt.recovery_stage("fault_apply"):
            # -- (2) blackout stall: in-flight copies past the stall instant
            #    take the outage on their clock and go dirty; the replica's
            #    FIFO chain (free_at) shifts with them
            aff = p_valid & jnp.isfinite(p_done) & (p_done > stall_t[p_rep])
            p_done = jnp.where(aff, p_done + stall_d[p_rep], p_done)
            p_learn = p_learn & ~aff
            ctr = ctr.at[rcv.CTR["stalled"]].add(jnp.sum(aff & is_real))
            free_at = jnp.where(free_at > stall_t, free_at + stall_d, free_at)

            # -- (3) crash kill: copies finishing after the crash are dropped;
            #    retryable real copies park as ghosts (done=+inf)
            killed = p_valid & jnp.isfinite(p_done) & (p_done > kill_t[p_rep])
            drain = drain.at[p_rep].add(killed.astype(jnp.int32))
            if retry_on:
                ghost = killed & is_real & ~p_dup & (p_att < budget)
            else:
                ghost = jnp.zeros_like(killed)
            ctr = ctr.at[rcv.CTR["kill_real"]].add(jnp.sum(killed & is_real))
            ctr = ctr.at[rcv.CTR["kill_fake"]].add(jnp.sum(killed & ~is_real))
            p_learn = p_learn & ~killed
            p_done = jnp.where(ghost, jnp.inf, p_done)
            p_retry = p_retry | ghost
            p_valid = p_valid & ~(killed & ~ghost)
            free_at = jnp.where(free_at > kill_t, kill_t, free_at)

            # -- (4) timeout: past-deadline copies go dirty; retryable ones
            #    queue a re-dispatch (statically elided when timeouts are off)
            if timeout_on:
                newly = (p_valid & is_real & jnp.isfinite(p_done)
                         & (t64 > p_dead) & ~p_to)
                p_to = p_to | newly
                p_learn = p_learn & ~newly
                if retry_on:
                    p_retry = p_retry | (newly & ~p_dup & (p_att < budget))
                ctr = ctr.at[rcv.CTR["timeout"]].add(jnp.sum(newly))

        # -- (5) flush due completions: CLEAN → learner fold (oldest done
        #    first, stable by insertion), dirty → queue drain only; every
        #    real completion min-folds its task's response
        with obt.stage("flush"):
            due = p_valid & (p_done <= t64)
            clean = due & p_learn
            n_clean = jnp.sum(clean)
            keydone = jnp.where(clean, p_done, jnp.inf)
            order = jnp.lexsort((p_seq, keydone))
            sel = order[:comp_cap]
            rank_ok = jnp.arange(comp_cap) < n_clean
            comp_w = jnp.where(rank_ok, p_rep[sel], -1).astype(jnp.int32)
            comp_t = jnp.where(
                rank_ok, (p_done[sel] - p_start[sel]).astype(jnp.float32), 0.0
            ).astype(jnp.float32)
            comp_now64 = jnp.max(jnp.where(rank_ok, p_done[sel], -jnp.inf))
            comp_now32 = jnp.where(n_clean > 0, comp_now64, t64).astype(
                jnp.float32)
            over_flush = over_flush + jnp.maximum(
                n_clean - comp_cap, 0).astype(jnp.int32)
            max_clean = jnp.maximum(max_clean, jnp.max(
                jnp.where(clean, p_done - p_start, -jnp.inf)))
            dirty = due & ~p_learn
            drain = drain.at[p_rep].add(dirty.astype(jnp.int32))
            ctr = ctr.at[rcv.CTR["comp_dirty"]].add(jnp.sum(dirty & is_real))
            dr = due & is_real
            lat_obs, ok_obs = p_done - p_arrv, dr  # telemetry: copy latency
            resp = resp.at[jnp.where(dr, p_task, n_pad)].min(
                jnp.where(dr, p_done - p_arrv, jnp.inf))
            ctr = ctr.at[rcv.CTR["comp_real"]].add(jnp.sum(dr))
            ctr = ctr.at[rcv.CTR["comp_fake"]].add(jnp.sum(due & ~is_real))
            p_valid = p_valid & ~due

        # -- (6) queue-view drain for killed/dirty copies, BEFORE the serve
        q_view = jnp.maximum(q_view - drain, 0)

        # -- (7) membership transition (outage windows ride the merged
        #    mask), then the μ̂ trace sample — the plain body's ordering
        if churn:
            learner = jax.lax.cond(
                jnp.any(rejoin_t),
                lambda l: lrn.reset_workers(l, rejoin_t, t32, active_t),
                lambda l: l,
                learner,
            )
        mu_tr = learner.mu_hat

        with obt.recovery_stage("retry_select"):
            # -- (8) stale-ghost sweep + (9) retry selection (earliest
            #    deadline first; candidacy is the PRIMARY sort key — with
            #    timeouts off every deadline ties at +inf)
            if retry_on:
                tclip = jnp.clip(p_task, 0, n_pad)
                ghosts = p_valid & p_retry & ~jnp.isfinite(p_done)
                p_valid = p_valid & ~(ghosts & jnp.isfinite(resp[tclip]))
                cand = p_valid & p_retry & ~jnp.isfinite(resp[tclip])
                keyd = jnp.where(cand, p_dead, jnp.inf)
                orderR = jnp.lexsort((p_seq, keyd, ~cand))
                chosen = orderR[:retry_cap]
                okR = jnp.arange(retry_cap) < jnp.sum(cand)
                r_task = jnp.where(okR, p_task[chosen], 0)
                r_arrv = jnp.where(okR, p_arrv[chosen], t64)
                r_cost = jnp.where(okR, p_cost[chosen], 1.0)
                r_att = jnp.where(okR, p_att[chosen] + 1, 0)
                ctr = ctr.at[rcv.CTR["retry"]].add(jnp.sum(okR))
                ghost_sel = okR & ~jnp.isfinite(p_done[chosen])
                selm = jnp.zeros_like(p_valid).at[chosen].set(okR)
                alivem = jnp.zeros_like(p_valid).at[chosen].set(
                    okR & ~ghost_sel)
                ghostm = jnp.zeros_like(p_valid).at[chosen].set(ghost_sel)
                p_retry = p_retry & ~selm
                p_dup = p_dup | alivem
                p_valid = p_valid & ~ghostm
            else:
                okR = jnp.zeros((0,), bool)
                r_task = jnp.zeros((0,), jnp.int32)
                r_arrv = jnp.zeros((0,), jnp.float64)
                r_cost = jnp.zeros((0,), jnp.float64)
                r_att = jnp.zeros((0,), jnp.int32)

        # -- (10) ONE widened serve/dispatch call: arrivals + retry slots
        #    against the CURRENT policy, mask and μ̂ (retry_cap=0 compiles
        #    the plain serve math — bit-identical program)
        if retry_on:
            slots = jnp.concatenate([jnp.ones((k,), bool), okR])
            fake_js, workers, q_view, learner, arr, key = rs._serve_step_math(
                q_view, learner, arr, learner.mu_hat, lcfg, key,
                comp_w, comp_t, (t32, last_fake, comp_now32),
                k, policy, max_fake, True, None, use_alias, active_t,
                k + retry_cap, slots,
            )
            wk, rw = workers[:k], workers[k:]
        else:
            fake_js, workers, q_view, learner, arr, key = rs._serve_step_math(
                q_view, learner, arr, learner.mu_hat, lcfg, key,
                comp_w, comp_t, (t32, last_fake, comp_now32),
                k, policy, max_fake, True, None, use_alias, active_t,
            )
            wk = workers
            rw = jnp.zeros((0,), jnp.int32)
        last_fake = t32

        # -- (11) speculative re-execution on the post-serve μ̂: duplicate
        #    the slowest suspected stragglers via the planner's greedy fill
        mu64 = learner.mu_hat.astype(jnp.float64)
        with obt.recovery_stage("spec_select"):
            if spec_cap > 0:
                age = t64 - p_arrv
                expect = p_cost / jnp.maximum(mu64[p_rep], mu_floor)
                ratio = age / expect
                tclip = jnp.clip(p_task, 0, n_pad)
                candS = (p_valid & jnp.isfinite(p_done) & is_real & ~p_dup
                         & ~p_retry & ~jnp.isfinite(resp[tclip])
                         & (ratio > spec_ratio))
                keyS = jnp.where(candS, -ratio, jnp.inf)
                orderS = jnp.lexsort((p_seq, keyS, ~candS))
                chosenS = orderS[:spec_cap]
                okS = jnp.arange(spec_cap) < jnp.sum(candS)
                p_dup = p_dup | jnp.zeros_like(p_valid).at[chosenS].set(okS)
                s_task = jnp.where(okS, p_task[chosenS], 0)
                s_arrv = jnp.where(okS, p_arrv[chosenS], t64)
                s_cost = jnp.where(okS, p_cost[chosenS], 1.0)
                s_att = jnp.where(okS, p_att[chosenS], 0)
                mu_plan = (jnp.where(active_t, learner.mu_hat, 0.0)
                           if churn else learner.mu_hat)
                spec_w = strg.speculative_workers(mu_plan, spec_cap).astype(
                    jnp.int32)
                ctr = ctr.at[rcv.CTR["spec"]].add(jnp.sum(okS))
                q_view = q_view.at[spec_w].add(okS.astype(jnp.int32))
            else:
                okS = jnp.zeros((0,), bool)
                s_task = jnp.zeros((0,), jnp.int32)
                s_arrv = jnp.zeros((0,), jnp.float64)
                s_cost = jnp.zeros((0,), jnp.float64)
                s_att = jnp.zeros((0,), jnp.int32)
                spec_w = jnp.zeros((0,), jnp.int32)

        # -- (12) deadlines for the new copies, from the post-serve μ̂
        #    (numpy-computed backoff LUT on both layers)
        dead_new = t64 + (mult * float(lut[0])) * costs64 / jnp.maximum(
            mu64[jnp.maximum(wk, 0)], mu_floor)
        lut_j = jnp.asarray(lut)
        if retry_on:
            fac_r = mult * lut_j[jnp.clip(r_att, 0, len(lut) - 1)]
            dead_rt = t64 + fac_r * r_cost / jnp.maximum(
                mu64[jnp.maximum(rw, 0)], mu_floor)
        else:
            dead_rt = jnp.zeros((0,), jnp.float64)
        if spec_cap > 0:
            fac_s = mult * lut_j[jnp.clip(s_att, 0, len(lut) - 1)]
            dead_sp = t64 + fac_s * s_cost / jnp.maximum(
                mu64[spec_w], mu_floor)
        else:
            dead_sp = jnp.zeros((0,), jnp.float64)

        # -- (13) pool chain: fakes → probe bursts → reals → retries →
        #    specs, the exact sequential recurrence with per-slot gating
        act = jnp.concatenate([
            fake_js >= 0, burst_t >= 0, jnp.ones((k,), bool),
            okR & (rw >= 0), okS,
        ])
        sub_w = jnp.concatenate([
            jnp.maximum(fake_js, 0), jnp.maximum(burst_t, 0), wk,
            jnp.maximum(rw, 0), spec_w,
        ])
        sub_arr = jnp.concatenate([
            jnp.full((max_fake + burst_cap,), t64), times64,
            jnp.full((retry_cap + spec_cap,), t64),
        ])
        sub_cost = jnp.concatenate([
            jnp.full((max_fake,), fake_cost),
            jnp.full((burst_cap,), burst_cost), costs64, r_cost, s_cost,
        ])

        free_at, sub_start, sub_done, steps = pool_chain(
            free_at, sub_w, sub_arr, sub_cost, act, speeds64)
        chain_steps = chain_steps + steps

        # -- (14) pending append: write the new copies with their full
        #    lifecycle columns into free slots (p_seq carries the order)
        sub_task = jnp.concatenate([
            jnp.full((max_fake + burst_cap,), -1, jnp.int32),
            turn * k + jnp.arange(k, dtype=jnp.int32),
            r_task.astype(jnp.int32), s_task.astype(jnp.int32),
        ])
        sub_arrv = jnp.concatenate([
            jnp.full((max_fake + burst_cap,), t64), times64, r_arrv, s_arrv,
        ])
        sub_dead = jnp.concatenate([
            jnp.full((max_fake + burst_cap,), jnp.inf), dead_new,
            dead_rt, dead_sp,
        ])
        sub_att = jnp.concatenate([
            jnp.zeros((max_fake + burst_cap + k,), jnp.int32),
            r_att.astype(jnp.int32), s_att.astype(jnp.int32),
        ])
        sub_dup = jnp.concatenate([
            jnp.zeros((max_fake + burst_cap + k + retry_cap,), bool),
            jnp.ones((spec_cap,), bool),
        ])
        ctr = ctr.at[rcv.CTR["launch_fake"]].add(
            jnp.sum(act[:max_fake + burst_cap]))

        with obt.stage("pending_append"):
            slot, pos, n_over, n_live = pending_slots(p_valid, act, pend_cap)
            p_done = p_done.at[slot].set(sub_done, mode="drop")
            p_start = p_start.at[slot].set(sub_start, mode="drop")
            p_rep = p_rep.at[slot].set(sub_w.astype(jnp.int32), mode="drop")
            p_seq = p_seq.at[slot].set(seq_ctr + pos, mode="drop")
            p_valid = p_valid.at[slot].set(True, mode="drop")
            p_task = p_task.at[slot].set(sub_task, mode="drop")
            p_arrv = p_arrv.at[slot].set(sub_arrv, mode="drop")
            p_cost = p_cost.at[slot].set(sub_cost, mode="drop")
            p_dead = p_dead.at[slot].set(sub_dead, mode="drop")
            p_att = p_att.at[slot].set(sub_att, mode="drop")
            p_dup = p_dup.at[slot].set(sub_dup, mode="drop")
            p_learn = p_learn.at[slot].set(True, mode="drop")
            p_to = p_to.at[slot].set(False, mode="drop")
            p_retry = p_retry.at[slot].set(False, mode="drop")
            over_pend = over_pend + n_over
            seq_ctr = seq_ctr + jnp.sum(act).astype(jnp.int32)
            pend_peak = jnp.maximum(pend_peak, n_live)

        carry = (q_view, learner, arr, key, last_fake, free_at,
                 p_done, p_start, p_rep, p_seq, p_valid, seq_ctr,
                 over_flush, over_pend, chain_steps,
                 p_task, p_arrv, p_cost, p_dead, p_att, p_dup, p_learn,
                 p_to, p_retry, resp, ctr, max_clean, turn + 1, pend_peak)
        # retry and backup copies: their workers and tasks, -1 where a slot
        # launched nothing
        sw = jnp.where(okS, spec_w, -1)
        rt = jnp.where(okR, r_task, -1).astype(jnp.int32)
        st = jnp.where(okS, s_task, -1).astype(jnp.int32)
        if observe is None:
            return carry, (mu_tr, wk, rw, sw, rt, st)
        with obt.stage("telemetry_fold"):
            tob = obw.faulty_turn_obs(
                observe, t=t32, resp=lat_obs, resp_ok=ok_obs, arrivals_k=k,
                q_view=q_view, lam_hat=est.lam_hat_ema(arr),
                mu_hat=learner.mu_hat, mu_true=speeds64, active=active_t,
                dctr=ctr - ctr_in,
            )
            tc, row, flag = obw.observe_turn(observe, tc, tob)
        if observe.emit_responses:
            return carry + (tc,), (mu_tr, wk, rw, sw, rt, st, row, flag)
        return carry + (tc,), (row, flag)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(lcfg, carry0, xs):
        return jax.lax.scan(functools.partial(body, lcfg), carry0, xs)

    return run


def run_simulation_scan(
    router: rt.RosellaRouter,
    pool: rt.SimulatedPool,
    *,
    arrival_rate: float,
    horizon: float,
    request_cost: float = 1.0,
    speed_schedule: "list[tuple[float, np.ndarray]] | None" = None,
    seed: int = 0,
    arrival_batch: int = 1,
    pend_cap: int = PEND_CAP,
    strict_overflow: bool = True,
    chunk_turns: int | None = None,
    observe: "obw.ObserveConfig | None" = None,
    obs_sink=None,
):
    """Drop-in for ``run_simulation`` with the whole loop scan-compiled.

    ``router`` supplies the initial state and configuration (policy,
    learner config, key, ``use_alias``) and ``pool`` the replica speeds —
    both are advanced to their final states on return, like the host loop.
    Semantics are the router's deterministic ``async_mu=False`` mode (the
    scan cannot observe host-timing-dependent μ̂ flips; pass an
    ``async_mu=False`` router when comparing streams).

    Returns ``(response_times, mu_trace, info)``; ``info`` carries the
    overflow counters (both 0 ⇒ the fixed capacities were faithful to the
    host loop) and the turn count.
    """
    wl = _precompute_workload(
        arrival_rate, horizon, request_cost, speed_schedule, seed,
        arrival_batch, pool.speeds,
    )
    if wl is None:
        return np.empty(0), np.zeros((0, router.n)), {
            "turns": 0, "flush_overflow": 0, "pend_overflow": 0,
            "pool_chain_steps": 0, "pend_peak": 0}
    times_np, costs_np, speeds_np = wl
    return run_workload_scan(
        router, pool, times_np, costs_np, speeds_np,
        fake_cost=request_cost * 0.25, pend_cap=pend_cap,
        strict_overflow=strict_overflow, chunk_turns=chunk_turns,
        observe=observe, obs_sink=obs_sink,
    )


#: Target device-side xs footprint per chunk when ``chunk_turns`` is
#: auto-sized (64 MiB keeps even fault-column workloads comfortably under
#: typical HBM/host-RAM budgets while amortizing per-chunk dispatch).
CHUNK_MAX_BYTES = 64 << 20


def auto_chunk_turns(T, k, n, *, churn=False, burst_cap=0, faulty=False,
                     pend_cap=PEND_CAP, max_bytes=None) -> int:
    """Heuristic chunk length (turns) for the chunked scan driver.

    Derivation: each turn's xs row costs ``8·(2k + n)`` bytes (times,
    costs, speeds) plus ``2n + 4·burst_cap`` with membership columns and
    ``24n`` with fault columns.  The cap is ``max_bytes // bytes_per_turn``
    (default ``CHUNK_MAX_BYTES`` = 64 MiB of xs per chunk), floored at
    ``max(64, pend_cap // k)`` so a chunk is never shorter than the
    in-flight window the pending buffer implies (chunking finer than that
    would re-dispatch a scan per queue drain for no memory win).  The
    result is clamped to ``[1, T]`` — small workloads keep compiling as a
    single chunk, so ``chunk_turns=None`` preserves today's programs
    bit-for-bit AND compile-for-compile at test scale.
    """
    per_turn = 8 * (2 * k + n)
    if churn:
        per_turn += 2 * n + 4 * burst_cap
    if faulty:
        per_turn += 3 * 8 * n
    if max_bytes is None:
        max_bytes = CHUNK_MAX_BYTES
    cap = int(max_bytes) // max(per_turn, 1)
    floor = max(64, pend_cap // max(k, 1))
    return max(1, min(int(T), max(cap, floor))) if T > 0 else 1


def _drive_scan(
    router: rt.RosellaRouter,
    pool: rt.SimulatedPool,
    xs_chunks,  # iterable of numpy xs tuples, each (times[t,k], costs[t,k],
    # speeds[t,n][, active, rejoin, burst][, kill, stall, stall_dur])
    *,
    n: int,
    k: int,
    churn: bool,
    burst_cap: int,
    faulty: bool,
    rc,  # resolved RecoveryConfig (None when not faulty)
    fake_cost: float,
    burst_cost: float,
    pend_cap: int,
    comp_cap: int | None,
    task_cap: int,  # faulty: response-buffer capacity (total tasks the
    # stream may launch); the ledger closes over the tasks actually seen
    observe: "obw.ObserveConfig | None",
    obs_sink,
    strict_overflow: bool,
    timing: bool = False,  # record per-chunk wall-clock (gen vs run,
    # block_until_ready-fenced), the phase seconds of the call's profiler
    # spans, bytes copied in, windows read back and RSS into
    # info["chunks"] — the sustained-throughput methodology of the load
    # harness
):
    """The chunk driver: pull xs chunks from an iterator, thread the DONATED
    carry device-to-device across chunk boundaries, and close the books.

    This is the shared engine under ``run_workload_scan`` (which feeds it
    slices of a pre-materialized workload) and ``repro.load.run_stream_scan``
    (which feeds it lazily generated chunks so the host never holds the
    full trace).  A scan over T turns is the composition of scans over its
    chunks, so chunking — however the chunks are produced — is bit-equal
    to one unchunked scan.

    Each call is one ``rosella.call`` profiler span with its phases
    (``obs.tracing.DriverCall``): pulling the chunk, copying it in,
    launching the program, the fence (``timing`` only) and the read-back."""
    from repro.serving import recovery as rcv

    if comp_cap is None:
        # the flush batch can never exceed the pending buffer; the
        # SERVE_COMP_CAP shape keeps the learner fold identical to the
        # host loop's serve_step padding at default capacities
        comp_cap = min(rt.SERVE_COMP_CAP, pend_cap)
    else:
        comp_cap = min(int(comp_cap), pend_cap)
    with jax.enable_x64(True):
        carry0 = (
            jnp.asarray(router.q_view),
            router.learner,
            router.arr,
            jnp.asarray(router.key),
            jnp.float32(router.last_fake_time),
            jnp.asarray(pool.free_at, jnp.float64),
            jnp.full((pend_cap,), jnp.inf, jnp.float64),  # p_done
            jnp.zeros((pend_cap,), jnp.float64),  # p_start
            jnp.zeros((pend_cap,), jnp.int32),  # p_rep
            jnp.zeros((pend_cap,), jnp.int32),  # p_seq
            jnp.zeros((pend_cap,), bool),  # p_valid
            jnp.int32(0),  # seq_ctr
            jnp.int32(0),  # over_flush
            jnp.int32(0),  # over_pend
            jnp.int32(0),  # chain_steps: pool-chain steps, summed over turns
        )
        if faulty:
            carry0 = carry0 + (
                jnp.full((pend_cap,), -1, jnp.int32),  # p_task
                jnp.zeros((pend_cap,), jnp.float64),  # p_arrv
                jnp.ones((pend_cap,), jnp.float64),  # p_cost
                jnp.full((pend_cap,), jnp.inf, jnp.float64),  # p_dead
                jnp.zeros((pend_cap,), jnp.int32),  # p_att
                jnp.zeros((pend_cap,), bool),  # p_dup
                jnp.ones((pend_cap,), bool),  # p_learn
                jnp.zeros((pend_cap,), bool),  # p_to
                jnp.zeros((pend_cap,), bool),  # p_retry
                jnp.full((task_cap + 1,), jnp.inf, jnp.float64),  # resp
                jnp.zeros((rcv.NCTR,), jnp.int64),  # ctr
                jnp.float64(0.0),  # max_clean
                jnp.int32(0),  # turn
            )
            run = _build_scan_faulty(
                n, k, comp_cap, pend_cap,
                router.policy, 8, router.use_alias, fake_cost,
                churn, burst_cap, float(burst_cost), rc, observe,
            )
        else:
            run = _build_scan(
                n, k, comp_cap, pend_cap,
                router.policy, 8, router.use_alias, fake_cost,
                churn, burst_cap, float(burst_cost), observe,
            )
        # pend_peak: the most live pending slots after an append, the last
        # carry leaf before the telemetry carry in both bodies
        carry0 = carry0 + (jnp.int32(0),)
        i_peak = len(carry0) - 1
        if observe is not None:
            carry0 = carry0 + (obw.init_carry(observe),)
        carry = carry0
        resp_l, mu_l, w_l = [], [], []
        copies_l = []  # faulty: per call, the retry and backup ys
        windows: list = []

        def _obs_chunk(rows, flags):
            new = obw.records_from_rows(observe, rows, flags)
            windows.extend(new)
            if obs_sink is not None and new:
                obs_sink(new)

        turns = 0
        active_last = None
        chunks_meta: list = []
        it = iter(xs_chunks)
        ci = 0
        while True:
            with obt.DriverCall(ci, timing) as call:
                try:
                    chunk = next(it)
                except StopIteration:
                    break
                c_turns = int(np.asarray(chunk[0]).shape[0])
                if c_turns == 0:
                    continue
                if faulty and (turns + c_turns) * k > task_cap:
                    raise RuntimeError(
                        f"stream exceeded task_cap={task_cap}: chunk {ci} "
                        f"would bring the launched-task count to "
                        f"{(turns + c_turns) * k} — size task_cap to the "
                        f"stream's total turns × k"
                    )
                call.phase("h2d")
                xs = tuple(jnp.asarray(x) for x in chunk)
                call.phase("launch")
                carry, ys = run(router.lcfg, carry, xs)
                if timing:
                    call.phase("fence")
                    jax.block_until_ready((carry, ys))
                call.phase("readback")
                n_windows = len(windows)
                if faulty:
                    if observe is None or observe.emit_responses:
                        mu_l.append(ys[0])
                        w_l.append(ys[1])
                        copies_l.append(ys[2:6])
                elif observe is None or observe.emit_responses:
                    resp_l.append(ys[0])
                    mu_l.append(ys[1])
                    w_l.append(ys[2])
                if observe is not None:
                    _obs_chunk(ys[-2], ys[-1])
                turns += c_turns
                if churn:
                    active_last = np.asarray(chunk[3][-1], bool)
                rss = oex.rss_mb() if timing else None
            if timing:
                sec = call.seconds
                chunks_meta.append({
                    "chunk": ci,
                    "turns": c_turns,
                    "requests": c_turns * k,
                    "gen_s": sec["next_chunk"],
                    "run_s": sec["launch"] + sec["fence"],
                    "rss_mb": rss,
                    "h2d_s": sec["h2d"],
                    "launch_s": sec["launch"],
                    "fence_s": sec["fence"],
                    "readback_s": sec["readback"],
                    "bytes_in": sum(int(x.nbytes) for x in xs),
                    "windows": len(windows) - n_windows,
                })
            ci += 1
        if observe is not None and turns > 0:
            tail = obw.final_partial_record(observe, carry[-1])
            if tail is not None:
                windows.append(tail)
                if obs_sink is not None:
                    obs_sink([tail])
        ledger = None
        n_tasks = turns * k
        if faulty:
            # the response min-fold rides the carry (a task's copies can
            # complete many turns after its launch); finalize with the
            # shared numpy epilogue so host and scan close the books
            # identically
            validF = np.asarray(carry[10])
            resp_acc = np.asarray(carry[24])[:n_tasks].copy()
            ctr = np.asarray(carry[25]).copy()
            rcv.drain_pending(
                resp_acc, ctr, np.asarray(carry[6])[validF],
                np.asarray(carry[15])[validF], np.asarray(carry[16])[validF],
            )
            resp, ledger = rcv.build_ledger(
                resp_acc, ctr, n_tasks, float(carry[26]))
            mu_trace = (np.concatenate([np.asarray(m) for m in mu_l])
                        if mu_l else np.zeros((0, n), np.float32))
        elif resp_l:
            resp = np.concatenate([np.asarray(r) for r in resp_l]).reshape(-1)
            mu_trace = np.concatenate([np.asarray(m) for m in mu_l])
        else:
            resp = np.empty(0)
            mu_trace = np.zeros((0, n), np.float32)
        info = {
            "turns": turns,
            "flush_overflow": int(carry[12]),
            "pend_overflow": int(carry[13]),
            "pool_chain_steps": int(carry[14]),
            "pend_peak": int(carry[i_peak]),
        }
        if w_l:  # placements of the k arrivals per turn, in request order
            info["workers"] = np.concatenate(
                [np.asarray(w) for w in w_l]).reshape(-1).astype(np.int64)
        # per turn, the retry and backup copies' workers and tasks (-1: none)
        for i, key in enumerate(("retry_workers", "spec_workers",
                                 "retry_tasks", "spec_tasks")):
            if copies_l:
                info[key] = np.concatenate(
                    [np.asarray(c[i]) for c in copies_l]).astype(np.int64)
        if ledger is not None:
            info["ledger"] = ledger
        if observe is not None:
            info["windows"] = windows
        if timing:
            info["chunks"] = chunks_meta
        # advance the host-side objects to the final state, as the host
        # loop would have left them
        router.q_view = jnp.asarray(np.asarray(carry[0]))
        router.learner = jax.tree.map(
            lambda x: jnp.asarray(np.asarray(x)), carry[1]
        )
        router.arr = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), carry[2])
        router.key = jnp.asarray(np.asarray(carry[3]))
        router.last_fake_time = float(carry[4])
        router.mu_front = router.learner.mu_hat
        router._mu_pending = None
        pool.free_at = np.asarray(carry[5])
    if churn and active_last is not None:
        router.active = jnp.asarray(active_last, bool)
    if router.use_alias:
        import repro.core.dispatch as dsp

        router.table_front = dsp.build_alias_table(
            router.mu_front, router.active
        )
    if strict_overflow and (info["flush_overflow"] or info["pend_overflow"]):
        raise RuntimeError(
            f"scan capacities overflowed (flush_overflow="
            f"{info['flush_overflow']}, pend_overflow="
            f"{info['pend_overflow']}): results silently dropped work. "
            f"Raise pend_cap (current {pend_cap}; pend_cap=None auto-sizes "
            f"to the total-submission bound) or pass strict_overflow=False "
            f"to inspect the counters."
        )
    return resp, mu_trace, info


def run_workload_scan(
    router: rt.RosellaRouter,
    pool: rt.SimulatedPool,
    times_np: np.ndarray,  # f64[T, k] per-turn arrival times
    costs_np: np.ndarray,  # f64[T, k] per-turn request costs
    speeds_np: np.ndarray,  # f64[T, n] replica speeds entering each turn
    *,
    active_np: np.ndarray | None = None,  # bool[T, n] membership per turn
    rejoin_np: np.ndarray | None = None,  # bool[T, n] offline→online edges
    burst_np: np.ndarray | None = None,  # i32[T, Bc] probe-burst targets (-1 pad)
    fake_cost: float = 0.25,
    burst_cost: float | None = None,  # default: 4×fake_cost = the full
    # request cost — rejoin probes must be cost-calibrated with real
    # traffic or the rejoined worker's μ̂ rebuilds ~4× high
    kill_np: np.ndarray | None = None,  # f64[T, n] crash instants (+inf)
    stall_np: np.ndarray | None = None,  # f64[T, n] blackout instants
    stall_dur_np: np.ndarray | None = None,  # f64[T, n] blackout durations
    recovery=None,  # RecoveryConfig — engages the failure-semantics scan
    # even without fault columns (timeouts/retries against slow workers)
    pend_cap: int | None = None,  # None → auto-sized: the total-submission
    # bound (turns × per-turn appends), clamped to [PEND_CAP, 65536] — a
    # workload that can NEVER overflow the pending set. Pass an explicit
    # cap to bound the per-turn flush-sort cost instead (the perf path);
    # overflow then raises under strict_overflow. The cap does not change
    # results absent overflow.
    strict_overflow: bool = True,  # overflowed capacities RAISE instead of
    # returning silently-lossy results; pass False to get the counters
    # back in info and handle them yourself (the benchmark harness warns)
    chunk_turns: int | None = None,  # stream the horizon through scans of
    # ≤ this many turns: the DONATED carry flows device-to-device across
    # chunk boundaries (no host round-trip), so arbitrarily long horizons
    # run at a bounded xs footprint. Bit-identical to one unchunked scan
    # (a scan over T is the composition of scans over its chunks). The
    # tail chunk compiles its own program when T % chunk_turns != 0.
    # None → auto-sized by ``auto_chunk_turns``: the largest chunk whose
    # xs rows fit ``chunk_max_bytes`` (default 64 MiB), floored at
    # max(64, pend_cap // k) turns so chunks never undercut the in-flight
    # window; small workloads resolve to a single chunk, i.e. exactly the
    # old whole-horizon program.
    chunk_max_bytes: int | None = None,  # auto-sizing memory hint — the
    # per-chunk xs byte budget fed to ``auto_chunk_turns`` (ignored when
    # chunk_turns is given)
    comp_cap: int | None = None,  # per-turn completion-flush capacity.
    # None → min(SERVE_COMP_CAP, pend_cap), the host loop's padding (keeps
    # the learner fold identical at default capacities). Raise it for
    # large arrival batches (k ≳ 256) or post-burst drains, where > 256
    # completions can come due in one turn and would count as
    # flush_overflow. Absent overflow the cap does not change results.
    observe: "obw.ObserveConfig | None" = None,  # in-scan telemetry: fold
    # windowed metrics in-carry and return the window stream in
    # info["windows"] (records, chunk-continuous). Telemetry is read-only
    # w.r.t. routing — responses stay bit-equal to observe=None. With
    # observe.emit_responses=False the per-request response/μ̂ ys drop
    # from the program (stream-only mode: empty responses, bounded
    # memory at any horizon).
    obs_sink=None,  # callable(list[record]) invoked once per chunk with
    # the window records that completed in that chunk (e.g. an
    # obs.JsonlSink) — the streaming path for long horizons
):
    """Scan-compile a PRE-MATERIALIZED workload — the environment engine's
    entry point (``repro.env``): any scenario that can lay out its arrival
    times, request costs, capacity trajectory and membership schedule as
    per-turn arrays runs as ONE compiled program. ``run_simulation_scan``
    is this function fed by the homogeneous-Poisson precompute; scenario
    workloads (MMPP flash crowds, diurnal waves, trace replays, OU speed
    drift, worker churn) come from ``Scenario.compile_serving``.

    With the membership columns present, the churn variant of the scan
    body runs: the active mask joins the traced state, rejoin edges
    cold-start the learner in-carry, and per-turn probe bursts
    (``burst_np`` worker ids, -1 padded) submit at ``burst_cost`` — the
    FULL request cost by default, NOT ``fake_cost``, so the rejoined
    worker's rebuilt sample ring is cost-calibrated with real traffic —
    matching ``env.serving.run_workload`` (the host loop)
    float-for-float. Without them, the compiled program is byte-identical
    to the pre-env scan.

    With fault columns (``kill_np``/``stall_np``/``stall_dur_np`` from
    ``Scenario.compile_serving``) or a ``recovery`` config, the
    failure-semantics program runs instead (``_build_scan_faulty``): crash
    kills, blackout stalls, deadline timeouts, retry re-dispatch and
    speculative re-execution — float-for-float against
    ``env.serving.run_workload`` with the same recovery config. Responses
    are then task-indexed with NaN for lost tasks, and ``info["ledger"]``
    carries the conservation ledger.

    Unless telemetry is stream-only, ``info["workers"]`` holds the worker
    each request was first placed on, in request order (the host loop's
    ``info["workers"]``), and the failure-semantics program adds
    ``info["retry_workers"]`` and ``info["retry_tasks"]`` (i64[T,
    retry_cap]), ``info["spec_workers"]`` and ``info["spec_tasks"]`` (i64[T,
    spec_cap]): each turn's retry and backup copies, where they run and the
    task each re-executes, -1 where a slot launched nothing."""
    T, k = times_np.shape
    n = router.n
    faulty = (kill_np is not None or stall_np is not None
              or recovery is not None)
    if active_np is None and router.active is not None:
        # the router already carries a (static) membership mask — honor it
        # like the host loop does on every serve_turn, or the scan would
        # silently route to offline replicas set_membership promised to
        # exclude (no rejoin edges: the mask is constant over the run)
        active_np = np.broadcast_to(
            np.asarray(router.active, bool), (T, n)
        ).copy()
    churn = active_np is not None
    burst_cap = 0
    if churn and burst_np is not None:
        burst_cap = int(burst_np.shape[1])
    if burst_cost is None:
        burst_cost = 4.0 * fake_cost
    from repro.serving import recovery as rcv

    rc = (recovery if recovery is not None else rcv.INERT_RECOVERY) \
        if faulty else None
    per_turn = 8 + burst_cap + k + (
        (rc.retry_cap + rc.spec_cap) if faulty else 0)
    if pend_cap is None:
        # total-submission bound: this workload can never overflow the
        # pending set (the flush-sort cost scales with the cap — pass an
        # explicit pend_cap on perf-critical paths)
        need = max(PEND_CAP, T * per_turn)
        pend_cap = PEND_CAP
        while pend_cap < need and pend_cap < 65536:
            pend_cap <<= 1

    xs_np = (
        np.asarray(times_np, np.float64),
        np.asarray(costs_np, np.float64),
        np.asarray(speeds_np, np.float64),
    )
    if churn:
        rej = (
            rejoin_np if rejoin_np is not None
            else np.zeros((T, n), bool)
        )
        bw = (
            burst_np if burst_np is not None
            else np.zeros((T, 0), np.int32)
        )
        xs_np = xs_np + (
            np.asarray(active_np, bool),
            np.asarray(rej, bool),
            np.asarray(bw, np.int32),
        )
    if faulty:
        xs_np = xs_np + (
            np.asarray(kill_np, np.float64) if kill_np is not None
            else np.full((T, n), np.inf),
            np.asarray(stall_np, np.float64) if stall_np is not None
            else np.full((T, n), np.inf),
            np.asarray(stall_dur_np, np.float64)
            if stall_dur_np is not None else np.zeros((T, n)),
        )
    if chunk_turns is None:
        chunk_turns = auto_chunk_turns(
            T, k, n, churn=churn, burst_cap=burst_cap, faulty=faulty,
            pend_cap=pend_cap, max_bytes=chunk_max_bytes,
        )
    step = max(int(chunk_turns), 1)

    def _slices():
        for s in range(0, T, step):
            yield tuple(x[s:s + step] for x in xs_np)

    return _drive_scan(
        router, pool, _slices(), n=n, k=k, churn=churn, burst_cap=burst_cap,
        faulty=faulty, rc=rc, fake_cost=fake_cost,
        burst_cost=float(burst_cost), pend_cap=pend_cap, comp_cap=comp_cap,
        task_cap=T * k, observe=observe, obs_sink=obs_sink,
        strict_overflow=strict_overflow,
    )


# ---------------------------------------------------------------------------
# One-program fleet: S frontends × environment × serving loop in ONE scan
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _build_fleet_scan(n, S, k_f, comp_cap, pend_cap, policy, max_fake,
                      use_alias, fake_cost, sync_every, frozen_mu,
                      churn=False, burst_cap=0, burst_cost=0.0, mesh=None,
                      faulty=False, observe=None):
    """Compile-once factory for the FLEET scan program: S full frontends
    (stale views, learners, λ̂ streams, double-buffered μ̂, herd
    bookkeeping — a ``FleetServeCarry``) ride the carry alongside the env
    columns and the shared replica pool, with a sync-round fold every
    ``sync_every`` turns under a ``lax.cond`` — so an S-frontend churn/
    interference episode is ONE compiled program, and at S=1 the traced
    math collapses to ``_build_scan``'s bit-for-bit.

    Per turn, in the host fleet loop's order (``run_fleet_simulation`` /
    ``env.serving.run_workload``): membership transition (per-frontend
    learner cold-start + forced μ̂ flip) → sync round (delta-reconciled
    global view, μ̂ merge, λ̂ sum, herd unwind — masked under churn) →
    per-frontend completion flush from the shared pending set → herd
    correction + μ̂ front-buffer flips → S serving turns in one vmapped
    engine call (``scheduler.serve_step_fleet``) → the shared pool chain
    (every frontend's fakes, probe bursts, then all reals in global
    arrival order) → pending-set append.

    ``frozen_mu=False`` (default) is the host-parity mode: each frontend
    routes on its own post-fold learner μ̂ exactly like a deterministic
    ``async_mu=False`` ``RosellaRouter``. ``frozen_mu=True`` is the
    FleetSimState regime: routing reads the carried ``mu_front`` rows and
    draws through the carried per-frontend alias tables, which rebuild
    ONLY at sync rounds and membership flips — the O(1)-amortized fleet
    hot path.

    ``mesh`` (optional, hashable) shards the frontend axis: the serve
    stage runs inside ``shard_map`` with NO collectives and the sync fold
    runs the ``fleet/sync`` psum/pmean/all_gather collectives — sync
    rounds are the only scheduler collectives in the loop (the shared
    pool/pending bookkeeping is the ENVIRONMENT's data motion: requests
    reaching workers and completions returning — physical in any
    deployment, and left to the partitioner)."""
    from repro.core import dispatch as dsp
    from repro.core import estimator as est
    from repro.fleet import conflict as cfl
    from repro.fleet import sync as fsync
    from repro.fleet.state import FleetServeCarry  # noqa: F401 (carry type)

    use_fresh = not frozen_mu
    k = S * k_f
    if mesh is not None:
        serve_stage = fsync.make_fleet_serve_stage(
            mesh, k_f, policy, max_fake=max_fake, use_fresh_mu=use_fresh,
            use_alias=use_alias, churn=churn,
        )
        sync_stage = fsync.make_fleet_scan_sync(mesh)

    if faulty:
        from repro.serving import recovery as rcv

    def body(lcfg, carry, xs):
        if observe is not None:
            carry, tc = carry[:-1], carry[-1]
        if faulty:
            (fl, free_at, p_done, p_start, p_rep, p_seq, p_fr, p_valid,
             seq_ctr, turn, over_flush, over_pend,
             p_task, p_arrv, p_learn, resp_acc, ctr, max_clean) = carry
            xs, fault_xs = xs[:-3], xs[-3:]
            kill_t, stall_t, stall_d = fault_xs
        else:
            (fl, free_at, p_done, p_start, p_rep, p_seq, p_fr, p_valid,
             seq_ctr, turn, over_flush, over_pend) = carry
        if observe is not None:
            # per-frontend telemetry ledger deltas for this turn (i32[S])
            kills_f = jnp.zeros((S,), jnp.int32)
            dirty_f = jnp.zeros((S,), jnp.int32)
            comp_f = jnp.zeros((S,), jnp.int32)
            lat_obs = jnp.zeros((S, 0), jnp.float64)
            ok_obs = jnp.zeros((S, 0), bool)
        if churn:
            (times64, costs64, speeds64, active_t, rejoin_t, changed_t,
             burst_t) = xs
        else:
            times64, costs64, speeds64 = xs
            active_t = rejoin_t = changed_t = None
            burst_t = jnp.zeros((0,), jnp.int32)
        t64 = times64[-1]
        t32 = t64.astype(jnp.float32)

        # -- fault arithmetic (kill/stall + loss accounting subset — the
        #    fleet carries NO retry/timeout/speculation machinery): same
        #    per-copy math as _build_scan_faulty steps (2)-(3), with the
        #    queue drain tracked per (frontend, worker)
        if faulty:
            is_real = p_task >= 0
            n_pad = resp_acc.shape[0] - 1
            drainSn = jnp.zeros((S, n), jnp.int32)
            aff = p_valid & jnp.isfinite(p_done) & (p_done > stall_t[p_rep])
            p_done = jnp.where(aff, p_done + stall_d[p_rep], p_done)
            p_learn = p_learn & ~aff
            ctr = ctr.at[rcv.CTR["stalled"]].add(jnp.sum(aff & is_real))
            free_at = jnp.where(free_at > stall_t, free_at + stall_d,
                                free_at)
            killed = p_valid & jnp.isfinite(p_done) & (p_done > kill_t[p_rep])
            drainSn = drainSn.at[p_fr, p_rep].add(killed.astype(jnp.int32))
            if observe is not None:
                kills_f = kills_f.at[p_fr].add(
                    (killed & is_real).astype(jnp.int32), mode="drop")
            ctr = ctr.at[rcv.CTR["kill_real"]].add(jnp.sum(killed & is_real))
            ctr = ctr.at[rcv.CTR["kill_fake"]].add(jnp.sum(killed & ~is_real))
            p_learn = p_learn & ~killed
            p_valid = p_valid & ~killed
            free_at = jnp.where(free_at > kill_t, kill_t, free_at)

        learner = fl.learner
        mu_front = fl.mu_front
        mu_pend = fl.mu_pend
        tables = fl.tables

        # -- membership transition: EVERY frontend cold-starts the
        #    rejoined workers (host: sync()/set_membership per frontend),
        #    and a change turn forces the per-frontend μ̂ flip + masked
        #    table rebuild — after this, no frontend can route offline
        if churn:
            learner = jax.lax.cond(
                jnp.any(rejoin_t),
                lambda l: jax.vmap(
                    lambda lf: lrn.reset_workers(lf, rejoin_t, t32, active_t)
                )(l),
                lambda l: l,
                learner,
            )
            mu_front = jnp.where(changed_t, learner.mu_hat, mu_front)
            mu_pend = jnp.where(changed_t, False, mu_pend)
            if frozen_mu and use_alias:
                with obt.stage("alias_build"):
                    tables = jax.lax.cond(
                        changed_t,
                        lambda mu_tb: jax.vmap(
                            lambda mrow: dsp.build_alias_table(mrow, active_t)
                        )(mu_tb[0]),
                        lambda mu_tb: mu_tb[1],
                        (mu_front, tables),
                    )

        # -- sync round every sync_every turns (turn 0 included, like the
        #    host loop): herd corrections unwind, per-frontend deltas sum
        #    onto the agreed snapshot, μ̂ merges, λ̂ streams sum. At S=1
        #    the fold is a numeric no-op on q (views are exact), so the
        #    single-scan bit-equality survives any cadence.
        lam_f = est.lam_hat_ema(fl.arr)  # f32[S], pre-serve (host order)

        def sync_fn(op):
            q_view, herd_applied, q_snap, lrn_, mu_f, mu_p, tbl = op
            if mesh is not None:
                q2, mu2, gaps, global_q, lam_sum = sync_stage(
                    q_view, herd_applied, q_snap, lrn_.mu_hat, lam_f,
                )
                mu_merged = mu2[0]
            else:
                qs = q_view - herd_applied
                deltas = qs - q_snap[None, :]
                # explicit i32 accumulators: this fold traces under the
                # x64 context, where default integer sums widen to i64
                global_q = jnp.maximum(
                    q_snap + deltas.sum(axis=0, dtype=jnp.int32), 0
                )
                gaps = jnp.abs(qs - global_q[None, :]).sum(
                    axis=1, dtype=jnp.int32
                )
                mu_merged = lrn.sync_estimates(lrn_.mu_hat)
                q2 = jnp.broadcast_to(global_q[None], q_view.shape)
                mu2 = jnp.broadcast_to(mu_merged[None], mu_f.shape)
                lam_sum = jnp.sum(lam_f)
            if frozen_mu and use_alias:
                with obt.stage("alias_build"):
                    tb = dsp.build_alias_table(mu_merged, active_t)
                tbl = dsp.AliasTable(
                    prob=jnp.broadcast_to(tb.prob[None], (S, n)),
                    alias=jnp.broadcast_to(tb.alias[None], (S, n)),
                )
            return (q2, jnp.zeros_like(herd_applied), global_q, mu2,
                    jnp.zeros_like(mu_p), tbl, t32,
                    lam_sum.astype(jnp.float32), gaps.astype(jnp.int32))

        def no_sync_fn(op):
            q_view, herd_applied, q_snap, lrn_, mu_f, mu_p, tbl = op
            return (q_view, herd_applied, q_snap, mu_f, mu_p, tbl,
                    fl.t_sync, fl.lam_global,
                    jnp.zeros((S,), jnp.int32))

        did_sync = (turn % sync_every) == 0
        (q_view, herd_applied, q_snap, mu_front, mu_pend, tables, t_sync,
         lam_global, gaps) = jax.lax.cond(
            did_sync, sync_fn, no_sync_fn,
            (fl.q_view, fl.herd_applied, fl.q_snap, learner, mu_front,
             mu_pend, tables),
        )

        # -- per-frontend completion flush from the SHARED pending set:
        #    completions return to the frontend that placed them; within a
        #    frontend, oldest done first, stable by insertion — the single
        #    scan's exact flush math vmapped over the p_fr partition
        def flushf(fm):
            n_due = jnp.sum(fm)
            keydone = jnp.where(fm, p_done, jnp.inf)
            # i32 scatter/gather indices: the x64 context makes lexsort
            # return i64, which the SPMD partitioner (mesh path) rejects
            # when it mixes with its own i32 shard offsets
            order = jnp.lexsort((p_seq, keydone)).astype(jnp.int32)
            sel = order[:comp_cap]
            rank_ok = jnp.arange(comp_cap) < n_due
            comp_w = jnp.where(rank_ok, p_rep[sel], -1).astype(jnp.int32)
            comp_t = jnp.where(
                rank_ok, (p_done[sel] - p_start[sel]).astype(jnp.float32),
                0.0,
            ).astype(jnp.float32)
            comp_now64 = jnp.max(jnp.where(rank_ok, p_done[sel], -jnp.inf))
            comp_now32 = jnp.where(n_due > 0, comp_now64, t64).astype(
                jnp.float32
            )
            flushed = jnp.zeros_like(p_valid).at[sel].set(rank_ok)
            return comp_w, comp_t, comp_now32, flushed, n_due

        with obt.stage("flush"):
            due = p_valid & (p_done <= t64)
            clean = due & p_learn if faulty else due
            fmask = clean[None, :] & (
                p_fr[None, :] == jnp.arange(S, dtype=jnp.int32)[:, None]
            )
            comp_w, comp_t, comp_now32, flushed_f, n_due_f = jax.vmap(
                flushf)(fmask)
            over_flush = over_flush + jnp.sum(
                jnp.maximum(n_due_f - comp_cap, 0)
            ).astype(jnp.int32)
            if faulty:
                # dirty completions (stall-touched, killed-adjacent) drain
                # the owning frontend's view only; every real completion
                # min-folds its task's response; the books stay balanced
                max_clean = jnp.maximum(max_clean, jnp.max(
                    jnp.where(clean, p_done - p_start, -jnp.inf)))
                dirtyF = due & ~p_learn
                drainSn = drainSn.at[p_fr, p_rep].add(
                    dirtyF.astype(jnp.int32))
                ctr = ctr.at[rcv.CTR["comp_dirty"]].add(
                    jnp.sum(dirtyF & is_real))
                drF = due & is_real
                if observe is not None:
                    dirty_f = dirty_f.at[p_fr].add(
                        (dirtyF & is_real).astype(jnp.int32), mode="drop")
                    comp_f = comp_f.at[p_fr].add(
                        (clean & is_real).astype(jnp.int32), mode="drop")
                    lat_obs = jnp.broadcast_to(
                        (p_done - p_arrv)[None, :], (S, pend_cap))
                    ok_obs = drF[None, :] & (
                        p_fr[None, :]
                        == jnp.arange(S, dtype=jnp.int32)[:, None])
                resp_acc = resp_acc.at[jnp.where(drF, p_task, n_pad)].min(
                    jnp.where(drF, p_done - p_arrv, jnp.inf))
                ctr = ctr.at[rcv.CTR["comp_real"]].add(jnp.sum(drF))
                ctr = ctr.at[rcv.CTR["comp_fake"]].add(
                    jnp.sum(due & ~is_real))
                p_valid = p_valid & ~due
                q_view = jnp.maximum(q_view - drainSn, 0)
            else:
                p_valid = p_valid & ~jnp.any(flushed_f, axis=0)

        # -- herd correction (pre-flip mu_front, like the host): inflate
        #    each view by the expected peer placements since its last sync,
        #    incrementally over what is already folded in. Zero at S=1 (the
        #    (S−1) factor) and wherever herd_scale is 0 — exact no-ops.
        want = jnp.round(
            fl.herd_scale[:, None] * jax.vmap(
                lambda lf, mu: cfl.expected_peer_placements(
                    lf, t32 - t_sync, mu, S
                )
            )(lam_f, mu_front)
        ).astype(jnp.int32)
        q_view = q_view + (want - herd_applied)
        herd_applied = want

        # -- μ̂ front-buffer flip per frontend (deterministic _flip_mu: a
        #    pending refresh is always this frontend's own learner μ̂)
        mu_front = jnp.where(mu_pend[:, None], learner.mu_hat, mu_front)

        # -- S serving turns in one vmapped engine call (or one shard_map
        #    with NO collectives on the sharded path)
        if mesh is not None:
            dummy = jnp.zeros((S, n), jnp.float32)
            tbp, tba = (
                (tables.prob, tables.alias) if tables is not None
                else (dummy, dummy.astype(jnp.int32))
            )
            msk = (
                active_t if churn
                else jnp.ones((n,), bool)
            )
            fake_js, workers, q_view, learner, arr, key = serve_stage(
                q_view, learner, fl.arr, mu_front, fl.key, comp_w, comp_t,
                fl.last_fake, comp_now32, t32, lcfg, tbp, tba, msk,
            )
        else:
            fake_js, workers, q_view, learner, arr, key = (
                rs.serve_step_fleet(
                    q_view, learner, fl.arr, mu_front, lcfg, fl.key,
                    comp_w, comp_t, (t32, fl.last_fake, comp_now32),
                    k_f, policy, max_fake, use_fresh, tables, use_alias,
                    active_t,
                )
            )
        last_fake = jnp.full((S,), t32)
        mu_pend = n_due_f > 0  # a flush arms the next flip (host serve_turn)
        mu_tr = mu_front[0]  # the trace row run_fleet_simulation samples

        # -- shared replica-pool chain: every frontend's fakes (frontend
        #    order), probe bursts, then ALL reals in global arrival order —
        #    the host loop's submit_batch sequence, one exact recurrence
        burst_fr = (
            jnp.arange(burst_cap, dtype=jnp.int32) % S if burst_cap
            else jnp.zeros((0,), jnp.int32)
        )
        act = jnp.concatenate(
            [(fake_js >= 0).reshape(-1), burst_t >= 0, jnp.ones((k,), bool)]
        )
        sub_w = jnp.concatenate(
            [jnp.maximum(fake_js, 0).reshape(-1), jnp.maximum(burst_t, 0),
             workers.reshape(-1)]
        )
        sub_arr = jnp.concatenate(
            [jnp.full((S * max_fake + burst_cap,), t64), times64]
        )
        sub_cost = jnp.concatenate(
            [jnp.full((S * max_fake,), fake_cost),
             jnp.full((burst_cap,), burst_cost), costs64]
        )
        sub_fr = jnp.concatenate(
            [jnp.repeat(jnp.arange(S, dtype=jnp.int32), max_fake),
             burst_fr,
             jnp.repeat(jnp.arange(S, dtype=jnp.int32), k_f)]
        )

        free_at, sub_start, sub_done, _ = pool_chain(
            free_at, sub_w, sub_arr, sub_cost, act, speeds64)
        resp = sub_done[S * max_fake + burst_cap:] - times64  # f64[k]

        # -- pending-set append (the single scan's free-slot placement + the
        #    p_fr tag; slots are unordered, p_seq carries insertion order)
        with obt.stage("pending_append"):
            slot, pos, n_over, _ = pending_slots(p_valid, act, pend_cap)
            p_done = p_done.at[slot].set(sub_done, mode="drop")
            p_start = p_start.at[slot].set(sub_start, mode="drop")
            p_rep = p_rep.at[slot].set(sub_w.astype(jnp.int32), mode="drop")
            p_seq = p_seq.at[slot].set(seq_ctr + pos, mode="drop")
            p_fr = p_fr.at[slot].set(sub_fr, mode="drop")
            p_valid = p_valid.at[slot].set(True, mode="drop")
            if faulty:
                nfb = S * max_fake + burst_cap
                sub_task = jnp.concatenate([
                    jnp.full((nfb,), -1, jnp.int32),
                    turn * k + jnp.arange(k, dtype=jnp.int32),
                ])
                sub_arrv = jnp.concatenate([
                    jnp.full((nfb,), t64), times64,
                ])
                p_task = p_task.at[slot].set(sub_task, mode="drop")
                p_arrv = p_arrv.at[slot].set(sub_arrv, mode="drop")
                p_learn = p_learn.at[slot].set(True, mode="drop")
                ctr = ctr.at[rcv.CTR["launch_fake"]].add(jnp.sum(act[:nfb]))
            over_pend = over_pend + n_over
            seq_ctr = seq_ctr + jnp.sum(act).astype(jnp.int32)

        fl = fl.replace(
            q_view=q_view, learner=learner, arr=arr, key=key,
            mu_front=mu_front, mu_pend=mu_pend, tables=tables,
            herd_applied=herd_applied, last_fake=last_fake,
            q_snap=q_snap, t_sync=t_sync, lam_global=lam_global,
        )
        carry = (fl, free_at, p_done, p_start, p_rep, p_seq, p_fr, p_valid,
                 seq_ctr, turn + 1, over_flush, over_pend)
        if faulty:
            carry = carry + (p_task, p_arrv, p_learn, resp_acc, ctr,
                             max_clean)
        if observe is None:
            return carry, (resp, mu_tr, workers, did_sync, gaps)

        # -- telemetry: one per-frontend fold (vmapped over S) per turn.
        #    Plain fleet turns complete within the turn (launched =
        #    completed = k_f); faulty turns read the per-frontend ledger
        #    deltas scattered above and fold the flushed-completion
        #    latencies masked by owning frontend.
        i32o = jnp.int32
        kf_s = jnp.full((S,), k_f, i32o)
        z_s = jnp.zeros((S,), i32o)
        if faulty:
            resp_o, ok_o = lat_obs, ok_obs
            comp_o, dirty_o, kill_o = comp_f, dirty_f, kills_f
        else:
            resp_o = resp.reshape(S, k_f)
            ok_o = jnp.ones((S, k_f), bool)
            comp_o, dirty_o, kill_o = kf_s, z_s, z_s
        with obt.stage("telemetry_fold"):
            tob = obw.TurnObs(
                t=jnp.full((S,), t32, jnp.float32),
                resp=resp_o, resp_ok=ok_o,
                arrivals=kf_s, q_view=q_view,
                lam_hat=est.lam_hat_ema(arr).astype(jnp.float32),
                mu_hat=learner.mu_hat,
                mu_true=jnp.broadcast_to(
                    speeds64.astype(jnp.float32)[None], (S, n)),
                active=(None if active_t is None
                        else jnp.broadcast_to(active_t[None], (S, n))),
                launched=kf_s, completed=comp_o, dirty=dirty_o,
                killed=kill_o, retried=z_s,
                collisions=obw.fleet_collisions(workers, n),
            )
            tc, row, flag_s = jax.vmap(
                functools.partial(obw.observe_turn, observe))(tc, tob)
        if observe.emit_responses:
            ys = (resp, mu_tr, workers, did_sync, gaps, row, flag_s[0])
        else:  # stream-only: ys carry ONLY the window stream
            ys = (row, flag_s[0])
        return carry + (tc,), ys

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(lcfg, carry0, xs):
        return jax.lax.scan(functools.partial(body, lcfg), carry0, xs)

    return run


def run_fleet_workload_scan(
    router: "rt.FleetRouter",
    pool: rt.SimulatedPool,
    times_np: np.ndarray,  # f64[T, k] per-turn arrival times (global order)
    costs_np: np.ndarray,  # f64[T, k]
    speeds_np: np.ndarray,  # f64[T, n]
    *,
    active_np: np.ndarray | None = None,  # bool[T, n] membership per turn
    rejoin_np: np.ndarray | None = None,  # bool[T, n] offline→online edges
    burst_np: np.ndarray | None = None,  # i32[T, Bc] probe-burst targets
    fake_cost: float = 0.25,
    burst_cost: float | None = None,
    pend_cap: int = PEND_CAP,
    sync_every: int = 1,
    frozen_mu: bool = False,
    chunk_turns: int | None = None,
    mesh=None,
    kill_np: np.ndarray | None = None,  # f64[T, n] crash instants (+inf)
    stall_np: np.ndarray | None = None,  # f64[T, n] blackout instants (+inf)
    stall_dur_np: np.ndarray | None = None,  # f64[T, n] blackout durations
    strict_overflow: bool = True,
    observe: "obw.ObserveConfig | None" = None,  # in-scan telemetry: one
    # vmapped per-frontend fold per turn; per-frontend window records in
    # info["windows_frontends"], the fleet-aggregate fold in
    # info["windows"]. emit_responses=False puts the program in
    # stream-only mode (response/μ̂/placement ys dropped entirely).
    obs_sink=None,  # callable(list[record]) — streamed per chunk
):
    """The one-program FLEET over a pre-materialized workload: S frontends
    × environment × serving loop as a single ``lax.scan`` (chunked when
    ``chunk_turns`` streams a long horizon — the donated carry crosses
    chunk boundaries device-side).

    ``kill_np``/``stall_np``/``stall_dur_np`` enable the fleet's fault
    SUBSET — crash (in-flight kill) and blackout (completion stall) with
    full loss accounting (``info["ledger"]``) — but NOT the re-dispatch
    machinery (timeout/retry/speculation), which is single-frontend only
    (``run_workload_scan``). At S=1 the faulty fleet is bit-equal to the
    faulty single scan with ``recovery=None``.

    The arrival batch k must divide evenly over the S frontends (frontend
    f owns the contiguous chunk ``times[:, f*k_f:(f+1)*k_f]`` — the host
    ``run_fleet_simulation`` chunking at its equal-split shapes).

    Parity contract (tests/test_fleet_scan.py): at S=1 the program is
    bit-equal to ``run_workload_scan``; at S>1 with ``sync_every=1``,
    ``frozen_mu=False`` and a ``SequentialPool``/``async_mu=False`` host
    fleet, responses, μ̂ trace and final states match float-for-float.
    ``frozen_mu=True`` instead routes on the carried per-frontend μ̂ views
    and alias tables (rebuilt only at sync rounds/membership flips — the
    FleetSimState amortization); ``mesh`` shards the frontend axis
    (``fleet/sync`` stages: sync rounds are the only scheduler
    collectives).

    Returns ``(response_times, mu_trace, info)`` with
    ``run_fleet_simulation``'s info keys (placement log, sync gaps, λ̂s)
    plus the scan overflow counters."""
    from repro.core import dispatch as dsp
    from repro.core import estimator as est

    T, k = times_np.shape
    n = router.n
    S = router.S
    if k % S != 0:
        raise ValueError(
            f"arrival_batch={k} must divide evenly over S={S} frontends "
            "on the scan path (the host loop's divmod chunks are only "
            "equal-split when S | k)"
        )
    k_f = k // S
    frs = router.frontends
    use_alias = frs[0].use_alias
    if active_np is None and frs[0].active is not None:
        active_np = np.broadcast_to(
            np.asarray(frs[0].active, bool), (T, n)
        ).copy()
    churn = active_np is not None
    burst_cap = 0
    if churn and burst_np is not None:
        burst_cap = int(burst_np.shape[1])
    if burst_cost is None:
        burst_cost = 4.0 * fake_cost
    sync_every = max(int(sync_every), 1)
    faulty = kill_np is not None or stall_np is not None
    from repro.serving import recovery as rcv

    with jax.enable_x64(True):
        xs_np = (
            np.asarray(times_np, np.float64),
            np.asarray(costs_np, np.float64),
            np.asarray(speeds_np, np.float64),
        )
        if churn:
            rej = (
                rejoin_np if rejoin_np is not None
                else np.zeros((T, n), bool)
            )
            bw = (
                burst_np if burst_np is not None
                else np.zeros((T, 0), np.int32)
            )
            changed = np.zeros((T,), bool)
            if T:
                changed[0] = True
                changed[1:] = np.any(
                    active_np[1:] != active_np[:-1], axis=1
                )
            xs_np = xs_np + (
                np.asarray(active_np, bool),
                np.asarray(rej, bool),
                changed,
                np.asarray(bw, np.int32),
            )
        if faulty:
            xs_np = xs_np + (
                np.asarray(kill_np, np.float64) if kill_np is not None
                else np.full((T, n), np.inf),
                np.asarray(stall_np, np.float64) if stall_np is not None
                else np.full((T, n), np.inf),
                np.asarray(stall_dur_np, np.float64)
                if stall_dur_np is not None else np.zeros((T, n)),
            )
        n_tasks = T * k

        from repro.fleet.state import FleetServeCarry

        stackt = lambda trees: jax.tree.map(  # noqa: E731
            lambda *ls: jnp.stack(ls), *trees
        )
        tables = None
        if frozen_mu and use_alias:
            tables = dsp.AliasTable(
                prob=jnp.stack([jnp.asarray(fr.table_front.prob)
                                for fr in frs]),
                alias=jnp.stack([jnp.asarray(fr.table_front.alias)
                                 for fr in frs]),
            )
        fl0 = FleetServeCarry(
            q_view=jnp.stack([jnp.asarray(fr.q_view) for fr in frs]),
            learner=stackt([fr.learner for fr in frs]),
            arr=stackt([fr.arr for fr in frs]),
            key=jnp.stack([jnp.asarray(fr.key) for fr in frs]),
            mu_front=jnp.stack([jnp.asarray(fr.mu_front) for fr in frs]),
            mu_pend=jnp.array(
                [fr._mu_pending is not None for fr in frs]
            ),
            tables=tables,
            herd_scale=jnp.asarray(
                np.asarray(router.herd_scale, np.float32)
            ),
            herd_applied=jnp.asarray(router._herd_applied, jnp.int32),
            last_fake=jnp.array(
                [fr.last_fake_time for fr in frs], jnp.float32
            ),
            q_snap=jnp.asarray(router._snap, jnp.int32),
            t_sync=jnp.float32(router.t_sync),
            lam_global=jnp.float32(router.lam_global),
        )
        carry0 = (
            fl0,
            jnp.asarray(pool.free_at, jnp.float64),
            jnp.full((pend_cap,), jnp.inf, jnp.float64),  # p_done
            jnp.zeros((pend_cap,), jnp.float64),  # p_start
            jnp.zeros((pend_cap,), jnp.int32),  # p_rep
            jnp.zeros((pend_cap,), jnp.int32),  # p_seq
            jnp.zeros((pend_cap,), jnp.int32),  # p_fr
            jnp.zeros((pend_cap,), bool),  # p_valid
            jnp.int32(0),  # seq_ctr
            jnp.int32(0),  # turn
            jnp.int32(0),  # over_flush
            jnp.int32(0),  # over_pend
        )
        if faulty:
            carry0 = carry0 + (
                jnp.full((pend_cap,), -1, jnp.int32),  # p_task
                jnp.zeros((pend_cap,), jnp.float64),  # p_arrv
                jnp.ones((pend_cap,), bool),  # p_learn
                jnp.full((n_tasks + 1,), jnp.inf, jnp.float64),  # resp_acc
                jnp.zeros((rcv.NCTR,), jnp.int64),  # ctr
                jnp.float64(0.0),  # max_clean
            )
        if observe is not None:
            carry0 = carry0 + (jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (S,) + x.shape),
                obw.init_carry(observe),
            ),)
        run = _build_fleet_scan(
            n, S, k_f, min(rt.SERVE_COMP_CAP, pend_cap), pend_cap,
            frs[0].policy, 8, use_alias, fake_cost, sync_every, frozen_mu,
            churn, burst_cap, float(burst_cost), mesh, faulty, observe,
        )
        step = T if chunk_turns is None else max(int(chunk_turns), 1)
        carry = carry0
        ys_l = []
        windows: list = []
        windows_f: list = []

        def _obs_chunk(rows, flags):
            new, new_f = obw.fleet_records_from_rows(observe, rows, flags)
            windows.extend(new)
            windows_f.extend(new_f)
            if obs_sink is not None and new:
                obs_sink(new)

        stream_only = observe is not None and not observe.emit_responses
        for ci, s in enumerate(range(0, T, step)):
            with obt.DriverCall(ci) as call:
                chunk = tuple(x[s:s + step] for x in xs_np)
                call.phase("h2d")
                xs = tuple(jnp.asarray(x) for x in chunk)
                call.phase("launch")
                carry, ys = run(frs[0].lcfg, carry, xs)
                call.phase("readback")
                if observe is not None:
                    _obs_chunk(ys[-2], ys[-1])
                if not stream_only:
                    ys_l.append(ys[:5])
        if ys_l:
            resp = np.concatenate(
                [np.asarray(y[0]) for y in ys_l]
            ).reshape(-1)
            mu_trace = np.concatenate([np.asarray(y[1]) for y in ys_l])
            workers_log = np.concatenate([np.asarray(y[2]) for y in ys_l])
            synced = np.concatenate([np.asarray(y[3]) for y in ys_l])
            gaps = np.concatenate([np.asarray(y[4]) for y in ys_l])
        else:
            resp = np.empty(0)
            mu_trace = np.zeros((0, n), np.float32)
            workers_log = np.zeros((0, S, k_f), np.int32)
            synced = np.zeros((0,), bool)
            gaps = np.zeros((0, S), np.int32)

        ledger = None
        if faulty:
            # finalize with the shared numpy epilogue (drain still-pending
            # copies, min-fold responses, close the conservation books) —
            # identical to the single faulty scan's ending, so the S=1
            # bit-equality extends to the returned responses and ledger
            validF = np.asarray(carry[7])
            resp_acc = np.asarray(carry[15])[:n_tasks].copy()
            ctr_np = np.asarray(carry[16]).copy()
            rcv.drain_pending(
                resp_acc, ctr_np, np.asarray(carry[2])[validF],
                np.asarray(carry[12])[validF],
                np.asarray(carry[13])[validF],
            )
            resp, ledger = rcv.build_ledger(
                resp_acc, ctr_np, n_tasks, float(carry[17]))

        fl = carry[0]
        mu_pend_np = np.asarray(fl.mu_pend)
        for f, fr in enumerate(frs):
            fr.q_view = jnp.asarray(np.asarray(fl.q_view[f]))
            fr.learner = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x[f])), fl.learner
            )
            fr.arr = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x[f])), fl.arr
            )
            fr.key = jnp.asarray(np.asarray(fl.key[f]))
            fr.last_fake_time = float(np.asarray(fl.last_fake)[f])
            fr.mu_front = jnp.asarray(np.asarray(fl.mu_front[f]))
            fr._mu_pending = (
                fr.learner.mu_hat if bool(mu_pend_np[f]) else None
            )
            if churn:
                fr.active = jnp.asarray(active_np[-1], bool)
            if fr.use_alias:
                fr.table_front = dsp.build_alias_table(
                    fr.mu_front, fr.active
                )
        router._snap = np.asarray(fl.q_snap).astype(np.int64)
        router._herd_applied = np.asarray(fl.herd_applied).astype(np.int64)
        router.t_sync = float(np.asarray(fl.t_sync))
        router.lam_global = float(np.asarray(fl.lam_global))
        pool.free_at = np.asarray(carry[1])

        info = {
            "turns": T,
            "flush_overflow": int(carry[10]),
            "pend_overflow": int(carry[11]),
            "frontends": np.tile(
                np.repeat(np.arange(S, dtype=np.int64), k_f), T
            ),
            "workers": workers_log.reshape(-1).astype(np.int64),
            "epochs": np.repeat(np.arange(T, dtype=np.int64) // sync_every,
                                k),
            "sync_gaps": (
                gaps[synced].astype(np.int64) if S > 1
                else np.zeros((0, S))
            ),
            "lam_hats": np.array(
                [float(est.lam_hat_ema(fr.arr)) for fr in frs]
            ),
        }
        if ledger is not None:
            info["ledger"] = ledger
        if observe is not None:
            if T > 0:
                tail, tail_f = obw.fleet_final_partial(observe, carry[-1])
                if tail is not None:
                    windows.append(tail)
                    windows_f.append(tail_f)
                    if obs_sink is not None:
                        obs_sink([tail])
            info["windows"] = windows
            info["windows_frontends"] = windows_f
    if strict_overflow and (info["flush_overflow"] or info["pend_overflow"]):
        raise RuntimeError(
            f"fleet scan overflow: flush_overflow={info['flush_overflow']} "
            f"pend_overflow={info['pend_overflow']} with pend_cap="
            f"{pend_cap} — results silently dropped completions; raise "
            "pend_cap or pass strict_overflow=False to accept"
        )
    return resp, mu_trace, info


def run_fleet_simulation_scan(
    router: "rt.FleetRouter",
    pool: rt.SimulatedPool,
    *,
    arrival_rate: float,
    horizon: float,
    request_cost: float = 1.0,
    speed_schedule: "list[tuple[float, np.ndarray]] | None" = None,
    seed: int = 0,
    arrival_batch: int = 1,
    sync_every: int = 1,
    pend_cap: int = PEND_CAP,
    frozen_mu: bool = False,
    chunk_turns: int | None = None,
    mesh=None,
):
    """Drop-in for ``run_fleet_simulation`` with the whole S-frontend loop
    scan-compiled (same RandomState workload precompute, so host and scan
    fleets see identical arrivals). ``arrival_batch`` must be a multiple
    of S. Returns ``(response_times, mu_trace, info)``."""
    wl = _precompute_workload(
        arrival_rate, horizon, request_cost, speed_schedule, seed,
        arrival_batch, pool.speeds,
    )
    if wl is None:
        S = router.S
        return np.empty(0), np.zeros((0, router.n)), {
            "turns": 0, "flush_overflow": 0, "pend_overflow": 0,
            "frontends": np.empty(0, np.int64),
            "workers": np.empty(0, np.int64),
            "epochs": np.empty(0, np.int64),
            "sync_gaps": np.zeros((0, S)),
            "lam_hats": np.zeros(S),
        }
    times_np, costs_np, speeds_np = wl
    return run_fleet_workload_scan(
        router, pool, times_np, costs_np, speeds_np,
        fake_cost=request_cost * 0.25, pend_cap=pend_cap,
        sync_every=sync_every, frozen_mu=frozen_mu,
        chunk_turns=chunk_turns, mesh=mesh,
    )
