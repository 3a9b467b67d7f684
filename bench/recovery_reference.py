"""Plain reference of Rosella's serving semantics under worker crashes, with
deadline timeouts, retries and backup copies.

A straightforward numpy implementation, turn by turn, of one frontend in
front of ``n`` workers that crash and come back (``bench/faults.py``), with
the recovery semantics of MapReduce (Dean and Ghemawat, OSDI 2004): a
crashed worker's in-flight work is lost and run again elsewhere (sec. 3.3),
and stragglers get backup copies (sec. 3.6).  It imports nothing of the
system under test; from ``bench/reference.py`` it takes the key chain and
hashes, the alias table and draw, the learner, the fake-job count and the
FIFO chain.  What it adds, per turn and in this order:

1. crash kill: every copy on a worker that would finish after the worker's
   crash instant is lost; a real copy that is no backup and has retries
   left stays behind as a ghost (no completion) awaiting a retry; the
   worker's FIFO clock falls back to the crash instant.  (A blackout stall
   stretches the copies past its instant and their clock instead.)
2. timeout: a real copy still running past its deadline goes dirty, and
   queues a retry if it is no backup and has retries left;
3. flush: every copy done by the turn's time leaves; the clean ones (no
   stall, kill or timeout touched them) feed the learner, oldest done first
   (ties in launch order), at most ``comp_cap``; the dirty ones only drain
   the queue view; each real one min-folds its task's response
   (done - the task's arrival);
4. queue view: drained of the killed and dirty copies;
5. rejoin: a worker back from an outage cold-starts in the learner (empty
   ring, epoch now, mu_hat = mean of the workers that stayed up);
6. stale ghosts (their task completed meanwhile) go;
7. retry selection: the candidates whose task has no response yet,
   earliest deadline first (ties in launch order), at most ``retry_cap``; a
   chosen ghost is consumed, a chosen timed-out copy keeps running but is
   never chosen again;
8. serve: the learner folds the clean completions; fake jobs go to uniform
   up workers; the arrivals and the retry slots are placed by PPoT-SQ(2)
   in one batch (``k + retry_cap`` draws) through the alias table of the
   up workers' mu_hat, all against one queue snapshot;
9. backup copies: among real copies that are running, no backup, not
   retried and whose task has no response, those whose age exceeds
   ``spec_ratio`` times their expected service, largest ratio first (ties
   in launch order), at most ``spec_cap``; they go to the greedy makespan
   fill over the up workers' mu_hat (slot j to the worker whose
   (alloc + 1) / mu_hat is least);
10. deadlines: launch time + ``timeout_mult * backoff ** attempt * cost /
    max(mu_hat, mu_floor)`` on the post-serve mu_hat (a retry's attempt is
    its original's plus one, a backup copy's its original's);
11. the FIFO chain: fakes, probe bursts, arrivals, retries, backup copies;
    then the new copies join the in-flight set.

When the stream ends, copies still running with a finite done time count
as completed (their response folds in); the ledger conserves: real copies
launched = completed + killed, fake ones likewise, tasks = completed +
lost.

Scheduling depends on every earlier decision, so the reference judges each
turn given the system's answers, never its state.  The arrivals run where
the system placed them; each retry and backup copy the system launched
re-executes the task it chose (the reference's candidate copy of it, else
its latest copy, else one made from the task's arrival) and runs where the
system put it.  Deadlines and the backup age test (steps 9-10) read the
reference's own post-serve mu_hat, with one allowance: where the system's
answer for the next turn lies within ``ref.MU_RTOL`` of the reference's
entry (relative to the turn's largest, as ``mu_off`` judges), they read the
system's entry, so that a float32 ulp of the learner does not turn a timeout over;
an entry farther off keeps the reference's own value and counts in
``mu_off``.  Its own choices of task and worker in every slot, its learner
and its ledger are compared with the system's.
Left to its own decisions, the reference would drift from a sound system:
two learners differ by float32 ulps (their sums are unordered) and an
emulated float64 clock by its last bits, one decision moved by a turn
(a timeout, a completion) changes which task a retry re-executes, and the
different copy changes every later completion on its worker.
``clock=np.float32`` gives the lower-precision control.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference as ref

F32 = ref.F32
#: A backup copy's worker may be any whose (alloc + 1) / mu_hat lies within
#: this share of the least: mu_hat differs by float32 ulps between two sound
#: implementations (``ref.MU_RTOL`` is ten times this).
SPEC_RTOL = 1e-6
LEDGER = ("n_tasks", "completed_tasks", "lost_tasks", "copies_real_launched",
          "copies_real_completed", "copies_real_killed", "fake_launched",
          "fake_completed", "fake_killed", "n_timeouts", "n_retries",
          "n_spec", "n_dirty_completions", "n_stalled")


@dataclasses.dataclass(frozen=True)
class Recovery:
    timeout_mult: float
    retry_budget: int
    backoff: float
    retry_cap: int
    spec_cap: int
    spec_ratio: float
    mu_floor: float

    @classmethod
    def from_config(cls, d: dict) -> "Recovery":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)})

    def lut(self) -> np.ndarray:
        return np.power(float(self.backoff),
                        np.arange(self.retry_budget + 2, dtype=np.float64))


@dataclasses.dataclass
class Columns:
    """The faults of each turn (``bench/faults.py``): bool[T, n] up, bool[T, n]
    back from an outage, i32[T, n * probe_burst] probe jobs (-1 padded),
    f64[T, n] crash instants (+inf none); optional blackouts (instant and
    length, f64[T, n])."""
    active: np.ndarray
    rejoin: np.ndarray
    burst: np.ndarray
    kill: np.ndarray
    stall: np.ndarray | None = None
    stall_len: np.ndarray | None = None


@dataclasses.dataclass
class Answers:
    """What the system said: i64[T*k] arrival placements; i64[T, retry_cap]
    retry and i64[T, spec_cap] backup copies, the worker each runs on and
    the task it re-executes (-1 where none); f64[T*k] responses (NaN =
    lost), f32[T, n] mu_hat entering each turn, and its ledger."""
    workers: np.ndarray
    retry_workers: np.ndarray
    spec_workers: np.ndarray
    retry_tasks: np.ndarray
    spec_tasks: np.ndarray
    resp: np.ndarray
    mu_trace: np.ndarray
    ledger: dict


@dataclasses.dataclass
class Result:
    answers: Answers  # the reference's own (its choice in every slot)
    allowed: np.ndarray  # bool[T, k] the arrival placement run is allowed
    slot_bad: int  # retry and backup slots launched by one side only, or
    # given another task or placed where the reference would not
    slots: int  # retry and backup slots launched by either side
    pend_overflow: int
    flush_overflow: int
    pend_max: int  # most copies in flight (ghosts included)
    ghost_max: int  # most ghosts at once
    retry_wait_max: int  # most copies waiting for a retry slot at once


def masked_alias(mu, active):
    """The alias table of the up workers' mu_hat: a down worker's bin has
    mass 0, accepts nothing and aliases to an up worker."""
    masked = np.where(active, mu, F32(0.0)).astype(F32)
    if np.sum(masked, dtype=F32) > 0:
        w = masked
    elif active.any():
        w = active.astype(F32)
    else:
        w = np.ones_like(mu)
    prob, alias = ref.alias_table(w)
    if not active.any():
        return np.ones_like(prob), alias
    prob = np.where(active, prob, F32(0.0)).astype(F32)
    alias = np.where(active[alias], alias, int(np.argmax(active)))
    return prob, alias


def active_choice(active, u):
    """A uniform draw over the up workers (up workers first, in index
    order)."""
    order = np.argsort(np.where(active, 0, 1), kind="stable")
    n_act = int(active.sum())
    n_eff = max(n_act, 1)
    j = np.minimum((u * F32(n_eff)).astype(np.int64), n_eff - 1)
    return order[j] if n_act else (u * F32(active.size)).astype(np.int64)


def spec_plan(mu_plan, m: int, forced=None):
    """The greedy makespan fill of ``m`` backup slots over ``mu_plan``
    (zero = never); with ``forced`` (i64[m], -1 = follow own choice) the
    fill follows the system's choices.  Returns (own[m], allowed[m])."""
    mu = np.asarray(mu_plan, F32)
    safe = np.where(mu > 0, np.maximum(mu, F32(1e-30)), F32(1e-30))
    cost = np.where(mu > 0, F32(1.0) / safe, np.inf).astype(F32)
    alloc = np.zeros(mu.size, np.int64)
    own = np.zeros(m, np.int64)
    ok = np.zeros(m, bool)
    for i in range(m):
        vals = ((alloc + 1).astype(F32) * cost).astype(F32)
        j = int(np.argmin(vals))
        own[i] = j
        f = -1 if forced is None else int(forced[i])
        inside = 0 <= f < mu.size
        ok[i] = inside and mu[f] > 0 and (
            f == j or vals[f] <= vals[j] * (1.0 + SPEC_RTOL))
        alloc[f if inside else j] += 1
    return own, ok


class Learner(ref.Learner):
    def reset(self, reset, now32, active):
        """Cold-start the rejoining workers (``reset``)."""
        keep = active & ~reset
        denom = F32(np.sum(keep))
        mu_keep = np.sum(np.where(keep, self.mu, F32(0.0)), dtype=F32)
        mu0 = (F32(mu_keep / max(denom, F32(1.0))) if denom > 0
               else F32(1.0))
        self.samples[reset] = 0.0
        self.stamps[reset] = 0.0
        self.widx[reset] = 0
        self.count[reset] = 0
        self.epoch[reset] = now32
        self.mu[reset] = mu0


#: The columns of a copy in flight and their types (None: the event clock).
_COLS = {"done": None, "start": None, "rep": np.int64, "seq": np.int64,
         "task": np.int64, "arrv": None, "cost": None, "dead": None,
         "att": np.int64, "dup": bool, "learn": bool, "to": bool,
         "retry": bool}


def _keep(c: dict, mask) -> dict:
    return c if mask.all() else {key: v[mask] for key, v in c.items()}


def _follow(own, cand, c, sys_tasks, n_tasks):
    """The copies the system chose for a turn's retry or backup slots, by
    their tasks: the reference's candidate copy of each task, else its
    latest copy of it, else -1 (it holds none).  A task outside the stream
    is left out.  Returns (copy indices, tasks, how many slots' tasks
    differ from the reference's own choice ``own``)."""
    want = sys_tasks[(sys_tasks >= 0) & (sys_tasks < n_tasks)]
    want = want.astype(np.int64)
    m = max(own.size, want.size)
    mine = np.full(m, -1)
    mine[:own.size] = c["task"][own]
    theirs = np.full(m, -1)
    theirs[:want.size] = want
    where = dict(zip(c["task"][cand].tolist(), cand.tolist()))
    pick = []
    for x in want.tolist():
        i = where.get(x)
        if i is None:
            same = np.nonzero(c["task"] == x)[0]
            i = int(same[np.argmax(c["seq"][same])]) if same.size else -1
        pick.append(i)
    outside = int(np.sum(sys_tasks >= n_tasks))
    return (np.asarray(pick, np.int64), want,
            int(np.sum(mine != theirs)) + outside)


def _copy_of(c, sel, tasks, times, costs, last_att):
    """(arrival, cost, attempt) of each selected copy; for a task the
    reference holds no copy of (-1), from its arrival and its latest
    launch."""
    has = sel >= 0
    i = np.where(has, sel, 0)

    def col(key, alt):
        return np.where(has, c[key][i], alt) if c[key].size else alt

    return (col("arrv", times.reshape(-1)[tasks]),
            col("cost", costs.reshape(-1)[tasks]),
            col("att", last_att[tasks]))


def _slot_placement(own, ok_ref, sys_w, allowed, n):
    """Where each launched slot's copy runs and how many slots disagree:
    the system's worker where both sides launch one, else the
    reference's."""
    sys_ok = sys_w >= 0
    both = ok_ref & sys_ok
    run = np.where(both & (sys_w < n), sys_w, own)
    bad = int(np.sum(ok_ref != sys_ok) + np.sum(both & ~allowed))
    return run, bad, int(np.sum(ok_ref | sys_ok))


def adopt_mu(own, theirs):
    """The mu_hat deadlines read: the system's entry where it lies within
    ``ref.MU_RTOL`` of the turn's largest of the reference's own entry (the
    comparison ``mu_off`` makes), else the reference's own."""
    own = np.asarray(own, F32)
    theirs = np.asarray(theirs, F32)
    scale = max(float(np.max(np.abs(own))), 1e-9)
    close = (np.abs(theirs.astype(np.float64) - own) <= ref.MU_RTOL * scale)
    return np.where(close, theirs, own).astype(F32)


def simulate(sem: ref.Semantics, rc: Recovery, seed: int, times, costs,
             speeds, cols: Columns, *, burst_cost: float, forced=None,
             clock=np.float64) -> Result:
    """Run the semantics over ``times``/``costs`` (f64[T, k]) with the
    workers' speeds ``speeds`` (f64[T, n]) and the fault columns ``cols``.
    With ``forced`` (the system's ``Answers``) the copies run where the
    system put them; without it the reference acts on its own choices."""
    T, k = times.shape
    n, R, S = sem.n, rc.retry_cap, rc.spec_cap
    kf, kr = ref.key_chain(seed, T)
    lut = rc.lut()
    mult, floor, budget = float(rc.timeout_mult), float(rc.mu_floor), \
        int(rc.retry_budget)
    timeout_on = bool(np.isfinite(mult))
    speeds = np.asarray(speeds).astype(clock)
    times_c = times.astype(clock)
    costs_c = costs.astype(clock)
    fake_cost, burst_c = clock(sem.fake_cost), clock(burst_cost)
    kill = np.asarray(cols.kill).astype(clock)
    stall = (None if cols.stall is None
             else np.asarray(cols.stall).astype(clock))
    stall_len = (None if cols.stall_len is None
                 else np.asarray(cols.stall_len).astype(clock))
    if forced is not None:
        f_w = np.asarray(forced.workers).reshape(T, k)
        f_r = np.asarray(forced.retry_workers).reshape(T, R)
        f_s = np.asarray(forced.spec_workers).reshape(T, S)
        f_rt = np.asarray(forced.retry_tasks).reshape(T, R)
        f_st = np.asarray(forced.spec_tasks).reshape(T, S)
        f_mu = np.asarray(forced.mu_trace, F32)
    lrn = Learner(sem)
    q = np.zeros(n, np.int64)
    free_at = np.zeros(n, clock)
    # the copies in flight, one column each (times on the event clock)
    c = {key: np.empty(0, _COLS[key] or clock) for key in _COLS}
    resp = np.full(T * k, np.inf, clock)
    last_att = np.zeros(T * k, np.int64)  # each task's latest attempt
    ctr = dict.fromkeys(("kill_real", "kill_fake", "timeout", "retry", "spec",
                         "comp_real", "comp_fake", "comp_dirty", "stalled",
                         "launch_fake"), 0)
    seq_ctr = 0
    mean_gap, last_time, arr_count = F32(0.0), F32(0.0), 0
    r = (1.0 - 1.0 / ref.EMA_WINDOW) ** k
    r32, one_r32 = F32(r), F32(1.0 - r)
    last_fake = F32(0.0)
    pend_over = flush_over = pend_max = ghost_max = wait_max = 0
    slot_bad = slots = 0
    own_w = np.empty((T, k), np.int64)
    own_r = np.full((T, R), -1, np.int64)
    own_s = np.full((T, S), -1, np.int64)
    own_rt = np.full((T, R), -1, np.int64)
    own_st = np.full((T, S), -1, np.int64)
    out_mu = np.empty((T, n), F32)
    out_ok = np.empty((T, k), bool)
    for t in range(T):
        t64 = times_c[t, -1]
        t32 = F32(t64)
        act_t = cols.active[t]
        real = c["task"] >= 0
        drain = np.zeros(n, np.int64)
        # -- blackout stall, crash kill, timeout
        if stall is not None and np.isfinite(stall[t]).any():
            aff = np.isfinite(c["done"]) & (c["done"] > stall[t][c["rep"]])
            c["done"] = np.where(aff, c["done"] + stall_len[t][c["rep"]],
                                 c["done"])
            c["learn"] = c["learn"] & ~aff
            ctr["stalled"] += int(np.sum(aff & real))
            free_at = np.where(free_at > stall[t], free_at + stall_len[t],
                               free_at)
        kt = kill[t]
        if np.isfinite(kt).any():
            killed = np.isfinite(c["done"]) & (c["done"] > kt[c["rep"]])
            drain += np.bincount(c["rep"][killed], minlength=n)
            ghost = (killed & real & ~c["dup"] & (c["att"] < budget)
                     & (R > 0))
            ctr["kill_real"] += int(np.sum(killed & real))
            ctr["kill_fake"] += int(np.sum(killed & ~real))
            c["learn"] = c["learn"] & ~killed
            c["done"] = np.where(ghost, np.inf, c["done"]).astype(clock)
            c["retry"] = c["retry"] | ghost
            c = _keep(c, ~(killed & ~ghost))
            real = c["task"] >= 0
            free_at = np.where(free_at > kt, kt, free_at)
        if timeout_on:
            newly = (real & np.isfinite(c["done"]) & (t64 > c["dead"])
                     & ~c["to"])
            c["to"] = c["to"] | newly
            c["learn"] = c["learn"] & ~newly
            if R > 0:
                c["retry"] = c["retry"] | (newly & ~c["dup"]
                                           & (c["att"] < budget))
            ctr["timeout"] += int(newly.sum())
        # -- flush
        due = c["done"] <= t64
        clean = np.nonzero(due & c["learn"])[0]
        comp_w = np.empty(0, np.int64)
        if clean.size:
            order = clean[np.lexsort((c["seq"][clean], c["done"][clean]))]
            if order.size > sem.comp_cap:
                flush_over += order.size - sem.comp_cap
            sel = order[:sem.comp_cap]
            comp_w = c["rep"][sel]
            comp_t = (c["done"][sel] - c["start"][sel]).astype(F32)
            comp_now = F32(np.max(c["done"][sel]))
        dirty = due & ~c["learn"]
        drain += np.bincount(c["rep"][dirty], minlength=n)
        ctr["comp_dirty"] += int(np.sum(dirty & real))
        dr = due & real
        np.minimum.at(resp, c["task"][dr], (c["done"] - c["arrv"])[dr])
        ctr["comp_real"] += int(dr.sum())
        ctr["comp_fake"] += int(np.sum(due & ~real))
        c = _keep(c, ~due)
        q = np.maximum(q - drain, 0)
        # -- rejoin, then mu_hat entering the turn
        if cols.rejoin[t].any():
            lrn.reset(cols.rejoin[t], t32, act_t)
        out_mu[t] = lrn.mu
        # -- stale ghosts, retry selection
        r_ok = np.zeros(R, bool)
        r_task = np.zeros(R, np.int64)
        r_arrv = np.full(R, t64, clock)
        r_cost = np.ones(R, clock)
        r_att = np.zeros(R, np.int64)
        if R > 0:
            ghosts = c["retry"] & ~np.isfinite(c["done"])
            stale = ghosts & np.isfinite(resp[np.maximum(c["task"], 0)])
            c = _keep(c, ~stale)
            cand = c["retry"] & ~np.isfinite(resp[np.maximum(c["task"], 0)])
            wait_max = max(wait_max, int(cand.sum()))
            idx = np.nonzero(cand)[0]
            sel = idx[np.lexsort((c["seq"][idx], c["dead"][idx]))][:R]
            tasks = c["task"][sel]
            if forced is not None:
                sel, tasks, bad = _follow(sel, idx, c, f_rt[t], T * k)
                slot_bad += bad
            nsel = sel.size
            if nsel:
                r_ok[:nsel] = True
                r_task[:nsel] = tasks
                r_arrv[:nsel], r_cost[:nsel], att = _copy_of(
                    c, sel, tasks, times_c, costs_c, last_att)
                r_att[:nsel] = att + 1
                last_att[tasks] = np.maximum(last_att[tasks], att + 1)
                ctr["retry"] += nsel
                held = sel[sel >= 0]
                ghost_sel = ~np.isfinite(c["done"][held])
                c["retry"][held] = False
                c["dup"][held[~ghost_sel]] = True
                keep = np.ones(c["done"].size, bool)
                keep[held[ghost_sel]] = False
                c = _keep(c, keep)
        ghost_max = max(ghost_max, int(np.sum(~np.isfinite(c["done"]))))
        real = c["task"] >= 0
        # -- serve: learner fold, fake jobs, arrivals and retry slots
        q = np.maximum(q - np.bincount(comp_w, minlength=n), 0)
        lam0 = F32(np.where(mean_gap > 0,
                            F32(1.0) / np.maximum(mean_gap, F32(1e-9)),
                            F32(0.0)))
        if comp_w.size:
            lrn.record(comp_w, comp_t, comp_now)
            lrn.refresh(lam0, comp_now)
        n_fake = ref.fake_jobs(sem, kf[t], lam0, F32(t32 - last_fake)).size
        fakes = active_choice(act_t, ref.uniform_pair(kf[t], ref.MAX_FAKE)[1])
        fakes = fakes[:n_fake]
        gap = F32(F32(t32 - last_time) / F32(k))
        mean_gap = gap if arr_count == 0 else F32(
            F32(r32 * mean_gap) + F32(one_r32 * gap))
        last_time, arr_count = t32, arr_count + k
        last_fake = t32
        prob, alias = masked_alias(lrn.mu, act_t)
        u1, u2, v1, v2 = ref.uniform_quad(kr[t], k + R)
        j1, j1b = ref.alias_draw(prob, alias, u1, v1)
        j2, j2b = ref.alias_draw(prob, alias, u2, v2)
        mine = np.where(q[j1] <= q[j2], j1, j2)

        def allowed(placed):
            ok = placed == mine
            for a, b in ((j1, j2b), (j1b, j2), (j1b, j2b)):
                ok |= placed == np.where(q[a] <= q[b], a, b)
            return ok

        own_w[t] = mine[:k]
        own_r[t] = np.where(r_ok, mine[k:], -1)
        own_rt[t] = np.where(r_ok, r_task, -1)
        if forced is None:
            placed, rw = mine[:k], mine[k:]
            out_ok[t] = True
        else:
            placed = np.where((f_w[t] >= 0) & (f_w[t] < n), f_w[t], 0)
            ok_all = allowed(np.concatenate([placed, np.maximum(f_r[t], 0)]))
            out_ok[t] = ok_all[:k]
            rw, bad, m_ = _slot_placement(mine[k:], r_ok, f_r[t], ok_all[k:],
                                          n)
            slot_bad, slots = slot_bad + bad, slots + m_
        q = (q + np.bincount(placed, minlength=n)
             + np.bincount(rw[r_ok], minlength=n))
        # -- backup copies on the post-serve mu_hat: the reference's own,
        #    or the system's answer entering the next turn where it agrees
        mu_post = (lrn.mu if forced is None or t + 1 == T
                   else adopt_mu(lrn.mu, f_mu[t + 1]))
        mu64 = mu_post.astype(np.float64)
        s_ok = np.zeros(S, bool)
        s_task = np.zeros(S, np.int64)
        s_arrv = np.full(S, t64, clock)
        s_cost = np.ones(S, clock)
        s_att = np.zeros(S, np.int64)
        sw = np.zeros(S, np.int64)
        if S > 0:
            ratio = (t64 - c["arrv"]) / (c["cost"] / np.maximum(
                mu64[c["rep"]], floor))
            live = real & ~np.isfinite(resp[np.maximum(c["task"], 0)])
            cand = (np.isfinite(c["done"]) & ~c["dup"] & ~c["retry"] & live
                    & (ratio > rc.spec_ratio))
            idx = np.nonzero(cand)[0]
            sel = idx[np.lexsort((c["seq"][idx], -ratio[idx]))][:S]
            tasks = c["task"][sel]
            if forced is not None:
                sel, tasks, bad = _follow(sel, idx, c, f_st[t], T * k)
                slot_bad += bad
            nsel = sel.size
            s_ok[:nsel] = True
            if nsel:
                s_task[:nsel] = tasks
                s_arrv[:nsel], s_cost[:nsel], s_att[:nsel] = _copy_of(
                    c, sel, tasks, times_c, costs_c, last_att)
                c["dup"][sel[sel >= 0]] = True
                ctr["spec"] += nsel
            mu_plan = np.where(act_t, lrn.mu, F32(0.0))
            if forced is None:
                sw, _ = spec_plan(mu_plan, S)
                own = sw
            else:
                own, ok_s = spec_plan(mu_plan, S, np.where(s_ok, f_s[t], -1))
                sw, bad, m_ = _slot_placement(own, s_ok, f_s[t], ok_s, n)
                slot_bad, slots = slot_bad + bad, slots + m_
            own_s[t] = np.where(s_ok, own, -1)
            own_st[t] = np.where(s_ok, s_task, -1)
            q = q + np.bincount(sw[s_ok], minlength=n)
        # -- deadlines on the post-serve mu_hat
        dead_new = t64 + (mult * float(lut[0])) * costs_c[t] / np.maximum(
            mu64[placed], floor)
        dead_rt = t64 + (mult * lut[np.minimum(r_att, lut.size - 1)]) \
            * r_cost / np.maximum(mu64[rw], floor)
        dead_sp = t64 + (mult * lut[np.minimum(s_att, lut.size - 1)]) \
            * s_cost / np.maximum(mu64[sw], floor)
        # -- the FIFO chain and the new copies
        bt = cols.burst[t]
        bursts = bt[bt >= 0].astype(np.int64)
        nf, nb = fakes.size, bursts.size
        sub_w = np.concatenate([fakes, bursts, placed, rw[r_ok], sw[s_ok]])
        sub_a = np.concatenate([np.full(nf + nb, t64, clock), times_c[t],
                                np.full(int(r_ok.sum() + s_ok.sum()), t64,
                                        clock)])
        sub_c = np.concatenate([np.full(nf, fake_cost, clock),
                                np.full(nb, burst_c, clock), costs_c[t],
                                r_cost[r_ok], s_cost[s_ok]])
        sub_s, sub_d = ref._chain(free_at, sub_w, sub_a, sub_c, speeds[t],
                                  clock)
        m = sub_w.size
        new = {
            "done": sub_d, "start": sub_s, "rep": sub_w,
            "seq": seq_ctr + np.arange(m),
            "task": np.concatenate([np.full(nf + nb, -1), t * k + np.arange(k),
                                    r_task[r_ok], s_task[s_ok]]),
            "arrv": np.concatenate([np.full(nf + nb, t64, clock), times_c[t],
                                    r_arrv[r_ok], s_arrv[s_ok]]),
            "cost": sub_c,
            "dead": np.concatenate([np.full(nf + nb, np.inf), dead_new,
                                    dead_rt[r_ok], dead_sp[s_ok]]),
            "att": np.concatenate([np.zeros(nf + nb + k, np.int64),
                                   r_att[r_ok], s_att[s_ok]]),
            "dup": np.concatenate([np.zeros(m - int(s_ok.sum()), bool),
                                   np.ones(int(s_ok.sum()), bool)]),
            "learn": np.ones(m, bool), "to": np.zeros(m, bool),
            "retry": np.zeros(m, bool),
        }
        ctr["launch_fake"] += nf + nb
        room = sem.pend_cap - c["done"].size
        if m > room:
            pend_over += m - room
            new = {key: v[:max(room, 0)] for key, v in new.items()}
        c = {key: np.concatenate([c[key], new[key].astype(c[key].dtype)])
             for key in _COLS}
        seq_ctr += m
        pend_max = max(pend_max, c["done"].size)
    # -- the stream's end: running copies complete
    fin = np.isfinite(c["done"])
    dr = fin & (c["task"] >= 0)
    np.minimum.at(resp, c["task"][dr], (c["done"] - c["arrv"])[dr])
    ctr["comp_real"] += int(dr.sum())
    ctr["comp_fake"] += int(np.sum(fin & (c["task"] < 0)))
    resp = resp.astype(np.float64)
    completed = int(np.isfinite(resp).sum())
    ledger = {
        "n_tasks": T * k, "completed_tasks": completed,
        "lost_tasks": T * k - completed,
        "copies_real_launched": T * k + ctr["retry"] + ctr["spec"],
        "copies_real_completed": ctr["comp_real"],
        "copies_real_killed": ctr["kill_real"],
        "fake_launched": ctr["launch_fake"],
        "fake_completed": ctr["comp_fake"], "fake_killed": ctr["kill_fake"],
        "n_timeouts": ctr["timeout"], "n_retries": ctr["retry"],
        "n_spec": ctr["spec"], "n_dirty_completions": ctr["comp_dirty"],
        "n_stalled": ctr["stalled"],
    }
    ledger["conserved"] = (
        ledger["copies_real_launched"]
        == ledger["copies_real_completed"] + ledger["copies_real_killed"]
        and ledger["fake_launched"]
        == ledger["fake_completed"] + ledger["fake_killed"])
    own = Answers(own_w.reshape(-1), own_r, own_s, own_rt, own_st,
                  np.where(np.isfinite(resp), resp, np.nan), out_mu, ledger)
    return Result(own, out_ok, slot_bad, slots, pend_over, flush_over,
                  pend_max, ghost_max, wait_max)


def check(sem: ref.Semantics, rc: Recovery, seed: int, times, costs, speeds,
          cols: Columns, answers: Answers, *, burst_cost: float) -> dict:
    """Judge the system's ``answers`` for the turns of ``times``; returns
    the compared numbers."""
    T, k = times.shape
    R, S = rc.retry_cap, rc.spec_cap
    w = np.asarray(answers.workers).reshape(-1)
    resp = np.asarray(answers.resp, np.float64).reshape(-1)
    mu_trace = np.asarray(answers.mu_trace, np.float32)
    led = answers.ledger or {}
    shape_ok = (w.size == T * k and resp.size == T * k
                and mu_trace.shape == (T, sem.n)
                and np.shape(answers.retry_workers) == (T, R)
                and np.shape(answers.spec_workers) == (T, S)
                and np.shape(answers.retry_tasks) == (T, R)
                and np.shape(answers.spec_tasks) == (T, S)
                and all(key in led for key in LEDGER))
    if not shape_ok:
        return {"misplaced": T * k, "unconserved": 1,
                "placement_mismatch": 1.0, "resp_off": 1.0, "mu_off": 1.0,
                "ledger_off": float("inf")}
    w2 = w.reshape(T, k)
    inside = (w2 >= 0) & (w2 < sem.n)
    down = ~np.take_along_axis(cols.active, np.where(inside, w2, 0), axis=1)
    bad = ~inside | down
    res = simulate(sem, rc, seed, times, costs, speeds, cols,
                   burst_cost=burst_cost, forced=answers)
    mine = res.answers
    both = np.isfinite(resp) & np.isfinite(mine.resp)
    gap = np.where(both, np.abs(resp - np.where(both, mine.resp, 0.0)),
                   np.where(np.isfinite(resp) == np.isfinite(mine.resp),
                            0.0, np.inf))
    gap = np.where(np.isnan(gap), np.inf, gap)
    scale = np.maximum(np.max(np.abs(mine.mu_trace), axis=1, keepdims=True),
                       F32(1e-9))
    mu_gap = np.abs(mu_trace.astype(np.float64) - mine.mu_trace) / scale
    mu_gap = np.where(np.isnan(mu_gap), np.inf, mu_gap)
    ledger_off = max(abs(int(led[key]) - int(mine.ledger[key]))
                     / max(int(mine.ledger[key]), 1) for key in LEDGER)
    placements = T * k + res.slots
    return {
        "misplaced": int(bad.sum()),
        "unconserved": int(not led.get("conserved", False)),
        "placement_mismatch": float(
            (np.sum(bad | ~res.allowed) + res.slot_bad) / placements),
        "resp_off": float(np.mean(gap > ref.RESP_TOL)),
        "mu_off": float(np.mean(mu_gap > ref.MU_RTOL)),
        "ledger_off": float(ledger_off),
        "resp_gap_s": float(np.max(gap)) if gap.size else 0.0,
        "mu_gap": float(np.max(mu_gap)) if mu_gap.size else 0.0,
        "pend_max": res.pend_max,
        "ghost_max": res.ghost_max,
        "retry_wait_max": res.retry_wait_max,
        "crashes": int(np.isfinite(cols.kill).sum()),
        "retries": int(mine.ledger["n_retries"]),
        "backups": int(mine.ledger["n_spec"]),
        "lost": int(mine.ledger["lost_tasks"]),
    }
