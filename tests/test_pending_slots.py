"""Free-slot placement of the scan's pending set (``scanloop.pending_slots``):
each turn's new in-flight copies take free slots in index order and
survivors never move, so slots are unordered and the order lives in
``p_seq``. Checked against a numpy model of the placement and of the
compaction it replaced (same overflow count, same copies dropped), and,
over whole runs of the plain and faulty scan bodies, against the host
loop's in-flight set and its largest size (``info["pend_peak"]``)."""
import jax
import numpy as np
import pytest

from repro import env
from repro.env.serving import run_scenario
from repro.serving import INERT_RECOVERY, RecoveryConfig, SequentialPool
from repro.serving import recovery as rcv
from repro.serving import scanloop
from repro.serving.scanloop import pending_slots


def _model(valid, act):
    """The i-th active submission takes the i-th free slot; the rest drop.
    Also the compaction's overflow count: ``nv`` survivors packed to the
    front, the new copies written at ``nv + pos``."""
    cap = valid.size
    free = np.flatnonzero(~valid)
    pos = np.cumsum(act) - 1
    slot = np.full(act.size, cap)
    for i in np.flatnonzero(act):
        if pos[i] < free.size:
            slot[i] = free[pos[i]]
    over_compacted = int(np.sum(act & (valid.sum() + pos >= cap)))
    return slot, over_compacted


def _holes(cap, live, rng):
    """A set fragmented as a flush leaves it: ``live`` slots scattered."""
    valid = np.zeros(cap, bool)
    valid[rng.choice(cap, live, replace=False)] = True
    return valid


def _scan_turn(rng, fake_on, burst_on, k):
    """One turn's ``act`` as the faulty body lays it out: 8 fakes, 240
    probe-burst slots, the reals, 4 retry and 2 backup slots."""
    return np.concatenate([rng.random(8) < fake_on, rng.random(240) < burst_on,
                           np.ones(k, bool), rng.random(6) < 0.5])


def _case(name):
    rng = np.random.default_rng(CASES.index(name))
    if name == "empty":
        return np.zeros(16, bool), np.ones(5, bool)
    if name == "holes_after_flush":
        valid = np.array([1, 0, 1, 1, 0, 0, 1, 0, 1, 1, 1, 0, 1, 0, 0, 1],
                         bool)
        return valid, np.ones(6, bool)
    if name == "exactly_full":
        return np.ones(16, bool), np.array([1, 0, 1, 1], bool)
    if name == "fills_to_full":
        return _holes(16, 11, rng), np.ones(5, bool)
    if name == "over_by_one":
        return _holes(16, 12, rng), np.ones(5, bool)
    if name == "over_by_many":
        return _holes(16, 14, rng), np.array([1, 1, 0, 1, 1, 1, 0, 1, 1], bool)
    if name == "inactive_fakes_and_bursts":
        valid = np.array([0, 1, 1, 0, 1, 0, 0, 1, 1, 0, 1, 1, 0, 0, 1, 0],
                         bool)
        act = np.array([1, 0, 1, 0,  # fakes, two drawn
                        0, 1, 0, 0,  # probe-burst slots, one used
                        1, 1, 1], bool)  # reals
        return valid, act
    if name == "cell_shape":  # 8,192 slots, the faulty body's 382 slots
        return _holes(8192, 2000, rng), _scan_turn(rng, 0.6, 0.02, 128)
    if name == "cell_shape_overflow":
        return _holes(8192, 8000, rng), _scan_turn(rng, 0.6, 0.5, 128)
    raise KeyError(name)


CASES = ["empty", "holes_after_flush", "exactly_full", "fills_to_full",
         "over_by_one", "over_by_many", "inactive_fakes_and_bursts",
         "cell_shape", "cell_shape_overflow"]


@pytest.mark.parametrize("name", CASES)
def test_pending_slots_against_model(name):
    valid, act = _case(name)
    cap = valid.size
    with jax.enable_x64(True):  # the scan bodies trace under x64
        slot, pos, n_over, n_live = jax.jit(
            pending_slots, static_argnums=2)(valid, act, cap)
    assert slot.dtype == pos.dtype == n_over.dtype == n_live.dtype == np.int32
    slot, pos = np.asarray(slot), np.asarray(pos)
    want, over_compacted = _model(valid, act)
    np.testing.assert_array_equal(slot, want)
    np.testing.assert_array_equal(pos[act], np.arange(act.sum()))
    # inactive submissions go nowhere
    assert (slot[~act] == cap).all()
    # the placed copies take distinct slots, each free before the turn
    placed = slot[act & (slot < cap)]
    assert np.unique(placed).size == placed.size
    assert not valid[placed].any()
    # the compaction's overflow count, and the same copies dropped: the
    # last ones in submission order
    assert int(n_over) == over_compacted == act.sum() - placed.size
    ranks = np.flatnonzero(act)
    dropped = slot[ranks] == cap
    assert not (dropped[:-1] & ~dropped[1:]).any()
    assert int(n_live) == valid.sum() + placed.size


RECOVERY = RecoveryConfig(timeout_mult=8.0, retry_budget=2, retry_cap=4,
                          spec_cap=2, spec_ratio=3.0)
PEND_CAP = 512  # a few times the runs' peak: slots are reused many times
CHUNK = 32  # turns a call: the carry is compared at every call's end
PLAIN_COLS = {"done": 6, "start": 7, "rep": 8, "seq": 9}
FAULTY_COLS = dict(PLAIN_COLS, task=15, arrv=16, cost=17, dead=18, att=19,
                   dup=20, learn=21, to=22, retry=23)


def _final_carry(monkeypatch, factory):
    """Record each call's carry as the scan returns it."""
    real = getattr(scanloop, factory)
    carries = []

    def build(*a, **kw):
        run = real(*a, **kw)

        def recording(lcfg, carry, xs):
            carry, ys = run(lcfg, carry, xs)
            carries.append(jax.tree.map(np.array, carry))
            return carry, ys
        return recording
    monkeypatch.setattr(scanloop, factory, build)
    return carries


def _host_states(monkeypatch):
    """The host recovery loop's in-flight columns after each append."""
    real = rcv._append
    states = []

    def append(cols, **new):
        out = real(cols, **new)
        # copies: later turns update the loop's columns in place
        states.append({key: v.copy() for key, v in out.items()})
        return out
    monkeypatch.setattr(rcv, "_append", append)
    return states


class _TurnMarks(SequentialPool):
    """Notes, as each host turn starts, how many appends came before it."""

    def __init__(self, speeds, states):
        super().__init__(speeds)
        self.states, self.marks = states, []

    def set_speeds(self, speeds):
        self.marks.append(len(self.states))
        super().set_speeds(speeds)


@pytest.mark.parametrize("body", ["plain", "faulty"])
def test_scan_pending_set_matches_host(monkeypatch, body):
    """Over a whole run (churn with probe bursts for the plain body; a
    crash storm with timeouts, retries, ghosts and backup copies for the
    faulty one), at the end of every call the carry's live rows, keyed by
    ``p_seq``, are the host loop's in-flight set, column for column; and
    ``info["pend_peak"]`` is the host's largest in-flight count."""
    name, recovery, factory, cols = {
        "plain": ("churn", None, "_build_scan", PLAIN_COLS),
        "faulty": ("crash_storm", RECOVERY, "_build_scan_faulty",
                   FAULTY_COLS),
    }[body]
    kw = dict(sequential_pool=True, arrival_batch=8, seed=2)
    states = _host_states(monkeypatch)
    scn = env.make(name)
    pool = _TurnMarks(np.asarray(scn.speeds, float), states)
    # the inert recovery loop replays the plain host loop bit for bit and
    # keeps each copy's insertion sequence
    run_scenario(scn, use_scan=False, recovery=recovery or INERT_RECOVERY,
                 pool=pool, **kw)
    carries = _final_carry(monkeypatch, factory)
    s = run_scenario(env.make(name), use_scan=True, recovery=recovery,
                     pend_cap=PEND_CAP, chunk_turns=CHUNK, **kw)
    assert s["info"]["pend_overflow"] == 0
    turns = s["info"]["turns"]
    assert len(pool.marks) == turns and len(carries) > 4
    ends = pool.marks[1:] + [len(states)]  # appends made by each turn's end
    shuffled = 0
    for c, carry in enumerate(carries):
        host = states[ends[min((c + 1) * CHUNK, turns) - 1] - 1]
        live = carry[10]
        seq = carry[PLAIN_COLS["seq"]][live]
        order = np.argsort(seq)
        assert np.all(np.diff(host["seq"]) > 0)
        for key, i in cols.items():
            np.testing.assert_array_equal(
                carry[i][live][order], np.asarray(host[key], carry[i].dtype),
                err_msg=f"{key}, call {c}")
        # survivors stay put while new copies fill the holes: in slot
        # order the live copies are out of insertion order
        shuffled += bool((np.diff(seq) < 0).any())
    assert shuffled
    peak = max(len(st["seq"]) for st in states)
    assert s["info"]["pend_peak"] == peak < PEND_CAP
