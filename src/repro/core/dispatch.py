"""Unified batched dispatch engine — the single placement substrate.

The paper's throughput claim (§1: "millions of tasks per second") rests on
the scheduler making *batches* of placement decisions against a snapshot of
cluster state, not on serializing a probe → place → update loop per task.
This module is that substrate: every scheduling policy in
``core/policies.py`` has a vectorized batch form here, and every consumer
layer dispatches through the same engine:

  * ``core/scheduler.schedule``   — frontends place whole job batches
  * ``core/simulator.simulate``   — a multi-task arrival places as one batch
  * ``serving/router``            — request batches route in one call
  * ``benchmarks/sched_throughput`` — decisions/second for every policy

Mechanics:

  probe generation    All randomness is drawn up front, q-independently:
                      inverse-CDF proportional sampling (j = #{cdf ≤ u},
                      the Pallas kernel's dense comparison) for the
                      μ̂-weighted policies, batched ``randint`` for the
                      uniform ones. Because the draws never depend on the
                      queue, the batched path and the sequential oracle
                      consume *identical* streams. The PPoT uniform pair
                      comes from a counter-hash PRNG (``_uniform_pair``) —
                      an order of magnitude cheaper than threefry on the
                      hot path. The CDF is built once per batch and
                      threaded through the draws dict to every consumer
                      (jnp sampling, v1 kernel, fused v2 kernel). Callers
                      that refresh μ̂ on a cadence pass an amortized
                      ``AliasTable`` instead (``build_alias_table``, O(1)
                      draws via ``alias_sample``) — the searchsorted
                      sweeps drop off the per-call cost entirely.

  selection           SQ(2) / LL(2) / ε-greedy folds are elementwise
                      against the queue snapshot every task in the batch
                      observes (the distributed-frontend reality: probes
                      are in flight concurrently).

  conflict fold-back  A sorted-histogram fold returns the batch's own
                      placements into the caller's queue view
                      (``q_after``). On the fused-kernel path the fold
                      happens *inside* the Pallas kernel.

  self-correction     Optional ``fold_chunks=C``: the batch is placed in C
                      sub-chunks, re-snapshotting the queue between chunks.
                      ``C = B`` degenerates to the per-task sequential
                      semantics — retained as the reference oracle
                      (``dispatch_sequential``) for parity tests.

Kernel contract (v2, ``kernels/ppot_dispatch``): when the PPoT-SQ(2) batch
has no active-mask and no pinned slots, the fused kernel computes
probe → select → in-kernel histogram fold-back in ONE Pallas call and
returns ``(workers, q_after)`` directly — the engine adds nothing on top.
Batches with masks/pins fall back to the v1 select kernel + engine fold.
Both paths are bit-identical to the pure-jnp math (tests/test_kernels.py,
tests/test_dispatch.py); ``use_kernel=None`` auto-selects the kernel on
TPU and the jnp path elsewhere.

``dispatch_inplace`` is the same engine jitted with ``q`` donated, for
host-driven callers that hand over their queue buffer and rebind it to
``q_after``. (The serving router gets the same donation one level up:
``scheduler.route_view``/``serve_step`` donate the router's q_view.)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import policies as pol
from repro.kernels.ppot_dispatch import ref as pd_ref
from repro.kernels.ppot_dispatch.kernel import (
    ppot_dispatch as _ppot_kernel,
    ppot_dispatch_fused as _ppot_kernel_fused,
    ppot_dispatch_fused_alias as _ppot_kernel_fused_alias,
)


class DispatchResult(NamedTuple):
    workers: jax.Array  # i32[B] chosen worker per task; -1 at inactive slots
    q_after: jax.Array  # i32[n] queue view with the batch folded back


class AliasTable(NamedTuple):
    """Walker alias table for O(1) proportional sampling.

    ``prob[i]`` is the acceptance threshold of bin ``i`` and ``alias[i]``
    the overflow partner: a draw (u, v) lands in bin ``i = ⌊u·n⌋`` and
    resolves to ``i`` if ``v < prob[i]`` else ``alias[i]`` — two gathers
    and a compare, independent of n. Built once per μ̂ refresh
    (``build_alias_table``) and threaded through the engine the way the
    CDF is, so the per-dispatch cost drops from two O(B log n)
    searchsorted sweeps to O(B) gathers (ROADMAP "next 2×" item).
    """

    prob: jax.Array  # f32[n] acceptance threshold per bin
    alias: jax.Array  # i32[n] overflow partner per bin


#: Policies whose μ̂-proportional probe draw can run through an
#: ``AliasTable`` (HALO samples from μ_true, never from the table's μ̂).
ALIAS_POLICIES = (pol.PSS, pol.PPOT_SQ2, pol.PPOT_LL2, pol.BANDIT)


@jax.jit
def build_alias_table(
    mu_hat: jax.Array, active: jax.Array | None = None
) -> AliasTable:
    """Vose/Walker alias-table construction, O(n) + one sort.

    Amortized across every dispatch between two μ̂ refreshes — far too
    expensive to build per call (the ROADMAP's objection to a per-call
    table), trivially cheap per refresh. All-zero μ̂ (dead cluster)
    degenerates to the uniform table, the same guard as ``make_cdf``.

    ``active`` (bool[n], optional) is the cluster-membership mask: inactive
    workers get EXACTLY zero mass — their scaled weight enters the pairing
    as 0.0, so their acceptance threshold is exactly 0.0 and their alias
    partner is an active worker (a zero-mass bin is always a "small" and
    always pairs while large bins remain; it can never absorb residual
    mass). No renormalization drift: active workers' relative masses are
    untouched. If every active worker has μ̂ = 0, mass falls back to
    uniform over the ACTIVE set (never the inactive one).

    The classic small/large pairing runs as a ``fori_loop`` over two
    index stacks packed into one array (smalls grow from 0, larges from
    n): each iteration finalizes exactly one bin, so n iterations finish
    the table. Exact for degenerate weights: uniform μ̂ → prob ≡ 1
    (every draw keeps its own bin), single-hot μ̂ → every cold bin
    aliases to the hot one with prob 0.
    """
    n = mu_hat.shape[0]
    if active is None:
        total = jnp.sum(mu_hat)
        w = jnp.where(total > 0, mu_hat, jnp.ones_like(mu_hat))
    else:
        masked = jnp.where(active, mu_hat, 0.0)
        total = jnp.sum(masked)
        # all-active-zero → uniform over the active set; all-inactive
        # (pathological) → uniform over everything, like the unmasked guard
        fallback = jnp.where(
            jnp.any(active), active.astype(mu_hat.dtype), jnp.ones_like(mu_hat)
        )
        w = jnp.where(total > 0, masked, fallback)
    p = (w * (n / jnp.sum(w))).astype(jnp.float32)  # scaled weights, mean 1
    idx = jnp.arange(n, dtype=jnp.int32)
    small = p < 1.0
    # one array, two stacks: smalls at [0, ns), larges at [n-nl, n)
    stack = idx[jnp.argsort(jnp.where(small, idx, n + idx))].astype(jnp.int32)
    ns0 = jnp.sum(small).astype(jnp.int32)

    def body(_, st):
        p, prob, alias, stack, ns, nl = st
        has_s, has_l = ns > 0, nl > 0
        both = has_s & has_l
        s = stack[jnp.maximum(ns - 1, 0)]
        l = stack[n - jnp.maximum(nl, 1)]
        # the bin finalized this iteration (a small while any remain)
        fin = jnp.where(has_s, s, l)
        prob = prob.at[fin].set(jnp.where(both, p[s], 1.0))
        alias = alias.at[fin].set(jnp.where(both, l, fin))
        pl = p[l] - (1.0 - p[s])  # large's residual mass after the pairing
        p = jnp.where(both, p.at[l].set(pl), p)
        goes_small = both & (pl < 1.0)
        # residual large drops into the slot the finalized small vacated
        stack = jnp.where(
            goes_small, stack.at[jnp.maximum(ns - 1, 0)].set(l), stack
        )
        ns = jnp.where(both, jnp.where(goes_small, ns, ns - 1),
                       jnp.where(has_s, ns - 1, ns))
        nl = jnp.where(both, jnp.where(goes_small, nl - 1, nl),
                       jnp.where(has_s, nl, nl - 1))
        return p, prob, alias, stack, ns, nl

    # seed the loop carry FROM the inputs (0·p + const) so every element
    # carries the input's replication type — a pure-constant init trips
    # shard_map's scan replication check when the table is built inside a
    # collective (fleet sync: the carry would start "replicated" and end
    # probe-dependent)
    prob0 = p * 0.0 + 1.0
    alias0 = idx + stack * 0
    _, prob, alias, _, _, _ = jax.lax.fori_loop(
        0, n, body, (p, prob0, alias0, stack, ns0, jnp.int32(n) - ns0)
    )
    if active is not None:
        # Hard mask guarantee, independent of pairing-loop float drift: an
        # inactive bin accepts nothing (prob exactly 0 → every draw takes
        # its alias) and every alias edge lands on an active worker.
        prob = jnp.where(active, prob, 0.0)
        first_active = jnp.argmax(active).astype(jnp.int32)
        alias = jnp.where(active[alias], alias, first_active)
        prob = jnp.where(jnp.any(active), prob, prob0)  # pathological all-off
    return AliasTable(prob=prob, alias=alias)


def alias_sample(table: AliasTable, u: jax.Array, v: jax.Array) -> jax.Array:
    """O(1) proportional sample: bin ⌊u·n⌋, keep if v < prob else alias.

    Two gathers + one compare per draw — the amortized replacement for
    ``inverse_cdf_sample``'s O(log n) searchsorted sweep. Exactly the
    categorical distribution the table was built from (the (u, v) grid is
    16-bit on the hot path, the same resolution as the inverse-CDF draw).
    """
    n = table.prob.shape[0]
    i = jnp.minimum((u * n).astype(jnp.int32), n - 1)
    return jnp.where(v < table.prob[i], i, table.alias[i]).astype(jnp.int32)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def inverse_cdf_sample(cdf: jax.Array, u: jax.Array) -> jax.Array:
    """j[b] = #{i : cdf[i] ≤ u[b]} — proportional sample via inverse CDF.

    ``searchsorted(side="right")`` returns exactly that count, so the jnp
    path stays bit-identical to the Pallas kernel's dense comparison while
    running O(B log n) instead of O(B·n). Small problems (the serving
    router's per-batch shapes) take the dense-comparison form instead —
    the same count, cheaper to run AND to compile than the searchsorted
    while-loop. No clip is needed for the PPoT pair: ``make_cdf`` ends at
    exactly 1.0 and the 16-bit uniforms are < 1.0, so j ≤ n−1 already;
    callers with open-range uniforms clip.
    """
    n = cdf.shape[0]
    if n * u.shape[0] <= (1 << 16):
        return jnp.sum((cdf[None, :] <= u[:, None]), axis=1).astype(jnp.int32)
    return jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)


def _key_data(key: jax.Array) -> jax.Array:
    """uint32[2] words of ``key`` (accepts legacy and typed PRNG keys)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32)


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3 finalizer — full-avalanche 32-bit mix."""
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


def _uniform_pair(key: jax.Array, B: int) -> tuple[jax.Array, jax.Array]:
    """Two batches of uniforms from ONE counter-hash sweep.

    Each slot hashes its index (a Weyl sequence seeded by the two PRNG key
    words) through the murmur3 finalizer — a SplitMix-style counter
    generator — and splits the u32 into high/low 16-bit uniforms. ~10×
    cheaper than the threefry sweep it replaced (the RNG was the single
    largest cost of the PPoT hot path on CPU); the 2^-16 grid is far below
    any μ̂ resolution the scheduler acts on.
    """
    kd = _key_data(key)
    x = jnp.arange(B, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9) + kd[0]
    x = _fmix32(x ^ (kd[1] * jnp.uint32(0x85EBCA6B)))
    u1 = (x >> 16).astype(jnp.float32) * (1.0 / 65536.0)
    u2 = (x & jnp.uint32(0xFFFF)).astype(jnp.float32) * (1.0 / 65536.0)
    return u1, u2


def _uniform_quad(key: jax.Array, B: int):
    """(u1, u2, v1, v2) — the alias sampler's four uniforms per task.

    The first counter-hash sweep is ``_uniform_pair`` verbatim (the bin
    draws u1/u2 stay on the stream the inverse-CDF engine consumes); the
    second sweep re-mixes the same Weyl counter against a different key
    schedule for the acceptance draws v1/v2 — one extra fmix sweep, still
    an order of magnitude cheaper than a threefry call.
    """
    kd = _key_data(key)
    x = jnp.arange(B, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9) + kd[0]
    h1 = _fmix32(x ^ (kd[1] * jnp.uint32(0x85EBCA6B)))
    h2 = _fmix32((x + jnp.uint32(0x7F4A7C15)) ^ (kd[1] * jnp.uint32(0xC2B2AE35)))
    u1 = (h1 >> 16).astype(jnp.float32) * (1.0 / 65536.0)
    u2 = (h1 & jnp.uint32(0xFFFF)).astype(jnp.float32) * (1.0 / 65536.0)
    v1 = (h2 >> 16).astype(jnp.float32) * (1.0 / 65536.0)
    v2 = (h2 & jnp.uint32(0xFFFF)).astype(jnp.float32) * (1.0 / 65536.0)
    return u1, u2, v1, v2


def _active_choice(mask: jax.Array, u: jax.Array) -> jax.Array:
    """Uniform draw over the ACTIVE workers: map u ∈ [0,1) through the
    index table of active workers (actives first, in index order). The
    masked replacement for ``randint(0, n)`` wherever a policy draws a
    uniform worker — under churn no probe may land on an offline worker.
    All-inactive degenerates to a uniform draw over everything (callers
    never dispatch against an empty cluster; the guard only keeps the
    gather in bounds)."""
    n = mask.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(jnp.where(mask, idx, n + idx)).astype(jnp.int32)
    n_act = jnp.sum(mask).astype(jnp.int32)
    n_eff = jnp.maximum(n_act, 1)
    j = jnp.minimum((u * n_eff).astype(jnp.int32), n_eff - 1)
    return jnp.where(n_act > 0, order[j], (u * n).astype(jnp.int32))


def masked_cdf(mu: jax.Array, mask: jax.Array) -> jax.Array:
    """``make_cdf`` with inactive workers' mass zeroed exactly — the
    searchsorted-path counterpart of the masked alias table. A zero-mass
    bin i has cdf[i-1] == cdf[i], so ``#{cdf ≤ u}`` can never land on it.
    All-active-zero falls back to uniform over the active set."""
    w = jnp.where(mask, mu, 0.0)
    total = jnp.sum(w)
    fallback = jnp.where(
        jnp.any(mask), mask.astype(mu.dtype), jnp.ones_like(mu)
    )
    w = jnp.where(total > 0, w, fallback)
    c = jnp.cumsum(w)
    return c / c[-1]


def _fold_counts(q: jax.Array, workers: jax.Array,
                 active: jax.Array | None) -> jax.Array:
    """Per-worker placement counts WITHOUT a scatter or a sort: split each
    worker id into (hi, lo) digits, one-hot both halves, and contract the
    two [B, √n]-ish indicator matrices over the batch axis — the [hi, lo]
    product counts exactly the (hi, lo) pairs, i.e. the histogram. The
    digit split keeps indicator construction at O(B·√n) instead of O(B·n),
    and the contraction is a dense f32 matmul (exact for integer counts up
    to 2^24) — ~2× faster than the XLA sort- or scatter-based folds on CPU
    at n=64, B=4096. With an active mask, inactive slots are binned at a
    sentinel (n) that falls off the histogram slice."""
    n = q.shape[0]
    nbins = n if active is None else n + 1  # sentinel bin for inactive slots
    w = workers if active is None else jnp.where(active, workers, n)
    k = max((nbins - 1).bit_length() // 2, 1)
    R2 = 1 << k
    R1 = -(-nbins // R2)
    hi = ((w[:, None] >> k) == jnp.arange(R1)[None, :]).astype(jnp.float32)
    lo = ((w[:, None] & (R2 - 1)) == jnp.arange(R2)[None, :]).astype(jnp.float32)
    counts = jax.lax.dot_general(
        hi, lo, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return counts.reshape(R1 * R2)[:n].astype(q.dtype)


# ---------------------------------------------------------------------------
# Probe generation (q-independent; shared by batched path and oracle)
# ---------------------------------------------------------------------------


def _draws(policy: str, key, B: int, n: int, cfg, mu_hat, mu_true,
           *, need_j: bool = True, table: AliasTable | None = None,
           mask: jax.Array | None = None) -> dict:
    """Draw every random quantity the policy needs for a batch of B tasks.

    Each [B]-shaped entry (batch axis leading) can be re-chunked by the
    engine for within-batch self-correction without re-drawing; the shared
    ``"cdf"`` entry ([n]-shaped) is built ONCE here and threaded to every
    consumer — jnp sampling, the v1 kernel and the fused v2 kernel all
    read the same array. ``need_j=False`` skips materializing j1/j2 for
    the fused-kernel path (the kernel re-derives them from u1/u2 on
    device, bit-identically).

    When the caller hands in an amortized ``table`` (built once per μ̂
    refresh), the μ̂-proportional policies (``ALIAS_POLICIES``) draw their
    probes via ``alias_sample`` — (u, v) pairs, two gathers + a compare —
    instead of the per-call CDF + searchsorted sweep. NOTE the RNG stream
    changes: the alias draw consumes an extra acceptance uniform per
    probe, so selections differ draw-for-draw from the inverse-CDF engine
    while matching it in distribution (tests/test_alias.py pins both).

    ``mask`` (bool[n], optional) restricts every draw to ACTIVE workers:
    uniform draws map through the active-index table (``_active_choice``),
    μ̂/μ-proportional draws sample a masked CDF (``masked_cdf``); a
    caller-supplied ``table`` must already be masked
    (``build_alias_table(mu, active)`` — the engine cannot verify).
    ``mask=None`` leaves every RNG stream bit-identical to before.
    """
    d: dict[str, jax.Array] = {}
    if table is not None and policy not in ALIAS_POLICIES:
        table = None  # μ_true-driven / uniform policies ignore the μ̂ table

    def _cdf(mu):
        return pd_ref.make_cdf(mu) if mask is None else masked_cdf(mu, mask)

    def _uni_workers(k, shape):
        if mask is None:
            return jax.random.randint(k, shape, 0, n, dtype=jnp.int32)
        return _active_choice(mask, jax.random.uniform(k, shape))

    if policy == pol.UNIFORM:
        d["j_uni"] = _uni_workers(key, (B,))
    elif policy == pol.POT:
        jj = _uni_workers(key, (2, B))
        d["j1"], d["j2"] = jj[0], jj[1]
    elif policy == pol.PSS:
        if table is not None:
            u, _, v, _ = _uniform_quad(key, B)
            d["j1"] = alias_sample(table, u, v)
        else:
            cdf = _cdf(mu_hat)
            u = jax.random.uniform(key, (B,))
            d["j1"] = jnp.clip(inverse_cdf_sample(cdf, u), 0, n - 1)
    elif policy == pol.HALO:
        cdf = _cdf(mu_true)
        u = jax.random.uniform(key, (B,))
        d["j1"] = jnp.clip(inverse_cdf_sample(cdf, u), 0, n - 1)
    elif policy in (pol.PPOT_SQ2, pol.PPOT_LL2):
        if table is not None:
            u1, u2, v1, v2 = _uniform_quad(key, B)
            if need_j:
                d["j1"] = alias_sample(table, u1, v1)
                d["j2"] = alias_sample(table, u2, v2)
            else:  # fused alias kernel re-derives j from (u, v) on device
                d["u1"], d["u2"], d["v1"], d["v2"] = u1, u2, v1, v2
        else:
            d["cdf"] = _cdf(mu_hat)
            d["u1"], d["u2"] = _uniform_pair(key, B)
            if need_j:
                d["j1"] = inverse_cdf_sample(d["cdf"], d["u1"])
                d["j2"] = inverse_cdf_sample(d["cdf"], d["u2"])
    elif policy == pol.BANDIT:
        k1, k3, k4 = jax.random.split(key, 3)
        if table is not None:
            u1, u2, v1, v2 = _uniform_quad(k1, B)
            d["j1"] = alias_sample(table, u1, v1)
            d["j2"] = alias_sample(table, u2, v2)
        else:
            cdf = _cdf(mu_hat)
            u1, u2 = _uniform_pair(k1, B)
            d["j1"] = inverse_cdf_sample(cdf, u1)
            d["j2"] = inverse_cdf_sample(cdf, u2)
        d["explore"] = jax.random.uniform(k3, (B,)) < cfg.bandit_eta
        d["j_uni"] = _uni_workers(k4, (B,))
    elif policy == pol.SPARROW:
        n_probe = max(int(cfg.sparrow_d) * B, B)
        d["probes"] = _uni_workers(key, (n_probe,))
    else:
        raise ValueError(f"unknown policy {policy!r}; choose from {pol.ALL_POLICIES}")
    return d


# ---------------------------------------------------------------------------
# Selection against a queue snapshot
# ---------------------------------------------------------------------------


def _select(policy: str, q_view, d: dict, mu_hat, mu_true, cfg,
            *, kernel: bool = False, interpret: bool = False) -> jax.Array:
    """Pick one worker per task in the (sub-)batch against ``q_view``."""
    if policy in (pol.UNIFORM,):
        return d["j_uni"]
    if policy in (pol.PSS, pol.HALO):
        return d["j1"]
    if policy in (pol.POT, pol.PPOT_SQ2):
        if policy == pol.PPOT_SQ2 and kernel:
            return _ppot_kernel(d["cdf"], q_view, d["u1"], d["u2"],
                                interpret=interpret)
        j1, j2 = d["j1"], d["j2"]
        return jnp.where(q_view[j1] <= q_view[j2], j1, j2)
    if policy == pol.PPOT_LL2:
        j1, j2 = d["j1"], d["j2"]
        mu = jnp.clip(mu_hat, min=1e-9)
        w1 = (q_view[j1] + 1.0) / mu[j1]
        w2 = (q_view[j2] + 1.0) / mu[j2]
        return jnp.where(w1 <= w2, j1, j2)
    if policy == pol.BANDIT:
        j1, j2 = d["j1"], d["j2"]
        j_ppot = jnp.where(q_view[j1] <= q_view[j2], j1, j2)
        return jnp.where(d["explore"], d["j_uni"], j_ppot)
    raise ValueError(f"no snapshot selection for policy {policy!r}")


def _sparrow_select(q_view, probes, B: int, m=None) -> jax.Array:
    """Sparrow batch sampling + late binding, fully vectorized.

    The reference semantics is the greedy loop: ``m`` times, place a task on
    the currently least-loaded *probed* worker (ties broken by earliest
    probe position) and fold the placement back. Greedy water-fills:
    participants level up to a common load, then round-robin. That makes it
    closed-form — sort probed workers by (load, first-probe-pos), find how
    many join the fill (k*), split the remaining tasks into full rounds + a
    remainder to the earliest-probed participants, and recover the per-slot
    order by sorting placements by (load-at-placement, first-probe-pos).
    Exactly the greedy sequence (slot-for-slot), without the m-step argmin
    scan. ``m`` may be traced (≤ B, the static shape bound); emission slots
    ≥ m are padding.
    """
    n = q_view.shape[0]
    P = probes.shape[0]
    if m is None:
        m = B
    INF = jnp.int32(2**30)
    # first probe position of each worker; unprobed → P (never placed)
    fp = jnp.full((n,), P, jnp.int32).at[probes].min(
        jnp.arange(P, dtype=jnp.int32)
    )
    probed = fp < P
    loads = jnp.where(probed, q_view.astype(jnp.int32), INF)
    order = jnp.lexsort((fp, loads))  # (load, first-probe-pos) ascending
    s = loads[order]
    ws = order.astype(jnp.int32)
    fps = fp[order]
    s_fin = jnp.where(s < INF, s, 0)
    Sx = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(s_fin)])
    # worker k joins the fill iff leveling the first k up to its load fits in m
    k_idx = jnp.arange(1, n, dtype=jnp.int32)
    cost = k_idx * s_fin[1:] - Sx[1:n]
    joins = (s[1:] < INF) & (cost <= m)
    k_star = 1 + jnp.sum(joins.astype(jnp.int32))
    lam0 = s_fin[k_star - 1]  # common level once all participants joined
    spent = k_star * lam0 - Sx[k_star]
    full, rem = (m - spent) // k_star, (m - spent) % k_star
    part = jnp.arange(n) < k_star
    fp_rank = jnp.argsort(jnp.argsort(jnp.where(part, fps, INF)))
    alloc = jnp.where(part, (lam0 - s_fin) + full + (fp_rank < rem), 0)
    alloc = alloc.astype(jnp.int32)
    # expand to per-slot placements and order them as greedy would emit them
    astart = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(alloc)[:-1]])
    wexp = jnp.repeat(ws, alloc, total_repeat_length=B)
    sexp = jnp.repeat(s_fin, alloc, total_repeat_length=B)
    fpexp = jnp.repeat(fps, alloc, total_repeat_length=B)
    stexp = jnp.repeat(astart, alloc, total_repeat_length=B)
    v = sexp + (jnp.arange(B, dtype=jnp.int32) - stexp)  # load at placement
    v = jnp.where(jnp.arange(B) < m, v, INF)  # padding sorts last
    return wexp[jnp.lexsort((fpexp, v))].astype(jnp.int32)


def within_batch_rank(workers: jax.Array, active: jax.Array) -> jax.Array:
    """rank[b] = #{a < b : active[a] ∧ workers[a] == workers[b]}.

    The per-worker ordinal of each task inside its own batch — what a
    sequential placement loop would have observed as "my position in this
    worker's queue beyond the snapshot". Sort-based O(B log B): a stable
    argsort groups equal workers while preserving batch order, so the rank
    is an exclusive running count of active slots since the group started —
    no B×B comparison matrix (``within_batch_rank_ref`` keeps the O(B²)
    all-pairs form as the parity oracle).
    """
    B = workers.shape[0]
    order = jnp.argsort(workers, stable=True)
    sa = active[order].astype(jnp.int32)
    sw = workers[order]
    ex = jnp.cumsum(sa) - sa  # exclusive count of active slots so far
    start = jnp.concatenate([jnp.ones((1,), bool), sw[1:] != sw[:-1]])
    # ex is nondecreasing, so a running max of its value at group starts
    # propagates "active count when my group began" to every group member.
    base = jax.lax.cummax(jnp.where(start, ex, 0))
    return jnp.zeros((B,), jnp.int32).at[order].set(ex - base)


def within_batch_rank_ref(workers: jax.Array, active: jax.Array) -> jax.Array:
    """O(B²) all-pairs reference for ``within_batch_rank`` (tests only)."""
    B = workers.shape[0]
    before = jnp.arange(B)[None, :] < jnp.arange(B)[:, None]
    same = (workers[None, :] == workers[:, None]) & active[None, :] & before
    return jnp.sum(same, axis=1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _chunking(B: int, fold_chunks: int) -> tuple[int, int]:
    """(chunks, padded_B): honor the requested self-correction granularity
    even when fold_chunks does not divide B by padding the batch up to the
    next multiple (pad slots are inactive and sliced off)."""
    C = max(min(int(fold_chunks), B), 1)
    Bp = -(-B // C) * C
    return C, Bp


def _dispatch_impl(
    policy: str,
    key: jax.Array,
    q: jax.Array,  # i32[n] queue snapshot (real queue / scheduler view)
    mu_hat: jax.Array,  # f32[n] learner estimates
    mu_true: jax.Array,  # f32[n] ground truth (only HALO reads it)
    cfg: pol.PolicyConfig,
    B: int,
    *,
    active: jax.Array | None = None,  # bool[B]; inactive slots place nothing
    forced: jax.Array | None = None,  # i32[B]; ≥0 pins the slot to that worker
    fold_chunks: int = 1,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    table: AliasTable | None = None,  # amortized μ̂ alias table (per refresh)
    mask: jax.Array | None = None,  # bool[n] membership: only active workers
) -> DispatchResult:
    """Place ``B`` tasks in one engine call. Returns (workers[B], q_after).

    ``fold_chunks=1`` is the fully batched path (all tasks see the same
    snapshot, one histogram fold-back). ``fold_chunks=C`` re-snapshots the
    queue between C equal sub-chunks (within-batch self-correction; B is
    padded up with inactive slots when C does not divide it);
    ``fold_chunks=B`` reproduces per-task sequential semantics and is the
    reference oracle. ``forced`` pins slots to externally-chosen workers
    (the simulator's placement-constrained tasks) — pinned placements fold
    back into the queue view the later chunks observe, like any other
    placement (for SPARROW the pin is applied after water-filling).
    ``use_kernel=None`` auto-selects the Pallas PPoT kernel on TPU; plain
    PPoT-SQ(2) batches (no mask, no pins) run the FUSED v2 kernel, which
    returns (workers, q_after) in one call. ``table`` switches the
    μ̂-proportional probe draw to the amortized alias sampler (and the
    fused kernel to its alias-probe variant); the caller owns the
    build-per-refresh cadence — pass a table built from THIS ``mu_hat``.

    ``mask`` (bool[n], optional) is the cluster-membership mask (worker
    churn): NO task is ever placed on an inactive worker — uniform draws
    map through the active-index table, proportional draws sample a
    zero-massed CDF, and a supplied ``table`` must have been built with
    the same mask (``build_alias_table(mu, active)``). Pinned ``forced``
    slots are the caller's contract (pin to active workers). Masked
    batches take the jnp path (the Pallas kernels are mask-oblivious);
    ``mask=None`` is bit-identical to the pre-mask engine.
    """
    n = q.shape[0]
    if use_kernel is None:
        use_kernel = _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()

    if policy == pol.SPARROW:
        # Water-filling already models per-task fold-back over the probe
        # set; fold_chunks does not apply. Pinned (forced) placements are
        # folded into the fill's queue snapshot first, then the remaining
        # tasks water-fill around them (the seed interleaved pins at their
        # slot positions; folding them up front is the batched equivalent).
        act = active if active is not None else jnp.ones((B,), bool)
        d = _draws(policy, key, B, n, cfg, mu_hat, mu_true, mask=mask)
        if forced is not None:
            pin = (forced >= 0) & act
            wpin = jnp.where(pin, forced, 0)
            q_fill = q + jnp.zeros_like(q).at[wpin].add(pin.astype(q.dtype))
        else:
            pin = jnp.zeros((B,), bool)
            q_fill = q
        unpinned = act & ~pin
        seq = _sparrow_select(q_fill, d["probes"], B, jnp.sum(unpinned))
        slot_rank = jnp.cumsum(unpinned.astype(jnp.int32)) - 1
        workers = seq[jnp.clip(slot_rank, 0, B - 1)]
        if forced is not None:
            workers = jnp.where(pin, forced, workers)
        workers = workers.astype(jnp.int32)
        q_after = q + _fold_counts(q, workers, act)
        return DispatchResult(workers=jnp.where(act, workers, -1), q_after=q_after)

    C, Bp = _chunking(B, fold_chunks)
    fused = (
        use_kernel and policy == pol.PPOT_SQ2 and C == 1
        and active is None and forced is None and mask is None
    )
    act = active
    if Bp != B:
        pad = jnp.zeros((Bp - B,), bool)
        head = jnp.ones((B,), bool) if act is None else act
        act = jnp.concatenate([head, pad])
        if forced is not None:
            forced = jnp.concatenate([forced, jnp.full((Bp - B,), -1, jnp.int32)])
    d = _draws(policy, key, Bp, n, cfg, mu_hat, mu_true, need_j=not fused,
               table=table, mask=mask)

    if fused:
        # One Pallas call: probe → select → in-kernel fold-back.
        if table is not None:
            workers, q_after = _ppot_kernel_fused_alias(
                table.prob, table.alias, q, d["u1"], d["v1"], d["u2"], d["v2"],
                interpret=interpret,
            )
        else:
            workers, q_after = _ppot_kernel_fused(
                d["cdf"], q, d["u1"], d["u2"], interpret=interpret
            )
        return DispatchResult(workers=workers, q_after=q_after)

    if C == 1:
        # v1 select kernel is CDF-based; alias batches already carry j1/j2
        kernel = use_kernel and policy == pol.PPOT_SQ2 and "cdf" in d
        workers = _select(policy, q, d, mu_hat, mu_true, cfg,
                          kernel=kernel, interpret=interpret)
        if forced is not None:
            workers = jnp.where(forced >= 0, forced, workers)
    else:
        fc_all = forced if forced is not None else jnp.full((Bp,), -1, jnp.int32)
        d.pop("cdf", None)  # [n]-shaped; chunks re-use the materialized j's
        stacked = {k: v.reshape(C, Bp // C) for k, v in d.items()}
        stacked["_active"] = (
            act if act is not None else jnp.ones((Bp,), bool)
        ).reshape(C, Bp // C)
        stacked["_forced"] = fc_all.reshape(C, Bp // C)

        def body(qv, dc):
            ac = dc.pop("_active")
            fc = dc.pop("_forced")
            w = _select(policy, qv, dc, mu_hat, mu_true, cfg, kernel=False)
            w = jnp.where(fc >= 0, fc, w)
            qv = qv + jnp.zeros_like(qv).at[w].add(ac.astype(qv.dtype))
            return qv, w

        _, ws = jax.lax.scan(body, q, stacked)
        workers = ws.reshape(Bp)
    if Bp != B:
        workers = workers[:B]
        act = act[:B] if act is not None else None

    workers = workers.astype(jnp.int32)
    q_after = q + _fold_counts(q, workers, act)
    if act is not None:
        workers = jnp.where(act, workers, -1)
    return DispatchResult(workers=workers, q_after=q_after)


_STATIC = ("policy", "B", "fold_chunks", "use_kernel", "interpret")

dispatch = functools.partial(jax.jit, static_argnames=_STATIC)(_dispatch_impl)

#: Same engine with ``q`` donated: the caller's queue buffer is consumed and
#: rewritten in place as ``q_after`` — for host loops that rebind
#: ``q = dispatch_inplace(...).q_after``; do NOT reuse the old ``q`` after
#: calling this variant. (The serving router donates one level up, via
#: ``scheduler.route_view``/``serve_step``.)
dispatch_inplace = functools.partial(
    jax.jit, static_argnames=_STATIC, donate_argnames=("q",)
)(_dispatch_impl)


def dispatch_sequential(
    policy: str, key, q, mu_hat, mu_true, cfg, B: int, *, active=None,
    table: AliasTable | None = None, mask: jax.Array | None = None,
) -> DispatchResult:
    """Reference oracle: identical probe stream, per-task queue fold-back.

    This is the paper's sequential frontend loop, kept only for parity
    testing and as the serial baseline in benchmarks/sched_throughput.
    With ``table`` it consumes the alias (u, v) stream, and with ``mask``
    the masked draw streams, so it stays the bit-exact oracle for
    alias-mode and membership-masked batches too.
    """
    return dispatch(policy, key, q, mu_hat, mu_true, cfg, B,
                    active=active, fold_chunks=B, use_kernel=False,
                    table=table, mask=mask)
