"""Pallas TPU kernels for batched PPoT dispatch (the paper's per-decision hot
path at "millions of tasks per second", §1).

Two generations live here:

``ppot_dispatch`` (v1)
    probe → SQ(2) select only. Returns ``workers`` and leaves the conflict
    fold-back (the per-worker placement histogram that produces ``q_after``)
    to a separate XLA scatter pass in the engine. Kept as the parity oracle
    for the fused kernel and for callers that fold externally (active-mask /
    pinned-slot batches).

``ppot_dispatch_fused`` (v2)
    one kernel: inverse-CDF probe → SQ(2) select → in-kernel histogram
    fold-back. Returns ``(workers, q_after)`` directly — the dispatch hot
    path never leaves the device between probe and queue update. The
    fold-back accumulates into a revisited output block across grid steps
    (the grid is sequential on TPU, so ``q_after`` is initialized to ``q``
    at step 0 and each job block adds its per-worker counts), with padding
    slots masked out of the histogram. ``b_blk`` (a multiple of 128 on
    TPU) is tunable; 256 is the default — sweep it on the chip (ROADMAP
    A9).

``ppot_dispatch_fused_alias`` (v3)
    the v2 pipeline with the probe stage swapped for the amortized Walker
    alias table (``core/dispatch.build_alias_table``): instead of the
    dense [n, B_BLK] CDF comparisons, each candidate is a bin draw
    ``i = ⌊u·n⌋`` plus two one-hot table gathers (prob + alias, the same
    masked reduce the queue gather uses) and a compare. The table is
    built once per μ̂ refresh. v2 stays
    as the inverse-CDF parity oracle; the alias kernel's oracle is the
    engine's jnp alias path on the same (u, v) stream (bit-identical,
    tests/test_alias.py).

HARDWARE ADAPTATION (DESIGN.md §2): a CPU scheduler does a per-job binary
search over the CDF. On TPU, branchy binary search wastes the VPU; instead
each grid step loads the whole worker state (CDF + queue lengths, n ≤ 2048,
trivially VMEM-resident) and a block of B_BLK jobs, and computes the
inverse-CDF sample as a dense [n, B_BLK] comparison — sum(cdf <= u) — which
is one vectorized reduce per candidate. Two candidates + SQ(2) argmin are
elementwise. Gathers are slow on TPU, so a queue-length (or table) lookup
is a one-hot mask over the same [n, B_BLK] tile reduced over workers — one
nonzero term per job, so the f32 sum is exact — and the one-hot of the
*chosen* worker, reduced over the job axis, is the fold-back histogram: the
fusion that removes the separate scatter pass.

Layout: jobs ride the lanes and workers the sublanes. Job vectors enter as
[1, B] rows cut into (1, B_BLK) blocks, worker state as [n, 1] columns
replicated every step. A 1-D block smaller than its array would have to
match XLA's own tiling of 1-D arrays (T(1024) from 512 elements up), which
Mosaic refuses for B_BLK = 256; a (1, B_BLK) block of a [1, B] row has no
such constraint, so the kernels compile at every B.

Grid: (B // B_BLK,), padded up. Index maps return explicit i32 indices:
the serving scan traces the dispatch engine under ``jax.enable_x64``, where
a bare ``0`` becomes an i64 constant that Mosaic cannot lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

B_BLK = 256  # default jobs per grid step (two 128-lane vregs)


def _rep(n):
    """Worker-state column [n, 1]: the same whole block every step."""
    return pl.BlockSpec((n, 1), lambda i: (jnp.int32(0), jnp.int32(0)))


def _blk(b_blk):
    """Job row [1, B]: block ``i`` of ``b_blk`` lanes."""
    return pl.BlockSpec((1, b_blk), lambda i: (jnp.int32(0), i))


def _rows(b_blk, *us):
    """Pad each job vector to a multiple of ``b_blk`` and lay it out as a
    [1, Bp] row; returns (rows, grid)."""
    pad = (-us[0].shape[0]) % b_blk
    rows = [jnp.pad(u, (0, pad)).reshape(1, -1) for u in us]
    return rows, (rows[0].shape[1] // b_blk,)


def _gather(col, oh):
    """col[j[b]] per job: the one-hot [n, b] mask reduced over workers.
    One nonzero term per job, so the f32 sum is exact (ids and queue
    lengths < 2^24)."""
    return jnp.sum(jnp.where(oh, col, 0.0), axis=0, keepdims=True)


def _inverse_cdf(cdf, u):
    """j[b] = #{i : cdf[i] ≤ u[b]}, clipped to n-1; cdf [n, 1], u [1, b]."""
    j = jnp.sum((cdf <= u).astype(jnp.float32), axis=0, keepdims=True)
    return jnp.minimum(j.astype(jnp.int32), cdf.shape[0] - 1)


def _sq2(qf, iota, j1, j2):
    """SQ(2): the candidate with the shorter queue (ties keep j1)."""
    take1 = _gather(qf, iota == j1) <= _gather(qf, iota == j2)
    return jnp.where(take1, j1, j2)


def _fold(B, b_blk, q, iota, w, qa_ref):
    """Fold the block's placements back into the revisited q_after block:
    the chosen one-hot, padding slots masked, reduced over the job axis —
    integer counts are exact in f32 (≤ b_blk < 2^24)."""
    i = pl.program_id(0)
    slot = i * b_blk + jax.lax.broadcasted_iota(jnp.int32, (1, b_blk), 1)
    ohw = (iota == w) & (slot < B)
    counts = jnp.sum(ohw.astype(jnp.float32), axis=1, keepdims=True)

    @pl.when(i == 0)
    def _():
        qa_ref[...] = q

    qa_ref[...] += counts.astype(jnp.int32)


def _kernel(cdf_ref, q_ref, u1_ref, u2_ref, out_ref):
    """v1: probe + select only (fold-back happens outside)."""
    cdf = cdf_ref[...]
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (cdf.shape[0], out_ref.shape[1]), 0)
    out_ref[...] = _sq2(q_ref[...], iota, _inverse_cdf(cdf, u1_ref[...]),
                        _inverse_cdf(cdf, u2_ref[...]))


def _fused_kernel(B, b_blk, cdf_ref, q_ref, u1_ref, u2_ref, w_ref, qa_ref):
    """v2: probe + select + fold-back histogram, accumulated across steps."""
    cdf = cdf_ref[...]
    q = q_ref[...]  # i32[n, 1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], b_blk), 0)
    w = _sq2(q.astype(jnp.float32), iota, _inverse_cdf(cdf, u1_ref[...]),
             _inverse_cdf(cdf, u2_ref[...]))
    w_ref[...] = w
    _fold(B, b_blk, q, iota, w, qa_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ppot_dispatch(cdf, q, u1, u2, *, interpret: bool = False):
    """v1 oracle: cdf f32[n], q i32[n], u1/u2 f32[B] → i32[B] worker choices.
    B is padded up to a multiple of B_BLK internally."""
    B = u1.shape[0]
    n = cdf.shape[0]
    (u1, u2), grid = _rows(B_BLK, u1, u2)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[_rep(n), _rep(n), _blk(B_BLK), _blk(B_BLK)],
        out_specs=_blk(B_BLK),
        out_shape=jax.ShapeDtypeStruct(u1.shape, jnp.int32),
        interpret=interpret,
    )(cdf.reshape(n, 1), q.astype(jnp.float32).reshape(n, 1), u1, u2)
    return out.reshape(-1)[:B]


def _fused_alias_kernel(B, b_blk, prob_ref, alias_ref, q_ref,
                        u1_ref, v1_ref, u2_ref, v2_ref, w_ref, qa_ref):
    """v3: alias-table probe + SQ(2) select + fold-back histogram."""
    q = q_ref[...]  # i32[n, 1]
    n = q.shape[0]
    prob = prob_ref[...]
    alias = alias_ref[...].astype(jnp.float32)  # ids exact in f32
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, b_blk), 0)

    def probe(u, v):  # bin ⌊u·n⌋, keep it or redirect to its alias
        b = jnp.minimum((u * n).astype(jnp.int32), n - 1)
        oh = iota == b
        return jnp.where(v < _gather(prob, oh), b,
                         _gather(alias, oh).astype(jnp.int32))

    w = _sq2(q.astype(jnp.float32), iota, probe(u1_ref[...], v1_ref[...]),
             probe(u2_ref[...], v2_ref[...]))
    w_ref[...] = w
    _fold(B, b_blk, q, iota, w, qa_ref)


@functools.partial(jax.jit, static_argnames=("b_blk", "interpret"))
def ppot_dispatch_fused_alias(prob, alias, q, u1, v1, u2, v2, *,
                              b_blk: int = B_BLK, interpret: bool = False):
    """v3 fused contract: prob f32[n], alias i32[n], q i32[n],
    u/v f32[B] → (workers i32[B], q_after i32[n]).

    The alias-probe variant of ``ppot_dispatch_fused``: same grid, same
    revisited-accumulator fold-back, but the probe stage is two amortized
    table gathers per candidate instead of a dense CDF reduce.
    Bit-identical to the engine's jnp alias path on the same uniforms.
    """
    B = u1.shape[0]
    n = prob.shape[0]
    rows, grid = _rows(b_blk, u1, v1, u2, v2)
    workers, q_after = pl.pallas_call(
        functools.partial(_fused_alias_kernel, B, b_blk),
        grid=grid,
        in_specs=[_rep(n)] * 3 + [_blk(b_blk)] * 4,
        out_specs=[_blk(b_blk), _rep(n)],  # q_after: revisited accumulator
        out_shape=[
            jax.ShapeDtypeStruct(rows[0].shape, jnp.int32),
            jax.ShapeDtypeStruct((n, 1), q.dtype),
        ],
        interpret=interpret,
    )(prob.reshape(n, 1), alias.reshape(n, 1), q.reshape(n, 1), *rows)
    return workers.reshape(-1)[:B], q_after.reshape(n)


@functools.partial(jax.jit, static_argnames=("b_blk", "interpret"))
def ppot_dispatch_fused(cdf, q, u1, u2, *, b_blk: int = B_BLK,
                        interpret: bool = False):
    """v2 fused contract: cdf f32[n], q i32[n], u1/u2 f32[B] →
    (workers i32[B], q_after i32[n]).

    ``q_after = q + histogram(workers)`` is computed in-kernel (no separate
    scatter pass); bit-identical to the v1-select + external-fold path and
    to the engine's pure-jnp math on the same uniforms.
    """
    B = u1.shape[0]
    n = cdf.shape[0]
    (u1, u2), grid = _rows(b_blk, u1, u2)
    workers, q_after = pl.pallas_call(
        functools.partial(_fused_kernel, B, b_blk),
        grid=grid,
        in_specs=[_rep(n), _rep(n), _blk(b_blk), _blk(b_blk)],
        out_specs=[_blk(b_blk), _rep(n)],  # q_after: revisited accumulator
        out_shape=[
            jax.ShapeDtypeStruct(u1.shape, jnp.int32),
            jax.ShapeDtypeStruct((n, 1), q.dtype),
        ],
        interpret=interpret,
    )(cdf.reshape(n, 1), q.reshape(n, 1), u1, u2)
    return workers.reshape(-1)[:B], q_after.reshape(n)
