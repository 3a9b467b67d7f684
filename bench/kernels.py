"""What the scheduler's device kernels must do, counted from their shapes,
and the least time the chip could take for it.

Counts are of the algorithm, not of an implementation: a kernel that does
more (a one-hot gather over all n workers where one load would do) gets no
credit for it.  Peaks come from ``bench/peaks.json``, keyed by the device
kind JAX reports; a device that is not there is an error.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
#: The fused alias PPoT-SQ(2) dispatch kernel: a Mosaic custom call whose
#: instruction text in the device trace names the jitted kernel wrapper.
PPOT_ALIAS = "ppot_dispatch_fused_alias"


def is_ppot_alias_kernel(event_name: str) -> bool:
    """The kernel's own events (a custom call), not the reshapes and slices
    around it that carry the same wrapper name in their metadata."""
    return PPOT_ALIAS in event_name and (
        "custom-call(" in event_name or "tpu_custom_call" in event_name)


#: Share of the turns run for which the traced window must hold the
#: kernel's event (one a turn) before its events and the nested ops beside
#: them are read: the profiler drops nested events from a long trace (a
#: 512-turn trace kept 98% of them; one machine's kept about two thirds),
#: and a reading from a trace that lost more would be low, not absent.
COMPLETE = 0.95


def kernel_events_complete(ctx) -> bool:
    """The trace holds the dispatch kernel's event for (nearly) every turn
    the traced window ran."""
    turns = ctx["host"]["turns"]
    seen = ctx["trace"].op_count(is_ppot_alias_kernel)
    return turns > 0 and COMPLETE * turns <= seen <= turns


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def ppot_alias_cost(B: int, n: int) -> tuple[float, float]:
    """(operations, bytes) of one fused alias PPoT-SQ(2) dispatch of B
    tasks over n workers: read the alias table (prob f32[n], alias i32[n]),
    the queue view (i32[n]) and four uniforms per task (f32[4, B]); write
    one worker per task (i32[B]) and the queue view back (i32[n]).  Per
    task and probe: scale and truncate the bin draw, one table compare and
    select (4 ops); per task: compare the two queue lengths and select
    (2 ops), and add one to the chosen worker's count (1 op)."""
    ops = B * (2 * 4 + 2 + 1)
    nbytes = 4 * (3 * n + 4 * B) + 4 * (B + n)
    return float(ops), float(nbytes)


def least_time_s(ops: float, nbytes: float, device_kind: str) -> float:
    """The larger of the compute bound and the memory bound, in seconds;
    operations are held to the bf16 peak, the highest of the chip."""
    pk = peaks(device_kind)
    return max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
