"""Device time of the faulty scan body's recovery stages, from a traced
window.

The faulty body (``scanloop._build_scan_faulty``) runs the plain body's
stages (``bench/stages.py``) and three of its own under
``jax.named_scope("rosella.<stage>")`` (``repro.obs.tracing``; this file
imports nothing of the program, so it lists the names itself and a test
holds the two lists equal): ``fault_apply`` (stall, kill and ghosting,
timeout), ``retry_select`` (the stale-ghost sweep and the retry selection)
and ``spec_select`` (the backup-copy selection and placement).  An op
belongs to the innermost scope of either list in its ``op_name``, read as
``bench/stages.py`` reads it.

The crash cell never runs the fused dispatch kernel (a masked dispatch
takes the jnp path), so the kernel cannot vouch for the trace here.  The
retry selection's sort runs once a turn: the trace is read only where the
sort instruction labelled ``retry_select`` that the trace holds most often
is there for at least ``kernels.COMPLETE`` of the turns run (the profiler
drops nested events from a long trace).
"""
from __future__ import annotations

import re

import numpy as np

from bench import kernels, stages
from bench.trace import op_kind, op_name

RECOVERY_STAGES = ("fault_apply", "retry_select", "spec_select")
SCOPES = stages.STAGES + RECOVERY_STAGES
VOUCH_STAGE, VOUCH_KIND = "retry_select", "sort"
_SCOPE = re.compile(re.escape(stages.PREFIX) + r"([A-Za-z0-9_]+)")


def scope_label(name: str | None) -> str | None:
    """The innermost scope of either list in an ``op_name``."""
    found = [s for s in _SCOPE.findall(name or "") if s in SCOPES]
    return found[-1] if found else None


def labels(ctx) -> list[np.ndarray]:
    """Per device, the scope of each op event of the window (None where it
    has none); computed once per traced run."""
    if "recovery_labels" in ctx:
        return ctx["recovery_labels"]
    tr = ctx["trace"]
    out = []
    if any("op_name=" in n for d in tr.devices for n in set(d.names.tolist())):
        for d in tr.devices:
            memo = {}
            for n in set(d.names.tolist()):
                m = stages._OP_NAME.search(n)
                memo[n] = scope_label(m.group(1) if m else None)
            out.append(np.array([memo[n] for n in d.names], dtype=object))
    else:
        path = stages.trace_file(ctx) if "cell" in ctx else None
        protos = stages.hlo_op_names(path.read_bytes()) if path else {}
        modules = stages.module_spans(path) if protos else {}
        for d in tr.devices:
            starts, ends, names = modules.get(d.name,
                                              (np.zeros(0),) * 2 + ([],))
            idx = np.searchsorted(starts, d.start, side="right") - 1
            memo: dict = {}
            lab = np.empty(d.names.size, dtype=object)
            for j, (name, m) in enumerate(zip(d.names, idx)):
                if (name, m) not in memo:
                    inside = m >= 0 and d.start[j] < ends[m]
                    table = (stages._module_table(protos, names[m])
                             if inside else {})
                    memo[name, m] = scope_label(table.get(op_name(name)))
                lab[j] = memo[name, m]
            out.append(lab)
    ctx["recovery_labels"] = out
    return out


def vouched(ctx) -> bool:
    """The retry selection's sort is in the trace for (nearly) every turn
    the window ran."""
    turns = ctx["host"]["turns"]
    counts = []
    for d, lab in zip(ctx["trace"].devices, labels(ctx)):
        per = {}
        for n in d.names[lab == VOUCH_STAGE]:
            if op_kind(n) == VOUCH_KIND:
                per[op_name(n)] = per.get(op_name(n), 0) + 1
        counts.append(max(per.values(), default=0))
    seen = float(np.mean(counts)) if counts else 0.0
    return turns > 0 and kernels.COMPLETE * turns <= seen <= turns


def stage_us(ctx, stage: str) -> float | None:
    """Device time of one stage per turn run (microseconds); absent where
    the trace is not vouched for."""
    turns = ctx["host"]["turns"]
    if not turns or not vouched(ctx):
        return None
    busy = [d.busy_ns(lab == stage)
            for d, lab in zip(ctx["trace"].devices, labels(ctx))]
    return 1e-3 * float(np.mean(busy)) / turns
