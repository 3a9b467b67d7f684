"""The program's profiler names, and what a traced window says of them.

The program names two layers (``repro.obs.tracing``; this file imports
nothing of the program, so it lists the names itself, and a test holds the
two lists equal):

* device stages: each stage of a scan turn runs under a
  ``jax.named_scope("rosella.<stage>")``, which lands in the ``op_name``
  metadata of every HLO instruction the stage lowers to.  An op belongs to
  the innermost ``rosella.<stage>`` of its ``op_name``; an op outside every
  scope (the turn loop itself, the unscoped glue) belongs to no stage.
  The ``op_name`` is read from the op event's HLO text where the text
  carries its metadata.  A TPU trace names an op event by its instruction
  text without the metadata, and its events' stats hold only offsets, so
  there the ``op_name`` comes from the compiled module's HLO proto that the
  profiler stores in the same trace (the ``/host:metadata`` plane), found
  by the module the op runs in (the device's ``XLA Modules`` line) and the
  instruction's name;
* host phases: each call of the chunk driver is a ``rosella.call`` span
  holding disjoint phase spans ``rosella.<phase>``.

``stage_us`` is a stage's device time per turn: the union of its ops'
intervals over the window, over the turns run.  ``idle_phase_share`` is
the share of the window in which no op runs on the device while the host
is inside one phase: the intersection of the device's idle intervals with
that phase's spans, never a guess from a gap's midpoint.  Both return
None on a trace that holds none of the program's names (a program that
does not emit them).
"""
from __future__ import annotations

import pathlib
import re

import numpy as np

from bench import kernels
from bench.trace import DEVICE_PREFIX, gaps, op_name

PREFIX = "rosella."
STAGES = ("flush", "learner_fold", "alias_build", "dispatch", "pool_chain",
          "pending_append", "telemetry_fold")
CALL = PREFIX + "call"
PHASES = ("next_chunk", "h2d", "launch", "fence", "readback")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(PREFIX) + r"([A-Za-z0-9_]+)")


def scope_stage(name: str | None) -> str | None:
    """The innermost stage scope of an ``op_name``."""
    if not name:
        return None
    found = [s for s in _SCOPE.findall(name) if s in STAGES]
    return found[-1] if found else None


def stage_of(event_name: str) -> str | None:
    """The stage of an op event whose HLO text carries its metadata."""
    m = _OP_NAME.search(event_name)
    return scope_stage(m.group(1)) if m else None


# -- the trace's own HLO protos ----------------------------------------------


def _varint(b, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a number, a memoryview for a length-delimited
    field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not read")
        yield key >> 3, v


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _instruction_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of one ``HloProto`` (hlo_module 1;
    its computations 3; their instructions 2; an instruction's name 1 and
    metadata 7, whose op_name is 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = op = None
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op = next((_text(x) for q, x in _fields(v) if q == 2),
                                  None)
                if name is not None and op:
                    out[name] = op
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Per compiled module (by its name in the trace, ``jit_run(7)``), the
    ``op_name`` of each instruction, from the ``Hlo Proto`` stats of the
    ``/host:metadata`` plane of a serialized ``XSpace`` (planes 1; a
    plane's name 2, event metadata 4 and stat metadata 5; an event
    metadata's name 2 and stats 5; a stat's metadata id 1 and bytes 6)."""
    view = memoryview(xspace)
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(view):
        if f != 1:
            continue
        events, stat_names, name = [], {}, None
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
                if name != "/host:metadata":
                    break
            elif g == 4:
                events.append(v)
            elif g == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1)] = _text(meta.get(2, b""))
        if name != "/host:metadata":
            continue
        for entry in events:
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            module = next((_text(v) for g, v in meta if g == 2), "")
            for g, v in meta:
                if g != 5:
                    continue
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                    out[module] = _instruction_op_names(stat[6])
    return out


def module_spans(path) -> dict[str, tuple]:
    """Per device plane, the ``XLA Modules`` line: (starts, ends, names)
    of the programs run, sorted by start."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            ev = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
            out[plane.name] = (np.array([e[0] for e in ev], float),
                               np.array([e[1] for e in ev], float),
                               [e[2] for e in ev])
    return out


def _module_table(protos: dict, module: str) -> dict[str, str]:
    if module in protos:
        return protos[module]
    base = module.split("(", 1)[0]
    same = [t for m, t in protos.items() if m.split("(", 1)[0] == base]
    return same[0] if len(same) == 1 else {}


def op_stages(trace, protos: dict, modules: dict) -> list[np.ndarray]:
    """Per device of ``trace``, the stage of each op event: its module is
    the program running on that device when the op starts, its
    ``op_name`` that module's entry for the instruction it names."""
    out = []
    for d in trace.devices:
        starts, ends, names = modules.get(d.name, (np.zeros(0),) * 2 + ([],))
        idx = np.searchsorted(starts, d.start, side="right") - 1
        memo: dict = {}
        labels = np.empty(d.names.size, dtype=object)
        for j, (name, m) in enumerate(zip(d.names, idx)):
            key = (name, m)
            if key not in memo:
                inside = m >= 0 and d.start[j] < ends[m]
                table = _module_table(protos, names[m]) if inside else {}
                memo[key] = scope_stage(table.get(op_name(name)))
            labels[j] = memo[key]
        out.append(labels)
    return out


def trace_file(ctx) -> pathlib.Path | None:
    """The traced window's ``.xplane.pb`` (where ``bench/run.py`` has its
    tracer write it)."""
    from bench import run as br

    root = br.OUT_DIR / f"trace-{ctx['cell'].name}"
    files = sorted(root.glob("plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def stage_labels(ctx) -> list[np.ndarray]:
    """Per device, the stage of each op event of the window (None where
    it has none); computed once per traced run."""
    if "stage_labels" not in ctx:
        tr = ctx["trace"]
        unique = [set(d.names.tolist()) for d in tr.devices]
        if any("op_name=" in n for names in unique for n in names):
            labels = []
            for d, names in zip(tr.devices, unique):
                table = {n: stage_of(n) for n in names}
                labels.append(np.array([table[n] for n in d.names],
                                       dtype=object))
        else:
            path = trace_file(ctx) if "cell" in ctx else None
            protos = hlo_op_names(path.read_bytes()) if path else {}
            modules = module_spans(path) if protos else {}
            labels = op_stages(tr, protos, modules)
        ctx["stage_labels"] = labels
    return ctx["stage_labels"]


def stage_seconds(ctx, stage: str) -> float:
    """Mean over devices of the union of the stage's op intervals."""
    tr = ctx["trace"]
    return float(np.mean([
        d.busy_ns(labels == stage)
        for d, labels in zip(tr.devices, stage_labels(ctx))])) * 1e-9


def stage_us(ctx, stage: str) -> float | None:
    """Device time of one stage per turn run (microseconds); absent where
    the trace dropped nested events (``kernels.kernel_events_complete``)
    or holds no stage scope."""
    turns = ctx["host"]["turns"]
    if not turns or not kernels.kernel_events_complete(ctx):
        return None
    if not any(labels.astype(bool).any() for labels in stage_labels(ctx)):
        return None
    return 1e6 * stage_seconds(ctx, stage) / turns


def merge(start, end) -> tuple[np.ndarray, np.ndarray]:
    """The union of intervals [start, end) as sorted disjoint intervals."""
    start = np.asarray(start, float)
    end = np.asarray(end, float)
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    run_id = np.cumsum(new) - 1
    run_end = np.zeros(int(new.sum()))
    np.maximum.at(run_end, run_id, e)
    return s[new], run_end


def overlap(a_start, a_end, b_start, b_end) -> float:
    """Length of the intersection of two sets of sorted disjoint
    intervals."""
    a_start = np.asarray(a_start, float)
    a_end = np.asarray(a_end, float)
    if a_start.size == 0 or np.size(b_start) == 0:
        return 0.0
    cum = np.concatenate([[0.0], np.cumsum(a_end - a_start)])

    def covered(t):  # length of A in (-inf, t]
        i = np.searchsorted(a_start, t, side="right")
        j = np.maximum(i - 1, 0)
        part = np.clip(np.minimum(t, a_end[j]) - a_start[j], 0.0, None)
        return np.where(i > 0, cum[j] + part, 0.0)

    return float(np.sum(covered(np.asarray(b_end, float))
                        - covered(np.asarray(b_start, float))))


def phase_spans(trace, phase: str) -> tuple[np.ndarray, np.ndarray]:
    """A phase's host spans clipped to the window, merged."""
    name = PREFIX + phase
    iv = [(max(s, trace.lo), min(e, trace.hi))
          for n, s, e in trace.host_spans
          if n == name and e > trace.lo and s < trace.hi]
    return merge([s for s, _ in iv], [e for _, e in iv])


def idle_phase_share(ctx, phase: str) -> float | None:
    """Share of the window (%) in which the device runs no op while the
    host is inside ``phase``; mean over the chips.  Absent where the trace
    holds no ``rosella.call`` span."""
    tr = ctx["trace"]
    if not any(n == CALL for n, _, _ in tr.host_spans):
        return None
    ps, pe = phase_spans(tr, phase)
    shares = []
    for d in tr.devices:
        gs, ge = gaps(d.start, d.end, tr.lo, tr.hi)
        shares.append(overlap(gs, ge, ps, pe) / (tr.hi - tr.lo))
    return 100.0 * float(np.mean(shares))
