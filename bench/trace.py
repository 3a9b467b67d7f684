"""Profiler trace of a window, and its reduction to device time.

``Tracer`` records a JAX profiler trace (no Python tracer; host annotations
only) of the traced window and marks the window and every chunk generation
with host spans on the trace's clock (``bench_window``, ``bench_gen``).
``reduce_planes`` turns the planes of an ``.xplane.pb`` into a ``Summary``:
per device, the intervals in which an operation ran (the ``XLA Ops`` line
of each ``/device:TPU:<i>`` plane), clipped to the window.

Operations of one program can be nested on the ops line (a fusion inside a
while loop's body, a kernel inside a custom call); busy time is the union
of their intervals, never their sum.
"""
from __future__ import annotations

import pathlib
import re
import shutil

import numpy as np

WINDOW_SPAN = "bench_window"
GEN_SPAN = "bench_gen"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
_SUFFIX = re.compile(r"[.\-_]?\d+$")


def op_name(event_name: str) -> str:
    """The HLO instruction's name: TPU traces name an op event by its whole
    instruction text, ``%while.42 = (u32[], ...) while(...)``."""
    head = event_name.split(" = ", 1)[0] if " = " in event_name else event_name
    return head.strip().lstrip("%")


def op_kind(name: str) -> str:
    """An op's name without its instance number: ``fusion.12`` -> ``fusion``."""
    name = op_name(name)
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name or prev


def union_length(start: np.ndarray, end: np.ndarray) -> float:
    """Total length covered by the intervals [start, end)."""
    if start.size == 0:
        return 0.0
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    # a new run starts where an interval begins after everything before it
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    run_id = np.cumsum(new) - 1
    run_start = s[new]
    run_end = np.zeros(run_start.size)
    np.maximum.at(run_end, run_id, e)
    return float(np.sum(run_end - run_start))


def gaps(start: np.ndarray, end: np.ndarray, lo: float, hi: float):
    """The uncovered intervals of [lo, hi) as (gap_start, gap_end) arrays."""
    if start.size == 0:
        return np.array([lo]), np.array([hi])
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    g_start = np.concatenate([[lo], reach])
    g_end = np.concatenate([s, [hi]])
    keep = g_end > g_start
    return g_start[keep], g_end[keep]


class Device:
    """One device's operations inside the window (ns on the trace clock)."""

    def __init__(self, name, names, start, end):
        self.name = name
        self.names = np.asarray(names, dtype=object)
        self.start = np.asarray(start, float)
        self.end = np.asarray(end, float)

    def select(self, pred) -> np.ndarray:
        kinds = {n: pred(n) for n in set(self.names.tolist())}
        return np.fromiter((kinds[n] for n in self.names), bool,
                           self.names.size)

    def busy_ns(self, mask=None) -> float:
        if mask is None:
            return union_length(self.start, self.end)
        return union_length(self.start[mask], self.end[mask])


class Summary:
    def __init__(self, devices, window, host_spans):
        self.devices = devices
        self.lo, self.hi = window
        self.host_spans = host_spans  # list of (name, start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return float(np.mean([d.busy_ns() for d in self.devices])) * 1e-9

    def op_seconds(self, pred) -> float:
        """Mean over devices of the union of the matching ops' intervals."""
        return float(np.mean([d.busy_ns(d.select(pred))
                              for d in self.devices])) * 1e-9

    def op_count(self, pred) -> float:
        return float(np.mean([int(d.select(pred).sum())
                              for d in self.devices]))

    def breakdown(self, top: int = 10) -> dict:
        """Device 0's ops by instruction (a loop's time includes the ops
        nested in it) and its longest idle gaps by what the host was
        doing."""
        d = self.devices[0]
        totals: dict[str, float] = {}
        for n, s, e in zip(d.names, d.start, d.end):
            k = op_name(n)
            totals[k] = totals.get(k, 0.0) + (e - s)
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        gs, ge = gaps(d.start, d.end, self.lo, self.hi)
        order = np.argsort(gs - ge)[:top]
        idle = [[self.host_doing(0.5 * (gs[i] + ge[i])),
                 float(ge[i] - gs[i]) * 1e-9] for i in order]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": idle}

    def host_doing(self, t: float) -> str:
        """The innermost host span open at ``t`` (outside the window span
        itself, the benchmark's own driver code)."""
        best, best_len = "host", None
        for name, s, e in self.host_spans:
            if name == WINDOW_SPAN or not (s <= t < e):
                continue
            if best_len is None or e - s < best_len:
                best, best_len = name, e - s
        return best


def reduce_planes(planes) -> Summary:
    """``planes``: what ``jax.profiler.ProfileData`` gives (each with
    ``name`` and ``lines``; each line with ``name`` and ``events``; each
    event with ``name``, ``start_ns`` and ``duration_ns``)."""
    host_spans = []
    dev_raw = []
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX):
            for line in p.lines:
                if line.name == OPS_LINE:
                    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
                    dev_raw.append((p.name, ev))
        elif p.name.startswith("/host:"):
            for line in p.lines:
                for e in line.events:
                    host_spans.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns))
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0]
    devices = []
    for name, ev in sorted(dev_raw, key=lambda x: int(x[0][len(DEVICE_PREFIX):])):
        keep = [(n, max(s, lo), min(e, hi)) for n, s, e in ev if e > lo and s < hi]
        names = [k[0] for k in keep]
        devices.append(Device(name, names, [k[1] for k in keep],
                              [k[2] for k in keep]))
    if not devices:
        raise ValueError("trace holds no device operations")
    return Summary(devices, (lo, hi), host_spans)


class Tracer:
    """Starts and stops the profiler around the traced window."""

    def __init__(self, out_dir):
        self.dir = pathlib.Path(out_dir)
        self._window = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def gen(self):
        import jax

        return jax.profiler.TraceAnnotation(GEN_SPAN)

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> Summary:
        from jax.profiler import ProfileData

        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no trace written under {self.dir}")
        return reduce_planes(ProfileData.from_file(str(files[-1])).planes)
