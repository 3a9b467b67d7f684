"""Benchmark harness: one run of one cell, printed as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name:

* ``bench/workloads/<cell>.json``: its configuration, traffic mix, driver
  and chips;
* ``bench/configs/<config>.json``: the deployment (cluster, policy,
  capacities) and ``bench/limits/<config>.json``: the limits of the
  correctness comparison;
* ``bench/traffic/<traffic>.json``: the parameters of the arrival stream;
* ``bench/drivers/<driver>.py``: what feeds the system under test;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric of
  ``BENCHMARK.json``.

A run sets up (imports, device, compile cache, one warm call at the cell's
shape), then measures for ``--seconds`` of wall time, then judges what the
timed window produced against the plain reference (``bench/reference.py``).
With ``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it records a profiler trace of the window and reports the
per-layer metrics.  The last line of standard output is the result; the
numbers compared for ``correct`` are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: Fixed, inside the checkout: the path is part of the compile cache's key.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
OUT_DIR = ROOT / ".bench_cache" / "out"


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names loaded."""

    def __init__(self, name: str, bench_dir: pathlib.Path = BENCH):
        if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name):
            raise ValueError(f"not a cell name: {name!r}")
        self.name = name
        self.spec = load_json(bench_dir / "workloads" / f"{name}.json")
        self.config_name = self.spec["config"]
        self.config = load_json(bench_dir / "configs" / f"{self.config_name}.json")
        self.limits = load_json(bench_dir / "limits" / f"{self.config_name}.json")
        self.traffic_name = self.spec["traffic"]
        self.traffic = load_json(bench_dir / "traffic" / f"{self.traffic_name}.json")
        self.chips = int(self.spec["chips"])
        self.driver = load_module(bench_dir / "drivers" / f"{self.spec['driver']}.py")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics of ``BENCHMARK.json`` that this cell reports in this
    kind of run: end-to-end ones untraced, per-layer ones traced."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when none exceeds
    its limit (a missing number is a failure)."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def run(argv=None, *, require_chip: bool = True, bench_dir=BENCH,
        compile_cache: bool = True) -> dict | None:
    """One benchmark run; returns the result object (None when the run
    cannot be made).  ``require_chip=False`` and ``compile_cache=False``
    let a test drive the rest of a run on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_json = load_json(bench_dir.parent / "BENCHMARK.json")
    cell = Cell(args.workload, bench_dir)

    import jax

    if compile_cache:
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    devices = devices[:cell.chips]

    compiles: list[float] = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    drv = cell.driver.Driver(cell, args.seed)
    drv.setup()
    setup_s = time.perf_counter() - T_START
    n_setup_compiles = len(compiles)

    tracer = None
    if args.trace:
        from bench import trace as btr

        tracer = btr.Tracer(OUT_DIR / f"trace-{cell.name}")
    host = drv.window(args.seconds, tracer)
    print(f"bench: {len(compiles) - n_setup_compiles} programs compiled or "
          f"loaded from the compile cache in the window, {n_setup_compiles} "
          f"in set-up", file=sys.stderr)
    dev = device_info(devices)
    metrics = {}
    breakdown = None
    if args.trace:
        summary = tracer.summary()
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = {"trace": summary, "host": host, "cell": cell,
               "device_kind": dev["kind"]}
        for m in cell_metrics(bench_json, cell.name, True):
            reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(host["metrics"], setup_s=setup_s)
        for m in cell_metrics(bench_json, cell.name, False):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    drv.release()
    numbers = drv.check()
    ok, checks = judge(numbers, cell.limits)
    result = {"correct": ok, "attempted": host["attempted"],
              "failed": int(numbers.get("misplaced", host["attempted"])),
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    result = run(argv)
    if result is None:
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
