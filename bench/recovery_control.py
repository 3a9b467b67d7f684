"""The crash cell's control: the plain recovery reference put in the
system's place with its event clock one precision lower (float32 for the
configuration's float64), judged by the same comparison as the system.

    python3 bench/recovery_control.py --workload <cell> --turns <T> --seeds <s1,s2,...>

Prints, per seed, the numbers ``bench/run.py`` compares and whether the
control passes the limits (it must not), then one JSON line with all
readings.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, seed: int, turns: int, clock=np.float32) -> dict:
    from bench import recovery_reference as rref

    drv = cell.driver.Driver(cell, seed)
    times, costs, speeds = drv.stream().turns(turns)
    cols = rref.Columns(*drv.faults.columns(times[:, -1]))
    ctl = rref.simulate(drv.sem, drv.rc, drv.router_seed, times, costs,
                        speeds, cols, burst_cost=drv.burst_cost, clock=clock)
    out = rref.check(drv.sem, drv.rc, drv.router_seed, times, costs, speeds,
                     cols, ctl.answers, burst_cost=drv.burst_cost)
    out["overflow"] = ctl.pend_overflow + ctl.flush_overflow
    return out


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as br

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--turns", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = br.Cell(args.workload)
    rows = {}
    for s in args.seeds.split(","):
        r = readings(cell, int(s), args.turns)
        ok, _ = br.judge(r, cell.limits)
        print(f"control seed {s}: {r} passes_limits={ok}", flush=True)
        rows[s] = r
    print(json.dumps({"workload": args.workload, "turns": args.turns,
                      "control": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
