#!/usr/bin/env python3
"""Readings that the comparison limits are set from, on the chip, in one
process: the program's sound runs and the control's.

    python3 bench/control.py --workload if_static_p80 --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 2

For each ``--seeds`` seed the cell is set up and measured as a benchmark run
does (a short window at the cell's own load) and its compared numbers are
printed: the lower readings.  For each ``--control-seeds`` seed the control
(the reference computed in bfloat16, put in the program's place) is compared
on the same requests: the upper readings.  One JSON line per reading.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import harness  # noqa: E402
from run import find_devices  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool,
             program: bool) -> list:
    drv_mod = harness.load_module(cell.driver_path, "bench_driver")
    drv = drv_mod.Driver(cell, seed, seconds, False)
    drv.setup()
    drv.window()
    drv.release()
    out = []
    if program:
        out.append(("program", drv.checks()))
    if control:
        out.append(("control", drv.control()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    if find_devices(cell.chips) is None:
        return 2
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    prog = [int(s) for s in args.seeds.split(",") if s]
    ctrl = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in sorted(set(prog) | set(ctrl)):
        for side, checks in readings(cell, seed, args.seconds,
                                     seed in ctrl, seed in prog):
            print(json.dumps({
                "workload": cell.name, "seed": seed, "side": side,
                "fails": [c.name for c in checks if not c.ok],
                "readings": {c.name: c.value for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
