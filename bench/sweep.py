#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell, on the chip, in one process.

    python3 bench/sweep.py --workload if_static_p80 --rates 4000,8000,16000 \
        --seconds 4 --seed 1

One engine is built and warmed once; each rate then gets its own seeded
requests and arrivals and runs the cell's open-loop driver for ``--seconds``.
Per rate it prints one JSON line: offered and completed rate, p50, p90 and
p99 latency, and ``drain_s``, how long after the last arrival the last answer
came.  A rate is sustained when the backlog does not grow over the window:
``drain_s`` stays within a few serve cycles instead of growing with the
window.  The cell's traffic file takes 0.8 x the highest sustained rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import numpy as np  # noqa: E402

import gen  # noqa: E402
import harness  # noqa: E402
import serving  # noqa: E402
from run import find_devices  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    if cell.traffic["driver"] != "open_loop":
        print("sweep: only open-loop cells have an offered rate",
              file=sys.stderr)
        return 2
    if find_devices(cell.chips) is None:
        return 2
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    drv_mod = harness.load_module(cell.driver_path, "bench_driver")
    drv = drv_mod.Driver(cell, args.seed, args.seconds, False)
    drv.setup()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = args.seed + 1 + k
        arr = gen.poisson_arrivals(rate, args.seconds, seed)
        reqs = serving.Requests(len(arr), cell.traffic, seed,
                                bool(cell.config["engine"]["telemetry"]))
        drv.engine.warmup(event_ts=reqs.event_ts)
        gc.collect()
        gc.freeze()
        t0, lags, _ = drv_mod.drive(drv.engine, reqs.objs, arr)
        done = np.array([r.done_at for r in reqs.objs], np.float64)
        lat = (done - (t0 + arr)) * 1e3
        t_end = float(np.max(done)) - t0
        del reqs
        gc.unfreeze()
        print(json.dumps({
            "rate_hz": rate, "n": len(arr),
            "completed_per_s": len(arr) / t_end,
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "p99_ms": float(np.percentile(lat, 99)),
            "drain_s": t_end - float(arr[-1]),
            "wake_lag_p99_ms": float(np.percentile(lags, 99) * 1e3)
            if lags.size else None}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
