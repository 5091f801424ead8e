#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop LM cell, on the chip, in one process.

    python3 bench/lm_sweep.py --workload granite_h_chat_p80 --rates 2,4,6 \
        --seconds 20 --seed 1

The engine is built and warmed once; each rate then gets its own seeded
arrivals and prompts (the lengths replay the traffic file's
``length_seed``) and runs the cell's driver loop for ``--seconds`` of
arrivals.  Per rate it prints one JSON line: offered and completed rate,
p50, p90 and p99 latency, ``drain_s`` (last answer after the last arrival),
the queue wait for a slot (p90 and max), the mean number of live slots per
decode step and the mean step time.  A rate is sustained when requests do
not wait for slots more and more over the hold: the queue wait stays a few
decode steps long instead of growing with the window.  The cell's traffic
file takes 0.8 x the highest sustained rate.
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

import harness  # noqa: E402
from run import find_devices  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    if cell.traffic["driver"] != "lm_open_loop":
        print("lm_sweep: only LM open-loop cells", file=sys.stderr)
        return 2
    if find_devices(cell.chips) is None:
        return 2
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    drv_mod = harness.load_module(cell.driver_path, "bench_driver")
    drv = drv_mod.Driver(cell, args.seed, args.seconds, False)
    drv.setup()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        drv.make_requests(rate, args.seed + 1 + k)
        before = drv.engine.stats()
        gc.collect()
        gc.freeze()
        t0, _lags, calls = drv_mod.drive(drv.engine, drv.objs, drv.arrivals)
        done = np.array([r.done_at for r in drv.objs], np.float64)
        lat = (done - (t0 + drv.arrivals)) * 1e3
        t_end = float(np.max(done)) - t0
        st = drv.engine.stats()
        waits = st["queue_wait_s"][before["queue_wait_s"].size:] * 1e3
        steps = st["decode_steps"] - before["decode_steps"]
        gc.unfreeze()
        print(json.dumps({
            "rate_hz": rate, "n": len(drv.arrivals),
            "completed_per_s": len(drv.arrivals) / t_end,
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "p99_ms": float(np.percentile(lat, 99)),
            "drain_s": t_end - float(drv.arrivals[-1]),
            "queue_wait_p90_ms": float(np.percentile(waits, 90)),
            "queue_wait_max_ms": float(np.max(waits)),
            "live_slots_mean": (st["decode_tokens"] - before["decode_tokens"]) / steps,
            "step_ms_mean": 1e3 * float(np.sum(calls)) / steps}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
