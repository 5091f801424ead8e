#!/usr/bin/env python3
"""Run one cell traced with the program's observability switched on, and
read the metrics that need it.

    python3 bench/obs_probe.py --workload <cell> --seed <n> --seconds <s>
        [--handle 0|1] [--keep-trace DIR]

The cell runs as ``bench/run.py --trace 1`` runs it (same set-up, window,
reference comparison and per-layer metrics), except that with ``--handle
1`` (the default) the program gets an ``Observability`` handle with a
``Tracer`` and a ``Registry``: a serving engine is built with it, and the
learning driver passes it to the window's ``train_online`` calls (not to
set-up).  After the window the registry's snapshot goes into the record's
``obs``, the trace's host spans are reduced (``bench/span_reduce.py``), and
these readers of ``bench/metrics/`` are read beside the cell's own:

    engine.queue_wait_p90_ms, engine.flush_us_per_req  (serving cells)
    train.compile_s_per_chunk                          (learning cells)

``--handle 0`` runs the same path with no handle: the on-cost of tracing is
the difference between the two at one seed.  One JSON line is printed last.
It exits non-zero, with no result line, when JAX finds no TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import harness  # noqa: E402
import run  # noqa: E402
import span_reduce  # noqa: E402

READERS = ("engine.queue_wait_p90_ms", "engine.flush_us_per_req",
           "train.compile_s_per_chunk")


def probe_driver(base, obs):
    """The cell's driver class, with ``obs`` handed to the program and the
    window's record kept on the instance."""

    class Probe(base):
        def setup(self):
            if obs is None:
                return super().setup()
            if hasattr(self, "train"):   # learning: the window's calls only
                super().setup()
                self.train = functools.partial(self.train,
                                               observability=obs)
                return None
            import repro.serve.engine as engine_mod

            real = engine_mod.SpikeEngine
            engine_mod.SpikeEngine = functools.partial(real,
                                                       observability=obs)
            try:
                return super().setup()
            finally:
                engine_mod.SpikeEngine = real

        def window(self):
            res = super().window()
            if obs is not None:
                res["record"]["obs"] = obs.metrics.snapshot()
            self.result = res
            return res

    return Probe


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--handle", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default=None, metavar="DIR")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    devices = run.find_devices(cell.chips)
    if devices is None:
        return 2
    from repro.obs import Observability, Registry, Tracer

    obs = (Observability(tracer=Tracer(), metrics=Registry())
           if args.handle else None)
    base = harness.load_module(cell.driver_path, "bench_driver").Driver
    made = []

    def factory(*a):
        made.append(probe_driver(base, obs)(*a))
        return made[-1]

    keep = args.keep_trace or tempfile.mkdtemp(prefix="obs_probe_")
    out = run.run_cell(cell, args.seed, args.seconds, True, devices,
                       t_start=T_START, driver_factory=factory,
                       keep_trace=keep)
    red = span_reduce.reduce(os.path.join(keep, f"{cell.name}.xplane.pb"))
    if not args.keep_trace:
        shutil.rmtree(keep, ignore_errors=True)
    res = made[0].result
    rec = dict(res["record"], trace=red)
    metrics = dict(out["metrics"])
    for name in READERS:
        mod = harness.load_module(
            os.path.join(BENCH_DIR, "metrics", name + ".py"),
            "probe_" + name.replace(".", "_"))
        v = mod.read(rec)
        if v is not None:
            metrics[name] = {"value": float(v)}
    covered = {"engine_in_bench.serve": span_reduce.covered_share(
                   red, "bench.serve", "engine."),
               "train_in_bench.train_online": span_reduce.covered_share(
                   red, "bench.train_online", "train.")}
    harness.print_checks(out["checks"])
    print(json.dumps({
        "correct": out["correct"], "handle": bool(args.handle),
        "metrics": metrics, "end_to_end": res["end_to_end"],
        "device": out["device"], "breakdown": out["breakdown"],
        "spans": {k: red[k] for k in ("span_s", "span_calls",
                                      "idle_by_span", "top_gaps",
                                      "module_s")},
        "idle_covered": covered,
        "compiles": {k: v for k, v in (rec.get("obs") or {}).items()
                     if k.startswith("esam_compile")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
