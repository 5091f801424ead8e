#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, driver and per-layer metrics are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up
builds the network on the device from the seed, makes every request of the
window, and warms every shape the traffic uses; then the driver measures for
``--seconds``.  With ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are read from the trace and the program's counters;
with ``--trace 0`` the end-to-end metrics are printed.  After the window the
served answers are compared with the plain reference (``bench/reference.py``)
and each compared number is printed beside its limit, on standard error and
last in the result line.

The run exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for: it never falls back to the CPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(REPO_DIR, "src"))

import harness  # noqa: E402
import work  # noqa: E402

#: a traced run measures a window of at most this many seconds: tracing
#: slows the host, and reading a longer trace would not end in time
TRACE_SECONDS = 5.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def find_devices(chips: int):
    """The chips this cell runs on, or None (with the reason said) when JAX
    finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        say(f"JAX found no TPU (platform {devs[0].platform!r}); no result")
        return None
    if len(devs) < chips:
        say(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
        return None
    return devs[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def profile(trace_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(trace_dir, profiler_options=opts)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, driver_factory=None,
             keep_trace: str = None) -> dict:
    """Set up, measure, compare.  Returns the pieces of the result line."""
    import jax

    from repro.launch.env import enable_compilation_cache

    cache = enable_compilation_cache()
    say(f"cell {cell.name}: {devices[0].device_kind} x{len(devices)}, "
        f"compile cache {cache}")
    peak = work.peaks(devices[0].device_kind)
    compiles = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: compiles.update(
            [name] if name in COMPILE_EVENTS else []))
    if driver_factory is None:
        mod = harness.load_module(cell.driver_path, "bench_driver")
        driver_factory = mod.Driver
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    drv = driver_factory(cell, seed, seconds, trace)
    say(f"set-up s: start to the driver {time.perf_counter() - t_start:.3f}")
    drv.setup()
    # the requests made for the window are the client's, held for the whole
    # run: keep the collector from walking them in every full collection
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    before = dict(compiles)
    pauses = []
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        elif info["generation"] == 2:
            pauses.append(time.perf_counter() - gc_t0[0])

    gc.callbacks.append(on_gc)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            with profile(trace_dir):
                with jax.profiler.TraceAnnotation("bench.window"):
                    res = drv.window()
        else:
            res = drv.window()
        in_window = {k: compiles[k] - before.get(k, 0) for k in COMPILE_EVENTS}
        print(f"window compiles: backend={in_window[COMPILE_EVENTS[0]]} "
              f"traces={in_window[COMPILE_EVENTS[1]]}", flush=True)
        longest = sorted(res["record"].get("call_s", []), reverse=True)[:5]
        print(f"window stalls: longest calls ms "
              f"{[round(x * 1e3, 3) for x in longest]}; full collections "
              f"{len(pauses)}, longest ms "
              f"{round(max(pauses, default=0.0) * 1e3, 3)}", flush=True)
        mem = memory_peak(devices)
        drv.release()
        t_ref = time.perf_counter()
        checks = drv.checks()
        say(f"reference s: {time.perf_counter() - t_ref:.3f}")
        rec = dict(res["record"], window_s=res["window_s"], chips=len(devices),
                   peaks=peak, topology=list(cell.config["topology"]),
                   workload=cell.name, config=cell.config,
                   traffic=cell.traffic, trace=None)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": mem}
        breakdown = None
        if trace:
            import trace_reduce

            xplane = trace_reduce.find_xplane(trace_dir)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    keep_trace, f"{cell.name}.xplane.pb"))
            red = trace_reduce.reduce(xplane, n_chips=len(devices))
            rec["trace"] = red
            metrics = harness.read_per_layer(cell, rec)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["top_gaps"]}
        else:
            metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] != "setup_s"}
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    finally:
        gc.callbacks.remove(on_gc)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    correct = all(c.ok for c in checks) and res["failed"] == 0
    return {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device,
            "checks": checks, "breakdown": breakdown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: keep the profiler trace in DIR")
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    import repro.launch.env  # noqa: F401  the program under test must exist
    devices = find_devices(cell.chips)
    if devices is None:
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   keep_trace=args.keep_trace)
    harness.print_checks(out["checks"])
    print(harness.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=out["metrics"], device=out["device"],
        checks=out["checks"], breakdown=out["breakdown"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
