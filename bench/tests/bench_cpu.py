"""Running a cell on the CPU for the tests: the harness's look for a chip
is skipped, the v5e peaks stand in for the CPU's, and the cell's traffic is
shrunk to what a test run can hold."""

import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

SMALL = {
    "if_static_p80": {"rate_hz": 300.0},
    "lif_events_p80": {"rate_hz": 80.0},
    "if_learn_stdp": {"chunk": 128, "pool_samples_per_s": 400},
}
SECONDS = 0.5
SEED = 2**33 + 12345


def small_cell(name: str):
    cell = harness.resolve(name)
    cell.traffic.update(SMALL[name])
    return cell


def run_small(monkeypatch, name: str, *, driver_factory=None, seed=SEED):
    import jax

    real = work.peaks
    monkeypatch.setattr(work, "peaks",
                        lambda kind, path=work.PEAKS_FILE:
                        real("TPU v5 lite", path))
    cell = small_cell(name)
    return run.run_cell(cell, seed, SECONDS, False, jax.devices()[:1],
                        t_start=time.perf_counter(),
                        driver_factory=driver_factory)


def driver_class(name: str):
    cell = small_cell(name)
    return harness.load_module(cell.driver_path,
                               "bench_driver_" + cell.traffic["driver"]).Driver
