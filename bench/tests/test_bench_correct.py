"""The comparison that decides ``correct``, on the CPU at a test size:
sound runs pass it and the control (the reference in bfloat16 in the
program's place) fails it."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_cpu  # noqa: E402

CELLS = ["if_static_p80", "lif_events_p80", "if_learn_stdp"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(monkeypatch, name):
    out = bench_cpu.run_small(monkeypatch, name)
    assert out["correct"], [(c.name, c.value, c.limit) for c in out["checks"]]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(monkeypatch, name):
    cell = bench_cpu.small_cell(name)
    real = bench_cpu.work.peaks
    monkeypatch.setattr(bench_cpu.work, "peaks",
                        lambda kind, path=None: real("TPU v5 lite"))
    drv = bench_cpu.driver_class(name)(cell, bench_cpu.SEED,
                                       bench_cpu.SECONDS, False)
    drv.setup()
    drv.window()
    drv.release()
    assert all(c.ok for c in drv.checks())
    failed = [c.name for c in drv.control() if not c.ok]
    assert failed, name
