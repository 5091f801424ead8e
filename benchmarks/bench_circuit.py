"""Paper Fig 6 + Fig 7: circuit-level access time/energy vs cell option.

This is the calibrated-constants plane (DESIGN.md §2a): the bench emits the
cost-model tables, verifies the paper's stated circuit-level relationships
hold in the model (Vprech saving >=43%, per-port energy minimum before the
4th port, write costs growing with ports), and records the rows to
``BENCH_circuit.json`` (override with env BENCH_CIRCUIT_OUT) so the
calibration trajectory is tracked across PRs."""

from __future__ import annotations

import os
import sys

try:
    from benchmarks.common import Recorder
except ModuleNotFoundError:  # direct `python benchmarks/bench_circuit.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder
from repro.core.esam import cost_model as cm


def run():
    rec = Recorder()
    # Fig 6 analogue: transposed-port write/read energy+time per cell option
    for p in range(5):
        spec = cm.cell_spec(p)
        rec.emit(
            f"fig6_cell_{spec.name}",
            0.0,
            f"tread_pj={spec.e_tread_pj:.3f};twrite_pj={spec.e_write_pj:.3f};"
            f"clock_ns={spec.clock_ns:.2f}",
        )
    # Fig 7 analogue: per-port inference read energy at Vprech=500mV
    for p in range(1, 5):
        spec = cm.cell_spec(p)
        drain = -(-128 // spec.ports)
        access_ns = drain * spec.clock_ns
        rec.emit(
            f"fig7_ports_{p}",
            0.0,
            f"read_pj_per_access={spec.e_read_pj:.3f};"
            f"array_drain_ns={access_ns:.1f}",
        )
    # paper-stated relationships
    assert cm.E_READ_PORT_PJ[0] < cm.E_READ_1RW_PJ * (1 - cm.VPRECH_ENERGY_SAVING) + 0.02
    assert cm.E_READ_PORT_PJ[3] > cm.E_READ_PORT_PJ[2]      # 4th port turns upward
    assert all(a < b for a, b in zip(cm.E_WRITE_PORT_PJ, cm.E_WRITE_PORT_PJ[1:]))
    rec.emit("fig7_vprech_saving", 0.0,
             f"saving>=43%:ok;time_penalty<=19%:{cm.VPRECH_TIME_PENALTY <= 0.19}")
    rec.write_json(os.environ.get("BENCH_CIRCUIT_OUT", "BENCH_circuit.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
