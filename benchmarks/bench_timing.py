"""Paper Table 2: pipeline stage durations + clock period per cell option,
plus the arbiter critical-path claim (tree vs flat, Sec 3.3)."""

from __future__ import annotations

from benchmarks.common import emit
from repro.core.esam import cost_model as cm


def run():
    for p in range(5):
        spec = cm.cell_spec(p)
        bottleneck = "arbiter" if spec.arbiter_ns >= spec.sram_neuron_ns else "sram+neuron"
        emit(
            f"table2_{spec.name}",
            0.0,
            f"arbiter_ns={spec.arbiter_ns};sram_neuron_ns={spec.sram_neuron_ns};"
            f"clock_ns={spec.clock_ns};bottleneck={bottleneck}",
        )
    # 4R system clock ~ published 810 MHz
    emit("table2_clock_check", 0.0,
         f"clock_mhz={cm.cell_spec(4).clock_hz/1e6:.0f};paper=810")
    # Sec 3.3: the 128-wide 4-port arbiter's tree decomposition
    emit("table2_arbiter_tree_128x4", 0.0,
         f"tree_path_ps={cm.ARBITER_TREE_CRITICAL_PATH_PS};"
         f"flat_path_ps={cm.ARBITER_FLAT_CRITICAL_PATH_PS};"
         f"area_overhead={cm.ARBITER_TREE_AREA_OVERHEAD}")


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
