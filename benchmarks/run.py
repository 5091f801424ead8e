"""Benchmark harness entry point — one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV rows.  Mapping to the paper:
  bench_circuit          Fig 6 + Fig 7   (cell-level time/energy)
  bench_timing           Table 2         (pipeline stages / clock)
  bench_online_learning  Sec 4.4.1       (26.0x / 19.5x column access)
  bench_system           Fig 8           (port sweep; 3.1x / 2.2x headline)
  bench_comparison       Table 3         (44 MInf/s, 607 pJ/Inf, 29 mW)
  bench_accuracy         Sec 4.4.2       (BNN->SNN conversion, V3)
  bench_temporal         (temporal plane) fused LIF scan vs naive loop,
                                          event-stream rates, encoders
  bench_faults           (robustness)    accuracy vs fault rate, spare-column
                                          remap, STDP repair, energy
  bench_roofline         (framework)     dry-run roofline per arch x shape
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_accuracy,
        bench_circuit,
        bench_comparison,
        bench_faults,
        bench_online_learning,
        bench_roofline,
        bench_spiking_lm,
        bench_system,
        bench_temporal,
        bench_timing,
    )

    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_circuit, bench_timing, bench_online_learning, bench_system,
                bench_comparison, bench_accuracy, bench_temporal,
                bench_faults, bench_spiking_lm, bench_roofline):
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod.__name__},0.0,FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    main()
