"""Paper Table 3: the 1RW+4R system vs published SOTA, on BOTH the
calibration activity profile and the *measured* profile of a freshly trained
BNN (synthetic digits — DESIGN.md §8 notes the MNIST substitution).

Recorded to ``BENCH_comparison.json`` (override with env BENCH_COMPARISON_OUT)
so the Table 3 trajectory is tracked across PRs like the other benches.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_comparison.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core.esam import bnn, conversion, cost_model as cm
from repro.core.esam.network import reference_activity, system_stats
from repro.data import digits

PAPER_ROWS = {
    "wang_assc20[6]": "tech=65nm;power=305nW;acc=97.6;thr=2inf/s;energy=195nJ",
    "chen_jssc19[9]": "tech=10nm;power=196mW;acc=97.9;thr=6250inf/s;energy=1000nJ",
    "kim_fns18[10]": "tech=65nm;power=53mW;acc=97.2;thr=20inf/s;transposable=yes",
}


def run():
    rec = Recorder()
    for name, row in PAPER_ROWS.items():
        rec.emit(f"table3_{name}", 0.0, row)

    # --- reference profile (paper operating point) -------------------
    s4 = system_stats(cm.PAPER_TOPOLOGY, reference_activity(), 4)
    rec.emit("table3_thiswork_ref_profile", 0.0,
             f"tech=3nm;clock_mhz={cm.cell_spec(4).clock_hz/1e6:.0f};"
             f"throughput_minf_s={s4.throughput_inf_s/1e6:.1f}(paper 44);"
             f"energy_pj_inf={s4.energy_pj_per_inf:.0f}(paper 607);"
             f"power_mw={s4.power_mw:.1f}(paper 29.0);"
             f"neurons={cm.PAPER_NEURONS};synapses~{cm.PAPER_SYNAPSES}")

    # --- measured profile from a trained binary-SNN ------------------
    x, y = digits.make_spike_dataset(2048, seed=0)
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    params, _ = bnn.fit(jax.random.PRNGKey(0), cm.PAPER_TOPOLOGY, xj, yj,
                        steps=150, batch=128)
    net = conversion.bnn_to_snn(params)

    # ONE compiled plan serves accuracy and cost-model activity together:
    # telemetry loads are reductions on the same pass, no second forward.
    plan = net.plan(mode="functional", telemetry=True)

    def measured():
        res = plan(xj.astype(bool))
        return res.logits, [c[:512] for c in res.loads]

    us, (logits, counts) = time_call(measured, repeats=3, warmup=1)
    counts_np = [np.asarray(c, np.float64) for c in counts]
    s4m = system_stats(cm.PAPER_TOPOLOGY, counts_np, 4)
    s0m = system_stats(cm.PAPER_TOPOLOGY, counts_np, 0)
    acc = float((logits.argmax(-1) == yj).mean())
    rec.emit("table3_thiswork_measured", us,
             "timed=plan_functional_telemetry_2048;"
             f"accuracy={acc*100:.2f}(paper 97.64 on MNIST);"
             f"throughput_minf_s={s4m.throughput_inf_s/1e6:.1f};"
             f"energy_pj_inf={s4m.energy_pj_per_inf:.0f};"
             f"power_mw={s4m.power_mw:.1f};"
             f"speedup_vs_1rw={s4m.throughput_inf_s/s0m.throughput_inf_s:.2f}x;"
             f"energy_eff_vs_1rw={s0m.energy_pj_per_inf/s4m.energy_pj_per_inf:.2f}x")

    rec.write_json(os.environ.get("BENCH_COMPARISON_OUT", "BENCH_comparison.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
