"""Paper Fig 8: system-level power / throughput / energy / area across the
five SRAM cell options — now driven by the rank-schedule cycle-accurate
plane, not just the closed-form cost model.

Three sweeps, all recorded to ``BENCH_system.json``:

  fig8_ref_*        cost model on the calibration activity profile (anchor)
  fig8_sim_*        cycle-accurate simulation of a batch pinned to the same
                    profile — the measured loads reproduce the 3.1x / 2.2x
                    headline from simulated traces, and every simulated
                    per-tile cycle count is asserted against the cost model
  fig8_measured_*   ``EsamNetwork.port_sweep`` on a digit batch through a
                    paper-topology network (measured batch activity)

plus ``plane_speedup_batch256``: wall-clock of the rank-schedule plane vs
the retained scan oracle on the first tile at batch 256 (acceptance: >=10x).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_system.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core.esam import cost_model as cm
from repro.core.esam import tile as tile_mod
from repro.core.esam.network import EsamNetwork, reference_activity, system_stats
from repro.data import digits

BATCH = 256


def _emit_sweep(rec: Recorder, tag: str, activity) -> tuple[float, float]:
    """Emit the five cell options + headline ratios for one activity profile."""
    stats = [system_stats(cm.PAPER_TOPOLOGY, activity, p) for p in range(5)]
    for s in stats:
        rec.emit(
            f"fig8_{tag}_{s.cell}",
            0.0,
            f"throughput_minf_s={s.throughput_inf_s/1e6:.2f};"
            f"energy_pj_inf={s.energy_pj_per_inf:.0f};"
            f"power_mw={s.power_mw:.1f};area_ratio={s.area_ratio_vs_1rw:.2f};"
            f"latency_ns={s.latency_ns:.1f};bottleneck_tile={s.bottleneck_tile}",
        )
    speedup = stats[4].throughput_inf_s / stats[0].throughput_inf_s
    eff = stats[0].energy_pj_per_inf / stats[4].energy_pj_per_inf
    rec.emit(
        f"fig8_{tag}_headline", 0.0,
        f"speedup_4r={speedup:.2f}x(paper {cm.PAPER_SPEEDUP_4R}x);"
        f"energy_eff_4r={eff:.2f}x(paper {cm.PAPER_ENERGY_EFF_4R}x)")
    return speedup, eff


def _reference_profile_spikes(n_in: int, per_group: int, batch: int) -> jax.Array:
    """Deterministic batch with exactly ``per_group`` spikes per 128-row group
    (positions rolled per sample so the arbiters see varied request patterns
    at a pinned load)."""
    n_groups = n_in // 128
    base = np.zeros((n_groups, 128), bool)
    base[:, :per_group] = True
    out = np.stack([np.roll(base, i, axis=1) for i in range(batch)])
    return jnp.asarray(out.reshape(batch, n_in))


def _simulated_reference_sweep(rec: Recorder) -> tuple[float, float]:
    """Drive the rank-schedule plane at the calibration loads, tile by tile,
    and evaluate the Fig 8 sweep on the loads the simulator actually drained."""
    key = jax.random.PRNGKey(0)
    topo = cm.PAPER_TOPOLOGY
    measured = []
    for t in range(len(topo) - 1):
        n_in, n_out = topo[t], topo[t + 1]
        bits = jax.random.bernoulli(
            jax.random.fold_in(key, t), 0.5, (n_in, n_out)).astype(jnp.int8)
        vth = jnp.zeros((n_out,), jnp.int32)
        spikes = _reference_profile_spikes(n_in, cm.REF_SPIKES_PER_GROUP[t], BATCH)
        loads = np.asarray(spikes, np.int32).reshape(BATCH, -1, 128).sum(-1)
        for p in range(5):
            ports = max(1, p)
            tr = tile_mod.simulate_tile_batch(bits, spikes, vth, ports)
            # every simulated drain must land on the cost model's cycle count
            want = np.ceil(loads / ports).max(axis=1).astype(np.int32)
            np.testing.assert_array_equal(np.asarray(tr.cycles), want)
        measured.append(loads.astype(np.float64))
    return _emit_sweep(rec, "sim", measured)


def _measured_network_sweep(rec: Recorder):
    """Fig 8 on *measured* batch activity: one jitted ``port_sweep`` through a
    paper-topology network on the digit set, loads taken from its traces."""
    key = jax.random.PRNGKey(1)
    topo = cm.PAPER_TOPOLOGY
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(len(topo) - 1)
    ]
    vth = [jnp.zeros((n,), jnp.int32) for n in topo[1:]]
    net = EsamNetwork(weight_bits=bits, vth=vth,
                      out_offset=jnp.zeros((topo[-1],), jnp.float32))
    x, _ = digits.make_spike_dataset(BATCH, seed=3)
    spikes = jnp.asarray(x).astype(bool)

    us, sweep = time_call(net.port_sweep, spikes, range(5))
    logits4 = np.asarray(sweep[4][0])
    np.testing.assert_array_equal(
        logits4, np.asarray(net.plan(mode="functional")(spikes).logits))

    activity = net.measured_activity(spikes, traces=sweep[4][1])
    speedup, eff = _emit_sweep(rec, "measured", activity)
    rec.emit("port_sweep_batched", us,
             f"batch={BATCH};cells=5;plane=rank_schedule;one_jitted_call=True;"
             f"input_activity={activity[0].mean()/128:.2f}")
    return speedup, eff


def _plane_speedup(rec: Recorder) -> float:
    """Wall-clock: rank-schedule plane vs retained scan oracle, batch 256."""
    key = jax.random.PRNGKey(2)
    n_in, n_out = cm.PAPER_TOPOLOGY[0], cm.PAPER_TOPOLOGY[1]
    bits = jax.random.bernoulli(key, 0.5, (n_in, n_out)).astype(jnp.int8)
    vth = jnp.zeros((n_out,), jnp.int32)
    x, _ = digits.make_spike_dataset(BATCH, seed=5)
    spikes = jnp.asarray(x).astype(bool)

    us_sched, tr_sched = time_call(
        tile_mod.simulate_tile_batch, bits, spikes, vth, 4)
    us_scan, tr_scan = time_call(
        tile_mod.simulate_tile_scan_batch, bits, spikes, vth, 4)
    np.testing.assert_array_equal(
        np.asarray(tr_sched.vmem_final), np.asarray(tr_scan.vmem_final))
    np.testing.assert_array_equal(
        np.asarray(tr_sched.grants_per_cycle), np.asarray(tr_scan.grants_per_cycle))
    speedup = us_scan / us_sched
    rec.emit("plane_speedup_batch256", us_sched,
             f"us_scan={us_scan:.1f};speedup={speedup:.1f}x;batch={BATCH};"
             f"tile={n_in}x{n_out};ports=4;bit_identical=True")
    return speedup


def run():
    rec = Recorder()
    ref_speed, ref_eff = _emit_sweep(rec, "ref", reference_activity())
    sim_speed, sim_eff = _simulated_reference_sweep(rec)
    _measured_network_sweep(rec)
    plane_speedup = _plane_speedup(rec)

    # write the report before the acceptance asserts so a failing run still
    # leaves the recorded rows behind for diagnosis
    rec.write_json(os.environ.get("BENCH_SYSTEM_OUT", "BENCH_system.json"))

    # acceptance: the simulated-trace sweep reproduces the paper headline …
    assert abs(sim_speed - cm.PAPER_SPEEDUP_4R) / cm.PAPER_SPEEDUP_4R < 0.05, sim_speed
    assert abs(sim_eff - cm.PAPER_ENERGY_EFF_4R) / cm.PAPER_ENERGY_EFF_4R < 0.05, sim_eff
    assert abs(sim_speed - ref_speed) < 1e-9 and abs(sim_eff - ref_eff) < 1e-9
    # … and the rank-schedule plane beats the scan plane >=10x at batch 256
    assert plane_speedup >= 10.0, f"plane speedup {plane_speedup:.1f}x < 10x"


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
