"""Paper Sec 4.4.1: transposable-port online learning.

Reproduces the 26.0x / 19.5x column read/write speedups, then measures the
fused column-event epoch (PR 2 tentpole) against the PR 1 per-sample scan —
batch 512 on the 768->10 readout tile and on the full 768:256:256:256:10
topology with the packed prefix — with column-updates/s and the hardware
cost accounting for every measured epoch.  Results go to
``BENCH_learning.json`` (override with env BENCH_LEARNING_OUT) so the perf
trajectory is tracked across PRs.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_online_learning.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core.esam import learning
from repro.data import digits

BATCH = 512


def _hw_cost(n_updates: int) -> str:
    """Hardware time/energy accounting for ``n_updates`` column accesses."""
    c4 = learning.column_update_cost(4)
    c0 = learning.column_update_cost(0)
    t4 = n_updates * (c4.read_ns + c4.write_ns) * 1e-3
    t0 = n_updates * (c0.read_ns + c0.write_ns) * 1e-3
    e4 = n_updates * c4.energy_pj * 1e-3
    e0 = n_updates * c0.energy_pj * 1e-3
    # per-update constant — stays defined even for a zero-update epoch
    speedup = (c0.read_ns + c0.write_ns) / (c4.read_ns + c4.write_ns)
    return (f"column_updates={n_updates};hw_time_4r_us={t4:.1f};"
            f"hw_time_1rw_us={t0:.1f};hw_energy_4r_nj={e4:.1f};"
            f"hw_energy_1rw_nj={e0:.1f};hw_speedup={speedup:.1f}x")


def _timed_epoch(fn, bits):
    """Median of 3 measured runs (time_call warms up / compiles once first)."""
    us, (new_bits, n) = time_call(fn, bits, repeats=3, warmup=1)
    return us, new_bits, int(n)


def _bench_pair(rec: Recorder, tag: str, bits, vth, x, y, key):
    """Old per-sample scan vs fused column-event epoch on one topology."""
    def scan_epoch(b):
        return learning.online_learning_epoch_scan(
            [*bits[:-1], b], vth, x, y, key, p_pot=0.2, p_dep=0.1)

    def fused_epoch(b):
        return learning.online_learning_epoch(
            [*bits[:-1], b], vth, x, y, key, p_pot=0.2, p_dep=0.1)

    us_scan, _, n_scan = _timed_epoch(scan_epoch, bits[-1])
    us_fused, _, n_fused = _timed_epoch(fused_epoch, bits[-1])
    rec.emit(f"learning_epoch_scan_{tag}", us_scan,
             f"plane=pr1_scan;rng=full_matrix_uniforms;batch={BATCH};"
             f"updates_per_s={n_scan / (us_scan * 1e-6):.0f};{_hw_cost(n_scan)}")
    rec.emit(f"learning_epoch_column_event_{tag}", us_fused,
             f"plane=fused_column_event;rng=fold_in_per_column;batch={BATCH};"
             f"speedup_vs_scan={us_scan / us_fused:.1f}x;"
             f"updates_per_s={n_fused / (us_fused * 1e-6):.0f};{_hw_cost(n_fused)}")
    return us_scan / us_fused


def run():
    rec = Recorder()
    base = learning.column_update_cost(0)
    c4 = learning.column_update_cost(4)
    rec.emit("learning_1rw_baseline", 0.0,
             f"col_read_ns={base.read_ns:.1f};col_write_ns={base.write_ns:.1f};"
             f"energy_pj={base.energy_pj:.1f}")
    rec.emit("learning_4r_transposed", 0.0,
             f"col_read_ns={c4.read_ns};col_write_ns={c4.write_ns};"
             f"read_speedup={c4.speedup_read_vs_1rw:.1f}x(paper 26.0x);"
             f"write_speedup={c4.speedup_write_vs_1rw:.1f}x(paper 19.5x)")

    x, y = digits.make_spike_dataset(BATCH, seed=7)
    x, y = jnp.asarray(x).astype(bool), jnp.asarray(y)
    key = jax.random.PRNGKey(1)

    # last tile only: 768 -> 10 (the paper's readout adaptation shape)
    bits = [jax.random.bernoulli(jax.random.PRNGKey(0), 0.5, (768, 10)).astype(jnp.int8)]
    vth = [jnp.full((10,), 2**31 - 1, jnp.int32)]
    _bench_pair(rec, "768x10", bits, vth, x, y, key)

    # full paper topology, frozen prefix: packed fused plane feeds the scan
    topo = (768, 256, 256, 256, 10)
    kw = jax.random.PRNGKey(2)
    bits_full = [
        jax.random.bernoulli(jax.random.fold_in(kw, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(len(topo) - 1)
    ]
    vth_full = [jnp.zeros((n,), jnp.int32) for n in topo[1:-1]]
    vth_full.append(jnp.full((topo[-1],), 2**31 - 1, jnp.int32))
    _bench_pair(rec, "768x256x256x256x10", bits_full, vth_full, x, y, key)

    # bit-identity of the fused plane vs the reference rule under shared RNG
    b_fused, n_f = learning.online_learning_epoch(
        bits, vth, x, y, key, p_pot=0.2, p_dep=0.1)
    b_ref, n_r = learning.online_learning_epoch_scan(
        bits, vth, x, y, key, p_pot=0.2, p_dep=0.1, rng_scheme="column")
    identical = bool((np.asarray(b_fused) == np.asarray(b_ref)).all()
                     and int(n_f) == int(n_r))
    rec.emit("learning_bit_identity", 0.0,
             f"fused_vs_reference_rule_shared_rng={identical};batch={BATCH}")
    assert identical, "column-event epoch diverged from the reference rule"

    rec.write_json(os.environ.get("BENCH_LEARNING_OUT", "BENCH_learning.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
