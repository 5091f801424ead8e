"""Fault-injection & mitigation plane: accuracy-vs-fault-rate sweeps, spare
column remapping, online STDP repair, energy-vs-mitigation -> BENCH_faults.json.

Four sections (env ``BENCH_FAULTS_SMOKE=1`` shrinks every knob for CI):

  fault_sweep_<type>_<mode>   accuracy of a trained BNN->SNN network vs
                              injected fault rate, one row per fault type
                              (stuck0 / stuck1 / read_disturb) x plan mode
                              (functional / packed).  Every faulted executable
                              is asserted bit-identical across the two modes
                              at every rate, so the rows differ only in which
                              datapath ran.
  fault_mitigation_remap      dead hidden columns mitigated by remapping the
                              worst columns onto spare columns at plan-build
                              time; accuracy vs spare budget plus the silicon
                              cost (``cm.spare_column_area_um2``).
  fault_repair_stdp           online-learning repair (Sec 4.4.1 plane): the
                              readout re-trains through the transposed column
                              port around dead hidden columns; accuracy
                              recovered per epoch and the column-access
                              energy the repair itself spent.
  fault_energy_vs_mitigation  modeled pJ/inference from measured arbiter
                              loads (packed telemetry) for the clean, the
                              faulted, and the remapped executable.

Override the output path with env BENCH_FAULTS_OUT.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_faults.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core.esam import bnn, conversion, cost_model as cm, learning
from repro.core.esam.faults import FaultModel
from repro.data import digits
from repro.train import online as online_train

SMOKE = os.environ.get("BENCH_FAULTS_SMOKE", "") not in ("", "0")
OUT = os.environ.get("BENCH_FAULTS_OUT", "BENCH_faults.json")
READ_PORTS = 4

# rate -> FaultModel per injected fault population
FAULT_TYPES = {
    "stuck0": lambda r: FaultModel(seed=11, stuck0_rate=r),
    "stuck1": lambda r: FaultModel(seed=11, stuck1_rate=r),
    "read_disturb": lambda r: FaultModel(seed=11, read_disturb=r),
}


def _data_and_net():
    n, steps = (512, 40) if SMOKE else (4096, 250)
    x, y = digits.make_spike_dataset(n, seed=0)
    split = (3 * n) // 4
    x_tr, y_tr = jnp.asarray(x[:split]), jnp.asarray(y[:split])
    x_te, y_te = jnp.asarray(x[split:]), jnp.asarray(y[split:])
    params, _ = bnn.fit(jax.random.PRNGKey(0), cm.PAPER_TOPOLOGY,
                        x_tr, y_tr, steps=steps, batch=128)
    net = conversion.bnn_to_snn(params)
    return net, (x_tr.astype(bool), y_tr), (x_te.astype(bool), y_te)


def _acc(logits, y) -> float:
    return float((np.asarray(logits).argmax(-1) == np.asarray(y)).mean())


def _bench_fault_sweep(rec: Recorder, net, x_te, y_te) -> None:
    rates = {
        "stuck0": (0.0, 0.05) if SMOKE else (0.0, 0.01, 0.02, 0.05, 0.1),
        "stuck1": (0.0, 0.05) if SMOKE else (0.0, 0.01, 0.02, 0.05, 0.1),
        "read_disturb": (0.0, 5e-3) if SMOKE else (0.0, 1e-3, 3e-3, 1e-2),
    }
    clean = net.plan(mode="functional")(x_te).logits
    for ftype, make in FAULT_TYPES.items():
        accs: dict[str, list[float]] = {"functional": [], "packed": []}
        us = {}
        for r in rates[ftype]:
            fm = make(r) if r else None
            logits = {}
            for mode in ("functional", "packed"):
                plan = net.plan(mode=mode, faults=fm)
                us[mode], logits[mode] = time_call(
                    lambda p=plan: p(x_te).logits, repeats=1)
                accs[mode].append(_acc(logits[mode], y_te))
            # the fault masks live in the plan, not the mode: both datapaths
            # must compile to the same faulted function
            np.testing.assert_array_equal(np.asarray(logits["functional"]),
                                          np.asarray(logits["packed"]))
            if r == 0.0:
                np.testing.assert_array_equal(
                    np.asarray(logits["functional"]), np.asarray(clean))
        for mode in ("functional", "packed"):
            rec.emit(
                f"fault_sweep_{ftype}_{mode}", us[mode],
                f"rates={list(rates[ftype])};"
                f"acc_pct={[round(a * 100, 2) for a in accs[mode]]};"
                f"modes_bit_identical=yes")


def _bench_remap(rec: Recorder, net, x_te, y_te) -> None:
    dead = 0.4
    spares = (0, 96) if SMOKE else (0, 32, 96)
    accs, areas = [], []
    for k in spares:
        fm = FaultModel(seed=5, dead_col_rate=dead, spare_cols=k)
        us, logits = time_call(
            lambda p=net.plan(mode="functional", faults=fm): p(x_te).logits,
            repeats=1)
        accs.append(_acc(logits, y_te))
        areas.append(cm.spare_column_area_um2(net.topology, k, READ_PORTS))
    clean_acc = _acc(net.plan(mode="functional")(x_te).logits, y_te)
    rec.emit(
        "fault_mitigation_remap", us,
        f"dead_col_rate={dead};spare_cols={list(spares)};"
        f"acc_pct={[round(a * 100, 2) for a in accs]};"
        f"clean_acc_pct={clean_acc * 100:.2f};"
        f"spare_area_um2={[round(a, 1) for a in areas]}")
    assert accs[-1] > accs[0] + 0.02, (
        f"remap recovered {accs[-1] - accs[0]:+.3f} accuracy only")


def _bench_repair(rec: Recorder, net, train, x_te, y_te) -> None:
    x_tr, y_tr = train
    epochs = 2 if SMOKE else 4
    fm = FaultModel(seed=5, dead_col_rate=0.4)
    faulted = net.plan(mode="functional", faults=fm)
    acc_fault = _acc(faulted(x_te).logits, y_te)
    us, res = time_call(
        lambda: online_train.train_online(
            net, x_tr, y_tr, epochs=epochs, shuffle=True,
            eval_spikes=x_te, eval_labels=y_te, faults=fm),
        repeats=1, warmup=0)
    cost = learning.column_update_cost(READ_PORTS)
    repair_pj = cost.energy_pj * sum(res.n_updates)
    deployed = _acc(
        res.network.plan(mode="functional", faults=fm)(x_te).logits, y_te)
    assert abs(deployed - res.accuracy[-1]) < 1e-6
    rec.emit(
        "fault_repair_stdp", us,
        f"dead_col_rate={fm.dead_col_rate};epochs={epochs};"
        f"acc_faulted_pct={acc_fault * 100:.2f};"
        f"acc_per_epoch_pct={[round(a * 100, 2) for a in res.accuracy]};"
        f"n_updates={res.n_updates};repair_energy_pj={repair_pj:.0f}")
    assert max(res.accuracy) > acc_fault, (
        f"STDP repair did not recover accuracy: "
        f"{max(res.accuracy):.3f} vs faulted {acc_fault:.3f}")


def _bench_energy(rec: Recorder, net, x_te) -> None:
    dead = 0.4
    configs = {
        "clean": None,
        "faulted": FaultModel(seed=5, dead_col_rate=dead),
        "remapped": FaultModel(seed=5, dead_col_rate=dead, spare_cols=96),
    }
    energy = {}
    for name, fm in configs.items():
        plan = net.plan(mode="packed", telemetry=True, faults=fm)
        us, loads = time_call(lambda p=plan: p(x_te).loads, repeats=1)
        rs = cm.request_stats(
            net.topology, [np.asarray(ld) for ld in loads], READ_PORTS)
        energy[name] = float(rs.energy_pj.mean())
    rec.emit(
        "fault_energy_vs_mitigation", us,
        f"dead_col_rate={dead};"
        + ";".join(f"pj_per_inf_{k}={v:.1f}" for k, v in energy.items()))


def run(rec: Recorder | None = None) -> None:
    own = rec is None
    if own:
        rec = Recorder()
    net, train, (x_te, y_te) = _data_and_net()
    _bench_fault_sweep(rec, net, x_te, y_te)
    _bench_remap(rec, net, x_te, y_te)
    _bench_repair(rec, net, train, x_te, y_te)
    _bench_energy(rec, net, x_te)
    if own:
        rec.write_json(OUT)


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    print("name,us_per_call,derived")
    run()
