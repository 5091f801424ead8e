"""Each fault a cell can have, planted under a run that skips only the
harness's look for a chip, turns ``correct`` false."""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_cpu  # noqa: E402

SERVING = ["if_static_p80", "lif_events_p80"]


def _checks(out):
    return {c.name: c.value for c in out["checks"]}


@pytest.mark.parametrize("name", SERVING)
def test_answer_altered_where_produced(monkeypatch, name):
    from repro.serve.engine import SpikeEngine

    flush = SpikeEngine._flush

    def altered(self):
        rounds = [reqs for reqs, _, _ in self._inflight]
        flush(self)
        for reqs in rounds:
            reqs[0].logits = reqs[0].logits + np.float32(1.0)

    monkeypatch.setattr(SpikeEngine, "_flush", altered)
    out = bench_cpu.run_small(monkeypatch, name)
    assert not out["correct"] and _checks(out)["logits_gap"] >= 1.0


@pytest.mark.parametrize("name", SERVING)
def test_half_the_batch_left_out(monkeypatch, name):
    """The second half of each round's real rows never reaches the plan:
    those requests are answered as if their input were silent."""
    from repro.serve.engine import SpikeEngine

    static, events = SpikeEngine._launch_static, SpikeEngine._launch_events

    def half_static(self, reqs, bucket, packed, pack_s):
        packed = packed.copy()
        packed[len(reqs) // 2:len(reqs)] = 0
        return static(self, reqs, bucket, packed, pack_s)

    def half_events(self, reqs, bucket, n_steps, packed, pack_s):
        packed = packed.copy()
        packed[:, len(reqs) // 2:len(reqs)] = 0
        return events(self, reqs, bucket, n_steps, packed, pack_s)

    monkeypatch.setattr(SpikeEngine, "_launch_static", half_static)
    monkeypatch.setattr(SpikeEngine, "_launch_events", half_events)
    out = bench_cpu.run_small(monkeypatch, name)
    assert not out["correct"], _checks(out)


def test_membrane_state_returned_unchanged(monkeypatch):
    """The LIF step hands back the membrane it was given: no state is
    carried from one timestep of a stream to the next."""
    from repro.kernels.lif_step import ops as lif_ops

    step = lif_ops.lif_step

    def stale(vmem, contrib, vth, refrac, **kw):
        spikes, _, r = step(vmem, contrib, vth, refrac, **kw)
        return spikes, vmem, r

    monkeypatch.setattr(lif_ops, "lif_step", stale)
    out = bench_cpu.run_small(monkeypatch, "lif_events_p80")
    assert not out["correct"], _checks(out)


def _learn_with(monkeypatch, fault):
    Driver = bench_cpu.driver_class("if_learn_stdp")

    def factory(cell, seed, seconds, trace):
        drv = Driver(cell, seed, seconds, trace)
        drv.train = fault(drv.train)
        return drv

    return bench_cpu.run_small(monkeypatch, "if_learn_stdp",
                               driver_factory=factory)


def test_learning_step_returns_state_unchanged(monkeypatch):
    def fault(train):
        def f(net, x, y, **kw):
            res = train(net, x, y, **kw)
            return dataclasses.replace(res, network=net)
        return f

    out = _learn_with(monkeypatch, fault)
    assert not out["correct"] and _checks(out)["readout_bits_gap"] > 0


def test_learning_leaves_out_half_the_batch(monkeypatch):
    def fault(train):
        def f(net, x, y, **kw):
            h = len(y) // 2
            return train(net, x[:h], y[:h], **kw)
        return f

    out = _learn_with(monkeypatch, fault)
    assert not out["correct"], _checks(out)


def test_learning_answer_altered(monkeypatch):
    def fault(train):
        def f(net, x, y, **kw):
            res = train(net, x, y, **kw)
            w = res.network.weight_bits
            flipped = w[-1].at[0, 0].set(1 - w[-1][0, 0])
            return dataclasses.replace(res, network=dataclasses.replace(
                res.network, weight_bits=list(w[:-1]) + [flipped]))
        return f

    out = _learn_with(monkeypatch, fault)
    assert not out["correct"] and _checks(out)["readout_bits_gap"] >= 1


def test_learning_fault_only_in_window(monkeypatch):
    """The chunks of set-up are sound and every chunk of the window drops
    its updates: only the check of a window chunk can see it."""
    def fault(train):
        calls = [0]

        def f(net, x, y, **kw):
            calls[0] += 1
            res = train(net, x, y, **kw)
            if calls[0] <= bench_cpu.small_cell("if_learn_stdp").traffic[
                    "reference_chunks"]:
                return res
            return dataclasses.replace(res, network=net)
        return f

    out = _learn_with(monkeypatch, fault)
    assert not out["correct"] and _checks(out)["readout_bits_gap"] > 0
