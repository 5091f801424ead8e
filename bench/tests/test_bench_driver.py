"""The open-loop driver against a fake engine and an injected clock."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import serving  # noqa: E402

open_loop = harness.load_module(
    os.path.join(BENCH, "drivers", "open_loop.py"), "bench_open_loop_t")


class Clock:
    def __init__(self, wake_late=0.0):
        self.now = 100.0
        self.wake_late = wake_late

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt + self.wake_late


class Req:
    """A request that counts every attribute the driver reads."""

    reads = 0

    def __init__(self):
        object.__setattr__(self, "done_at", None)

    def __getattribute__(self, name):
        if not name.startswith("__"):
            type(self).reads += 1
        return object.__getattribute__(self, name)


class FakeEngine:
    """Serve costs ``base + per_req * k`` seconds and answers everything
    admitted, stamping each request at the moment it is answered."""

    def __init__(self, clock, base=0.004, per_req=0.0001):
        self.clock, self.base, self.per_req = clock, base, per_req
        self.queue, self.serves, self.admitted = [], 0, 0

    def submit(self, reqs):
        self.queue.extend(reqs)
        self.admitted += len(reqs)

    def queue_depth(self):
        return len(self.queue)

    def serve(self):
        self.clock.now += self.base
        for r in self.queue:
            self.clock.now += self.per_req
            object.__setattr__(r, "done_at", self.clock.now)
        self.queue.clear()
        self.serves += 1


def test_latency_runs_from_nominal_arrival_and_every_request_counts():
    clock = Clock()
    eng = FakeEngine(clock)
    arr = np.array([0.0, 0.001, 0.0015, 0.010, 0.0101, 0.2])
    reqs = [Req() for _ in arr]
    t0, lags, calls = open_loop.drive(eng, reqs, arr, clock=clock,
                               sleep=clock.sleep)
    done = np.array([object.__getattribute__(r, "done_at") for r in reqs])
    assert not np.isnan(done).any() and eng.admitted == len(arr)
    lat = done - (t0 + arr)
    # request 0 is served alone: 4 ms + 0.1 ms after its arrival
    assert lat[0] == pytest.approx(0.0041)
    # requests 1 and 2 arrived during that serve: they wait for it, then
    # are served together (latency counts the wait from nominal arrival)
    assert lat[1] == pytest.approx(0.0041 - 0.001 + 0.0041)
    assert lat[2] == pytest.approx(0.0041 - 0.0015 + 0.0042)
    assert (lat > 0).all()
    # the client slept before arrivals 3 and 5, and woke on time
    assert lags.size >= 2 and np.allclose(lags, 0.0)
    # one duration per serve: 4 ms plus 0.1 ms a request
    assert len(calls) == eng.serves
    assert calls[0] == pytest.approx(0.0041)


def test_a_late_wake_is_reported_as_client_lag():
    clock = Clock(wake_late=0.0005)
    eng = FakeEngine(clock)
    arr = np.array([0.01, 0.02, 0.03])
    reqs = [Req() for _ in arr]
    _, lags, _ = open_loop.drive(eng, reqs, arr, clock=clock,
                                 sleep=clock.sleep)
    assert np.allclose(lags, 0.0005)


def test_only_pending_requests_are_looked_at():
    """The driver reads no attribute of any request: completion is noted by
    the request itself, so a serve costs the client nothing per finished
    request, however many there are."""
    clock = Clock()
    eng = FakeEngine(clock, base=0.001, per_req=0.0)
    rng = np.random.default_rng(0)
    arr = np.sort(rng.uniform(0, 2.0, 5000))
    reqs = [Req() for _ in arr]
    Req.reads = 0
    open_loop.drive(eng, reqs, arr, clock=clock, sleep=clock.sleep)
    assert Req.reads == 0
    assert eng.admitted == 5000 and eng.serves > 100


def test_stamped_request_notes_when_its_last_result_lands():
    from repro.serve.engine import SpikeRequest

    t = [5.0]
    cls = serving.stamped(SpikeRequest, "energy_pj", clock=lambda: t[0])
    r = cls(spikes=np.zeros(768, np.uint8))
    assert r.done_at is None and r.energy_pj is None
    r.logits = np.zeros(10)
    t[0] = 7.5
    r.energy_pj = 3.0
    assert r.done_at == 7.5 and r.energy_pj == 3.0
    assert isinstance(r, SpikeRequest)
