"""Open-loop traffic generator + chaos harness: seeded determinism, the
acceptance-criteria chaos drill (crash mid-drain + 10x slowdown behind the
retrying router, every non-shed request completes exactly once), storms
against bounded queues, and report integrity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.esam.network import EsamNetwork
from repro.serve.engine import (EventRequest, FaultAwareRouter, SpikeEngine,
                                SpikeRequest)
from repro.serve.traffic import (ChaosConfig, ReplicaCrashError,
                                 TrafficConfig, arrival_times, build_requests,
                                 install_chaos, run_open_loop)
from repro.train.fault_tolerance import RetryPolicy

N_IN = 128


def _net(seed=0, topo=(N_IN, 128, 10)):
    key = jax.random.PRNGKey(seed)
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(len(topo) - 1)
    ]
    vth = [jnp.zeros((n,), jnp.int32) for n in topo[1:]]
    return EsamNetwork(weight_bits=bits, vth=vth,
                       out_offset=jnp.zeros((topo[-1],), jnp.float32))


def _engine(net=None, **kw):
    kw.setdefault("interpret", True)
    kw.setdefault("max_batch", 8)
    return SpikeEngine(net if net is not None else _net(), **kw)


# ----------------------------------------------------------------------- #
# generator determinism
# ----------------------------------------------------------------------- #
def test_arrivals_are_seeded_poisson():
    cfg = TrafficConfig(rate_hz=100.0, n_requests=500, seed=3, n_in=N_IN)
    a1, a2 = arrival_times(cfg), arrival_times(cfg)
    np.testing.assert_array_equal(a1, a2)
    assert (np.diff(a1) >= 0).all() and a1[0] > 0
    # mean gap ~ 1/rate (500 samples: within 20%)
    assert np.diff(a1, prepend=0.0).mean() == pytest.approx(0.01, rel=0.2)
    # a different seed is a different schedule
    assert not np.array_equal(
        a1, arrival_times(TrafficConfig(rate_hz=100.0, n_requests=500,
                                        seed=4, n_in=N_IN)))


def test_build_requests_blend_and_replay():
    cfg = TrafficConfig(rate_hz=50.0, n_requests=200, seed=9, p_event=0.4,
                        event_t_choices=(2, 4), n_in=N_IN)
    reqs1, arr1 = build_requests(cfg)
    reqs2, arr2 = build_requests(cfg)
    np.testing.assert_array_equal(arr1, arr2)
    assert len(reqs1) == 200
    n_event = sum(isinstance(r, EventRequest) for r in reqs1)
    assert 0 < n_event < 200                       # mixed blend
    assert {r.n_steps for r in reqs1
            if isinstance(r, EventRequest)} <= {2, 4}
    # replay is bit-identical, request by request
    for r1, r2 in zip(reqs1, reqs2):
        assert type(r1) is type(r2)
        payload = "events" if isinstance(r1, EventRequest) else "spikes"
        np.testing.assert_array_equal(getattr(r1, payload),
                                      getattr(r2, payload))


def test_storm_splices_extra_arrivals_sorted():
    cfg = TrafficConfig(rate_hz=10.0, n_requests=20, seed=1, n_in=N_IN)
    chaos = ChaosConfig(storm_at_s=0.05, storm_size=15)
    reqs, arr = build_requests(cfg, chaos=chaos)
    assert len(reqs) == 35 and len(arr) == 35
    assert (np.diff(arr) >= 0).all()
    assert (arr == 0.05).sum() >= 15               # the burst lands at once


# ----------------------------------------------------------------------- #
# chaos harness wiring
# ----------------------------------------------------------------------- #
def test_install_chaos_crash_hook_raises_after_n_rounds():
    eng = _engine()
    install_chaos([eng], ChaosConfig(crash_replica=0, crash_after_rounds=2))
    reqs = [SpikeRequest(spikes=np.zeros(N_IN, np.uint8)) for _ in range(20)]
    with pytest.raises(ReplicaCrashError):
        eng.serve(reqs)
    # two rounds ran before the crash round aborted
    assert eng.stats()["dispatch_rounds"] == 2


def test_install_chaos_slowdown_feeds_watchdog():
    slept = []
    eng = _engine()
    install_chaos([eng], ChaosConfig(slowdown=((0, 0.25),)),
                  sleep=slept.append)
    eng.serve([SpikeRequest(spikes=np.zeros(N_IN, np.uint8))
               for _ in range(20)])
    assert slept == [0.25, 0.25, 0.25]             # one stall per round


# ----------------------------------------------------------------------- #
# open-loop driver
# ----------------------------------------------------------------------- #
def test_open_loop_completes_everything_below_saturation():
    eng = _engine()
    cfg = TrafficConfig(rate_hz=2000.0, n_requests=24, seed=11, n_in=N_IN,
                        p_event=0.25)
    rep = run_open_loop(eng, cfg, max_wall_s=60.0)
    assert rep.n_offered == 24 and rep.n_completed == 24
    assert rep.n_shed == rep.n_rejected == rep.n_failed == 0
    assert 0.0 < rep.p50_ms <= rep.p99_ms <= rep.p999_ms
    assert rep.goodput_slo == 1.0                  # no SLO -> completion rate
    assert rep.duration_s < 60.0
    d = rep.to_dict()
    assert d["n_completed"] == 24 and "p999_ms" in d


def test_open_loop_storm_against_bounded_queue_sheds():
    eng = _engine(queue_limit=8)
    cfg = TrafficConfig(rate_hz=500.0, n_requests=8, seed=13, n_in=N_IN,
                        deadline_s=5.0)
    chaos = ChaosConfig(storm_at_s=0.0, storm_size=64)
    rep = run_open_loop(eng, cfg, slo_s=5.0, chaos=chaos, max_wall_s=60.0)
    assert rep.n_offered == 72
    # a 64-request burst against an 8-deep queue must reject
    assert rep.n_rejected > 0
    assert rep.n_completed + rep.n_shed + rep.n_rejected == 72
    assert rep.backpressure_events > 0
    assert 0.0 <= rep.goodput_slo < 1.0


def test_open_loop_deadline_sheds_are_counted():
    # an engine stalled 50ms per round vs 1ms deadlines: later arrivals
    # expire while queued
    eng = _engine()
    install_chaos([eng], ChaosConfig(slowdown=((0, 0.05),)))
    cfg = TrafficConfig(rate_hz=400.0, n_requests=40, seed=17, n_in=N_IN,
                        deadline_s=0.001)
    rep = run_open_loop(eng, cfg, max_wall_s=60.0)
    assert rep.n_shed > 0
    assert rep.n_completed + rep.n_shed == 40
    # every completion that beat its deadline counts toward goodput; the
    # sheds never do
    assert rep.goodput_slo <= rep.n_completed / 40


# ----------------------------------------------------------------------- #
# the acceptance-criteria chaos drill
# ----------------------------------------------------------------------- #
def test_chaos_crash_plus_slowdown_exactly_once():
    """One of two replicas crashes mid-drain and the survivor runs with a
    10x stall; every non-shed request still completes exactly once, with
    retries and the crash visible in the router's counters."""
    net = _net()
    engines = [_engine(net), _engine(net)]
    router = FaultAwareRouter(
        engines,
        retry=RetryPolicy(max_attempts=4, base_backoff_s=1e-4, seed=7),
    )
    # replica 0 crashes on its second round; replica 1 stalls 10x a typical
    # ~1ms interpret round
    chaos = ChaosConfig(slowdown=((1, 0.01),), crash_replica=0,
                        crash_after_rounds=1)
    cfg = TrafficConfig(rate_hz=5000.0, n_requests=32, seed=23, n_in=N_IN)
    rep = run_open_loop(router, cfg, chaos=chaos, max_wall_s=60.0)

    assert rep.n_offered == 32
    # exactly-once: every request reached exactly one terminal state and
    # every completed request carries exactly one result
    assert (rep.n_completed + rep.n_shed + rep.n_rejected
            + rep.n_failed) == 32
    assert rep.n_completed == 32                   # nothing shed or lost
    assert rep.crashes == 1
    assert rep.retries > 0                         # victims were re-routed
    st = router.stats()
    assert st["down"] == [0]
    assert st["backlog"] == 0
    # the crashed replica's queues were emptied — a later direct drain
    # cannot double-serve anything
    assert engines[0].queue_depth() == 0
    # per-engine dispatch counts add up to >= offered: the crashed replica
    # still counted the round whose results it discarded, and those requests
    # were served again on the survivor — but each request object carries
    # exactly one result (rep.n_completed above), never two
    served = sum(e.stats()["n_requests"] for e in engines)
    assert served >= 32


def test_chaos_results_match_clean_replay():
    """Chaos must not corrupt results: the same seeded traffic served
    cleanly on a fresh engine yields bit-identical logits, request by
    request, even for the re-routed crash victims."""
    net = _net()
    # 32 requests round-robin to 16 per replica = two rounds each, so the
    # crash (second round) fires with one round's results already in flight
    cfg = TrafficConfig(rate_hz=5000.0, n_requests=32, seed=29, n_in=N_IN)
    reqs, _ = build_requests(cfg)
    engines = [_engine(net), _engine(net)]
    router = FaultAwareRouter(
        engines, retry=RetryPolicy(max_attempts=4, base_backoff_s=1e-5))
    # replica 0 crashes on its second round: its first round's results are
    # discarded pre-flush and the victims re-route to replica 1
    install_chaos(engines, ChaosConfig(crash_replica=0,
                                       crash_after_rounds=1))
    router.serve(reqs)
    assert all(r.status == "done" for r in reqs)
    assert router.stats()["crashes"] == 1

    clean, _ = build_requests(cfg)                 # bit-identical replay
    _engine(net).serve(clean)
    for a, b in zip(reqs, clean):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.label == b.label


def test_all_replicas_down_fails_remaining_requests():
    net = _net()
    engines = [_engine(net), _engine(net)]
    router = FaultAwareRouter(
        engines, retry=RetryPolicy(max_attempts=5, base_backoff_s=1e-5))
    install_chaos(engines, ChaosConfig(crash_replica=0,
                                       crash_after_rounds=0))
    install_chaos([engines[1]], ChaosConfig(crash_replica=0,
                                            crash_after_rounds=0))
    reqs = [SpikeRequest(spikes=np.zeros(N_IN, np.uint8)) for _ in range(4)]
    router.serve(reqs)
    st = router.stats()
    assert st["crashes"] == 2 and sorted(st["down"]) == [0, 1]
    assert all(r.status == "failed" for r in reqs)
    assert st["failed"] == 4
    with pytest.raises(Exception):
        router.route(SpikeRequest(spikes=np.zeros(N_IN, np.uint8)))


def test_retry_budget_exhaustion_marks_failed_not_lost():
    net = _net()
    engines = [_engine(net), _engine(net)]
    router = FaultAwareRouter(
        engines, retry=RetryPolicy(max_attempts=1, base_backoff_s=1e-5))
    install_chaos(engines, ChaosConfig(crash_replica=0,
                                       crash_after_rounds=0))
    reqs = [SpikeRequest(spikes=np.zeros(N_IN, np.uint8)) for _ in range(6)]
    for r in reqs:
        router.route(r)
    router.serve()
    # with a 1-attempt budget, replica 0's victims fail instead of retrying;
    # replica 1's share completes normally
    statuses = {r.status for r in reqs}
    assert statuses <= {"done", "failed"}
    assert sum(r.status == "failed" for r in reqs) == router.stats()["failed"]
    assert sum(r.status == "done" for r in reqs) == sum(
        e.stats()["n_requests"] for e in engines)
    lost = [r for r in reqs if r.status == "pending"]
    assert not lost


def test_router_propagates_errors_that_are_not_crashes():
    """Only ``ReplicaCrashError`` marks a replica down; any other exception
    from a replica (a compile refusal, a bad shape) is a bug and must reach
    the caller instead of turning into re-routed or failed requests."""
    net = _net()
    engines = [_engine(net), _engine(net)]

    def refuse(round_idx):
        raise ValueError("kernel refused")

    engines[0].round_hook = refuse
    router = FaultAwareRouter(
        engines, retry=RetryPolicy(max_attempts=5, base_backoff_s=1e-5))
    reqs = [SpikeRequest(spikes=np.zeros(N_IN, np.uint8)) for _ in range(4)]
    with pytest.raises(ValueError, match="kernel refused"):
        router.serve(reqs)
    st = router.stats()
    assert st["crashes"] == 0 and st["down"] == [] and st["failed"] == 0
