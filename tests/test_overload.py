"""Overload-hardened serving plane: deadlines, bounded admission,
backpressure, the degradation ladder, per-round dispatch counters, the
``degraded_route`` fix, and the zero-pressure identity property (engine with
no overload knobs == the raw packed plan, bit for bit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.core.esam.network import EsamNetwork
from repro.train import fault_tolerance as ft
from repro.serve.engine import (EventRequest, FaultAwareRouter, SpikeEngine,
                                SpikeRequest, _bucket_sizes)
from repro.serve.overload import (AdmissionVerdict, DegradationLadder,
                                  LadderLevel)


def _net(key=None, topo=(128, 128, 10)):
    key = key if key is not None else jax.random.PRNGKey(0)
    n_tiles = len(topo) - 1
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(n_tiles)
    ]
    vth = [jnp.zeros((topo[i + 1],), jnp.int32) for i in range(n_tiles)]
    return EsamNetwork(weight_bits=bits, vth=vth,
                       out_offset=jnp.zeros((topo[-1],), jnp.float32))


def _spike_reqs(n, n_in=128, seed=0):
    return [
        SpikeRequest(spikes=(np.random.default_rng((seed, i)).random(n_in)
                             < 0.3).astype(np.uint8))
        for i in range(n)
    ]


def _event_reqs(n, t, n_in=128, seed=100):
    return [
        EventRequest(events=(np.random.default_rng((seed, i))
                             .random((t, n_in)) < 0.3).astype(np.uint8))
        for i in range(n)
    ]


# ----------------------------------------------------------------------- #
# bounded admission queue + backpressure
# ----------------------------------------------------------------------- #
def test_bounded_queue_rejects_and_counts():
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, queue_limit=4)
    reqs = _spike_reqs(7)
    verdicts = eng.submit(reqs)
    assert [v.admitted for v in verdicts] == [True] * 4 + [False] * 3
    assert all(v.reason == "queue_full" for v in verdicts[4:])
    assert all(r.status == "rejected" for r in reqs[4:])
    assert eng.queue_depth() == 4
    eng.serve()
    st_ = eng.stats()
    assert st_["rejected_full"] == 3
    assert st_["n_requests"] == 4
    assert all(r.logits is not None for r in reqs[:4])
    assert all(r.logits is None for r in reqs[4:])


def test_backpressure_past_high_water():
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, queue_limit=8,
                      high_water=2)
    verdicts = eng.submit(_spike_reqs(5))
    assert [v.backpressure for v in verdicts] == [False, False, True, True,
                                                  True]
    assert eng.stats()["backpressure_events"] == 3
    # default high-water = half the queue limit
    eng2 = SpikeEngine(_net(), interpret=True, queue_limit=8)
    assert eng2.stats()["high_water"] == 4


def test_unbounded_queue_always_admits():
    eng = SpikeEngine(_net(), interpret=True, max_batch=8)
    verdicts = eng.submit(_spike_reqs(40))
    assert all(v.admitted and not v.backpressure for v in verdicts)
    single = eng.submit(_spike_reqs(1)[0])
    assert isinstance(single, AdmissionVerdict) and single.admitted


# ----------------------------------------------------------------------- #
# per-request deadlines
# ----------------------------------------------------------------------- #
def test_deadline_shed_counted_and_terminal():
    t = [0.0]
    eng = SpikeEngine(_net(), interpret=True, max_batch=8,
                      clock=lambda: t[0])
    reqs = _spike_reqs(6)
    reqs[1].deadline_s = -1.0          # already expired
    reqs[4].deadline_s = 100.0         # far future
    eng.serve(reqs)
    assert reqs[1].status == "shed" and reqs[1].logits is None
    assert reqs[4].status == "done" and reqs[4].logits is not None
    st_ = eng.stats()
    assert st_["shed_deadline"] == 1
    assert st_["n_requests"] == 5


def test_deadline_expiring_mid_drain_sheds_later_round():
    """The clock advances one unit per dispatch round; a deadline of 0.5
    sheds everything not dispatched in the very first round."""
    t = [0.0]
    eng = SpikeEngine(_net(), interpret=True, max_batch=4,
                      clock=lambda: t[0])
    orig = eng._dispatch

    def advancing(reqs):
        orig(reqs)
        t[0] += 1.0

    eng._dispatch = advancing
    reqs = _spike_reqs(10)
    for r in reqs:
        r.deadline_s = 0.5
    eng.serve(reqs)
    done = [r for r in reqs if r.status == "done"]
    shed = [r for r in reqs if r.status == "shed"]
    assert len(done) == 4 and len(shed) == 6       # one round, rest shed
    assert eng.stats()["shed_deadline"] == 6


def test_event_requests_shed_on_deadline_too():
    t = [0.0]
    eng = SpikeEngine(_net(), interpret=True, max_batch=8,
                      clock=lambda: t[0])
    reqs = _event_reqs(3, t=2)
    reqs[0].deadline_s = -1.0
    eng.serve(reqs)
    assert reqs[0].status == "shed"
    assert all(r.status == "done" for r in reqs[1:])
    assert eng.stats()["shed_deadline"] == 1


# ----------------------------------------------------------------------- #
# degradation ladder
# ----------------------------------------------------------------------- #
def _pressure_ladder(**kw):
    return DegradationLadder(levels=(
        LadderLevel("full"),
        LadderLevel("reduced", event_t_cap=2, read_ports=2, bucket_cap=4),
    ), **kw)


def test_ladder_steps_down_on_queue_depth_and_back_up():
    # a never-flagging watchdog pins the pressure signal to queue depth
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, high_water=4,
                      watchdog=ft.StragglerWatchdog(threshold=1e9),
                      ladder=_pressure_ladder(step_down_after=2,
                                              step_up_after=2))
    eng.serve(_spike_reqs(24))          # deep queue -> sustained pressure
    st_ = eng.stats()
    assert st_["ladder_transitions"] >= 1
    log = st_["ladder_transition_log"]
    assert log[0]["from"] == "full" and log[0]["to"] == "reduced"
    assert log[0]["reason"] == "queue_depth"
    # pressure cleared: a few quiet rounds step back up to full service
    for _ in range(3):
        eng.serve(_spike_reqs(2, seed=7))
    st2 = eng.stats()
    assert st2["degradation_level"] == 0
    assert st2["ladder_transition_log"][-1]["reason"] == "pressure_cleared"


def test_degraded_level_truncates_event_streams():
    ladder = _pressure_ladder(step_down_after=1, step_up_after=50)
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, high_water=1,
                      ladder=ladder)
    reqs = _event_reqs(10, t=4)
    eng.serve(reqs)
    served = [r for r in reqs if r.status == "done"]
    assert served
    # once degraded, streams are truncated to the level's T cap
    assert eng.stats()["degradation_level"] == 1
    assert any(r.served_steps == 2 for r in served)
    full = [r for r in served if r.served_steps == 4]
    trunc = [r for r in served if r.served_steps == 2]
    assert len(full) + len(trunc) == len(served)


def test_degraded_level_caps_round_size():
    ladder = _pressure_ladder(step_down_after=1, step_up_after=50)
    eng = SpikeEngine(_net(), interpret=True, max_batch=16, min_bucket=4,
                      high_water=1, ladder=ladder)
    eng.serve(_spike_reqs(32))
    st_ = eng.stats()
    assert st_["degradation_level"] == 1
    # after the step-down, rounds are capped at bucket_cap=4
    assert 4 in st_["rounds_per_bucket"]


def test_ladder_default_levels_are_pow2_buckets():
    lad = DegradationLadder.default(128, 4)
    assert lad.levels[0].event_t_cap is None
    for lv in lad.levels[1:]:
        if lv.bucket_cap is not None:
            assert lv.bucket_cap & (lv.bucket_cap - 1) == 0
        assert lv.read_ports is None or 1 <= lv.read_ports <= 4


def test_no_ladder_means_pinned_full_service():
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, high_water=1)
    eng.serve(_spike_reqs(20))
    st_ = eng.stats()
    assert st_["degradation_level"] == 0 and st_["ladder_transitions"] == 0


# ----------------------------------------------------------------------- #
# per-round host-sync/dispatch counters (dp8 regression observability)
# ----------------------------------------------------------------------- #
def test_round_counters_track_padding_and_times():
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, min_bucket=8)
    eng.serve(_spike_reqs(11))          # rounds of 8 + 3 -> bucket 8 twice
    st_ = eng.stats()
    assert st_["rounds_static"] == 2 and st_["rounds_event"] == 0
    assert st_["rows_real_total"] == 11
    assert st_["rows_padded_total"] == 5            # 3-row round padded to 8
    assert st_["rounds_per_bucket"] == {8: 2}
    assert st_["padded_rows_per_bucket"] == {8: 5}
    assert st_["pad_fraction"] == pytest.approx(5 / 16)
    assert st_["host_pack_s_total"] > 0.0
    assert st_["dispatch_s_total"] > 0.0
    eng.serve(_event_reqs(3, t=2))
    st2 = eng.stats()
    assert st2["rounds_event"] == 1
    assert st2["rows_real_total"] == 14


# ----------------------------------------------------------------------- #
# FaultAwareRouter: degraded_route is visible, raise mode available
# ----------------------------------------------------------------------- #
def _degraded_engine():
    """An engine whose health() reads 0 (forced), without any device work."""
    eng = SpikeEngine(_net(), interpret=True, max_batch=8)
    eng.health = lambda: 0.0
    return eng


def test_all_degraded_fallback_counts_degraded_route():
    eng = _degraded_engine()
    router = FaultAwareRouter([eng], health_threshold=0.5)
    idx = router.route(_spike_reqs(1)[0])
    assert idx == 0
    assert router.stats()["degraded_route"] == 1


def test_all_degraded_raise_mode():
    from repro.serve.engine import AllReplicasDegradedError

    router = FaultAwareRouter([_degraded_engine()], health_threshold=0.5,
                              on_all_degraded="raise")
    with pytest.raises(AllReplicasDegradedError):
        router.route(_spike_reqs(1)[0])
    assert router.stats()["degraded_route"] == 1
    assert router.routed == [0]                    # nothing silently queued


def test_router_spill_to_degraded_on_full_healthy_queue_is_counted():
    healthy = SpikeEngine(_net(), interpret=True, max_batch=8, queue_limit=1)
    degraded = _degraded_engine()
    router = FaultAwareRouter([healthy, degraded], health_threshold=0.5)
    r1, r2 = _spike_reqs(2)
    assert router.route(r1) == 0
    assert router.route(r2) == 1                   # healthy queue full
    assert router.stats()["degraded_route"] == 1
    assert r2.status == "pending"                  # overflow, not rejection


def test_router_rejects_when_every_queue_full():
    engines = [SpikeEngine(_net(), interpret=True, queue_limit=1)
               for _ in range(2)]
    router = FaultAwareRouter(engines)
    reqs = _spike_reqs(3)
    assert router.route(reqs[0]) == 0
    assert router.route(reqs[1]) == 1
    assert router.route(reqs[2]) is None
    assert reqs[2].status == "rejected"
    assert router.stats()["rejected_full"] == 1


# ----------------------------------------------------------------------- #
# _bucket_sizes / _bucket edge cases (property tests)
# ----------------------------------------------------------------------- #
@settings(max_examples=60)
@given(max_batch=st.integers(1, 512), min_bucket=st.integers(1, 64),
       dp_exp=st.integers(0, 4))
def test_bucket_sizes_properties(max_batch, min_bucket, dp_exp):
    dp = 2 ** dp_exp
    sizes = _bucket_sizes(max_batch, min_bucket, dp)
    assert sizes == sorted(sizes)
    # every bucket is a power of two and a multiple of the dp degree
    for b in sizes:
        assert b & (b - 1) == 0
        assert b % dp == 0
    # the ladder covers max_batch: the top bucket fits any round the engine
    # can form (rounds are capped at max_batch requests)
    assert sizes[-1] >= max_batch
    # strictly doubling ladder (no duplicate shapes to compile)
    for a, b in zip(sizes, sizes[1:]):
        assert b == 2 * a


def test_bucket_sizes_min_bucket_larger_than_max_batch():
    # max_batch < min_bucket: the smallest bucket never exceeds the
    # rounded-up max_batch, so tiny engines don't over-pad
    sizes = _bucket_sizes(4, 64, 1)
    assert sizes == [4]


def test_bucket_sizes_dp_larger_than_max_batch():
    # dp > max_batch: divisibility wins, a single dp-wide bucket
    sizes = _bucket_sizes(3, 2, 8)
    assert sizes == [8]


def test_bucket_sizes_non_pow2_max_batch():
    sizes = _bucket_sizes(100, 8, 2)
    assert sizes == [8, 16, 32, 64, 128]


def test_bucket_clamps_to_top_bucket():
    """A round larger than the top bucket clamps to it — the serve loop
    never forms such a round (rounds are capped at max_batch), so the clamp
    is the documented safety behavior, not a truncation path."""
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, min_bucket=4)
    assert eng._buckets == [4, 8]
    assert eng._bucket(3) == 4
    assert eng._bucket(8) == 8
    assert eng._bucket(1000) == 8


# ----------------------------------------------------------------------- #
# zero-pressure identity: acceptance-criteria property test
# ----------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)   # the first example compiles the plan
@given(seed=st.integers(0, 2 ** 16), n=st.integers(1, 20))
def test_zero_pressure_identity_vs_raw_plan(seed, n):
    """No deadline, unbounded queue, no ladder, no chaos: the overloaded
    engine's results are bit-identical to the raw packed plan on the same
    padded bucket — i.e. to the pre-overload engine."""
    net = _net()
    eng = SpikeEngine(net, interpret=True, max_batch=16)
    reqs = _spike_reqs(n, seed=seed)
    eng.serve(reqs)
    bucket = eng._bucket(min(n, 16))
    # reference: the raw plan on the first round's padded bucket
    first = reqs[:16]
    packed = jnp.asarray(packing.pack_padded_rows_np(
        [r.spikes for r in first], bucket, 128))
    want = np.asarray(net.plan(mode="packed", interpret=True)(packed).logits)
    for i, r in enumerate(first):
        np.testing.assert_array_equal(r.logits, want[i])
        assert r.status == "done"


def test_mixed_static_event_serve_preserves_order_and_results():
    """Satellite: mixed static+event serve() returns the caller's list in
    order, each request carrying its own kind's results."""
    net = _net()
    eng = SpikeEngine(net, interpret=True, max_batch=8)
    statics = _spike_reqs(3, seed=1)
    events = _event_reqs(3, t=2, seed=2)
    mixed = [statics[0], events[0], statics[1], events[1], statics[2],
             events[2]]
    out = eng.serve(list(mixed))
    assert [id(r) for r in out] == [id(r) for r in mixed]
    assert all(r.logits is not None for r in mixed)
    # static results == packed plan on the static bucket
    packed = jnp.asarray(packing.pack_padded_rows_np(
        [r.spikes for r in statics], 8, 128))
    want = np.asarray(net.plan(mode="packed", interpret=True)(packed).logits)
    for i, r in enumerate(statics):
        np.testing.assert_array_equal(r.logits, want[i])
    # event labels are argmax of their own logits, T recorded
    for r in events:
        assert r.served_steps == 2
        assert r.label == int(np.asarray(r.logits).argmax())
