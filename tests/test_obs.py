"""Observability plane: tracer/metrics/http/profile units, engine + router
integration (spans close, counters reconcile with stats()), and the
versioned stats-schema regression gate."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from repro.obs import Observability
from repro.obs.http import PROMETHEUS_CONTENT_TYPE, MetricsServer
from repro.obs.metrics import DEFAULT_BOUNDS, Registry
from repro.obs.profile import DeviceProfiler, record_warmup_times
from repro.obs.trace import REQUEST_PHASES, Tracer, validate_trace
from repro.serve.engine import (STATS_SCHEMA_VERSION, FaultAwareRouter,
                                ReplicaCrashError, SpikeEngine, stats_schema)
from repro.train import fault_tolerance as ft

from test_async_serve import _mixed, _net, _spike_reqs


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ----------------------------------------------------------------------- #
# tracer
# ----------------------------------------------------------------------- #
def test_tracer_complete_and_instant_deterministic_timestamps():
    clk = FakeClock()
    tr = Tracer(clock=clk, pid=7)
    clk.advance(0.001)                       # +1000us
    t0 = tr.now_us()
    assert t0 == pytest.approx(1000.0)
    clk.advance(0.0005)
    tr.complete("pack", t0, tr.now_us() - t0, cat="round", bucket=8)
    tr.instant("shed", deadline_s=1.0)
    ev = tr.events()
    assert [e["ph"] for e in ev] == ["X", "i"]
    assert ev[0]["ts"] == pytest.approx(1000.0)
    assert ev[0]["dur"] == pytest.approx(500.0)
    assert ev[0]["args"] == {"bucket": 8}
    assert ev[0]["pid"] == 7
    assert ev[1]["s"] == "t"


def test_tracer_async_pair_and_span_context():
    tr = Tracer(clock=FakeClock())
    with tr.span("drain", cat="engine", round=3):
        pass
    tr.requests(5.0, [(0.0, None)], begin={"kind": "static"},
                end={"status": "done"})
    ev = tr.events()
    assert [e["ph"] for e in ev] == ["X", "b", "e"]
    assert ev[1]["id"] == ev[2]["id"]
    assert ev[0]["args"] == {"round": 3}
    assert ev[1]["args"] == {"kind": "static"}
    assert ev[2]["args"] == {"status": "done"}


def test_tracer_ring_buffer_bounds_memory_and_counts_drops():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_thread_safety_under_concurrent_emission():
    tr = Tracer(clock=FakeClock(), capacity=1 << 14)

    def emit():
        for _ in range(500):
            tr.instant("tick")

    threads = [threading.Thread(target=emit) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == 2000


def test_tracer_export_is_valid_trace_event_json(tmp_path):
    tr = Tracer(clock=FakeClock())
    tr.complete("dispatch", 0.0, 10.0)
    tr.requests(12.0, [(0.0, 4.0)], begin={}, end={})
    path = str(tmp_path / "trace.json")
    doc = tr.export(path)
    on_disk = json.load(open(path))
    assert on_disk == json.loads(json.dumps(doc))
    summary = validate_trace(on_disk)
    assert summary["request_begun"] == summary["request_closed"] == 1
    assert summary["request_close_fraction"] == 1.0
    # the metadata record names the process for the Perfetto UI
    assert on_disk["traceEvents"][0]["ph"] == "M"


def test_validate_trace_rejects_malformed_events():
    with pytest.raises(ValueError):
        validate_trace({"nope": []})
    bad_x = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0.0,
                              "pid": 1, "tid": 1}]}       # missing dur
    with pytest.raises(ValueError):
        validate_trace(bad_x)
    bad_async = {"traceEvents": [{"name": "a", "ph": "b", "ts": 0.0,
                                  "pid": 1, "tid": 1}]}   # missing id
    with pytest.raises(ValueError):
        validate_trace(bad_async)
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"name": "a", "ph": "??", "ts": 0.0,
                                         "pid": 1, "tid": 1}]})


def test_unclosed_request_span_lowers_close_fraction():
    def ev(ph, rid):
        return {"name": "request", "ph": ph, "cat": "request", "ts": 0.0,
                "pid": 1, "tid": 1, "id": rid}

    s = validate_trace({"traceEvents": [ev("b", 1), ev("b", 2),
                                        ev("e", 1)]})
    assert s["request_begun"] == 2 and s["request_closed"] == 1
    assert s["request_close_fraction"] == 0.5


# ----------------------------------------------------------------------- #
# metrics registry
# ----------------------------------------------------------------------- #
def test_counter_gauge_basics_and_idempotent_getters():
    reg = Registry()
    c = reg.counter("esam_test_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert reg.counter("esam_test_total").value == 3.5   # same instrument
    with pytest.raises(AssertionError):
        c.inc(-1)
    g = reg.gauge("esam_depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    with pytest.raises(ValueError):
        reg.gauge("esam_test_total")                     # kind mismatch


def test_labeled_series_are_independent():
    reg = Registry()
    reg.counter("esam_served_total", kind="static").inc(3)
    reg.counter("esam_served_total", kind="event").inc(4)
    assert reg.counter("esam_served_total", kind="static").value == 3
    assert reg.counter("esam_served_total", kind="event").value == 4
    snap = reg.snapshot()
    assert snap['esam_served_total{kind="event"}']["value"] == 4


def test_histogram_quantiles_without_storing_samples():
    reg = Registry()
    h = reg.histogram("esam_lat_seconds")
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-4, 1e-1, size=2000)
    for s in samples:
        h.observe(float(s))
    assert h.count == 2000
    assert h.sum == pytest.approx(samples.sum(), rel=1e-9)
    # log-bucketed (factor-2 bounds): estimates land within 2x of truth
    for q in (0.5, 0.95, 0.99):
        true = np.quantile(samples, q)
        est = h.quantile(q)
        assert true / 2 <= est <= true * 2, (q, true, est)


def test_histogram_cumulative_buckets_are_monotone_with_inf_tail():
    reg = Registry()
    h = reg.histogram("esam_h", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    cum = h.cumulative_buckets()
    assert [c for _, c in cum] == [1, 2, 3, 4]
    assert np.isinf(cum[-1][0])


def test_prometheus_text_exposition_format():
    reg = Registry()
    reg.counter("esam_req_total", "requests").inc(5)
    reg.gauge("esam_depth", "queue depth").set(2)
    h = reg.histogram("esam_lat", "latency", bounds=(1.0, 2.0))
    h.observe(1.5)
    text = reg.prometheus_text()
    assert "# HELP esam_req_total requests" in text
    assert "# TYPE esam_req_total counter" in text
    assert "esam_req_total 5.0" in text
    assert "# TYPE esam_lat histogram" in text
    assert 'esam_lat_bucket{le="1.0"} 0' in text
    assert 'esam_lat_bucket{le="2.0"} 1' in text
    assert 'esam_lat_bucket{le="+Inf"} 1' in text
    assert "esam_lat_sum 1.5" in text
    assert "esam_lat_count 1" in text
    assert text.endswith("\n")


def test_default_bounds_cover_microseconds_to_minutes():
    assert DEFAULT_BOUNDS[0] == pytest.approx(1e-6)
    assert DEFAULT_BOUNDS[-1] > 60.0
    assert all(b2 / b1 == pytest.approx(2.0)
               for b1, b2 in zip(DEFAULT_BOUNDS, DEFAULT_BOUNDS[1:]))


# ----------------------------------------------------------------------- #
# http scrape endpoint
# ----------------------------------------------------------------------- #
def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return r.status, r.headers.get("Content-Type"), r.read()


def test_metrics_server_serves_prometheus_json_trace_and_health():
    reg = Registry()
    reg.counter("esam_req_total").inc(3)
    tr = Tracer(clock=FakeClock())
    tr.instant("tick")
    with MetricsServer(reg, port=0, tracer=tr) as srv:
        port = srv.port
        status, ctype, body = _get(port, "/metrics")
        assert status == 200 and ctype == PROMETHEUS_CONTENT_TYPE
        assert b"esam_req_total 3.0" in body
        status, ctype, body = _get(port, "/metrics.json")
        assert status == 200 and ctype == "application/json"
        assert json.loads(body)["esam_req_total"]["value"] == 3.0
        status, _, body = _get(port, "/trace.json")
        assert status == 200
        validate_trace(json.loads(body))
        status, _, body = _get(port, "/healthz")
        assert status == 200 and body == b"ok\n"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/nope")
        assert ei.value.code == 404
    assert srv.port is None                  # stopped


def test_metrics_server_scrape_while_writing():
    reg = Registry()
    c = reg.counter("esam_live_total")
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            c.inc()

    t = threading.Thread(target=writer)
    t.start()
    try:
        with MetricsServer(reg, port=0) as srv:
            for _ in range(5):
                status, _, body = _get(srv.port, "/metrics")
                assert status == 200 and b"esam_live_total" in body
    finally:
        stop.set()
        t.join()


# ----------------------------------------------------------------------- #
# device profiling hooks
# ----------------------------------------------------------------------- #
class FakeJaxProfiler:
    def __init__(self, fail=False):
        self.fail = fail
        self.started = []
        self.stopped = 0

    def start_trace(self, logdir):
        if self.fail:
            raise RuntimeError("no backend")
        self.started.append(logdir)

    def stop_trace(self):
        self.stopped += 1


def test_device_profiler_captures_exact_round_window():
    reg = Registry()
    fake = FakeJaxProfiler()
    prof = DeviceProfiler("/tmp/x", skip_rounds=2, n_rounds=3,
                          registry=reg, profiler=fake)
    for i in range(10):
        prof.on_round_start(i)
        prof.on_round_end(i)
    assert fake.started == ["/tmp/x"]
    assert fake.stopped == 1
    assert prof.captured == 3 and prof.done and not prof.active
    assert reg.get("esam_profile_rounds_captured").value == 3
    prof.stop()                              # idempotent
    assert fake.stopped == 1


def test_device_profiler_failure_never_raises_into_the_drain():
    prof = DeviceProfiler("/tmp/x", profiler=FakeJaxProfiler(fail=True))
    prof.on_round_start(0)                   # must not raise
    assert prof.done and prof.error is not None
    prof.on_round_end(0)
    assert prof.captured == 0


def test_record_warmup_times_flattens_nested_engine_shapes():
    reg = Registry()
    record_warmup_times(reg, {"static": {8: 0.5, 16: 0.25},
                              "event_t4": {8: 0.125},
                              "telemetry_s": 0.0625, "total_s": 1.0})
    assert reg.get("esam_warmup_compile_seconds",
                   shape="static_b8").value == 0.5
    assert reg.get("esam_warmup_compile_seconds",
                   shape="event_t4_b8").value == 0.125
    assert reg.get("esam_warmup_compile_seconds",
                   shape="total_s").value == 1.0


# ----------------------------------------------------------------------- #
# engine integration: spans close + counters reconcile with stats()
# ----------------------------------------------------------------------- #
def _obs():
    return Observability.enabled(registry=Registry())


def test_engine_trace_covers_lifecycle_and_closes_every_request():
    obs = _obs()
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, telemetry=True,
                      observability=obs)
    eng.serve(_mixed(10, [(3, 2)]))
    summary = validate_trace(obs.tracer.export())
    assert summary["request_begun"] == 13
    assert summary["request_close_fraction"] == 1.0
    for phase in ("queue", "engine.pack", "engine.dispatch",
                  "engine.device_drain", "engine.telemetry_flush"):
        assert phase in REQUEST_PHASES
        assert summary["phases"].get(phase, 0) > 0, (phase, summary["phases"])
    assert summary["phases"]["engine.round"] == eng.stats()["dispatch_rounds"]
    assert summary["phases"]["engine.serve"] == 1
    assert summary["phases"]["engine.flush"] == 1


def test_engine_metrics_reconcile_with_stats():
    obs = _obs()
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, telemetry=True,
                      observability=obs)
    eng.serve(_mixed(12, [(4, 2), (2, 4)]))
    st = eng.stats()
    snap = obs.metrics.snapshot()

    def v(name):
        return snap[name]["value"]

    assert v("esam_requests_submitted_total") == 18
    assert v('esam_requests_served_total{kind="static"}') == st["n_requests"]
    assert (v('esam_requests_served_total{kind="event"}')
            == st["n_event_requests"])
    assert v("esam_timesteps_served_total") == st["timesteps_total"]
    assert v("esam_dispatch_rounds_total") == st["dispatch_rounds"]
    assert v("esam_rows_real_total") == st["rows_real_total"]
    assert v("esam_rows_padded_total") == st["rows_padded_total"]
    assert v("esam_fused_rounds_total") == st["fused_rounds"]
    assert v("esam_rounds_saved_total") == st["rounds_saved"]
    # energy/cycles counters inc with exactly the float64 sums stats() folds
    total_energy = (st["energy_pj_per_inf"] * st["n_requests"]
                    + st["event_energy_pj_mean"] * st["n_event_requests"])
    assert v("esam_energy_pj_total") == pytest.approx(total_energy)
    assert snap["esam_request_latency_seconds"]["count"] == 18
    assert v("esam_queue_depth") == 0


def test_engine_rejection_and_shed_paths_are_counted_and_closed():
    obs = _obs()
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, telemetry=False,
                      queue_limit=4, observability=obs)
    reqs = _spike_reqs(8)
    eng.submit(reqs)                         # queue of 4: half rejected
    st_depth = eng.queue_depth()
    assert st_depth == 4
    for r in reqs[:4]:
        r.deadline_s = -1.0                  # already expired => shed
    eng.serve()
    snap = obs.metrics.snapshot()
    assert snap["esam_requests_rejected_total"]["value"] == 4
    assert snap["esam_requests_shed_total"]["value"] == 4
    summary = validate_trace(obs.tracer.export())
    # every admitted request closed (shed is a terminal transition)
    assert summary["request_close_fraction"] == 1.0
    names = {e["name"] for e in obs.tracer.events()}
    assert "rejected" in names and "shed" in names


def test_engine_ladder_transitions_traced_and_counted():
    from repro.serve.overload import DegradationLadder
    obs = _obs()
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, telemetry=True,
                      observability=obs,
                      ladder=DegradationLadder.default(4))
    eng.submit(_spike_reqs(40))              # depth 40 >> 2*max_batch
    eng.serve()
    st = eng.stats()
    if st["ladder_transitions"]:             # depends on drain pacing
        snap = obs.metrics.snapshot()
        assert (snap["esam_ladder_transitions_total"]["value"]
                == st["ladder_transitions"])
        names = {e["name"] for e in obs.tracer.events()}
        assert "ladder_transition" in names


def test_engine_profiler_hooks_called_per_round():
    fake = FakeJaxProfiler()
    reg = Registry()
    obs = Observability(
        tracer=None, metrics=reg,
        profile=DeviceProfiler("/tmp/p", skip_rounds=0, n_rounds=2,
                               registry=reg, profiler=fake))
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, telemetry=False,
                      observability=obs)
    eng.serve(_spike_reqs(12))
    assert obs.profile.captured == 2 and obs.profile.done
    assert fake.stopped == 1


def test_engine_warmup_books_compile_time_gauges():
    obs = _obs()
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, telemetry=True,
                      observability=obs)
    eng.warmup(event_ts=(2,))
    total = obs.metrics.get("esam_warmup_compile_seconds", shape="total_s")
    assert total is not None and total.value > 0
    names = {e["name"] for e in obs.tracer.events()}
    assert "warmup_done" in names


# ----------------------------------------------------------------------- #
# router integration
# ----------------------------------------------------------------------- #
def test_router_counters_mirrored_into_registry_on_crash():
    obs = _obs()
    engines = [SpikeEngine(_net(), interpret=True, max_batch=8,
                           telemetry=True, observability=obs)
               for _ in range(2)]
    crashed = []

    def hook(round_idx):
        if not crashed:
            crashed.append(round_idx)
            raise ReplicaCrashError("chaos")

    engines[0].round_hook = hook
    router = FaultAwareRouter(
        engines, health_threshold=0.0, observability=obs,
        retry=ft.RetryPolicy(base_backoff_s=1e-4), sleep=lambda s: None)
    reqs = _spike_reqs(6)
    router.serve(reqs)
    st = router.stats()
    assert st["crashes"] == 1 and st["retries"] > 0
    snap = obs.metrics.snapshot()
    assert snap["esam_router_crashes_total"]["value"] == st["crashes"]
    assert snap["esam_router_retries_total"]["value"] == st["retries"]
    assert snap["esam_router_replicas_down"]["value"] == 1
    names = {e["name"] for e in obs.tracer.events()}
    assert {"replica_crash", "reroute", "router.replica_drain"} <= names
    assert all(r.status == "done" for r in reqs)


# ----------------------------------------------------------------------- #
# versioned stats schema (satellite a)
# ----------------------------------------------------------------------- #
def test_stats_schema_matches_stats_keys_exactly():
    schema = stats_schema()
    documented = {k for section in schema.values() for k in section}
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, telemetry=True)
    eng.serve(_mixed(6, [(2, 2)]))
    st = eng.stats()
    assert set(st) == documented, (
        f"stats() and stats_schema() diverged; bump STATS_SCHEMA_VERSION "
        f"and update the schema. only_in_stats={set(st) - documented} "
        f"only_in_schema={documented - set(st)}")
    assert st["stats_schema_version"] == STATS_SCHEMA_VERSION


def test_stats_schema_ci_grepped_keys_stay_stable():
    """The keys CI scripts and the launcher grep today, frozen at v1 —
    removing or renaming any is a breaking change that must bump
    STATS_SCHEMA_VERSION."""
    frozen_v1 = {
        "n_requests", "data_parallel", "cell", "fuse_rounds", "overlap",
        "rounds_saved", "fused_rounds", "rounds_static",
        "throughput_pipelined_inf_s", "energy_pj_per_inf",
        "latency_ns_mean", "cycles_mean", "n_event_requests",
        "timesteps_total", "energy_pj_per_timestep", "event_energy_pj_mean",
        "event_latency_ns_mean", "event_cycles_mean", "health",
        "tile_health", "degraded", "dispatch_rounds", "straggler_rounds",
        "queue_depth", "shed_deadline", "rejected_full",
        "backpressure_events", "ladder_transitions",
        "ladder_transition_log", "degradation_level", "pad_fraction",
    }
    documented = {k for section in stats_schema().values() for k in section}
    missing = frozen_v1 - documented
    assert not missing, f"v1 stats keys went missing: {missing}"
    assert STATS_SCHEMA_VERSION == 1


def test_stats_schema_returns_fresh_copy():
    a = stats_schema()
    a["identity"]["n_requests"] = "mutated"
    assert stats_schema()["identity"]["n_requests"] != "mutated"
