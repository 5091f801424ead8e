"""The host-span reduction (``bench/span_reduce.py``), the readers of the
metrics that need the program's observability, and the probe that switches
it on: on a synthetic profile worked out by hand, on hand-made records, and
through the real program on the CPU at a test size."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tests"))

import bench_cpu  # noqa: E402
import harness  # noqa: E402
import obs_probe  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


def _profile():
    """The caller's thread serves once (10,000 ns window); the packer
    thread packs across the caller's flush, while the device is idle."""
    caller = [
        _ev("bench.window", 0, 10000),
        _ev("bench.serve", 1000, 8000),
        _ev("engine.serve", 1100, 7800),
        _ev("engine.round", 1200, 2800),
        _ev("engine.dispatch", 2000, 1000),
        _ev("engine.flush", 5000, 3500),
        _ev("engine.device_drain", 5200, 2000),
        _ev("PjitFunction(fn)", 2100, 50)]
    packer = [_ev("engine.pack", 4000, 4000)]
    host = _plane("/host:CPU", [("python3", caller), ("python3", packer)])
    tpu = _plane("/device:TPU:0", [
        ("XLA Modules", [_ev("jit_esam_plan_packed(77)", 2500, 1000),
                         _ev("jit_esam_request_stats(78)", 9500, 1000)]),
        ("XLA Ops", [_ev("%esam_cascade_popcount.1 = () custom-call()",
                         2500, 1000),
                     _ev("%copy.2 = () copy()", 9500, 1000)])])
    return types.SimpleNamespace(planes=[host, tpu])


def test_innermost_segments_by_hand():
    spans = [(0, 100, "bench.window"), (10, 90, "bench.serve"),
             (20, 40, "engine.round"), (25, 30, "engine.dispatch"),
             (60, 80, "engine.flush")]
    assert span_reduce.innermost_segments(spans, 0, 100) == [
        (0, 10, "bench.window"), (10, 20, "bench.serve"),
        (20, 25, "engine.round"), (25, 30, "engine.dispatch"),
        (30, 40, "engine.round"), (40, 60, "bench.serve"),
        (60, 80, "engine.flush"), (80, 90, "bench.serve"),
        (90, 100, "bench.window")]


def test_synthetic_profile_by_hand():
    red = span_reduce.reduce_spans(_profile())
    assert red["window_s"] == pytest.approx(10000e-9)
    # spans of every thread, clipped to the window; the packer's pack counts
    assert red["span_s"]["engine.pack"] == pytest.approx(4000e-9)
    assert red["span_s"]["engine.flush"] == pytest.approx(3500e-9)
    assert red["span_calls"]["engine.round"] == 1
    assert "PjitFunction(fn)" not in red["span_s"]
    # busy [2500,3500] and [9500,10000]: idle [0,2500], [3500,9500]; each
    # piece is named on the caller's thread, never by the packer's pack
    idle = red["idle_by_span"]
    assert idle["bench.window"] == pytest.approx((1000 + 500) * 1e-9)
    assert idle["bench.serve"] == pytest.approx((100 + 100) * 1e-9)
    assert idle["engine.serve"] == pytest.approx((100 + 1000 + 400) * 1e-9)
    assert idle["engine.round"] == pytest.approx((800 + 500) * 1e-9)
    assert idle["engine.dispatch"] == pytest.approx(500e-9)
    assert idle["engine.flush"] == pytest.approx((200 + 1300) * 1e-9)
    assert idle["engine.device_drain"] == pytest.approx(2000e-9)
    assert "engine.pack" not in idle
    assert sum(idle.values()) == pytest.approx(8500e-9)
    # the [3500,9500] gap's middle, 6500, lies in the caller's device drain
    # (the packer's pack spans it too, and must not name it); [0,2500]'s,
    # 1250, in the round before its dispatch
    assert red["top_gaps"] == [["engine.device_drain", pytest.approx(6e-6)],
                               ["engine.round", pytest.approx(2.5e-6)]]
    assert red["module_s"] == {"jit_esam_plan_packed": pytest.approx(1e-6),
                               "jit_esam_request_stats":
                                   pytest.approx(0.5e-6)}
    share = span_reduce.covered_share(red, "bench.serve", "engine.")
    assert share == pytest.approx(6800 / 7000)


def test_a_profile_without_a_window_is_refused():
    pd = _profile()
    pd.planes[0].lines[0].events = pd.planes[0].lines[0].events[1:]
    with pytest.raises(ValueError, match="bench.window"):
        span_reduce.reduce_spans(pd)


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "m_" + name.replace(".", "_"))


@pytest.mark.parametrize("name", obs_probe.READERS)
def test_readers_find_nothing_in_an_empty_record(name):
    assert _reader(name).read({}) is None


def test_queue_wait_reader_on_a_hand_made_record():
    m = _reader("engine.queue_wait_p90_ms")
    snap = {"esam_request_queue_seconds": {"count": 40, "p90": 0.00325}}
    assert m.read({"obs": snap}) == pytest.approx(3.25)
    snap["esam_request_queue_seconds"]["count"] = 0
    assert m.read({"obs": snap}) is None


def test_flush_reader_on_a_hand_made_record():
    m = _reader("engine.flush_us_per_req")
    rec = {"trace": {"span_s": {"engine.flush": 0.012}},
           "engine": {"n_requests": 300, "n_event_requests": 100}}
    assert m.read(rec) == pytest.approx(30.0)
    rec["trace"]["span_s"] = {}
    assert m.read(rec) is None       # a program with no flush span


def test_compile_reader_on_a_hand_made_record():
    m = _reader("train.compile_s_per_chunk")
    name = "esam_compile_seconds_total"
    snap = {
        f'{name}{{event="backend_compile",span="train.prefix"}}':
            {"type": "counter", "value": 0.5},
        f'{name}{{event="jaxpr_trace",span="train.plan"}}':
            {"type": "counter", "value": 0.25},
        f'{name}{{event="backend_compile",span="none"}}':
            {"type": "counter", "value": 9.0},
        'esam_compiles_total{event="backend_compile",span="train.prefix"}':
            {"type": "counter", "value": 3.0},
    }
    assert m.read({"obs": snap, "chunks": 5}) == pytest.approx(0.15)
    assert m.read({"obs": {}, "chunks": 5}) is None
    assert m.read({"obs": {"x": {"value": 1.0}}, "chunks": 5}) == 0.0


def _probe_small(monkeypatch, name):
    from repro.obs import Observability, Registry, Tracer

    obs = Observability(tracer=Tracer(), metrics=Registry())
    base = bench_cpu.driver_class(name)
    made = []

    def factory(*a):
        made.append(obs_probe.probe_driver(base, obs)(*a))
        return made[-1]

    out = bench_cpu.run_small(monkeypatch, name, driver_factory=factory)
    return out, made[0], obs


def test_probe_hands_the_engine_its_handle(monkeypatch):
    out, drv, obs = _probe_small(monkeypatch, "if_static_p80")
    assert out["correct"]
    rec = drv.result["record"]
    served = rec["engine"]["n_requests"]
    assert rec["obs"]["esam_request_queue_seconds"]["count"] == served > 0
    assert _reader("engine.queue_wait_p90_ms").read(rec) > 0
    names = {e["name"] for e in obs.tracer.events()}
    assert {"engine.serve", "engine.flush", "engine.device_drain"} <= names


def test_probe_hands_train_online_its_handle_in_the_window(monkeypatch):
    out, drv, obs = _probe_small(monkeypatch, "if_learn_stdp")
    assert out["correct"]
    rec = drv.result["record"]
    epochs = obs.metrics.get("esam_train_epochs_total").value
    assert epochs == rec["chunks"] > 0          # window calls only
    assert _reader("train.compile_s_per_chunk").read(rec) >= 0.0


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """``if_static_p80`` traced for 0.3 s on one TPU v5 lite with the
    program's handle on (``bench/obs_probe.py --seconds 0.3``; 29 serves,
    46 rounds); gzip-compressed to keep the repository small."""
    import gzip
    import shutil

    out = tmp_path_factory.mktemp("trace") / "if_static_p80_obs.xplane.pb"
    with gzip.open(os.path.join(BENCH, "tests", "data",
                                "if_static_p80_obs.xplane.pb.gz")) as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(out)


def test_recorded_trace_spans_by_hand(recorded):
    """Values read off the trace, and checked against a count of 100 ns
    slices of the window (each slice idle or busy on /device:TPU:0, and
    named by the shortest caller-thread span around it): the
    ``bench.window`` span, the program's spans on the caller's line and the
    packer's, and the idle time inside each."""
    red = span_reduce.reduce(recorded)
    assert red["window_s"] == pytest.approx(391_402_334e-9)
    assert red["span_calls"] == {
        "bench.window": 1, "bench.idle_wait": 1, "bench.submit": 29,
        "bench.serve": 29, "engine.serve": 29, "engine.flush": 29,
        "engine.round": 46, "engine.pack": 46, "engine.dispatch": 46,
        "engine.device_drain": 46, "engine.telemetry_flush": 46}
    assert red["span_s"]["engine.flush"] == pytest.approx(140_950_318e-9)
    assert red["span_s"]["engine.pack"] == pytest.approx(39_283_401e-9)
    idle = red["idle_by_span"]
    want = {"bench.window": 2_896_261, "bench.idle_wait": 90_731,
            "bench.submit": 11_079_500, "bench.serve": 878_448,
            "engine.serve": 38_860_845, "engine.round": 2_946_624,
            "engine.dispatch": 193_415_661, "engine.flush": 21_325_182,
            "engine.device_drain": 104_837_077,
            "engine.telemetry_flush": 14_788_059}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    # the packer thread's engine.pack names no idle time
    assert "engine.pack" not in idle
    # idle by span sums to the window less the busy time trace_reduce finds
    busy = trace_reduce.reduce(recorded, n_chips=1)["busy_s"]
    assert sum(idle.values()) == pytest.approx(red["window_s"] - busy)
    assert [g[0] for g in red["top_gaps"][:3]] == [
        "engine.dispatch", "engine.device_drain", "engine.device_drain"]
    assert red["top_gaps"][0][1] == pytest.approx(122_208_294e-9)
    assert set(red["module_s"]) == {"jit_esam_plan_packed",
                                    "jit_esam_request_stats"}
    share = span_reduce.covered_share(red, "bench.serve", "engine.")
    assert share == pytest.approx(0.99767, abs=1e-5)
