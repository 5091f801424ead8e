"""The trace reduction and the roofline arithmetic, checked against values
worked out by hand: on a small synthetic profile, and on a trace recorded on
the chip (``bench/tests/data``)."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=evs) for ln, evs in lines])


def _profile():
    host = _plane("/host:CPU", [("python3", [
        _ev("bench.window", 1000, 10000),
        _ev("bench.serve", 2000, 4000),
        _ev("bench.idle_wait", 6000, 3000),
        _ev("PjitFunction(fn)", 2100, 50)])])
    tpu0 = _plane("/device:TPU:0", [
        ("XLA Modules", [_ev("jit_fn(1)", 1400, 1700)]),
        ("XLA Ops", [
            _ev("%esam_cascade_popcount.1 = (s32[64,128]) custom-call()",
                1500, 1000),
            _ev("%copy.2 = u32[64,24] copy()", 2400, 600),
            _ev("%while.1 = (s32[]) while()", 7000, 1000),
            _ev("%fusion.3 = u32[16,8] fusion()", 7100, 200),
            _ev("%copy.5 = f32[64,10] copy()", 10500, 1500),
            _ev("%copy.9 = f32[64,10] copy()", 100, 200)])])
    tpu1 = _plane("/device:TPU:1", [
        ("XLA Ops", [_ev("%esam_cascade_popcount.7 = () custom-call()",
                         1000, 1000)])])
    return types.SimpleNamespace(planes=[host, tpu0, tpu1])


def test_synthetic_profile_by_hand():
    red = trace_reduce.reduce_profile(_profile(), n_chips=2)
    # window: the bench.window span, 1000..11000 ns
    assert red["window_s"] == pytest.approx(10000e-9)
    # chip 0: [1500,3000] + [7000,8000] + [10500,11000] (clipped) = 3000 ns
    # chip 1: [1000,2000] = 1000 ns; busy is their mean
    assert red["per_chip_busy_s"]["/device:TPU:0"] == pytest.approx(3000e-9)
    assert red["per_chip_busy_s"]["/device:TPU:1"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(2000e-9)
    assert red["idle_share"] == pytest.approx(0.8)
    # per-op time: instance numbers dropped, the while container left out,
    # the op outside the window left out, the one across its end clipped
    assert red["op_s"]["esam_cascade_popcount"] == pytest.approx(2000e-9)
    assert red["op_calls"]["esam_cascade_popcount"] == 2
    assert red["op_s"]["copy"] == pytest.approx(1100e-9)
    assert red["op_s"]["fusion"] == pytest.approx(200e-9)
    assert "while" not in red["op_s"]
    s, n = trace_reduce.kernel_seconds(red, ("esam_cascade_popcount",))
    assert (s, n) == (pytest.approx(2000e-9), 2)
    # gaps are named by the innermost host span around their middle: chip
    # 1's [2000,11000] by bench.idle_wait, chip 0's [3000,7000] by
    # bench.serve, and chip 0's [8000,10500] by the window itself
    assert red["top_gaps"][0] == ["bench.idle_wait", pytest.approx(9000e-9)]
    assert red["top_gaps"][1] == ["bench.serve", pytest.approx(4000e-9)]
    names = [g[0] for g in red["top_gaps"]]
    assert "bench.window" in names


def test_roofline_arithmetic_by_hand():
    """20 cascade calls of 64 rows in 38 µs of kernel time on one chip."""
    red = {"op_s": {"esam_cascade_popcount": 38e-6},
           "op_calls": {"esam_cascade_popcount": 20}}
    rec = {"trace": red, "topology": [768, 256, 256, 256, 10],
           "peaks": work.peaks("TPU v5 lite"),
           "engine": {"n_requests": 1280}}
    mod = harness.load_module(os.path.join(BENCH, "metrics",
                                           "cascade_roofline.py"), "m_casc")
    ops = 2 * 1280 * 330240
    nbytes = 1280 * (96 + 40) + 20 * 41280
    least = max(ops / 393e12, nbytes / 819e9)
    assert least == pytest.approx(ops / 393e12)  # compute-bound
    share, bound = mod.read(rec)
    assert share == pytest.approx(100 * least / 38e-6)
    assert 5.6 < share < 5.7 and bound == "compute"


def test_roofline_bound_is_memory_for_small_calls():
    """20 calls of 8 rows: the weight bits read once per call outweigh the
    work, so the bytes set the least time."""
    red = {"op_s": {"esam_cascade_popcount": 20e-6},
           "op_calls": {"esam_cascade_popcount": 20}}
    rec = {"trace": red, "topology": [768, 256, 256, 256, 10],
           "peaks": work.peaks("TPU v5 lite"),
           "engine": {"n_requests": 160}}
    mod = harness.load_module(os.path.join(BENCH, "metrics",
                                           "cascade_roofline.py"), "m_casc3")
    nbytes = 160 * (96 + 40) + 20 * 41280
    assert nbytes / 819e9 > 2 * 160 * 330240 / 393e12
    share, bound = mod.read(rec)
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 20e-6)


def test_idle_share_and_mfu_readers():
    idle = harness.load_module(os.path.join(
        BENCH, "metrics", "device.idle_share.serve.py"), "m_idle")
    mfu = harness.load_module(os.path.join(
        BENCH, "metrics", "mfu.serve.py"), "m_mfu")
    assert idle.read({"trace": {"busy_s": 0.25, "window_s": 10.0}}) == \
        pytest.approx(97.5)
    rec = {"macs": 330240 * 100000, "window_s": 10.0, "chips": 4,
           "peaks": work.peaks("TPU v5 lite")}
    assert mfu.read(rec) == pytest.approx(
        100 * 2 * 330240 * 1e4 / (4 * 393e12))
    lag = harness.load_module(os.path.join(
        BENCH, "metrics", "client.wake_lag_p99_ms.py"), "m_lag")
    assert lag.read({}) is None
    assert lag.read({"wake_lags_s": []}) == 0.0  # never idle, never late
    assert lag.read({"wake_lags_s": [0.001] * 99 + [0.1]}) == \
        pytest.approx(1e3 * (0.001 + 0.01 * 0.099))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """``if_static_p80`` traced for 0.3 s on one TPU v5 lite (6,240
    requests); gzip-compressed to keep the repository small."""
    import gzip
    import shutil

    out = tmp_path_factory.mktemp("trace") / "if_static_p80.xplane.pb"
    with gzip.open(os.path.join(DATA, "if_static_p80.xplane.pb.gz")) as f, \
            open(out, "wb") as g:
        shutil.copyfileobj(f, g)
    return trace_reduce.reduce(str(out), n_chips=1)


def test_recorded_trace_by_hand(recorded):
    """Values read off the trace by hand: the bench.window host span, the
    union of the 2,075 XLA Ops intervals on /device:TPU:0 inside it, and the
    63 ``%esam_cascade_popcount`` custom calls."""
    red = recorded
    assert red["chips_traced"] == 1
    assert red["window_s"] == pytest.approx(311_803_530e-9)
    assert red["busy_s"] == pytest.approx(385_951e-9)
    assert red["idle_share"] == pytest.approx(1 - 385_951 / 311_803_530)
    secs, calls = trace_reduce.kernel_seconds(red, ("esam_cascade_popcount",))
    assert calls == 63 and secs == pytest.approx(158_412e-9)
    assert red["top_ops"][0][0] == "esam_cascade_popcount"
    assert {g[0] for g in red["top_gaps"]} <= {"bench.serve", "bench.submit",
                                              "bench.window"}


def test_recorded_trace_roofline(recorded):
    """The run served 6,240 requests: 2 x 6,240 x 330,240 int8 ops in
    158.412 µs of cascade time, compute-bound, read 6.620086% on the chip."""
    mod = harness.load_module(os.path.join(BENCH, "metrics",
                                           "cascade_roofline.py"), "m_casc2")
    rec = {"trace": recorded, "topology": [768, 256, 256, 256, 10],
           "peaks": work.peaks("TPU v5 lite"), "engine": {"n_requests": 6240}}
    least = 2 * 6240 * 330240 / 393e12
    assert 6240 * 136 + 63 * 41280 < least * 819e9  # not memory-bound
    share, bound = mod.read(rec)
    assert share == pytest.approx(100 * least / 158_412e-9)
    assert share == pytest.approx(6.620086033269512) and bound == "compute"
