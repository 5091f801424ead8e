"""Spans on the profiler's clock, queue wait, and compile attribution.

``Tracer.span`` enters a ``jax.profiler.TraceAnnotation``, so the engine's
and ``train_online``'s phases land in a profiler capture next to the device
work; the engine books each request's queue wait whether or not it traces;
``attribute_compiles`` books every compile to the span that triggered it.
"""

import argparse
import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring   # the listener lists live here

from repro.obs import Observability, Registry, Tracer
from repro.obs import profile as obs_profile
from repro.obs.metrics import FINE_BOUNDS
from repro.obs.trace import current_span, validate_trace
from repro.serve.engine import SpikeEngine, _stats_jit

from test_async_serve import _mixed, _net, _spike_reqs


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _listening() -> bool:
    return (obs_profile._on_compile
            in monitoring.get_event_time_span_listeners())


# ----------------------------------------------------------------------- #
# the span API
# ----------------------------------------------------------------------- #
def test_span_stack_is_per_thread():
    tr = Tracer(clock=FakeClock())
    seen = {}

    def other():
        seen["other"] = current_span()

    with tr.span("engine.serve"):
        with tr.span("engine.flush"):
            assert current_span() == "engine.flush"
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert current_span() == "engine.serve"
    assert seen["other"] is None
    assert current_span() is None
    names = [e["name"] for e in tr.events()]
    assert names == ["engine.flush", "engine.serve"]   # closed inner first


def test_span_closes_and_pops_on_error():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("engine.round", round=0):
            raise RuntimeError("crash mid-round")
    assert current_span() is None
    (ev,) = tr.events()
    assert ev["name"] == "engine.round" and ev["args"] == {"round": 0}


def test_request_lifecycles_expand_to_their_events():
    tr = Tracer(clock=FakeClock(), capacity=2)
    tr.requests(40.0, [(10.0, 25.0), (30.0, None)], begin={"kind": "s"},
                end={"status": "done"})
    tr.requests(50.0, [], begin={}, end={})  # nothing to record
    tr.requests(72.0, [(70.0, 71.0)], begin={}, end={"status": "shed"})
    assert len(tr) == 2                      # one ring entry per batch
    ev = tr.events()
    assert [(e["name"], e["ph"]) for e in ev] == [
        ("request", "b"), ("queue", "X"), ("request", "e"),
        ("request", "b"), ("request", "e"),
        ("request", "b"), ("queue", "X"), ("request", "e")]
    assert ev[1]["ts"] == 10.0 and ev[1]["dur"] == 15.0
    assert ev[0]["id"] == ev[2]["id"] == ev[1]["args"]["req"]
    assert len({e["id"] for e in ev if e["ph"] == "b"}) == 3
    s = validate_trace(tr.export())
    assert s["request_begun"] == 3 and s["request_close_fraction"] == 1.0
    tr.instant("x")                          # a full ring evicts the oldest
    assert tr.dropped == 1 and len(tr) == 2


# ----------------------------------------------------------------------- #
# engine spans in a real profiler capture
# ----------------------------------------------------------------------- #
def _line_spans(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):   # a line per thread
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("engine.")]
            if evs:
                out[(plane.name, i)] = evs
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_nest_on_the_callers_profiler_line(tmp_path):
    eng = SpikeEngine(_net(), interpret=True, max_batch=8, telemetry=True,
                      observability=Observability(tracer=Tracer()))
    eng.serve(_mixed(4, [(2, 2)]))           # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(_mixed(12, [(3, 2)], seed=1))
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _line_spans(path)
    callers = [evs for evs in lines.values()
               if any(n == "engine.serve" for n, _, _ in evs)]
    assert len(callers) == 1
    evs = callers[0]
    (serve,) = [e for e in evs if e[0] == "engine.serve"]
    rounds = [e for e in evs if e[0] == "engine.round"]
    (flush,) = [e for e in evs if e[0] == "engine.flush"]
    drains = [e for e in evs if e[0] == "engine.device_drain"]
    dispatches = [e for e in evs if e[0] == "engine.dispatch"]
    assert len(rounds) == eng.stats()["dispatch_rounds"] - 2 > 0
    assert len(dispatches) == len(rounds)
    # a static round pulls its per-tile totals after the attach, in a
    # second drain: 12 static requests at max_batch 8 make two such rounds
    assert len(drains) == len(rounds) + 2
    assert all(_inside(r, serve) for r in rounds)
    assert _inside(flush, serve)
    assert all(_inside(d, flush) for d in drains)
    assert all(any(_inside(d, r) for r in rounds) for d in dispatches)


def test_overlapped_pack_spans_run_on_the_packer_line(tmp_path):
    eng = SpikeEngine(_net(), interpret=True, max_batch=4, overlap=True,
                      observability=Observability(tracer=Tracer()))
    eng.serve(_spike_reqs(8))
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(_spike_reqs(16, seed=3))
    eng.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _line_spans(path)
    caller = [k for k, evs in lines.items()
              if any(n == "engine.serve" for n, _, _ in evs)]
    packer = [k for k, evs in lines.items()
              if any(n == "engine.pack" for n, _, _ in evs)]
    assert len(caller) == 1 and len(packer) == 1 and caller != packer


# ----------------------------------------------------------------------- #
# queue wait
# ----------------------------------------------------------------------- #
def test_queue_wait_p90_from_fine_bounds_matches_the_sample():
    reg = Registry()
    h = reg.histogram("esam_request_queue_seconds", bounds=FINE_BOUNDS)
    sample = np.random.default_rng(7).lognormal(np.log(4e-3), 0.6, 20000)
    for v in sample:
        h.observe(v)
    want = float(np.percentile(sample, 90))
    assert abs(h.quantile(0.9) - want) <= 0.05 * want
    batched = Registry().histogram("b", bounds=FINE_BOUNDS)
    batched.observe_many(sample)
    assert batched.cumulative_buckets() == h.cumulative_buckets()
    assert batched.sum == pytest.approx(h.sum)
    snap = reg.snapshot()["esam_request_queue_seconds"]
    assert snap["p90"] == h.quantile(0.9)
    assert all(b / a <= 2 ** (1 / 8) + 1e-12
               for a, b in zip(FINE_BOUNDS, FINE_BOUNDS[1:]))


@pytest.mark.parametrize("tracer", [False, True])
def test_engine_books_queue_wait_with_or_without_the_tracer(tracer):
    clk = FakeClock()
    reg = Registry()
    obs = Observability(tracer=Tracer(clock=clk) if tracer else None,
                        metrics=reg)
    eng = SpikeEngine(_net(), interpret=True, max_batch=8,
                      observability=obs)
    if not tracer:   # the host clock: stamp through the same path
        eng._obs_now = lambda: clk() * 1e6
    eng.submit(_spike_reqs(12))
    clk.advance(0.25)
    eng.serve()
    q = reg.snapshot()["esam_request_queue_seconds"]
    assert q["count"] == 12
    assert abs(q["p90"] - 0.25) <= 0.25 * (2 ** (1 / 8) - 1)
    lat = reg.snapshot()["esam_request_latency_seconds"]
    assert lat["count"] == 12
    assert eng._req_spans == {}
    if tracer:
        queue = [e for e in obs.tracer.events() if e["name"] == "queue"]
        assert len(queue) == 12
        assert all(e["dur"] == pytest.approx(0.25e6) for e in queue)


# ----------------------------------------------------------------------- #
# compile attribution
# ----------------------------------------------------------------------- #
def test_first_jit_inside_a_span_books_one_backend_compile():
    reg = Registry()
    tr = Tracer()

    def fresh(x):
        return x * 3.0 + 1.0

    x = np.ones(5, np.float32)
    assert not _listening()
    with obs_profile.attribute_compiles(reg):
        assert _listening()
        with tr.span("x"):
            jax.block_until_ready(jax.jit(fresh)(x))
    assert not _listening()
    got = reg.get("esam_compiles_total", span="x", event="backend_compile")
    assert got is not None and got.value == 1
    secs = reg.get("esam_compile_seconds_total", span="x",
                   event="backend_compile")
    assert secs.value > 0
    for event in ("jaxpr_trace", "lowering"):
        assert reg.get("esam_compiles_total", span="x",
                       event=event).value >= 1


def test_compiles_outside_any_span_book_under_none():
    reg = Registry()
    with obs_profile.attribute_compiles(reg):
        jax.block_until_ready(jax.jit(lambda x: x - 7.0)(
            np.ones(3, np.float32)))
    assert reg.get("esam_compiles_total", span="none",
                   event="backend_compile").value == 1


def test_one_listener_shared_by_nested_and_concurrent_registries():
    a, b = Registry(), Registry()
    n0 = len(monitoring.get_event_time_span_listeners())
    with obs_profile.attribute_compiles(a):
        with obs_profile.attribute_compiles(b):
            with obs_profile.attribute_compiles(a):
                assert len(monitoring.get_event_time_span_listeners()) \
                    == n0 + 1
                jax.block_until_ready(jax.jit(lambda x: x / 5.0)(
                    np.ones(4, np.float32)))
        assert _listening()
    assert not _listening()
    assert len(monitoring.get_event_time_span_listeners()) == n0
    for reg in (a, b):
        assert reg.get("esam_compiles_total", span="none",
                       event="backend_compile").value == 1
    with obs_profile.attribute_compiles(None):
        assert not _listening()


def test_engine_books_an_unwarmed_shape_to_its_serve():
    reg = Registry()
    eng = SpikeEngine(_net(), interpret=True, max_batch=8,
                      observability=Observability(tracer=Tracer(),
                                                  metrics=reg))
    eng.serve(_spike_reqs(3))
    snap = reg.snapshot()
    keys = [k for k in snap if k.startswith("esam_compiles_total")]
    assert keys and all('span="engine.' in k or 'span="none"' in k
                        for k in keys)
    assert any('span="engine.dispatch"' in k for k in keys)
    assert not _listening()


def test_train_online_books_compiles_to_its_steps():
    from test_online_plane import _driver_fixture

    from repro.train.online import train_online

    net, x, y = _driver_fixture()
    reg = Registry()
    obs = Observability(tracer=Tracer(), metrics=reg)
    res = train_online(net, x[:64], y[:64], epochs=1,
                       key=jax.random.PRNGKey(9), observability=obs)
    assert res.epochs_run == 1
    names = {e["name"] for e in obs.tracer.events()}
    assert {"train.plan", "train.prefix", "train.epoch",
            "train.eval"} <= names
    booked = {k for k, v in reg.snapshot().items()
              if k.startswith("esam_compile_seconds_total") and v["value"]}
    assert booked and all('span="train.' in k or 'span="none"' in k
                          for k in booked)
    assert not _listening()


def test_chunked_train_online_lowers_its_prefix_plan_once():
    """Each chunk hands ``train_online`` the network the last one learned,
    derived from the first, and its prefix plan takes the executable the
    first chunk built: chunks 2 and 3 lower nothing under ``train.prefix``,
    book one ``plan_shared`` each under ``train.plan``, and learn what plans
    built with nothing shared learn."""
    import dataclasses

    from test_online_plane import _driver_fixture

    from repro.train.online import train_online

    net0, x, y = _driver_fixture()
    n = 40

    def run(obs=None, unshared=False):
        net, out = net0, []
        for c in range(3):
            if unshared:
                net = dataclasses.replace(net, _executables={})
            res = train_online(net, x[c * n:(c + 1) * n],
                               y[c * n:(c + 1) * n], epochs=1,
                               key=jax.random.PRNGKey(20 + c),
                               observability=obs)
            net = res.network
            out.append((np.asarray(net.weight_bits[-1]), res.n_updates))
            if obs is not None:
                lowered = obs.metrics.get("esam_compiles_total",
                                          span="train.prefix",
                                          event="lowering")
                shared = obs.metrics.get("esam_compiles_total",
                                         span="train.plan",
                                         event="plan_shared")
                booked.append((lowered.value,
                               0 if shared is None else shared.value))
        return out

    booked = []
    got = run(Observability(tracer=Tracer(), metrics=Registry()))
    assert booked[0][0] >= 1
    assert [lo for lo, _ in booked] == [booked[0][0]] * 3
    assert [sh for _, sh in booked] == [0, 1, 2]
    assert all(upd[0] > 0 for _, upd in got)
    want = run(unshared=True)
    for (bits, upd), (want_bits, want_upd) in zip(got, want):
        np.testing.assert_array_equal(bits, want_bits)
        assert upd == want_upd


# ----------------------------------------------------------------------- #
# stable names on the device work, and the launcher's profiled runs
# ----------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["packed", "prefix"])
def test_plan_executables_have_stable_names(mode):
    plan = _net().plan(mode=mode)
    head = plan.lower(8).as_text().splitlines()[0]
    assert head.startswith(f"module @jit_esam_plan_{mode} ")


@pytest.mark.parametrize("temporal,name", [(False, "esam_request_stats"),
                                           (True, "esam_stream_stats")])
def test_telemetry_executable_has_a_stable_name(temporal, name):
    net = _net()
    if temporal:
        from repro.core.esam.temporal import TemporalConfig

        res = net.plan(mode="temporal", telemetry=True,
                       temporal=TemporalConfig(n_steps=2))(
            jnp.zeros((2, 8, 4), jnp.uint32))
    else:
        res = net.plan(mode="packed", telemetry=True)(
            jnp.zeros((8, 4), jnp.uint32))
    txt = _stats_jit(net.topology, 4, temporal).lower(res.loads).as_text()
    assert txt.splitlines()[0].startswith(f"module @jit_{name} ")


def test_launcher_traces_whenever_it_profiles(tmp_path):
    from repro.launch.serve import _build_observability

    args = argparse.Namespace(
        trace_out=None, metrics_port=None, report_json=None,
        profile_rounds=2, profile_dir=str(tmp_path), profile_skip=0)
    obs, server = _build_observability(args)
    assert server is None
    assert obs.tracer is not None and obs.profile is not None
    args.profile_rounds = 0
    assert _build_observability(args) == (None, None)
