"""The observability off-path is bit-identical to the instrumented path.

Property (hypothesis): for any request blend and engine configuration —
including the fused super-batch path and the background packer thread
(``overlap=True``) — an engine built with ``observability=None`` produces
exactly the same outputs (logits, labels, per-request telemetry) AND the
same ``stats()`` as one built with the full tracing + metrics plane on.
Spans observe, never perturb.

Wall-clock-valued stats keys (``host_pack_s_total``, ``dispatch_s_total``,
``straggler_rounds``) are excluded: they measure the host's actual timing,
which no two runs — instrumented or not — ever reproduce bit-for-bit.
"""

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.obs import Observability
from repro.obs.metrics import Registry
from repro.serve.engine import SpikeEngine

from test_async_serve import _assert_same_results, _mixed, _net

#: stats keys that are functions of host wall time, not of the datapath
_WALL_CLOCK_KEYS = frozenset(
    {"host_pack_s_total", "dispatch_s_total", "straggler_rounds"})


def _comparable(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if k not in _WALL_CLOCK_KEYS}


def _serve(reqs, *, observability, fuse, overlap, telemetry):
    eng = SpikeEngine(_net(), interpret=True, max_batch=8,
                      telemetry=telemetry, fuse_rounds=fuse, overlap=overlap,
                      observability=observability)
    eng.serve(reqs)
    st = eng.stats()
    eng.close()
    return st


@settings(max_examples=10, deadline=None)
@given(n_static=st.integers(0, 24),
       n_ev2=st.integers(0, 6),
       n_ev4=st.integers(0, 6),
       fuse=st.sampled_from([None, 2, "auto"]),
       overlap=st.booleans(),
       telemetry=st.booleans(),
       seed=st.integers(0, 3))
def test_observability_off_path_is_bit_identical(
        n_static, n_ev2, n_ev4, fuse, overlap, telemetry, seed):
    spec = [(n_ev2, 2), (n_ev4, 4)]
    base_reqs = _mixed(n_static, spec, seed=seed)
    obs_reqs = _mixed(n_static, spec, seed=seed)

    st_base = _serve(base_reqs, observability=None, fuse=fuse,
                     overlap=overlap, telemetry=telemetry)
    obs = Observability.enabled(registry=Registry())
    st_obs = _serve(obs_reqs, observability=obs, fuse=fuse,
                    overlap=overlap, telemetry=telemetry)

    _assert_same_results(obs_reqs, base_reqs)
    assert _comparable(st_obs) == _comparable(st_base)


def test_observability_off_engine_holds_no_instruments():
    eng = SpikeEngine(_net(), interpret=True, max_batch=8)
    assert eng._obs is None and eng._tracer is None and eng._m is None
    eng.serve(_mixed(4, [(2, 2)]))
    assert eng._req_spans == {}              # nothing booked on the off path


def test_tracer_only_and_metrics_only_lanes_are_also_inert():
    """Partial bundles (tracer without metrics, metrics without tracer)
    must be exactly as inert for the datapath as the full bundle."""
    from repro.obs.trace import Tracer

    want = _mixed(8, [(3, 2)], seed=9)
    _serve(want, observability=None, fuse="auto", overlap=True,
           telemetry=True)
    for bundle in (Observability(tracer=Tracer()),
                   Observability(metrics=Registry())):
        got = _mixed(8, [(3, 2)], seed=9)
        _serve(got, observability=bundle, fuse="auto", overlap=True,
               telemetry=True)
        _assert_same_results(got, want)


def _compile_listener_registered() -> bool:
    from jax._src import monitoring

    from repro.obs import profile

    return profile._on_compile in monitoring.get_event_time_span_listeners()


def test_off_path_registers_no_compile_listener():
    """Without a handle nothing is registered with ``jax.monitoring``, not
    even while the engine serves (checked from inside each round) or while
    ``train_online`` runs."""
    from test_online_plane import _driver_fixture

    from repro.train.online import train_online

    seen = []
    eng = SpikeEngine(_net(), interpret=True, max_batch=4,
                      round_hook=lambda _: seen.append(
                          _compile_listener_registered()))
    eng.serve(_mixed(6, [(2, 2)]))
    assert seen and not any(seen)
    net, x, y = _driver_fixture()
    train_online(net, x[:32], y[:32], epochs=1)
    assert not _compile_listener_registered()


def test_train_online_spans_and_metrics_are_inert():
    """``train_online`` with every lane on learns exactly what it learns
    with none: readout bits, accuracy, update counts."""
    from test_online_plane import _driver_fixture

    from repro.obs.trace import Tracer
    from repro.train.online import train_online

    net, x, y = _driver_fixture()
    key = np.asarray([0, 17], np.uint32)
    off = train_online(net, x[:96], y[:96], epochs=2, key=key)
    obs = Observability(tracer=Tracer(), metrics=Registry())
    on = train_online(net, x[:96], y[:96], epochs=2, key=key,
                      observability=obs)
    np.testing.assert_array_equal(np.asarray(on.network.weight_bits[-1]),
                                  np.asarray(off.network.weight_bits[-1]))
    assert on.accuracy == off.accuracy and on.n_updates == off.n_updates
    spans = [e["name"] for e in obs.tracer.events() if e["ph"] == "X"]
    assert spans.count("train.epoch") == spans.count("train.eval") == 2
