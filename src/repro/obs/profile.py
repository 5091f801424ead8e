"""Device profiling hooks: ``jax.profiler`` capture + compile-time lanes.

Three profiling surfaces for the serving stack:

  * :class:`DeviceProfiler` — arms ``jax.profiler`` trace capture around a
    window of serving drain rounds (skip the first ``skip_rounds``, capture
    ``n_rounds``).  The engine calls ``on_round_start``/``on_round_end`` per
    dispatch round; the profiler starts/stops exactly once, never raises
    into the drain (a failed backend capture is recorded in ``error``
    instead — profiling must not take down serving), and books the captured
    window into the metrics registry.  The engine's ``Tracer`` spans land
    in the same capture (``Tracer.span`` enters a profiler annotation).
  * :func:`record_warmup_times` — folds ``SpikeEngine.warmup()`` /
    ``EsamPlan.warmup()`` per-shape compile seconds into registry gauges
    (``esam_warmup_compile_seconds{shape=...}``), so AOT warmup and
    persistent-cache behavior are visible on the scrape endpoint rather
    than only in a returned dict.
  * :func:`attribute_compiles` — while any registry is inside it, one
    ``jax.monitoring`` listener books every jaxpr trace, lowering, backend
    compile and shared plan executable into
    ``esam_compiles_total{span=,event=}`` and
    ``esam_compile_seconds_total{span=,event=}``, ``span`` being the
    innermost ``Tracer`` span open on the compiling thread: which step
    recompiled, and for how long.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

from repro.obs.metrics import Registry
from repro.obs.trace import current_span


class DeviceProfiler:
    """Capture a ``jax.profiler`` trace around N serving drain rounds."""

    def __init__(self, logdir: str, *, skip_rounds: int = 0,
                 n_rounds: int = 1, registry: Optional[Registry] = None,
                 profiler=None):
        assert n_rounds >= 1, n_rounds
        self.logdir = logdir
        self.skip_rounds = int(skip_rounds)
        self.n_rounds = int(n_rounds)
        self.registry = registry
        self._profiler = profiler      # injectable for tests; None => jax's
        self.active = False
        self.done = False
        self.captured = 0
        self.error: Optional[str] = None
        self._seen = 0

    def _jax_profiler(self):
        if self._profiler is None:
            import jax
            self._profiler = jax.profiler
        return self._profiler

    def on_round_start(self, round_idx: int) -> None:
        """Called by the engine before each dispatch round."""
        if self.done or self.active:
            return
        if self._seen < self.skip_rounds:
            self._seen += 1
            return
        try:
            self._jax_profiler().start_trace(self.logdir)
            self.active = True
        except Exception as e:  # noqa: BLE001 — profiling never kills serving
            self.error = f"{type(e).__name__}: {e}"
            self.done = True

    def on_round_end(self, round_idx: int) -> None:
        """Called by the engine after each dispatch round."""
        if not self.active:
            return
        self.captured += 1
        if self.captured >= self.n_rounds:
            self.stop()

    def stop(self) -> None:
        """Stop an in-flight capture (idempotent; also the abort path)."""
        if self.active:
            try:
                self._jax_profiler().stop_trace()
            except Exception as e:  # noqa: BLE001
                self.error = f"{type(e).__name__}: {e}"
            self.active = False
        self.done = True
        if self.registry is not None:
            self.registry.gauge(
                "esam_profile_rounds_captured",
                "drain rounds inside the jax.profiler capture window",
            ).set(self.captured)


def record_warmup_times(registry: Registry, times: dict,
                        prefix: str = "static") -> None:
    """Fold a ``warmup()`` result dict into per-shape compile-time gauges.

    Accepts both shapes the repo produces: ``EsamPlan.warmup`` returns
    ``{batch: seconds}``; ``SpikeEngine.warmup`` returns
    ``{"static": {batch: s}, "event_t4": {batch: s}, ..., "telemetry_s": s,
    "total_s": s}`` — nesting is flattened into the ``shape`` label.
    """
    for key, val in times.items():
        if isinstance(val, dict):
            record_warmup_times(registry, val, prefix=str(key))
            continue
        shape = (f"{prefix}_b{key}" if isinstance(key, int)
                 else (str(key) if prefix == "static" else f"{prefix}_{key}"))
        registry.gauge(
            "esam_warmup_compile_seconds",
            "AOT warmup compile seconds per plan shape",
            shape=shape,
        ).set(float(val))


#: the compile pipeline's events in ``jax.monitoring`` (trace, lower to an
#: MLIR module, compile or load from the persistent cache, take a shared plan
#: executable) and their ``event`` label
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    # zero length: a plan took an executable another plan had built
    # (``core/esam/plan.py``), so it traces, lowers and compiles nothing
    "/esam/plan/executable_shared": "plan_shared",
}
_books_lock = threading.Lock()
_books: dict[int, list] = {}    # id(registry) -> [registry, users]


def _on_compile(event: str, start: float, end: float, **_kw) -> None:
    label = COMPILE_EVENTS.get(event)
    if label is None:
        return
    span = current_span() or "none"
    with _books_lock:
        registries = [reg for reg, _ in _books.values()]
    for reg in registries:
        reg.counter("esam_compiles_total",
                    "jaxpr traces, lowerings and backend compiles, by the "
                    "span open on the compiling thread",
                    span=span, event=label).inc()
        reg.counter("esam_compile_seconds_total",
                    "seconds of jaxpr traces, lowerings and backend "
                    "compiles, by the span open on the compiling thread",
                    span=span, event=label).inc(max(0.0, end - start))


@contextlib.contextmanager
def attribute_compiles(registry: Optional[Registry]):
    """Book the compiles inside the body into ``registry`` (nothing when it
    is None).  The one listener is registered when the first registry
    enters and unregistered when the last leaves; nesting and concurrent
    users share it."""
    if registry is None:
        yield
        return
    from jax import monitoring

    with _books_lock:
        entry = _books.setdefault(id(registry), [registry, 0])
        entry[1] += 1
        if len(_books) == 1 and entry[1] == 1:
            monitoring.register_event_time_span_listener(_on_compile)
    try:
        yield
    finally:
        with _books_lock:
            entry[1] -= 1
            if entry[1] == 0:
                del _books[id(registry)]
                if not _books:
                    monitoring.unregister_event_time_span_listener(
                        _on_compile)
