"""Unified observability plane: tracing, metrics, and device profiling.

The serving stack (``SpikeEngine``, ``FaultAwareRouter``, the traffic
harness, the online-learning driver) takes one optional
:class:`Observability` handle and, when given, emits:

  * request-lifecycle + phase spans into an :class:`~repro.obs.trace.
    Tracer` (exportable as Perfetto ``trace_event`` JSON); each phase span
    is also a ``jax.profiler`` annotation, so it lands in any profiler
    capture on the device trace's clock,
  * counters / gauges / latency histograms into a
    :class:`~repro.obs.metrics.Registry` (scraped over HTTP by
    :class:`~repro.obs.http.MetricsServer`, snapshotted into
    ``TrafficReport`` and ``--report-json``), among them each request's
    queue wait and the compiles booked to the span that triggered them
    (:func:`~repro.obs.profile.attribute_compiles`),
  * ``jax.profiler`` captures around drain rounds via a
    :class:`~repro.obs.profile.DeviceProfiler`.

Everything defaults **off** (``observability=None``), and the off path is
property-tested bit-identical to the instrumented path — spans observe,
never perturb.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from repro.obs.metrics import REGISTRY, Registry
from repro.obs.profile import DeviceProfiler
from repro.obs.trace import Tracer

__all__ = ["Observability", "Registry", "REGISTRY", "Tracer",
           "DeviceProfiler"]


@dataclasses.dataclass
class Observability:
    """The bundle a serving component is instrumented with.

    Any field may be None — tracing, metrics, and profiling are independent
    lanes; a component guards each emission on the lane being present.
    """

    tracer: Optional[Tracer] = None
    metrics: Optional[Registry] = None
    profile: Optional[DeviceProfiler] = None

    @classmethod
    def enabled(cls, *, clock=time.monotonic, capacity: int = 1 << 16,
                registry: Optional[Registry] = None,
                profile: Optional[DeviceProfiler] = None) -> "Observability":
        """Tracer + metrics on (the common case); profiling opt-in."""
        return cls(tracer=Tracer(clock=clock, capacity=capacity),
                   metrics=REGISTRY if registry is None else registry,
                   profile=profile)
