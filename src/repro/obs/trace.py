"""Request tracing for the serving stack: Perfetto ``trace_event`` spans on
the profiler's clock.

The serving plane's perf story so far lives in aggregate counters
(``SpikeEngine.stats()``) — good for gating, useless for *attribution*: when
a round stalls you want to see which phase (host pack, dispatch, device
drain, telemetry flush) ate the time, per round, on a timeline.  This module
is the zero-dependency substrate for that:

  * :class:`Tracer` — a thread-safe, bounded ring buffer of trace events
    with an injectable monotonic clock (tests drive it with a fake clock for
    deterministic timestamps).  When the buffer fills, the *oldest* events
    drop and ``dropped`` counts them — memory stays bounded no matter how
    long an engine lives.
  * :meth:`Tracer.span` — the one way program code opens a phase span.  It
    enters a ``jax.profiler.TraceAnnotation`` of the same name, so the span
    sits on the host timeline of any active profiler capture, on the same
    clock as the device's operations, and records an ``"X"`` event in the
    ring.  Open spans are kept per thread (:func:`current_span`), so a
    compile can be booked to the phase that triggered it
    (``repro.obs.profile.attribute_compiles``).
  * Chrome/Perfetto ``trace_event`` export (:meth:`Tracer.export`): the JSON
    a drain produces opens directly in https://ui.perfetto.dev (or
    ``chrome://tracing``).  Request lifecycles are async ``"b"``/``"e"``
    span pairs keyed by request id, with a ``queue`` span each; phases
    (``engine.pack``/``engine.dispatch``/``engine.device_drain``/...) are
    complete ``"X"`` events with real measured durations; ladder
    transitions, sheds, and crashes are instants.
  * :func:`validate_trace` — the schema check the CI observability smoke
    (and the tests) run against an exported file: well-formed events, and
    every begun request span accounted for.

Nothing here imports the serving stack (the engine imports *us*), and a
``Tracer`` never touches the device: spans observe host-side control flow
only, so the traced datapath stays bit-identical to the untraced one
(property-tested in ``tests/test_obs_identity.py``).  Per-request events
stay in the ring only: a profiler annotation cannot be opened after the
fact, and one per request would cost more than it tells.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional

#: the full request lifecycle the engine emits, in order (admit/complete are
#: the async "b"/"e" pair; the rest are "X" phase spans or instants)
REQUEST_PHASES = ("admit", "queue", "engine.pack", "fuse", "engine.dispatch",
                  "engine.device_drain", "engine.telemetry_flush", "complete")

_VALID_PH = {"X", "B", "E", "b", "e", "n", "i", "I", "C", "M"}

#: names of the spans open on each thread, outermost first
_open = threading.local()
_annotation = None   # jax.profiler.TraceAnnotation, imported on first use


def current_span() -> Optional[str]:
    """The innermost span open on the calling thread, or None."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


class Tracer:
    """Thread-safe bounded trace-event recorder.

    ``clock`` is any zero-arg callable returning seconds (monotonic);
    timestamps are microseconds relative to construction.  ``capacity``
    bounds memory: the ring holds at most that many entries (an event, or
    the lifecycles of one round's requests) and evicts the oldest
    (``dropped`` counts evictions).
    """

    def __init__(self, *, clock=time.monotonic, capacity: int = 1 << 16,
                 pid: Optional[int] = None):
        assert capacity >= 1, capacity
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self.capacity = capacity
        self.dropped = 0
        self.pid = os.getpid() if pid is None else int(pid)
        self._next_id = 1                # request span ids (under _lock)

    # ------------------------------------------------------------------ #
    # emission
    # ------------------------------------------------------------------ #
    def now_us(self) -> float:
        """Microseconds since this tracer was created (injected clock)."""
        return (self._clock() - self._t0) * 1e6

    def _push(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def _base(self, name: str, ph: str, cat: str, ts_us, args: dict) -> dict:
        ev = {"name": name, "ph": ph, "cat": cat,
              "ts": float(self.now_us() if ts_us is None else ts_us),
              "pid": self.pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        return ev

    def complete(self, name: str, ts_us: float, dur_us: float, *,
                 cat: str = "serve", **args) -> None:
        """One complete ("X") span with an explicit start and duration."""
        ev = self._base(name, "X", cat, ts_us, args)
        ev["dur"] = max(0.0, float(dur_us))
        self._push(ev)

    def instant(self, name: str, *, cat: str = "serve", **args) -> None:
        ev = self._base(name, "i", cat, None, args)
        ev["s"] = "t"                    # thread-scoped instant
        self._push(ev)

    def span(self, name: str, *, cat: str = "serve", **args) -> "_Span":
        """One phase span around a ``with`` body: a profiler annotation
        ``name`` (on the device trace's clock whenever a capture is
        active) and an "X" event in the ring, with ``name`` on this
        thread's span stack meanwhile."""
        return _Span(self, name, cat, args)

    def requests(self, ts_end: float, stamps: list, *, begin: dict,
                 end: dict) -> None:
        """Requests that closed together at ``ts_end``, recorded after the
        fact as one ring entry.  Each ``(ts_admit, ts_queued)`` stamp is
        expanded, only when read, into its async "b"/"e" pair (``begin`` /
        ``end`` args, shared by the batch) and, when it reached a round at
        ``ts_queued``, its ``queue`` span."""
        if not stamps:
            return
        with self._lock:
            first = self._next_id
            self._next_id += len(stamps)
        self._push((first, threading.get_ident(), ts_end, begin, end,
                    stamps))

    def _expand(self, entry: tuple) -> list[dict]:
        first, tid, ts_end, begin, end, stamps = entry
        evs = []
        for rid, (ts_admit, ts_queued) in enumerate(stamps, first):
            base = {"name": "request", "cat": "request", "pid": self.pid,
                    "tid": tid, "id": rid}
            evs.append(dict(base, ph="b", ts=float(ts_admit), args=begin))
            if ts_queued is not None:
                evs.append({"name": "queue", "ph": "X", "cat": "request",
                            "ts": float(ts_admit),
                            "dur": max(0.0, float(ts_queued - ts_admit)),
                            "pid": self.pid, "tid": tid,
                            "args": {"req": rid}})
            evs.append(dict(base, ph="e", ts=float(ts_end), args=end))
        return evs

    # ------------------------------------------------------------------ #
    # inspection + export
    # ------------------------------------------------------------------ #
    def __len__(self) -> int:
        """Ring entries held (a round's request lifecycles are one)."""
        with self._lock:
            return len(self._events)

    def events(self) -> list[dict]:
        """A snapshot copy of the buffered events (oldest entry first)."""
        with self._lock:
            entries = list(self._events)
        out = []
        for e in entries:
            if isinstance(e, tuple):
                out.extend(self._expand(e))
            else:
                out.append(e)
        return out

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def export(self, path: Optional[str] = None, *,
               process_name: str = "esam-serve") -> dict:
        """The Chrome/Perfetto ``trace_event`` JSON document (optionally
        written to ``path``).  Open it in ui.perfetto.dev."""
        meta = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "ts": 0.0, "cat": "__metadata",
            "args": {"name": process_name},
        }]
        doc = {
            "traceEvents": meta + self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped,
                          "capacity": self.capacity},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc


class _Span:
    """The context manager :meth:`Tracer.span` returns."""

    __slots__ = ("tracer", "name", "cat", "args", "t0", "ann")

    def __init__(self, tracer: Tracer, name: str, cat: str, args: dict):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append(self.name)
        self.t0 = self.tracer.now_us()
        self.ann = _annotation(self.name)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        _open.stack.pop()
        self.tracer.complete(self.name, self.t0,
                             self.tracer.now_us() - self.t0, cat=self.cat,
                             **self.args)
        return False


def validate_trace(doc: dict) -> dict:
    """Validate a ``trace_event`` document; raises ``ValueError`` on schema
    violations.  Returns a summary the CI smoke asserts on::

        {"events", "request_begun", "request_closed", "request_close_fraction",
         "phases"}

    ``request_close_fraction`` is closed/begun async request spans — the
    acceptance criterion wants it >= 0.99 for accepted requests (every
    admitted request must reach a terminal state that closes its span).
    """
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace document must be a dict with 'traceEvents'")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    begun: set = set()
    closed: set = set()
    phases: dict[str, int] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object: {ev!r}")
        for key in ("name", "ph", "ts", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"event {i} missing '{key}': {ev!r}")
        if not isinstance(ev["name"], str) or ev["ph"] not in _VALID_PH:
            raise ValueError(f"event {i} bad name/ph: {ev!r}")
        if not isinstance(ev["ts"], (int, float)) or ev["ts"] < 0:
            raise ValueError(f"event {i} bad ts: {ev!r}")
        if ev["ph"] == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                raise ValueError(f"X event {i} needs dur >= 0: {ev!r}")
        if ev["ph"] in ("b", "e"):
            if "id" not in ev:
                raise ValueError(f"async event {i} needs an id: {ev!r}")
            if ev.get("cat") == "request":
                (begun if ev["ph"] == "b" else closed).add(ev["id"])
        phases[ev["name"]] = phases.get(ev["name"], 0) + 1
    unmatched = closed - begun
    if unmatched:
        raise ValueError(f"request spans closed but never begun: "
                         f"{sorted(unmatched)[:8]}")
    return {
        "events": len(events),
        "request_begun": len(begun),
        "request_closed": len(begun & closed),
        "request_close_fraction": (len(begun & closed) / len(begun)
                                   if begun else 1.0),
        "phases": phases,
    }
