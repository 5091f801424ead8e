"""Reduce a JAX profiler trace's host spans: where the host was while the
device sat idle.

``bench/trace_reduce.py`` gives device busy time, per-operation time and
idle gaps named by the benchmark's own spans.  This reduction reads the
program's spans too: the engine's ``engine.*`` phases and
``train_online``'s ``train.*`` steps, which ``repro.obs.trace.Tracer.span``
puts on the profiler's clock.  Each host thread is one line of the host
plane; the thread that holds ``bench.window`` is the caller's.

    span_s, span_calls   seconds (clipped to the window) and calls of each
                         bench.*, engine.* and train.* span, on any thread
    idle_by_span         device-idle seconds (per chip, averaged over chips)
                         keyed by the innermost such span open on the
                         caller's thread; ``bench.window`` where none is
    top_gaps             the longest idle gaps, each named by the innermost
                         such span on the caller's thread at its middle: a
                         packer-thread span never names a gap
    module_s             device seconds per XLA module (executable) name

Run it on a kept trace:  python3 bench/span_reduce.py <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import sys

import trace_reduce

PREFIXES = ("bench.", "engine.", "train.")
MODULES_LINE = "XLA Modules"


def module_name(event_name: str) -> str:
    """``jit_esam_plan_packed(1609838...)`` -> ``jit_esam_plan_packed``."""
    return event_name.split("(", 1)[0]


def _host_lines(pd):
    """[(spans on one host thread)], each span (start_ns, end_ns, name)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for ev in line.events if ev.name.startswith(PREFIXES)]
            if spans:
                out.append(spans)
    return out


def innermost_segments(spans, w0: float, w1: float):
    """Cut [w0, w1] into (start, end, name) pieces, each named by the
    innermost span of one thread's (nested) ``spans`` open over it;
    ``bench.window`` where none is."""
    out = []
    stack = [(w0, w1, trace_reduce.WINDOW)]
    cur = w0

    def close_until(t):
        nonlocal cur
        while len(stack) > 1 and stack[-1][1] <= t:
            _, e, name = stack.pop()
            if e > cur:
                out.append((cur, e, name))
                cur = e

    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        if name == trace_reduce.WINDOW:
            continue
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        close_until(s)
        if s > cur:
            out.append((cur, s, stack[-1][2]))
            cur = s
        stack.append((s, e, name))
    close_until(w1)
    if w1 > cur:
        out.append((cur, w1, trace_reduce.WINDOW))
    return out


def _idle(ops, w0: float, w1: float):
    """Idle intervals of one chip inside [w0, w1]."""
    merged = trace_reduce.union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                                 if e > w0 and s < w1])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _modules(pd, w0: float, w1: float) -> dict:
    out: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    n = module_name(ev.name)
                    out[n] = out.get(n, 0.0) + (e - s) * 1e-9
    return out


def reduce_spans(pd) -> dict:
    """The reduction of a loaded ``ProfileData`` (see module doc)."""
    lines = _host_lines(pd)
    window = [(sp, spans) for spans in lines for sp in spans
              if sp[2] == trace_reduce.WINDOW]
    if not window:
        raise ValueError(f"the trace holds no {trace_reduce.WINDOW} span")
    (w0, w1, _), caller = window[0]
    span_s: dict = {}
    span_calls: dict = {}
    for spans in lines:
        for s, e, name in spans:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                span_s[name] = span_s.get(name, 0.0) + (e - s) * 1e-9
                span_calls[name] = span_calls.get(name, 0) + 1
    segs = innermost_segments(caller, w0, w1)
    starts = [s for s, _, _ in segs]
    devices = trace_reduce._device_ops(pd)
    idle_by_span: dict = {}
    gaps = []
    for ops in devices.values():
        i = 0
        for a, b in _idle(ops, w0, w1):
            gaps.append((b - a, a, b))
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                s, e, name = segs[j]
                cut = min(b, e) - max(a, s)
                if cut > 0:
                    idle_by_span[name] = (idle_by_span.get(name, 0.0)
                                          + cut * 1e-9 / len(devices))
                j += 1
    gaps.sort(reverse=True)

    def name_at(t):
        return segs[max(0, bisect.bisect_right(starts, t) - 1)][2]

    return {
        "window_s": (w1 - w0) * 1e-9,
        "span_s": span_s,
        "span_calls": span_calls,
        "idle_by_span": idle_by_span,
        "top_gaps": [[name_at((a + b) / 2), g * 1e-9]
                     for g, a, b in gaps[:trace_reduce.TOP]],
        "module_s": _modules(pd, w0, w1),
    }


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_spans(ProfileData.from_file(path))


def covered_share(red: dict, outer: str, prefix: str) -> float:
    """Of the device-idle time inside the caller's ``outer`` span, the share
    that a ``prefix`` span of the program covers (the program's spans nest
    inside the benchmark's call)."""
    inner = sum(v for k, v in red["idle_by_span"].items()
                if k.startswith(prefix))
    total = inner + red["idle_by_span"].get(outer, 0.0)
    return inner / total if total > 0 else 0.0


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1, sort_keys=True))
