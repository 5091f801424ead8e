"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
share, per-operation device time and the longest idle gaps.

Device planes are ``/device:TPU:<n>``; on each, the line of XLA operations
is the device's work.  Busy time is the union of those operations'
intervals inside the window, which is the host span named ``bench.window``
that the harness puts around the measured window.  Each gap between busy
intervals is named by the innermost ``bench.*`` host span around its middle:
what the benchmark's own code was doing while the device sat idle.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
TOP = 10
#: control-flow ops whose events enclose the ops of their bodies: they count
#: towards busy time but not towards per-operation time
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%esam_cascade_popcount.1 = (s32[...]...) custom-call(...)`` ->
    ``esam_cascade_popcount``: the HLO op's name without its instance
    number."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    base, dot, num = head.rpartition(".")
    return base if dot and num.isdigit() else head


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _device_ops(pd):
    """{plane name: [(start_ns, end_ns, op name)]} of every TPU device."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                         op_name(ev.name)) for ev in line.events]
        out[plane.name] = ops
    return out


def _name_gap(mid: float, spans) -> str:
    best: Optional[tuple] = None
    for s, e, name in spans:
        if s <= mid <= e and name != WINDOW:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else WINDOW


def reduce_profile(pd, n_chips: int) -> dict:
    """The reduction of a loaded ``ProfileData`` (see module doc)."""
    spans = _host_spans(pd)
    win = [sp for sp in spans if sp[2] == WINDOW]
    devices = _device_ops(pd)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if win:
        w0, w1 = win[0][0], win[0][1]
    else:  # no window span: the whole span of device work
        w0 = min(s for ops in devices.values() for s, _, _ in ops)
        w1 = max(e for ops in devices.values() for _, e, _ in ops)
    busy, op_s, op_n, gaps = {}, {}, {}, []
    for dev, ops in devices.items():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            if n in CONTAINERS:
                continue
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
            op_n[n] = op_n.get(n, 0) + 1
        merged = union([(s, e) for s, e, _ in clipped])
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy.values()) / max(1, len(busy))
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "per_chip_busy_s": busy,
        "chips_traced": len(busy),
        "chips_expected": n_chips,
        "op_s": op_s,
        "op_calls": op_n,
        "top_ops": [[n, s] for n, s in
                    sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]],
        "top_gaps": [[_name_gap((a + b) / 2, spans), g * 1e-9]
                     for g, a, b in gaps[:TOP]],
    }


def reduce(path: str, n_chips: int) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), n_chips)


def kernel_seconds(red: dict, patterns) -> tuple[float, int]:
    """Device seconds and calls, summed over chips, of the operations whose
    name contains any of ``patterns``."""
    s = n = 0
    for name, secs in red["op_s"].items():
        if any(p in name for p in patterns):
            s += secs
            n += red["op_calls"][name]
    return s, n
