"""What the serving drivers share: the cell's requests made from the seed,
request objects that note when their results reach the host, and the
comparison of served answers with the plain reference."""

from __future__ import annotations

import contextlib
import time

import numpy as np

import gen
import reference as ref
from harness import Check

NULL = contextlib.nullcontext()


def annotate(trace: bool, name: str):
    """A profiler span on the host timeline in traced runs; nothing else."""
    if not trace:
        return NULL
    import jax

    return jax.profiler.TraceAnnotation(name)


def stamped(base, attr: str, clock=time.perf_counter):
    """Subclass of a request type that notes ``clock()`` in ``done_at`` when
    ``attr``, the last result the engine attaches, is set: the moment this
    request's own results are on the host."""

    def get(self):
        return self.__dict__.get(attr)

    def put(self, v):
        self.__dict__[attr] = v
        if v is not None:
            self.__dict__["done_at"] = clock()

    return type("Stamped" + base.__name__, (base,),
                {attr: property(get, put), "done_at": None})


class Requests:
    """A cell's requests, all made before the window.

    ``kind[i]`` is 0 for a static digit plane, 1 for an event stream;
    ``t_of[i]`` is a stream's length (0 for a static request).  ``objs`` are
    the request objects the engine is given, built on views of the arrays.
    """

    def __init__(self, n: int, traffic: dict, seed: int, telemetry: bool,
                 clock=time.perf_counter):
        from repro.serve.engine import EventRequest, SpikeRequest

        p_event = float(traffic.get("p_event", 0.0))
        n_ev = int(round(p_event * n))
        self.kind = gen.rng(seed, 5).permutation(
            np.r_[np.zeros(n - n_ev, np.int8), np.ones(n_ev, np.int8)])
        self.x, self.labels = gen.digit_spikes(
            n, seed, flip_noise=float(traffic.get("flip_noise", 0.02)))
        self.t_of = np.zeros(n, np.int64)
        ev_idx = np.flatnonzero(self.kind == 1)
        self.events, self.pos = {}, np.zeros(n, np.int64)
        if ev_idx.size:
            self.t_of[ev_idx] = gen.balanced_choice(
                traffic["event_t_choices"], ev_idx.size, seed, 4)
            self.events = gen.rate_streams(
                self.x[ev_idx], self.t_of[ev_idx], seed,
                gain=float(traffic.get("gain", 1.0)))
            for t in self.events:
                sel = ev_idx[self.t_of[ev_idx] == t]
                self.pos[sel] = np.arange(sel.size)
        s_cls = stamped(SpikeRequest, "energy_pj" if telemetry else "label",
                        clock)
        e_cls = stamped(EventRequest,
                        "energy_pj_per_step" if telemetry else "label", clock)
        self.objs = [
            e_cls(events=self.events[int(self.t_of[i])][:, self.pos[i]])
            if self.kind[i] else s_cls(spikes=self.x[i]) for i in range(n)]

    def __len__(self) -> int:
        return len(self.objs)

    @property
    def event_ts(self) -> tuple:
        return tuple(sorted(self.events))

    def macs(self, idx, macs_per_inference: int) -> int:
        """MACs the requests ``idx`` need: one inference per static request,
        one per timestep of a stream."""
        steps = np.maximum(self.t_of[idx], 1)
        return int(steps.sum()) * int(macs_per_inference)


def sample_indices(done: np.ndarray, t_of: np.ndarray, size: int,
                   seed: int) -> np.ndarray:
    """Up to ``size`` answered requests drawn from the seed, always holding
    the longest one answered."""
    idx = np.flatnonzero(done)
    if idx.size <= size:
        return idx
    pick = gen.rng(seed, 6).choice(idx, size=size, replace=False)
    longest = idx[np.argmax(t_of[idx])]
    return np.unique(np.r_[pick, longest])


def compare(reqs: Requests, idx: np.ndarray, network, cfg: dict,
            dtype: str = "exact", answers=None) -> list:
    """The gaps between the served answers of requests ``idx`` and the
    reference: logits, simulated cycles and energy, plus requests due that
    were never answered.  ``answers`` (logits, cycles, energy) replaces the
    program's answers, for the control."""
    cell = cfg["cell"]
    leak = float(cfg["neuron"].get("leak", 0.0))
    lim = cfg["limits"]
    if answers is None:
        got_l = np.stack([np.asarray(reqs.objs[i].logits, np.float64)
                          for i in idx]) if idx.size else np.zeros((0, 1))
        got_c = np.array([reqs.objs[i].cycles for i in idx], np.float64)
        got_e = np.array([reqs.objs[i].energy_pj for i in idx], np.float64)
    else:
        got_l, got_c, got_e = answers
    want_l = np.zeros_like(got_l)
    want_c = np.zeros_like(got_c)
    want_e = np.zeros_like(got_e)
    static = reqs.kind[idx] == 0
    if static.any():
        l, c, e = ref.if_forward(network.bits, network.vth,
                                 network.out_offset, reqs.x[idx[static]],
                                 cell, dtype)
        want_l[static], want_c[static], want_e[static] = l, c, e
    for t in reqs.event_ts:
        m = (reqs.kind[idx] == 1) & (reqs.t_of[idx] == t)
        if not m.any():
            continue
        ev = gen.unpack_bits(reqs.events[t][:, reqs.pos[idx[m]]])
        l, c, e = ref.lif_forward(network.bits, network.vth,
                                  network.out_offset, ev, leak, cell, dtype)
        want_l[m], want_c[m], want_e[m] = l, c, e
    return [Check("logits_gap", ref.gap_abs(got_l, want_l),
                  lim["logits_gap"]),
            Check("cycles_gap", ref.gap_abs(got_c, want_c),
                  lim["cycles_gap"]),
            Check("energy_rel_gap", ref.gap_rel(got_e, want_e),
                  lim["energy_rel_gap"])]


def control_answers(reqs: Requests, idx: np.ndarray, network, cfg: dict):
    """The control: the reference in bfloat16 put in the program's place."""
    n = idx.size
    ans = (np.zeros((n, network.topology[-1])), np.zeros(n), np.zeros(n))
    static = reqs.kind[idx] == 0
    cell = cfg["cell"]
    if static.any():
        l, c, e = ref.if_forward(network.bits, network.vth,
                                 network.out_offset, reqs.x[idx[static]],
                                 cell, "bf16")
        ans[0][static], ans[1][static], ans[2][static] = l, c, e
    for t in reqs.event_ts:
        m = (reqs.kind[idx] == 1) & (reqs.t_of[idx] == t)
        if m.any():
            ev = gen.unpack_bits(reqs.events[t][:, reqs.pos[idx[m]]])
            l, c, e = ref.lif_forward(
                network.bits, network.vth, network.out_offset, ev,
                float(cfg["neuron"].get("leak", 0.0)), cell, "bf16")
            ans[0][m], ans[1][m], ans[2][m] = l, c, e
    return ans


def engine_counters(engine) -> dict:
    """The engine's host counters that the per-layer metrics read."""
    st = engine.stats()
    keys = ("n_requests", "n_event_requests", "timesteps_total",
            "rounds_static", "rounds_event", "rows_real_total",
            "rows_padded_total", "host_pack_s_total", "dispatch_s_total",
            "dispatch_rounds", "data_parallel")
    return {k: st[k] for k in keys}


def counter_delta(before: dict, after: dict) -> dict:
    return {k: (after[k] - before[k] if k != "data_parallel" else after[k])
            for k in after}
