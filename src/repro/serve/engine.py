"""ESAM spike serving: ``SpikeEngine`` and ``FaultAwareRouter``.  (LM
serving is ``serve/lm_engine.Engine``.)

``SpikeEngine``: ESAM spike-classification serving on the packed plane —
requests are bit-packed host-side into the uint32 wire format (32 spikes per
lane word, the paper's parallel-pulse inter-tile bus) and continuously
batched through ONE compiled ``EsamPlan`` (optionally ``shard_map``-ped over
a device mesh), so neither the server->device transfer nor the tile cascade
ever materializes an unpacked spike tensor in HBM.  Beyond single-shot
``SpikeRequest``s it admits event *streams* (``EventRequest``,
``submit_events``): T timesteps of spike planes with per-request T, bucketed
on (batch, T) and drained through the membrane-resident temporal plan
(``mode="temporal"``) with the same device-resident telemetry discipline.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.obs import Observability
from repro.obs.metrics import FINE_BOUNDS
from repro.obs.profile import attribute_compiles
from repro.serve.overload import AdmissionVerdict, DegradationLadder
from repro.train import fault_tolerance as ft


# ------------------------------------------------------------------ #
# ESAM spike-classification serving (packed plane, plan-compiled)
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class SpikeRequest:
    spikes: np.ndarray                     # {0,1}[n_in] (any dtype)
    # overload plane (optional): absolute deadline in the engine's clock —
    # requests still queued past it are shed instead of dispatched
    deadline_s: Optional[float] = None
    # lifecycle: "pending" -> "done" | "shed" (deadline) | "rejected"
    # (bounded queue full) | "failed" (router retry budget exhausted)
    status: str = "pending"
    attempts: int = 0                      # router retry count
    # filled by the engine:
    logits: Optional[np.ndarray] = None    # float32[n_classes]
    label: Optional[int] = None            # argmax readout
    # filled when the engine runs with telemetry (paper-unit hardware cost):
    cycles: Optional[int] = None           # CIM clock cycles, summed over tiles
    latency_ns: Optional[float] = None     # cycles * cell clock period
    energy_pj: Optional[float] = None      # per-inference energy (pJ/inf)


@dataclasses.dataclass
class EventRequest:
    """An event-stream classification request: T timesteps of spike planes.

    ``events``: {0,1}[T, n_in] (any dtype), or pre-packed wire-format
    uint32[T, ceil(n_in/32)].  T may differ per request — the engine buckets
    event rounds on (batch, T).
    """

    events: np.ndarray
    # overload plane (optional): see SpikeRequest
    deadline_s: Optional[float] = None
    status: str = "pending"
    attempts: int = 0
    # filled by the engine:
    logits: Optional[np.ndarray] = None    # float32[n_classes]
    label: Optional[int] = None            # argmax readout
    served_steps: Optional[int] = None     # timesteps actually served (the
    #                                        ladder may truncate the stream)
    # filled when the engine runs with telemetry (paper-unit hardware cost):
    cycles: Optional[int] = None           # CIM cycles, summed over T steps
    latency_ns: Optional[float] = None     # cycles * cell clock period
    energy_pj: Optional[float] = None      # whole-stream energy
    energy_pj_per_step: Optional[float] = None  # energy_pj / T

    @property
    def n_steps(self) -> int:
        return int(np.asarray(self.events).shape[0])


@functools.lru_cache(maxsize=None)
def _stats_jit(topology: tuple, read_ports: int, temporal: bool):
    """One jitted device-side cost function per (topology, ports, mode).

    The eager ``request_stats_device`` dispatches ~20 tiny jnp ops per tile;
    on a sharded mesh each one fans out across every device, and that host
    overhead — not the datapath — dominated the dp8 round time.  Jitting
    collapses the whole accounting into ONE dispatch.  Module-level cache so
    every engine (sync or fused, any replica) shares the same compiled
    executable — which also makes their telemetry bit-identical by
    construction.  The executable is named ``esam_request_stats`` (static)
    or ``esam_stream_stats`` (temporal) on the device trace."""
    from repro.core.esam import cost_model as cm

    if temporal:
        def esam_stream_stats(loads):
            return cm.temporal_request_stats_device(topology, loads,
                                                    read_ports)
        return jax.jit(esam_stream_stats)

    def esam_request_stats(loads):
        return cm.request_stats_device(topology, loads, read_ports)
    return jax.jit(esam_request_stats)


# ------------------------------------------------------------------ #
# stats() schema: documented, versioned, grouped into typed sections.
# CI (PR 7-9) greps several of these keys out of bench derived strings —
# tests/test_obs.py pins the schema so a rename can never silently break
# those gates.  Bump STATS_SCHEMA_VERSION on any key change.
# ------------------------------------------------------------------ #
STATS_SCHEMA_VERSION = 1

_STATS_SCHEMA: dict[str, dict[str, str]] = {
    # engine identity + configuration
    "identity": {
        "stats_schema_version": "int",
        "requests": "int",              # legacy alias of n_requests
        "n_requests": "int",
        "telemetry": "bool",
        "cell": "str",
        "read_ports": "int",
        "data_parallel": "int",
    },
    # fault-aware serving: tile health + dispatch watchdog
    "health": {
        "faulted": "bool",
        "tile_health": "list",
        "health": "float",
        "degraded": "bool",
        "dispatch_rounds": "int",
        "straggler_rounds": "int",
    },
    # overload plane: admission, deadlines, degradation ladder
    "overload": {
        "queue_depth": "int",
        "queue_limit": "int|None",
        "high_water": "int|None",
        "shed_deadline": "int",
        "rejected_full": "int",
        "backpressure_events": "int",
        "degradation_level": "int",
        "degradation_level_name": "str",
        "ladder_transitions": "int",
        "ladder_transition_log": "list",
    },
    # per-round host-sync/dispatch observability (dp8 attribution numbers)
    "rounds": {
        "rounds_static": "int",
        "rounds_event": "int",
        "rows_real_total": "int",
        "rows_padded_total": "int",
        "pad_fraction": "float",
        "rounds_per_bucket": "dict",
        "padded_rows_per_bucket": "dict",
        "real_rows_per_bucket": "dict",
        "pad_fraction_per_bucket": "dict",
        "host_pack_s_total": "float",
        "dispatch_s_total": "float",
    },
    # fused async dispatch (the dp-scaling fix)
    "fusion": {
        "fuse_rounds": "int",
        "overlap": "bool",
        "fused_rounds": "int",
        "rounds_saved": "int",
    },
    # event-stream (temporal plane) aggregates
    "events": {
        "n_event_requests": "int",
        "timesteps_total": "int",
        "event_energy_pj_mean": "float",
        "event_latency_ns_mean": "float",
        "event_cycles_mean": "float",
        "energy_pj_per_timestep": "float",
    },
    # paper-unit hardware cost aggregates (zero-filled before any traffic)
    "cost": {
        "cycles_mean": "float",
        "latency_ns_mean": "float",
        "energy_pj_per_inf": "float",
        "throughput_inf_s": "float",
        "throughput_pipelined_inf_s": "float",
    },
}


def stats_schema() -> dict[str, dict[str, str]]:
    """The versioned schema of ``SpikeEngine.stats()``: section -> key ->
    type name (``"int|None"`` marks optionally-unset config knobs).

    The returned dict is a fresh copy — mutate freely.  ``stats()`` always
    returns exactly the union of these keys (regression-tested), and
    ``stats()["stats_schema_version"] == STATS_SCHEMA_VERSION``.
    """
    return {section: dict(keys) for section, keys in _STATS_SCHEMA.items()}


def _bucket_sizes(max_batch: int, min_bucket: int, dp: int) -> list[int]:
    """Power-of-two bucket ladder: min_bucket, 2*min_bucket, ... >= max_batch.

    Every bucket is a multiple of the data-parallel degree ``dp`` so a padded
    batch always divides the mesh; the smallest bucket never exceeds the
    (rounded-up) ``max_batch`` itself.
    """
    top = 1
    while top < max_batch:
        top <<= 1
    lo = max(min(min_bucket, top), dp)
    b = 1
    while b < lo:
        b <<= 1
    sizes = [b]
    while sizes[-1] < top:
        sizes.append(sizes[-1] * 2)
    return sizes


class SpikeEngine:
    """Continuously-batched ESAM serving over one compiled execution plan.

    Requests enter an admission queue (``submit``; ``serve`` is submit+drain)
    and are dispatched in multi-batch rounds of up to ``max_batch`` requests.
    Each round is zero-padded up to the next power-of-two bucket
    (``min_bucket``-based ladder, always a multiple of the data-parallel
    degree) so the compiled plan sees a handful of static shapes instead of
    one per queue length — silent pad rows are exact for the binary CIM MAC.
    Packing happens on the host (numpy — the device only ever sees the uint32
    wire format); with ``rules`` the plan is compiled ``shard_map``-ped over
    the mesh and each bucket is sharded over the ``spike_batch`` axes.

    With ``telemetry=True`` the plan additionally returns each tile's
    arbiter loads (group popcounts of the inter-tile bitplanes — same pass,
    nothing unpacked) and the paper-unit hardware cost is computed *on
    device* (``cost_model.request_stats_device``), staying device-resident
    through the whole dispatch loop: the engine performs no per-batch host
    sync — per-request costs land on the host in one flush at drain end
    (where the running aggregate folds into exact float64 totals, immune to
    float32 drift over long-lived engines), and ``stats()`` is a pure host
    read.

    **Fused async dispatch** (the dp-scaling plane): ``fuse_rounds``
    coalesces up to that many legacy bucket-rounds into ONE super-batch
    dispatch per drain step (``"auto"`` = the data-parallel degree, so dp8
    issues ~1/8th the rounds over 8x the batch; the bucket ladder is
    extended to ``max_batch * fuse`` and every super-batch stays dp-aligned).
    The fused path is bit-identical per row to the per-bucket path — the
    binary CIM MAC is row-independent and zero padding is exact — so fusion
    changes *when* work is dispatched, never *what* is computed
    (property-tested).  ``overlap=True`` double-buffers the host side: a
    background packer thread builds round N+1's wire-format batch while
    round N's dispatch runs, through a bounded depth-2 ring (no
    ``block_until_ready`` anywhere in the drain — results stay device-side
    until the flush).  A degraded ladder level may cap fusion
    (``LadderLevel.fuse_cap``) so shed/deadline sweeps stay frequent under
    pressure.  ``warmup()`` AOT-compiles the whole bucket ladder (and the
    event (bucket, T) grid) ahead of the first request.
    """

    def __init__(self, net, *, max_batch: int = 128, min_bucket: int = 8,
                 fuse_rounds=None,  # None | "auto" | int >= 1
                 overlap: bool = False,
                 interpret: Optional[bool] = None,
                 telemetry: bool = False, read_ports: int = 4,
                 temporal=None,  # Optional[temporal.TemporalConfig]
                 faults=None,  # Optional[faults.FaultModel]
                 watchdog: Optional[ft.StragglerWatchdog] = None,
                 health_threshold: float = 0.75,
                 rules: Optional[shd.ShardingRules] = None,
                 queue_limit: Optional[int] = None,
                 high_water: Optional[int] = None,
                 ladder: Optional[DegradationLadder] = None,
                 clock=time.monotonic,
                 round_hook=None,
                 observability: Optional[Observability] = None,
                 batch_size: Optional[int] = None):
        from repro.core import packing
        from repro.core.esam import cost_model as cm
        from repro.core.esam import temporal as temporal_mod

        if batch_size is not None:   # deprecated alias (pre-plan engine)
            max_batch = batch_size
        self.net = net
        self.max_batch = max_batch
        self.n_in = net.topology[0]
        self.telemetry = telemetry
        self.read_ports = read_ports
        self.rules = rules
        self.faults = faults
        self.health_threshold = health_threshold
        self._packing = packing
        self._cm = cm
        self._interpret = interpret
        self._min_bucket = min_bucket
        # dispatch-round straggler watchdog: each continuous-batching round's
        # host-side wall time (packing + dispatch; device work is async) is
        # recorded, and rounds slower than threshold x the EMA are flagged —
        # surfaced through stats() so a coordinator can drain traffic away
        self._watchdog = watchdog or ft.StragglerWatchdog()
        self._rounds = 0
        # ---- overload plane -------------------------------------------- #
        # bounded admission: submit() rejects past queue_limit; high-water
        # mark (default: half the limit) turns verdicts into backpressure
        self._clock = clock
        self._queue_limit = queue_limit
        if high_water is None and queue_limit is not None:
            high_water = max(1, queue_limit // 2)
        self._high_water = high_water
        # graceful-degradation ladder state (None => pinned to full service)
        self._ladder = ladder
        self._ladder_level = 0
        self._pressure_streak = 0
        self._clear_streak = 0
        self._ladder_flagged_seen = 0
        self._transitions: list[dict] = []
        # chaos/observability hook: called with the round index before each
        # dispatch round (inside the watchdog-timed section) — a raising hook
        # models a replica crashing mid-drain
        self.round_hook = round_hook
        # ---- observability plane (repro.obs) --------------------------- #
        # All three lanes default off; every emission below is guarded so
        # the off path stays bit-identical to the instrumented path (spans
        # observe, never perturb — property-tested in test_obs_identity).
        self._obs = observability
        self._tracer = observability.tracer if observability else None
        self._metrics = observability.metrics if observability else None
        self._profiler = observability.profile if observability else None
        # id(request) -> (admission us, round-formation us or None), on
        # the clock of _obs_now; entries are removed at every terminal
        # transition, so the map never outgrows the queue.  Tuples of
        # floats: the collector stops tracking them, however many are held
        self._req_spans: dict[int, tuple] = {}
        self._m = self._make_instruments(self._metrics)
        # overload counters (all surfaced through stats())
        self._shed_deadline = 0
        self._rejected_full = 0
        self._backpressure_events = 0
        # per-round host-sync/dispatch observability (satellite for the dp8
        # serving regression): pack vs dispatch host time, padded-vs-real
        # rows per bucket — aggregates only, O(1) per round
        self._round_counters = {
            "rounds_static": 0, "rounds_event": 0,
            "rows_real": 0, "rows_padded": 0,
            "host_pack_s": 0.0, "dispatch_s": 0.0,
            "fused_rounds": 0, "rounds_saved": 0,
        }
        self._rounds_per_bucket: dict[int, int] = {}
        self._padded_rows_per_bucket: dict[int, int] = {}
        self._real_rows_per_bucket: dict[int, int] = {}
        # LIF dynamics template for event-stream requests; n_steps is taken
        # from each request (per-request T), the rest from this config.  The
        # default (zero leak, zero reset) makes a T=1 event request
        # bit-identical to the static packed path.
        self._temporal = temporal or temporal_mod.TemporalConfig(n_steps=1)
        dp = 1 if rules is None else rules.axis_size("spike_batch")
        # round fusion: how many legacy bucket-rounds may coalesce into one
        # super-batch dispatch ("auto" tracks the dp degree so the dispatch
        # count drops ~1/dp); the bucket ladder is extended to cover the
        # fused super-batches.  fuse=1 (default) is the legacy drain.
        if fuse_rounds is not None and fuse_rounds != "auto":
            assert int(fuse_rounds) >= 1, fuse_rounds
        self._fuse_arg = fuse_rounds
        self._fuse = self._fuse_factor(dp)
        self._overlap = bool(overlap)
        self._pool: Optional[ThreadPoolExecutor] = None
        self._buckets = _bucket_sizes(max_batch * self._fuse, min_bucket, dp)
        # the engine owns every array it hands the plan (packed fresh per
        # round), so the input buffer is donated — XLA reuses the allocation
        # across drain rounds instead of re-allocating per dispatch
        self._plan = net.plan(
            mode="packed", telemetry=telemetry, interpret=interpret,
            faults=faults, rules=rules, donate=True)
        n_tiles = len(net.topology) - 1
        # tile-health calibration: expected mean drain cycles per tile on the
        # reference activity profile (the paper's 53%/50% calibration point).
        # Measured telemetry deviating from this — up (stuck-at-1 load
        # inflation) or down (dead/stuck-at-0 columns silencing traffic) —
        # marks the tile degraded.
        topo = net.topology
        ref = [
            np.full((1, cm.tile_geometry(topo[t], topo[t + 1])[0]),
                    float(cm.REF_SPIKES_PER_GROUP[t])
                    if t < len(cm.REF_SPIKES_PER_GROUP) else 64.0)
            for t in range(n_tiles)
        ]
        self._expected_tile_cycles = cm.request_stats(
            topo, ref, read_ports).cycles_per_tile.mean(axis=0)  # [n_tiles]
        # admission queues + per-round device results awaiting one host flush
        self._pending: list[SpikeRequest] = []
        self._pending_events: list[EventRequest] = []
        self._inflight: list[tuple[list, jax.Array, Optional[dict]]] = []
        # exact float64 telemetry totals, folded in at each drain flush
        self._served = 0
        self._served_events = 0
        self._served_timesteps = 0
        self._totals = {
            "cycles": 0.0,
            "cycles_per_tile": np.zeros((n_tiles,), np.float64),
            "latency_ns": 0.0,
            "energy_pj": 0.0,
        }
        self._event_totals = {
            "cycles": 0.0,
            "latency_ns": 0.0,
            "energy_pj": 0.0,
        }

    # -------------------------------------------------------------- #
    # observability plane: instruments + span helpers (all no-ops when off)
    # -------------------------------------------------------------- #
    @staticmethod
    def _make_instruments(reg) -> Optional[dict]:
        """Pre-register every engine metric so the scrape endpoint shows the
        full (zeroed) surface before the first request.  Counter totals are
        incremented with exactly the values ``stats()`` folds, so the two
        always reconcile (tested)."""
        if reg is None:
            return None
        c, g, h = reg.counter, reg.gauge, reg.histogram
        return {
            "submitted": c("esam_requests_submitted_total",
                           "requests admitted to the engine queue"),
            "rejected": c("esam_requests_rejected_total",
                          "bounded-queue admission rejections"),
            "shed": c("esam_requests_shed_total",
                      "requests shed on an expired deadline"),
            "served_static": c("esam_requests_served_total",
                               "requests served", kind="static"),
            "served_event": c("esam_requests_served_total",
                              "requests served", kind="event"),
            "timesteps": c("esam_timesteps_served_total",
                           "event-stream timesteps served"),
            "rounds": c("esam_dispatch_rounds_total",
                        "continuous-batching dispatch rounds"),
            "fused": c("esam_fused_rounds_total",
                       "rounds that coalesced >1 legacy bucket-round"),
            "rounds_saved": c("esam_rounds_saved_total",
                              "legacy bucket-rounds saved by fusion"),
            "rows_real": c("esam_rows_real_total",
                           "real (non-padded) rows dispatched"),
            "rows_padded": c("esam_rows_padded_total",
                             "zero-padded bucket rows dispatched"),
            "backpressure": c("esam_backpressure_events_total",
                              "admissions past the high-water mark"),
            "ladder_transitions": c("esam_ladder_transitions_total",
                                    "degradation-ladder level changes"),
            "energy": c("esam_energy_pj_total",
                        "modeled inference energy (pJ), telemetry lane"),
            "cycles": c("esam_cycles_total",
                        "modeled CIM cycles, telemetry lane"),
            "queue_depth": g("esam_queue_depth",
                             "requests admitted and awaiting dispatch"),
            "ladder_level": g("esam_degradation_level",
                              "current degradation-ladder level (0=full)"),
            "health": g("esam_health",
                        "weakest-tile health score in [0,1]"),
            "pack_s": h("esam_round_pack_seconds",
                        "host-side wire-format packing time per round"),
            "dispatch_s": h("esam_round_dispatch_seconds",
                            "plan dispatch-call time per round"),
            "queue_s": h("esam_request_queue_seconds",
                         "admit -> round-formation queue wait",
                         bounds=FINE_BOUNDS),
            "latency_s": h("esam_request_latency_seconds",
                           "admit -> terminal-state request latency"),
        }

    def _dp_degree(self) -> int:
        return 1 if self.rules is None else self.rules.axis_size("spike_batch")

    def _obs_now(self) -> float:
        """Microseconds on the tracer's clock when tracing, else on the
        host's: the clock of every per-request stamp."""
        if self._tracer is not None:
            return self._tracer.now_us()
        return time.perf_counter() * 1e6

    def _span(self, name: str, **args):
        """A tracer span (profiler annotation + ring event), or nothing."""
        if self._tracer is None:
            return contextlib.nullcontext()
        return self._tracer.span(name, cat="engine", **args)

    def _obs_close(self, reqs, kind: str, status: str) -> None:
        """Requests of one ``kind`` reached a terminal state: their
        latencies go into the histogram and their lifecycles (admission,
        queue wait, close) into the ring, one update each for the batch."""
        now = self._obs_now()
        pop = self._req_spans.pop
        done = [(r.deadline_s, s) for r in reqs
                if (s := pop(id(r), None)) is not None]
        if self._m is not None:
            self._m["latency_s"].observe_many(
                [(now - s[0]) * 1e-6 for _, s in done])
        if self._tracer is not None:
            by_deadline: dict = {}
            for deadline, s in done:
                by_deadline.setdefault(deadline, []).append(s)
            for deadline, stamps in by_deadline.items():
                self._tracer.requests(
                    now, stamps, end={"status": status},
                    begin={"kind": kind, "deadline_s": deadline,
                           "dp": self._dp_degree()})

    def _obs_queue(self, reqs) -> None:
        """Each request's queue wait, admission -> round formation, into
        the histogram; the stamp waits for the request's close."""
        now = self._obs_now()
        spans = self._req_spans
        waits = []
        for r in reqs:
            s = spans.get(id(r))
            if s is not None:
                spans[id(r)] = (s[0], now)
                waits.append((now - s[0]) * 1e-6)
        if self._m is not None:
            self._m["queue_s"].observe_many(waits)

    # -------------------------------------------------------------- #
    # admission + dispatch
    # -------------------------------------------------------------- #
    def queue_depth(self) -> int:
        """Requests currently admitted and awaiting dispatch (both queues)."""
        return len(self._pending) + len(self._pending_events)

    def submit(self, requests):
        """Queue requests without dispatching (single request or list).

        ``SpikeRequest`` and ``EventRequest`` objects may be mixed; each is
        routed to its own admission queue.  Returns an
        :class:`~repro.serve.overload.AdmissionVerdict` per request (a single
        verdict for a single request): with a bounded queue
        (``queue_limit``) a full queue rejects the request (its ``status``
        becomes ``"rejected"``, nothing is queued) and depth beyond the
        high-water mark flags ``backpressure`` so a closed-loop caller can
        slow down.  Unbounded engines always admit — callers that ignore the
        verdict keep the pre-overload behavior.
        """
        single = isinstance(requests, (SpikeRequest, EventRequest))
        if single:
            requests = [requests]
        verdicts = []
        # one clock read stamps every request this call admits
        t_admit = self._obs_now() if self._obs is not None else 0.0
        n_admitted = 0
        for r in requests:
            depth = self.queue_depth()
            if self._queue_limit is not None and depth >= self._queue_limit:
                r.status = "rejected"
                self._rejected_full += 1
                if self._m is not None:
                    self._m["rejected"].inc()
                if self._tracer is not None:
                    self._tracer.instant("rejected", queue_depth=depth)
                verdicts.append(AdmissionVerdict(
                    admitted=False, reason="queue_full", queue_depth=depth))
                continue
            if isinstance(r, EventRequest):
                self._pending_events.append(r)
            else:
                self._pending.append(r)
            if self._obs is not None:
                self._req_spans[id(r)] = (t_admit, None)
                n_admitted += 1
            depth += 1
            bp = self._high_water is not None and depth > self._high_water
            if bp:
                self._backpressure_events += 1
                if self._m is not None:
                    self._m["backpressure"].inc()
            verdicts.append(AdmissionVerdict(
                admitted=True, backpressure=bp, queue_depth=depth))
        if self._m is not None and n_admitted:
            self._m["submitted"].inc(n_admitted)
            self._m["queue_depth"].set(self.queue_depth())
        return verdicts[0] if single else verdicts

    def submit_events(self, requests):
        """Queue event-stream requests (single ``EventRequest`` or list)."""
        if isinstance(requests, EventRequest):
            requests = [requests]
        assert all(isinstance(r, EventRequest) for r in requests)
        return self.submit(requests)

    def serve(self, requests=None) -> list:
        """Enqueue ``requests`` (optional), drain both queues, flush results.

        Returns the list of requests served in this call (the passed-in list
        when given, else everything that was pending)."""
        if self._obs is None:
            return self._serve(requests)
        with attribute_compiles(self._metrics), self._span("engine.serve"):
            return self._serve(requests)

    def _serve(self, requests) -> list:
        if requests is not None:
            self.submit(requests)
            out = requests if isinstance(requests, list) else [requests]
        else:
            out = list(self._pending) + list(self._pending_events)
        self._shed_expired()
        self._drain_static()
        self._drain_events()
        self._flush()
        return out

    # -------------------------------------------------------------- #
    # drain loops: synchronous (legacy) and overlapped (double-buffered)
    # -------------------------------------------------------------- #
    def _pop_static_round(self) -> list[SpikeRequest]:
        """Pop one round's worth of static requests (up to the fused
        budget — ``fuse_rounds`` legacy rounds coalesced)."""
        self._ladder_tick()
        budget = self._round_budget()
        reqs = self._pending[: budget]
        del self._pending[: budget]
        if self._obs is not None:
            self._obs_queue(reqs)
        return reqs

    def _pop_event_round(self) -> tuple[list[EventRequest], int]:
        """Pop one (batch, T) event round: the head request's effective T
        and everything sharing it, in arrival order, up to the fused budget.
        A degraded ladder level caps T, so streams whose effective
        (truncated) T coincides share a round."""
        self._ladder_tick()
        budget = self._round_budget()
        t_cap = self._level().event_t_cap
        t = self._pending_events[0].n_steps
        if t_cap is not None:
            t = min(t, t_cap)
        round_reqs, rest = [], []
        for r in self._pending_events:
            eff = r.n_steps if t_cap is None else min(r.n_steps, t_cap)
            if eff == t and len(round_reqs) < budget:
                round_reqs.append(r)
            else:
                rest.append(r)
        self._pending_events = rest
        if self._obs is not None:
            self._obs_queue(round_reqs)
        return round_reqs, t

    def _drain_static(self) -> None:
        if self._overlap:
            self._drain_overlap("_pending", self._form_static_round)
            return
        while self._pending:
            self._timed_round(self._dispatch, self._pop_static_round())
            self._shed_expired()

    def _drain_events(self) -> None:
        if self._overlap:
            self._drain_overlap("_pending_events", self._form_event_round)
            return
        while self._pending_events:
            round_reqs, t = self._pop_event_round()
            self._timed_round(self._dispatch_events, round_reqs, t)
            self._shed_expired()

    def _form_static_round(self):
        """Pop a round and split it into (pack, launch) halves so the pack
        (host numpy) can run on the packer thread while the previous round's
        dispatch is in flight.  Everything the closures touch is captured
        here on the main thread; ``launch`` runs JAX calls on the main
        thread only."""
        reqs = self._pop_static_round()
        bucket = self._bucket(len(reqs))
        return (lambda: self._pack_static(reqs, bucket),
                lambda packed, pack_s: self._launch_static(
                    reqs, bucket, packed, pack_s))

    def _form_event_round(self):
        reqs, t = self._pop_event_round()
        bucket = self._bucket(len(reqs))
        for r in reqs:
            r.served_steps = t
        events = [np.asarray(r.events) for r in reqs]  # capture on main thread
        return (lambda: self._pack_events(events, t, bucket),
                lambda packed, pack_s: self._launch_events(
                    reqs, bucket, t, packed, pack_s))

    def _drain_overlap(self, queue_name: str, form) -> None:
        """Double-buffered drain: a bounded depth-2 ring of formed rounds —
        the packer thread builds round N+1's wire-format batch while round
        N's dispatch call runs on the main thread.  The watchdog times the
        dispatch half only (pack time is recorded separately per round, as
        always).  A raising round hook (chaos crash) aborts with formed
        rounds popped-but-unserved — exactly the crash-mid-drain state the
        router's retry path recovers."""
        pool = self._packer_pool()
        ring: collections.deque = collections.deque()
        try:
            while getattr(self, queue_name) or ring:
                while getattr(self, queue_name) and len(ring) < 2:
                    pack, launch = form()
                    ring.append((pool.submit(pack), launch))
                fut, launch = ring.popleft()
                packed, pack_s = fut.result()
                self._timed_round(launch, packed, pack_s)
                self._shed_expired()
        finally:
            while ring:
                ring.popleft()[0].cancel()

    def _packer_pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="spike-packer")
        return self._pool

    def close(self) -> None:
        """Shut down the background packer thread (no-op when never used)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -------------------------------------------------------------- #
    # cold start: AOT-compile the bucket ladder before the first request
    # -------------------------------------------------------------- #
    def warmup(self, *, event_ts=(), aot: bool = True) -> dict:
        """Compile every shape the drain loop can dispatch, ahead of time.

        The static plan is AOT-compiled for the engine's whole bucket
        ladder; ``event_ts`` additionally warms the temporal (bucket, T)
        grid — the set is expanded with the degradation ladder's
        ``event_t_cap`` rungs so degraded rounds stay warm too.  With the
        persistent compilation cache enabled (``launch/env.py``) a restart
        re-warms from disk in milliseconds.  Returns per-shape compile
        seconds plus ``total_s``; after it, serving any warmed shape
        performs zero compilation (regression-tested).
        """
        t0 = time.perf_counter()
        times: dict = {"static": self._plan.warmup(self._buckets, aot=aot)}
        ts = {int(t) for t in event_ts}
        if ts and self._ladder is not None:
            caps = {lv.event_t_cap for lv in self._ladder.levels
                    if lv.event_t_cap is not None}
            ts |= {min(t, c) for t in set(ts) for c in caps}
        for t in sorted(ts):
            times[f"event_t{t}"] = self._event_plan(t).warmup(
                self._buckets, aot=aot)
        if self.telemetry:
            # the jitted cost accounting's dispatch cache keys on the
            # *sharding* of the plan's load outputs, not just their shapes —
            # warm it on real (zeros) plan outputs so the first served round
            # pays no compile outside the plan either.  Nothing is recorded:
            # counters, telemetry totals and the inflight ring stay
            # untouched.
            topo = self.net.topology
            width = self._packing.packed_width(self.n_in)
            ports = self._effective_read_ports()
            tw0 = time.perf_counter()
            for b in self._buckets:
                res = self._plan(jnp.zeros((b, width), jnp.uint32))
                jax.block_until_ready(
                    _stats_jit(topo, ports, False)(res.loads))
                for t in sorted(ts):
                    resT = self._event_plan(t)(
                        jnp.zeros((t, b, width), jnp.uint32))
                    jax.block_until_ready(
                        _stats_jit(topo, ports, True)(resT.loads))
            times["telemetry_s"] = time.perf_counter() - tw0
        times["total_s"] = time.perf_counter() - t0
        if self._metrics is not None:
            from repro.obs.profile import record_warmup_times
            record_warmup_times(self._metrics, times)
        if self._tracer is not None:
            self._tracer.instant("warmup_done", cat="engine",
                                 total_s=times["total_s"],
                                 shapes=len(self._buckets) + len(ts))
        return times

    # -------------------------------------------------------------- #
    # overload plane: deadline shedding + degradation ladder
    # -------------------------------------------------------------- #
    def _shed_expired(self) -> None:
        """Drop still-queued requests whose deadline already passed — they
        would burn a device round only to be useless to the caller.  Shed
        requests get ``status="shed"`` (logits stay None) and are counted in
        ``stats()["shed_deadline"]``.  Requests without a deadline never
        shed (the zero-pressure identity path)."""
        now = None
        for name in ("_pending", "_pending_events"):
            queue = getattr(self, name)
            if not any(r.deadline_s is not None for r in queue):
                continue
            if now is None:
                now = self._clock()
            keep = []
            for r in queue:
                if r.deadline_s is not None and now > r.deadline_s:
                    r.status = "shed"
                    self._shed_deadline += 1
                    if self._m is not None:
                        self._m["shed"].inc()
                    if self._tracer is not None:
                        self._tracer.instant("shed", deadline_s=r.deadline_s)
                    if self._obs is not None:
                        self._obs_close(
                            [r], "event" if isinstance(r, EventRequest)
                            else "static", "shed")
                else:
                    keep.append(r)
            setattr(self, name, keep)

    def _level(self):
        if self._ladder is None:
            from repro.serve.overload import LadderLevel
            return LadderLevel("full")
        return self._ladder.level(self._ladder_level)

    def _round_limit(self) -> int:
        cap = self._level().bucket_cap
        return self.max_batch if cap is None else max(1, min(self.max_batch,
                                                             cap))

    def _fuse_factor(self, dp: int) -> int:
        """Resolve the ``fuse_rounds`` knob: None => 1 (legacy drain),
        ``"auto"`` => the data-parallel degree (dp8 fuses 8 legacy rounds
        into one sharded super-batch), an int => itself."""
        if self._fuse_arg is None:
            return 1
        if self._fuse_arg == "auto":
            return max(1, int(dp))
        return max(1, int(self._fuse_arg))

    def _round_budget(self) -> int:
        """Requests per dispatch round: the ladder's bucket ceiling times
        the fusion factor (itself capped by the level's ``fuse_cap`` so a
        degraded engine sweeps deadlines between smaller rounds)."""
        cap = self._level().fuse_cap
        fuse = self._fuse if cap is None else max(1, min(self._fuse, cap))
        return self._round_limit() * fuse

    def _effective_read_ports(self) -> int:
        ports = self._level().read_ports
        return self.read_ports if ports is None else ports

    def _ladder_tick(self) -> None:
        """One pressure observation per dispatch round.  Pressure = queue
        depth beyond the high-water mark OR the watchdog flagged the previous
        round a straggler.  ``step_down_after`` pressured rounds in a row
        move one level down; ``step_up_after`` clear rounds move back up.
        Every transition is recorded (round index, levels, reason)."""
        if self._ladder is None:
            return
        flagged = len(self._watchdog.flagged)
        straggler = flagged > self._ladder_flagged_seen
        self._ladder_flagged_seen = flagged
        deep = (self._high_water is not None
                and self.queue_depth() > self._high_water)
        if deep or straggler:
            self._pressure_streak += 1
            self._clear_streak = 0
            if (self._pressure_streak >= self._ladder.step_down_after
                    and self._ladder_level < self._ladder.n_levels - 1):
                self._record_transition(
                    self._ladder_level + 1,
                    "queue_depth" if deep else "straggler")
                self._pressure_streak = 0
        else:
            self._clear_streak += 1
            self._pressure_streak = 0
            if (self._clear_streak >= self._ladder.step_up_after
                    and self._ladder_level > 0):
                self._record_transition(self._ladder_level - 1,
                                        "pressure_cleared")
                self._clear_streak = 0

    def _record_transition(self, to_level: int, reason: str) -> None:
        self._transitions.append({
            "round": self._rounds,
            "from_level": self._ladder_level,
            "to_level": to_level,
            "from": self._ladder.level(self._ladder_level).name,
            "to": self._ladder.level(to_level).name,
            "reason": reason,
        })
        if self._m is not None:
            self._m["ladder_transitions"].inc()
            self._m["ladder_level"].set(to_level)
        if self._tracer is not None:
            self._tracer.instant(
                "ladder_transition", cat="ladder", round=self._rounds,
                from_level=self._ladder_level, to_level=to_level,
                reason=reason)
        self._ladder_level = to_level

    def _timed_round(self, dispatch, *args) -> None:
        """One dispatch round under the straggler watchdog: the host-side
        round wall time (packing + dispatch; device work stays async) feeds
        the EMA, and slow rounds are flagged into ``stats()``.  The chaos
        hook runs inside the timed section — an injected stall inflates the
        EMA exactly like a real straggler, and a raising hook aborts the
        round before dispatch (the crash-mid-drain model: this round's
        requests are popped but never served, which is what the router's
        retry path recovers)."""
        t0 = time.perf_counter()
        if self._profiler is not None:
            self._profiler.on_round_start(self._rounds)
        span = (self._tracer.span(
            "engine.round", cat="round", round=self._rounds,
            level=self._level().name, dp=self._dp_degree())
            if self._tracer is not None else contextlib.nullcontext())
        with span:
            if self.round_hook is not None:
                self.round_hook(self._rounds)
            dispatch(*args)
        if self._profiler is not None:
            self._profiler.on_round_end(self._rounds)
        if self._m is not None:
            self._m["rounds"].inc()
            self._m["queue_depth"].set(self.queue_depth())
        self._watchdog.record(self._rounds, time.perf_counter() - t0)
        self._rounds += 1

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _note_round(self, kind: str, bucket: int, n_real: int,
                    pack_s: float, dispatch_s: float,
                    n_legacy: int = 1) -> None:
        """Fold one round into the host-sync observability aggregates.
        ``n_legacy`` is how many legacy (un-fused) bucket-rounds this
        dispatch replaced — rounds where it exceeds 1 count as fused and
        the difference accumulates in ``rounds_saved``."""
        c = self._round_counters
        c[f"rounds_{kind}"] += 1
        c["rows_real"] += n_real
        c["rows_padded"] += bucket - n_real
        c["host_pack_s"] += pack_s
        c["dispatch_s"] += dispatch_s
        if n_legacy > 1:
            c["fused_rounds"] += 1
            c["rounds_saved"] += n_legacy - 1
        if self._m is not None:
            self._m[f"served_{kind}"].inc(n_real)
            self._m["rows_real"].inc(n_real)
            self._m["rows_padded"].inc(bucket - n_real)
            self._m["pack_s"].observe(pack_s)
            self._m["dispatch_s"].observe(dispatch_s)
            if n_legacy > 1:
                self._m["fused"].inc()
                self._m["rounds_saved"].inc(n_legacy - 1)
        self._rounds_per_bucket[bucket] = (
            self._rounds_per_bucket.get(bucket, 0) + 1)
        self._padded_rows_per_bucket[bucket] = (
            self._padded_rows_per_bucket.get(bucket, 0) + bucket - n_real)
        self._real_rows_per_bucket[bucket] = (
            self._real_rows_per_bucket.get(bucket, 0) + n_real)

    def _n_legacy(self, n: int) -> int:
        """Legacy bucket-rounds a super-batch of ``n`` requests replaces."""
        return max(1, math.ceil(n / self._round_limit()))

    def _pack_static(self, reqs: list[SpikeRequest],
                     bucket: int) -> tuple[np.ndarray, float]:
        """Host half of a static round: bit-pack to the padded wire format
        (pure numpy — safe on the packer thread)."""
        span = (self._tracer.span(
            "engine.pack", cat="round", kind="static", bucket=bucket,
            n_real=len(reqs))
            if self._tracer is not None else contextlib.nullcontext())
        with span:
            t0 = time.perf_counter()
            packed = self._packing.pack_padded_rows_np(
                [r.spikes for r in reqs], bucket, self.n_in)
            pack_s = time.perf_counter() - t0
        return packed, pack_s

    def _launch_static(self, reqs: list[SpikeRequest], bucket: int,
                       packed: np.ndarray, pack_s: float) -> None:
        """Device half: run the plan, keep every result device-side (no
        host sync here).  Pack time and dispatch-call time are recorded
        separately per bucket — the observability that attributed the dp8
        regression to host sync + tiny per-bucket dispatches."""
        span = (self._tracer.span(
            "engine.dispatch", cat="round", kind="static", bucket=bucket,
            n_real=len(reqs), dp=self._dp_degree())
            if self._tracer is not None else contextlib.nullcontext())
        with span:
            t1 = time.perf_counter()
            res = self._plan(jnp.asarray(packed))
            rs = None
            if self.telemetry:
                # lazy device-side cost — nothing is synced inside the drain
                rs = _stats_jit(self.net.topology,
                                self._effective_read_ports(), False)(res.loads)
            t2 = time.perf_counter()
        if self._tracer is not None:
            n_legacy = self._n_legacy(len(reqs))
            if n_legacy > 1:
                self._tracer.instant("fuse", cat="round", bucket=bucket,
                                     rounds_coalesced=n_legacy)
        self._note_round("static", bucket, len(reqs), pack_s, t2 - t1,
                         self._n_legacy(len(reqs)))
        self._served += len(reqs)
        self._inflight.append((reqs, res.logits, rs))

    def _dispatch(self, reqs: list[SpikeRequest]) -> None:
        """One continuous-batching round (synchronous path): pad to bucket,
        pack, launch."""
        bucket = self._bucket(len(reqs))
        packed, pack_s = self._pack_static(reqs, bucket)
        self._launch_static(reqs, bucket, packed, pack_s)

    def _event_plan(self, n_steps: int):
        """The (donated) temporal plan for effective stream length
        ``n_steps`` — cached per (batch-invariant) spec on the network."""
        cfg = dataclasses.replace(self._temporal, n_steps=n_steps)
        return self.net.plan(
            mode="temporal", temporal=cfg, telemetry=self.telemetry,
            interpret=self._interpret, faults=self.faults, rules=self.rules,
            donate=True)

    def _pack_events(self, events: list[np.ndarray], n_steps: int,
                     bucket: int) -> tuple[np.ndarray, float]:
        """Host half of an event round (pure numpy — packer-thread safe)."""
        width = self._packing.packed_width(self.n_in)
        span = (self._tracer.span(
            "engine.pack", cat="round", kind="event", bucket=bucket,
            t=n_steps, n_real=len(events))
            if self._tracer is not None else contextlib.nullcontext())
        with span:
            t0 = time.perf_counter()
            packed = np.zeros((n_steps, bucket, width), np.uint32)
            for i, ev in enumerate(events):
                assert ev.shape[0] >= n_steps, (ev.shape, n_steps)
                if ev.dtype == np.uint32 and ev.shape[-1] == width:
                    packed[:, i] = ev[:n_steps]
                else:
                    assert ev.shape[1:] == (self.n_in,), (ev.shape, self.n_in)
                    packed[:, i] = self._packing.pack_spikes_np(
                        ev[:n_steps] != 0)
            pack_s = time.perf_counter() - t0
        return packed, pack_s

    def _launch_events(self, reqs: list[EventRequest], bucket: int,
                       n_steps: int, packed: np.ndarray,
                       pack_s: float) -> None:
        span = (self._tracer.span(
            "engine.dispatch", cat="round", kind="event", bucket=bucket,
            t=n_steps, n_real=len(reqs), dp=self._dp_degree())
            if self._tracer is not None else contextlib.nullcontext())
        with span:
            t1 = time.perf_counter()
            res = self._event_plan(n_steps)(jnp.asarray(packed))
            rs = None
            if self.telemetry:
                rs = _stats_jit(self.net.topology,
                                self._effective_read_ports(), True)(res.loads)
            t2 = time.perf_counter()
        if self._tracer is not None:
            n_legacy = self._n_legacy(len(reqs))
            if n_legacy > 1:
                self._tracer.instant("fuse", cat="round", bucket=bucket,
                                     rounds_coalesced=n_legacy)
        self._note_round("event", bucket, len(reqs), pack_s, t2 - t1,
                         self._n_legacy(len(reqs)))
        self._served_events += len(reqs)
        self._served_timesteps += len(reqs) * n_steps
        if self._m is not None:
            self._m["timesteps"].inc(len(reqs) * n_steps)
        self._inflight.append((reqs, res.logits, rs))

    def _dispatch_events(self, reqs: list[EventRequest], n_steps: int) -> None:
        """One event round (synchronous path): same-T requests padded to a
        batch bucket and run through the temporal plan (compiled once per
        (batch, T) shape); the stream cost stays device-side like the
        static path's.  ``n_steps`` is the *effective* T — a degraded
        ladder level truncates longer streams to it (recorded per request
        as ``served_steps``)."""
        bucket = self._bucket(len(reqs))
        for r in reqs:
            r.served_steps = n_steps
        events = [np.asarray(r.events) for r in reqs]
        packed, pack_s = self._pack_events(events, n_steps, bucket)
        self._launch_events(reqs, bucket, n_steps, packed, pack_s)

    def _flush(self) -> None:
        """Attach logits/labels (+ per-request cost) and fold the telemetry
        totals — one host transfer per round's arrays, all at drain end
        rather than inside the dispatch loop.  Totals accumulate in float64
        here (the arrays are on the host anyway for per-request attachment),
        masking the zero-padded tail slots of each bucket.  Per round, the
        pulls of every per-request array come first (``engine.device_drain``),
        then the per-request attach and the folds (``engine.telemetry_flush``),
        then the pull of the static per-tile totals, which no request waits
        for (a second ``engine.device_drain``)."""
        with self._span("engine.flush", rounds=len(self._inflight)):
            self._flush_rounds()

    def _flush_rounds(self) -> None:
        for reqs, logits_j, rs in self._inflight:
            n = len(reqs)
            is_event = bool(reqs) and isinstance(reqs[0], EventRequest)
            kind = "event" if is_event else "static"
            span = (self._tracer.span("engine.device_drain", cat="flush",
                                      kind=kind, n_real=n)
                    if self._tracer is not None else contextlib.nullcontext())
            with span:
                logits = np.asarray(logits_j)
                if rs is not None:
                    cycles = np.asarray(rs["cycles"])
                    latency = np.asarray(rs["latency_ns"])
                    energy = np.asarray(rs["energy_pj"])
                    if is_event:
                        per_step = np.asarray(rs["energy_pj_per_step"])
            span = (self._tracer.span("engine.telemetry_flush", cat="flush",
                                      kind=kind, n_real=n,
                                      telemetry=rs is not None)
                    if self._tracer is not None else contextlib.nullcontext())
            with span:
                for i, r in enumerate(reqs):
                    r.logits = logits[i]
                    r.label = int(logits[i].argmax())
                    r.status = "done"
                if rs is not None:
                    for i, r in enumerate(reqs):
                        r.cycles = int(cycles[i])
                        r.latency_ns = float(latency[i])
                        r.energy_pj = float(energy[i])
                    if is_event:
                        for i, r in enumerate(reqs):
                            r.energy_pj_per_step = float(per_step[i])
                    tot = self._event_totals if is_event else self._totals
                    cycles_sum = float(cycles[:n].sum(dtype=np.float64))
                    energy_sum = float(energy[:n].sum(dtype=np.float64))
                    tot["cycles"] += cycles_sum
                    tot["latency_ns"] += float(
                        latency[:n].sum(dtype=np.float64))
                    tot["energy_pj"] += energy_sum
                    if self._m is not None:
                        self._m["cycles"].inc(cycles_sum)
                        self._m["energy"].inc(energy_sum)
            if rs is not None and not is_event:
                # static pipeline: per-tile stage totals feed the
                # pipelined-throughput bottleneck model; pulled after the
                # attach, so no request's results wait for them
                span = (self._tracer.span("engine.device_drain", cat="flush",
                                          kind=kind, n_real=n, tiles=True)
                        if self._tracer is not None
                        else contextlib.nullcontext())
                with span:
                    cycles_per_tile = np.asarray(rs["cycles_per_tile"],
                                                 np.float64)
                self._totals["cycles_per_tile"] += cycles_per_tile[:n].sum(
                    axis=0)
            if self._obs is not None:
                self._obs_close(reqs, kind, "done")
        self._inflight.clear()
        if self._m is not None and self.telemetry and self._served:
            self._m["health"].set(self.health())

    # -------------------------------------------------------------- #
    # fault-aware serving: tile health + degraded-mesh replan
    # -------------------------------------------------------------- #
    def tile_health(self) -> np.ndarray:
        """Per-tile health score in [0, 1] from device-resident telemetry.

        The engine's telemetry totals already carry each tile's measured
        drain cycles (group popcounts straight off the wire, folded at
        flush).  Health is ``1 - |measured - expected| / expected`` against
        the reference-activity calibration, clipped to [0, 1]: stuck-at-1
        faults inflate a tile's arbiter loads, dead/stuck-at-0 columns
        silence them, and both read as deviation.  Tiles with no traffic yet
        (or telemetry off) score 1.0 — unknown is not degraded.
        """
        n_tiles = len(self.net.topology) - 1
        if not self.telemetry or self._served == 0:
            return np.ones((n_tiles,))
        measured = self._totals["cycles_per_tile"] / self._served
        dev = np.abs(measured - self._expected_tile_cycles) / np.maximum(
            self._expected_tile_cycles, 1e-9)
        return np.clip(1.0 - dev, 0.0, 1.0)

    def health(self) -> float:
        """Engine health: the weakest tile's score (pipeline bottleneck)."""
        return float(self.tile_health().min())

    def replan_degraded(self, n_devices: int) -> ft.ReplanResult:
        """Degraded-mesh operation: shrink the data-parallel mesh to the
        surviving device count and recompile the serving plan.

        In-flight results are flushed first, then ``elastic_replan`` picks
        the largest power-of-two data axis within ``n_devices`` (surplus
        chips idle as hot spares — ``.dropped_chips`` of the returned plan),
        the bucket ladder is rebuilt for the new divisibility, and the
        engine's plan is recompiled with the same fault model.  Telemetry
        totals survive (same network, same tiles).
        """
        self._flush()
        if self._tracer is not None:
            self._tracer.instant("replan_degraded", cat="engine",
                                 n_devices=int(n_devices))
        plan = ft.elastic_replan(max(1, int(n_devices)), model_parallel=1)
        (data, _), _ = plan
        self.rules = (shd.make_esam_rules(shd.esam_data_mesh(data))
                      if data > 1 else None)
        dp = 1 if self.rules is None else self.rules.axis_size("spike_batch")
        self._fuse = self._fuse_factor(dp)   # "auto" tracks the new mesh
        self._buckets = _bucket_sizes(
            self.max_batch * self._fuse, self._min_bucket, dp)
        self._plan = self.net.plan(
            mode="packed", telemetry=self.telemetry,
            interpret=self._interpret, faults=self.faults, rules=self.rules,
            donate=True)
        return plan

    # -------------------------------------------------------------- #
    # aggregate telemetry
    # -------------------------------------------------------------- #
    def _pad_fraction_per_bucket(self) -> dict[int, float]:
        """Per-bucket pad overhead, safe under fused rounds: a bucket a
        fused super-batch only ever grazed (or that saw zero real rows — a
        formed-but-crashed round) divides by its total rows, never by
        zero."""
        out = {}
        for b in sorted(set(self._padded_rows_per_bucket)
                        | set(self._real_rows_per_bucket)):
            pad = self._padded_rows_per_bucket.get(b, 0)
            real = self._real_rows_per_bucket.get(b, 0)
            out[b] = pad / (pad + real) if (pad + real) else 0.0
        return out

    def stats(self) -> dict:
        """Aggregate hardware-cost telemetry in paper units.

        Safe to call at any time: before anything is served it returns the
        well-defined empty aggregate (all-zero costs, ``n_requests == 0``).
        A pure host read — no device work: the totals were folded in exact
        float64 at each drain flush.
        """
        spec = self._cm.cell_spec(self.read_ports)
        n = self._served
        ne, nt = self._served_events, self._served_timesteps
        et = self._event_totals
        base = {
            "stats_schema_version": STATS_SCHEMA_VERSION,
            "requests": n,          # legacy key
            "n_requests": n,
            "telemetry": self.telemetry,
            "cell": spec.name,
            "read_ports": self.read_ports,
            "data_parallel": 1 if self.rules is None
            else self.rules.axis_size("spike_batch"),
            # fault-aware serving: health + dispatch-round watchdog
            "faulted": self.faults is not None,
            "tile_health": [float(h) for h in self.tile_health()],
            "health": self.health(),
            "degraded": self.health() < self.health_threshold,
            "dispatch_rounds": self._rounds,
            "straggler_rounds": len(self._watchdog.flagged),
            # overload plane: admission + deadline + degradation ladder
            "queue_depth": self.queue_depth(),
            "queue_limit": self._queue_limit,
            "high_water": self._high_water,
            "shed_deadline": self._shed_deadline,
            "rejected_full": self._rejected_full,
            "backpressure_events": self._backpressure_events,
            "degradation_level": self._ladder_level,
            "degradation_level_name": self._level().name,
            "ladder_transitions": len(self._transitions),
            "ladder_transition_log": list(self._transitions),
            # per-round host-sync/dispatch observability (dp8 regression
            # diagnosis): pack time vs dispatch-call time, pad overhead
            "rounds_static": self._round_counters["rounds_static"],
            "rounds_event": self._round_counters["rounds_event"],
            "rows_real_total": self._round_counters["rows_real"],
            "rows_padded_total": self._round_counters["rows_padded"],
            "pad_fraction": (
                self._round_counters["rows_padded"]
                / max(1, self._round_counters["rows_real"]
                      + self._round_counters["rows_padded"])),
            "rounds_per_bucket": dict(self._rounds_per_bucket),
            "padded_rows_per_bucket": dict(self._padded_rows_per_bucket),
            "real_rows_per_bucket": dict(self._real_rows_per_bucket),
            "pad_fraction_per_bucket": self._pad_fraction_per_bucket(),
            "host_pack_s_total": self._round_counters["host_pack_s"],
            "dispatch_s_total": self._round_counters["dispatch_s"],
            # fused async dispatch (the dp-scaling fix): configuration plus
            # evidence of fewer, larger rounds
            "fuse_rounds": self._fuse,
            "overlap": self._overlap,
            "fused_rounds": self._round_counters["fused_rounds"],
            "rounds_saved": self._round_counters["rounds_saved"],
            # event-stream aggregates (temporal plane)
            "n_event_requests": ne,
            "timesteps_total": nt,
            "event_energy_pj_mean": et["energy_pj"] / ne if ne else 0.0,
            "event_latency_ns_mean": et["latency_ns"] / ne if ne else 0.0,
            "event_cycles_mean": et["cycles"] / ne if ne else 0.0,
            "energy_pj_per_timestep": et["energy_pj"] / nt if nt else 0.0,
        }
        if n == 0:
            return {**base, "cycles_mean": 0.0, "latency_ns_mean": 0.0,
                    "energy_pj_per_inf": 0.0, "throughput_inf_s": 0.0,
                    "throughput_pipelined_inf_s": 0.0}
        mean_latency_ns = self._totals["latency_ns"] / n
        # pipelined rate: tiles overlap consecutive samples, so the slowest
        # mean tile stage sets the cadence (same model as system_stats)
        bottleneck_cycles = float(np.max(self._totals["cycles_per_tile"])) / n
        return {
            **base,
            "cycles_mean": self._totals["cycles"] / n,
            "latency_ns_mean": mean_latency_ns,
            "energy_pj_per_inf": self._totals["energy_pj"] / n,
            # un-pipelined device-side rate implied by the mean latency
            "throughput_inf_s":
                1e9 / mean_latency_ns if mean_latency_ns else 0.0,
            "throughput_pipelined_inf_s":
                1e9 / (bottleneck_cycles * spec.clock_ns)
                if bottleneck_cycles else 0.0,
        }


# ------------------------------------------------------------------ #
# fault-aware routing across SpikeEngine replicas
# ------------------------------------------------------------------ #
class ReplicaCrashError(RuntimeError):
    """A replica died mid-drain (the chaos harness injects these).  The
    router treats only this as a crash: any other exception from a replica
    — a compile refusal, a bad shape — is a bug and propagates."""


class AllReplicasDownError(RuntimeError):
    """Every replica has crashed — nothing can serve."""


class AllReplicasDegradedError(RuntimeError):
    """Every live replica is below the health threshold and the router was
    built with ``on_all_degraded="raise"``."""


class FaultAwareRouter:
    """Drains spike traffic around degraded, stalled, and crashed replicas.

    Holds N ``SpikeEngine`` replicas (each typically a physical macro / mesh
    slice, possibly built with its own ``FaultModel``) and routes every
    request by tile health: round-robin across the replicas whose weakest
    tile still scores above ``health_threshold``.  When *all* live replicas
    are degraded the router either raises (``on_all_degraded="raise"``) or
    falls back to the healthiest one — but never silently: every fallback is
    counted in ``stats()["degraded_route"]`` so callers can see traffic
    landing on known-bad silicon.  Health comes from each engine's
    device-resident telemetry — the router performs no extra device work.

    Overload hardening (``retry`` — a :class:`fault_tolerance.RetryPolicy`):
    a replica that *crashes mid-drain* (its drain raises; chaos models this
    with a raising round hook) is taken out of rotation and every request it
    had queued-but-not-completed is re-routed to a surviving replica after
    exponential backoff with counter-based seeded jitter (deterministic —
    no wall-clock RNG in the datapath).  A replica whose drain exceeds
    ``retry.attempt_timeout_s`` is counted a timeout and marked *slow*:
    round-robin prefers non-slow healthy replicas from then on.  Requests
    whose retry budget is exhausted get ``status="failed"`` instead of being
    silently lost.
    """

    def __init__(self, engines, *, health_threshold: float = 0.75,
                 retry: Optional[ft.RetryPolicy] = None,
                 on_all_degraded: str = "fallback",
                 observability: Optional[Observability] = None,
                 sleep=time.sleep, clock=time.monotonic):
        assert engines, "router needs at least one engine"
        assert on_all_degraded in ("fallback", "raise"), on_all_degraded
        self.engines = list(engines)
        self.health_threshold = health_threshold
        self.retry = retry or ft.RetryPolicy()
        self.on_all_degraded = on_all_degraded
        self.routed = [0] * len(self.engines)
        self.counters = {"retries": 0, "crashes": 0, "timeouts": 0,
                         "degraded_route": 0, "rejected_full": 0,
                         "failed": 0}
        self._rr = 0
        self._down: set[int] = set()
        self._slow: set[int] = set()
        self._assigned: list[list] = [[] for _ in self.engines]
        self._backoff_counter = 0
        self._sleep = sleep
        self._clock = clock
        self._obs = observability
        self._tracer = observability.tracer if observability else None
        self._metrics = observability.metrics if observability else None

    def _count(self, name: str, n: int = 1) -> None:
        """Bump a router counter, mirrored into ``esam_router_*_total``."""
        self.counters[name] += n
        if self._metrics is not None:
            self._metrics.counter(
                f"esam_router_{name}_total",
                "fault-aware router event counter").inc(n)

    def _health_gauges(self) -> None:
        if self._metrics is not None:
            self._metrics.gauge(
                "esam_router_replicas_down",
                "replicas out of rotation (crashed)").set(len(self._down))
            self._metrics.gauge(
                "esam_router_replicas_slow",
                "replicas flagged slow (drain timeout)").set(len(self._slow))

    def backlog(self) -> int:
        """Routed requests not yet completed on a live replica."""
        return sum(len(self._assigned[i]) for i in range(len(self.engines))
                   if i not in self._down)

    def route(self, request, *, exclude=()) -> Optional[int]:
        """Queue one request on the chosen replica; returns its index, or
        ``None`` when every candidate's bounded queue rejected it (the
        request's status is then ``"rejected"`` and
        ``stats()["rejected_full"]`` counts it)."""
        avoid = set(exclude) | self._down
        candidates = [i for i in range(len(self.engines)) if i not in avoid]
        if not candidates:
            raise AllReplicasDownError(
                f"all {len(self.engines)} replicas are down")
        scores = {i: self.engines[i].health() for i in candidates}
        healthy = [i for i in candidates
                   if scores[i] >= self.health_threshold]
        fast = [i for i in healthy if i not in self._slow]
        pool = fast or healthy
        if pool:
            idx = pool[self._rr % len(pool)]
            self._rr += 1
            order = [idx] + [i for i in pool if i != idx] + sorted(
                (i for i in candidates if i not in pool),
                key=lambda i: -scores[i])
        else:
            # every live candidate is degraded: no silent routing onto
            # known-bad silicon — count it, and raise if so configured
            self._count("degraded_route")
            if self._tracer is not None:
                self._tracer.instant("degraded_route", cat="router",
                                     scores={i: float(s)
                                             for i, s in scores.items()})
            if self.on_all_degraded == "raise":
                raise AllReplicasDegradedError(
                    f"all live replicas below health threshold "
                    f"{self.health_threshold} (scores: {scores})")
            order = sorted(candidates, key=lambda i: -scores[i])
        for idx in order:
            verdict = self.engines[idx].submit(request)
            if verdict is None or verdict.admitted:
                request.status = "pending"   # clear any earlier rejection
                if pool and idx not in pool:
                    # healthy queues were all full and the request spilled
                    # onto a degraded replica — visible, not silent
                    self._count("degraded_route")
                self._assigned[idx].append(request)
                self.routed[idx] += 1
                return idx
        self._count("rejected_full")
        return None

    def serve(self, requests=None) -> list:
        """Route ``requests`` (optional), then drain every live replica —
        re-routing work off any replica that crashes or stalls mid-drain."""
        if requests is not None:
            if isinstance(requests, (SpikeRequest, EventRequest)):
                requests = [requests]
            for r in requests:
                self.route(r)
        self._drain()
        return requests if requests is not None else []

    def _drain(self) -> None:
        """Drain passes until every routed request reaches a terminal state.

        A crash mid-drain moves the replica to ``_down`` and re-routes its
        incomplete requests (retry + backoff), which may enqueue work on a
        replica already drained this pass — hence the outer loop.  Bounded:
        each pass either completes requests or downs a replica."""
        max_passes = 2 * len(self.engines) + 2
        for _ in range(max_passes):
            for idx, eng in enumerate(self.engines):
                if idx in self._down:
                    continue
                if not (self._assigned[idx] or eng.queue_depth()):
                    continue
                span = (self._tracer.span("router.replica_drain",
                                          cat="router", replica=idx)
                        if self._tracer is not None
                        else contextlib.nullcontext())
                t0 = self._clock()
                try:
                    with span:
                        eng.serve()
                except ReplicaCrashError:
                    self._on_crash(idx)
                    continue
                dt = self._clock() - t0
                to = self.retry.attempt_timeout_s
                if to is not None and dt > to:
                    self._count("timeouts")
                    self._slow.add(idx)
                    if self._tracer is not None:
                        self._tracer.instant("replica_slow", cat="router",
                                             replica=idx, drain_s=dt,
                                             timeout_s=to)
                    self._health_gauges()
                self._assigned[idx] = [
                    r for r in self._assigned[idx]
                    if r.logits is None and r.status == "pending"]
            if self.backlog() == 0:
                return

    def _on_crash(self, idx: int) -> None:
        """Crashed replica: out of rotation; re-route its incomplete
        requests with exponential backoff + seeded jitter.  Requests it
        already completed keep their results (exactly-once: results attach
        on exactly one replica; lost in-flight work is re-served)."""
        self._count("crashes")
        self._down.add(idx)
        self._health_gauges()
        victims = [r for r in self._assigned[idx]
                   if r.logits is None and r.status == "pending"]
        if self._tracer is not None:
            self._tracer.instant("replica_crash", cat="router", replica=idx,
                                 victims=len(victims))
        self._assigned[idx] = []
        # empty the dead replica's queues: its pending requests are exactly
        # the victims being re-routed, and leaving them behind would both
        # leak queue depth and double-serve if the engine were ever drained
        # again (exactly-once depends on this)
        eng = self.engines[idx]
        eng._pending.clear()
        eng._pending_events.clear()
        eng._inflight.clear()
        for r in victims:
            r.attempts += 1
            if r.attempts >= self.retry.max_attempts:
                r.status = "failed"
                self._count("failed")
                continue
            self._backoff_counter += 1
            self._sleep(self.retry.backoff_s(r.attempts,
                                             self._backoff_counter))
            try:
                dest = self.route(r, exclude={idx})
            except AllReplicasDownError:
                r.status = "failed"
                self._count("failed")
                continue
            if dest is not None:
                self._count("retries")
                if self._tracer is not None:
                    self._tracer.instant("reroute", cat="router",
                                         from_replica=idx, to_replica=dest,
                                         attempt=r.attempts)

    def stats(self) -> dict:
        per_engine = [
            {"health": e.health(), "degraded": h < self.health_threshold,
             "down": i in self._down, "slow": i in self._slow,
             "routed": n, "n_requests": e.stats()["n_requests"]}
            for i, (e, n, h) in enumerate(zip(
                self.engines, self.routed,
                (e.health() for e in self.engines)))
        ]
        return {
            "n_engines": len(self.engines),
            "health_threshold": self.health_threshold,
            "routed": list(self.routed),
            "engines": per_engine,
            "down": sorted(self._down),
            "slow": sorted(self._slow),
            "backlog": self.backlog(),
            **self.counters,
        }
