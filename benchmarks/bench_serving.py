"""Sharded-plan serving throughput: the ESAM system-level claim as a bench.

Drives ``SpikeEngine`` (admission queue -> power-of-two buckets -> one
compiled, optionally ``shard_map``-ped packed plan, with fused multi-round
dispatch + host/device overlap) with synthetic digit traffic and records,
per configuration:

  * wall-clock serving rate (requests/s) on this host,
  * the modeled hardware operating point in paper units — pipelined MInf/s
    and pJ/Inf from the device-resident telemetry accumulators,
  * dp-scaling lanes (dp2/dp4/dp8 on the host-platform mesh): each lane's
    req/s ratio vs the single-device lane (``vs_single``) plus the fused
    round counters — the regression gate for the old dp8 0.29x loss,
  * a cold-start lane: first-request latency on a cold engine vs an
    AOT-warmed one (``SpikeEngine.warmup``), fresh networks per lane so no
    plan cache crosses over,
  * open-loop lanes (seeded Poisson arrivals below and above saturation
    plus a request storm): p50/p99/p99.9 latency, shed / rejected counts,
    and goodput-under-SLO through the overload-hardened plane (bounded
    queue, deadlines, degradation ladder),
  * a chaos lane: two replicas behind the retrying ``FaultAwareRouter``
    with one crashed mid-drain and one slowed — completion accounting and
    retry counts,
  * an observability-overhead lane: the same warmed drain with the tracing
    + metrics plane on vs off (best-of-3 each side) — CI gates the
    ``overhead_pct`` under the plane's 5% budget,

into ``BENCH_serving.json`` (override with env BENCH_SERVING_OUT).  Run
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise
the data-parallel plan on CPU; set ``BENCH_SERVING_SMOKE=1`` for the small
CI configuration.
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder
except ModuleNotFoundError:  # direct `python benchmarks/bench_serving.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder
from repro.core.esam import cost_model as cm
from repro.core.esam.network import EsamNetwork
from repro.data import digits
from repro.distributed import sharding as shd
from repro.serve.engine import SpikeEngine, SpikeRequest

# enough requests that the dp lanes measure steady-state super-batching
# (at 256 the whole run is one or two rounds and fixed dispatch overhead
# dominates the scaling ratio)
N_REQUESTS = int(os.environ.get("BENCH_SERVING_REQUESTS", "2048"))
MAX_BATCH = 128


def _paper_net(seed: int = 0) -> EsamNetwork:
    key = jax.random.PRNGKey(seed)
    topo = cm.PAPER_TOPOLOGY
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(len(topo) - 1)
    ]
    vth = [jnp.zeros((n,), jnp.int32) for n in topo[1:]]
    return EsamNetwork(weight_bits=bits, vth=vth,
                       out_offset=jnp.zeros((topo[-1],), jnp.float32))


def _serve_once(rec: Recorder, tag: str, net, reqs_np, rules,
                vs_single: float = None) -> float:
    """One throughput lane on the fused async engine.  ``warmup()`` AOT-
    compiles the bucket ladder (and warms the telemetry ops) up front, so
    the timed run measures steady-state serving and the timed engine's
    stats() see only the timed requests.  Returns the req/s rate; dp lanes
    pass the single-device rate as ``vs_single`` to record the scaling
    ratio the CI gate asserts."""
    engine_kw = dict(max_batch=MAX_BATCH, telemetry=True, read_ports=4,
                     rules=rules, fuse_rounds="auto", overlap=True)
    eng = SpikeEngine(net, **engine_kw)
    eng.warmup()
    reqs = [SpikeRequest(spikes=r) for r in reqs_np]
    t0 = time.perf_counter()
    eng.serve(reqs)
    wall_s = time.perf_counter() - t0
    st = eng.stats()
    req_s = len(reqs) / wall_s
    extra = "" if vs_single is None else (
        f"vs_single={req_s / vs_single:.2f}x;"
        f"scaling_efficiency={req_s / (vs_single * st['data_parallel']):.2f};")
    rec.emit(
        f"serving_{tag}", wall_s * 1e6 / len(reqs),
        f"requests={len(reqs)};requests_per_s={req_s:,.0f};"
        f"data_parallel={st['data_parallel']};buckets={eng._buckets};"
        f"{extra}"
        f"fuse={st['fuse_rounds']};overlap={st['overlap']};"
        f"rounds_static={st['rounds_static']};"
        f"fused_rounds={st['fused_rounds']};"
        f"rounds_saved={st['rounds_saved']};"
        f"model_minf_s={st['throughput_pipelined_inf_s']/1e6:.2f}"
        f"(paper {cm.PAPER_THROUGHPUT_INF_S/1e6:.0f});"
        f"model_energy_pj_inf={st['energy_pj_per_inf']:.0f}"
        f"(paper {cm.PAPER_ENERGY_PJ_PER_INF:.0f});"
        f"cell={st['cell']}",
    )
    eng.close()
    return req_s


SMOKE = bool(os.environ.get("BENCH_SERVING_SMOKE"))


def _overload_lanes(rec: Recorder, net) -> None:
    """Open-loop Poisson lanes below and above saturation + a chaos lane.

    The over-saturation lane adds a request storm against a bounded queue,
    so sheds/rejections are structurally guaranteed (the CI overload smoke
    asserts ``shed_total > 0``), and the deadline turns queue growth into
    deadline sheds rather than unbounded latency.
    """
    from repro.serve.overload import DegradationLadder
    from repro.serve.traffic import ChaosConfig, TrafficConfig, run_open_loop
    from repro.train.fault_tolerance import RetryPolicy

    n = 48 if SMOKE else 160
    max_batch = 32
    queue_limit = 2 * max_batch
    n_in = net.topology[0]

    def mk(queue_limit=queue_limit):
        return SpikeEngine(net, max_batch=max_batch, telemetry=True,
                           queue_limit=queue_limit,
                           ladder=DegradationLadder.default(max_batch))

    # AOT-warm every bucket in the ladder, then measure the sustainable
    # rate on an unbounded engine so the lane rates are anchored at this
    # host's actual saturation point.  (Open-loop rounds can be as small as
    # one request; an unwarmed small bucket would charge its compile to the
    # first lane round, shedding everything behind it on the deadline.)
    blend = dict(n_requests=n, p_event=0.0, n_in=n_in)
    warm = mk(queue_limit=None)
    from repro.serve.traffic import build_requests, warmup_engine
    warmup_engine(warm, TrafficConfig(rate_hz=1.0, **blend))
    timed = build_requests(TrafficConfig(rate_hz=1.0, seed=22, **blend))[0]
    t0 = time.perf_counter()
    warm.serve(timed)
    rate_sust = len(timed) / (time.perf_counter() - t0)
    # ~48 requests' worth of service: comfortably above one open-loop
    # drain's latency floor, so goodput separates the lanes (≈1 under
    # saturation, <1 over it) instead of reading 0 everywhere
    deadline_s = 48.0 / rate_sust
    slo_s = deadline_s

    lanes = [
        ("under", 0.5 * rate_sust, None),
        ("over", 2.0 * rate_sust,
         ChaosConfig(storm_at_s=0.0, storm_size=3 * queue_limit)),
    ]
    for tag, rate, chaos in lanes:
        eng = mk()
        cfg = TrafficConfig(rate_hz=rate, seed=23, deadline_s=deadline_s,
                            **blend)
        rep = run_open_loop(eng, cfg, slo_s=slo_s, chaos=chaos)
        shed_total = rep.n_shed + rep.n_rejected
        rec.emit(
            f"serving_openloop_{tag}", rep.p99_ms * 1e3,
            f"rate_hz={rate:.0f};sustainable_hz={rate_sust:.0f};"
            f"offered={rep.n_offered};completed={rep.n_completed};"
            f"p50_ms={rep.p50_ms:.2f};p99_ms={rep.p99_ms:.2f};"
            f"p999_ms={rep.p999_ms:.2f};goodput_slo={rep.goodput_slo:.3f};"
            f"slo_ms={1e3 * slo_s:.1f};deadline_ms={1e3 * deadline_s:.1f};"
            f"shed={rep.n_shed};rejected={rep.n_rejected};"
            f"shed_total={shed_total};"
            f"backpressure={rep.backpressure_events};"
            f"ladder_transitions={rep.ladder_transitions};"
            f"max_degradation_level={rep.max_degradation_level}",
        )

    # chaos lane: replica 0 crashes mid-drain, replica 1 runs 10x slowed —
    # the router's retry/backoff path must complete every admitted request
    engines = [mk(queue_limit=None), mk(queue_limit=None)]
    from repro.serve.engine import FaultAwareRouter
    router = FaultAwareRouter(
        engines, retry=RetryPolicy(max_attempts=4, base_backoff_s=1e-4,
                                   seed=5))
    chaos = ChaosConfig(slowdown=((1, 2e-3),), crash_replica=0,
                        crash_after_rounds=1)
    cfg = TrafficConfig(rate_hz=2.0 * rate_sust, seed=29, **blend)
    rep = run_open_loop(router, cfg, chaos=chaos)
    lost = rep.n_offered - (rep.n_completed + rep.n_shed + rep.n_rejected
                            + rep.n_failed)
    assert lost == 0, f"chaos lane lost {lost} requests"
    rec.emit(
        "serving_chaos", rep.p99_ms * 1e3,
        f"offered={rep.n_offered};completed={rep.n_completed};"
        f"retries={rep.retries};crashes={rep.crashes};"
        f"timeouts={rep.timeouts};failed={rep.n_failed};lost={lost};"
        f"p99_ms={rep.p99_ms:.2f}",
    )


def _obs_overhead_lane(rec: Recorder, net) -> None:
    """Tracer-on vs tracer-off drain cost: the observability plane's <5%
    overhead budget as a measured lane (the CI serving-bench validation
    gates ``overhead_pct`` against it).

    Both sides serve the identical warmed closed-loop workload; best-of-3
    medians each side so a CI noise spike on either doesn't fail the gate.
    Tracing + metrics ride the full path (request spans, round/pack/
    dispatch spans, histogram observes) into a fresh registry per repeat.
    """
    from repro.obs import Observability
    from repro.obs.metrics import Registry

    n = 256 if SMOKE else 1024
    x, _ = digits.make_spike_dataset(n, seed=31)

    def drain_s(obs) -> float:
        eng = SpikeEngine(net, max_batch=MAX_BATCH, telemetry=True,
                          fuse_rounds="auto", overlap=True,
                          observability=obs)
        eng.warmup()
        reqs = [SpikeRequest(spikes=r) for r in x]
        t0 = time.perf_counter()
        eng.serve(reqs)
        wall = time.perf_counter() - t0
        eng.close()
        return wall

    off_s = min(drain_s(None) for _ in range(3))
    on_s = min(drain_s(Observability.enabled(registry=Registry()))
               for _ in range(3))
    overhead_pct = 100.0 * (on_s - off_s) / off_s
    rec.emit(
        "serving_obs_overhead", on_s * 1e6 / n,
        f"requests={n};tracer_off_ms={off_s * 1e3:.1f};"
        f"tracer_on_ms={on_s * 1e3:.1f};"
        f"overhead_pct={overhead_pct:.2f}%;gate=5%;repeats=3",
    )


def _cold_start_lane(rec: Recorder) -> None:
    """First-request latency, cold vs AOT-warmed.

    Each sub-lane builds a *fresh* network (fresh arrays => empty plan
    cache), so the cold lane genuinely pays the first compile in the serve
    path and the warm lane pays it in ``warmup()`` instead.  With the
    persistent compilation cache enabled (``launch/env.py``) the warmup
    itself re-warms from disk on a restart.
    """
    def first_request_ms(warm: bool, seed: int):
        net = _paper_net(seed)
        eng = SpikeEngine(net, max_batch=32, telemetry=True)
        warmup_s = 0.0
        if warm:
            t0 = time.perf_counter()
            eng.warmup()
            warmup_s = time.perf_counter() - t0
        spikes = (np.random.default_rng(seed).random(net.topology[0])
                  < 0.3).astype(np.uint8)
        t0 = time.perf_counter()
        eng.serve([SpikeRequest(spikes=spikes)])
        return (time.perf_counter() - t0) * 1e3, warmup_s

    cold_ms, _ = first_request_ms(False, seed=101)
    warm_ms, warmup_s = first_request_ms(True, seed=102)
    rec.emit(
        "serving_cold_start", warm_ms * 1e3,
        f"cold_first_request_ms={cold_ms:.1f};"
        f"warm_first_request_ms={warm_ms:.1f};"
        f"warmup_s={warmup_s:.2f};"
        f"speedup={cold_ms / max(warm_ms, 1e-9):.1f}x;"
        f"compilation_cache={jax.config.jax_compilation_cache_dir or 'off'}",
    )


def run():
    rec = Recorder()
    net = _paper_net()
    x, _ = digits.make_spike_dataset(N_REQUESTS, seed=7)

    single_req_s = _serve_once(rec, "single_device", net, x, rules=None)
    n_dev = len(jax.devices())
    if n_dev > 1:
        # dp-scaling ladder: every power-of-two mesh up to the host's
        # device count (smoke keeps just the full mesh — the CI gate)
        dps = [n_dev] if SMOKE else sorted(
            d for d in (2, 4, 8) if d <= n_dev)
        for d in dps:
            rules = shd.make_esam_rules(shd.esam_data_mesh(d))
            _serve_once(rec, f"sharded_dp{d}", net, x, rules=rules,
                        vs_single=single_req_s)
    else:
        rec.emit("serving_sharded_skipped", 0.0,
                 "devices=1(set XLA_FLAGS=--xla_force_host_platform_"
                 "device_count=8 for the data-parallel lanes)")

    _cold_start_lane(rec)
    _obs_overhead_lane(rec, net)
    _overload_lanes(rec, net)

    rec.write_json(os.environ.get("BENCH_SERVING_OUT", "BENCH_serving.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
