"""Serving launcher: LM decoding or ESAM spike serving.

LM mode (default): greedy decoding through ``serve/lm_engine.Engine``, with
slot-based continuous batching (dense, moe, vlm and hybrid_moe families).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-4.0-h-small --smoke \
        --requests 6 --max-new 16

ESAM mode (``--esam``): synthetic spike traffic served end-to-end through
the sharded execution plan — requests flow through ``SpikeEngine``'s
admission queue, power-of-two buckets, and the ``shard_map``-ped packed
plan when more than one device is visible, with fused multi-round dispatch
and host/device overlap on by default (``--fuse``/``--no-overlap`` to
tune).  Prints the aggregate paper-unit operating point (MInf/s + pJ/Inf)
next to the wall-clock serving rate.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --esam --smoke

Cold start: ``--warmup`` AOT-compiles the engine's whole bucket ladder
before the first request and prints a greppable ``COLDSTART
first_request_ms=...`` line.  The persistent JAX compilation cache is
always on (``launch/env.py``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<repo>/.jax_cache``), so a *restarted* server re-warms from disk;
``--host-devices N`` forces an N-device host mesh without hand-writing
XLA_FLAGS.

    PYTHONPATH=src python -m repro.launch.serve --esam --smoke \
        --warmup --host-devices 8

Traffic mode (``--traffic``): open-loop Poisson traffic (seeded arrivals,
mixed static/event blends) through the overload-hardened plane — bounded
admission queue, per-request deadlines, the degradation ladder, and (with
``--replicas N``) the retrying ``FaultAwareRouter``; ``--chaos`` arms a
canned chaos plan (replica 0 crashes mid-drain, replica 1 slowed).  Prints
p50/p99/p99.9 latency, shed/rejected/retry counts, and goodput-under-SLO.

    PYTHONPATH=src python -m repro.launch.serve --traffic --smoke \
        --rate 2000 --requests 64 --deadline-ms 500 --replicas 2
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import base as cb
from repro.launch import env as env_mod
from repro.models import lm, params as pm
from repro.serve.engine import SpikeEngine, SpikeRequest
from repro.serve.lm_engine import Engine, Request


# ------------------------------------------------------------------ #
# observability plane: --metrics-port / --trace-out / --profile-rounds
# ------------------------------------------------------------------ #
def _build_observability(args):
    """Build the launcher's Observability handle (or None when every lane
    is off) plus the scrape server when ``--metrics-port`` was given.

    Returns ``(obs, metrics_server)``; the caller threads ``obs`` into the
    engines and finishes with :func:`_finish_observability`."""
    want_trace = args.trace_out is not None
    want_metrics = args.metrics_port is not None or args.report_json
    want_profile = args.profile_rounds > 0
    if not (want_trace or want_metrics or want_profile):
        return None, None
    from repro.obs import DeviceProfiler, Observability, Registry, Tracer

    registry = Registry() if (want_metrics or want_profile) else None
    # a profiled run always traces: the engine's spans are what name the
    # host timeline of the capture
    tracer = Tracer() if (want_trace or want_profile) else None
    profiler = None
    if want_profile:
        profiler = DeviceProfiler(
            args.profile_dir, skip_rounds=args.profile_skip,
            n_rounds=args.profile_rounds, registry=registry)
    obs = Observability(tracer=tracer, metrics=registry, profile=profiler)
    server = None
    if args.metrics_port is not None:
        from repro.obs.http import MetricsServer

        server = MetricsServer(registry, port=args.metrics_port,
                               tracer=tracer)
        port = server.start()
        print(f"METRICS port={port} url=http://127.0.0.1:{port}/metrics")
    return obs, server


def _finish_observability(args, obs, server) -> None:
    """Export the trace, print the greppable summary lines, then hold the
    scrape endpoint open for ``--metrics-hold-s`` (CI curls it here)."""
    if obs is None:
        return
    if obs.profile is not None:
        obs.profile.stop()
        status = obs.profile.error or "ok"
        print(f"PROFILE dir={obs.profile.logdir} "
              f"rounds={obs.profile.captured} status={status}")
    if obs.tracer is not None and args.trace_out is not None:
        from repro.obs.trace import validate_trace

        doc = obs.tracer.export(args.trace_out)
        summary = validate_trace(doc)
        print(f"TRACE path={args.trace_out} events={summary['events']} "
              f"requests={summary['request_begun']} "
              f"close_fraction={summary['request_close_fraction']:.4f}")
    if server is not None:
        if args.metrics_hold_s > 0:
            print(f"METRICS holding for {args.metrics_hold_s:.0f}s", flush=True)
            time.sleep(args.metrics_hold_s)
        server.stop()


def _lm_main(args):
    cfg = cb.smoke(args.arch) if args.smoke else cb.get(args.arch)
    params = pm.init(lm.model_specs(cfg), jax.random.PRNGKey(args.seed))
    batch_size = 4 if args.batch_size is None else args.batch_size
    n_requests = 4 if args.requests is None else args.requests
    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12)).astype(np.int32),
                max_new_tokens=args.max_new)
        for _ in range(n_requests)
    ]
    # a slot holds the longest prompt and its answer
    max_len = max(len(r.prompt) for r in reqs) + args.max_new
    eng = Engine(params, cfg, batch_size=batch_size, max_len=max_len)
    out = eng.serve(reqs)
    for i, r in enumerate(out):
        print(f"req {i}: prompt[{len(r.prompt)}] -> {r.output.tolist()}")


def random_esam_network(topology, seed: int):
    import jax.numpy as jnp

    from repro.core.esam.network import EsamNetwork

    key = jax.random.PRNGKey(seed)
    bits, vth = [], []
    for i in range(len(topology) - 1):
        k = jax.random.fold_in(key, i)
        bits.append(jax.random.bernoulli(
            k, 0.5, (topology[i], topology[i + 1])).astype(jnp.int8))
        vth.append(jnp.zeros((topology[i + 1],), jnp.int32))
    return EsamNetwork(
        weight_bits=bits, vth=vth,
        out_offset=jnp.zeros((topology[-1],), jnp.float32))


def _esam_main(args, obs=None):
    from repro.core.esam import cost_model as cm
    from repro.data import digits
    from repro.distributed import sharding as shd

    topology = (768, 256, 10) if args.smoke else cm.PAPER_TOPOLOGY
    n_requests = args.requests if args.requests is not None else (
        64 if args.smoke else 512)
    max_batch = 128 if args.batch_size is None else args.batch_size
    net = random_esam_network(topology, args.seed)

    rules = None
    if len(jax.devices()) > 1:
        rules = shd.make_esam_rules(shd.esam_data_mesh())
    engine_kw = dict(max_batch=max_batch, telemetry=True,
                     read_ports=args.read_ports, rules=rules,
                     fuse_rounds=_fuse_arg(args), overlap=not args.no_overlap)

    x, _ = digits.make_spike_dataset(n_requests, seed=args.seed)
    reqs = [SpikeRequest(spikes=x[i]) for i in range(n_requests)]
    eng = SpikeEngine(net, observability=obs, **engine_kw)
    if args.warmup:
        # AOT-compile the whole bucket ladder up front, then time the very
        # first request the warmed engine serves — the cold-start headline
        wt = eng.warmup()
        t0 = time.perf_counter()
        eng.serve([reqs[0]])
        first_ms = (time.perf_counter() - t0) * 1e3
        print(f"COLDSTART first_request_ms={first_ms:.2f} "
              f"warmup_s={wt['total_s']:.2f} "
              f"buckets={len(eng._buckets)} "
              f"cache={env_mod.compilation_cache_dir()}")
        reqs_timed = reqs[1:]
    else:
        # warm on a throwaway engine serving the SAME workload shape, so
        # every bucket the timed run dispatches is already compiled (plans
        # are cached per network) and the timed engine's stats() see only
        # the timed requests
        SpikeEngine(net, **engine_kw).serve(
            [SpikeRequest(spikes=r) for r in x])
        reqs_timed = reqs
    t0 = time.perf_counter()
    eng.serve(reqs_timed)
    wall_s = time.perf_counter() - t0

    st = eng.stats()
    print(f"esam-serve: {st['n_requests']} requests "
          f"(data_parallel={st['data_parallel']}, cell={st['cell']}, "
          f"buckets={eng._buckets}, fuse={st['fuse_rounds']}, "
          f"overlap={st['overlap']}, rounds_saved={st['rounds_saved']})")
    print(f"  wall-clock        : {wall_s*1e3:8.1f} ms  "
          f"({len(reqs_timed)/wall_s:,.0f} req/s)")
    print(f"  model throughput  : {st['throughput_pipelined_inf_s']/1e6:8.2f} MInf/s "
          f"(pipelined; paper {cm.PAPER_THROUGHPUT_INF_S/1e6:.0f})")
    print(f"  model energy      : {st['energy_pj_per_inf']:8.1f} pJ/Inf "
          f"(paper {cm.PAPER_ENERGY_PJ_PER_INF:.0f})")
    print(f"  model latency     : {st['latency_ns_mean']:8.1f} ns/inf "
          f"({st['cycles_mean']:.1f} cycles)")
    labels = [r.label for r in reqs]
    assert all(l is not None for l in labels)


def _events_main(args, obs=None):
    """Synthetic event-stream traffic through the temporal plan: mixed-T
    rate-encoded digit streams drain via ``SpikeEngine.submit_events``
    ((batch, T)-bucketed rounds), printing spikes/s next to the modeled
    pJ/timestep from the measured per-step activity."""
    from repro.core.esam import cost_model as cm
    from repro.core.esam.temporal import TemporalConfig
    from repro.data import events as events_mod
    from repro.serve.engine import EventRequest, SpikeEngine

    topology = (768, 256, 10) if args.smoke else cm.PAPER_TOPOLOGY
    t_mix = (2, 4) if args.smoke else (4, 8, 16)
    n_requests = args.requests if args.requests is not None else (
        32 if args.smoke else 256)
    max_batch = 64 if args.batch_size is None else args.batch_size
    net = random_esam_network(topology, args.seed)
    cfg = TemporalConfig(n_steps=1, leak=args.leak)
    engine_kw = dict(max_batch=max_batch, telemetry=True,
                     read_ports=args.read_ports, temporal=cfg)

    def make_requests():
        reqs, rng = [], np.random.default_rng(args.seed)
        for i, t in enumerate(rng.choice(t_mix, size=n_requests)):
            ev, _ = events_mod.encode_digit_events(
                1, int(t), encoder="rate", seed=args.seed + i, gain=0.7,
                packed=True)
            reqs.append(EventRequest(events=ev[:, 0]))
        return reqs

    # warm a throwaway engine on the same workload shape (plans are cached
    # per network) so the timed engine's stats() see only the timed requests
    SpikeEngine(net, **engine_kw).serve(make_requests())
    eng = SpikeEngine(net, observability=obs, **engine_kw)
    reqs = make_requests()
    t0 = time.perf_counter()
    eng.serve(reqs)
    wall_s = time.perf_counter() - t0

    st = eng.stats()
    n_spikes = sum(
        int(np.bitwise_count(np.asarray(r.events)).sum()) for r in reqs)
    print(f"esam-events: {st['n_event_requests']} streams, "
          f"{st['timesteps_total']} timesteps (T mix {tuple(t_mix)}, "
          f"cell={st['cell']})")
    print(f"  wall-clock        : {wall_s*1e3:8.1f} ms  "
          f"({st['timesteps_total']/wall_s:,.0f} steps/s, "
          f"{n_spikes/wall_s:,.0f} spikes/s)")
    print(f"  model energy      : {st['energy_pj_per_timestep']:8.1f} "
          f"pJ/timestep ({st['event_energy_pj_mean']:.1f} pJ/stream)")
    print(f"  model latency     : {st['event_latency_ns_mean']:8.1f} "
          f"ns/stream ({st['event_cycles_mean']:.1f} cycles)")
    assert all(r.label is not None for r in reqs)


def _traffic_main(args, obs=None):
    """Open-loop Poisson traffic (optionally chaos-injected) through the
    overload-hardened serving plane, printing the SLO-facing numbers."""
    from repro.core.esam import cost_model as cm
    from repro.serve.engine import FaultAwareRouter, SpikeEngine
    from repro.serve.overload import DegradationLadder
    from repro.serve.traffic import ChaosConfig, TrafficConfig, run_open_loop
    from repro.train.fault_tolerance import RetryPolicy

    topology = (768, 256, 10) if args.smoke else cm.PAPER_TOPOLOGY
    n_requests = args.requests if args.requests is not None else (
        64 if args.smoke else 256)
    max_batch = 32 if args.batch_size is None else args.batch_size
    net = random_esam_network(topology, args.seed)

    def make_engine(engine_obs=None):
        # the warmup engine stays un-instrumented so the scrape/trace
        # surfaces carry only the measured open-loop run
        return SpikeEngine(
            net, max_batch=max_batch, telemetry=True,
            read_ports=args.read_ports, queue_limit=4 * max_batch,
            fuse_rounds=_fuse_arg(args), overlap=not args.no_overlap,
            observability=engine_obs,
            ladder=DegradationLadder.default(max_batch, args.read_ports))

    # closed-loop warmup on the same request blend: first pass compiles
    # every (bucket, T) the traffic can hit, second pass measures the
    # sustainable rate, so --rate defaults land relative to saturation
    from repro.serve.traffic import build_requests
    warm = make_engine()
    blend = dict(rate_hz=1.0, n_requests=n_requests, p_event=args.p_event,
                 event_t_choices=(2, 4), n_in=topology[0])
    warm.serve(build_requests(TrafficConfig(seed=args.seed, **blend))[0])
    timed = build_requests(TrafficConfig(seed=args.seed + 1, **blend))[0]
    t0 = time.perf_counter()
    warm.serve(timed)
    rate_sust = len(timed) / (time.perf_counter() - t0)
    rate = args.rate if args.rate is not None else 2.0 * rate_sust

    engines = [make_engine(obs) for _ in range(max(1, args.replicas))]
    # health_threshold=0: a random network's measured telemetry deviates
    # from the reference calibration, so tile-health routing would mark
    # every replica degraded and starve all but one — this lane exercises
    # the overload plane (crash/retry/deadlines), not health scoring
    server = engines[0] if len(engines) == 1 else FaultAwareRouter(
        engines, health_threshold=0.0, observability=obs,
        retry=RetryPolicy(base_backoff_s=1e-3, attempt_timeout_s=2.0))
    chaos = None
    if args.chaos:
        chaos = ChaosConfig(
            slowdown=((1, 5e-3),) if len(engines) > 1 else (),
            crash_replica=0 if len(engines) > 1 else None,
            crash_after_rounds=2,
            storm_at_s=0.0, storm_size=2 * max_batch)
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None
    slo_s = args.slo_ms / 1e3 if args.slo_ms else deadline_s
    cfg = TrafficConfig(
        rate_hz=rate, n_requests=n_requests, seed=args.seed,
        p_event=args.p_event, event_t_choices=(2, 4),
        n_in=topology[0], deadline_s=deadline_s)
    if args.warmup:
        from repro.serve.traffic import warmup_engine
        warmup_engine(server, cfg)
    rep = run_open_loop(server, cfg, slo_s=slo_s, chaos=chaos,
                        observability=obs)
    if args.report_json:
        import json
        with open(args.report_json, "w") as f:
            json.dump(rep.to_dict(), f, indent=2, default=str)
        print(f"REPORT path={args.report_json}")

    print(f"esam-traffic: offered {rep.n_offered} requests @ {rate:,.0f}/s "
          f"(sustainable ~{rate_sust:,.0f}/s, replicas={len(engines)}, "
          f"chaos={'on' if chaos else 'off'})")
    print(f"  completed         : {rep.n_completed}  "
          f"(shed {rep.n_shed}, rejected {rep.n_rejected}, "
          f"failed {rep.n_failed}, deadline-miss {rep.n_deadline_miss})")
    print(f"  latency           : p50 {rep.p50_ms:8.1f} ms   "
          f"p99 {rep.p99_ms:8.1f} ms   p99.9 {rep.p999_ms:8.1f} ms")
    print(f"  goodput under SLO : {100 * rep.goodput_slo:6.1f} %  "
          f"(SLO {1e3 * rep.slo_s:.0f} ms)" if rep.slo_s else
          f"  goodput           : {100 * rep.goodput_slo:6.1f} %")
    print(f"  resilience        : retries {rep.retries}, "
          f"crashes {rep.crashes}, timeouts {rep.timeouts}, "
          f"degraded routes {rep.degraded_routes}")
    print(f"  degradation       : {rep.ladder_transitions} transitions, "
          f"deepest level {rep.max_degradation_level}; "
          f"backpressure events {rep.backpressure_events}")


def _fuse_arg(args):
    """Resolve --fuse: "auto" (default) | "off" | an integer factor."""
    if args.fuse in ("off", "none", "0"):
        return None
    if args.fuse == "auto":
        return "auto"
    return int(args.fuse)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--esam", action="store_true",
                    help="serve ESAM spike traffic through the sharded plan")
    ap.add_argument("--events", action="store_true",
                    help="serve ESAM event-stream traffic (temporal plan)")
    ap.add_argument("--traffic", action="store_true",
                    help="open-loop Poisson traffic through the "
                         "overload-hardened plane (deadlines, ladder, "
                         "retries); see also --chaos/--replicas")
    ap.add_argument("--rate", type=float, default=None,
                    help="--traffic: offered arrival rate in req/s "
                         "(default: 2x the measured sustainable rate)")
    ap.add_argument("--deadline-ms", type=float, default=250.0,
                    help="--traffic: per-request deadline (0 disables)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="--traffic: goodput SLO (default: the deadline)")
    ap.add_argument("--p-event", type=float, default=0.25,
                    help="--traffic: fraction of event-stream requests")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--traffic: engine replicas behind the router")
    ap.add_argument("--chaos", action="store_true",
                    help="--traffic: crash replica 0 mid-drain, slow "
                         "replica 1, and inject a request storm")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="default: 4 (LM), 64 (--esam --smoke), 512 (--esam), "
                         "32 (--events --smoke), 256 (--events)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="default: 4 (LM), 128 (--esam max_batch)")
    ap.add_argument("--read-ports", type=int, default=4)
    ap.add_argument("--leak", type=float, default=0.125,
                    help="--events: LIF leak per timestep")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fuse", default="auto",
                    help="round fusion factor: 'auto' (= dp degree), "
                         "'off', or an integer")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the background host packer "
                         "(synchronous legacy drain)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the bucket ladder before serving and "
                         "print COLDSTART first-request latency")
    ap.add_argument("--host-devices", type=int, default=None,
                    help="force an N-device host-platform mesh "
                         "(XLA_FLAGS, applied before backend init)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus /metrics on this port "
                         "(0 = ephemeral; prints 'METRICS port=...')")
    ap.add_argument("--metrics-hold-s", type=float, default=0.0,
                    help="keep the /metrics endpoint up this long after the "
                         "run finishes (lets CI scrape before exit)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export a Perfetto trace_event JSON of the run "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--profile-rounds", type=int, default=0, metavar="N",
                    help="capture a jax.profiler trace around N drain "
                         "rounds (see --profile-dir/--profile-skip)")
    ap.add_argument("--profile-dir", default="/tmp/esam-profile",
                    help="logdir for the jax.profiler capture")
    ap.add_argument("--profile-skip", type=int, default=1,
                    help="drain rounds to skip before the profiler arms "
                         "(skips cold-start compiles; default 1)")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="--traffic: write the TrafficReport (with the "
                         "metrics snapshot) as JSON")
    args = ap.parse_args()
    if args.host_devices is not None:
        env_mod.apply_host_devices(args.host_devices)
    env_mod.enable_compilation_cache()
    obs, metrics_server = _build_observability(args)
    try:
        if args.traffic:
            _traffic_main(args, obs)
        elif args.events:
            _events_main(args, obs)
        elif args.esam:
            _esam_main(args, obs)
        else:
            _lm_main(args)
    finally:
        _finish_observability(args, obs, metrics_server)


if __name__ == "__main__":
    main()
