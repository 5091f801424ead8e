#!/usr/bin/env python3
"""On-chip smoke test: the ESAM plane end to end on a TPU at the paper's widths.

    python chip_smoke.py             # one chip: serve, events, learn
    python chip_smoke.py --chips 4   # four chips: dp-sharded plan vs one chip

Every phase drives a user entry point — ``SpikeEngine.serve``,
``SpikeEngine.submit_events``, ``train_online`` — on the paper's
768:256:256:256:10 network with random ±1 weights made from ``--seed``, and
checks its outputs bit for bit against a reference that shares none of the
code under test:

  serve   512 digit requests (max_batch 128, telemetry, 4 read ports, fusion
          and host/device overlap as the launcher sets them) after
          ``warmup()``; logits == a host numpy int64 MAC -> fire -> readout.
  events  256 rate-encoded streams, T in {4, 8, 16}, the launcher's event
          mix; logits of a few streams == the per-step loop oracle
          (``temporal_forward_naive``, jnp LIF, no kernels).
  learn   one ``train_online`` epoch on 512 digit samples; readout bits ==
          the jnp column-event scan epoch under the same key.

Each phase also checks that its compiled program holds a ``tpu_custom_call``,
i.e. that the Pallas kernels ran and no reference stood in for them (for
learn, the ``stdp_column_event_epoch`` kernel by name).
``--chips 4`` runs only the dp-sharded packed plan over four chips (and the
serve launcher's engine on that mesh) against the single-chip plan on the
same 512 requests, and checks the output shards live on four devices.

Each phase prints one line of counts and wall seconds; the last line is
``{"ok": true, "device": {...}}``.  Any failed check or exception exits
non-zero before that line, and so does a run where JAX finds no TPU: this
script never falls back to the CPU.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_REQUESTS = 512
N_STREAMS = 256
T_MIX = (4, 8, 16)
LEAK = 0.125            # the serve launcher's --events default
STREAMS_CHECKED = 4     # per T, against the per-step loop oracle


class SmokeFailure(RuntimeError):
    """A phase's output disagreed with its reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _has_kernel(compiled_text: str) -> bool:
    return "tpu_custom_call" in compiled_text


def _numpy_logits(net, spikes):
    """Host reference: int64 ±1 MAC -> IF fire per hidden tile -> readout."""
    import numpy as np

    s = (np.asarray(spikes) != 0).astype(np.int64)
    for w, th in zip(net.weight_bits[:-1], net.vth[:-1]):
        v = s @ (2 * np.asarray(w, np.int64) - 1)
        s = (v >= np.asarray(th, np.int64)).astype(np.int64)
    v = s @ (2 * np.asarray(net.weight_bits[-1], np.int64) - 1)
    return v + np.asarray(net.out_offset, np.float64)


def phase_serve(net, seed: int) -> None:
    import numpy as np

    from repro.data import digits
    from repro.serve.engine import SpikeEngine, SpikeRequest

    x, _ = digits.make_spike_dataset(N_REQUESTS, seed=seed)
    eng = SpikeEngine(net, max_batch=128, telemetry=True, read_ports=4,
                      fuse_rounds="auto", overlap=True)
    t0 = time.perf_counter()
    eng.warmup()
    warmup_s = time.perf_counter() - t0
    reqs = [SpikeRequest(spikes=x[i]) for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    eng.serve(reqs)
    logits = np.stack([r.logits for r in reqs])   # host copy: the sync point
    serve_s = time.perf_counter() - t0
    st = eng.stats()
    eng.close()
    _check(all(r.status == "done" for r in reqs), "serve: a request not done")
    _check(np.array_equal(logits.astype(np.float64), _numpy_logits(net, x)),
           "serve: logits differ from the numpy int64 reference")
    # the engine's plan (plans are cached per network and spec)
    plan = net.plan(mode="packed", telemetry=True, donate=True)
    _check(_has_kernel(plan.lower(128).compile().as_text()),
           "serve: no tpu_custom_call in the serving plan")
    print(f"serve: requests={N_REQUESTS} rounds={st['rounds_static']} "
          f"buckets={len(eng._buckets)} warmup_s={warmup_s} serve_s={serve_s} "
          f"logits=bit-identical(numpy int64) tpu_custom_call=yes", flush=True)


def phase_events(net, seed: int) -> None:
    import numpy as np

    from repro.core import packing
    from repro.core.esam.temporal import TemporalConfig, temporal_forward_naive
    from repro.data import events as events_mod
    from repro.serve.engine import EventRequest, SpikeEngine

    rng = np.random.default_rng(seed)
    reqs = []
    for i, t in enumerate(rng.choice(T_MIX, size=N_STREAMS)):
        ev, _ = events_mod.encode_digit_events(
            1, int(t), encoder="rate", seed=seed + i, gain=0.7, packed=True)
        reqs.append(EventRequest(events=ev[:, 0]))
    eng = SpikeEngine(net, max_batch=64, telemetry=True, read_ports=4,
                      temporal=TemporalConfig(n_steps=1, leak=LEAK))
    t0 = time.perf_counter()
    eng.warmup(event_ts=T_MIX)
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.submit_events(reqs)
    eng.serve()
    logits = np.stack([r.logits for r in reqs])   # host copy: the sync point
    serve_s = time.perf_counter() - t0
    st = eng.stats()
    eng.close()
    _check(all(r.status == "done" for r in reqs), "events: a stream not done")
    checked = 0
    for t in T_MIX:
        idx = [i for i, r in enumerate(reqs) if r.n_steps == t]
        idx = idx[:STREAMS_CHECKED]
        ev = np.stack([packing.unpack_spikes_np(reqs[i].events, net.topology[0])
                       for i in idx], axis=1)          # [T, n, n_in]
        want = temporal_forward_naive(
            net, ev, TemporalConfig(n_steps=t, leak=LEAK), jit_step=True)
        _check(np.array_equal(logits[idx], np.asarray(want, np.float32)),
               f"events: T={t} logits differ from the per-step loop oracle")
        checked += len(idx)
    plan = net.plan(mode="temporal", telemetry=True, donate=True,
                    temporal=TemporalConfig(n_steps=max(T_MIX), leak=LEAK))
    _check(_has_kernel(plan.lower(64).compile().as_text()),
           "events: no tpu_custom_call in the temporal plan")
    print(f"events: streams={N_STREAMS} timesteps={st['timesteps_total']} "
          f"rounds={st['rounds_event']} warmup_s={warmup_s} serve_s={serve_s} "
          f"checked={checked} logits=bit-identical(per-step loop) "
          f"tpu_custom_call=yes", flush=True)


def phase_learn(net, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.esam import learning
    from repro.data import digits
    from repro.train.online import train_online

    x, y = digits.make_spike_dataset(N_REQUESTS, seed=seed + 1)
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    res = train_online(net, x, y, epochs=1, key=key)
    got = np.asarray(jax.block_until_ready(res.network.weight_bits[-1]))
    learn_s = time.perf_counter() - t0
    spikes = jnp.asarray(x).astype(bool)
    want, want_n = learning.online_learning_epoch_scan(
        list(net.weight_bits), list(net.vth), spikes, jnp.asarray(y),
        jax.random.fold_in(key, 0), rng_scheme="column")
    _check(np.array_equal(got, np.asarray(want)),
           "learn: readout bits differ from the jnp column-event epoch")
    _check(res.n_updates[0] == int(want_n),
           "learn: column-update count differs from the jnp epoch")
    bits_t = jnp.asarray(net.weight_bits[-1]).T
    pre = jnp.zeros((N_REQUESTS, net.topology[-2]), bool)
    epoch = learning.column_event_epoch.lower(
        bits_t, pre, jnp.asarray(y), key, p_pot=0.12, p_dep=0.06,
        out_offset=net.out_offset)
    text = epoch.compile().as_text()
    _check(_has_kernel(text) and "stdp_column_event_epoch" in text,
           "learn: no stdp_column_event_epoch kernel in the compiled epoch")
    print(f"learn: samples={N_REQUESTS} epochs=1 "
          f"column_updates={res.n_updates[0]} accuracy={res.accuracy[0]} "
          f"learn_s={learn_s} readout_bits=bit-identical(jnp epoch) "
          f"tpu_custom_call=stdp_column_event_epoch", flush=True)


def phase_dp(net, seed: int) -> None:
    """The dp-sharded packed plan over every chip vs the one-chip plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packing
    from repro.data import digits
    from repro.distributed import sharding as shd
    from repro.serve.engine import SpikeEngine, SpikeRequest

    n_dev = len(jax.devices())
    x, _ = digits.make_spike_dataset(N_REQUESTS, seed=seed)
    packed = packing.pack_spikes_np(x != 0)
    rules = shd.make_esam_rules(shd.esam_data_mesh())
    one = net.plan(mode="packed")
    dp = net.plan(mode="packed", rules=rules)
    want = np.asarray(one(jnp.asarray(packed)).logits)
    t0 = time.perf_counter()
    out = jax.block_until_ready(dp(jnp.asarray(packed)).logits)
    dp_s = time.perf_counter() - t0
    devices = {s.device for s in out.addressable_shards}
    _check(len(devices) == n_dev,
           f"dp: output shards on {len(devices)} devices, not {n_dev}")
    _check(np.array_equal(np.asarray(out), want),
           "dp: sharded logits differ from the single-chip plan")
    _check(_has_kernel(dp.lower(N_REQUESTS).compile().as_text()),
           "dp: no tpu_custom_call in the sharded plan")
    # the serve launcher's path on the mesh: SpikeEngine with the dp rules
    eng = SpikeEngine(net, max_batch=128, telemetry=True, read_ports=4,
                      rules=rules, fuse_rounds="auto", overlap=True)
    eng.warmup()
    reqs = [SpikeRequest(spikes=x[i]) for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    eng.serve(reqs)
    logits = np.stack([r.logits for r in reqs])
    serve_s = time.perf_counter() - t0
    eng.close()
    _check(np.array_equal(logits, want),
           "dp: engine logits on the mesh differ from the single-chip plan")
    print(f"dp: requests={N_REQUESTS} devices={len(devices)} "
          f"plan_s={dp_s} engine_serve_s={serve_s} "
          f"logits=bit-identical(single-chip plan) tpu_custom_call=yes",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp-sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {n_dev}",
              file=sys.stderr)
        return 1

    from repro.core.esam.cost_model import PAPER_TOPOLOGY
    from repro.launch.env import enable_compilation_cache
    from repro.launch.serve import random_esam_network

    cache = enable_compilation_cache()
    print(f"chip_smoke: {dev.device_kind} x{n_dev}, compile cache {cache}",
          flush=True)
    net = random_esam_network(PAPER_TOPOLOGY, args.seed)
    phases = ([phase_dp] if args.chips == 4
              else [phase_serve, phase_events, phase_learn])
    for phase in phases:
        phase(net, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
