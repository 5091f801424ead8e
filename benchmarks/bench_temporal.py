"""Temporal event plane: fused scan vs naive loop, stream throughput,
encoder accuracy -> BENCH_temporal.json.

Three sections (env ``BENCH_TEMPORAL_SMOKE=1`` shrinks every knob for CI):

  temporal_fused_vs_naive   one jitted membrane-resident ``lax.scan`` vs the
                            naive per-step Python loop (dense tiles, eager
                            op-by-op dispatch, one device round-trip per
                            timestep) on the same event stream.  Three
                            ratios are recorded: the one-shot naive run
                            (``speedup`` — what the naive implementation
                            costs when actually run; the full run at T=32,
                            batch 256 asserts the >=5x floor on it), the
                            warmed eager loop, and the warmed jitted
                            per-step loop (whose logits are bit-identical
                            to the scan).  On this CPU container device ==
                            host, so the per-step state round-trip is a
                            near-free memcpy and the warm ratios understate
                            what the resident scan buys on a real
                            accelerator, where every step of the naive loop
                            crosses the PCIe/ICI boundary twice.
  temporal_stream_T*        event-stream rate (timesteps/s, input spikes/s)
                            and the modeled pJ/timestep from the measured
                            per-step activity, across T in {4, 8, 16, 32}.
  temporal_encoder_*        rate-vs-latency encoder accuracy of a trained
                            BNN->SNN network on the synthetic digit set.

Override the output path with env BENCH_TEMPORAL_OUT.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_temporal.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core import packing
from repro.core.esam import bnn, conversion, cost_model as cm, temporal
from repro.core.esam.network import EsamNetwork
from repro.data import digits, events

SMOKE = os.environ.get("BENCH_TEMPORAL_SMOKE", "") not in ("", "0")
OUT = os.environ.get("BENCH_TEMPORAL_OUT", "BENCH_temporal.json")
READ_PORTS = 4


def _rand_net(topology, seed: int = 0) -> EsamNetwork:
    key = jax.random.PRNGKey(seed)
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topology[i], topology[i + 1])).astype(jnp.int8)
        for i in range(len(topology) - 1)
    ]
    # mildly positive thresholds keep per-step hidden activity in a
    # plausible band (~30-50%) instead of the all-fire regime of vth=0
    vth = [
        jax.random.randint(jax.random.fold_in(key, 100 + i), (n,), 0, 12,
                           jnp.int32)
        for i, n in enumerate(topology[1:])
    ]
    return EsamNetwork(weight_bits=bits, vth=vth,
                       out_offset=jnp.zeros((topology[-1],), jnp.float32))


def _event_stream(n: int, n_steps: int, seed: int = 0):
    ev, _ = events.encode_digit_events(
        n, n_steps, encoder="rate", seed=seed, gain=0.7)
    return ev  # uint8[T, n, 768]


def _bench_fused_vs_naive(rec: Recorder) -> None:
    n_steps, batch = (4, 32) if SMOKE else (32, 256)
    net = _rand_net((768, 256, 10) if SMOKE else cm.PAPER_TOPOLOGY)
    cfg = temporal.TemporalConfig(n_steps=n_steps, leak=0.125)
    ev = _event_stream(batch, n_steps)
    packed = jnp.asarray(packing.pack_spikes_np(ev))

    plan = net.plan(mode="temporal", temporal=cfg)
    fused_us, res = time_call(lambda: plan(packed).logits)
    # oracle: the jitted per-step loop — bit-identical integer datapath
    jitted_us, jitted_logits = time_call(
        lambda: temporal.temporal_forward_naive(net, ev, cfg),
        warmup=1, repeats=1 if SMOKE else 2)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(jitted_logits))
    # headline baseline: the eager op-by-op per-step loop, run once, cold —
    # the cost the naive first implementation actually pays on this stream
    # (unfused float arithmetic -> ulp-level agreement, not bitwise)
    naive_us, naive_logits = time_call(
        lambda: temporal.temporal_forward_naive(net, ev, cfg, jit_step=False),
        warmup=0, repeats=1)
    np.testing.assert_allclose(
        np.asarray(res), np.asarray(naive_logits), rtol=1e-5, atol=1e-3)
    # steady-state eager (per-op caches warm): the conservative ratio
    warm_us, _ = time_call(
        lambda: temporal.temporal_forward_naive(net, ev, cfg, jit_step=False),
        warmup=0, repeats=1 if SMOKE else 2)
    speedup = naive_us / fused_us
    rec.emit(
        "temporal_fused_vs_naive", fused_us,
        f"T={n_steps};batch={batch};naive_one_shot_us={naive_us:.1f};"
        f"speedup={speedup:.1f}x;warm_eager_us={warm_us:.1f};"
        f"speedup_warm_eager={warm_us / fused_us:.1f}x;"
        f"jitted_loop_us={jitted_us:.1f};"
        f"speedup_vs_jitted_loop={jitted_us / fused_us:.1f}x;"
        f"bit_identical_to_jitted_loop=yes;floor=5x")
    if not SMOKE:
        assert speedup >= 5.0, (
            f"fused temporal scan only {speedup:.1f}x over the naive loop")


def _bench_stream_rates(rec: Recorder) -> None:
    steps_list = (2, 4) if SMOKE else (4, 8, 16, 32)
    batch = 32 if SMOKE else 256
    net = _rand_net((768, 256, 10) if SMOKE else cm.PAPER_TOPOLOGY)
    for n_steps in steps_list:
        cfg = temporal.TemporalConfig(n_steps=n_steps, leak=0.125)
        ev = _event_stream(batch, n_steps, seed=n_steps)
        packed = jnp.asarray(packing.pack_spikes_np(ev))
        plan = net.plan(mode="temporal", temporal=cfg, telemetry=True)
        # return the arrays (PlanResult is not a pytree): time_call must
        # block on the actual device work, not just the dispatch
        def _run():
            r = plan(packed)
            return r.logits, r.loads

        us, (logits, loads) = time_call(_run)
        wall_s = us / 1e6
        rs = cm.temporal_request_stats_device(net.topology, loads, READ_PORTS)
        pj_step = float(np.asarray(rs["energy_pj_per_step"]).mean())
        in_spikes = int(ev.sum())
        rec.emit(
            f"temporal_stream_T{n_steps}", us,
            f"batch={batch};steps_per_s={batch * n_steps / wall_s:,.0f};"
            f"spikes_per_s={in_spikes / wall_s:,.0f};"
            f"pj_per_timestep={pj_step:.1f};"
            f"pj_per_stream={float(np.asarray(rs['energy_pj']).mean()):.1f}")


def _bench_encoder_accuracy(rec: Recorder) -> None:
    n, steps = (512, 40) if SMOKE else (4096, 250)
    n_steps = 4 if SMOKE else 8
    x, y = digits.make_spike_dataset(n, seed=0)
    params, _ = bnn.fit(jax.random.PRNGKey(0), cm.PAPER_TOPOLOGY,
                        jnp.asarray(x), jnp.asarray(y), steps=steps)
    net = conversion.bnn_to_snn(params)
    cfg = temporal.TemporalConfig(n_steps=n_steps)
    plan = net.plan(mode="temporal", temporal=cfg)
    static_acc = float(
        (net.plan(mode="functional")(jnp.asarray(x).astype(bool))
         .logits.argmax(-1) == jnp.asarray(y)).mean())
    for enc in ("rate", "latency"):
        ev = events.encode(x, n_steps, encoder=enc, seed=1, **(
            {"gain": 0.7} if enc == "rate" else {}))
        us, res = time_call(lambda: plan(packing.pack_spikes_np(ev)).logits)
        acc = float((np.asarray(res).argmax(-1) == y).mean())
        rec.emit(
            f"temporal_encoder_{enc}", us,
            f"T={n_steps};n={n};acc={acc * 100:.2f};"
            f"static_acc={static_acc * 100:.2f}")


def run(rec: Recorder | None = None) -> None:
    own = rec is None
    if own:
        rec = Recorder()
    _bench_fused_vs_naive(rec)
    _bench_stream_rates(rec)
    _bench_encoder_accuracy(rec)
    if own:
        rec.write_json(OUT)


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    print("name,us_per_call,derived")
    run()
