"""The system under test, built from a configuration file and a seed.

The benchmark makes the weights itself, on the device, in one jitted call
from the seed, and hands the program an ``EsamNetwork`` of them; the host
copy of the same bits is what the reference reads.  Everything else the
program does (plans, kernels, packing, batching) is the program's own.
"""

from __future__ import annotations

import numpy as np


def device_key(seed: int, stream: int):
    """A raw uint32[2] PRNG key for (seed, stream), for seeds of any width."""
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def make_weights(topology, seed: int):
    """Random ±1 weights as stored bits: Bernoulli(1/2) int8 per layer,
    drawn on the device in one jitted call.  Returns device arrays."""
    import jax

    shapes = tuple((a, b) for a, b in zip(topology[:-1], topology[1:]))

    @jax.jit
    def draw(key):
        return [jax.random.bernoulli(jax.random.fold_in(key, i), 0.5, s)
                .astype(np.int8) for i, s in enumerate(shapes)]

    return draw(device_key(seed, 0))


class Network:
    """The configuration's network: device weights for the program, host
    copies for the reference."""

    def __init__(self, cfg: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core.esam.network import EsamNetwork

        topo = tuple(cfg["topology"])
        self.topology = topo
        bits = make_weights(topo, seed)
        vth = [jnp.full((n,), int(cfg["neuron"]["threshold"]), jnp.int32)
               for n in topo[1:]]
        off = jnp.zeros((topo[-1],), jnp.float32)
        self.net = EsamNetwork(weight_bits=list(bits), vth=vth, out_offset=off)
        jax.block_until_ready(bits)
        self.bits = [np.asarray(b) for b in bits]
        self.vth = [np.asarray(v) for v in vth]
        self.out_offset = np.asarray(off)


def temporal_config(cfg: dict):
    from repro.core.esam.temporal import TemporalConfig

    nrn = cfg["neuron"]
    return TemporalConfig(n_steps=1, leak=float(nrn.get("leak", 0.0)),
                          reset=nrn.get("reset", "zero"),
                          refractory=int(nrn.get("refractory", 0)))


def make_engine(cfg: dict, network: Network, chips: int):
    """``SpikeEngine`` with the configuration's engine settings.  On more
    than one chip it gets the data-parallel rules over every chip, as the
    serve launcher builds it on a multi-chip host."""
    from repro.serve.engine import SpikeEngine

    e = cfg["engine"]
    rules = None
    if chips > 1:
        from repro.distributed import sharding as shd

        rules = shd.make_esam_rules(shd.esam_data_mesh())
    return SpikeEngine(
        network.net, max_batch=int(e["max_batch"]),
        min_bucket=int(e.get("min_bucket", 8)),
        fuse_rounds=e.get("fuse_rounds"), overlap=bool(e.get("overlap")),
        telemetry=bool(e["telemetry"]), read_ports=int(e["read_ports"]),
        temporal=temporal_config(cfg), rules=rules)
