"""Plain reference of the ESAM network, its simulated telemetry and its
online learning rule, written from the paper's equations.

It imports nothing of the program under test.  It reads the topology, the
neuron model and the simulated cell's timing and energy constants from the
configuration file, and the weights from the benchmark's own seeded draw.

``dtype`` selects the arithmetic: ``"exact"`` holds every integer exactly
and the LIF membrane and the telemetry in float32 as the configuration
states; ``"bf16"`` is the control, the same computation with every membrane
and every telemetry value rounded to bfloat16 (the nearest lower precision).
"""

from __future__ import annotations

import functools
import math

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


def _round(x, dtype):
    """float32 as the configuration states, or the control's bfloat16."""
    if dtype == "bf16":
        return np.asarray(x, np.float32).astype(BF16).astype(np.float32)
    return np.asarray(x, np.float32)


def signed(bits: np.ndarray) -> np.ndarray:
    """Stored bit 1 -> weight +1, 0 -> -1, as float32 (exact small ints)."""
    return 2.0 * np.asarray(bits, np.float32) - 1.0


# ------------------------------------------------------------------ #
# telemetry: the simulated CIM array's cycles and energy per inference
# ------------------------------------------------------------------ #
def tile_cost(loads, n_in, n_out, cell, dtype="exact"):
    """Per-sample (cycles, energy_pj) of one tile from its input spikes.

    ``loads``: int[..., n_groups] active inputs per ``array_rows`` group.
    Each group drains through ``read_ports`` ports, one cycle per port-load,
    plus one compare/fire cycle; energy counts row reads in every column
    group, the arbiter and clock tree per active cycle, neuron accumulation
    per cycle and one fire per neuron.
    """
    rows, cols = cell["array_rows"], cell["array_cols"]
    n_groups, n_colgroups = -(-n_in // rows), -(-n_out // cols)
    ld = np.asarray(loads, np.float64)
    cyc = np.ceil(ld / cell["read_ports"]).max(axis=-1) + 1.0
    reads = ld.sum(axis=-1) * n_colgroups
    if dtype == "bf16":
        f = lambda v: _round(v, "bf16").astype(np.float64)  # noqa: E731
    else:
        f = lambda v: v  # noqa: E731
    e = f(reads * cell["e_read_pj"])
    e = f(e + f(cyc * (n_groups * cell["e_arbiter_pj_per_cycle_128"])))
    e = f(e + f(cyc * (n_out * cell["e_neuron_accum_pj"])))
    e = f(e + n_out * cell["e_neuron_fire_pj"])
    e = f(e + f(cyc * (n_groups * n_colgroups
                       * cell["e_tile_clocktree_pj_per_cycle"])))
    return cyc, e


def group_loads(spikes: np.ndarray, rows: int) -> np.ndarray:
    """{0,1}[..., n] -> active count per group of ``rows`` inputs."""
    n = spikes.shape[-1]
    g = -(-n // rows)
    pad = g * rows - n
    s = np.asarray(spikes, np.int64)
    if pad:
        s = np.concatenate([s, np.zeros(s.shape[:-1] + (pad,), np.int64)], -1)
    return s.reshape(s.shape[:-1] + (g, rows)).sum(-1)


def _cost(tile_inputs, topology, cell, dtype):
    cycles = energy = 0.0
    for t, s in enumerate(tile_inputs):
        c, e = tile_cost(group_loads(s, cell["array_rows"]),
                         topology[t], topology[t + 1], cell, dtype)
        cycles = cycles + c
        energy = _round(energy + e, dtype) if dtype == "bf16" else energy + e
    return cycles, energy


# ------------------------------------------------------------------ #
# static IF network
# ------------------------------------------------------------------ #
def if_forward(bits, vth, out_offset, x, cell, dtype="exact"):
    """Binary IF cascade on spikes x {0,1}[n, n_in].

    Returns (logits float64[n, n_cls], cycles[n], energy_pj[n]).  Hidden
    neurons fire where the membrane, the ±1 sum of active inputs, reaches
    their threshold; the readout is the last tile's membrane plus the offset.
    """
    topology = [bits[0].shape[0]] + [w.shape[1] for w in bits]
    s = np.asarray(x, np.float32)
    inputs = [s]
    for w, th in zip(bits[:-1], vth[:-1]):
        v = _round(s @ signed(w), dtype)
        s = (v >= np.asarray(th, np.float32)).astype(np.float32)
        inputs.append(s)
    v = _round(s @ signed(bits[-1]), dtype)
    logits = v.astype(np.float64) + np.asarray(out_offset, np.float64)
    cycles, energy = _cost(inputs, topology, cell, dtype)
    return logits, cycles, energy


# ------------------------------------------------------------------ #
# temporal LIF network
# ------------------------------------------------------------------ #
def lif_forward(bits, vth, out_offset, events, leak, cell, dtype="exact"):
    """LIF network over event streams {0,1}[T, n, n_in] of one length T.

    Per step and hidden layer: v = v * (1 - leak) + (±1 sum of the layer's
    input spikes), fire where v >= threshold, reset fired neurons to 0.  The
    readout integrates the last tile with the same leak and never fires.
    Returns (logits float64[n, n_cls], cycles[n], energy_pj[n]), the cost
    summed over the T steps.
    """
    topology = [bits[0].shape[0]] + [w.shape[1] for w in bits]
    t_steps, n = events.shape[:2]
    decay = np.float32(1.0 - leak)
    ws = [signed(w) for w in bits]
    v = [np.zeros((n, w.shape[1]), np.float32) for w in bits[:-1]]
    out = np.zeros((n, bits[-1].shape[1]), np.float32)
    cycles = np.zeros(n)
    energy = np.zeros(n, np.float32 if dtype == "bf16" else np.float64)
    for t in range(t_steps):
        s = np.asarray(events[t], np.float32)
        inputs = [s]
        for i, th in enumerate(vth[:-1]):
            c = s @ ws[i]
            vi = _round(_round(v[i] * decay, dtype) + c, dtype)
            fired = vi >= np.asarray(th, np.float32)
            v[i] = np.where(fired, np.float32(0.0), vi)
            s = fired.astype(np.float32)
            inputs.append(s)
        out = _round(_round(out * decay, dtype) + s @ ws[-1], dtype)
        c, e = _cost(inputs, topology, cell, dtype)
        cycles += c
        energy = _round(energy + e, dtype) if dtype == "bf16" else energy + e
    logits = out.astype(np.float64) + np.asarray(out_offset, np.float64)
    return logits, cycles, np.asarray(energy, np.float64)


# ------------------------------------------------------------------ #
# online learning: supervised stochastic 1-bit STDP on the readout
# ------------------------------------------------------------------ #
def hidden_spikes(bits, vth, x):
    """The frozen hidden tiles' output: the readout's input spikes."""
    s = np.asarray(x, np.float32)
    for w, th in zip(bits[:-1], vth[:-1]):
        s = ((s @ signed(w)) >= np.asarray(th, np.float32)).astype(np.float32)
    return s.astype(bool)


@functools.lru_cache(maxsize=None)
def _uniform_draw(n_in: int):
    """Jitted (key, idx) -> float32[n, 3, n_in]: sample i, phase k drawn
    from ``fold_in(fold_in(key, i), k)``; compiled once per width."""
    import jax

    def one(key, i, k):
        return jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, i), k), (n_in,))

    per_phase = jax.vmap(one, in_axes=(None, None, 0))
    draw = jax.vmap(per_phase, in_axes=(None, 0, None))
    return jax.jit(lambda key, idx: draw(key, idx, jax.numpy.arange(3)))


def stdp_uniforms(key, n: int, n_in: int):
    """float32[3, n, n_in]: per sample i and phase k, uniform draws of
    ``fold_in(fold_in(key, i), k)`` (k = 0 teacher potentiation, 1 teacher
    depression, 2 wrong-winner depression), as the learning rule's
    counter-based RNG defines them."""
    import jax.numpy as jnp

    u = _uniform_draw(int(n_in))(key, jnp.arange(n, dtype=jnp.int32))
    return np.ascontiguousarray(np.asarray(u).transpose(1, 0, 2))


def stdp_epoch(bits_t, pre, labels, uniforms, p_pot, p_dep, out_offset,
               dtype="exact"):
    """One pass of supervised STDP over (pre, labels), in sample order.

    ``bits_t`` {0,1}[n_cls, n_in] is the readout, one row per output neuron.
    Per sample: readout membrane 2 * (bits . s) - |s|, prediction = argmax of
    membrane + offset (first index on ties).  When it is wrong, the teacher
    row potentiates active inputs with probability p_pot and depresses silent
    ones with probability p_dep; the predicted row depresses active inputs
    with probability p_dep.  Returns (new bits_t, number of row updates).
    """
    bits_t = np.array(bits_t, np.int64)
    u = uniforms
    p_pot_, p_dep_ = np.float32(p_pot), np.float32(p_dep)
    if dtype == "bf16":
        u = u.astype(BF16).astype(np.float32)
        p_pot_, p_dep_ = (np.float32(BF16(p)) for p in (p_pot, p_dep))
    off = np.asarray(out_offset, np.float32)
    n_upd = 0
    for i in range(pre.shape[0]):
        s = pre[i]
        vmem = 2 * bits_t[:, s].sum(-1) - int(s.sum())
        pred = int(np.argmax(vmem.astype(np.float32) + off))
        y = int(labels[i])
        if pred == y:
            continue
        row = bits_t[y]
        row[s & (u[0, i] < p_pot_)] = 1
        row[~s & (u[1, i] < p_dep_)] = 0
        bits_t[pred, s & (u[2, i] < p_dep_)] = 0
        n_upd += 2
    return bits_t.astype(np.int8), n_upd


def gap_rel(got, want) -> float:
    """Largest |got - want| / max(|want|, 1): a relative gap that stays
    defined where the reference value is 0."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))


def gap_abs(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    d = np.abs(got - want)
    return float(np.max(np.where(np.isnan(d), math.inf, d)))
