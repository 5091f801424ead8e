"""Online learning via the transposable port: stochastic 1-bit STDP.

ESAM's learning contribution is *architectural*: the column-wise RW port makes
"update all synapses of one post-synaptic neuron" a 2x4-cycle operation instead
of 2x128 (Sec 4.4.1).  The learning *rule* it enables is the stochastic-STDP
family with 1-bit weights of Yousefzadeh et al. [16]: on a post-synaptic
learning event, synapses from recently-active pre-neurons potentiate (bit->1)
with probability p_pot and synapses from silent pre-neurons depress (bit->0)
with probability p_dep.

On TPU the transposed port becomes a layout choice: weights live
transposed-resident as ``{0,1}[N_out, N_in]`` so one learning neuron's
synapses are one contiguous row, and each supervised event is a row write
of that layout.  Per sample only the <= 2 event columns (teacher + wrong
winner) draw RNG — counter-based ``fold_in`` keys, never a ``[n_in, n_out]``
uniform matrix — and the whole epoch runs as one Pallas kernel that keeps
the readout in VMEM (``column_event_epoch``, through
``kernels/stdp.stdp_column_event_epoch``).  The cost accounting that
reproduces the paper's 26.0x / 19.5x claims is below.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.esam import cost_model as cm


# --------------------------------------------------------------------- #
# The update rule (functional plane)
# --------------------------------------------------------------------- #
def stdp_update_from_uniforms(
    weight_bits: jax.Array,   # {0,1}[n_in, n_out]
    pre_spikes: jax.Array,    # bool[n_in]
    post_events: jax.Array,   # bool[n_out]
    u_pot: jax.Array,         # float[n_in, n_out] (or broadcastable)
    u_dep: jax.Array,         # float[n_in, n_out] (or broadcastable)
    p_pot: float,
    p_dep: float,
) -> jax.Array:
    """The pure stochastic-STDP rule given explicit uniform draws.

    This is the single source of truth for the rule; ``stdp_update`` (keyed)
    and the scan plane apply it directly, and the column write that the
    ``kernels/stdp`` epoch kernel is tested against (``stdp_column_event_ref``)
    is bit-exact against it under shared uniforms (tested).
    """
    pre = pre_spikes.astype(bool)[:, None]
    post = post_events.astype(bool)[None, :]
    potentiate = post & pre & (u_pot < p_pot)
    depress = post & ~pre & (u_dep < p_dep)
    new_bits = jnp.where(potentiate, 1, jnp.where(depress, 0, weight_bits))
    return new_bits.astype(weight_bits.dtype)


def stdp_update(
    weight_bits: jax.Array,   # {0,1}[n_in, n_out]
    pre_spikes: jax.Array,    # bool[n_in]   — pre-synaptic activity trace
    post_events: jax.Array,   # bool[n_out]  — which post neurons learn now
    key: jax.Array,
    p_pot: float = 0.1,
    p_dep: float = 0.05,
) -> jax.Array:
    """One stochastic-STDP event: returns updated weight bits."""
    k1, k2 = jax.random.split(key)
    u_pot = jax.random.uniform(k1, weight_bits.shape)
    u_dep = jax.random.uniform(k2, weight_bits.shape)
    return stdp_update_from_uniforms(
        weight_bits, pre_spikes, post_events, u_pot, u_dep, p_pot, p_dep
    )


# --------------------------------------------------------------------- #
# Column-event RNG: counter-based keys, <= 3 * n_in draws per sample
# --------------------------------------------------------------------- #
def column_event_uniforms(
    key: jax.Array, sample_index: jax.Array, n_in: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-sample uniforms for the <= 2 event columns of supervised STDP.

    Counter-based ``fold_in`` scheme — phase 0 potentiates / phase 1 depresses
    the teacher column, phase 2 depresses the wrong-winner column.  Both the
    fused column-event plane and the scan reference draw through this one
    function, which is what makes them bit-comparable.
    """
    ks = jax.random.fold_in(key, sample_index)
    u_pot = jax.random.uniform(jax.random.fold_in(ks, 0), (n_in,))
    u_dep_teacher = jax.random.uniform(jax.random.fold_in(ks, 1), (n_in,))
    u_dep_wrong = jax.random.uniform(jax.random.fold_in(ks, 2), (n_in,))
    return u_pot, u_dep_teacher, u_dep_wrong


# --------------------------------------------------------------------- #
# Hardware cost accounting (Sec 4.4.1)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ColumnUpdateCost:
    cell: str
    read_cycles: int
    write_cycles: int
    read_ns: float
    write_ns: float
    energy_pj: float            # read-modify-write of one column
    speedup_read_vs_1rw: float
    speedup_write_vs_1rw: float


def column_update_cost(read_ports: int, rows: int = 128) -> ColumnUpdateCost:
    """Time/energy to read+write one weight column (one learning neuron).

    The 1RW baseline must touch all `rows` rows through the single RW port
    (2 x 128 cycles = 257.8 ns, 157 pJ for the full array, Sec 4.4.1).  With
    the transposed column port, access takes COL_MUX_FACTOR cycles each way at
    the transposed-path clock.
    """
    spec = cm.cell_spec(read_ports)
    rc, wc = cm.column_update_cycles(read_ports, rows)
    if read_ports == 0:
        # 1RW column RMW: precharge+read = 2 cycles per row, then one write per
        # row at the 1RW write time (see cost_model baseline decode).
        read_ns, write_ns = cm.T1RW_COL_READ_NS, cm.T1RW_COL_WRITE_NS
        energy = rows * (cm.E_READ_1RW_PJ + cm.E_WRITE_1RW_PJ)  # RMW every row
    else:
        clock = cm.T4R_TRANSPOSED_CLOCK_NS
        # Measured end-to-end column access times for the 4R cell (Sec 4.4.1);
        # cycle counts for other port counts scale identically (same mux).
        read_ns = cm.T4R_COL_READ_NS if read_ports == 4 else rc * clock + spec.sram_neuron_ns
        write_ns = cm.T4R_COL_WRITE_NS if read_ports == 4 else wc * clock + spec.sram_neuron_ns
        energy = spec.e_tread_pj + spec.e_write_pj   # one column-read + one column-write
    base_read_ns = cm.T1RW_COL_READ_NS
    base_write_ns = cm.T1RW_COL_WRITE_NS
    return ColumnUpdateCost(
        cell=spec.name,
        read_cycles=int(rc),
        write_cycles=int(wc),
        read_ns=float(read_ns),
        write_ns=float(write_ns),
        energy_pj=float(energy),
        speedup_read_vs_1rw=float(base_read_ns / read_ns),
        speedup_write_vs_1rw=float(base_write_ns / write_ns),
    )


# --------------------------------------------------------------------- #
# Frozen-prefix activations
# --------------------------------------------------------------------- #
def last_hidden_spikes(
    network_bits: list[jax.Array],
    vth: list[jax.Array],
    spikes: jax.Array,          # bool[batch, n_in]
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Run the frozen prefix tiles; returns the last tile's input spikes.

    Uses the packed popcount plane (``network.packed_prefix`` — uint32
    bitplanes between tiles) when every hidden width is 32-aligned,
    falling back to the dense functional tiles otherwise.  Both are
    bit-identical (tests/test_packing.py), so the learning plane sees the same
    pre-synaptic trace either way.
    """
    hidden = network_bits[:-1]
    if hidden and all(w.shape[1] % 32 == 0 for w in hidden):
        from repro.core import packing
        from repro.core.esam import network as network_mod

        p = network_mod.packed_prefix(
            network_bits, vth, packing.pack_spikes(spikes), interpret=interpret)
        return packing.unpack_spikes(p, hidden[-1].shape[1], dtype=jnp.bool_)
    from repro.core.esam import tile as tile_mod

    s = spikes
    for w, th in zip(hidden, vth[:-1]):
        s, _ = tile_mod.functional_tile(w, s, th)
    return s


def readout_vmem(bits_t: jax.Array, spikes: jax.Array) -> jax.Array:
    """V_mem = s . (2b - 1) on the transposed-resident ``[n_out, n_in]`` layout.

    Integer arithmetic throughout — bit-identical to ``tile.functional_tile``'s
    einsum on the row-major layout (summation order is irrelevant for int32).
    Accepts a single sample ``[n_in]`` or any batch ``[..., n_in]``.
    """
    sv = spikes.astype(jnp.int32)
    w = bits_t.astype(jnp.int32)
    return 2 * jnp.einsum("...i,oi->...o", sv, w) - sv.sum(-1, keepdims=True)


# --------------------------------------------------------------------- #
# The fused column-event epoch (tentpole plane)
# --------------------------------------------------------------------- #
@functools.partial(
    jax.jit,
    static_argnames=("p_pot", "p_dep", "interpret"),
    donate_argnums=(0,),
)
def column_event_epoch(
    bits_t: jax.Array,          # {0,1}[n_out, n_in] transposed-resident layout
    pre: jax.Array,             # bool[batch, n_in] — last tile's input spikes
    labels: jax.Array,          # int32[batch]
    key: jax.Array,
    *,
    p_pot: float,
    p_dep: float,
    out_offset: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One supervised-STDP epoch as a single Pallas kernel.

    The epoch's draws come first, all samples at once (``column_event_uniforms``
    under ``vmap``: counter-based, so the same bits as one sample at a time).
    Then ``kernels/stdp.stdp_column_event_epoch`` runs the samples in order
    with the transposed readout resident in VMEM: per sample the last-tile
    matvec, the argmax readout, the teacher / wrong-winner events and their
    two gated column-port writes — the TPU rendering of the paper's
    online-learning loop through the column RW port.  The input buffer is
    donated.

    ``out_offset`` shifts the argmax that derives the wrong-winner event, so
    learning can target the *deployed* readout (the folded conversion offset
    ``EsamNetwork.forward`` adds before its argmax).  The default ``None``
    keeps the offset-free vmem argmax of the scan reference (bit-comparable).

    Returns (updated bits_t, number of column updates as a device scalar).
    """
    from repro.kernels.stdp import ops as stdp_ops

    n_out, n_in = bits_t.shape
    idx = jnp.arange(pre.shape[0], dtype=jnp.int32)
    u_pot, u_dep_t, u_dep_w = jax.vmap(
        lambda i: column_event_uniforms(key, i, n_in))(idx)
    if out_offset is None:
        out_offset = jnp.zeros((n_out,), jnp.float32)
    return stdp_ops.stdp_column_event_epoch(
        bits_t, pre, labels, u_pot, u_dep_t, u_dep_w, out_offset,
        p_pot=p_pot, p_dep=p_dep, interpret=interpret)


def online_learning_epoch(
    network_bits: list[jax.Array],
    vth: list[jax.Array],
    spikes: jax.Array,          # bool[batch, n_in]
    labels: jax.Array,          # int32[batch] — supervised teacher events
    key: jax.Array,
    p_pot: float = 0.12,
    p_dep: float = 0.06,
    pre_spikes: jax.Array | None = None,
    *,
    interpret: bool | None = None,
):
    """Supervised-STDP pass over a batch for the *last* tile (delta-rule style).

    Teacher signal: the correct class neuron is a potentiation event; the
    argmax-wrong neuron is a depression event.  Returns (new last-layer bits,
    number of column updates as an int32 device scalar — cast once at the
    caller if a host int is needed; the count feeds the cost model).

    ``pre_spikes`` takes the last hidden layer's spikes if the caller already
    ran ``EsamNetwork.forward(..., collect=True)``; otherwise the frozen
    prefix runs once through the packed fused plane (``last_hidden_spikes``).
    The epoch itself is the fused column-event kernel (``column_event_epoch``).
    """
    s = pre_spikes if pre_spikes is not None else last_hidden_spikes(
        network_bits, vth, spikes, interpret=interpret)
    bits_t = jnp.asarray(network_bits[-1]).T
    bits_t, n_updates = column_event_epoch(
        bits_t, s.astype(bool), labels, key,
        p_pot=float(p_pot), p_dep=float(p_dep), interpret=interpret)
    return bits_t.T, n_updates


def online_learning_epoch_scan(
    network_bits: list[jax.Array],
    vth: list[jax.Array],
    spikes: jax.Array,
    labels: jax.Array,
    key: jax.Array,
    p_pot: float = 0.12,
    p_dep: float = 0.06,
    pre_spikes: jax.Array | None = None,
    rng_scheme: str = "matrix",
):
    """The PR 1 per-sample scan: full ``[n_in, n_out]`` rewrite every sample.

    Kept as the measured baseline (benchmarks/bench_online_learning.py) and
    as the bit-identity oracle for the fused plane:

    * ``rng_scheme="matrix"`` — the original behavior: two full
      ``[n_in, n_out]`` uniform matrices drawn per sample from a split chain.
    * ``rng_scheme="column"`` — the shared counter-based column scheme
      (``column_event_uniforms``), broadcast across columns; only the event
      column's draw ever matters, so this is bit-identical to
      ``online_learning_epoch`` under the same key (tested).
    """
    from repro.core.esam import tile as tile_mod

    assert rng_scheme in ("matrix", "column"), rng_scheme
    bits_last = network_bits[-1]
    n_in, n_out = bits_last.shape
    if pre_spikes is not None:
        s = pre_spikes
    else:
        s = spikes
        for w, th in zip(network_bits[:-1], vth[:-1]):
            s, _ = tile_mod.functional_tile(w, s, th)

    def body(carry, inp):
        bits, k = carry
        s_i, y_i, i = inp
        _, vmem = tile_mod.functional_tile(bits, s_i, vth[-1])
        pred = jnp.argmax(vmem)
        wrong = pred != y_i
        post_pot = jax.nn.one_hot(y_i, n_out, dtype=bool) & wrong
        post_dep = jax.nn.one_hot(pred, n_out, dtype=bool) & wrong
        if rng_scheme == "matrix":
            k, k1, k2 = jax.random.split(k, 3)
            # correct neuron: Hebbian — pull its column toward the pre pattern
            bits = stdp_update(bits, s_i, post_pot, k1, p_pot, p_dep)
            # wrong winner: pure depression of active-pre synapses (bit -> 0).
            # Expressed via stdp_update with the pre trace inverted and
            # potentiation disabled — potentiating silent positions would
            # *raise* the winner's response to shifted variants instead of
            # suppressing it.
            bits = stdp_update(bits, ~s_i, post_dep, k2, 0.0, p_dep)
        else:
            u_pot, u_dep_t, u_dep_w = column_event_uniforms(key, i, n_in)
            bits = stdp_update_from_uniforms(
                bits, s_i, post_pot, u_pot[:, None], u_dep_t[:, None],
                p_pot, p_dep)
            bits = stdp_update_from_uniforms(
                bits, ~s_i, post_dep, u_dep_w[:, None], u_dep_w[:, None],
                0.0, p_dep)
        return (bits, k), wrong.astype(jnp.int32) * 2

    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    (bits_last, _), upd = jax.lax.scan(body, (bits_last, key), (s, labels, idx))
    return bits_last, upd.sum()
