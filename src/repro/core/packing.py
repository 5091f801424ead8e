"""Bit-packed spike planes: 32 binary spikes per uint32 lane word.

The paper's inter-tile fabric moves spikes as parallel single-bit pulses
(Sec 3.1) — one wire per pre-synaptic neuron, never a full-precision word.
Our functional plane previously stored every spike in its own int8/bf16
element, moving 8-16x the bits the hardware would.  This module defines the
repo-wide wire format that closes that gap:

    spikes {0,1}[..., n]  <->  packed uint32[..., ceil(n/32)]

Bit ``b`` of word ``j`` holds spike ``j*32 + b`` (LSB-first within a word).
Positions past ``n`` in the last word are zero ("silent") — a zero spike
contributes nothing to the CIM MAC regardless of the stored weight bit, so
padding is exact, never approximate.

Both jnp and numpy implementations are provided: the jnp pair is what the
packed Pallas kernels (kernels/cim_popcount) and ``forward_fused`` use;
the numpy pair lets the host-side data pipeline and serving engine emit the
wire format without touching an accelerator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LANE_BITS = 32  # spikes per packed word (uint32 lanes)


def packed_width(n: int) -> int:
    """Number of uint32 words needed for n spikes."""
    return -(-n // LANE_BITS)


def packed_nbytes(n: int) -> int:
    """Wire bytes per sample for an n-spike plane (vs n bytes unpacked int8)."""
    return packed_width(n) * 4


# --------------------------------------------------------------------- #
# jnp (device) pair
# --------------------------------------------------------------------- #
def pack_spikes(spikes: jax.Array) -> jax.Array:
    """{0,1}[..., n] (any dtype) -> uint32[..., ceil(n/32)]."""
    n = spikes.shape[-1]
    w = packed_width(n)
    bits = (spikes != 0).astype(jnp.uint32)
    pad = w * LANE_BITS - n
    if pad:
        widths = [(0, 0)] * (bits.ndim - 1) + [(0, pad)]
        bits = jnp.pad(bits, widths)
    b = bits.reshape(bits.shape[:-1] + (w, LANE_BITS))
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    # distinct powers of two — the sum is an exact bitwise OR, no overflow
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def group_popcount(packed: jax.Array, group: int = 128) -> jax.Array:
    """Spike count per ``group``-bit row group, straight off the wire format.

    packed: uint32[..., W] bitplanes of a width-(W*32) spike plane whose
    logical width is a multiple of ``group`` (tail padding past it is zero,
    so counts stay exact).  Returns int32[..., W*32/group] — exactly the
    arbiter loads ``EsamNetwork.spike_counts`` measures, without unpacking.
    """
    assert group % LANE_BITS == 0, group
    words_per_group = group // LANE_BITS
    pc = jax.lax.population_count(packed).astype(jnp.int32)
    w = pc.shape[-1]
    assert w % words_per_group == 0, (w, group)
    return pc.reshape(pc.shape[:-1] + (w // words_per_group, words_per_group)).sum(-1)


def unpack_spikes(packed: jax.Array, n: int, dtype=jnp.int8) -> jax.Array:
    """uint32[..., W] -> {0,1}[..., n] in ``dtype``."""
    w = packed.shape[-1]
    assert w == packed_width(n), (w, n)
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(packed.shape[:-1] + (w * LANE_BITS,))
    return flat[..., :n].astype(dtype)


# --------------------------------------------------------------------- #
# weight bit planes — the other operand of the popcount-domain MAC
# --------------------------------------------------------------------- #
def pack_weight_planes(weight_bits: jax.Array) -> jax.Array:
    """Stored bits {0,1}[K, N] -> uint32[N, ceil(K/32)] weight bit planes.

    Row ``n`` packs output neuron ``n``'s column of stored bits along the
    pre-synaptic axis, in exactly the spike wire layout (bit ``b`` of word
    ``j`` is pre-neuron ``j*32 + b``, zero tail).  With both operands in this
    layout the CIM MAC never unpacks: for ±1 weights stored as {0,1} bits,

        V[b, n] = sum_k s[b,k] * (2*w[k,n] - 1)
                = 2 * sum_j popcount(spikes[b,j] & planes[n,j]) - popcount(spikes[b])

    and zero padding is exact in *both* terms — a padded spike bit is 0, so
    it joins neither the AND nor the row popcount.  Planes are sliced once at
    plan-build time (``EsamPlan``) and reused for every batch.
    """
    return pack_spikes(jnp.asarray(weight_bits).swapaxes(-1, -2))


def unpack_weight_planes(planes: jax.Array, n_in: int, dtype=jnp.int8) -> jax.Array:
    """uint32[N, ceil(K/32)] -> stored bits {0,1}[K, N] (round trip)."""
    return unpack_spikes(planes, n_in, dtype).swapaxes(-1, -2)


def pack_weight_planes_np(weight_bits: np.ndarray) -> np.ndarray:
    """Host twin of ``pack_weight_planes`` (bit-identical layout)."""
    return pack_spikes_np(np.asarray(weight_bits).swapaxes(-1, -2))


def unpack_weight_planes_np(planes: np.ndarray, n_in: int, dtype=np.int8) -> np.ndarray:
    return unpack_spikes_np(planes, n_in, dtype).swapaxes(-1, -2)


# --------------------------------------------------------------------- #
# numpy (host) pair — bit-identical layout, no jax dependency at call time
# --------------------------------------------------------------------- #
def pack_spikes_np(spikes: np.ndarray) -> np.ndarray:
    n = spikes.shape[-1]
    w = packed_width(n)
    bits = (np.asarray(spikes) != 0).astype(np.uint32)
    pad = w * LANE_BITS - n
    if pad:
        widths = [(0, 0)] * (bits.ndim - 1) + [(0, pad)]
        bits = np.pad(bits, widths)
    b = bits.reshape(bits.shape[:-1] + (w, LANE_BITS))
    shifts = np.arange(LANE_BITS, dtype=np.uint32)
    return np.sum(b << shifts, axis=-1, dtype=np.uint64).astype(np.uint32)


def unpack_spikes_np(packed: np.ndarray, n: int, dtype=np.int8) -> np.ndarray:
    w = packed.shape[-1]
    assert w == packed_width(n), (w, n)
    shifts = np.arange(LANE_BITS, dtype=np.uint32)
    bits = (packed[..., None] >> shifts) & np.uint32(1)
    flat = bits.reshape(packed.shape[:-1] + (w * LANE_BITS,))
    return flat[..., :n].astype(dtype)


# --------------------------------------------------------------------- #
# host-side batch prep — the single copy of pad-to-batch + pack
# --------------------------------------------------------------------- #
def pad_spike_rows_np(rows, batch: int, n_in: int) -> np.ndarray:
    """Stack per-request spike rows into a zero-padded {0,1} uint8 batch.

    ``rows``: sequence of {0,1}[n_in] arrays (any dtype), ``len(rows) <=
    batch``.  Unused slots stay all-zero ("silent"), which is exact padding
    for the binary CIM MAC.  This is the one host-side pad-to-batch
    implementation — the serving engine, the serving bench, and the examples
    all batch through here instead of each rolling their own.
    """
    assert len(rows) <= batch, (len(rows), batch)
    out = np.zeros((batch, n_in), np.uint8)
    for i, r in enumerate(rows):
        r = np.asarray(r)
        assert r.shape == (n_in,), (r.shape, n_in)
        out[i] = r != 0
    return out


def pack_padded_rows_np(rows, batch: int, n_in: int) -> np.ndarray:
    """``pad_spike_rows_np`` straight into the uint32 wire format."""
    return pack_spikes_np(pad_spike_rows_np(rows, batch, n_in))
