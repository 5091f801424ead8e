"""Pallas TPU kernels: bit-packed binary CIM MAC (+ fused IF fire + re-pack).

The paper's tiles exchange spikes as parallel single-bit pulses (Sec 3.1); the
packed kernel family is the TPU rendering of that wire: spikes arrive from HBM
as uint32 bitplanes (32 spikes per lane word, LSB-first — see
``repro.core.packing``), are unpacked *in VMEM* with shifts/masks on the VPU,
and feed the MXU exactly like the unpacked ``cim_matmul``.  HBM spike traffic
drops 32x vs f32 spikes (8x vs the int8 wire) while the MAC schedule, block
shapes, and results stay bit-identical.

The fused variant additionally re-packs the fired output spikes before the
store, so a cascade of tiles (``EsamNetwork.forward_fused``) moves *only*
packed words between layers — the inter-tile pulse bus, end to end.

Grid/block layout mirrors ``cim_matmul``: grid (B/bm, N/bn, K/bk) with K
innermost and an f32 VMEM accumulator; the spike operand block is
(bm, bk/32) uint32 rather than (bm, bk).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import LANE_BITS


def unpack_bits_block(packed: jax.Array) -> jax.Array:
    """(bm, bkw) uint32 -> (bm, bkw*32) bf16 {0,1}; VPU shifts + masks only."""
    bm, bkw = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, bkw, LANE_BITS), 2)
    bits = (packed[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(bm, bkw * LANE_BITS).astype(jnp.bfloat16)


def pack_bits_block(fired: jax.Array) -> jax.Array:
    """(bm, bn) bool -> (bm, bn/32) uint32 — the fire-stage re-pack.

    Word ``k`` is ``sum_b fired[:, 32k + b] << b``: a matmul against a
    {0, 2^b} selection matrix, done on the MXU in two 16-bit halves so every
    partial sum (<= 2^16 - 1) is exact in the f32 accumulator and every
    operand (0, 1 or a power of two <= 2^15) is exact in bf16.  A lane-split
    reshape would be the VPU spelling, but Mosaic has no layout for it.
    """
    bm, bn = fired.shape
    bnw = bn // LANE_BITS
    row = jax.lax.broadcasted_iota(jnp.int32, (bn, bnw), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, bnw), 1)
    bit = row % LANE_BITS
    mine = row // LANE_BITS == col
    half = LANE_BITS // 2
    f = fired.astype(jnp.bfloat16)

    def half_word(lo_bit):
        sel = mine & (bit >= lo_bit) & (bit < lo_bit + half)
        weights = jnp.where(sel, jnp.left_shift(1, bit - lo_bit), 0)
        v = jnp.dot(f, weights.astype(jnp.float32).astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        return v.astype(jnp.int32).astype(jnp.uint32)

    return (half_word(half) << half) | half_word(0)


def mac_packed_kernel(s_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    """grid = (B/bm, N/bn, K/bk); K innermost.  s_ref holds packed words."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    spikes = unpack_bits_block(s_ref[...])
    w = (2.0 * w_ref[...].astype(jnp.bfloat16) - 1.0)
    acc_ref[...] += jax.lax.dot_general(
        spikes, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(jnp.int32)


def fused_fire_packed_kernel(
    s_ref, w_ref, vth_ref, out_ref, acc_ref, *, n_k: int, pack_output: bool
):
    """Packed MAC with the IF threshold compare fused in the epilogue; when
    ``pack_output`` the fired spikes leave the kernel already bit-packed, so
    V_mem *and* the unpacked spike tensor never exist in HBM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    spikes = unpack_bits_block(s_ref[...])
    w = (2.0 * w_ref[...].astype(jnp.bfloat16) - 1.0)
    acc_ref[...] += jax.lax.dot_general(
        spikes, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _fire():
        vmem = acc_ref[...].astype(jnp.int32)
        fired = vmem >= vth_ref[...]
        if pack_output:
            out_ref[...] = pack_bits_block(fired)
        else:
            out_ref[...] = fired.astype(jnp.int8)
