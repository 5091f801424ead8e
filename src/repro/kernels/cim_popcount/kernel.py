"""Pallas TPU kernels: popcount-domain CIM MAC + single-launch tile cascade.

Spikes travel as uint32 bitplanes (32 spikes per lane word, LSB-first —
see ``repro.core.packing``), and weights are bit-sliced at plan-build time
into the same layout (``packing.pack_weight_planes``), so both operands
stay packed and each MAC is AND + popcount with the row-popcount offset,
entirely on the VPU, with nothing unpacked:

    V = 2 * sum_j popcount(s_word_j & w_word_j) - popcount(s)

Inside a kernel the weight planes are word-major (``[W, n]``): word ``j`` of
every output neuron is one sublane row, broadcast down the batch, while
word ``j`` of every sample is one lane column of the spike block, broadcast
across the neurons.  Each kernel takes the whole packed K extent (at most a
few dozen words) in one block, so no K grid axis or accumulator is needed.

``mega_cascade_kernel`` then fuses the whole tile cascade (MAC -> IF fire ->
re-pack -> next tile) into ONE launch: the grid walks batch blocks only, the
fired bitplanes stay resident as kernel values between tiles, and each
tile's weight-plane slab is DMA'd from HBM into a double-buffered VMEM
scratch while the previous tile computes — the layer-wise weight/output-
stationary dataflow of Chauvaux et al. rendered as a Pallas pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import LANE_BITS

#: vth padding for columns past a tile's real width — no spike plane can
#: reach it (V <= n_in < 2^30), so padded neurons provably never fire.
VTH_NEVER_FIRE = 1 << 30


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


def popcount_mac_block(s: jax.Array, w: jax.Array) -> jax.Array:
    """AND + popcount MAC of one block: (bm, W) x word-major (W, bn) -> int32
    (bm, bn).

    Static unroll over the W words; each step ANDs a lane column of the
    spikes against a sublane row of the planes on a 2-D (bm, bn) tile —
    pure VPU, no unpack.
    """
    bm, w_words = s.shape
    bn = w.shape[1]
    acc = jnp.zeros((bm, bn), jnp.int32)
    for j in range(w_words):
        acc += jax.lax.population_count(
            s[:, j:j + 1] & w[j:j + 1, :]).astype(jnp.int32)
    return acc


def _popcount_v(s: jax.Array, w: jax.Array) -> jax.Array:
    """V_mem of one block: 2 * AND-popcount - row popcount."""
    spc = jax.lax.population_count(s).astype(jnp.int32).sum(-1, keepdims=True)
    return 2 * popcount_mac_block(s, w) - spc


def popcount_mac_kernel(s_ref, w_ref, out_ref):
    """grid = (B/bm, N/bn).  s block (bm, W), word-major plane block (W, bn)."""
    out_ref[...] = _popcount_v(s_ref[...], w_ref[...])


def popcount_fire_kernel(s_ref, w_ref, vth_ref, out_ref, *, pack_output: bool):
    """Popcount MAC with the IF compare (+ output re-pack) fused in the
    epilogue — V_mem never leaves VMEM."""
    fired = _popcount_v(s_ref[...], w_ref[...]) >= vth_ref[...]
    if pack_output:
        out_ref[...] = pack_bits_block(fired)
    else:
        out_ref[...] = fired.astype(out_ref.dtype)


def mega_cascade_kernel(
    s_ref,       # (bm, W_in0) uint32 — the network input plane block
    vth_ref,     # (n_hidden, n_max_pad) int32, padded with VTH_NEVER_FIRE
    w_ref,       # ANY-space uint32[n_tiles, w_max, n_max_pad] word-major planes
    logits_ref,  # (bm, n_cls_pad) int32
    *rest,       # fired refs per hidden tile, then wbuf + DMA semaphores
    n_pad: tuple[int, ...],    # per tile: padded output width (128-aligned)
    w_words: tuple[int, ...],  # per tile: real input words ceil(K_t/32)
):
    """One launch, whole cascade.  grid = (B/bm,).

    The fired bitplanes are plain kernel values (VMEM-resident SSA), never
    stored between tiles except into their own output ref; tile t+1's weight
    slab is prefetched by async copy while tile t computes (double-buffered
    ``wbuf`` + one DMA semaphore per slot).
    """
    n_tiles = len(n_pad)
    fired_refs = rest[: n_tiles - 1]
    wbuf, sem = rest[n_tiles - 1], rest[n_tiles]
    vth = vth_ref[...]

    copies = [
        pltpu.make_async_copy(w_ref.at[t], wbuf.at[t % 2], sem.at[t % 2])
        for t in range(n_tiles)
    ]
    copies[0].start()

    s = s_ref[...]                                             # (bm, W_in0)
    spc = jax.lax.population_count(s).astype(jnp.int32).sum(-1, keepdims=True)
    for t in range(n_tiles):
        if t + 1 < n_tiles:
            copies[t + 1].start()
        copies[t].wait()
        w = wbuf[t % 2]                                        # (w_max, n_max_pad)
        v = 2 * popcount_mac_block(
            s[:, : w_words[t]], w[: w_words[t], : n_pad[t]]
        ) - spc                                                # (bm, n_pad[t])
        if t == n_tiles - 1:
            logits_ref[...] = v
        else:
            fired = v >= vth[t : t + 1, : n_pad[t]]
            s = pack_bits_block(fired)                         # stays resident
            fired_refs[t][...] = s
            spc = fired.astype(jnp.int32).sum(-1, keepdims=True)
