"""Pure-jnp oracles for the popcount-domain CIM MAC.

Two families.  The dense oracles (``cim_matmul_ref``, ``esam_layer_ref``)
compute the binary MAC on unpacked {0,1} spikes and weight bits, and their
packed spellings (``cim_matmul_packed_ref``, ``esam_layer_packed_ref``)
unpack the wire first — they are independent of the popcount identity, and
the kernels' tests compare against them.  The popcount references
(``cim_popcount_ref`` and the layer / cascade built on it) compute in the
*same domain as the kernel* — AND + popcount per uint32 word with the
row-popcount offset — so they double as the fast non-TPU dispatch target:
on CPU one vectorized popcount pass beats both the interpret-mode kernel
and an unpack + BLAS round trip, and the arithmetic is exact int32 end to
end (bit-identical to the dense oracle, property-tested in
tests/test_popcount.py).

Identity (±1 weights stored as {0,1} bits ``w``, spikes ``s``):

    V[b, n] = sum_k s[b,k] * (2*w[k,n] - 1)
            = 2 * sum_j popcount(packed[b,j] & planes[n,j]) - popcount(packed[b])

Zero tail padding in the wire format is exact in both terms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing


def cim_matmul_ref(spikes: jax.Array, weight_bits: jax.Array) -> jax.Array:
    """V_mem = spikes @ (2*bits - 1).

    Args:
      spikes: {0,1} (any float/int/bool dtype) [batch, n_in]
      weight_bits: {0,1} [n_in, n_out]
    Returns:
      int32 [batch, n_out]
    """
    w = (2 * weight_bits.astype(jnp.int32) - 1)
    return spikes.astype(jnp.int32) @ w


def esam_layer_ref(
    spikes: jax.Array, weight_bits: jax.Array, vth: jax.Array
) -> jax.Array:
    """Fused MAC + IF fire: out spikes = (V_mem >= V_th)."""
    return (cim_matmul_ref(spikes, weight_bits) >= vth[None, :]).astype(jnp.int8)


def cim_matmul_packed_ref(packed: jax.Array, weight_bits: jax.Array) -> jax.Array:
    """V_mem int32[B, N] from uint32 bitplanes [B, ceil(K/32)]."""
    spikes = packing.unpack_spikes(packed, weight_bits.shape[0])
    return cim_matmul_ref(spikes, weight_bits)


def esam_layer_packed_ref(
    packed: jax.Array,
    weight_bits: jax.Array,
    vth: jax.Array,
    *,
    pack_output: bool = True,
) -> jax.Array:
    """Fused-fire oracle; packed output when ``pack_output``."""
    spikes = packing.unpack_spikes(packed, weight_bits.shape[0])
    out = esam_layer_ref(spikes, weight_bits, vth)
    return packing.pack_spikes(out) if pack_output else out


def _and_popcount(packed: jax.Array, planes: jax.Array) -> jax.Array:
    """sum_j popcount(packed[b,j] & planes[n,j]) -> int32[B, N].

    Word-at-a-time accumulation keeps the intermediate at [B, N] instead of
    materializing the full [B, N, W] AND tensor.
    """
    B, W = packed.shape
    N, W2 = planes.shape
    assert W == W2, (packed.shape, planes.shape)

    def body(j, acc):
        a = jax.lax.dynamic_index_in_dim(packed, j, 1, keepdims=True)   # [B, 1]
        b = jax.lax.dynamic_index_in_dim(planes, j, 1, keepdims=True)   # [N, 1]
        return acc + jax.lax.population_count(a & b.T).astype(jnp.int32)

    return jax.lax.fori_loop(0, W, body, jnp.zeros((B, N), jnp.int32))


def cim_popcount_ref(packed: jax.Array, planes: jax.Array) -> jax.Array:
    """V_mem int32[B, N] from spike words [B, W] and weight planes [N, W]."""
    spc = jax.lax.population_count(packed).astype(jnp.int32).sum(-1)
    return 2 * _and_popcount(packed, planes) - spc[:, None]


def esam_layer_popcount_ref(
    packed: jax.Array,
    planes: jax.Array,
    vth: jax.Array,
    *,
    pack_output: bool = True,
) -> jax.Array:
    """Fused popcount MAC + IF fire (+ re-pack) oracle."""
    fired = cim_popcount_ref(packed, planes) >= vth[None, :].astype(jnp.int32)
    return packing.pack_spikes(fired) if pack_output else fired.astype(jnp.int8)


def esam_cascade_popcount_ref(
    packed: jax.Array,
    planes: tuple,   # per tile: uint32[N_t, ceil(K_t/32)]
    vth: tuple,      # per tile: int32[N_t]
) -> tuple[jax.Array, tuple]:
    """Whole-cascade oracle: hidden fires on the popcount plane, int32 logits.

    Returns (vmem int32[B, n_cls], fired hidden planes tuple) — exactly the
    mega-kernel's outputs, for bit-identity gating.
    """
    p = packed
    fired = []
    for w, th in zip(planes[:-1], vth[:-1]):
        p = esam_layer_popcount_ref(p, w, th, pack_output=True)
        fired.append(p)
    return cim_popcount_ref(p, planes[-1]), tuple(fired)
