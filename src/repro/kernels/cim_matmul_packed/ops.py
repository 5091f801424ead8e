"""Jit'd public wrappers for the packed CIM MAC kernels.

These wrappers own the padding contract: the caller hands in the natural
shapes (B samples, K pre-neurons packed into ceil(K/32) words, N post
neurons) and the wrapper zero-pads B up to a block multiple and K up to a
packed block multiple.  Zero padding is exact for the binary CIM MAC — a
silent spike contributes nothing whatever the stored weight bit — so padded
and unpadded results are bit-identical on the valid region.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.packing import LANE_BITS
from repro.kernels.common import default_interpret, pad_dim_to, round_up
from repro.kernels.cim_matmul_packed import kernel as knl
from repro.kernels.cim_matmul_packed.ref import (  # noqa: F401  (re-export)
    cim_matmul_packed_ref,
    esam_layer_packed_ref,
)

__all__ = [
    "cim_matmul_packed",
    "esam_layer_packed",
    "cim_matmul_packed_ref",
    "esam_layer_packed_ref",
]


def _prep(packed, weight_bits, block_b, block_n, block_k):
    """Pad operands to block multiples; returns operands + grid geometry."""
    B, kw = packed.shape
    K, N = weight_bits.shape
    assert kw == packing.packed_width(K), (kw, K)
    k_words = kw * LANE_BITS
    bk = min(block_k, k_words)
    assert bk % LANE_BITS == 0, bk
    k_pad = round_up(k_words, bk)
    w = pad_dim_to(weight_bits, k_pad, 0)
    p = pad_dim_to(packed, k_pad // LANE_BITS, 1)
    bm = min(block_b, B)
    b_pad = round_up(B, bm)
    p = pad_dim_to(p, b_pad, 0)
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    return p, w, (B, b_pad, k_pad, N, bm, bn, bk)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def cim_matmul_packed(
    packed: jax.Array,       # uint32[B, ceil(K/32)] bit-packed spikes
    weight_bits: jax.Array,  # {0,1}[K, N]
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """V_mem int32[B, N] = unpack(packed) @ (2*bits-1), unpacking in VMEM."""
    if interpret is None:
        interpret = default_interpret()
    p, w, (B, b_pad, k_pad, N, bm, bn, bk) = _prep(
        packed, weight_bits, block_b, block_n, block_k
    )
    n_k = k_pad // bk
    bkw = bk // LANE_BITS
    grid = (b_pad // bm, N // bn, n_k)
    out = pl.pallas_call(
        functools.partial(knl.mac_packed_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="cim_matmul_packed",
        interpret=interpret,
    )(p, w)
    return out[:B]


@functools.partial(
    jax.jit,
    static_argnames=("pack_output", "block_b", "block_n", "block_k", "interpret"),
)
def esam_layer_packed(
    packed: jax.Array,       # uint32[B, ceil(K/32)]
    weight_bits: jax.Array,  # {0,1}[K, N]
    vth: jax.Array,          # int32[N]
    *,
    pack_output: bool = True,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused packed tile: MAC + IF fire (+ output re-pack).

    Returns uint32[B, N/32] when ``pack_output`` (N must be a multiple of 32)
    else int8[B, N] — in either case V_mem never leaves VMEM.
    """
    if interpret is None:
        interpret = default_interpret()
    _, N = weight_bits.shape
    assert vth.shape == (N,), (vth.shape, N)
    p, w, (B, b_pad, k_pad, N, bm, bn, bk) = _prep(
        packed, weight_bits, block_b, block_n, block_k
    )
    if pack_output:
        assert N % LANE_BITS == 0 and bn % LANE_BITS == 0, (N, bn)
    n_k = k_pad // bk
    bkw = bk // LANE_BITS
    grid = (b_pad // bm, N // bn, n_k)
    vth2d = vth[None, :].astype(jnp.int32)
    if pack_output:
        out_spec = pl.BlockSpec((bm, bn // LANE_BITS), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((b_pad, N // LANE_BITS), jnp.uint32)
    else:
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
        out_shape = jax.ShapeDtypeStruct((b_pad, N), jnp.int8)
    out = pl.pallas_call(
        functools.partial(
            knl.fused_fire_packed_kernel, n_k=n_k, pack_output=pack_output
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="esam_layer_packed",
        interpret=interpret,
    )(p, w, vth2d)
    return out[:B]
