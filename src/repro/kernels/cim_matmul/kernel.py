"""Pallas TPU kernel: batched binary CIM MAC (+ optional fused IF fire).

TPU adaptation of the paper's multiport read (DESIGN.md §2): the MXU plays the
role of an "all-ports" SRAM array — every row of a 128-wide spike tile is a
port.  Spikes {0,1} enter as bf16, stored weight bits are decoded to {-1,+1}
inside the kernel (the Fig-5 bitline decode), and accumulation runs in a f32
VMEM scratch across the K grid dimension; results are exact integers (values
are bounded by n_in << 2^24).

Block shapes are MXU-aligned (multiples of 8 x 128 for bf16 operands) and
sized so one (bm x bk) spike tile, one (bk x bn) weight tile, and the
(bm x bn) accumulator all fit in VMEM simultaneously.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret


def _mac_kernel(s_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    """grid = (B/bm, N/bn, K/bk); K is the innermost (fastest) dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    spikes = s_ref[...].astype(jnp.bfloat16)
    # Fig 5 decode: stored bit {0,1} -> synaptic value {-1,+1}
    w = (2.0 * w_ref[...].astype(jnp.bfloat16) - 1.0)
    acc_ref[...] += jax.lax.dot_general(
        spikes, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(jnp.int32)


def _fused_fire_kernel(s_ref, w_ref, vth_ref, out_ref, acc_ref, *, n_k: int):
    """Same MAC, with the IF threshold compare fused in the epilogue so V_mem
    never round-trips through HBM (the R_empty fire event of Sec 3.4)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    spikes = s_ref[...].astype(jnp.bfloat16)
    w = (2.0 * w_ref[...].astype(jnp.bfloat16) - 1.0)
    acc_ref[...] += jax.lax.dot_general(
        spikes, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _fire():
        vmem = acc_ref[...].astype(jnp.int32)
        out_ref[...] = (vmem >= vth_ref[...]).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def cim_matmul(
    spikes: jax.Array,       # {0,1}[B, K] any dtype
    weight_bits: jax.Array,  # {0,1}[K, N]
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """V_mem int32[B, N] = spikes @ (2*bits-1)."""
    if interpret is None:
        interpret = default_interpret()
    B, K = spikes.shape
    K2, N = weight_bits.shape
    assert K == K2, (K, K2)
    bm, bn, bk = min(block_b, B), min(block_n, N), min(block_k, K)
    assert B % bm == 0 and N % bn == 0 and K % bk == 0, (B, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (B // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_mac_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="cim_matmul",
        interpret=interpret,
    )(spikes, weight_bits)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n", "block_k", "interpret")
)
def esam_layer(
    spikes: jax.Array,       # {0,1}[B, K]
    weight_bits: jax.Array,  # {0,1}[K, N]
    vth: jax.Array,          # int32[N]
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused tile inference: out spikes int8[B, N] = (V_mem >= V_th)."""
    if interpret is None:
        interpret = default_interpret()
    B, K = spikes.shape
    K2, N = weight_bits.shape
    assert K == K2, (K, K2)
    assert vth.shape == (N,), (vth.shape, N)
    bm, bn, bk = min(block_b, B), min(block_n, N), min(block_k, K)
    assert B % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    grid = (B // bm, N // bn, n_k)
    vth2d = vth[None, :].astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_fused_fire_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="esam_layer",
        interpret=interpret,
    )(spikes, weight_bits, vth2d)
