"""Pallas TPU kernel: IF neuron array — multi-round V_mem accumulation + fire.

Hardware mapping (Sec 3.4 / Fig 5): the neuron's m-bit V_mem register
accumulates each cycle's validity-masked port sum and is compared against the
t-bit V_th register when R_empty.  On TPU the V_mem "register" is a VMEM
accumulator that stays resident across all T rounds — the kernel reads the
whole round sequence for its neuron tile into VMEM, reduces it with a
fori_loop (keeping per-round semantics: integer adds in order), and fuses the
threshold compare + fire, so V_mem never spills to HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret


def _if_kernel(upd_ref, vth_ref, spikes_ref, vmem_ref):
    # upd_ref: [bb, T, bn]; per-round integer accumulation, order preserved.
    bb, T, bn = upd_ref.shape

    def round_step(t, vmem):
        return vmem + upd_ref[:, t, :].astype(jnp.int32)

    vmem = jax.lax.fori_loop(0, T, round_step, jnp.zeros((bb, bn), jnp.int32))
    vmem_ref[...] = vmem
    spikes_ref[...] = (vmem >= vth_ref[...].astype(jnp.int32)).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def if_neuron(
    updates: jax.Array,   # int32[B, T, N] per-cycle contributions
    vth: jax.Array,       # int32[N]
    *,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool | None = None,
):
    """Returns (spikes int8[B, N], vmem int32[B, N])."""
    if interpret is None:
        interpret = default_interpret()
    B, T, N = updates.shape
    bb, bn = min(block_b, B), min(block_n, N)
    assert B % bb == 0 and N % bn == 0
    grid = (B // bb, N // bn)
    vth2d = vth[None, :].astype(jnp.int32)
    return pl.pallas_call(
        _if_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, T, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), jnp.int8),
            jax.ShapeDtypeStruct((B, N), jnp.int32),
        ],
        name="if_neuron",
        interpret=interpret,
    )(updates, vth2d)
