"""Pallas TPU kernel: LIF step — leak-integrate-fire-reset on resident V_mem.

Hardware mapping (IMPULSE-style fused weight + membrane CIM, Agrawal et al.;
Sec 3.4 / Fig 5 of the source paper): each neuron's m-bit V_mem register
survives *between* timesteps of an event stream, is leaked, accumulates the
cycle's validity-masked port sum, is compared against V_th on R_empty, and on
fire is reset (to zero, or by threshold subtraction) and optionally held
silent for a refractory window.

On TPU the resident register file is the [B, N] membrane tensor the temporal
``lax.scan`` carries: this kernel is the per-step update, one elementwise
VPU pass over (bb, bn) VMEM blocks — leak multiply, integrate add, masked
compare, reset select and refractory count-down all fused so V_mem makes
exactly one HBM round-trip per timestep (the scan keeps even that on-device).
Layout: grid (B/bb, N/bn), thresholds broadcast as a (1, bn) row.

Numerics: with ``leak=0`` every value is an integer carried in float32 and
the kernel is bit-identical to ``lif_step_ref`` on every backend (this is
what the temporal plane's T=1 == packed identity rests on).  With a nonzero
leak the compiler may contract the leak-multiply + integrate-add into one
FMA (single rounding) where the jnp reference rounds twice — agreement is
then to float32 ulp, not bitwise (tested with tolerance).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret
from repro.kernels.lif_step.ref import RESET_MODES


def _lif_kernel(vmem_ref, upd_ref, vth_ref, refrac_ref,
                spikes_ref, vout_ref, rout_ref,
                *, leak: float, reset: str, refractory: int):
    th = vth_ref[...].astype(jnp.float32)
    v = vmem_ref[...] * jnp.float32(1.0 - leak) + upd_ref[...].astype(jnp.float32)
    refrac = refrac_ref[...]
    fired = (v >= th) & (refrac == 0)
    if reset == "zero":
        v_next = jnp.where(fired, jnp.float32(0.0), v)
    else:
        v_next = jnp.where(fired, v - th, v)
    spikes_ref[...] = fired.astype(jnp.int8)
    vout_ref[...] = v_next
    rout_ref[...] = jnp.where(
        fired, jnp.int32(refractory), jnp.maximum(refrac - 1, 0))


@functools.partial(
    jax.jit,
    static_argnames=("leak", "reset", "refractory",
                     "block_b", "block_n", "interpret"),
)
def lif_step(
    vmem: jax.Array,       # float32[B, N]
    contrib: jax.Array,    # int32[B, N]
    vth: jax.Array,        # int32[N]
    refrac: jax.Array,     # int32[B, N]
    *,
    leak: float = 0.0,
    reset: str = "zero",
    refractory: int = 0,
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool | None = None,
):
    """Returns (spikes int8[B, N], vmem' float32[B, N], refrac' int32[B, N])."""
    assert reset in RESET_MODES, (reset, RESET_MODES)
    if interpret is None:
        interpret = default_interpret()
    B, N = vmem.shape
    assert contrib.shape == (B, N) and refrac.shape == (B, N)
    assert vth.shape == (N,), (vth.shape, N)
    # a batch that the sublane block does not divide goes as one full block
    bb = block_b if B % block_b == 0 else B
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    grid = (B // bb, N // bn)
    vth2d = vth[None, :].astype(jnp.int32)
    blk = pl.BlockSpec((bb, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(
            _lif_kernel, leak=leak, reset=reset, refractory=refractory),
        grid=grid,
        in_specs=[
            blk,
            blk,
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            blk,
        ],
        out_specs=[blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), jnp.int8),
            jax.ShapeDtypeStruct((B, N), jnp.float32),
            jax.ShapeDtypeStruct((B, N), jnp.int32),
        ],
        name="lif_step",
        interpret=interpret,
    )(vmem.astype(jnp.float32), contrib, vth2d, refrac)
