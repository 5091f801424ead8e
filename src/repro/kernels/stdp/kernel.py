"""Pallas TPU kernel: transposed-port online-learning update (stochastic STDP).

Hardware mapping (Sec 3.2 / 4.4.1): the transposable column RW port makes
"update every synapse of one learning neuron" a contiguous access.  On TPU the
"port" is a *layout* decision: weights are stored transposed ([N_out, N_in],
one learning neuron's synapses = one contiguous row of lanes), so the learning
write is a dense row-masked VMEM update instead of a strided scatter — the
memory-system analogue of the dedicated column port.

The stochastic potentiate/depress draws ([16]) enter as precomputed uniforms
so the kernel is deterministic and bit-exact against ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret


def _stdp_kernel(bits_ref, pre_ref, post_ref, upot_ref, udep_ref, out_ref,
                 *, p_pot: float, p_dep: float):
    bits = bits_ref[...]
    pre = pre_ref[...].astype(bool)        # [1, bn_in]
    post = post_ref[...].astype(bool)      # [bm_out, 1]
    potentiate = post & pre & (upot_ref[...] < p_pot)
    depress = post & ~pre & (udep_ref[...] < p_dep)
    out_ref[...] = jnp.where(potentiate, 1, jnp.where(depress, 0, bits)).astype(bits.dtype)


@functools.partial(
    jax.jit, static_argnames=("p_pot", "p_dep", "block_out", "block_in", "interpret")
)
def stdp_update(
    bits_t: jax.Array,   # {0,1}[N_out, N_in] transposed weight layout
    pre: jax.Array,      # {0,1}[N_in]
    post: jax.Array,     # {0,1}[N_out]
    u_pot: jax.Array,    # float32[N_out, N_in]
    u_dep: jax.Array,    # float32[N_out, N_in]
    *,
    p_pot: float,
    p_dep: float,
    block_out: int = 8,
    block_in: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns the updated transposed weight bits, int8[N_out, N_in]."""
    if interpret is None:
        interpret = default_interpret()
    n_out, n_in = bits_t.shape
    bm, bn = min(block_out, n_out), min(block_in, n_in)
    assert n_out % bm == 0 and n_in % bn == 0
    grid = (n_out // bm, n_in // bn)
    return pl.pallas_call(
        functools.partial(_stdp_kernel, p_pot=p_pot, p_dep=p_dep),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_out, n_in), bits_t.dtype),
        name="stdp_update",
        interpret=interpret,
    )(bits_t, pre[None, :], post[:, None], u_pot, u_dep)


def _column_event_kernel(idx_ref, bits_ref, pre_ref, upot_ref, udep_ref, out_ref,
                         *, p_pot: float, p_dep: float):
    bits = bits_ref[...].astype(jnp.int32)     # [N_out, N_in], whole tile
    pre = pre_ref[...] != 0                    # [1, N_in]
    row = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    event = (row == idx_ref[0]) & (idx_ref[1] > 0)
    potentiate = pre & (upot_ref[...] < p_pot)
    depress = jnp.logical_not(pre) & (udep_ref[...] < p_dep)
    new = jnp.where(potentiate, 1, jnp.where(depress, 0, bits))
    out_ref[...] = jnp.where(event, new, bits).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p_pot", "p_dep", "interpret"))
def stdp_column_event(
    bits_t: jax.Array,   # {0,1}[N_out, N_in] transposed weight layout
    col: jax.Array,      # int32[] — the learning neuron (one column port access)
    apply: jax.Array,    # bool[] — gate; the write is suppressed when False
    pre: jax.Array,      # {0,1}[N_in] pre-synaptic activity trace
    u_pot: jax.Array,    # float32[N_in]
    u_dep: jax.Array,    # float32[N_in]
    *,
    p_pot: float,
    p_dep: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Column write: update ONE learning neuron's synapses in place.

    The learning neuron (a scalar-prefetched row index into the
    transposed-resident layout) selects the one row that changes; the block
    is the whole ``[N_out, N_in]`` readout tile (a few KB at the paper's
    widths), since a one-row block breaks the TPU's 8-sublane tiling.  The
    output aliases ``bits_t``'s buffer (``input_output_aliases``) — the TPU
    rendering of the 2x4-cycle transposable-port column RMW (Sec 4.4.1).
    """
    if interpret is None:
        interpret = default_interpret()
    n_out, n_in = bits_t.shape
    idx = jnp.stack([jnp.asarray(col, jnp.int32), apply.astype(jnp.int32)])
    tile = pl.BlockSpec((n_out, n_in), lambda j, idx: (0, 0))
    row = pl.BlockSpec((1, n_in), lambda j, idx: (0, 0))
    return pl.pallas_call(
        functools.partial(_column_event_kernel, p_pot=p_pot, p_dep=p_dep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[tile, row, row, row],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct((n_out, n_in), bits_t.dtype),
        input_output_aliases={1: 0},   # bits_t buffer is the output buffer
        name="stdp_column_event",
        interpret=interpret,
    )(idx, bits_t, pre.astype(jnp.int8)[None, :], u_pot[None, :], u_dep[None, :])
