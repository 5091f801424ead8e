"""Pallas TPU kernel: a whole online-learning epoch of stochastic STDP.

Hardware mapping (Sec 3.2 / 4.4.1): the transposable column RW port makes
"update every synapse of one learning neuron" a contiguous access.  On TPU the
"port" is a *layout* decision: weights are stored transposed ([N_out, N_in],
one learning neuron's synapses = one contiguous row of lanes), so each
learning write is a dense row-masked VMEM update instead of a strided
scatter — the memory-system analogue of the dedicated column port.

The stochastic potentiate/depress draws ([16]) enter as precomputed uniforms
so the kernel is deterministic and bit-exact against ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, round_up


#: samples per grid step of the epoch kernel: a block of event codes is
#: EPOCH_BLOCK x n_in int32 in VMEM (1 MB at the paper's n_in = 256), and
#: a block of labels fills one 1024-word tile of SMEM
EPOCH_BLOCK = 1024
#: samples per aligned sublane tile of codes, unrolled inside the loop
_GROUP = 8
# bit fields of one sample's event code, per pre-synaptic input
_S, _POT, _DEP_T, _DEP_W = 1, 2, 4, 8


def _column_event_epoch_kernel(labels_ref, bits_ref, codes_ref, off_ref,
                               out_ref, n_ref, *, batch: int, block: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        out_ref[...] = bits_ref[...]
        n_ref[...] = jnp.zeros_like(n_ref)

    off = off_ref[...]                          # f32[n_out, 1], -inf on padding
    bits = out_ref[...].astype(jnp.int32)       # [n_out, n_in], resident
    row = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    row_c = jax.lax.broadcasted_iota(jnp.int32, off.shape, 0)
    first = b * block

    def group(g, carry):
        bits, n = carry
        start = pl.multiple_of(g * _GROUP, _GROUP)
        tile = codes_ref[pl.ds(start, _GROUP), :]
        for k in range(_GROUP):
            c = tile[k:k + 1, :]                # [1, n_in] one sample's codes
            s = c & _S
            vmem = (2 * jnp.sum(bits & s, axis=1, keepdims=True)
                    - jnp.sum(s, axis=1, keepdims=True))
            logit = vmem.astype(jnp.float32) + off
            top = jnp.max(logit, axis=0, keepdims=True)
            pred = jnp.min(jnp.where(logit == top, row_c, bits.shape[0]),
                           axis=0, keepdims=True)   # first index of the max
            y = labels_ref[start + k]
            wrong = (pred != y) & (first + start + k < batch)
            taught = jnp.where((c & _POT) != 0, 1,
                               jnp.where((c & _DEP_T) != 0, 0, bits))
            depressed = jnp.where((c & _DEP_W) != 0, 0, bits)
            bits = jnp.where(wrong & (row == y), taught,
                             jnp.where(wrong & (row == pred), depressed, bits))
            n = n + wrong.astype(jnp.int32)
        return bits, n

    bits, n = jax.lax.fori_loop(0, block // _GROUP, group,
                                (bits, jnp.zeros((1, 1), jnp.int32)))
    out_ref[...] = bits.astype(out_ref.dtype)
    n_ref[...] += 2 * n


@functools.partial(jax.jit, static_argnames=("p_pot", "p_dep", "interpret"))
def stdp_column_event_epoch(
    bits_t: jax.Array,      # {0,1}[N_out, N_in] transposed weight layout
    pre: jax.Array,         # bool[batch, N_in] pre-synaptic traces
    labels: jax.Array,      # int32[batch] teacher neurons
    u_pot: jax.Array,       # float32[batch, N_in] teacher potentiation draws
    u_dep_t: jax.Array,     # float32[batch, N_in] teacher depression draws
    u_dep_w: jax.Array,     # float32[batch, N_in] wrong-winner depression draws
    out_offset: jax.Array,  # float32[N_out] added to the membrane before argmax
    *,
    p_pot: float,
    p_dep: float,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """A whole supervised-STDP epoch in one kernel, samples in order.

    Per sample: V_mem = 2 * (bits . s) - |s| on the resident readout, the
    prediction is the first index of the max of V_mem + ``out_offset``, and
    when it is wrong the teacher row takes the column write of
    ``stdp_column_event_ref`` (potentiate active, depress silent inputs) and the
    predicted row depresses its active inputs.  The draws' comparisons are
    made here, before the kernel, into one event code per input; the kernel
    loops over the samples with the readout held in VMEM across the whole
    grid (its block index is constant, its buffer aliases ``bits_t``).
    Rows padded to 8 sublanes get offset -inf and never win; inputs padded
    to 128 lanes are silent; samples past ``batch`` in the last block write
    nothing.  Returns (updated bits_t, column updates as an int32 scalar).
    """
    if interpret is None:
        interpret = default_interpret()
    n_out, n_in = bits_t.shape
    batch = pre.shape[0]
    s = pre.astype(bool)
    codes = (s.astype(jnp.int32) * _S
             | (s & (u_pot < p_pot)).astype(jnp.int32) * _POT
             | (~s & (u_dep_t < p_dep)).astype(jnp.int32) * _DEP_T
             | (s & (u_dep_w < p_dep)).astype(jnp.int32) * _DEP_W)
    block = min(EPOCH_BLOCK, round_up(max(batch, 1), _GROUP))
    batch_p = round_up(max(batch, 1), block)
    n_out_p, n_in_p = round_up(n_out, 8), round_up(n_in, 128)
    codes = jnp.pad(codes, ((0, batch_p - batch), (0, n_in_p - n_in)))
    labels = jnp.pad(labels.astype(jnp.int32), (0, batch_p - batch))
    off = jnp.pad(out_offset.astype(jnp.float32), (0, n_out_p - n_out),
                  constant_values=-jnp.inf)[:, None]
    bits = jnp.pad(bits_t, ((0, n_out_p - n_out), (0, n_in_p - n_in)))
    resident = pl.BlockSpec((n_out_p, n_in_p), lambda j: (0, 0))
    bits, n = pl.pallas_call(
        functools.partial(_column_event_epoch_kernel, batch=batch, block=block),
        grid=(batch_p // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda j: (j,),
                         memory_space=pltpu.SMEM),
            resident,
            pl.BlockSpec((block, n_in_p), lambda j: (j, 0)),
            pl.BlockSpec((n_out_p, 1), lambda j: (0, 0)),
        ],
        out_specs=[resident, pl.BlockSpec((1, 1), lambda j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bits.shape, bits.dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        input_output_aliases={1: 0},   # the readout's buffer is the output's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="stdp_column_event_epoch",
        interpret=interpret,
    )(labels, bits, codes, off)
    return bits[:n_out, :n_in], n[0, 0]
