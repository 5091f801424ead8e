"""Pure-jnp oracles for the transposed STDP epoch kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def stdp_column_event_ref(
    bits_t: jax.Array,    # {0,1}[N_out, N_in] transposed weight layout
    col: jax.Array,       # int32[] — index of the learning neuron (one column)
    apply: jax.Array,     # bool[] — gate; identity when False
    pre: jax.Array,       # {0,1}[N_in] pre-synaptic activity trace
    u_pot: jax.Array,     # float[N_in] uniforms for potentiation
    u_dep: jax.Array,     # float[N_in] uniforms for depression
    p_pot: float,
    p_dep: float,
) -> jax.Array:
    """One column event: stochastic STDP applied to a single learning neuron.

    Only row ``col`` of the transposed layout (= one weight column, all
    synapses of one post neuron) may change — the column-port access pattern.
    """
    old = bits_t[col]
    pre_m = pre.astype(bool)
    potentiate = pre_m & (u_pot < p_pot)
    depress = ~pre_m & (u_dep < p_dep)
    new = jnp.where(potentiate, 1, jnp.where(depress, 0, old)).astype(bits_t.dtype)
    new = jnp.where(apply, new, old)
    return bits_t.at[col].set(new)


@functools.partial(jax.jit, static_argnames=("p_pot", "p_dep"))
def column_event_epoch_ref(
    bits_t: jax.Array,    # {0,1}[N_out, N_in] transposed weight layout
    pre: jax.Array,       # bool[batch, N_in] last tile's input spikes
    labels: jax.Array,    # int32[batch]
    key: jax.Array,
    *,
    p_pot: float,
    p_dep: float,
    out_offset: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Oracle of ``stdp_column_event_epoch``: a scan over the samples.

    Per sample: the readout matvec and argmax (of V_mem + ``out_offset``
    when given), the counter-based draws of that sample, and two gated
    column events (``stdp_column_event_ref``) — the teacher row, then the
    wrong winner's pure depression of its active inputs.  Returns (updated
    bits_t, number of column updates).
    """
    from repro.core.esam.learning import column_event_uniforms, readout_vmem

    n_in = bits_t.shape[1]

    def body(bits_t, inp):
        s_i, y_i, i = inp
        vmem = readout_vmem(bits_t, s_i)
        if out_offset is None:
            pred = jnp.argmax(vmem)
        else:
            pred = jnp.argmax(vmem.astype(jnp.float32) + out_offset)
        wrong = pred != y_i
        u_pot, u_dep_t, u_dep_w = column_event_uniforms(key, i, n_in)
        bits_t = stdp_column_event_ref(
            bits_t, y_i, wrong, s_i, u_pot, u_dep_t, p_pot, p_dep)
        # wrong winner: inverted trace, potentiation disabled
        bits_t = stdp_column_event_ref(
            bits_t, pred, wrong, jnp.logical_not(s_i), u_dep_w, u_dep_w,
            0.0, p_dep)
        return bits_t, wrong

    idx = jnp.arange(pre.shape[0], dtype=jnp.int32)
    bits_t, wrong = jax.lax.scan(body, bits_t, (pre.astype(bool), labels, idx))
    return bits_t, 2 * wrong.sum(dtype=jnp.int32)
