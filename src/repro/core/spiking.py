"""SpikingLinear — the paper's idea as an optional LM-framework layer
(beyond-paper, DESIGN.md §Arch-applicability).

ESAM's architectural insight is event-driven selection: only active
(spiking) pre-synaptic rows contribute, weights are ±1 bits, and an arbiter
grants at most p events per cycle.  As an LM ablation layer this becomes a
drop-in binary-activation linear:

  * activations binarize to {0,1} spikes with a straight-through estimator;
  * weights binarize to {-1,+1} (latent-float training, sign forward);
  * an optional *top-p activation arbiter* keeps only the p largest
    pre-activations per token — the software analogue of the port limit,
    giving controllable event sparsity;
  * the forward MAC is exactly the ESAM binary MAC (`cim_matmul_ref`, which
    the `kernels/cim_popcount` kernels reproduce bit for bit), so the layer
    runs on the ESAM TPU plane unchanged.

This layer is ablation-grade (binary nets lose accuracy); it is never used
in the faithful assigned-architecture configs.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec


def spiking_linear_specs(d_in: int, d_out: int) -> dict:
    return {
        "w": ParamSpec((d_in, d_out), ("embed", "mlp"), dtype=jnp.float32),
        "b": ParamSpec((d_out,), ("mlp",), init="zeros", dtype=jnp.float32),
    }


def _ste_spike(x: jax.Array) -> jax.Array:
    """{0,1} spikes with clipped-identity backward."""
    hard = (x >= 0).astype(x.dtype)
    soft = jnp.clip(x * 0.5 + 0.5, 0.0, 1.0)
    return soft + jax.lax.stop_gradient(hard - soft)


def _ste_sign(w: jax.Array) -> jax.Array:
    hard = jnp.where(w >= 0, 1.0, -1.0).astype(w.dtype)
    soft = jnp.clip(w, -1.0, 1.0)
    return soft + jax.lax.stop_gradient(hard - soft)


def top_p_arbiter(x: jax.Array, p: int) -> jax.Array:
    """Keep the p largest entries per row (the port-limit analogue).

    Unlike the hardware arbiter (which serializes over cycles), the LM-layer
    version simply masks: events beyond the p-th largest are dropped, which
    bounds the per-token event count exactly like a p-port tile bounds
    per-cycle row reads.
    """
    if p >= x.shape[-1]:
        return x
    thresh = jax.lax.top_k(x, p)[0][..., -1:]
    return jnp.where(x >= thresh, x, -jnp.inf)


def spiking_linear(
    params: dict, x: jax.Array, *, ports: Optional[int] = None
) -> jax.Array:
    """x: [..., d_in] real -> [..., d_out] real (V_mem-style integer-valued).

    ports: optional top-p event limit applied to the pre-spike activations.
    """
    pre = x
    if ports is not None:
        pre = top_p_arbiter(pre, ports)
    spikes = _ste_spike(pre)
    wb = _ste_sign(params["w"])
    return spikes @ wb + params["b"]


def event_rate(x: jax.Array, *, ports: Optional[int] = None) -> jax.Array:
    """Fraction of active events after arbitration (for sparsity accounting
    against the ESAM cost model: cycles = ceil(events / ports))."""
    pre = top_p_arbiter(x, ports) if ports is not None else x
    return (pre >= 0).mean()
