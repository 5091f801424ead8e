"""Jit'd public wrapper for the arbiter kernel.

``port_schedule`` is the dispatch point the cycle-accurate plane
(``core.esam.tile``) consumes: the fused Pallas kernel on TPU, the jnp
reference elsewhere (interpret-mode Pallas would only slow the batched
simulator down on CPU, and the two are bit-identical — tested).
"""

from __future__ import annotations

import jax

from repro.kernels.arbiter.kernel import port_schedule as port_schedule_kernel
from repro.kernels.arbiter.ref import (
    arbiter_ref,
    port_schedule_ref,
    priority_grants_oracle,
)


def port_schedule(
    requests: jax.Array,
    *,
    ports: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Closed-form drain schedule for N row groups — see ``port_schedule_ref``.

    ``use_kernel=None`` (default) runs the fused Pallas kernel only when the
    backend compiles it natively (TPU); pass True/False to force either path.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return port_schedule_kernel(requests, ports=ports, interpret=interpret)
    return port_schedule_ref(requests, ports)


__all__ = [
    "arbiter_ref",
    "port_schedule",
    "port_schedule_kernel",
    "port_schedule_ref",
    "priority_grants_oracle",
]
