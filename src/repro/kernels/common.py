"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (VMEM BlockSpecs, MXU-aligned tiles) and are validated
on CPU via ``interpret=True`` — the kernel body runs in Python with the same
block schedule, so correctness transfers.
"""

from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def pad_dim_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` of x up to ``size`` (no-op if already there).

    Zero spike bits / zero weight rows are exact padding for the binary CIM
    MAC: a silent spike contributes nothing regardless of the stored bit.
    """
    cur = x.shape[axis]
    if cur == size:
        return x
    assert cur < size, (cur, size)
    import jax.numpy as jnp

    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - cur)
    return jnp.pad(x, widths)
