"""Declarative parameter specs.

Each model family declares its parameters as a pytree of ``ParamSpec`` leaves
(shape + logical axes + init).  From one spec tree we derive:

  * ``init(specs, key)``            — materialized params (smoke tests, examples)
  * ``shape_structs(specs)``        — ShapeDtypeStructs (dry-run: NO allocation)
  * ``logical_axes(specs)``         — same-structure tree of logical-axis tuples
  * ``shardings(specs, rules)``     — NamedShardings for jit in_shardings

This is what lets ``dryrun.py`` lower+compile trillion-parameter configs on a
CPU container: parameters never exist, only their metadata.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | scaled | mamba_a_log | mamba_dt_bias
    scale: Optional[float] = None  # stddev override
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _leaf_init(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "mamba_a_log":
        # Mamba2's default: A = -exp(A_log) with A ~ U[1, 16]
        a = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(spec.dtype)
    if spec.init == "mamba_dt_bias":
        # Mamba2's default: softplus(dt_bias) = dt, log-uniform in [1e-3, 0.1]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3)), 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(spec.dtype)
    std = spec.scale
    if std is None:
        fan_in = spec.shape[0] if len(spec.shape) >= 1 else 1
        if len(spec.shape) >= 2:
            fan_in = int(np.prod(spec.shape[:-1]))
        std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def init(specs, key: jax.Array):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = [_leaf_init(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def shape_structs(specs, rules=None):
    """ShapeDtypeStructs, optionally with shardings attached (for .lower())."""

    def one(s: ParamSpec):
        if rules is None:
            return jax.ShapeDtypeStruct(s.shape, s.dtype)
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rules.sharding(s.axes))

    return jax.tree.map(one, specs, is_leaf=is_spec)


def logical_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def shardings(specs, rules):
    return jax.tree.map(lambda s: rules.sharding(s.axes), specs, is_leaf=is_spec)


def param_count(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs, is_leaf=is_spec))


def param_bytes(specs) -> int:
    return sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree.leaves(specs, is_leaf=is_spec)
    )
