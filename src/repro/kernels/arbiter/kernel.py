"""Pallas TPU kernel: p-port fixed-priority spike arbiter, whole drain.

Hardware mapping (DESIGN.md §2): the paper's 1-port arbiter is a fixed
priority encoder; p ports are p cascaded encoders (Fig 4).  The sequential
grant-and-mask cascade is re-expressed as *rank selection*, which yields
bit-identical grants without a per-cycle loop:

    rank[i]   = inclusive-prefix-count of requests up to lane i, minus 1
    cycle[i]  = rank[i] // p                  the cycle lane i is granted on
    grant_k   = request & (rank == c*p + k)   on port k at cycle c

``port_schedule`` evaluates the whole drain of a block of row groups in one
launch: the prefix count is one matmul against an upper-triangular ones
matrix on the MXU, and the per-cycle grant counts follow in closed form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pad_dim_to, round_up

_SUBBLOCK = 32  # row groups are whole 32-wide base priority encoders


def _port_schedule_kernel(req_ref, cycle_ref, counts_ref, *, ports: int, n_cycles: int):
    """Rank + schedule + cycle-keyed segment counts, fused in VMEM.

    One grid step covers a block of row groups.  The inclusive prefix count
    is one matmul against the upper-triangular ones matrix on the MXU
    (``{0,1}`` operands and counts <= W are exact in bf16 / f32); on top of
    the rank we evaluate the *whole* drain in closed form — grant cycle
    ``rank // p`` per lane — instead of one arbitration round, and
    accumulate the per-cycle grant counts (the segment histogram) without
    leaving VMEM.
    """
    r = req_ref[...]                              # int32 [bg, W]
    bg, w = r.shape
    # --- prefix count: rank[i] = sum_{k <= i} r[k] - 1 --------------------
    k = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    upper = (k <= i).astype(jnp.bfloat16)
    incl = jnp.dot(r.astype(jnp.bfloat16), upper,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    rank = incl - 1
    # --- closed-form schedule: grant cycle per lane -----------------------
    cycle_ref[...] = jnp.where(r == 1, rank // ports, n_cycles)
    # --- segment accumulation: grants per cycle ---------------------------
    # Cycle c serves ranks [c*p, (c+1)*p), so its grant count is
    # clip(popcount - c*p, 0, p): the histogram needs no per-lane scatter.
    pop = incl[:, w - 1:]                                  # [bg, 1] group popcount
    cid = jax.lax.broadcasted_iota(jnp.int32, (bg, n_cycles), 1)
    counts_ref[...] = jnp.clip(pop - cid * ports, 0, ports)


@functools.partial(jax.jit, static_argnames=("ports", "block_g", "interpret"))
def port_schedule(
    requests: jax.Array,   # {0,1}[N, W] — W = 128 row-group width
    *,
    ports: int = 4,
    block_g: int = 256,
    interpret: bool | None = None,
):
    """Closed-form drain schedule for N independent row groups (full drain in
    one kernel launch — no per-cycle loop).

    Row groups are zero-padded to a multiple of the block (a multiple of
    the 8-row sublane tile); padded groups request nothing and are dropped.
    Returns (cycle_of int32[N, W], counts int32[N, C]) with C = ceil(W/p);
    semantics match ``repro.kernels.arbiter.ref.port_schedule_ref``.
    """
    if interpret is None:
        interpret = default_interpret()
    N, W = requests.shape
    assert W % _SUBBLOCK == 0, f"row-group width {W} must be a multiple of {_SUBBLOCK}"
    n_cycles = -(-W // ports)
    bg = min(block_g, round_up(max(N, 1), 8))
    n_pad = round_up(max(N, 1), bg)
    req = pad_dim_to(requests.astype(jnp.int32), n_pad, 0)
    cycle_of, counts = pl.pallas_call(
        functools.partial(_port_schedule_kernel, ports=ports, n_cycles=n_cycles),
        grid=(n_pad // bg,),
        in_specs=[pl.BlockSpec((bg, W), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bg, W), lambda i: (i, 0)),
            pl.BlockSpec((bg, n_cycles), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, W), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, n_cycles), jnp.int32),
        ],
        name="port_schedule",
        interpret=interpret,
    )(req)
    return cycle_of[:N], counts[:N]
