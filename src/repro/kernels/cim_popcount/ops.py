"""Jit'd public wrappers for the popcount-domain CIM MAC kernels.

Callers hand in natural shapes (B samples, K pre-neurons packed into
ceil(K/32) words, N post neurons) and the wrappers zero-pad the batch to a
block multiple — exact for the binary MAC in both popcount terms, since a
silent spike contributes nothing whatever the stored bit.  Dispatch is the
same as ``kernels/arbiter``'s:
``use_kernel=None`` runs the Pallas kernel only where it compiles natively
(TPU) and the vectorized popcount reference elsewhere (on CPU the reference
beats both the interpret-mode kernel and an unpack + BLAS round trip).  The
two paths are bit-identical int32 (tests/test_popcount.py).

``esam_cascade_popcount`` is the single-launch mega kernel: the caller
pre-stacks every tile's weight planes and thresholds once
(``stack_cascade_operands``, done at plan-build time by ``EsamPlan``) and
each call runs the whole cascade — MAC, IF fire, re-pack, next tile — in one
``pallas_call`` with double-buffered weight-plane DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.packing import LANE_BITS
from repro.kernels.common import default_interpret, pad_dim_to, round_up
from repro.kernels.cim_popcount import kernel as knl
from repro.kernels.cim_popcount.ref import (  # noqa: F401  (re-export)
    cim_popcount_ref,
    esam_cascade_popcount_ref,
    esam_layer_popcount_ref,
)

__all__ = [
    "cim_popcount_matmul",
    "esam_layer_popcount",
    "esam_cascade_popcount",
    "stack_cascade_operands",
    "cascade_geometry",
    "cim_popcount_ref",
    "esam_layer_popcount_ref",
    "esam_cascade_popcount_ref",
]

#: lane alignment for per-tile output widths inside the mega kernel
_COL_PAD = 128


def _use_kernel(use_kernel: bool | None) -> bool:
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return use_kernel


def _prep(packed, planes, block_b, block_n):
    """Pad the batch to a block multiple; returns operands + grid geometry.

    The whole packed K extent is one block (both operands' word axes are
    full array dims), so only the batch is padded.  Planes uint32[N, kw] go
    word-major (``[kw, N]``) for the kernel: word ``j`` of every neuron is
    then one sublane row.
    """
    B, kw = packed.shape
    N, kw2 = planes.shape
    assert kw == kw2, (packed.shape, planes.shape)
    bm = min(block_b, B)
    b_pad = round_up(B, bm)
    p = pad_dim_to(packed, b_pad, 0)
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    return p, planes.T, (B, b_pad, kw, N, bm, bn)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_n", "use_kernel", "interpret"),
)
def cim_popcount_matmul(
    packed: jax.Array,   # uint32[B, ceil(K/32)] bit-packed spikes
    planes: jax.Array,   # uint32[N, ceil(K/32)] weight bit planes
    *,
    block_b: int = 128,
    block_n: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """V_mem int32[B, N] = 2*popcount(s & w) - popcount(s); nothing unpacks."""
    if not _use_kernel(use_kernel):
        return cim_popcount_ref(packed, planes)
    if interpret is None:
        interpret = default_interpret()
    p, w, (B, b_pad, kw, N, bm, bn) = _prep(packed, planes, block_b, block_n)
    out = pl.pallas_call(
        knl.popcount_mac_kernel,
        grid=(b_pad // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((kw, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b_pad, N), jnp.int32),
        name="cim_popcount_matmul",
        interpret=interpret,
    )(p, w)
    return out[:B]


@functools.partial(
    jax.jit,
    static_argnames=("pack_output", "block_b", "use_kernel", "interpret"),
)
def esam_layer_popcount(
    packed: jax.Array,   # uint32[B, ceil(K/32)]
    planes: jax.Array,   # uint32[N, ceil(K/32)]
    vth: jax.Array,      # int32[N]
    *,
    pack_output: bool = True,
    block_b: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused popcount tile: MAC + IF fire (+ output re-pack), V_mem in VMEM.

    One block spans all N output neurons, so the packed output block
    (bm, N/32) is a full-width row of words.
    """
    if not _use_kernel(use_kernel):
        return esam_layer_popcount_ref(packed, planes, vth, pack_output=pack_output)
    if interpret is None:
        interpret = default_interpret()
    N = planes.shape[0]
    assert vth.shape == (N,), (vth.shape, N)
    p, w, (B, b_pad, kw, N, bm, _) = _prep(packed, planes, block_b, N)
    if pack_output:
        assert N % LANE_BITS == 0, N
        out_shape = jax.ShapeDtypeStruct((b_pad, N // LANE_BITS), jnp.uint32)
    else:
        out_shape = jax.ShapeDtypeStruct((b_pad, N), jnp.int8)
    out = pl.pallas_call(
        functools.partial(knl.popcount_fire_kernel, pack_output=pack_output),
        grid=(b_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, kw), lambda i: (i, 0)),
            pl.BlockSpec((kw, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, out_shape.shape[1]), lambda i: (i, 0)),
        out_shape=out_shape,
        name="esam_layer_popcount",
        interpret=interpret,
    )(p, w, vth[None, :].astype(jnp.int32))
    return out[:B]


# --------------------------------------------------------------------- #
# single-launch mega-kernel cascade
# --------------------------------------------------------------------- #
def cascade_geometry(topology: tuple[int, ...]) -> dict:
    """Static padding geometry shared by the stacker and the mega kernel.

    Per tile t (K_t = topology[t] -> N_t = topology[t+1]):
      n_pad[t]    output width padded to the 128-lane grid
      w_words[t]  real input words ceil(K_t/32) — fired bits past a tile's
                  real width never fire (vth padding), so words past this
                  are provably zero and the AND loop skips them.
    """
    n_tiles = len(topology) - 1
    assert n_tiles >= 1, topology
    n_pad = tuple(round_up(n, _COL_PAD) for n in topology[1:])
    w_words = tuple(packing.packed_width(k) for k in topology[:-1])
    return {
        "n_tiles": n_tiles,
        "n_pad": n_pad,
        "w_words": w_words,
        "n_max_pad": max(n_pad),
        "w_max": max(w_words),
    }


def stack_cascade_operands(weight_planes, vth, topology):
    """Stack per-tile planes/thresholds into the mega kernel's DMA slabs.

    weight_planes: per tile uint32[N_t, ceil(K_t/32)]; vth: per tile
    int32[N_t].  Returns (w_stack uint32[n_tiles, n_max_pad, w_max],
    vth_stack int32[n_hidden, n_max_pad]).  Plane padding is zero (AND-dead);
    vth padding is ``VTH_NEVER_FIRE`` so padded neurons stay silent and the
    re-packed inter-tile plane carries only real bits.  Built once per
    parameter set at plan-build time, never per call.
    """
    g = cascade_geometry(tuple(topology))
    n_tiles, n_max_pad, w_max = g["n_tiles"], g["n_max_pad"], g["w_max"]
    assert len(weight_planes) == n_tiles, (len(weight_planes), n_tiles)
    w_stack = jnp.stack([
        pad_dim_to(pad_dim_to(p, n_max_pad, 0), w_max, 1)
        for p in weight_planes
    ])
    n_hidden = max(n_tiles - 1, 1)
    vth_stack = jnp.full((n_hidden, n_max_pad), knl.VTH_NEVER_FIRE, jnp.int32)
    for t, th in enumerate(vth[: n_tiles - 1]):
        vth_stack = vth_stack.at[t, : th.shape[0]].set(th.astype(jnp.int32))
    return w_stack, vth_stack


@functools.partial(
    jax.jit,
    static_argnames=("topology", "block_b", "use_kernel", "interpret"),
)
def esam_cascade_popcount(
    packed: jax.Array,      # uint32[B, ceil(n_in/32)]
    w_stack: jax.Array,     # uint32[n_tiles, n_max_pad, w_max]
    vth_stack: jax.Array,   # int32[n_hidden, n_max_pad]
    *,
    topology: tuple[int, ...],
    block_b: int = 128,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, tuple]:
    """The whole tile cascade in ONE kernel launch.

    grid = (B/bm,): each program carries its batch block through every tile —
    popcount MAC, IF fire, re-pack — with the fired bitplanes resident in
    VMEM and the next tile's weight slab DMA'd in under the current MAC.
    Returns (logits int32[B, n_cls], fired hidden planes tuple of
    uint32[B, N_t/32]) — bit-identical to the per-tile packed cascade.
    """
    topology = tuple(topology)
    g = cascade_geometry(topology)
    n_tiles = g["n_tiles"]
    for n in topology[1:-1]:
        assert n % LANE_BITS == 0, ("hidden widths must be 32-aligned", topology)
    if not _use_kernel(use_kernel):
        planes = tuple(
            w_stack[t, : topology[t + 1], : g["w_words"][t]]
            for t in range(n_tiles)
        )
        vth = tuple(
            vth_stack[t, : topology[t + 1]] for t in range(n_tiles - 1)
        ) + (None,)
        return esam_cascade_popcount_ref(packed, planes, vth)
    if interpret is None:
        interpret = default_interpret()
    if n_tiles == 1:
        return (
            cim_popcount_matmul(
                packed, w_stack[0, : topology[1], : g["w_words"][0]],
                use_kernel=True, interpret=interpret,
            ),
            (),
        )
    B = packed.shape[0]
    bm = min(block_b, B)
    b_pad = round_up(B, bm)
    p = pad_dim_to(packed, b_pad, 0)
    n_cls_pad = g["n_pad"][-1]
    out_shapes = [jax.ShapeDtypeStruct((b_pad, n_cls_pad), jnp.int32)] + [
        jax.ShapeDtypeStruct((b_pad, g["n_pad"][t] // LANE_BITS), jnp.uint32)
        for t in range(n_tiles - 1)
    ]
    out_specs = [pl.BlockSpec((bm, n_cls_pad), lambda i: (i, 0))] + [
        pl.BlockSpec((bm, g["n_pad"][t] // LANE_BITS), lambda i: (i, 0))
        for t in range(n_tiles - 1)
    ]
    outs = pl.pallas_call(
        functools.partial(
            knl.mega_cascade_kernel, n_pad=g["n_pad"], w_words=g["w_words"]
        ),
        grid=(b_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, g["w_words"][0]), lambda i: (i, 0)),
            pl.BlockSpec(vth_stack.shape, lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((2, g["w_max"], g["n_max_pad"]), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        name="esam_cascade_popcount",
        interpret=interpret,
    )(p, vth_stack, w_stack.swapaxes(1, 2))   # word-major slabs
    logits = outs[0][:B, : topology[-1]]
    fired = tuple(
        outs[1 + t][:B, : packing.packed_width(topology[t + 1])]
        for t in range(n_tiles - 1)
    )
    return logits, fired
