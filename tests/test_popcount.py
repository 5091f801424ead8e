"""Popcount-domain CIM MAC: bit identity of the AND+popcount datapath
(jnp reference, interpret-mode Pallas kernels, single-launch mega cascade)
against the packed oracles (``cim_matmul_packed_ref``, which unpacks the
wire and runs the dense MAC) and the unpacked functional plane.  Nothing
here is approximate — every assert is exact int32 / uint32 equality."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.kernels.cim_popcount import ops as pop_ops
from repro.kernels.cim_popcount import ref as pop_ref
from repro.kernels.cim_popcount.kernel import VTH_NEVER_FIRE


def _operands(key, B, K, N, p_spike=0.4):
    s = jax.random.bernoulli(key, p_spike, (B, K))
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(
        jnp.int8
    )
    return s, w, packing.pack_spikes(s), packing.pack_weight_planes(w)


# ----------------------------------------------------------------------- #
# MAC: ref and interpret kernel vs packed oracle + dense oracle
# ----------------------------------------------------------------------- #
# odd K (non-multiple of 32/128) and odd B exercise both padding terms of
# the identity 2*popcount(s & w) - popcount(s); kernel rows need
# N % min(128, N) == 0 (the kernel's neuron-block contract).
MAC_SHAPES = [(8, 128, 128), (37, 100, 10), (64, 384, 256), (200, 70, 32),
              (5, 33, 64)]


@pytest.mark.parametrize("B,K,N", MAC_SHAPES)
def test_popcount_matmul_bit_exact(B, K, N):
    s, w, p, planes = _operands(jax.random.PRNGKey(B * 31 + K + N), B, K, N)
    oracle = pop_ref.cim_matmul_packed_ref(p, w)
    np.testing.assert_array_equal(
        np.asarray(oracle), np.asarray(pop_ref.cim_matmul_ref(s, w))
    )
    ref = pop_ops.cim_popcount_matmul(p, planes, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(oracle))
    out = pop_ops.cim_popcount_matmul(
        p, planes, use_kernel=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_popcount_matmul_property(seed):
    """Random B/K/N incl. single-word K and tiny N — ref path only (every
    shape is legal there), against the dense jnp oracle."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 64))
    K = int(rng.integers(1, 300))
    N = int(rng.integers(1, 96))
    s, w, p, planes = _operands(
        jax.random.PRNGKey(seed), B, K, N, float(rng.uniform(0.05, 0.95))
    )
    np.testing.assert_array_equal(
        np.asarray(pop_ops.cim_popcount_ref(p, planes)),
        np.asarray(pop_ref.cim_matmul_ref(s, w)),
    )


@pytest.mark.parametrize("pack_output", [True, False])
@pytest.mark.parametrize("B,K,N", [(8, 128, 128), (37, 100, 64), (21, 96, 32)])
def test_popcount_layer_fused_fire_bit_exact(B, K, N, pack_output):
    """Fused MAC + IF fire (+ re-pack) == the packed fused-layer oracle."""
    key = jax.random.PRNGKey(B + K * 3 + N)
    s, w, p, planes = _operands(key, B, K, N)
    vth = jax.random.randint(jax.random.fold_in(key, 2), (N,), -9, 9, jnp.int32)
    oracle = pop_ref.esam_layer_packed_ref(p, w, vth, pack_output=pack_output)
    ref = pop_ops.esam_layer_popcount(
        p, planes, vth, pack_output=pack_output, use_kernel=False
    )
    out = pop_ops.esam_layer_popcount(
        p, planes, vth, pack_output=pack_output, use_kernel=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


# ----------------------------------------------------------------------- #
# mega cascade: one launch == per-tile packed cascade == functional chain
# ----------------------------------------------------------------------- #
CASCADE_TOPOS = [(768, 256, 256, 10), (300, 128, 96, 10), (100, 64, 32),
                 (256, 128)]


def _cascade_operands(key, topo):
    planes, vth = [], []
    for i in range(len(topo) - 1):
        k = jax.random.fold_in(key, i)
        w = jax.random.bernoulli(k, 0.5, (topo[i], topo[i + 1])).astype(jnp.int8)
        planes.append(packing.pack_weight_planes(w))
        vth.append(jax.random.randint(
            jax.random.fold_in(k, 1), (topo[i + 1],), -10, 10, jnp.int32))
    return planes, vth


def _oracle_cascade(packed, planes, vth, topo):
    """Per-tile packed-oracle cascade: unpack, dense MAC, fire, re-pack."""
    p = packed
    fired = []
    for t in range(len(topo) - 2):
        w = packing.unpack_weight_planes(planes[t], topo[t])
        p = pop_ref.esam_layer_packed_ref(p, w, vth[t])
        fired.append(p)
    w = packing.unpack_weight_planes(planes[-1], topo[-2])
    return pop_ref.cim_matmul_packed_ref(p, w), fired


@pytest.mark.parametrize("topo", CASCADE_TOPOS)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_mega_cascade_bit_exact(topo, use_kernel):
    key = jax.random.PRNGKey(sum(topo))
    planes, vth = _cascade_operands(key, topo)
    s = jax.random.bernoulli(jax.random.fold_in(key, 7), 0.35, (37, topo[0]))
    p = packing.pack_spikes(s)
    want, want_fired = _oracle_cascade(p, planes, vth, topo)
    w_stack, vth_stack = pop_ops.stack_cascade_operands(planes, vth, topo)
    logits, fired = pop_ops.esam_cascade_popcount(
        p, w_stack, vth_stack, topology=topo,
        use_kernel=use_kernel, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    assert len(fired) == len(want_fired)
    for a, b in zip(fired, want_fired):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mega_cascade_batch_off_grid_and_single_row():
    """Batch padding rows are dead weight, never aliased into real rows."""
    topo = (128, 64, 10)
    key = jax.random.PRNGKey(3)
    planes, vth = _cascade_operands(key, topo)
    w_stack, vth_stack = pop_ops.stack_cascade_operands(planes, vth, topo)
    for B in (1, 5, 129):
        s = jax.random.bernoulli(jax.random.fold_in(key, B), 0.5, (B, 128))
        p = packing.pack_spikes(s)
        want, _ = _oracle_cascade(p, planes, vth, topo)
        logits, _ = pop_ops.esam_cascade_popcount(
            p, w_stack, vth_stack, topology=topo,
            use_kernel=True, interpret=True,
        )
        assert logits.shape == (B, 10)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))


def test_cascade_geometry_and_operand_stacking():
    """Padding contract: lane-aligned widths, real word counts, zero plane
    padding (AND-dead) and VTH_NEVER_FIRE threshold padding (silent)."""
    topo = (300, 128, 96, 10)
    g = pop_ops.cascade_geometry(topo)
    assert g["n_tiles"] == 3
    assert g["n_pad"] == (128, 128, 128)
    assert g["w_words"] == (10, 4, 3)
    assert g["n_max_pad"] == 128 and g["w_max"] == 10
    planes, vth = _cascade_operands(jax.random.PRNGKey(9), topo)
    w_stack, vth_stack = pop_ops.stack_cascade_operands(planes, vth, topo)
    assert w_stack.shape == (3, 128, 10) and w_stack.dtype == jnp.uint32
    assert vth_stack.shape == (2, 128)
    # real region round-trips; padding is zero / never-fire
    for t in range(3):
        n_t, kw_t = topo[t + 1], g["w_words"][t]
        np.testing.assert_array_equal(
            np.asarray(w_stack[t, :n_t, :kw_t]), np.asarray(planes[t]))
        assert not np.asarray(w_stack[t, n_t:, :]).any()
        assert not np.asarray(w_stack[t, :, kw_t:]).any()
    np.testing.assert_array_equal(np.asarray(vth_stack[0, :128]),
                                  np.asarray(vth[0]))
    np.testing.assert_array_equal(np.asarray(vth_stack[1, :96]),
                                  np.asarray(vth[1]))
    assert (np.asarray(vth_stack[1, 96:]) == VTH_NEVER_FIRE).all()


def test_vth_never_fire_is_unreachable():
    """No binary MAC can reach the padding threshold: |V| <= K << 2^30."""
    assert VTH_NEVER_FIRE > 2**20  # far beyond any supported fan-in


def test_popcount_dispatch_defaults_to_backend():
    """use_kernel=None routes to the jnp reference off-TPU (and the real
    kernel on TPU) — same contract as kernels/arbiter."""
    key = jax.random.PRNGKey(5)
    s, w, p, planes = _operands(key, 8, 128, 128)
    out = pop_ops.cim_popcount_matmul(p, planes)  # use_kernel=None
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pop_ref.cim_matmul_ref(s, w)))
