"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref oracles,
sweeping shapes/dtypes (deliverable (c)).  Every kernel here is one a chip
path dispatches; tests/test_tpu_compile.py compiles each for a v5e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.kernels.arbiter import ops as arb_ops
from repro.kernels.cim_popcount import ops as pop_ops
from repro.kernels.cim_popcount import ref as pop_ref
from repro.kernels.stdp import ops as stdp_ops

# ----------------------------------------------------------------------- #
# cim_popcount_matmul / esam_layer_popcount vs the dense binary-MAC oracle
# ----------------------------------------------------------------------- #
SHAPES = [(8, 128, 128), (128, 128, 256), (64, 384, 128), (256, 256, 384)]
SPIKE_DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8, jnp.bool_]


def _popcount_matmul(s, w, **blocks):
    """The popcount MAC kernel (interpret mode) on the packed operands."""
    return pop_ops.cim_popcount_matmul(
        packing.pack_spikes(s != 0), packing.pack_weight_planes(w),
        use_kernel=True, interpret=True, **blocks)


@pytest.mark.parametrize("B,K,N", SHAPES)
@pytest.mark.parametrize("sdt", SPIKE_DTYPES)
def test_cim_matmul_matches_ref(B, K, N, sdt):
    key = jax.random.PRNGKey(B + K + N)
    s = jax.random.bernoulli(key, 0.4, (B, K)).astype(sdt)
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    out = _popcount_matmul(s, w)
    ref = pop_ref.cim_matmul_ref(s, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("B,K,N", SHAPES[:2])
@pytest.mark.parametrize("blocks", [(128, 128), (8, 64), (64, 128)])
def test_cim_matmul_block_shape_sweep(B, K, N, blocks):
    """Batch and neuron blocks; the whole packed K extent is one block."""
    bb, bn = blocks
    key = jax.random.PRNGKey(7)
    s = jax.random.bernoulli(key, 0.3, (B, K)).astype(jnp.float32)
    w = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (K, N)).astype(jnp.int8)
    out = _popcount_matmul(s, w, block_b=bb, block_n=bn)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pop_ref.cim_matmul_ref(s, w)))


@pytest.mark.parametrize("B,K,N", SHAPES[:3])
def test_esam_layer_fused_fire(B, K, N):
    key = jax.random.PRNGKey(11)
    s = jax.random.bernoulli(key, 0.5, (B, K)).astype(jnp.float32)
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    vth = jax.random.randint(jax.random.fold_in(key, 2), (N,), -9, 9, jnp.int32)
    out = pop_ops.esam_layer_popcount(
        packing.pack_spikes(s), packing.pack_weight_planes(w), vth,
        pack_output=False, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pop_ref.esam_layer_ref(s, w, vth)))


def test_cim_matmul_extreme_inputs():
    # all-zero spikes, all-one spikes, all-one weights
    for sval, wval in [(0, 0), (0, 1), (1, 0), (1, 1)]:
        s = jnp.full((8, 128), sval, jnp.float32)
        w = jnp.full((128, 128), wval, jnp.int8)
        out = _popcount_matmul(s, w)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(pop_ref.cim_matmul_ref(s, w)))


# ----------------------------------------------------------------------- #
# arbiter
# ----------------------------------------------------------------------- #
def _first_cycle(req, ports):
    """One arbiter clock cycle read off the ``port_schedule`` kernel: the
    lanes it grants at cycle 0, port k taking the k-th in priority order.
    Returns (grants bool[G, p, W], remaining bool[G, W], valid bool[G, p])."""
    cycle_of, counts = arb_ops.port_schedule_kernel(req, ports=ports, interpret=True)
    req = np.asarray(req, bool)
    first = np.asarray(cycle_of) == 0
    grants = np.zeros((req.shape[0], ports, req.shape[1]), bool)
    for g, row in enumerate(first):
        for k, lane in enumerate(np.flatnonzero(row)):
            grants[g, k, lane] = True
    valid = np.arange(ports)[None, :] < np.asarray(counts)[:, :1]
    return grants, req & ~first, valid


@pytest.mark.parametrize("ports", [1, 2, 3, 4])
@pytest.mark.parametrize("G,W", [(8, 128), (16, 128), (8, 256), (24, 64)])
def test_arbiter_kernel_matches_ref(ports, G, W):
    key = jax.random.PRNGKey(ports * 100 + G)
    req = jax.random.bernoulli(key, 0.3, (G, W)).astype(jnp.int8)
    g, rem, val = _first_cycle(req, ports)
    g2, rem2, val2 = arb_ops.arbiter_ref(req, ports)
    np.testing.assert_array_equal(g, np.asarray(g2, bool))
    np.testing.assert_array_equal(rem, np.asarray(rem2, bool))
    np.testing.assert_array_equal(val, np.asarray(val2, bool))


@given(data=st.data())
@settings(max_examples=25, deadline=None)
def test_arbiter_kernel_property(data):
    """Property sweep vs the hardware cascade oracle, random densities."""
    G = data.draw(st.sampled_from([8, 16]))
    density = data.draw(st.floats(0.0, 1.0))
    ports = data.draw(st.integers(1, 4))
    seed = data.draw(st.integers(0, 2**16))
    req = jax.random.bernoulli(jax.random.PRNGKey(seed), density, (G, 128)).astype(jnp.int8)
    g, rem, val = _first_cycle(req, ports)
    for row in range(G):
        g_ref, rem_ref, val_ref = arb_ops.priority_grants_oracle(
            np.asarray(req[row], bool), ports
        )
        np.testing.assert_array_equal(g[row], g_ref)
        np.testing.assert_array_equal(rem[row], rem_ref)
        np.testing.assert_array_equal(val[row], val_ref)


@pytest.mark.parametrize("ports", [1, 2, 3, 4])
@pytest.mark.parametrize("N,W", [(8, 128), (16, 128), (6, 128), (5, 128), (8, 256)])
def test_port_schedule_kernel_matches_ref(ports, N, W):
    key = jax.random.PRNGKey(ports * 100 + N + W)
    req = jax.random.bernoulli(key, 0.4, (N, W)).astype(jnp.int8)
    c, n = arb_ops.port_schedule_kernel(req, ports=ports, interpret=True)
    c2, n2 = arb_ops.port_schedule_ref(req, ports)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n2))


@given(data=st.data())
@settings(max_examples=25, deadline=None)
def test_port_schedule_matches_cascade_oracle(data):
    """The closed-form schedule is the cascade's grant order: replaying the
    priority-encoder oracle cycle by cycle must land every grant on the cycle
    the schedule assigned it."""
    ports = data.draw(st.integers(1, 4))
    density = data.draw(st.floats(0.0, 1.0))
    seed = data.draw(st.integers(0, 2**16))
    req = jax.random.bernoulli(jax.random.PRNGKey(seed), density, (4, 128))
    cycle_of, counts = arb_ops.port_schedule(req.astype(jnp.int8), ports=ports,
                                             use_kernel=False)
    n_cycles = counts.shape[-1]
    for g in range(4):
        r = np.asarray(req[g], bool)
        for cyc in range(n_cycles):
            grants, r, valid = arb_ops.priority_grants_oracle(r, ports)
            granted = np.flatnonzero(grants.any(axis=0))
            assert int(np.asarray(counts)[g, cyc]) == int(valid.sum())
            np.testing.assert_array_equal(
                np.asarray(cycle_of)[g, granted], cyc)
        assert not r.any()


# ----------------------------------------------------------------------- #
# compile-path (non-interpret) coverage — skip gracefully where the backend
# cannot compile Pallas TPU kernels (e.g. plain CPU CI)
# ----------------------------------------------------------------------- #
def _compiled_or_skip(fn):
    try:
        return jax.block_until_ready(fn())
    except Exception as e:  # noqa: BLE001 — Mosaic/XLA raises backend-specific types
        if jax.default_backend() == "tpu":
            raise  # TPU is the dispatch target of ops.port_schedule — fail loudly
        pytest.skip(
            f"non-interpret pallas unsupported on {jax.default_backend()}: "
            f"{type(e).__name__}")


@pytest.mark.parametrize("ports", [1, 4])
def test_port_schedule_kernel_compiled(ports):
    req = jax.random.bernoulli(jax.random.PRNGKey(ports), 0.5, (8, 128)).astype(jnp.int8)
    c, n = _compiled_or_skip(
        lambda: arb_ops.port_schedule_kernel(req, ports=ports, interpret=False))
    c2, n2 = arb_ops.port_schedule_ref(req, ports)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n2))


# ----------------------------------------------------------------------- #
# stdp: the column write the epoch kernel is held to (its oracle's step)
# ----------------------------------------------------------------------- #
def test_stdp_kernel_agrees_with_core_learning_rule():
    """Column writes of ``stdp_column_event_ref`` (transposed layout), one
    per post event, == core ``stdp_update_from_uniforms`` (row-major rule).
    The epoch kernel is tested against a scan of these column writes
    (tests/test_online_plane.py)."""
    from repro.core.esam import learning as core_learning

    key = jax.random.PRNGKey(5)
    n_in, n_out = 256, 128
    bits = jax.random.bernoulli(key, 0.5, (n_in, n_out)).astype(jnp.int8)
    pre = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n_in,))
    post = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.3, (n_out,))
    # core rule with fixed uniforms == column writes with the same uniforms
    k1, k2 = jax.random.split(jax.random.fold_in(key, 3))
    u_pot = jax.random.uniform(k1, (n_in, n_out))
    u_dep = jax.random.uniform(k2, (n_in, n_out))
    ref = core_learning.stdp_update_from_uniforms(bits, pre, post, u_pot, u_dep, 0.25, 0.1)
    out = jax.lax.fori_loop(
        0, n_out,
        lambda c, b: stdp_ops.stdp_column_event_ref(
            b, c, post[c], pre, u_pot[:, c], u_dep[:, c], 0.25, 0.1),
        bits.T)
    np.testing.assert_array_equal(np.asarray(out.T), np.asarray(ref))


# ----------------------------------------------------------------------- #
# kernel-vs-core end-to-end
# ----------------------------------------------------------------------- #
def test_kernel_layer_equals_core_functional_tile():
    from repro.core.esam import tile as core_tile

    key = jax.random.PRNGKey(21)
    s = jax.random.bernoulli(key, 0.45, (64, 256))
    bits = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (256, 128)).astype(jnp.int8)
    vth = jax.random.randint(jax.random.fold_in(key, 2), (128,), -8, 8, jnp.int32)
    spikes_k = pop_ops.esam_layer_popcount(
        packing.pack_spikes(s), packing.pack_weight_planes(bits), vth,
        pack_output=False, use_kernel=True, interpret=True)
    spikes_c, _ = core_tile.functional_tile(bits, s, vth)
    np.testing.assert_array_equal(np.asarray(spikes_k, bool), np.asarray(spikes_c))
