"""Packed spike plane: wire-format round trips, the popcount kernels'
bit-exactness on packed operands vs the packed and dense jnp oracles, and
the fused multi-tile cascade."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.core.esam import EsamNetwork
from repro.kernels.cim_popcount import ops as pop_ops
from repro.kernels.cim_popcount import ref as pop_ref


# ----------------------------------------------------------------------- #
# pack / unpack round trips
# ----------------------------------------------------------------------- #
@given(
    n=st.integers(1, 300),
    batch=st.integers(1, 8),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=40, deadline=None)
def test_pack_unpack_round_trip(n, batch, seed):
    """unpack(pack(x)) == x for random shapes incl. non-multiple-of-32 n."""
    s = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (batch, n))
    p = packing.pack_spikes(s)
    assert p.dtype == jnp.uint32 and p.shape == (batch, packing.packed_width(n))
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_spikes(p, n)), np.asarray(s, np.int8)
    )


@given(n=st.sampled_from([128, 256, 768]), seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_group_popcount_matches_unpacked_counts(n, seed):
    """Arbiter loads straight off the wire == counts on the unpacked plane."""
    s = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.4, (4, n))
    counts = packing.group_popcount(packing.pack_spikes(s))
    want = np.asarray(s, np.int32).reshape(4, n // 128, 128).sum(-1)
    np.testing.assert_array_equal(np.asarray(counts), want)


@given(n=st.integers(1, 300), seed=st.integers(0, 2**16))
@settings(max_examples=40, deadline=None)
def test_pack_of_unpack_is_identity_on_words(n, seed):
    """pack(unpack(w)) == w when the tail bits beyond n are zero."""
    s = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (4, n))
    p = packing.pack_spikes(s)
    p2 = packing.pack_spikes(packing.unpack_spikes(p, n))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(p))


def test_numpy_and_jnp_packing_are_bit_identical():
    s = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(3), 0.4, (16, 100)))
    np.testing.assert_array_equal(
        packing.pack_spikes_np(s), np.asarray(packing.pack_spikes(jnp.asarray(s)))
    )
    np.testing.assert_array_equal(
        packing.unpack_spikes_np(packing.pack_spikes_np(s), 100),
        s.astype(np.int8),
    )


def test_packed_width_and_nbytes():
    assert packing.packed_width(768) == 24
    assert packing.packed_width(10) == 1
    # >= 8x wire reduction vs the int8 spike plane for 32-aligned widths
    assert packing.packed_nbytes(768) * 8 == 768


# ----------------------------------------------------------------------- #
# weight bit planes (popcount-domain wire format for the weight matrix)
# ----------------------------------------------------------------------- #
@given(
    n_in=st.integers(1, 300),
    n_out=st.integers(1, 40),
    seed=st.integers(0, 2**16),
)
@settings(max_examples=40, deadline=None)
def test_weight_plane_round_trip(n_in, n_out, seed):
    """unpack(pack(W)) == W for random [K, N] incl. non-multiple-of-32 K."""
    w = jax.random.bernoulli(
        jax.random.PRNGKey(seed), 0.5, (n_in, n_out)).astype(jnp.int8)
    planes = packing.pack_weight_planes(w)
    assert planes.dtype == jnp.uint32
    assert planes.shape == (n_out, packing.packed_width(n_in))
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_weight_planes(planes, n_in)), np.asarray(w)
    )


@given(n_in=st.integers(1, 200), seed=st.integers(0, 2**16))
@settings(max_examples=30, deadline=None)
def test_weight_plane_signed_round_trip(n_in, seed):
    """Signed +-1 matrices ride the same planes: bits = (W > 0), and the
    plane round trip reconstructs W exactly via 2b - 1."""
    bits = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.5, (n_in, 24))
    w_signed = 2 * bits.astype(jnp.int32) - 1
    planes = packing.pack_weight_planes((w_signed > 0).astype(jnp.int8))
    back = packing.unpack_weight_planes(planes, n_in, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(2 * back - 1), np.asarray(w_signed)
    )


@pytest.mark.parametrize("n_in", [32, 100, 256])
def test_weight_plane_all_zero_and_all_one(n_in):
    """Degenerate planes: all-zero rows pack to zero words; all-one rows set
    exactly the first n_in bits (tail stays silent — padding is exact)."""
    zeros = packing.pack_weight_planes(jnp.zeros((n_in, 8), jnp.int8))
    np.testing.assert_array_equal(np.asarray(zeros), 0)
    ones = packing.pack_weight_planes(jnp.ones((n_in, 8), jnp.int8))
    per_plane = np.array(
        [bin(int(wd)).count("1") for wd in np.asarray(ones[0])]
    ).sum()
    assert per_plane == n_in  # no stray bits past n_in in the last word
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_weight_planes(ones, n_in)), 1
    )


def test_weight_plane_numpy_and_jnp_bit_identical():
    w = np.asarray(
        jax.random.bernoulli(jax.random.PRNGKey(17), 0.5, (100, 12)), np.int8)
    np.testing.assert_array_equal(
        packing.pack_weight_planes_np(w),
        np.asarray(packing.pack_weight_planes(jnp.asarray(w))),
    )
    np.testing.assert_array_equal(
        packing.unpack_weight_planes_np(packing.pack_weight_planes_np(w), 100),
        w,
    )


def test_weight_planes_share_spike_wire_layout():
    """Weight planes are pack_spikes of W^T — one plane per output column,
    bit j*32+b of plane n holds W[j*32+b, n]; same LSB-first lane format the
    spike wire uses, so AND+popcount needs no per-operand shuffling."""
    w = jax.random.bernoulli(jax.random.PRNGKey(23), 0.5, (96, 4))
    np.testing.assert_array_equal(
        np.asarray(packing.pack_weight_planes(w)),
        np.asarray(packing.pack_spikes(w.T)),
    )


# ----------------------------------------------------------------------- #
# popcount kernels on packed operands vs the packed + dense oracles
# ----------------------------------------------------------------------- #
# includes K not a multiple of 32 or 128 (100, 160) and B/N off the tile
# grid; the wrappers pad the batch, and the whole packed K is one block.
PACKED_SHAPES = [(8, 128, 128), (64, 384, 128), (37, 100, 10), (200, 160, 32)]


@pytest.mark.parametrize("B,K,N", PACKED_SHAPES)
def test_cim_matmul_packed_bit_exact(B, K, N):
    key = jax.random.PRNGKey(B * 7 + K + N)
    s = jax.random.bernoulli(key, 0.4, (B, K))
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    p = packing.pack_spikes(s)
    out = pop_ops.cim_popcount_matmul(
        p, packing.pack_weight_planes(w), use_kernel=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pop_ref.cim_matmul_packed_ref(p, w))
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pop_ref.cim_matmul_ref(s, w))
    )


@pytest.mark.parametrize("B,K,N", [(8, 128, 128), (64, 384, 256), (37, 100, 64)])
@pytest.mark.parametrize("pack_output", [True, False])
def test_esam_layer_packed_bit_exact(B, K, N, pack_output):
    key = jax.random.PRNGKey(B + K + N)
    s = jax.random.bernoulli(key, 0.5, (B, K))
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    vth = jax.random.randint(jax.random.fold_in(key, 2), (N,), -9, 9, jnp.int32)
    p = packing.pack_spikes(s)
    out = pop_ops.esam_layer_popcount(
        p, packing.pack_weight_planes(w), vth, pack_output=pack_output,
        use_kernel=True, interpret=True)
    ref = pop_ref.esam_layer_packed_ref(p, w, vth, pack_output=pack_output)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    fired = pop_ref.esam_layer_ref(s, w, vth)
    if pack_output:
        np.testing.assert_array_equal(
            np.asarray(packing.unpack_spikes(out, N)), np.asarray(fired)
        )
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(fired))


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_cim_matmul_packed_property(seed):
    """Random B/K/N: every batch pads, the whole K is one block, and an N
    under 128 is one neuron block — all inside the kernel's block contract."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 64))
    K = int(rng.integers(1, 300))
    N = int(rng.integers(1, 96))
    key = jax.random.PRNGKey(seed)
    s = jax.random.bernoulli(key, float(rng.uniform(0, 1)), (B, K))
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    out = pop_ops.cim_popcount_matmul(
        packing.pack_spikes(s), packing.pack_weight_planes(w),
        use_kernel=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(pop_ref.cim_matmul_ref(s, w))
    )


# ----------------------------------------------------------------------- #
# fused multi-tile cascade == layer-by-layer functional plane
# ----------------------------------------------------------------------- #
def _rand_net(key, topo):
    bits, vth = [], []
    for i in range(len(topo) - 1):
        k = jax.random.fold_in(key, i)
        bits.append(
            jax.random.bernoulli(k, 0.5, (topo[i], topo[i + 1])).astype(jnp.int8)
        )
        vth.append(
            jax.random.randint(jax.random.fold_in(k, 1), (topo[i + 1],), -10, 10, jnp.int32)
        )
    off = jax.random.normal(jax.random.fold_in(key, 99), (topo[-1],))
    return EsamNetwork(weight_bits=bits, vth=vth, out_offset=off)


def test_forward_fused_equals_forward_esam_mnist_topology():
    """256-sample batch through the paper's 768:256:256:256:10 topology."""
    from repro.core.esam import cost_model as cm

    net = _rand_net(jax.random.PRNGKey(0), cm.PAPER_TOPOLOGY)
    s = jax.random.bernoulli(jax.random.PRNGKey(42), 0.35, (256, 768))
    np.testing.assert_array_equal(
        np.asarray(net.forward_fused(s, interpret=True)),
        np.asarray(net.forward(s)),
    )


def test_forward_fused_single_sample_and_odd_batch():
    net = _rand_net(jax.random.PRNGKey(5), (128, 64, 10))
    s1 = jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (128,))
    np.testing.assert_array_equal(
        np.asarray(net.forward_fused(s1, interpret=True)), np.asarray(net.forward(s1))
    )
    s = jax.random.bernoulli(jax.random.PRNGKey(2), 0.5, (37, 128))
    np.testing.assert_array_equal(
        np.asarray(net.forward_fused(s, interpret=True)), np.asarray(net.forward(s))
    )


def test_forward_fused_packed_accepts_wire_format():
    """Pre-packed host-side batches (the serving path) give identical logits."""
    net = _rand_net(jax.random.PRNGKey(9), (256, 128, 10))
    s = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(3), 0.4, (64, 256)))
    packed = jnp.asarray(packing.pack_spikes_np(s))
    np.testing.assert_array_equal(
        np.asarray(net.forward_fused_packed(packed, interpret=True)),
        np.asarray(net.forward(jnp.asarray(s))),
    )


# ----------------------------------------------------------------------- #
# packed plane consumers: data pipeline + serving engine
# ----------------------------------------------------------------------- #
def test_spike_pipeline_emits_packed_wire_format_and_resumes():
    from repro.data.pipeline import SpikePipeline, SpikePipelineConfig

    pipe = SpikePipeline(SpikePipelineConfig(batch=16, seed=3))
    b0 = pipe.next_batch()
    assert b0["spikes_packed"].dtype == np.uint32
    assert b0["spikes_packed"].shape == (16, packing.packed_width(b0["n_in"]))
    # resumable: a fresh pipeline sought to the same step is bit-exact
    pipe2 = SpikePipeline(SpikePipelineConfig(batch=16, seed=3))
    pipe2.seek(1)
    b1a, b1b = pipe.next_batch(), pipe2.next_batch()
    np.testing.assert_array_equal(b1a["spikes_packed"], b1b["spikes_packed"])
    np.testing.assert_array_equal(b1a["labels"], b1b["labels"])
    # packed plane matches the unpacked plane of the same step
    pipe3 = SpikePipeline(SpikePipelineConfig(batch=16, seed=3, packed=False))
    b0u = pipe3.batch_at(0)
    np.testing.assert_array_equal(
        b0["spikes_packed"], packing.pack_spikes_np(b0u["spikes"])
    )


def test_spike_engine_serves_packed_batches():
    from repro.serve.engine import SpikeEngine, SpikeRequest

    net = _rand_net(jax.random.PRNGKey(11), (768, 256, 10))
    s = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(4), 0.3, (5, 768)))
    # batch_size=2 forces multiple rounds + a padded final slot
    eng = SpikeEngine(net, batch_size=2, interpret=True)
    reqs = [SpikeRequest(spikes=s[i]) for i in range(5)]
    out = eng.serve(reqs)
    want = np.asarray(net.forward(jnp.asarray(s)))
    for i, r in enumerate(out):
        np.testing.assert_array_equal(r.logits, want[i])
        assert r.label == int(want[i].argmax())
