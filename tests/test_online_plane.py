"""Column-event online-learning plane: the fused transposable-port epoch.

Covers STDP rule equivalence (functional rule vs the jnp column writes the
epoch kernel's oracle is built on, under shared uniforms), bit-identity of
the epoch kernel against its scan oracle and the scan reference under the
shared key-folding scheme (readout widths, draw probabilities, sample
blocks, ragged tails, ties, padded rows),
multi-tile learning through the packed prefix, and the multi-epoch
train/online driver (accuracy tracking, checkpointing, resume, faults).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.esam import learning, tile
from repro.core.esam.network import EsamNetwork
from repro.data import digits
from repro.kernels.stdp import ops as stdp_ops
from repro.train import online as online_train


# ----------------------------------------------------------------------- #
# STDP rule: functional plane vs the oracle's column writes (shared uniforms)
# ----------------------------------------------------------------------- #
def _column_writes(bits_t, pre, post, u_pot_t, u_dep_t, p_pot, p_dep):
    """``stdp_column_event_ref`` once per column, gated by its post event."""
    return jax.lax.fori_loop(
        0, bits_t.shape[0],
        lambda c, b: stdp_ops.stdp_column_event_ref(
            b, c, post[c], pre, u_pot_t[c], u_dep_t[c], p_pot, p_dep),
        bits_t)


@pytest.mark.parametrize("n_in,n_out", [(128, 16), (256, 128), (64, 8)])
@pytest.mark.parametrize("p_pot,p_dep", [(0.0, 0.0), (1.0, 1.0), (0.3, 0.1)])
def test_stdp_three_way_equivalence(n_in, n_out, p_pot, p_dep):
    """learning rule == stdp/ref column writes, bit-exact (the epoch
    kernel's leg is ``test_epoch_kernel_matches_oracle_across_sample_blocks``)."""
    key = jax.random.PRNGKey(n_in + n_out)
    ks = jax.random.split(key, 5)
    bits = jax.random.bernoulli(ks[0], 0.5, (n_in, n_out)).astype(jnp.int8)
    pre = jax.random.bernoulli(ks[1], 0.4, (n_in,))
    post = jax.random.bernoulli(ks[2], 0.3, (n_out,))
    u_pot = jax.random.uniform(ks[3], (n_in, n_out))
    u_dep = jax.random.uniform(ks[4], (n_in, n_out))

    functional = learning.stdp_update_from_uniforms(
        bits, pre, post, u_pot, u_dep, p_pot, p_dep)
    oracle = _column_writes(bits.T, pre, post, u_pot.T, u_dep.T, p_pot, p_dep)
    np.testing.assert_array_equal(np.asarray(functional), np.asarray(oracle.T))


def test_column_event_kernel_matches_full_matrix_rule():
    """A gated column event == the full-matrix rule with a one-hot post mask."""
    key = jax.random.PRNGKey(9)
    n_in, n_out = 256, 16
    bits = jax.random.bernoulli(key, 0.5, (n_in, n_out)).astype(jnp.int8)
    pre = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n_in,))
    u_pot = jax.random.uniform(jax.random.fold_in(key, 2), (n_in,))
    u_dep = jax.random.uniform(jax.random.fold_in(key, 3), (n_in,))
    col = jnp.asarray(7, jnp.int32)
    out_t = stdp_ops.stdp_column_event_ref(
        bits.T, col, jnp.asarray(True), pre, u_pot, u_dep, 0.4, 0.2)
    full = learning.stdp_update_from_uniforms(
        bits, pre, jax.nn.one_hot(col, n_out, dtype=bool),
        u_pot[:, None], u_dep[:, None], 0.4, 0.2)
    np.testing.assert_array_equal(np.asarray(out_t.T), np.asarray(full))


# ----------------------------------------------------------------------- #
# Fused epoch vs scan reference: bit-identity under the shared key scheme
# ----------------------------------------------------------------------- #
@given(data=st.data())
@settings(max_examples=12, deadline=None)
def test_column_event_epoch_bit_identical_to_scan(data):
    n_in = data.draw(st.sampled_from([64, 128, 256]))
    n_out = data.draw(st.sampled_from([8, 10, 16]))
    batch = data.draw(st.integers(1, 24))
    density = data.draw(st.floats(0.0, 1.0))
    seed = data.draw(st.integers(0, 2**16))
    p_pot = data.draw(st.floats(0.0, 1.0))
    p_dep = data.draw(st.floats(0.0, 1.0))
    key = jax.random.PRNGKey(seed)
    bits = jax.random.bernoulli(key, 0.5, (n_in, n_out)).astype(jnp.int8)
    x = jax.random.bernoulli(jax.random.fold_in(key, 1), density, (batch, n_in))
    y = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0, n_out, jnp.int32)
    vth = [jnp.full((n_out,), 2**31 - 1, jnp.int32)]
    ep_key = jax.random.fold_in(key, 3)

    b_fused, n_fused = learning.online_learning_epoch(
        [bits], vth, x, y, ep_key, p_pot=p_pot, p_dep=p_dep)
    b_scan, n_scan = learning.online_learning_epoch_scan(
        [bits], vth, x, y, ep_key, p_pot=p_pot, p_dep=p_dep, rng_scheme="column")
    b_ref, n_ref = stdp_ops.column_event_epoch_ref(
        bits.T, x, y, ep_key, p_pot=p_pot, p_dep=p_dep)
    np.testing.assert_array_equal(np.asarray(b_fused), np.asarray(b_scan))
    np.testing.assert_array_equal(np.asarray(b_fused), np.asarray(b_ref.T))
    assert int(n_fused) == int(n_scan) == int(n_ref)


def test_fused_epoch_matches_scan_through_hidden_tiles():
    """Packed-prefix fused epoch == functional-prefix scan, multi-tile."""
    topo = (128, 64, 10)
    key = jax.random.PRNGKey(4)
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(2)
    ]
    vth = [jax.random.randint(jax.random.fold_in(key, 10), (64,), -5, 5, jnp.int32),
           jnp.full((10,), 2**31 - 1, jnp.int32)]
    x = jax.random.bernoulli(jax.random.fold_in(key, 20), 0.4, (48, 128))
    y = jax.random.randint(jax.random.fold_in(key, 21), (48,), 0, 10, jnp.int32)
    b_fused, n_f = learning.online_learning_epoch(
        bits, vth, x, y, jax.random.PRNGKey(9), p_pot=0.3, p_dep=0.15)
    b_scan, n_s = learning.online_learning_epoch_scan(
        bits, vth, x, y, jax.random.PRNGKey(9), p_pot=0.3, p_dep=0.15,
        rng_scheme="column")
    np.testing.assert_array_equal(np.asarray(b_fused), np.asarray(b_scan))
    assert int(n_f) == int(n_s)


# ----------------------------------------------------------------------- #
# The epoch kernel against its scan oracle: blocks, ties, padding
# ----------------------------------------------------------------------- #
def _epoch_case(n_in, n_out, batch, seed):
    key = jax.random.PRNGKey(seed)
    bits_t = jax.random.bernoulli(key, 0.5, (n_out, n_in)).astype(jnp.int8)
    x = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.4, (batch, n_in))
    y = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0, n_out,
                           jnp.int32)
    return bits_t, x, y, jax.random.fold_in(key, 3)


def _epoch_vs_oracle(bits_t, x, y, key, out_offset, p_pot=0.3, p_dep=0.2):
    """The fused epoch == ``column_event_epoch_ref``; returns its result."""
    want, n_want = stdp_ops.column_event_epoch_ref(
        bits_t, x, y, key, p_pot=p_pot, p_dep=p_dep, out_offset=out_offset)
    got, n_got = learning.column_event_epoch(
        jnp.array(bits_t), x, y, key, p_pot=p_pot, p_dep=p_dep,
        out_offset=out_offset)   # a copy: the epoch donates its readout
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(n_got) == int(n_want)
    return got, int(n_got)


@pytest.mark.parametrize("n_in,n_out,batch,offset,p_pot,p_dep", [
    (256, 10, 1024, False, 0.3, 0.2),    # exactly one block of samples
    (256, 10, 1030, True, 0.3, 0.2),     # a second block of 6 samples
    (128, 16, 2500, False, 0.3, 0.2),    # three blocks, the last ragged
    (64, 8, 2049, True, 0.3, 0.2),
] + [
    # readout widths against draw probabilities (p = 0 and 1 included),
    # one short block of samples, its tail ragged
    (n_in, n_out, 37, False, p_pot, p_dep)
    for n_out, n_in in [(16, 128), (128, 256), (8, 128)]
    for p_pot, p_dep in [(0.0, 0.0), (1.0, 1.0), (0.3, 0.1)]
] + [
    # inputs off the 128-lane grid (768, 384, 100) and with an offset
    (n_in, n_out, 37, True, p_pot, p_dep)
    for n_out, n_in in [(10, 768), (16, 256), (128, 128), (10, 384), (8, 100)]
    for p_pot, p_dep in [(1.0, 1.0), (0.25, 0.1)]
])
def test_epoch_kernel_matches_oracle_across_sample_blocks(
        n_in, n_out, batch, offset, p_pot, p_dep):
    bits_t, x, y, key = _epoch_case(n_in, n_out, batch, batch)
    out_offset = jnp.linspace(-2.0, 2.0, n_out) if offset else None
    _, n = _epoch_vs_oracle(bits_t, x, y, key, out_offset, p_pot, p_dep)
    assert n > 0


@pytest.mark.parametrize("offset", [None, "zeros", "two_maxima"])
def test_epoch_kernel_ties_take_the_first_index(offset):
    """An all-zero readout gives every neuron the same membrane, so the
    prediction is the first index of the max of the offset."""
    n_in, n_out = 256, 10
    bits_t = jnp.zeros((n_out, n_in), jnp.int8)
    out_offset = {None: None, "zeros": jnp.zeros((n_out,)),
                  "two_maxima": jnp.zeros((n_out,)).at[jnp.array([2, 5])]
                  .set(1.0)}[offset]
    first = 2 if offset == "two_maxima" else 0
    _, x, _, key = _epoch_case(n_in, n_out, 1, 7)
    for label, n_want in ((first, 0), (5 if first == 2 else 1, 2)):
        _, n = _epoch_vs_oracle(bits_t, x, jnp.array([label], jnp.int32),
                                key, out_offset)
        assert n == n_want, (label, n)
    # a whole batch from the tie: every sample against the oracle
    _, x, y, key = _epoch_case(n_in, n_out, 40, 8)
    _epoch_vs_oracle(bits_t, x, y, key, out_offset)


@pytest.mark.parametrize("n_out", [10, 3])
def test_epoch_kernel_never_predicts_padded_rows(n_out):
    """Rows padded to the 8-row tile never win: with every real logit at
    -1e30 the prediction is row 0, so label 0 is never wrong."""
    bits_t, x, _, key = _epoch_case(256, n_out, 30, n_out)
    y = jnp.zeros((30,), jnp.int32)
    got, n = _epoch_vs_oracle(bits_t, x, y, key, jnp.full((n_out,), -1e30))
    assert n == 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(bits_t))


def test_multi_tile_learning_improves_accuracy_packed_prefix():
    """768:256:10 net: supervised STDP on the readout learns through the
    frozen random hidden tile, prefix on the packed plane (Sec 4.4.1's
    on-device adaptation use case at paper scale)."""
    topo = (768, 256, 10)
    key = jax.random.PRNGKey(0)
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(2)
    ]
    vth = [jnp.zeros((256,), jnp.int32), jnp.full((10,), 2**31 - 1, jnp.int32)]
    x, y = digits.make_spike_dataset(512, seed=3)
    x, y = jnp.asarray(x).astype(bool), jnp.asarray(y)
    pre = learning.last_hidden_spikes(bits, vth, x)

    def accuracy(b_last):
        _, vmem = tile.functional_tile(b_last, pre, vth[-1])
        return float((vmem.argmax(-1) == y).mean())

    acc0 = accuracy(bits[-1])
    b = bits[-1]
    for epoch in range(6):
        b, _ = learning.online_learning_epoch(
            [bits[0], b], vth, x, y, jax.random.PRNGKey(10 + epoch),
            p_pot=0.2, p_dep=0.1, pre_spikes=pre)
    acc1 = accuracy(b)
    assert acc0 < 0.2, acc0                 # random readout is near chance
    assert acc1 > acc0 + 0.1, (acc0, acc1)  # STDP learns through the prefix


# ----------------------------------------------------------------------- #
# train/online.py: the multi-epoch driver
# ----------------------------------------------------------------------- #
def _driver_fixture():
    topo = (768, 64, 10)
    key = jax.random.PRNGKey(1)
    bits = [
        jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i + 1])).astype(jnp.int8)
        for i in range(2)
    ]
    vth = [jnp.zeros((64,), jnp.int32), jnp.full((10,), 2**31 - 1, jnp.int32)]
    net = EsamNetwork(weight_bits=bits, vth=vth, out_offset=jnp.zeros((10,)))
    x, y = digits.make_spike_dataset(256, seed=11)
    return net, jnp.asarray(x).astype(bool), jnp.asarray(y)


def test_train_online_tracks_accuracy_and_updates():
    net, x, y = _driver_fixture()
    res = online_train.train_online(
        net, x, y, epochs=4, key=jax.random.PRNGKey(5), p_pot=0.2, p_dep=0.1)
    assert res.epochs_run == 4 and res.start_epoch == 0
    assert len(res.accuracy) == 4 and len(res.n_updates) == 4
    assert all(n > 0 for n in res.n_updates)
    # the driver's resident-layout accuracy matches the network-level readout
    logits = res.network.forward(x)
    acc = float((jnp.argmax(logits, -1) == y).mean())
    assert abs(acc - res.accuracy[-1]) < 1e-6
    # prefix tiles are untouched; the readout actually learned
    np.testing.assert_array_equal(
        np.asarray(res.network.weight_bits[0]), np.asarray(net.weight_bits[0]))
    assert res.accuracy[-1] > 0.2


def test_train_online_checkpoint_resume_bit_identical(tmp_path):
    """2 epochs + checkpoint + resume to 4 == straight 4-epoch run."""
    net, x, y = _driver_fixture()
    key = jax.random.PRNGKey(5)
    straight = online_train.train_online(
        net, x, y, epochs=4, key=key, p_pot=0.2, p_dep=0.1)

    ckpt = str(tmp_path / "online")
    first = online_train.train_online(
        net, x, y, epochs=2, key=key, p_pot=0.2, p_dep=0.1,
        checkpoint_dir=ckpt, checkpoint_every=1)
    assert first.epochs_run == 2
    resumed = online_train.train_online(
        net, x, y, epochs=4, key=key, p_pot=0.2, p_dep=0.1,
        checkpoint_dir=ckpt, resume=True)
    assert resumed.start_epoch == 2 and resumed.epochs_run == 2
    np.testing.assert_array_equal(
        np.asarray(resumed.network.weight_bits[-1]),
        np.asarray(straight.network.weight_bits[-1]))
    assert resumed.accuracy[-1] == straight.accuracy[-1]


def test_train_online_rejects_partial_eval_split():
    net, x, y = _driver_fixture()
    with pytest.raises(ValueError, match="eval_labels"):
        online_train.train_online(net, x, y, epochs=1, eval_spikes=x)
    with pytest.raises(ValueError, match="eval_labels"):
        online_train.train_online(net, x, y, epochs=1, eval_labels=y)


def test_train_online_learns_against_deployed_offset_readout():
    """With a folded out_offset, the driver's events target the offset-shifted
    argmax (the deployed winner), and its tracked accuracy still matches the
    network-level forward readout."""
    import dataclasses

    net, x, y = _driver_fixture()
    offset = jnp.linspace(-3.0, 3.0, 10)
    net = dataclasses.replace(net, out_offset=offset)
    res = online_train.train_online(
        net, x, y, epochs=3, key=jax.random.PRNGKey(6), p_pot=0.2, p_dep=0.1)
    logits = res.network.forward(x)
    acc = float((jnp.argmax(logits, -1) == y).mean())
    assert abs(acc - res.accuracy[-1]) < 1e-6
    assert res.accuracy[-1] > 0.2


def test_train_online_shuffle_is_deterministic():
    net, x, y = _driver_fixture()
    a = online_train.train_online(
        net, x, y, epochs=2, key=jax.random.PRNGKey(7), shuffle=True)
    b = online_train.train_online(
        net, x, y, epochs=2, key=jax.random.PRNGKey(7), shuffle=True)
    np.testing.assert_array_equal(
        np.asarray(a.network.weight_bits[-1]),
        np.asarray(b.network.weight_bits[-1]))
    assert a.n_updates == b.n_updates


def test_faulted_train_online_matches_the_oracle_epoch(monkeypatch):
    """The faulted path (clamp, then the epoch) through the kernel == the
    same run with the scan oracle in the epoch's place."""
    from repro.core.esam.faults import FaultModel

    net, x, y = _driver_fixture()
    fm = FaultModel(seed=3, stuck0_rate=0.05, stuck1_rate=0.05,
                    dead_col_rate=0.1)

    def run():
        return online_train.train_online(
            net, x, y, epochs=2, key=jax.random.PRNGKey(8), p_pot=0.2,
            p_dep=0.1, faults=fm)

    got = run()

    def oracle(bits_t, pre, labels, key, *, p_pot, p_dep, out_offset=None,
               interpret=None):
        return stdp_ops.column_event_epoch_ref(
            bits_t, pre, labels, key, p_pot=p_pot, p_dep=p_dep,
            out_offset=out_offset)

    monkeypatch.setattr(learning, "column_event_epoch", oracle)
    want = run()
    np.testing.assert_array_equal(
        np.asarray(got.network.weight_bits[-1]),
        np.asarray(want.network.weight_bits[-1]))
    assert got.n_updates == want.n_updates and all(got.n_updates)
    assert got.accuracy == want.accuracy
