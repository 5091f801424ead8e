"""EsamPlan: the single compiled entry point.

Property tests assert the plan's output is bit-identical to the raw
datapaths each legacy ``forward*`` variant was built on — functional tile
chain, packed kernel cascade, rank-schedule simulator — across packed /
unpacked inputs, collect on/off, telemetry on/off; plus the continuously
batched ``SpikeEngine`` on top, and the sharded-vs-single-device identity
on an 8-device host-platform mesh (subprocess, XLA_FLAGS)."""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import packing
from repro.core.esam import EsamNetwork
from repro.core.esam import tile as tile_mod

TOPOLOGIES = [(256, 128, 10), (768, 256, 256, 10), (128, 64, 32)]


def _rand_net(key, topo):
    bits, vth = [], []
    for i in range(len(topo) - 1):
        k = jax.random.fold_in(key, i)
        bits.append(jax.random.bernoulli(k, 0.5, (topo[i], topo[i + 1])).astype(jnp.int8))
        vth.append(jax.random.randint(
            jax.random.fold_in(k, 1), (topo[i + 1],), -10, 10, jnp.int32))
    off = jax.random.normal(jax.random.fold_in(key, 99), (topo[-1],))
    return EsamNetwork(weight_bits=bits, vth=vth, out_offset=off)


def _oracle_functional(net, s):
    """Hand-rolled functional chain — the pre-plan ``forward`` body."""
    per_layer = []
    x = s
    for w, th in zip(net.weight_bits[:-1], net.vth[:-1]):
        x, _ = tile_mod.functional_tile(w, x, th)
        per_layer.append(x)
    _, vmem = tile_mod.functional_tile(net.weight_bits[-1], x, net.vth[-1])
    return vmem.astype(jnp.float32) + net.out_offset, per_layer


# ----------------------------------------------------------------------- #
# plan vs raw datapaths, all flag combinations
# ----------------------------------------------------------------------- #
@pytest.mark.parametrize("topo", TOPOLOGIES)
@pytest.mark.parametrize("collect", [False, True])
@pytest.mark.parametrize("telemetry", [False, True])
def test_functional_plan_bit_identical(topo, collect, telemetry):
    if telemetry and any(n % 128 for n in topo[:-1]):
        pytest.skip("telemetry loads need 128-aligned layer widths")
    net = _rand_net(jax.random.PRNGKey(sum(topo)), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(7), 0.4, (9, topo[0]))
    want, per_layer = _oracle_functional(net, s)
    res = net.plan(mode="functional", collect=collect, telemetry=telemetry)(s)
    np.testing.assert_array_equal(np.asarray(res.logits), np.asarray(want))
    if collect:
        assert len(res.planes) == len(per_layer)
        for a, b in zip(res.planes, per_layer):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert res.planes is None
    if telemetry:
        inputs = [s, *per_layer]
        assert len(res.loads) == len(topo) - 1
        for ld, si in zip(res.loads, inputs):
            n_groups = -(-si.shape[-1] // 128)
            want_ld = np.asarray(si, np.int32).reshape(
                9, n_groups, -1).sum(-1)
            np.testing.assert_array_equal(np.asarray(ld), want_ld)
    else:
        assert res.loads is None


@pytest.mark.parametrize("topo", [(256, 128, 10), (768, 256, 256, 10)])
@pytest.mark.parametrize("packed_input", [False, True])
@pytest.mark.parametrize("collect", [False, True])
def test_packed_plan_bit_identical(topo, packed_input, collect):
    net = _rand_net(jax.random.PRNGKey(13 + sum(topo)), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(3), 0.35, (21, topo[0]))
    want, _ = _oracle_functional(net, s)
    plan = net.plan(mode="packed", collect=collect, telemetry=True,
                    interpret=True)
    x = packing.pack_spikes(s) if packed_input else s
    res = plan(x)
    np.testing.assert_array_equal(np.asarray(res.logits), np.asarray(want))
    # telemetry loads come straight off the wire format (group popcounts)
    inputs = [s]
    xx = s
    for w, th in zip(net.weight_bits[:-1], net.vth[:-1]):
        xx, _ = tile_mod.functional_tile(w, xx, th)
        inputs.append(xx)
    for ld, si in zip(res.loads, inputs):
        n_groups = -(-si.shape[-1] // 128)
        want_ld = np.asarray(si, np.int32).reshape(21, n_groups, -1).sum(-1)
        np.testing.assert_array_equal(np.asarray(ld), want_ld)
    if collect:
        assert len(res.planes) == len(topo) - 1
        np.testing.assert_array_equal(
            np.asarray(res.planes[0]), np.asarray(packing.pack_spikes(s)))


def test_prefix_plan_matches_packed_cascade():
    topo = (768, 256, 256, 10)
    net = _rand_net(jax.random.PRNGKey(29), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3, (16, 768))
    plan = net.plan(mode="prefix", interpret=True)
    assert plan.prefix_packed
    res = plan(packing.pack_spikes(s))
    # oracle: functional chain through the hidden tiles, then pack
    x = s
    for w, th in zip(net.weight_bits[:-1], net.vth[:-1]):
        x, _ = tile_mod.functional_tile(w, x, th)
    np.testing.assert_array_equal(
        np.asarray(res.prefix), np.asarray(packing.pack_spikes(x)))
    # unpacked spikes accepted too
    np.testing.assert_array_equal(
        np.asarray(plan(s).prefix), np.asarray(res.prefix))


def test_prefix_plan_dense_fallback_unaligned_hidden():
    topo = (128, 48, 10)          # 48 not 32-aligned -> dense prefix
    net = _rand_net(jax.random.PRNGKey(31), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(6), 0.5, (7, 128))
    plan = net.plan(mode="prefix")
    assert not plan.prefix_packed
    x, _ = tile_mod.functional_tile(net.weight_bits[0], s, net.vth[0])
    np.testing.assert_array_equal(
        np.asarray(plan(s).prefix), np.asarray(x))


@pytest.mark.parametrize("ports", [1, 3])
def test_cycle_plan_matches_simulator(ports):
    topo = (256, 128, 10)
    net = _rand_net(jax.random.PRNGKey(41), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(8), 0.4, (6, 256))
    res = net.plan(mode="cycle", read_ports=ports)(s)
    want, _ = _oracle_functional(net, s)
    np.testing.assert_array_equal(np.asarray(res.logits), np.asarray(want))
    x = s
    for i, (w, th) in enumerate(zip(net.weight_bits, net.vth)):
        tr = tile_mod.simulate_tile_batch(w, x, th, ports)
        for field in ("out_spikes", "vmem_final", "cycles", "grants_per_cycle"):
            np.testing.assert_array_equal(
                np.asarray(getattr(res.traces[i], field)),
                np.asarray(getattr(tr, field)))
        x = tr.out_spikes


def test_cycle_sweep_plan_is_one_call_and_shares_port_counts():
    topo = (256, 128, 10)
    net = _rand_net(jax.random.PRNGKey(43), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(9), 0.4, (5, 256))
    res = net.plan(mode="cycle", read_ports=(0, 1, 4))(s)
    assert sorted(res.sweep) == [0, 1, 4]
    # options 0 and 1 share the single-port simulation
    np.testing.assert_array_equal(
        np.asarray(res.sweep[0]["traces"][0].cycles),
        np.asarray(res.sweep[1]["traces"][0].cycles))
    want, _ = _oracle_functional(net, s)
    for p in (0, 1, 4):
        np.testing.assert_array_equal(
            np.asarray(res.sweep[p]["logits"]), np.asarray(want))


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_plan_property_random_batch_and_leading_shapes(seed):
    """Packed and functional plans agree with the oracle on random shapes,
    including single samples (empty leading shape) and 3-D batches."""
    rng = np.random.default_rng(seed)
    topo = (128, 64, 10)
    net = _rand_net(jax.random.PRNGKey(seed), topo)
    shape = [(128,), (int(rng.integers(1, 9)), 128),
             (2, int(rng.integers(1, 5)), 128)][int(rng.integers(0, 3))]
    s = jax.random.bernoulli(
        jax.random.PRNGKey(seed + 1), float(rng.uniform(0.1, 0.9)), shape)
    want, _ = _oracle_functional(net, s)
    got_f = net.plan(mode="functional")(s).logits
    got_p = net.plan(mode="packed", interpret=True)(s).logits
    assert got_f.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want))


def test_legacy_wrappers_delegate_and_warn():
    """Every legacy forward* wrapper returns plan output and deprecation-warns
    (once per process — the filter here just makes them visible)."""
    from repro.core.esam import network as network_mod

    net = _rand_net(jax.random.PRNGKey(51), (256, 128, 10))
    s = jax.random.bernoulli(jax.random.PRNGKey(10), 0.4, (4, 256))
    want, per_layer = _oracle_functional(net, s)
    network_mod.reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        np.testing.assert_array_equal(np.asarray(net.forward(s)), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(net.forward_fused(s, interpret=True)), np.asarray(want))
        packed = packing.pack_spikes(s)
        np.testing.assert_array_equal(
            np.asarray(net.forward_fused_packed(packed, interpret=True)),
            np.asarray(want))
        logits, planes = net.forward_fused_packed_collect(packed, interpret=True)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(net.forward_prefix_packed(packed, interpret=True)),
            np.asarray(planes[-1]))
        lc, traces = net.forward_cycle_accurate(s[0], ports=4)
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(want[0]))
        assert traces[0].cycles.shape == ()
        lb, _ = net.forward_cycle_accurate_batch(s, ports=2)
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(want))
    names = {str(w.message).split(" ")[0] for w in caught
             if issubclass(w.category, DeprecationWarning)}
    assert any("EsamNetwork.forward" in n for n in names)
    assert len([w for w in caught
                if issubclass(w.category, DeprecationWarning)]) >= 7


def test_cached_plan_reads_current_weights():
    """A cached plan must serve the network's CURRENT parameters — in-place
    weight swaps (e.g. a learned readout) may not return stale logits."""
    net = _rand_net(jax.random.PRNGKey(52), (128, 64, 10))
    s = jax.random.bernoulli(jax.random.PRNGKey(14), 0.4, (5, 128))
    plan = net.plan(mode="functional")
    before = np.asarray(plan(s).logits)
    net.weight_bits[-1] = (1 - net.weight_bits[-1]).astype(jnp.int8)
    after = np.asarray(net.plan(mode="functional")(s).logits)
    want, _ = _oracle_functional(net, s)
    assert net.plan(mode="functional") is plan   # same compiled plan ...
    np.testing.assert_array_equal(after, np.asarray(want))  # ... fresh weights
    assert not np.array_equal(after, before)


def test_plan_executable_closes_over_presliced_operands():
    """The compiled executable never sees raw weight_bits: every mode's prep
    hands it mode-native operands built once at plan-build/prep time — uint32
    weight bit planes / DMA slabs for the popcount datapaths, decoded +-1
    matrices for the dense ones — and the prep cache only rebuilds when the
    parameter objects actually change."""
    net = _rand_net(jax.random.PRNGKey(71), (256, 128, 10))
    # packed (mega cascade): stacked uint32 planes + vth slab, no raw bits
    plan = net.plan(mode="packed", interpret=True)
    assert plan._use_mega
    params = plan._prepare()
    assert "weight_bits" not in params
    assert params["w_stack"].dtype == jnp.uint32
    assert params["w_stack"].shape[0] == 2           # one slab per tile
    assert params["vth_stack"].shape == (1, 128)     # hidden-tile thresholds
    # prep is cached: same params object until a weight actually changes
    assert plan._prepare() is params
    net.weight_bits[-1] = (1 - net.weight_bits[-1]).astype(jnp.int8)
    params2 = plan._prepare()
    assert params2 is not params
    assert not np.array_equal(np.asarray(params2["w_stack"]),
                              np.asarray(params["w_stack"]))
    # functional: decoded +-1 matrices, hoisted out of the traced body
    fplan = net.plan(mode="functional")
    fparams = fplan._prepare()
    assert "weight_bits" not in fparams
    assert all(np.isin(np.asarray(w), (-1, 1)).all()
               for w in fparams["w_signed"])
    # temporal: per-step MAC operands (bit planes + f32 signed) pre-built
    from repro.core.esam.temporal import TemporalConfig

    tplan = net.plan(mode="temporal",
                     temporal=TemporalConfig(n_steps=2), interpret=True)
    tparams = tplan._prepare()
    assert all(p.dtype == jnp.uint32 for p in tparams["w_planes"])
    assert all(w.dtype == jnp.float32 for w in tparams["w_signed_f32"])
    # cycle: decoded matrices shared across the port sweep when unfaulted
    cplan = net.plan(mode="cycle", read_ports=(0, 4))
    by_ports = cplan._prepare()["cycle_w_signed"]
    assert set(by_ports) == {1, 4}
    assert by_ports[1] is by_ports[4]


@pytest.mark.parametrize("mode", ["functional", "packed", "prefix", "cycle",
                                  "temporal"])
@pytest.mark.parametrize("faulted", [False, True])
def test_plan_modes_bit_identical_clean_and_faulted(mode, faulted):
    """Popcount-backed packed/prefix/temporal plans agree bit-exactly with
    the functional (unpacked) plane per mode, clean and under a fault model
    (faults now applied at prep time, outside the executable)."""
    from repro.core.esam.faults import FaultModel
    from repro.core.esam.temporal import TemporalConfig

    topo = (256, 128, 10)
    net = _rand_net(jax.random.PRNGKey(73 + faulted), topo)
    s = jax.random.bernoulli(jax.random.PRNGKey(15), 0.4, (13, 256))
    fm = FaultModel(seed=5, stuck0_rate=0.03, stuck1_rate=0.03,
                    vth_sigma=1.0, read_disturb=1e-3) if faulted else None
    # oracle: functional chain on the eagerly-faulted parameters, at the
    # same effective port count the plan will use
    ports = 2 if mode == "cycle" else 4
    if faulted:
        from repro.core.esam import faults as faults_mod

        masks = fm.build_masks(net.topology, (ports,))
        wb = faults_mod.faulted_weights(net.weight_bits, masks, ports)
        vth = faults_mod.faulted_vth(net.vth, masks)
        oracle_net = EsamNetwork(weight_bits=list(wb), vth=list(vth),
                                 out_offset=net.out_offset)
    else:
        oracle_net = net
    want, _ = _oracle_functional(oracle_net, s)
    kw = {"faults": fm} if faulted else {}
    if mode == "temporal":
        # T=1, no leak, zero-state: one step == the static forward pass
        cfg = TemporalConfig(n_steps=1, leak=0.0, reset="zero", refractory=0)
        res = net.plan(mode="temporal", interpret=True, temporal=cfg,
                       **kw)(s[None])
    elif mode == "cycle":
        res = net.plan(mode="cycle", read_ports=2, **kw)(s)
    elif mode == "prefix":
        plan = net.plan(mode="prefix", interpret=True, **kw)
        prefix = plan(s).prefix
        # readout on the popcount prefix == functional hidden chain packed
        x = s
        for w, th in zip(oracle_net.weight_bits[:-1], oracle_net.vth[:-1]):
            x, _ = tile_mod.functional_tile(w, x, th)
        np.testing.assert_array_equal(
            np.asarray(prefix), np.asarray(packing.pack_spikes(x)))
        return
    else:
        res = net.plan(mode=mode, interpret=True, **kw)(s)
    np.testing.assert_array_equal(np.asarray(res.logits), np.asarray(want))
    if faulted:
        clean, _ = _oracle_functional(net, s)
        assert not np.array_equal(np.asarray(res.logits), np.asarray(clean))


def test_plans_are_cached_per_network():
    net = _rand_net(jax.random.PRNGKey(53), (128, 64, 10))
    assert net.plan(mode="functional") is net.plan(mode="functional")
    assert net.plan(mode="functional") is not net.plan(
        mode="functional", collect=True)
    # replace() drops the cache (weights changed -> stale executables)
    net2 = dataclasses.replace(net, weight_bits=list(net.weight_bits))
    assert net2.plan(mode="functional") is not net.plan(mode="functional")


_SHARE_MODES = {
    "functional": {},
    "packed": {"interpret": True},
    "prefix": {"interpret": True},
    "temporal": {"interpret": True},
}


def _share_kw(mode):
    from repro.core.esam.temporal import TemporalConfig

    kw = dict(_SHARE_MODES[mode], telemetry=True)
    if mode == "temporal":
        kw["temporal"] = TemporalConfig(n_steps=2, leak=0.25)
    return kw


def _share_run(net, mode, s):
    res = net.plan(mode=mode, **_share_kw(mode))(
        jnp.stack([s, s[::-1]]) if mode == "temporal" else s)
    fields = [getattr(res, f.name) for f in dataclasses.fields(res)]
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(fields)]


def _derived(net, key):
    """``net`` with new weights, thresholds and offsets in every layer, as
    ``dataclasses.replace`` derives it (so it shares ``net``'s executables)."""
    other = _rand_net(key, net.topology)
    return dataclasses.replace(net, weight_bits=other.weight_bits,
                               vth=other.vth, out_offset=other.out_offset)


@pytest.mark.parametrize("mode", sorted(_SHARE_MODES))
def test_plans_of_one_structure_share_one_executable(mode):
    """A network derived from another, with different weights in every
    layer, gets the same executable, and each still computes its own
    result, bit for bit what an unshared plan of that network gives."""
    topo = (256, 128, 128, 10)
    a = _rand_net(jax.random.PRNGKey(81), topo)
    b = _derived(a, jax.random.PRNGKey(82))
    assert all(not np.array_equal(np.asarray(wa), np.asarray(wb))
               for wa, wb in zip(a.weight_bits, b.weight_bits))
    s = jax.random.bernoulli(jax.random.PRNGKey(16), 0.4, (9, 256))
    pa = a.plan(mode=mode, **_share_kw(mode))
    pb = b.plan(mode=mode, **_share_kw(mode))
    assert pa is not pb and pa._exec is pb._exec
    shared = {"a": _share_run(a, mode, s), "b": _share_run(b, mode, s)}
    assert any(not np.array_equal(x, y)
               for x, y in zip(shared["a"], shared["b"]))
    for name, net in (("a", a), ("b", b)):
        alone = dataclasses.replace(net, _executables={})
        assert alone.plan(mode=mode, **_share_kw(mode))._exec is not pa._exec
        got = _share_run(alone, mode, s)
        assert len(got) == len(shared[name])
        for x, y in zip(got, shared[name]):
            np.testing.assert_array_equal(x, y)


def test_plan_executables_differ_by_topology_spec_and_rules():
    from repro.core.esam.faults import FaultModel
    from repro.distributed import sharding as shd

    a = _rand_net(jax.random.PRNGKey(83), (128, 64, 10))
    b = _derived(a, jax.random.PRNGKey(84))
    wide = dataclasses.replace(
        a, **{f: getattr(_rand_net(jax.random.PRNGKey(85), (128, 96, 10)), f)
              for f in ("weight_bits", "vth", "out_offset")})
    assert a.plan(mode="packed")._exec is b.plan(mode="packed")._exec
    assert a.plan(mode="packed")._exec is not wide.plan(mode="packed")._exec
    assert a.plan(mode="packed")._exec is not b.plan(
        mode="packed", telemetry=True)._exec
    assert a.plan(mode="packed")._exec is not b.plan(mode="prefix")._exec
    # a network built on its own shares nothing with one it is not derived
    # from (so a kernel patched between two builds reaches the second)
    alone = _rand_net(jax.random.PRNGKey(84), (128, 64, 10))
    assert alone.plan(mode="packed")._exec is not a.plan(mode="packed")._exec
    # faults are applied to the operands, never traced
    fm = FaultModel(seed=5, stuck0_rate=0.03, stuck1_rate=0.03)
    assert a.plan(mode="packed", faults=fm)._exec is b.plan(
        mode="packed")._exec
    mesh = shd.esam_data_mesh(1)
    dp = shd.make_esam_rules(mesh)
    # rules are keyed on their mesh and axes, not on the rules object
    assert a.plan(mode="packed", rules=dp)._exec is b.plan(
        mode="packed", rules=shd.make_esam_rules(mesh))._exec
    assert a.plan(mode="packed", rules=dp)._exec is not b.plan(
        mode="packed")._exec
    two_axes = shd.make_mesh_axes((1, 1), ("data", "model"))
    assert a.plan(mode="packed", rules=dp)._exec is not b.plan(
        mode="packed", rules=shd.make_esam_rules(two_axes))._exec
    assert b.plan(mode="packed", rules=shd.make_esam_rules(two_axes))._exec \
        is not b.plan(mode="packed", rules=shd.make_esam_rules(
            two_axes, col_axis="model"))._exec


def test_patching_one_plans_executable_leaves_other_plans_alone():
    a = _rand_net(jax.random.PRNGKey(86), (128, 64, 10))
    b = _derived(a, jax.random.PRNGKey(87))
    s = jax.random.bernoulli(jax.random.PRNGKey(17), 0.4, (3, 128))
    pa, pb = a.plan(mode="packed"), b.plan(mode="packed")
    assert pa._exec is pb._exec
    want = np.asarray(pb(s).logits)

    def bomb(*_a, **_k):
        raise AssertionError("patched executable reached another plan")

    pa._exec = bomb
    with pytest.raises(AssertionError, match="patched"):
        pa(s)
    np.testing.assert_array_equal(np.asarray(pb(s).logits), want)
    c = dataclasses.replace(a)
    np.testing.assert_array_equal(
        np.asarray(c.plan(mode="packed")(s).logits),
        np.asarray(_oracle_functional(a, s)[0]))


# ----------------------------------------------------------------------- #
# sharded plan == single device, on the 8-device host-platform mesh
# ----------------------------------------------------------------------- #
_SHARDED_SCRIPT = r"""
import warnings; warnings.simplefilter("ignore")
import jax, jax.numpy as jnp, numpy as np
from repro.core.esam.network import EsamNetwork
from repro.distributed import sharding as shd
from repro.core import packing

assert len(jax.devices()) == 8, jax.devices()
key = jax.random.PRNGKey(0)
topo = (768, 256, 256, 10)
bits = [jax.random.bernoulli(jax.random.fold_in(key, i), 0.5,
                             (topo[i], topo[i+1])).astype(jnp.int8)
        for i in range(len(topo)-1)]
vth = [jax.random.randint(jax.random.fold_in(key, 10+i), (topo[i+1],),
                          -10, 10, jnp.int32) for i in range(len(topo)-1)]
net = EsamNetwork(weight_bits=bits, vth=vth,
                  out_offset=jax.random.normal(jax.random.fold_in(key, 99),
                                               (topo[-1],)))
s = jax.random.bernoulli(jax.random.fold_in(key, 7), 0.35, (37, 768))

single = net.plan(mode="packed", telemetry=True, collect=True, interpret=True)(s)
dp_rules = shd.make_esam_rules(shd.esam_data_mesh())
dp_plan = net.plan(mode="packed", telemetry=True, collect=True, interpret=True,
                   rules=dp_rules)
# dp-sharded packed plans run the popcount mega cascade (batch-only shard);
# the executable closes over the prepped uint32 DMA slabs, not raw bits
assert dp_plan._use_mega
dp_params = dp_plan._prepare()
assert dp_params["w_stack"].dtype == jnp.uint32, dp_params["w_stack"].dtype
assert "weight_bits" not in dp_params
dp = dp_plan(s)
np.testing.assert_array_equal(np.asarray(dp.logits), np.asarray(single.logits))
for a, b in zip(dp.planes, single.planes):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(dp.loads, single.loads):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# data x model: hidden tile columns sharded over the model axis
mp_rules = shd.make_esam_rules(
    shd.make_mesh_axes((4, 2), ("data", "model")), col_axis="model")
mp_plan = net.plan(mode="packed", telemetry=True, interpret=True,
                   rules=mp_rules)
assert any(mp_plan._col_shard), mp_plan._col_shard
# column-sharded tiles cannot all_gather inside one launch: the plan falls
# back to per-tile popcount kernels over sharded uint32 weight planes
assert not mp_plan._use_mega
assert all(p.dtype == jnp.uint32 for p in mp_plan._prepare()["w_planes"])
mp = mp_plan(s)
np.testing.assert_array_equal(np.asarray(mp.logits), np.asarray(single.logits))
for a, b in zip(mp.loads, single.loads):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

fmp = net.plan(mode="functional", rules=mp_rules)(s)
np.testing.assert_array_equal(np.asarray(fmp.logits), np.asarray(single.logits))

# cycle-accurate sweep, data-parallel
cy_single = net.plan(mode="cycle", read_ports=(0, 4))(s)
cy_dp = net.plan(mode="cycle", read_ports=(0, 4), rules=dp_rules)(s)
for p in (0, 4):
    np.testing.assert_array_equal(
        np.asarray(cy_dp.sweep[p]["logits"]),
        np.asarray(cy_single.sweep[p]["logits"]))
    for ta, tb in zip(cy_dp.sweep[p]["traces"], cy_single.sweep[p]["traces"]):
        np.testing.assert_array_equal(np.asarray(ta.cycles), np.asarray(tb.cycles))
        np.testing.assert_array_equal(
            np.asarray(ta.grants_per_cycle), np.asarray(tb.grants_per_cycle))

# temporal plan, data-parallel: bit-identical to single device
from repro.core.esam.temporal import TemporalConfig
tcfg = TemporalConfig(n_steps=3, leak=0.25, reset="subtract")
ev = jax.random.bernoulli(jax.random.fold_in(key, 8), 0.3, (3, 37, 768))
t_single = net.plan(mode="temporal", temporal=tcfg, telemetry=True,
                    interpret=True)(ev)
t_dp = net.plan(mode="temporal", temporal=tcfg, telemetry=True,
                interpret=True, rules=dp_rules)(ev)
np.testing.assert_array_equal(np.asarray(t_dp.logits),
                              np.asarray(t_single.logits))
for a, b in zip(t_dp.loads, t_single.loads):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# serving engine through the sharded plan
from repro.serve.engine import SpikeEngine, SpikeRequest
eng = SpikeEngine(net, max_batch=16, interpret=True, telemetry=True,
                  rules=dp_rules)
reqs = eng.serve([SpikeRequest(spikes=np.asarray(s[i])) for i in range(11)])
for i, r in enumerate(reqs):
    np.testing.assert_array_equal(r.logits, np.asarray(single.logits[i]))
st = eng.stats()
assert st["n_requests"] == 11 and st["data_parallel"] == 8

# faulted plan, dp-sharded: the counter-based fault masks are built on the
# host from the topology alone, so the sharded executable must be
# bit-identical to the faulted single-device one (and differ from clean)
from repro.core.esam.faults import FaultModel
fm = FaultModel(seed=3, stuck0_rate=0.02, stuck1_rate=0.02,
                vth_sigma=1.0, read_disturb=1e-3)
f_single = net.plan(mode="packed", telemetry=True, interpret=True,
                    faults=fm)(s)
f_dp = net.plan(mode="packed", telemetry=True, interpret=True,
                faults=fm, rules=dp_rules)(s)
np.testing.assert_array_equal(np.asarray(f_dp.logits),
                              np.asarray(f_single.logits))
for a, b in zip(f_dp.loads, f_single.loads):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert not np.array_equal(np.asarray(f_dp.logits), np.asarray(single.logits))
f_fn = net.plan(mode="functional", faults=fm, rules=dp_rules)(s)
np.testing.assert_array_equal(np.asarray(f_fn.logits),
                              np.asarray(f_single.logits))
print("SHARDED_IDENTITY_OK")
"""


def test_sharded_plan_identity_on_host_mesh():
    """The shard_map-ped plan is bit-identical to single-device, verified in a
    subprocess so the host platform can be split into 8 devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARDED_IDENTITY_OK" in proc.stdout


# ----------------------------------------------------------------------- #
# the continuously batched SpikeEngine on top of the plan
# ----------------------------------------------------------------------- #
def test_spike_engine_stats_empty_regression():
    """stats() before any serve() is a well-defined zero aggregate."""
    from repro.serve.engine import SpikeEngine

    net = _rand_net(jax.random.PRNGKey(61), (128, 64, 10))
    st = SpikeEngine(net, interpret=True, telemetry=True).stats()
    assert st["n_requests"] == 0 and st["requests"] == 0
    for key in ("cycles_mean", "latency_ns_mean", "energy_pj_per_inf",
                "throughput_inf_s", "throughput_pipelined_inf_s"):
        assert st[key] == 0.0, (key, st[key])
    assert np.isfinite(list(
        v for v in st.values() if isinstance(v, float))).all()


def test_spike_engine_bucket_ladder_and_queue():
    from repro.serve.engine import SpikeEngine, SpikeRequest, _bucket_sizes

    assert _bucket_sizes(128, 8, 1) == [8, 16, 32, 64, 128]
    assert _bucket_sizes(128, 8, 16) == [16, 32, 64, 128]
    assert _bucket_sizes(2, 8, 1) == [2]       # min_bucket never exceeds max
    assert _bucket_sizes(100, 8, 1) == [8, 16, 32, 64, 128]

    net = _rand_net(jax.random.PRNGKey(63), (128, 64, 10))
    s = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(11), 0.4, (11, 128)))
    eng = SpikeEngine(net, max_batch=8, min_bucket=2, interpret=True)
    assert eng._bucket(1) == 2 and eng._bucket(3) == 4 and eng._bucket(8) == 8
    # submit() queues without running; serve() drains everything pending
    eng.submit([SpikeRequest(spikes=s[i]) for i in range(3)])
    assert all(r.logits is None for r in eng._pending)
    out = eng.serve([SpikeRequest(spikes=s[i]) for i in range(3, 11)])
    assert not eng._pending and not eng._inflight
    want = np.asarray(net.plan(mode="functional")(jnp.asarray(s)).logits)
    for i, r in enumerate(out):        # the 8 passed to serve()
        np.testing.assert_array_equal(r.logits, want[3 + i])


def test_spike_engine_device_telemetry_matches_numpy_cost_model():
    """Device-resident float32 accounting agrees with the float64 numpy
    request_stats to ~1e-6 relative; cycles stay exact."""
    from repro.core.esam import cost_model as cm
    from repro.serve.engine import SpikeEngine, SpikeRequest

    net = _rand_net(jax.random.PRNGKey(65), (768, 256, 10))
    s = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(12), 0.3, (9, 768)))
    eng = SpikeEngine(net, max_batch=4, interpret=True, telemetry=True,
                      read_ports=3)
    reqs = eng.serve([SpikeRequest(spikes=s[i]) for i in range(9)])
    act = net.measured_activity(jnp.asarray(s).astype(bool))
    rs = cm.request_stats(net.topology, act, 3)
    for i, r in enumerate(reqs):
        assert r.cycles == int(rs.cycles[i])
        assert r.latency_ns == pytest.approx(float(rs.latency_ns[i]))
        assert r.energy_pj == pytest.approx(float(rs.energy_pj[i]))
    st = eng.stats()
    assert st["cycles_mean"] == pytest.approx(rs.cycles.mean())
    assert st["energy_pj_per_inf"] == pytest.approx(rs.energy_pj.mean())
    # pipelined rate: bottleneck mean tile stage, same model as system_stats
    bottleneck = rs.cycles_per_tile.mean(axis=0).max()
    want_pipe = 1e9 / (bottleneck * cm.cell_spec(3).clock_ns)
    assert st["throughput_pipelined_inf_s"] == pytest.approx(want_pipe)


def test_request_stats_device_matches_numpy():
    from repro.core.esam import cost_model as cm

    rng = np.random.default_rng(0)
    topo = (768, 256, 256, 256, 10)
    loads = [rng.integers(0, 129, size=(13, -(-topo[t] // 128))).astype(np.int32)
             for t in range(len(topo) - 1)]
    for p in range(5):
        dev = cm.request_stats_device(topo, [jnp.asarray(l) for l in loads], p)
        ref = cm.request_stats(topo, [l.astype(np.float64) for l in loads], p)
        np.testing.assert_array_equal(np.asarray(dev["cycles"]), ref.cycles)
        np.testing.assert_allclose(
            np.asarray(dev["latency_ns"]), ref.latency_ns, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(dev["energy_pj"]), ref.energy_pj, rtol=1e-5)


def test_packing_batch_prep_helpers():
    rows = [np.ones(100, np.int8), np.zeros(100, np.float32),
            (np.arange(100) % 2).astype(np.int32)]
    padded = packing.pad_spike_rows_np(rows, 8, 100)
    assert padded.shape == (8, 100) and padded.dtype == np.uint8
    np.testing.assert_array_equal(padded[0], 1)
    np.testing.assert_array_equal(padded[3:], 0)
    packed = packing.pack_padded_rows_np(rows, 8, 100)
    np.testing.assert_array_equal(packed, packing.pack_spikes_np(padded))
