"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode runs a kernel body in Python and accepts block shapes and
value slices that the chip's compiler (Mosaic) refuses, so every kernel a
plan mode, ``train_online`` or ``SpikeEngine`` dispatches on a TPU is
compiled here for a described ``v5e:2x2`` topology — no chip attached —
at the paper's widths (768:256:256:256:10) and at serving buckets 8 and
128.  Each test asserts that the kernel survived as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at import
time): only one process at a time may load the TPU compiler library, so
the first test of this file to run takes it, and the file must stay the
only one that does.

``test_every_kernel_is_dispatched_and_compiled`` needs no chip: it reads
the sources and holds ``kernels/`` to exactly ``CHIP_KERNELS`` — each
``pallas_call`` named there is taken by a module of ``src/repro`` outside
``kernels/`` and is compiled by a test of this file.  When a plan mode
starts dispatching a kernel, add its name and a compile test here (and a
phase of ``chip_smoke.py``); when nothing dispatches one, delete it.
"""

from __future__ import annotations

import ast
import functools
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import packing
from repro.core.esam import learning
from repro.core.esam.cost_model import PAPER_TOPOLOGY
from repro.kernels.arbiter import ops as arb_ops
from repro.kernels.cim_popcount import ops as pop_ops
from repro.kernels.lif_step import ops as lif_ops

BATCHES = (8, 128)
#: row-group width of the arbiter (128 SRAM rows per group)
GROUP = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *structs, **kw_structs) -> str:
    """Compile ``fn`` for the described chip and return the optimized HLO."""
    return jax.jit(fn).lower(*structs, **kw_structs).compile().as_text()


def _kernel_ops(hlo: str) -> set:
    """The Pallas kernels' op names as a device trace shows them (without
    the instance number): what the roofline metrics match letter for letter.
    Custom calls XLA adds itself (a ``ConcatBitcast`` of prefetched slices)
    are not kernels."""
    return {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r'%([\w.\-]+) = [^\n]*custom-call\([^\n]*'
        r'custom_call_target="tpu_custom_call"', hlo)}


def _struct(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _words(n: int) -> int:
    return packing.packed_width(n)


@pytest.mark.parametrize("batch", BATCHES)
def test_mega_cascade_compiles(one_chip, batch):
    g = pop_ops.cascade_geometry(PAPER_TOPOLOGY)
    fn = functools.partial(
        pop_ops.esam_cascade_popcount, topology=PAPER_TOPOLOGY,
        use_kernel=True, interpret=False)
    hlo = _compile(
        fn,
        _struct(one_chip, (batch, _words(PAPER_TOPOLOGY[0])), jnp.uint32),
        _struct(one_chip, (g["n_tiles"], g["n_max_pad"], g["w_max"]),
                jnp.uint32),
        _struct(one_chip, (g["n_tiles"] - 1, g["n_max_pad"]), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"esam_cascade_popcount"}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("tile", [0, 1])
def test_esam_layer_popcount_compiles(one_chip, batch, tile):
    n_in, n_out = PAPER_TOPOLOGY[tile], PAPER_TOPOLOGY[tile + 1]
    fn = functools.partial(
        pop_ops.esam_layer_popcount, use_kernel=True, interpret=False)
    hlo = _compile(
        fn,
        _struct(one_chip, (batch, _words(n_in)), jnp.uint32),
        _struct(one_chip, (n_out, _words(n_in)), jnp.uint32),
        _struct(one_chip, (n_out,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"esam_layer_popcount"}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("tile", [0, 1, 3])
def test_cim_popcount_matmul_compiles(one_chip, batch, tile):
    """Tile 0 is the temporal plan's lifted input MAC (16 steps of the batch
    flattened), tile 1 a hidden MAC, tile 3 the readout."""
    n_in, n_out = PAPER_TOPOLOGY[tile], PAPER_TOPOLOGY[tile + 1]
    rows = batch * 16 if tile == 0 else batch
    fn = functools.partial(
        pop_ops.cim_popcount_matmul, use_kernel=True, interpret=False)
    hlo = _compile(
        fn,
        _struct(one_chip, (rows, _words(n_in)), jnp.uint32),
        _struct(one_chip, (n_out, _words(n_in)), jnp.uint32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"cim_popcount_matmul"}


@pytest.mark.parametrize("batch", BATCHES)
def test_lif_step_compiles(one_chip, batch):
    n = PAPER_TOPOLOGY[1]
    fn = functools.partial(
        lif_ops.lif_step, leak=0.125, reset="zero", refractory=0,
        use_kernel=True, interpret=False)
    hlo = _compile(
        fn,
        _struct(one_chip, (batch, n), jnp.float32),
        _struct(one_chip, (batch, n), jnp.int32),
        _struct(one_chip, (n,), jnp.int32),
        _struct(one_chip, (batch, n), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"lif_step"}


def test_stdp_column_event_epoch_compiles(one_chip):
    """``train_online``'s epoch: the paper's readout learning a 4,096-sample
    chunk, the draws beside one kernel that holds the readout in VMEM."""
    n_in, n_out = PAPER_TOPOLOGY[-2], PAPER_TOPOLOGY[-1]
    batch = 4096
    fn = functools.partial(
        learning.column_event_epoch, p_pot=0.12, p_dep=0.06, interpret=False)
    hlo = _compile(
        fn,
        _struct(one_chip, (n_out, n_in), jnp.int8),
        _struct(one_chip, (batch, n_in), jnp.bool_),
        _struct(one_chip, (batch,), jnp.int32),
        _struct(one_chip, (2,), jnp.uint32),
        out_offset=_struct(one_chip, (n_out,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"stdp_column_event_epoch"}


def _paper_network():
    from repro.core.esam.network import EsamNetwork

    topo = PAPER_TOPOLOGY
    return EsamNetwork(
        weight_bits=[jnp.zeros((a, b), jnp.int8)
                     for a, b in zip(topo[:-1], topo[1:])],
        vth=[jnp.zeros((b,), jnp.int32) for b in topo[1:]],
        out_offset=jnp.zeros((topo[-1],), jnp.float32))


def test_train_online_prefix_step_compiles(one_chip, monkeypatch):
    """``train_online``'s prefix step for a 4,096-sample chunk: cast, pack,
    the prefix plan's executable and the unpack in one program, the hidden
    tiles as ``esam_layer_popcount`` launches.  The plan picks its datapath
    from the backend, so the test names the chip's."""
    from repro.train import online

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = _paper_network().plan(mode="prefix", interpret=False)
    params = jax.tree_util.tree_map(
        lambda a: _struct(one_chip, a.shape, a.dtype), plan._prepare())
    lowered = online._prefix_step.lower(
        plan._exec, plan.prefix_packed, PAPER_TOPOLOGY[-2], params,
        _struct(one_chip, (4096, PAPER_TOPOLOGY[0]), jnp.uint8))
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"esam_layer_popcount"}


@pytest.mark.parametrize("shuffle", [False, True])
def test_train_online_epoch_step_compiles(one_chip, shuffle):
    """``train_online``'s epoch step for a 4,096-sample chunk: key, shuffle,
    the draws, the ``stdp_column_event_epoch`` kernel and the eval accuracy
    in one program."""
    from repro.train import online

    n_in, n_out = PAPER_TOPOLOGY[-2], PAPER_TOPOLOGY[-1]
    batch = 4096
    pre = _struct(one_chip, (batch, n_in), jnp.bool_)
    labels = _struct(one_chip, (batch,), jnp.int32)
    lowered = online._epoch_step.lower(
        _struct(one_chip, (n_in, n_out), jnp.int8), pre, labels, pre, labels,
        _struct(one_chip, (2,), jnp.uint32), _struct(one_chip, (), jnp.int32),
        _struct(one_chip, (n_out,), jnp.float32), None, p_pot=0.12,
        p_dep=0.06, shuffle=shuffle, interpret=False)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"stdp_column_event_epoch"}


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("ports", [1, 3, 4])
def test_port_schedule_compiles(one_chip, batch, ports):
    """One request row per 128-row group of the network input."""
    groups = batch * PAPER_TOPOLOGY[0] // GROUP
    fn = functools.partial(
        arb_ops.port_schedule, ports=ports, use_kernel=True, interpret=False)
    hlo = _compile(fn, _struct(one_chip, (groups, GROUP), jnp.bool_))
    assert "tpu_custom_call" in hlo
    assert _kernel_ops(hlo) == {"port_schedule"}


# ----------------------------------------------------------------------- #
# every kernel under kernels/ is dispatched by the program and compiled here
# ----------------------------------------------------------------------- #
#: the ``pallas_call`` names a chip path runs, each compiled by a test above
CHIP_KERNELS = ("esam_cascade_popcount", "esam_layer_popcount",
                "cim_popcount_matmul", "lif_step", "stdp_column_event_epoch",
                "port_schedule")
SRC = Path(__file__).resolve().parent.parent / "src" / "repro"
KERNELS = SRC / "kernels"


def _pallas_call_names() -> set:
    """The ``name=`` of every ``pallas_call`` defined under ``kernels/``."""
    names = set()
    for path in KERNELS.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", None) == "pallas_call"):
                names.update(k.value.value for k in node.keywords
                             if k.arg == "name")
    return names


def _taken_from_kernels() -> set:
    """Names that modules of ``src/repro`` outside ``kernels/`` take from
    it: ``from repro.kernels... import f`` and ``<module alias>.f``."""
    taken = set()
    for path in SRC.rglob("*.py"):
        if KERNELS in path.parents:
            continue
        tree = ast.parse(path.read_text())
        aliases = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom)
                    and (node.module or "").startswith("repro.kernels")):
                for a in node.names:
                    taken.add(a.name)
                    aliases.add(a.asname or a.name)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in aliases):
                taken.add(node.attr)
    return taken


def _compiled_here() -> set:
    """The kernel names the compile tests of this file assert."""
    return set(re.findall(r'_kernel_ops\(hlo\) == \{"(\w+)"\}',
                          Path(__file__).read_text()))


@pytest.mark.parametrize("kernel", (None,) + CHIP_KERNELS)
def test_every_kernel_is_dispatched_and_compiled(kernel):
    """``None``: the kernels under ``kernels/`` are exactly ``CHIP_KERNELS``.
    A name: its ``pallas_call`` is defined, its wrapper (of the same name)
    is taken from outside ``kernels/``, and a test above compiles it."""
    if kernel is None:
        assert _pallas_call_names() == set(CHIP_KERNELS)
        return
    assert kernel in _pallas_call_names()
    assert kernel in _taken_from_kernels()
    assert kernel in _compiled_here()
