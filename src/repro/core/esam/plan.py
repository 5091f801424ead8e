"""Execution plans: ONE compiled entry point for every ESAM forward variant.

Event-based CIM accelerators get their efficiency from a *fixed dataflow
plan*: the schedule of a layer-stationary pipeline is decided once, before
any spike moves (Chauvaux et al.; Moitra et al.).  This module is that plan
layer for the repo.  An :class:`EsamPlan` is built once from

    (EsamNetwork, mode, collect, telemetry, read_ports, sharding rules)

and runs exactly one jitted — or, with sharding rules, one
``shard_map``-ped — executable, shared by every plan of the same static
structure built on that network or on one derived from it.  Every
consumer (the seven legacy ``EsamNetwork.forward*`` wrappers,
``port_sweep``, ``measured_activity``, the online-learning driver, the
serving engine, the benchmarks) runs through a plan, so the packing,
prefix-reuse, popcount-telemetry and cost plumbing lives here and nowhere
else.

Modes
-----
``functional``  dense MAC cascade (bool spikes between tiles) — the oracle.
``packed``      the bit-packed fused cascade: uint32 bitplanes on the wire,
                the whole cascade in one popcount Pallas launch (the fast
                plane).
``prefix``      hidden tiles only; returns the last tile's *input* plane
                (packed when every hidden width is 32-aligned, else bool) —
                what the online-learning plane reuses across epochs.
``cycle``       the rank-schedule cycle-accurate plane; with a tuple of
                cell options in ``read_ports`` it becomes the full Fig 8
                port sweep compiled as one executable.
``temporal``    the multi-timestep LIF plane (``core/esam/temporal.py``):
                one jitted membrane-resident ``lax.scan`` over a
                ``[T, batch, n_in]`` event stream; requires a
                :class:`~repro.core.esam.temporal.TemporalConfig`.  With
                T=1, zero leak and zero reset it is bit-identical to
                ``packed`` (property-tested).

Orthogonal flags: ``collect`` returns the inter-tile planes, ``telemetry``
returns the per-tile arbiter loads (group popcounts straight off the wire).

Sharding
--------
Pass :class:`~repro.distributed.sharding.ShardingRules` built by
``sharding.make_esam_rules``: the batch is sharded over the ``spike_batch``
mesh axes (weights replicated), and hidden-layer columns are additionally
sharded over the ``tile_col`` axis where widths divide evenly — each device
fires its slice of a tile's neurons and the fired plane is all-gathered onto
the inter-tile pulse bus.  Both layouts are bit-identical to the
single-device plan (integer datapath, deterministic gather order; enforced
by tests on an ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` mesh).
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from typing import Any, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.core.esam import arbiter as arb
from repro.core.esam import faults as faults_mod
from repro.core.esam import neuron as nrn
from repro.core.esam import tile as tile_mod
from repro.core.esam import temporal as temporal_mod

MODES = ("functional", "packed", "prefix", "cycle", "temporal")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Static description of one compiled ESAM executable."""

    mode: str = "packed"
    collect: bool = False
    telemetry: bool = False
    #: cell option(s).  An int for a single plan; a tuple of cell options
    #: turns ``cycle`` mode into the one-executable port sweep.
    read_ports: int | tuple[int, ...] = 4
    record_vmem_trace: bool = False
    interpret: Optional[bool] = None
    #: temporal mode only: the LIF dynamics (T, leak, reset, refractory) —
    #: part of the cache key, so each (T, collect, telemetry) spec compiles
    #: exactly one executable.
    temporal: Optional[temporal_mod.TemporalConfig] = None
    #: fault population injected into the datapath (frozen + hashable, so
    #: each FaultModel is its own cache entry).  ``None`` compiles the clean
    #: plan, bit-identical to pre-fault builds (property-tested).
    faults: Optional[faults_mod.FaultModel] = None
    #: donate the input buffer to XLA so allocations are reused across rounds
    #: (the serving engine's drain loop dispatches a fresh padded batch per
    #: round).  Only safe when every caller hands the plan arrays it owns —
    #: a donated array is invalidated by the call.
    donate: bool = False

    def __post_init__(self):
        assert self.mode in MODES, (self.mode, MODES)
        if isinstance(self.read_ports, tuple):
            assert self.mode == "cycle", "read_ports sweep needs mode='cycle'"
        if self.mode == "temporal":
            assert self.temporal is not None, (
                "mode='temporal' needs a TemporalConfig")
        else:
            assert self.temporal is None, (self.mode, self.temporal)


@dataclasses.dataclass
class PlanResult:
    """Outputs of one plan execution (fields populated per spec).

    ``planes`` carries what travels the inter-tile wire in that mode: the
    hidden layers' output spikes (``functional``) or the tile-input uint32
    bitplanes including the network input (``packed``) — matching what the
    legacy ``forward(collect=True)`` / ``forward_fused_packed_collect``
    returned.  ``loads`` are int32 arbiter loads per tile input,
    ``[..., n_groups]`` — the cost model's measured activity.  In temporal
    mode ``planes``/``loads`` gain a per-timestep axis after the batch:
    ``[..., T, n_words]`` / ``[..., T, n_groups]`` (batch-first so one
    sharding spec covers every mode).
    """

    logits: Optional[jax.Array] = None
    planes: Optional[tuple] = None
    loads: Optional[tuple] = None
    traces: Optional[tuple] = None           # TileTrace per tile (cycle mode)
    prefix: Optional[jax.Array] = None       # prefix mode only
    sweep: Optional[Mapping[int, Any]] = None  # {cell option: {logits, traces}}


def _packed_cascade(
    weight_bits: Sequence[jax.Array],
    vth: Sequence[jax.Array],
    packed: jax.Array,
    *,
    interpret: Optional[bool] = None,
    collect: bool = False,
    col_axis: Optional[str] = None,
    col_shard: Optional[Sequence[bool]] = None,
):
    """Cascade the hidden tiles (all but the last) on the packed plane.

    The learning plane's entry to the packed prefix datapath
    (``learning.last_hidden_spikes``): each hidden tile is the same fused
    popcount kernel (``esam_layer_popcount``) that ``EsamPlan``'s prefix
    mode and column-sharded packed mode run, so the learning plane's
    pre-synaptic trace can never desynchronize from the serving datapath.
    Weight planes are bit-sliced from the stored bits per call; plans
    hoist that slicing to build time.

    Hidden widths must be multiples of 32 (128-aligned tile columns in every
    paper topology) so fired planes re-pack exactly.  Under ``tile_col``
    sharding (``col_axis`` inside a shard_map) each device holds a 32-aligned
    column slice of the flagged layers and the fired plane is all-gathered —
    word order equals column order, so the gathered plane is bit-identical
    to the unsharded wire.  ``interpret=True`` forces the Pallas kernel (in
    interpret mode off-TPU), as in ``EsamPlan``.

    ``collect=True`` returns (prefix, [tile-input bitplane per tile]).
    """
    from repro.kernels.cim_popcount import ops as pop_ops

    for w in weight_bits[:-1]:
        assert w.shape[1] % 32 == 0, (
            "hidden width must be 32-aligned for the packed plane",
            w.shape,
        )
    use_kernel = True if interpret else None
    p = packed
    planes = [p]
    for i, (w, th) in enumerate(zip(weight_bits[:-1], vth[:-1])):
        p = pop_ops.esam_layer_popcount(
            p, packing.pack_weight_planes(w), th,
            use_kernel=use_kernel, interpret=interpret)
        if col_shard is not None and col_shard[i]:
            p = jax.lax.all_gather(p, col_axis, axis=-1, tiled=True)
        planes.append(p)
    if collect:
        return p, planes
    return p


@dataclasses.dataclass(frozen=True)
class _Structure:
    """Everything a plan's executable reads while it is traced.

    Plans equal in it trace, lower and compile the same program, so they
    share one executable (:func:`_shared_executable`); a network's arrays
    reach that program only as its runtime ``params`` argument.
    """

    #: the plan's spec with ``faults=None``: the fault masks are applied to
    #: the prepped operands, never inside the traced body
    spec: PlanSpec
    topology: tuple[int, ...]
    prefix_packed: bool
    use_mega: bool
    col_shard: tuple[bool, ...]
    col_axis: Optional[str]
    batch_axes: tuple[str, ...]
    #: the sharding rules' mesh, held here strongly (an id could be recycled
    #: once the rules are collected); ``None`` for a single-device plan
    mesh: Any


_SHARE_LOCK = threading.Lock()
#: recorded (zero length) through ``jax.monitoring`` each time a plan takes an
#: executable another plan built; ``obs.profile`` books it as ``plan_shared``
SHARED_EVENT = "/esam/plan/executable_shared"
#: recorded (zero length) each time a plan takes the operands another plan
#: of its line prepped from the same arrays; booked as ``prep_shared``
PREP_SHARED_EVENT = "/esam/plan/operands_shared"


def _shared_executable(executables: dict, st: _Structure):
    """The executable of structure ``st`` in ``executables`` (a network's
    ``_executables``, which the networks derived from it hold too): built on
    first use, then shared."""
    with _SHARE_LOCK:
        exe = executables.get(st)
        shared = exe is not None
        if not shared:
            exe = executables[st] = EsamPlan._compile(st)
    if shared:
        t = time.perf_counter()
        jax.monitoring.record_event_time_span(SHARED_EVENT, t, t)
    return exe


class EsamPlan:
    """One compiled ESAM executable, built once and reused for every batch.

    The executable is shared by every plan of the same static structure (the
    spec but its fault model, the topology, the sharding mesh and axes)
    built on the network or on a network derived from it with
    ``dataclasses.replace`` (they hold one ``_executables`` dict): a plan
    for a network whose weights changed (a learned readout) reuses the
    traced, lowered and compiled program and brings only its own prepped
    operands (``_prepare``) and AOT shapes (``warmup``).  Its operands too
    come from the line (``_prepped``) when the arrays its mode reads are
    those an earlier plan of the line prepped: a prefix plan reads no
    readout.  ``_exec`` stays an attribute of each plan.

    Call the plan with spikes ``{0,1}[..., n_in]`` (any dtype / leading
    shape) or, for the packed modes, pre-packed ``uint32[..., n_in/32]``
    wire-format planes; leading dims are flattened into one batch axis, the
    batch is zero-padded to the sharding's divisibility requirement (exact:
    a silent spike never contributes to the CIM MAC), and every output is
    unpadded and reshaped back.  Returns a :class:`PlanResult`.
    """

    def __init__(
        self,
        network,
        spec: PlanSpec,
        rules=None,  # Optional[sharding.ShardingRules]
    ):
        self.spec = spec
        self.rules = rules
        self.network = network
        self.topology = network.topology
        n_tiles = len(self.topology) - 1
        hidden_ok = all(
            w.shape[1] % 32 == 0 for w in network.weight_bits[:-1]
        )
        if spec.mode in ("packed", "temporal"):
            assert hidden_ok, (
                "packed/temporal plans need 32-aligned hidden widths",
                self.topology)
        #: prefix mode runs packed when the hidden widths allow it, else the
        #: dense functional tiles — both bit-identical (tests/test_packing).
        self.prefix_packed = spec.mode == "prefix" and hidden_ok
        self._packed_input = (
            spec.mode in ("packed", "temporal") or self.prefix_packed)
        self._n_in = self.topology[0]
        self._in_width = (
            packing.packed_width(self._n_in) if self._packed_input else self._n_in
        )

        # -------- sharding geometry (static, decided at build time) -------
        if rules is None:
            self._batch_axes: tuple[str, ...] = ()
            self._col_axis = None
            self._dp = 1
            col_size = 1
        else:
            self._batch_axes = rules.mesh_axes("spike_batch")
            assert self._batch_axes, "ESAM rules must map spike_batch"
            self._dp = rules.axis_size("spike_batch")
            col_axes = rules.mesh_axes("tile_col")
            assert len(col_axes) <= 1, "tile_col maps to at most one mesh axis"
            self._col_axis = col_axes[0] if col_axes else None
            col_size = rules.axis_size("tile_col")
            if spec.mode in ("cycle", "temporal"):
                assert col_size == 1, (
                    f"{spec.mode} plans are data-parallel only")
        lane = packing.LANE_BITS if self._packed_input else 1
        self._col_shard = tuple(
            self._col_axis is not None
            and i < n_tiles - 1
            and self.topology[i + 1] % (col_size * lane) == 0
            and col_size > 1
            for i in range(n_tiles)
        )

        # -------- fault masks (drawn once, at plan build) -----------------
        # Cycle-sweep plans need one upset mask per *effective* port count in
        # the sweep (disturb scales with ports); every other mode reads at
        # the plan's single port count.  Counter-based generation makes the
        # masks identical across device counts, so sharded faulted plans stay
        # bit-identical to single-device (the masks just ride the replicated/
        # column-sharded param specs).
        if spec.faults is not None:
            if spec.mode == "cycle" and isinstance(spec.read_ports, tuple):
                opts = spec.read_ports
            else:
                opts = (spec.read_ports if isinstance(spec.read_ports, int)
                        else 4,)
            self._fault_ports = tuple(
                sorted({max(1, int(o)) for o in opts}))
            self._fault_masks = spec.faults.build_masks(
                self.topology, self._fault_ports)
        else:
            self._fault_ports = ()
            self._fault_masks = None

        # -------- operand prep (hoisted out of every call) ----------------
        # The compiled executable never sees raw {0,1}[K, N] stored bits: it
        # closes over mode-native operands — ±1 decodes, uint32 weight bit
        # planes, the mega-kernel DMA slabs — sliced ONCE here (and again
        # only if the network's parameter arrays are swapped; see _prepare).
        #: packed plans run the single-launch popcount mega kernel unless a
        #: tile column is sharded (the inter-tile all_gather cannot happen
        #: inside one launch) — then per-tile popcount kernels + gather.
        self._use_mega = spec.mode == "packed" and not any(self._col_shard)
        self._eff_ports = (max(1, int(spec.read_ports))
                           if isinstance(spec.read_ports, int) else None)
        self._prep_key = None
        self._prep_src = None    # strong refs pin ids against reuse after GC
        self._prep_params = None
        #: AOT-compiled executables keyed on padded batch size (``warmup``).
        #: Compiled objects take the prepped params as a runtime argument, so
        #: a parameter swap (same shapes) never invalidates them.
        self._aot: dict[int, Any] = {}
        self._exec = _shared_executable(network._executables, _Structure(
            spec=dataclasses.replace(spec, faults=None),
            topology=self.topology,
            prefix_packed=self.prefix_packed, use_mega=self._use_mega,
            col_shard=self._col_shard, col_axis=self._col_axis,
            batch_axes=self._batch_axes,
            mesh=None if rules is None else rules.mesh))

    # ------------------------------------------------------------------ #
    # operand prep: decode / bit-slice / fault once, serve every batch
    # ------------------------------------------------------------------ #
    @staticmethod
    def _cycle_port_options(rp) -> tuple[int, ...]:
        options = rp if isinstance(rp, tuple) else (rp,)
        return tuple(sorted({max(1, int(o)) for o in options}))

    def _sources(self) -> tuple:
        """The network's arrays this plan's executable reads: a prefix plan
        reads the hidden tiles' weights and thresholds and nothing of the
        readout; every other mode reads every tile and the offset."""
        net = self.network
        if self.spec.mode == "prefix":
            return (*net.weight_bits[:-1], *net.vth[:-1])
        return (*net.weight_bits, *net.vth, net.out_offset)

    def _build_params(self, src: tuple):
        """Mode-native operands from ``src`` (``_sources``).

        Fault masks were drawn at build time; applying them here (eagerly,
        outside the executable) keeps every per-call trace free of both the
        {0,1} -> ±1 decode and the mask arithmetic.  Counter-based masks make
        the prepped operands identical across device counts, so sharded
        faulted plans stay bit-identical to single-device.  A prefix plan's
        tiles are the first ones, so the masks' tile order still holds.
        """
        from repro.kernels.cim_popcount import ops as pop_ops

        spec, fmk = self.spec, self._fault_masks
        n = len(src) // 2
        wb, vth = tuple(src[:n]), tuple(src[n:2 * n])
        if fmk is not None:
            vth = tuple(faults_mod.faulted_vth(vth, fmk))
            if spec.mode != "cycle":
                wb = tuple(faults_mod.faulted_weights(wb, fmk, self._eff_ports))
        params: dict[str, Any] = {"vth": vth}
        if spec.mode != "prefix":
            params["out_offset"] = src[-1]
        if spec.mode == "functional" or (
            spec.mode == "prefix" and not self.prefix_packed
        ):
            params["w_signed"] = tuple(nrn.decode_bitlines(w) for w in wb)
        elif spec.mode in ("packed", "prefix"):
            planes = tuple(packing.pack_weight_planes(w) for w in wb)
            if self._use_mega:
                w_stack, vth_stack = pop_ops.stack_cascade_operands(
                    planes, vth, self.topology)
                params["w_stack"], params["vth_stack"] = w_stack, vth_stack
            else:
                params["w_planes"] = planes
        elif spec.mode == "temporal":
            # both dispatch targets: uint32 planes for the popcount kernel
            # path, the pre-decoded ±1 f32 operand for the BLAS ref path
            params["w_planes"] = tuple(packing.pack_weight_planes(w) for w in wb)
            params["w_signed_f32"] = tuple(
                2.0 * w.astype(jnp.float32) - 1.0 for w in wb)
        else:  # cycle — one ±1 decode per effective port count in the sweep
            by_ports: dict[int, tuple] = {}
            clean = None
            for ports in self._cycle_port_options(spec.read_ports):
                if fmk is not None:
                    wb_p = faults_mod.faulted_weights(wb, fmk, ports)
                    by_ports[ports] = tuple(
                        nrn.decode_bitlines(w) for w in wb_p)
                else:
                    # no faults: every port count reads the same array
                    if clean is None:
                        clean = tuple(nrn.decode_bitlines(w) for w in wb)
                    by_ports[ports] = clean
            params["cycle_w_signed"] = by_ports
        return params

    def _prepare(self):
        """Cached prep, re-run only when an array the plan reads is swapped.

        Keyed on the ids of the arrays the mode reads (``_sources``): jax
        arrays are immutable, so value changes can only arrive as *new*
        array objects (e.g. a learned readout swapped in), which changes the
        key — a cached plan can never serve stale parameters.  ``_prep_src``
        holds strong references so a freed array's id cannot be reused while
        cached.  A plan that misses looks in its network line's ``_prepped``
        (handed on by ``dataclasses.replace``, like ``_executables``): one
        entry per spec and operand layout, replaced on a miss, so a derived
        network whose readout alone changed preps nothing for its prefix.
        """
        src = self._sources()
        key = tuple(map(id, src))
        if key != self._prep_key:
            self._prep_params = self._shared_params(src, key)
            self._prep_key = key
            self._prep_src = src
        return self._prep_params

    def _shared_params(self, src: tuple, ids: tuple):
        """The operands of ``src`` from the line's ``_prepped``, prepped and
        stored there on a miss; a hit records ``PREP_SHARED_EVENT``."""
        prepped = self.network._prepped
        slot = (self.spec, self._use_mega)
        with _SHARE_LOCK:
            entry = prepped.get(slot)
        if entry is not None and entry[0] == ids:
            t = time.perf_counter()
            jax.monitoring.record_event_time_span(PREP_SHARED_EVENT, t, t)
            return entry[2]
        params = self._build_params(src)
        with _SHARE_LOCK:
            prepped[slot] = (ids, src, params)
        return params

    # ------------------------------------------------------------------ #
    # the single compiled executable, built from the plan's structure alone
    # ------------------------------------------------------------------ #
    @staticmethod
    def _make_fn(st: _Structure):
        spec = st.spec
        col_axis = st.col_axis
        col_shard = st.col_shard if any(st.col_shard) else None
        topo = st.topology
        # spec.interpret=True forces the Pallas datapath (in interpret mode
        # off-TPU); the default dispatches kernel-on-TPU / popcount-ref
        # elsewhere, mirroring kernels/arbiter.
        use_kernel = True if spec.interpret else None

        def gather(x):
            return jax.lax.all_gather(x, col_axis, axis=-1, tiled=True)

        def dense_prefix(ws, vth, s):
            """The tiles of ``ws`` (hidden tiles only) on the dense plane."""
            hidden = []
            for i, (w, th) in enumerate(zip(ws, vth)):
                s, _ = tile_mod.functional_tile(None, s, th, w_signed=w)
                if col_shard is not None and col_shard[i]:
                    s = gather(s)
                hidden.append(s)
            return s, hidden

        def popcount_prefix(planes, vth, p):
            """Per-tile popcount cascade over the tiles of ``planes`` (hidden
            tiles only; the col-sharded fallback: fired slices all_gather
            onto the pulse bus between launches)."""
            from repro.kernels.cim_popcount import ops as pop_ops

            collected = [p]
            for i, (w, th) in enumerate(zip(planes, vth)):
                p = pop_ops.esam_layer_popcount(
                    p, w, th, use_kernel=use_kernel, interpret=spec.interpret)
                if col_shard is not None and col_shard[i]:
                    p = gather(p)
                collected.append(p)
            return p, collected

        def fn(params, x):
            vth = params["vth"]
            off = params.get("out_offset")     # a prefix plan has none
            out: dict[str, Any] = {}
            if spec.mode == "functional":
                ws = params["w_signed"]
                s, hidden = dense_prefix(ws[:-1], vth[:-1], x)
                _, vmem = tile_mod.functional_tile(
                    None, s, vth[-1], w_signed=ws[-1])
                out["logits"] = vmem.astype(jnp.float32) + off
                if spec.collect:
                    out["planes"] = tuple(hidden)
                if spec.telemetry:
                    out["loads"] = tuple(
                        arb.split_row_groups(si.astype(jnp.int32)).sum(-1)
                        for si in [x, *hidden]
                    )
            elif spec.mode == "packed":
                from repro.kernels.cim_popcount import ops as pop_ops

                if st.use_mega:
                    vmem, fired = pop_ops.esam_cascade_popcount(
                        x, params["w_stack"], params["vth_stack"],
                        topology=topo, use_kernel=use_kernel,
                        interpret=spec.interpret)
                    planes = (x,) + fired
                else:
                    p, planes = popcount_prefix(
                        params["w_planes"][:-1], vth[:-1], x)
                    vmem = pop_ops.cim_popcount_matmul(
                        p, params["w_planes"][-1],
                        use_kernel=use_kernel, interpret=spec.interpret)
                out["logits"] = vmem.astype(jnp.float32) + off
                if spec.collect:
                    out["planes"] = tuple(planes)
                if spec.telemetry:
                    out["loads"] = tuple(
                        packing.group_popcount(pl) for pl in planes
                    )
            elif spec.mode == "prefix":
                if st.prefix_packed:
                    p, planes = popcount_prefix(params["w_planes"], vth, x)
                else:
                    p, planes_b = dense_prefix(params["w_signed"], vth, x)
                    planes = [x, *planes_b]
                out["prefix"] = p
                if spec.collect:
                    out["planes"] = tuple(planes)
                if spec.telemetry:
                    out["loads"] = tuple(
                        packing.group_popcount(pl) if st.prefix_packed
                        else arb.split_row_groups(pl.astype(jnp.int32)).sum(-1)
                        for pl in planes
                    )
            elif spec.mode == "temporal":
                # x: uint32[B, T, n_words] batch-first (shardable); the scan
                # wants time leading, and its stacked outputs come back
                # batch-first from temporal_forward.
                res = temporal_mod.temporal_forward(
                    None, vth, off, x.swapaxes(0, 1), spec.temporal,
                    interpret=spec.interpret,
                    collect=spec.collect, telemetry=spec.telemetry,
                    w_planes=params["w_planes"],
                    w_signed_f32=params["w_signed_f32"],
                    topology=topo)
                out.update(res)
            else:  # cycle
                rp = spec.read_ports
                sweep = isinstance(rp, tuple)
                options = rp if sweep else (rp,)
                by_ports: dict[int, dict] = {}
                per_option: dict[int, dict] = {}
                for opt in options:
                    ports = max(1, int(opt))
                    if ports not in by_ports:
                        traces = []
                        s = x
                        for w_sgn, th in zip(
                                params["cycle_w_signed"][ports], vth):
                            tr = tile_mod.simulate_tile_batch(
                                None, s, th, ports, spec.record_vmem_trace,
                                w_signed=w_sgn)
                            traces.append(tr)
                            s = tr.out_spikes
                        logits = traces[-1].vmem_final.astype(jnp.float32) + off
                        by_ports[ports] = {
                            "logits": logits, "traces": tuple(traces)}
                    per_option[int(opt)] = by_ports[ports]
                if sweep:
                    out["sweep"] = per_option
                else:
                    res = per_option[int(rp)]
                    out["logits"] = res["logits"]
                    out["traces"] = res["traces"]
                if spec.telemetry:
                    any_traces = next(iter(by_ports.values()))["traces"]
                    inputs = [x, *(tr.out_spikes for tr in any_traces[:-1])]
                    out["loads"] = tuple(
                        arb.split_row_groups(si.astype(jnp.int32)).sum(-1)
                        for si in inputs
                    )
            return out

        # a stable executable name on the device trace (jit_esam_plan_<mode>)
        fn.__name__ = fn.__qualname__ = f"esam_plan_{spec.mode}"
        return fn

    @staticmethod
    def _compile(st: _Structure):
        fn = EsamPlan._make_fn(st)
        donate = (1,) if st.spec.donate else ()
        if st.spec.donate:
            # CPU/interpret backends may decline the donation (shape-mismatched
            # outputs); that is an optimization miss, not an error worth a
            # per-round warning in the serve loop
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        if st.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        from repro import compat

        ba = st.batch_axes if len(st.batch_axes) > 1 else st.batch_axes[0]
        ca = st.col_axis
        spec = st.spec
        # operand specs mirror _build_params: ±1 decodes shard like the
        # stored bits (columns = last axis), weight bit planes are
        # column-major so the sharded axis is the leading one; a prefix
        # plan's operands are the hidden tiles' alone, with no offset
        prefix = spec.mode == "prefix"
        col_shard = st.col_shard[:-1] if prefix else st.col_shard
        w_specs = tuple(
            P(None, ca) if sh else P(None, None) for sh in col_shard
        )
        p_specs = tuple(
            P(ca, None) if sh else P(None, None) for sh in col_shard
        )
        v_specs = tuple(P(ca) if sh else P(None) for sh in col_shard)
        params_spec: dict[str, Any] = {"vth": v_specs}
        if not prefix:
            params_spec["out_offset"] = P(None)
        if spec.mode == "functional" or (
            spec.mode == "prefix" and not st.prefix_packed
        ):
            params_spec["w_signed"] = w_specs
        elif spec.mode in ("packed", "prefix"):
            if st.use_mega:
                params_spec["w_stack"] = P(None, None, None)
                params_spec["vth_stack"] = P(None, None)
            else:
                params_spec["w_planes"] = p_specs
        elif spec.mode == "temporal":
            params_spec["w_planes"] = p_specs
            params_spec["w_signed_f32"] = w_specs
        else:  # cycle (data-parallel only — every operand replicated)
            ports = EsamPlan._cycle_port_options(spec.read_ports)
            params_spec["cycle_w_signed"] = {p: w_specs for p in ports}
        x_spec = P(ba, None, None) if spec.mode == "temporal" else P(ba, None)
        mapped = compat.shard_map(
            fn,
            mesh=st.mesh,
            in_specs=(params_spec, x_spec),
            out_specs=P(ba),
        )
        return jax.jit(mapped, donate_argnums=donate)

    # ------------------------------------------------------------------ #
    # cold start: AOT warmup of the executable's shape ladder
    # ------------------------------------------------------------------ #
    def _input_struct(self, batch: int) -> jax.ShapeDtypeStruct:
        """Abstract input of one padded batch, as ``_normalize`` produces it."""
        if self.spec.mode == "temporal":
            return jax.ShapeDtypeStruct(
                (batch, self.spec.temporal.n_steps, self._in_width),
                jnp.uint32)
        dtype = jnp.uint32 if self._packed_input else jnp.bool_
        return jax.ShapeDtypeStruct((batch, self._in_width), dtype)

    def warmup(self, batch_sizes: Sequence[int], *,
               aot: bool = True) -> dict[int, float]:
        """Compile this plan's executable ahead of serving, one shape per
        (dp-aligned, padded) batch size — typically an engine's bucket ladder.

        With ``aot=True`` (default) each shape is lowered and compiled once
        and the Compiled object cached on the plan: ``__call__`` then invokes
        it directly, bypassing the jit dispatch cache entirely, so a warmed
        shape can never recompile in the serve path (the cold-start
        regression test asserts ``_exec`` is untouched).  Compiled objects
        take the prepped operands as runtime arguments — swapping parameter
        arrays of the same shape keeps the warmup valid.  ``aot=False``
        instead executes a zeros batch per shape, populating the ordinary
        jit cache (useful where a backend rejects AOT calls).

        Returns ``{batch: seconds}`` compile times — with the persistent
        compilation cache enabled (``launch/env.py``) a re-run's times drop
        to the cache-hit cost, which is what makes cold start instant.
        """
        params = self._prepare()
        times: dict[int, float] = {}
        for b in batch_sizes:
            b = int(b)
            assert b >= 1 and b % self._dp == 0, (b, self._dp)
            t0 = time.perf_counter()
            if aot:
                if b not in self._aot:
                    self._aot[b] = self.lower(b).compile()
            else:
                struct = self._input_struct(b)
                x = jnp.zeros(struct.shape, struct.dtype)
                jax.block_until_ready(self._exec(params, x))
            times[b] = time.perf_counter() - t0
        return times

    def lower(self, batch: int):
        """Lower this plan's executable for one padded batch size: the jax
        ``Lowered`` program, for inspecting what a batch of that size runs
        (``.as_text()``, ``.compile().as_text()``) without running it."""
        return self._exec.lower(self._prepare(), self._input_struct(int(batch)))

    # ------------------------------------------------------------------ #
    # execution
    # ------------------------------------------------------------------ #
    def _normalize(self, x) -> tuple[jax.Array, tuple[int, ...]]:
        """Coerce input to a flat 2-D batch; returns (x2d, leading shape).

        Temporal plans instead take a time-first event stream
        ``[T, ..., n_in]`` (spikes or wire format) and flatten it to a
        batch-first ``uint32[B, T, n_words]`` — time is never a batch axis.
        """
        x = jnp.asarray(x)
        if self.spec.mode == "temporal":
            t = self.spec.temporal.n_steps
            if x.ndim < 2 or x.shape[0] != t:
                raise ValueError(
                    f"temporal plan expects events[{t}, ..., n], got {x.shape}")
            lead = x.shape[1:-1]
            if x.dtype == jnp.uint32 and x.shape[-1] == self._in_width:
                pass                                  # already wire format
            elif x.shape[-1] == self._n_in:
                x = packing.pack_spikes(x != 0)       # spikes -> wire format
            else:
                raise ValueError(
                    f"expected events[{t}, ..., {self._n_in}] or packed "
                    f"uint32[{t}, ..., {self._in_width}], got {x.shape} "
                    f"{x.dtype}")
            return x.reshape(t, -1, x.shape[-1]).swapaxes(0, 1), lead
        lead = x.shape[:-1]
        if self._packed_input:
            if x.dtype == jnp.uint32 and x.shape[-1] == self._in_width:
                pass                                  # already wire format
            elif x.shape[-1] == self._n_in:
                x = packing.pack_spikes(x != 0)       # spikes -> wire format
            else:
                raise ValueError(
                    f"expected spikes[..., {self._n_in}] or packed "
                    f"uint32[..., {self._in_width}], got {x.shape} {x.dtype}")
        else:
            if x.shape[-1] != self._n_in:
                raise ValueError(
                    f"expected spikes[..., {self._n_in}], got {x.shape}")
            x = x.astype(bool)
        return x.reshape(-1, x.shape[-1]), lead

    def __call__(self, x) -> PlanResult:
        x, lead = self._normalize(x)
        b = x.shape[0]
        pad = (-b) % self._dp
        if pad:
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        # operands are prepped from the network's *current* arrays (cached on
        # their ids — see _prepare), so a cached plan can never serve stale
        # parameters, yet no decode/bit-slice survives into the call
        exec_fn = self._aot.get(x.shape[0])
        out = (exec_fn or self._exec)(self._prepare(), x)
        out = jax.tree_util.tree_map(
            lambda a: a[:b].reshape(lead + a.shape[1:]), out)
        return PlanResult(**out)
