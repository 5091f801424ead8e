"""Multi-tile ESAM network: functional + cycle-accurate simulation and the
system-level performance model (throughput / energy / power / area).

Tiles are cascaded directly; spikes travel between tiles as parallel binary
pulses (Sec 3.1), which lets the tile pipeline overlap consecutive samples:
tile t processes sample s while tile t+1 processes sample s-1.  System
throughput is therefore set by the slowest tile stage; latency is the sum of
stages (both in cycles of the cell-dependent clock, Table 2).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.esam import arbiter as arb
from repro.core.esam import cost_model as cm
from repro.core.esam import tile as tile_mod
from repro.core.esam import plan as plan_mod
from repro.core.esam.plan import EsamPlan, PlanSpec

ROW_GROUP = 128

#: The legacy ``forward*`` entry points below are deprecated wrappers over
#: ``EsamNetwork.plan`` — each warns once per process.
_DEPRECATION_WARNED: set[str] = set()


def reset_deprecation_warnings() -> None:
    """Forget which deprecated ``forward*`` wrappers have already warned.

    The warn-once registry is process-global, so a test asserting that a
    wrapper warns would otherwise depend on whether another test tripped the
    same wrapper first.  Warning-assertion tests call this before recording
    (tests/test_plan.py, tests/test_network_deprecations.py).
    """
    _DEPRECATION_WARNED.clear()


def _warn_deprecated(name: str, instead: str) -> None:
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"EsamNetwork.{name} is deprecated; build an execution plan once via "
        f"EsamNetwork.plan({instead}) and call it per batch.",
        DeprecationWarning,
        stacklevel=3,
    )


@dataclasses.dataclass
class EsamNetwork:
    """A stack of CIM-P tiles (binary SNN).

    weight_bits: per layer, {0,1}[n_in, n_out] stored bits ('1' -> +1, '0' -> -1).
    vth: per layer, int32[n_out] per-neuron thresholds (Fig 5's t-bit register).
    out_offset: float[n_classes] — per-neuron readout offset folded from the
      BNN's final-layer bias during conversion (argmax-preserving).

    All inference entry points compile through :class:`EsamPlan`
    (``core/esam/plan.py``): ``plan(...)`` builds — and caches per network —
    one plan for a given (mode, collect, telemetry, read_ports, sharding)
    tuple; its jitted (or shard_map-ped) executable is shared with the
    networks derived from this one.  The historical
    ``forward*`` methods survive as thin deprecated wrappers over it.
    """

    weight_bits: list[jax.Array]
    vth: list[jax.Array]
    out_offset: jax.Array
    _plan_cache: dict = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    #: plan executables by static structure (``plan._Structure``).  An init
    #: field, so ``dataclasses.replace`` hands the same dict to the derived
    #: network while ``_plan_cache`` starts empty: a network whose weights
    #: changed (a learned readout) lowers none of its plans again.
    _executables: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def topology(self) -> tuple[int, ...]:
        return tuple([self.weight_bits[0].shape[0]] + [w.shape[1] for w in self.weight_bits])

    # ------------------------------------------------------------------ #
    # Execution plans — the single compiled entry point
    # ------------------------------------------------------------------ #
    def plan(
        self,
        *,
        mode: str = "packed",
        collect: bool = False,
        telemetry: bool = False,
        read_ports: int | tuple[int, ...] = 4,
        record_vmem_trace: bool = False,
        interpret: bool | None = None,
        temporal=None,  # Optional[temporal.TemporalConfig], mode="temporal"
        faults=None,  # Optional[faults.FaultModel]
        rules=None,
        donate: bool = False,
    ) -> EsamPlan:
        """Build (or fetch from this network's cache) one compiled plan.

        A new plan takes the executable of any earlier plan with the same
        arguments (``faults`` aside) and sharding mesh and axes built on a
        network of this one's line (each derived from the same original by
        ``dataclasses.replace``), so a network with swapped weights (a
        learned readout) lowers nothing again; only its operands are
        prepped anew.

        ``rules`` takes :func:`repro.distributed.sharding.make_esam_rules`
        output to compile the plan sharded over a device mesh; plans built
        with rules are cached by rule-object identity.  ``mode="temporal"``
        takes a :class:`~repro.core.esam.temporal.TemporalConfig` — each
        (T, leak, reset, refractory, collect, telemetry) tuple compiles one
        executable, cached like every other spec.  ``faults`` takes a
        :class:`~repro.core.esam.faults.FaultModel` to compile the plan with
        that fault population injected into the datapath (each model is its
        own cache entry; ``None`` is the clean plan, bit-identical to
        pre-fault builds).  ``donate=True`` donates the input batch to XLA
        so drain loops reuse device allocations round-over-round — only for
        callers that own the arrays they pass (the serving engine).
        """
        spec = PlanSpec(
            mode=mode,
            collect=collect,
            telemetry=telemetry,
            read_ports=read_ports,
            record_vmem_trace=record_vmem_trace,
            interpret=interpret,
            temporal=temporal,
            faults=faults,
            donate=donate,
        )
        key = (spec, None if rules is None else id(rules))
        cached = self._plan_cache.get(key)
        if cached is None:
            cached = EsamPlan(self, spec, rules=rules)
            self._plan_cache[key] = cached
        return cached

    @property
    def n_neurons(self) -> int:
        return sum(w.shape[1] for w in self.weight_bits)

    @property
    def n_synapses(self) -> int:
        return sum(int(np.prod(w.shape)) for w in self.weight_bits)

    # ------------------------------------------------------------------ #
    # Functional (batched, MXU-friendly) plane — deprecated wrappers
    # ------------------------------------------------------------------ #
    def forward(self, spikes: jax.Array, collect: bool = False):
        """Batched inference. spikes: bool[..., n_in] -> logits float[..., n_cls].

        The final tile's V_mem plus the folded offset is the classification
        score (output neurons are read out, not thresholded — argmax readout).

        .. deprecated:: use ``plan(mode="functional")``.
        """
        _warn_deprecated("forward", 'mode="functional"')
        res = self.plan(mode="functional", collect=collect)(spikes)
        if collect:
            return res.logits, list(res.planes)
        return res.logits

    def spike_counts(
        self, spikes: jax.Array, per_layer: Sequence[jax.Array] | None = None
    ) -> list[jax.Array]:
        """Per-layer, per-row-group spike counts for a batch (for the cost model).

        Returns a list over tiles of int32[..., n_groups]: the arbiter load of
        each 128-row group at that tile's input.

        ``per_layer`` takes the hidden-layer spikes a caller already computed
        via ``forward(..., collect=True)`` — the counts are then pure
        reductions and no tile matmul is re-run.  Without it the functional
        plan runs once with telemetry on.
        """
        n_hidden = len(self.weight_bits) - 1
        if per_layer is None:
            return list(
                self.plan(mode="functional", telemetry=True)(spikes).loads)
        assert len(per_layer) >= n_hidden, (len(per_layer), n_hidden)
        layer_inputs = [spikes, *per_layer[:n_hidden]]
        return [
            arb.split_row_groups(s.astype(jnp.int32)).sum(-1) for s in layer_inputs
        ]

    # ------------------------------------------------------------------ #
    # Packed (bit-plane) fused plane — deprecated wrappers
    # ------------------------------------------------------------------ #
    def forward_fused(
        self, spikes: jax.Array, *, interpret: bool | None = None
    ) -> jax.Array:
        """``forward`` on the packed datapath: spikes are bit-packed once at
        the input, the whole cascade runs in the popcount mega kernel
        (kernels/cim_popcount), and only uint32 bitplanes — 32 spikes per
        lane word, the paper's parallel-pulse wire — travel between tiles.
        Logits are bit-identical to ``forward`` (tested).

        .. deprecated:: use ``plan()`` (packed is the default mode).
        """
        _warn_deprecated("forward_fused", 'mode="packed"')
        return self.plan(mode="packed", interpret=interpret)(spikes).logits

    def forward_prefix_packed(
        self, packed: jax.Array, *, interpret: bool | None = None
    ) -> jax.Array:
        """Run only the frozen hidden tiles on the packed plane.

        Takes and returns the uint32 bitplane wire format: the result is the
        last tile's *input* spike plane, uint32[B, n_hidden/32] — the prefix
        the online-learning plane reuses across epochs.

        .. deprecated:: use ``plan(mode="prefix")``.
        """
        _warn_deprecated("forward_prefix_packed", 'mode="prefix"')
        return self.plan(mode="prefix", interpret=interpret)(packed).prefix

    def forward_fused_packed(
        self, packed: jax.Array, *, interpret: bool | None = None
    ) -> jax.Array:
        """Fused cascade over pre-packed spikes uint32[B, ceil(n_in/32)].

        .. deprecated:: use ``plan(mode="packed")``.
        """
        _warn_deprecated("forward_fused_packed", 'mode="packed"')
        return self.plan(mode="packed", interpret=interpret)(packed).logits

    def forward_fused_packed_collect(
        self, packed: jax.Array, *, interpret: bool | None = None
    ) -> tuple[jax.Array, list[jax.Array]]:
        """``forward_fused_packed`` plus the tile-input bitplane at every tile
        boundary — one pass, nothing unpacked.  The planes' group popcounts
        (``packing.group_popcount``) are the measured arbiter loads, so the
        serving plane's cost telemetry rides the packed datapath for free.

        .. deprecated:: use ``plan(mode="packed", collect=True)``.
        """
        _warn_deprecated("forward_fused_packed_collect",
                         'mode="packed", collect=True')
        res = self.plan(mode="packed", collect=True, interpret=interpret)(packed)
        return res.logits, list(res.planes)

    # ------------------------------------------------------------------ #
    # Cycle-accurate (event-driven) plane — deprecated wrappers
    # ------------------------------------------------------------------ #
    def forward_cycle_accurate(
        self, spikes1: jax.Array, ports: int, record_vmem_trace: bool = False
    ):
        """Single-sample event-driven simulation through every tile.

        Returns (logits, [TileTrace per tile]).  Output logits are bit-identical
        to ``forward`` (tested) — the multiport schedule only changes *when*
        contributions accumulate, never their sum.

        .. deprecated:: use ``plan(mode="cycle", read_ports=ports)``.
        """
        _warn_deprecated("forward_cycle_accurate", 'mode="cycle"')
        res = self.plan(
            mode="cycle", read_ports=int(ports),
            record_vmem_trace=record_vmem_trace,
        )(spikes1)
        return res.logits, list(res.traces)

    def forward_cycle_accurate_batch(
        self, spikes: jax.Array, ports: int, record_vmem_trace: bool = False
    ):
        """Event-driven simulation of a whole batch on the rank-schedule plane.

        spikes: bool[batch, n_in].  Returns (logits float[batch, n_cls],
        [batched TileTrace per tile]) — each trace field has a leading batch
        axis.  With the default ``record_vmem_trace=False`` the per-sample
        state stays O(n_out), which is what makes this plane batchable.

        .. deprecated:: use ``plan(mode="cycle", read_ports=ports)``.
        """
        _warn_deprecated("forward_cycle_accurate_batch", 'mode="cycle"')
        res = self.plan(
            mode="cycle", read_ports=int(ports),
            record_vmem_trace=record_vmem_trace,
        )(spikes)
        return res.logits, list(res.traces)

    def port_sweep(
        self,
        spikes: jax.Array,
        read_ports: Sequence[int] = range(5),
        record_vmem_trace: bool = False,
    ) -> dict[int, tuple[jax.Array, list[tile_mod.TileTrace]]]:
        """Batched cycle-accurate design-space sweep over SRAM cell options.

        Runs the rank-schedule plane through every tile for each cell option
        in ``read_ports`` (0 = the 1RW baseline reading through its RW port),
        all inside ONE compiled plan — the Fig 8 workload as a single device
        program instead of a Python loop of simulations.  Cell options
        sharing an effective port count (0 and 1: the 1RW cell reads through
        its single RW port) share one simulation inside the plan.

        spikes: bool[batch, n_in].  Returns {read_ports: (logits, traces)};
        logits are identical across entries (the schedule only moves *when*
        contributions land), while traces carry the per-option cycle counts
        the cost model consumes.
        """
        rp = tuple(int(p) for p in read_ports)
        res = self.plan(
            mode="cycle", read_ports=rp, record_vmem_trace=record_vmem_trace
        )(spikes)
        return {p: (res.sweep[p]["logits"], list(res.sweep[p]["traces"]))
                for p in rp}

    def measured_activity(
        self,
        spikes: jax.Array,
        traces: Sequence[tile_mod.TileTrace] | None = None,
    ) -> list[np.ndarray]:
        """Measured arbiter loads of a batch, ready for ``system_stats``.

        Returns per tile float64[batch, n_groups] — the *measured* activity
        profile (vs the synthetic ``reference_activity``).  Pass the traces of
        a ``port_sweep``/``forward_cycle_accurate_batch`` run to reuse the
        spikes the simulator actually drained; otherwise the functional plan
        runs once with telemetry on.
        """
        if traces is not None:
            per_layer = [tr.out_spikes for tr in traces[:-1]]
            counts = self.spike_counts(spikes, per_layer=per_layer)
        else:
            counts = self.plan(mode="functional", telemetry=True)(spikes).loads
        return [np.asarray(c, np.float64) for c in counts]


#: Back-compat alias: the packed hidden-tile cascade now lives in
#: ``core/esam/plan.py`` (the plan layer is its single owner).
packed_prefix = plan_mod._packed_cascade


# ---------------------------------------------------------------------- #
# System-level performance model
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SystemStats:
    cell: str
    read_ports: int
    clock_ns: float
    cycles_per_tile: tuple[float, ...]   # mean cycles until R_empty, + fire cycle
    bottleneck_tile: int
    latency_ns: float                    # single-inference latency
    throughput_inf_s: float              # pipelined
    energy_pj_per_inf: float
    dynamic_power_mw: float
    power_mw: float                      # incl. static
    area_um2: float
    area_ratio_vs_1rw: float


#: (row groups, column groups) of 128x128 arrays for an n_in x n_out tile.
_tile_geometry = cm.tile_geometry


def system_stats(
    topology: Sequence[int],
    spikes_per_group: Sequence[np.ndarray] | Sequence[Sequence[float]],
    read_ports: int,
) -> SystemStats:
    """Evaluate the full-system operating point for one cell option.

    Batch means over ``cost_model.request_stats`` — the same per-request
    accounting the serving plane reports — so an operating point can be
    evaluated on the synthetic calibration profile (``reference_activity``)
    or on *measured* batch activity (``EsamNetwork.measured_activity``)
    interchangeably.

    Args:
      topology: e.g. (768, 256, 256, 256, 10).
      spikes_per_group: per tile, array[..., n_groups] of arbiter loads (may be
        a batch — averaged for throughput/energy; max-over-groups is taken per
        sample *before* averaging, matching how the hardware stalls).
      read_ports: 0 (=1RW baseline) .. 4.
    """
    spec = cm.cell_spec(read_ports)
    rs = cm.request_stats(topology, spikes_per_group, read_ports)
    cycles = rs.cycles_per_tile.mean(axis=0)         # [T] mean incl. fire cycle
    energy = float(rs.energy_pj.mean())
    bottleneck = int(np.argmax(cycles))
    stage_ns = max(cycles) * spec.clock_ns
    throughput = 1e9 / stage_ns
    latency_ns = float(sum(cycles) * spec.clock_ns)
    dyn_mw = energy * 1e-12 * throughput * 1e3
    area = _system_area_um2(topology, read_ports)
    return SystemStats(
        cell=spec.name,
        read_ports=read_ports,
        clock_ns=spec.clock_ns,
        cycles_per_tile=tuple(float(c) for c in cycles),
        bottleneck_tile=bottleneck,
        latency_ns=latency_ns,
        throughput_inf_s=float(throughput),
        energy_pj_per_inf=float(energy),
        dynamic_power_mw=float(dyn_mw),
        power_mw=float(dyn_mw + cm.STATIC_POWER_MW),
        area_um2=area,
        area_ratio_vs_1rw=area / _system_area_um2(topology, 0),
    )


def _system_area_um2(topology: Sequence[int], read_ports: int) -> float:
    area = 0.0
    base = cm.CELL_AREA_6T_UM2 * ROW_GROUP * ROW_GROUP
    for t in range(len(topology) - 1):
        g, c = _tile_geometry(topology[t], topology[t + 1])
        n_arrays = g * c
        area += n_arrays * (base * cm.CELL_AREA_RATIO[read_ports]
                            + base * cm.PERIPHERY_AREA_FRACTION)
    return area


def reference_activity(topology: Sequence[int] = cm.PAPER_TOPOLOGY) -> list[np.ndarray]:
    """The calibration activity profile (see cost_model.REF_SPIKES_PER_GROUP)."""
    out = []
    for t in range(len(topology) - 1):
        n_groups, _ = _tile_geometry(topology[t], topology[t + 1])
        out.append(np.full((1, n_groups), cm.REF_SPIKES_PER_GROUP[t], np.float64))
    return out
