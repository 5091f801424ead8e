"""ESAM core: the paper's contribution as a composable JAX module.

Planes:
  * functional (batched, MXU-friendly): ``EsamNetwork.forward`` — bit-exact
    with the event-driven plane; this is what the TPU kernels accelerate.
  * packed fused (bit-plane wire format): ``EsamNetwork.forward_fused`` —
    spikes travel between tiles as uint32 bitplanes (32 spikes/word, the
    paper's parallel-pulse bus) through the kernels/cim_popcount
    cascade; logits bit-identical to ``forward``.
  * cycle-accurate (event-driven): ``EsamNetwork.forward_cycle_accurate``
    (+ ``_batch``) + ``system_stats`` — reproduces the paper's
    throughput/energy/power claims from the calibrated 3nm cost model.
"""

from repro.core.esam import arbiter, bnn, conversion, cost_model, faults, learning, neuron, network, plan, tile
from repro.core.esam.faults import FaultModel
from repro.core.esam.network import EsamNetwork, SystemStats, reference_activity, system_stats
from repro.core.esam.plan import EsamPlan, PlanResult, PlanSpec

__all__ = [
    "arbiter",
    "bnn",
    "conversion",
    "cost_model",
    "faults",
    "FaultModel",
    "learning",
    "neuron",
    "network",
    "plan",
    "tile",
    "EsamNetwork",
    "EsamPlan",
    "PlanResult",
    "PlanSpec",
    "SystemStats",
    "system_stats",
    "reference_activity",
]
