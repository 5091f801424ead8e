"""Work and byte counts of the ESAM kernels, from shapes alone, and the
roofline arithmetic.

The counts are of what the algorithm needs, whatever implements it: a binary
multiply-accumulate of a {0,1} spike with a ±1 weight counts as the two int8
operations the chip's int8 peak is quoted for, and the fewest bytes a call
can move are its packed input spikes, the weight bits (once per call) and
its outputs.  A later kernel that computes the same thing another way is
judged on the same yardstick.
"""

from __future__ import annotations

import json
import os
from typing import Sequence

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

OPS_PER_MAC = 2          # one binary MAC = two int8 operations
LOGIT_BYTES = 4          # one float32 logit out per class


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``.  A kind missing
    from the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def macs_per_inference(topology: Sequence[int]) -> int:
    """Synapses of the network: one MAC each per static inference."""
    return sum(a * b for a, b in zip(topology[:-1], topology[1:]))


def weight_bytes(topology: Sequence[int]) -> int:
    """All weight bits, packed 8 to a byte."""
    return macs_per_inference(topology) // 8


def spike_bytes(n: int) -> int:
    """One packed spike plane of n inputs."""
    return -(-n // 8)


def cascade_work(topology: Sequence[int], rows: int) -> tuple[int, int]:
    """(ops, bytes) of one static cascade call over ``rows`` requests."""
    ops = OPS_PER_MAC * rows * macs_per_inference(topology)
    nbytes = (rows * spike_bytes(topology[0]) + weight_bytes(topology)
              + rows * topology[-1] * LOGIT_BYTES)
    return ops, nbytes


def temporal_work(topology: Sequence[int], rows: int,
                  n_steps: int) -> tuple[int, int]:
    """(ops, bytes) of one temporal call: ``rows`` streams of ``n_steps``
    planes.  Every step is one full cascade; the membrane stays on chip."""
    ops = OPS_PER_MAC * rows * n_steps * macs_per_inference(topology)
    nbytes = (rows * n_steps * spike_bytes(topology[0])
              + weight_bytes(topology) + rows * topology[-1] * LOGIT_BYTES)
    return ops, nbytes


def stdp_work(n_in: int) -> tuple[int, int]:
    """(ops, bytes) of one column event: read and write one learning
    neuron's n_in weight bits and read the packed pre-synaptic trace.  The
    decision per synapse is a compare and a select, counted as two ops."""
    return 2 * n_in, 3 * spike_bytes(n_in)


def learn_macs_per_sample(topology: Sequence[int]) -> int:
    """MACs of one online-learning sample: the frozen hidden tiles and the
    readout membrane that picks the learning event."""
    return macs_per_inference(topology)


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
