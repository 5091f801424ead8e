"""Jit'd public wrapper for the STDP kernel.

``stdp_column_event_epoch`` is what the online-learning plane issues — a
whole epoch of column events in one kernel, the readout resident in VMEM
(see kernel.py).  It is validated bit-exact against ``column_event_epoch_ref``,
a scan of ``stdp_column_event_ref`` column writes; that column write is in
turn tested against the functional rule in ``core.esam.learning`` under
shared uniforms.
"""

from repro.kernels.stdp.kernel import stdp_column_event_epoch
from repro.kernels.stdp.ref import column_event_epoch_ref, stdp_column_event_ref

__all__ = [
    "stdp_column_event_ref",
    "stdp_column_event_epoch",
    "column_event_epoch_ref",
]
