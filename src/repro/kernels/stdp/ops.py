"""Jit'd public wrappers for the STDP kernels.

``stdp_update`` is the full-matrix transposed-layout update (one masked
rewrite of the whole tile); ``stdp_column_event`` is one column event, one
learning neuron per call; ``stdp_column_event_epoch`` is what the
online-learning plane issues — a whole epoch of column events in one kernel,
the readout resident in VMEM (see kernel.py).  All are validated bit-exact
against the ref.py oracles and the functional rule in ``core.esam.learning``
under shared uniforms.
"""

from repro.kernels.stdp.kernel import (
    stdp_column_event,
    stdp_column_event_epoch,
    stdp_update,
)
from repro.kernels.stdp.ref import (
    column_event_epoch_ref,
    stdp_column_event_ref,
    stdp_update_ref,
)

__all__ = [
    "stdp_update",
    "stdp_update_ref",
    "stdp_column_event",
    "stdp_column_event_ref",
    "stdp_column_event_epoch",
    "column_event_epoch_ref",
]
