"""The STDP column-event kernel's share of its roofline, in %.

Least time: every column update the learning rule applied (two per wrongly
classified sample, ``n_updates`` of ``train_online``) reads and writes one
neuron's weight bits and reads the packed pre-synaptic trace
(``bench/work.stdp_work``); the bytes bound it.  It is divided by the
device time of the trace's operations named below.
Returns the share and the bound, ``"compute"`` or ``"memory"``, that
sets the least time.
"""

import trace_reduce
import work

KERNELS = ("stdp_column_event",)


def read(rec):
    red = rec.get("trace")
    n_upd = rec.get("column_updates")
    if not red or not n_upd:
        return None
    secs, _calls = trace_reduce.kernel_seconds(red, KERNELS)
    if secs <= 0:
        return None
    ops, nbytes = work.stdp_work(rec["topology"][-2])
    least, bound = work.least_time(ops * n_upd, nbytes * n_upd, rec["peaks"])
    return 100.0 * least / secs, bound
