"""The static cascade kernel's share of its roofline, in %.

Least time: the larger of the cascade's operations (two int8 operations per
binary MAC, 330,240 MACs per served request) over the int8 peak and its
fewest bytes (packed input spikes and output logits per request, the weight
bits once per call on every chip) over the HBM bandwidth (``bench/work.py``).
It is divided by the device time, summed over chips, of the trace's
operations named below: the single-launch popcount mega cascade and, where
a tile's columns are sharded, the per-tile popcount kernels.
Returns the share and the bound, ``"compute"`` or ``"memory"``, that
sets the least time.
"""

import trace_reduce
import work

KERNELS = ("esam_cascade_popcount", "esam_layer_popcount")


def read(rec):
    red, e = rec.get("trace"), rec.get("engine")
    if not red or not e or e["n_requests"] <= 0:
        return None
    secs, calls = trace_reduce.kernel_seconds(red, KERNELS)
    if secs <= 0:
        return None
    topo = rec["topology"]
    ops, _ = work.cascade_work(topo, e["n_requests"])
    per_req = work.spike_bytes(topo[0]) + topo[-1] * work.LOGIT_BYTES
    nbytes = (e["n_requests"] * per_req
              + calls * work.weight_bytes(topo))
    least, bound = work.least_time(ops, nbytes, rec["peaks"])
    return 100.0 * least / secs, bound
