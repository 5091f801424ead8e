"""The temporal plan's kernels' share of their roofline, in %.

Least time: every timestep of every served stream is one full cascade (two
int8 operations per binary MAC, 330,240 MACs per step); the fewest bytes are
the packed event planes in, the logits out, and the weight bits once per
plan call (``bench/work.py``).  It is divided by the device time of the
trace's operations named below: the popcount MAC and the LIF update
kernels of the temporal scan.
Returns the share and the bound, ``"compute"`` or ``"memory"``, that
sets the least time.
"""

import trace_reduce
import work

KERNELS = ("cim_popcount_matmul", "lif_step")


def read(rec):
    red, e = rec.get("trace"), rec.get("engine")
    if not red or not e or e["timesteps_total"] <= 0:
        return None
    secs, _calls = trace_reduce.kernel_seconds(red, KERNELS)
    if secs <= 0:
        return None
    topo = rec["topology"]
    ops = work.OPS_PER_MAC * e["timesteps_total"] * work.macs_per_inference(topo)
    nbytes = (e["timesteps_total"] * work.spike_bytes(topo[0])
              + e["n_event_requests"] * topo[-1] * work.LOGIT_BYTES
              + e["rounds_event"] * work.weight_bytes(topo))
    least, bound = work.least_time(ops, nbytes, rec["peaks"])
    return 100.0 * least / secs, bound
