"""The LM's share of its roofline over the traced window, in %.

Least time: for every prefill call and decode step the window ran, the
larger of its FLOPs over the bf16 peak and its fewest bytes over the HBM
bandwidth (``bench/lm_work.py``: the held weights once a call, the Mamba2
state read and written, the KV rows read up to each sequence's length),
summed.  It is divided by the device's busy time in the trace, so time the
device spends on anything the model does not need (padding, layout copies,
slots with no sequence) lowers the share, and idle time does not.
Returns the share and the bound, ``"compute"`` or ``"memory"``, that sets
most of the least time.
"""

import lm_work


def read(rec):
    red, lm = rec.get("trace"), rec.get("lm")
    if not red or not lm or red["busy_s"] <= 0:
        return None
    least, bound = lm_work.window_least_time(lm_work.shapes(rec["config"]), lm,
                                             rec["peaks"])
    return 100.0 * least / red["busy_s"], bound
