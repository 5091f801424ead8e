"""How long an LM request waited for a decode slot, from its admission to
the engine to the prefill that put it in a slot: the 90th percentile over
the window's requests, in ms (``queue_wait_s`` of ``Engine.stats()``, the
same waits the engine observes into ``lm_request_queue_seconds``)."""

import numpy as np


def read(rec):
    waits = (rec.get("lm") or {}).get("queue_wait_s")
    if waits is None or len(waits) == 0:
        return None
    return float(np.percentile(np.asarray(waits), 90.0) * 1e3)
