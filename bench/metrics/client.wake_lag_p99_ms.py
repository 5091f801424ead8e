"""How late the open-loop client woke for a due arrival after it had slept:
the 99th percentile, in ms.  This is the generator's own lateness (a starved
client), not queueing: arrivals that fall due while a serve runs are not
counted.  A client that never slept was never idle, so never late waking:
0."""

import numpy as np


def read(rec):
    lags = rec.get("wake_lags_s")
    if lags is None:
        return None
    if len(lags) == 0:
        return 0.0
    return float(np.percentile(np.asarray(lags), 99.0) * 1e3)
