"""Host time the engine spends per served request, in microseconds: its own
packing and dispatch-call counters (``host_pack_s_total``,
``dispatch_s_total`` of ``SpikeEngine.stats()``) over the window, divided by
the requests (static and event) it served.  Dispatch is asynchronous, so the
dispatch part is enqueue time."""


def read(rec):
    e = rec.get("engine")
    if not e:
        return None
    n = e["n_requests"] + e["n_event_requests"]
    if n <= 0:
        return None
    return (e["host_pack_s_total"] + e["dispatch_s_total"]) / n * 1e6
