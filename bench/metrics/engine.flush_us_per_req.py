"""Host time the engine spends in its flush per served request, in
microseconds: the seconds of the program's ``engine.flush`` spans inside
the traced window (every device-to-host pull of each round, then the
per-request attach and the telemetry folds), divided by the requests
(static and event) the engine served in the window."""


def read(rec):
    red, e = rec.get("trace"), rec.get("engine")
    if not red or not e:
        return None
    secs = red.get("span_s", {}).get("engine.flush")
    n = e["n_requests"] + e["n_event_requests"]
    if secs is None or n <= 0:
        return None
    return secs / n * 1e6
