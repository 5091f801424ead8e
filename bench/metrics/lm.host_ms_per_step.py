"""Host time the LM engine spends per decode step outside the per-step pull,
in ms: the seconds of ``Engine.stats()``'s ``admit`` (queue, padding,
upload), ``prefill`` and ``decode`` (the dispatch calls) and ``retire``
(appending the pulled tokens, the stop rule, retirement) phases over the
window, divided by the decode steps.  This is the host's part of the time
between one step's pull and the next step's decode on the device.  The pull
(``drain``) is left out: it waits for the device, whose time the trace's
``busy_s`` holds."""


def read(rec):
    lm = rec.get("lm")
    if not lm or lm["decode_steps"] <= 0:
        return None
    host = sum(lm["host_s"][p] for p in ("admit", "prefill", "decode", "retire"))
    return host / lm["decode_steps"] * 1e3
