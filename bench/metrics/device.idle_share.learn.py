"""Share of the traced window in which no operation ran on the device, in
%: one minus the union of the device's operation intervals over the window,
per chip, averaged over the chips (``bench/trace_reduce.py``)."""


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
