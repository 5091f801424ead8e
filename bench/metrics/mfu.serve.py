"""The whole step's share of the chips' int8 peak, in %: two int8
operations per binary MAC of the work completed in the window (330,240 MACs
per static inference, per stream timestep, or per learning sample), per
second of the window, over chips x peak."""


def read(rec):
    if not rec.get("macs") or not rec.get("window_s"):
        return None
    rate = 2.0 * rec["macs"] / rec["window_s"]
    return 100.0 * rate / (rec["chips"] * rec["peaks"]["int8_ops_per_s"])
