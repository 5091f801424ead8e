"""The whole step's share of the chips' bf16 peak, in %: two FLOPs per
multiply-accumulate of the prompt and generated tokens the window processed
(``bench/lm_work.py``: the held share of every layer, attention over earlier
positions, the logits), per second of the window, over chips x peak."""

import lm_work


def read(rec):
    lm = rec.get("lm")
    if not lm or not rec.get("window_s"):
        return None
    flops = 2.0 * lm_work.window_macs(lm_work.shapes(rec["config"]), lm)
    return 100.0 * flops / rec["window_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
