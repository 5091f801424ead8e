"""Share of the prompt positions the LM engine prefilled that were padding,
in %: ``(prefill_bucket_tokens - prefill_tokens) / prefill_bucket_tokens``
over the window, from ``Engine.stats()``.  Each prompt is right-padded to a
power-of-two bucket, and a prefill holds up every slot's next decode step."""


def read(rec):
    lm = rec.get("lm")
    if not lm or lm["prefill_bucket_tokens"] <= 0:
        return None
    return 100.0 * (lm["prefill_bucket_tokens"] - lm["prefill_tokens"]) / lm[
        "prefill_bucket_tokens"]
