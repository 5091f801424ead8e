"""Share of the rows the engine dispatched that were padding, in %:
``rows_padded / (rows_real + rows_padded)`` over the window, from
``SpikeEngine.stats()``.  Small rounds padded up to a bucket show here."""


def read(rec):
    e = rec.get("engine")
    if not e:
        return None
    rows = e["rows_real_total"] + e["rows_padded_total"]
    if rows <= 0:
        return None
    return 100.0 * e["rows_padded_total"] / rows
