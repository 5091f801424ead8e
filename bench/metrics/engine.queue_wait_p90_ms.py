"""How long a request waited in the engine's admission queue, from its
admission to the formation of the round that took it: the 90th percentile
over the window, in ms.  The engine observes each wait into its
``esam_request_queue_seconds`` histogram (bounds 2^(1/8) apart) when it
holds a metrics registry; the record's ``obs`` is that registry's snapshot
after the window."""

HIST = "esam_request_queue_seconds"


def read(rec):
    h = (rec.get("obs") or {}).get(HIST)
    if not h or h["count"] <= 0:
        return None
    return h["p90"] * 1e3
