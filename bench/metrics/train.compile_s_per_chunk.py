"""Seconds of compilation per learning chunk inside the window: the jaxpr
traces, lowerings and backend compiles (or persistent-cache loads) that
``train_online`` booked under its own ``train.*`` spans
(``esam_compile_seconds_total{span=,event=}`` of the record's ``obs``
snapshot), divided by the chunks the window ran."""

import re

NAME = "esam_compile_seconds_total"
SPAN = re.compile(r'span="(train\.[^"]*)"')


def read(rec):
    snap, chunks = rec.get("obs"), rec.get("chunks")
    if not snap or not chunks:
        return None
    secs = sum(v["value"] for k, v in snap.items()
               if k.startswith(NAME + "{") and SPAN.search(k))
    return secs / chunks
