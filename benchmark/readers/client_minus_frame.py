"""Median over the window's requests of the client's latency minus
the engine's frame span of the same request (matched by the trace id
the result carries): what the door adds -- the WebSocket both ways,
admission, the result pump."""

import statistics

from benchmark.readers.frame_minus_spans import frame_ms


def read(args, ctx):
    added = []
    for request in ctx.requests:
        entry = ctx.frames.get(request.get("trace"))
        whole = None if entry is None else frame_ms(entry)
        if whole is not None and request["status"] == "ok":
            begun = request["due_s"] if request["due_s"] is not None \
                else request["sent_s"]
            added.append((request["recv_s"] - begun) * 1000.0 - whole)
    return statistics.median(added) if added else None
