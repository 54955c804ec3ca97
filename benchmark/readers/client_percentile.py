"""A percentile, in ms, of the client's latencies over the window.
args: ``q`` (0..100)."""

from benchmark.traffic import percentile


def read(args, ctx):
    if not ctx.client_latencies_ms:
        return None
    return percentile(ctx.client_latencies_ms, float(args["q"]))
