"""Device time, in ms, of the ops whose name starts with a string,
summed over the traced slice.  args: ``starts_with``."""

from benchmark import trace


def read(args, ctx):
    if ctx.cut is None:
        return None
    seconds = trace.op_seconds(ctx.cut, args["starts_with"])
    return seconds * 1000.0 if seconds else None
