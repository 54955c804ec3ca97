"""The share of the traced slice, in %, in which no operation ran on
the device (averaged over the chips used)."""

from benchmark import trace


def read(args, ctx):
    if ctx.cut is None:
        return None
    busy_s, window_s = trace.busy_and_window(ctx.cut)
    return 100.0 * (1.0 - busy_s / window_s)
