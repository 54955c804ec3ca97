"""Device time, in ms, of the programs whose name contains a string,
from the traced slice.  args: ``contains``; ``stat``: ``median`` of one
run, or ``sum`` over the slice divided by the counter ``per`` over the
same slice."""

import statistics

from benchmark import trace


def read(args, ctx):
    if ctx.cut is None:
        return None
    durations = trace.program_durations(ctx.cut, args["contains"])
    if not durations:
        return None
    if args.get("stat", "median") == "median":
        return statistics.median(durations) * 1000.0
    per = ctx.slice_counters.get(args["per"])
    return None if not per else sum(durations) * 1000.0 / per
