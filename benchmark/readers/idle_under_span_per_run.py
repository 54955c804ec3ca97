"""The chip's idle time that one thread's work explains, per run of a
program, in ms: the idle time of the traced slice lying under the host
intervals named ``under...`` (waits left out) over the number of runs
of ``program`` in the slice.  With the LLM worker's ``llm_tick:`` phases
and ``decode_loop`` it is the host time per decode block that the chip
waits for -- what is left of the worker's ticks when the time it
overlaps with a busy chip, or is held up in a launch behind one, is
taken away.  Reads the host spans that the metric ``after`` leaves in
``ctx.host`` (``idle_by_host_span``: the flight recorder laid on the
trace's clock); None where that metric has nothing."""

from benchmark import host_timeline, trace


def read(args, ctx):
    if ctx.cut is None or ctx.metric(args["after"]) is None:
        return None
    entry = ctx.cut["devices"][sorted(ctx.cut["devices"])[0]]
    runs = sum(1 for name, _, _ in entry["modules"]
               if args["program"] in trace.program_name(name))
    if not runs:
        return None
    by_span = host_timeline.idle_by_span(
        host_timeline.idle_gaps(ctx.cut), ctx.host)
    under = sum(own for name, own in by_span.items()
                if name.startswith(args["under"])
                and not host_timeline.is_wait(name))
    ctx.notes["idle_under_" + args["under"].rstrip(":")] = {
        "idle_ms": under / 1e6, "runs": runs}
    return under / 1e6 / runs
