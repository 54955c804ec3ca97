"""A kernel's share of its roofline, in %: the least time the chip
could take for what the kernel's calls in the traced slice must stream
and compute, over the device time of the ops whose name starts with a
string.  The count is the configuration's architecture's (the function
``count`` of ``benchmark/architectures/<name>.py``: ``(config, rows,
context_tokens) -> {"bytes", "operations"}`` for ONE step's calls), at
the rows a step ran with and their mean context, times the steps the
batcher counted in the slice; the roofline and the peaks are
``benchmark/roofline.py``'s.  Nothing where the program has no such
op, the architecture no such count, or the chip no published peaks.
args: ``starts_with``, ``count``, ``rows_metric``, ``steps_counter``."""

from benchmark import roofline, trace


def read(args, ctx):
    count = getattr(ctx.architecture, args["count"], None)
    if ctx.cut is None or ctx.peaks is None or count is None:
        return None
    seconds = trace.op_seconds(ctx.cut, args["starts_with"])
    steps = ctx.slice_counters.get(args["steps_counter"])
    rows = ctx.metric(args["rows_metric"])
    answered = ctx.counters.get("client.answered")
    if not (seconds and steps and rows and answered):
        return None
    # Mean context of a live row: its prompt plus, on average, half of
    # what it generates (as ``decode_roofline`` takes it).
    context = (ctx.counters["batcher.prefill_tokens"] / answered
               + ctx.workload["new_tokens"] / 2.0)
    work = count(ctx.config, rows, context)
    least_s, bound = roofline.least_seconds(work, ctx.peaks)
    ctx.notes[args["count"]] = {
        "bytes": work["bytes"], "operations": work["operations"],
        "rows": rows, "context_tokens": context, "steps": steps,
        "bound": bound, "least_ms": least_s * 1000.0,
        "op_ms_per_step": seconds * 1000.0 / steps}
    return 100.0 * least_s * steps / seconds
