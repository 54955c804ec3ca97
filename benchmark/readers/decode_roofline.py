"""A decode step's share of its roofline, in %: the least time the
chip could take for what the step must stream and compute (counted by
the configuration's architecture, ``decode_step``, from the published
widths, the rows per step and their mean context; the roofline and the
peaks are ``benchmark/roofline.py``'s) over the measured device time
per step.  The count goes to the notes wherever there are rows to
count for, the share only where the step was timed on a chip with
published peaks.  args: ``time_metric``, ``rows_metric``."""

from benchmark import roofline


def read(args, ctx):
    rows = ctx.metric(args["rows_metric"])
    answered = ctx.counters.get("client.answered")
    if not rows or not answered:
        return None
    # Mean context of a live row: its prompt plus, on average, half of
    # what it generates.
    context = (ctx.counters["batcher.prefill_tokens"] / answered
               + ctx.workload["new_tokens"] / 2.0)
    work = ctx.architecture.decode_step(ctx.config, rows, context)
    notes = ctx.notes["decode_roofline"] = {
        "bytes": work["bytes"], "operations": work["operations"],
        "rows": rows, "context_tokens": context}
    step_ms = ctx.metric(args["time_metric"])
    if not step_ms or ctx.peaks is None:
        return None
    least_s, bound = roofline.least_seconds(work, ctx.peaks)
    notes.update(bound=bound, least_ms=least_s * 1000.0)
    return 100.0 * least_s * 1000.0 / step_ms
