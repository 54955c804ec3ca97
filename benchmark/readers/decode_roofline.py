"""A decode step's share of its roofline, in %: the least time the
chip could take for what the step must stream and compute
(``benchmark/roofline.py``, from the configuration's widths, the rows
per step and their mean context) over the measured device time per
step.  args: ``time_metric``, ``rows_metric``."""

from benchmark import roofline


def read(args, ctx):
    step_ms = ctx.metric(args["time_metric"])
    rows = ctx.metric(args["rows_metric"])
    answered = ctx.counters.get("client.answered")
    if not step_ms or not rows or not answered:
        return None
    # Mean context of a live row: its prompt plus, on average, half of
    # what it generates.
    context = (ctx.counters["batcher.prefill_tokens"] / answered
               + ctx.workload["new_tokens"] / 2.0)
    work = roofline.decode_step(ctx.config, rows, context)
    least_s, bound = roofline.least_seconds(work, ctx.peaks)
    ctx.notes["decode_roofline"] = {
        "bound": bound, "bytes": work["bytes"],
        "operations": work["operations"], "rows": rows,
        "context_tokens": context, "least_ms": least_s * 1000.0}
    return 100.0 * least_s * 1000.0 / step_ms
