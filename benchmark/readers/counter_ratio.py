"""A program counter over the window, or the ratio of two.
args: ``numerator``, optional ``denominator`` (counter names)."""


def read(args, ctx):
    value = ctx.counters.get(args["numerator"])
    if value is None:
        return None
    if "denominator" not in args:
        return float(value)
    below = ctx.counters.get(args["denominator"])
    return None if not below else value / below
