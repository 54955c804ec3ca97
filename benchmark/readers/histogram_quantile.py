"""A quantile of a telemetry-registry histogram since the window
opened (log buckets: within 9 %).  args: ``name``, ``q`` (0..1),
optional ``labels``."""


def read(args, ctx):
    return ctx.quantile(args["name"], float(args["q"]),
                        args.get("labels"))
