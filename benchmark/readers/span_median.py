"""Median duration, in ms, of one of the engine's spans over the frames
that finished inside the window (exact stamps from the telemetry
trace buffer).  args: ``span`` (``element:DET`` ...)."""

import statistics


def durations(frames, name):
    return [span["duration_ms"] for entry in frames.values()
            for span in entry["spans"] if span["name"] == name]


def read(args, ctx):
    found = durations(ctx.frames, args["span"])
    return statistics.median(found) if found else None
