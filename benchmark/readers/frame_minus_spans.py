"""Median over the window's frames of a frame's own span minus the sum
of its spans of one kind: a frame's time outside its elements.
args: ``kind`` (``element``)."""

import statistics


def frame_ms(entry):
    """The duration of the frame's root span, None if it has none."""
    for span in entry["spans"]:
        if span["kind"] == "frame":
            return span["duration_ms"]
    return None


def read(args, ctx):
    outside = []
    for entry in ctx.frames.values():
        whole = frame_ms(entry)
        if whole is not None:
            outside.append(whole - sum(
                span["duration_ms"] for span in entry["spans"]
                if span["kind"] == args["kind"]))
    return statistics.median(outside) if outside else None
