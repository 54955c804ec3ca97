"""The share of the traced slice's idle time, in %, during which some
thread of the program was working (not waiting): how much of the chip's
idleness the host explains.  Lays the program's host timeline (the
flight recorder's ring) on the device trace's clock
(``benchmark/host_timeline.py`` says how, and why no host clock does
it), splits the gaps ``trace.busy_intervals`` leaves on the first
device by the leaf interval they fall under -- work before wait, so a
gap in which the LLM worker waited while the micro-batcher uploaded
frames goes to the upload -- and leaves those intervals in
``ctx.host``, from which the harness names each gap of its
``breakdown``.  args: ``program`` (the program whose runs a phase
returns at), ``sync`` (those phases: the edge each returns at,
``start`` or ``end``, and the least ``ms`` for one to have blocked),
``idle_before_ms`` (a ``start`` edge is paired only where the chip had
run no program for that long), ``tiled`` (the prefix of one thread's
phases, whose coverage of the window goes to the notes) and the
alignment's thresholds ``inlier_ms``, ``spread_ms``, ``least_pairs``,
``rival_share``.  The bracket is the harness's stamp around
``start_trace`` (``ctx.trace_stamp``), cut to ``anchor_margin_s`` after
the call was entered.  None, with the reason in the notes, when the
alignment is refused."""

from benchmark import host_timeline, trace


def read(args, ctx):
    ring = host_timeline.live_recorder()
    if ctx.cut is None or ring is None or ctx.trace_stamp is None:
        return None
    entered, returned = ctx.trace_stamp
    held, full = ring.intervals()
    intervals = [interval for interval in held
                 if not interval[0].startswith(host_timeline.SPAN_PREFIXES)]
    tiles = host_timeline.tiling(intervals, args["tiled"], *ctx.window)
    if tiles is not None:
        ctx.notes[args["tiled"].rstrip(":")] = tiles
    lo, hi = trace.window_of(ctx.cut)
    entry = ctx.cut["devices"][sorted(ctx.cut["devices"])[0]]
    runs = host_timeline.program_runs(entry["modules"], args["program"])
    pairs = host_timeline.sync_pairs(
        runs, intervals, args["sync"], float(args["idle_before_ms"]))
    notes = host_timeline.align(
        pairs, bounds=(entered, min(returned, entered + float(
            args["anchor_margin_s"]))),
        inlier_ms=float(args["inlier_ms"]),
        spread_ms=float(args["spread_ms"]),
        least_pairs=int(args["least_pairs"]),
        rival_share=float(args["rival_share"]))
    ctx.notes["host_timeline"] = notes
    notes["runs"] = len(runs)
    notes["runs_after_idle"] = sum(
        idle * 1000.0 >= float(args["idle_before_ms"])
        for _, _, idle in runs)
    notes["ring"] = ring.stats
    offset = notes["offset_s"]
    if offset is None:
        return None
    if full and held[0][1] + held[0][2] > lo / 1e9 + offset:
        notes["offset_s"] = None
        notes["refused"] = "the ring wrapped past the slice's start"
        return None
    segments = [[name, round(start), round(duration)]
                for name, start, duration in host_timeline.flatten(
                    [[name, (start - offset) * 1e9, duration * 1e9]
                     for name, start, duration in intervals], lo, hi)]
    gaps = host_timeline.idle_gaps(ctx.cut)
    by_span = host_timeline.idle_by_span(gaps, segments)
    idle = sum(end - start for start, end in gaps)
    if not idle:
        return None
    notes["idle_ms"] = idle / 1e6
    notes["idle_ms_by_span"] = {
        name: own / 1e6 for name, own in sorted(
            by_span.items(), key=lambda item: -item[1])[:16]}
    notes["idle_named_share"] = \
        1.0 - by_span.get(host_timeline.NO_SPAN, 0) / idle
    ctx.host = segments
    return 100.0 * sum(own for name, own in by_span.items()
                       if name != host_timeline.NO_SPAN
                       and not host_timeline.is_wait(name)) / idle
