"""The program's host timeline beside the device trace.

The program keeps ONE host timeline: the flight recorder's ring
(``aiko_services_tpu/observability/recorder.py``), whose duration
events -- the LLM worker's tick phases ``llm_tick:<phase>``, the
micro-batcher's halves ``mb_run:<element>`` / ``mb_finish:<element>``,
``fetch:<element>``, ``dispatch_done:<element>``, ``build:<function>``,
``gc:<generation>`` ... -- come out of ``FlightRecorder.intervals()`` as
``[name, start, duration]`` in ``time.perf_counter()`` seconds.  The
functions here lay them on the clock of a cut (``benchmark/trace.py``).

**Which clock the trace is on** (my chip run, PR 26: a probe that took
``time.time_ns()``, ``time.monotonic_ns()``, ``time.perf_counter_ns()``
and ``CLOCK_BOOTTIME`` around a device-only trace and printed
``trace.window_of(cut)`` beside them): none of the host's.  The
``start_ns`` that ``ProfileData`` hands out count from the start of the
profiler session -- the first program of the probe began at 45.6 ms, and
zero fell 1.9 ms after ``jax.profiler.start_trace`` was entered -- and
the program cannot know when the harness made that call.  So no clock
converts, and the alignment has two parts.

**Coarse: the stamp around ``start_trace``** (PR 28).  The harness
takes ``perf_counter`` just before it enters
``jax.profiler.start_trace`` and just after the call returns, and the
profiler session starts inside the call: the offset (perf_counter minus
trace time) lies between the two stamps.  The call takes 48-54 ms, and
the session starts early in it (0.7-2.1 ms after entry in the probes
above, 0.7-1.5 ms in four ``camera-paced`` runs of PR 28), so the
reader cuts the bracket to ``anchor_margin_s`` after entry: 15 ms,
where the window's last results gave one of a tick (PR 26: 92-97 ms in
``camera-paced``, which after PR 27's shorter ticks could hold the
wrong block and miss the right one).  Only differences inside the
bracket are looked at, so the fine part is a check more than a search.

**Fine: the program's own work** (my chip runs, PR 26, both cells).
ISSUE 26 expected a ``retire_wait`` that blocked to end just after its
``decode_loop`` program ends.  It does (1.4 ms after, in
``chat-batch``), but in steady state few block: the worker is held up
in its launches instead (the runtime lets 32 be outstanding; PERF.md
section 6), arrives at the block's enqueue with the chip idle, and the
``dispatch`` phase returns 0.0-0.3 ms after the ``decode_loop`` block
it enqueued STARTS on the device (checked in both cells against a stamp
taken around ``jax.profiler.start_trace`` in a scratch harness: the
offsets found here put the trace's zero 0.7 and 1.9 ms after that call
was entered, as the probe did).  So the alignment takes each family of
worker phases (``sync``: name, the edge its calls return at, and the
least ``ms`` for one to count as having blocked) --
``llm_tick:dispatch`` the program's start, ``llm_tick:retire_wait``
its end -- and looks for the one offset at which many phase ends fall
just after such an edge: the differences ``phase end - program edge``
of all pairs are sorted, and the densest stretch of ``inlier_ms`` wins.
Its smallest difference is the offset; the distance from there to the
fifth-smallest bounds the error (what a call's return lags its edge by
is inside it; the larger differences are the interpreter lock and the
copy, not the clock).  At a wrong offset only chance pairs line up,
except where ticks are periodic and the bracket holds two of them: a
second stretch with over ``rival_share`` as many pairs is refused as
ambiguous (with every phase the ring held on offer, one seed in three
of ``camera-paced`` had a rival of 7 pairs against 9, one tick away).
(Pairing a phase with BOTH edges was tried and is ambiguous by
construction in ``camera-paced``, whose blocks all last 43.6 ms: an end
looks like a start one block later.)

**Which blocks are paired** (PR 28).  The premise -- the worker
reaches the block's enqueue with the chip idle -- fails for a block
that starts back to back with the program before it: there the worker
was ahead of the chip and its ``dispatch`` returned before the block
started (after PR 27 shortened admission, 3 blocks of 12 in a
``camera-paced`` slice; ``chat-batch`` has had some all along).  So a
``start`` edge is on offer only where the chip had been idle for
``idle_before_ms`` before it (``program_runs`` reads that off the
cut's programs: 1.0-2.1 ms where the premise holds, 0.004-0.26 ms
where it does not; my chip runs, PR 27 and PR 28).  An ``end`` edge
needs no such rule: a ``retire_wait`` that blocked returns after its
block's end whoever was ahead.

The offset is refused (``None``, with the reason in the notes) with
fewer than ``least_pairs`` pairs inside the bracket, a distance over
``spread_ms``, such a rival, or a ring that wrapped past the slice's
start.  Every threshold comes from the metric's file
(``benchmark/layer_metrics/device.idle_host_bound_share.json``).
"""

from __future__ import annotations

from benchmark import trace

# A thread that is in one of these is waiting, not working.  The
# worker's launch phases (``llm_tick:prefill``, ``:fold``, ``:dispatch``)
# are not among them: a launch blocks only while the chip still has 32
# programs to run (PERF.md section 6), so wherever the chip is IDLE
# under one, the worker is launching, not waiting.
WAIT_PREFIXES = ("llm_tick:wait_work", "llm_tick:retire_wait", "fetch:")
# These stop or hold whichever thread they happen on.
GLOBAL_PREFIXES = ("gc:", "build:")
# These are spans of a whole request (park to resume, ingest to done, a
# remote round trip): they cover everything and explain nothing.
SPAN_PREFIXES = ("resume:", "done:", "response:")
NO_SPAN = trace.NO_HOST_SPAN


def live_recorder():
    """The process's busiest flight recorder, or None: under
    ``recorder: off``, and on a program from before PR 26, which has no
    ``live_recorders`` (the driver lays these files over the parent
    commit too, where a reader must find nothing and not raise)."""
    from aiko_services_tpu.observability import recorder
    found = getattr(recorder, "live_recorders", list)()
    return max(found, key=lambda ring: ring.recorded) if found else None


def is_wait(name: str) -> bool:
    return name.startswith(WAIT_PREFIXES)


def program_runs(modules, program: str) -> list[tuple]:
    """``(start_s, end_s, idle_before_s)`` of every run of the programs
    whose name contains ``program``, in trace seconds; the last is how
    long the device had run no program at all when this one started
    (nought for the slice's first, whose past is not in the cut)."""
    runs, busy_until = [], None
    for name, start, duration in sorted(modules,
                                        key=lambda event: event[1]):
        if program in trace.program_name(name):
            idle = 0 if busy_until is None else max(0, start - busy_until)
            runs.append((start / 1e9, (start + duration) / 1e9,
                         idle / 1e9))
        busy_until = max(busy_until or 0, start + duration)
    return runs


def sync_pairs(runs, intervals, sync: dict, idle_before_ms: float) -> list:
    """What ``align`` pairs, per family of ``sync`` (phase name ->
    ``[edge, least ms]``): the trace seconds of that edge of the
    program's ``runs`` (``program_runs``) -- a ``start`` only where
    the chip had been idle ``idle_before_ms`` before it -- and the
    perf_counter seconds at which the family's phases of at least
    ``least ms`` ended."""
    after_idle = [start for start, _, idle in runs
                  if idle * 1000.0 >= idle_before_ms]
    return [([end for _, end, _ in runs] if edge == "end" else after_idle,
             [start + duration for name, start, duration in intervals
              if name == family and duration * 1000.0 >= least_ms])
            for family, (edge, least_ms) in sync.items()]


def align(pairs, *, bounds, inlier_ms, spread_ms, least_pairs,
          rival_share) -> dict:
    """The offset (perf_counter minus trace time), among those inside
    ``bounds``, at which most of the host's returns fall within
    ``inlier_ms`` after the program edge they return at.  ``pairs``:
    ``(edges_s, syncs_s)`` per family of phases -- trace seconds of
    that family's edge of every run of the program, perf_counter
    seconds of its phases' ends.  ``offset_s`` is None when refused,
    with ``refused`` saying why; the rest says how well it is known."""
    report = {"offset_s": None, "bounds_s": list(bounds),
              "edges": sum(len(edges) for edges, _ in pairs),
              "syncs": sum(len(syncs) for _, syncs in pairs),
              "pairs": 0, "spread5_ms": None, "runner_up_pairs": 0}
    differences = sorted(
        sync - edge for edges, syncs in pairs
        for sync in syncs for edge in edges
        if bounds[0] <= sync - edge <= bounds[1])
    width = inlier_ms / 1000.0
    stretches = []              # (pairs, index of the first)
    last = 0
    for first, floor in enumerate(differences):
        while last < len(differences) \
                and differences[last] - floor <= width:
            last += 1
        stretches.append((last - first, first))
    if not stretches or max(stretches)[0] < least_pairs:
        report["pairs"] = max(stretches)[0] if stretches else 0
        report["refused"] = (f"{report['pairs']} returns within "
                             f"{inlier_ms} ms after a program edge at "
                             f"the best offset, fewer than {least_pairs}")
        return report
    pairs, first = max(stretches, key=lambda found: (found[0], -found[1]))
    floor = differences[first]
    spread = (differences[first + least_pairs - 1] - floor) * 1000.0
    report.update(pairs=pairs, spread5_ms=spread, runner_up_pairs=max(
        (count for count, index in stretches
         if abs(differences[index] - floor) > width), default=0))
    if spread > spread_ms:
        report["refused"] = (f"the {least_pairs} smallest differences "
                             f"span {spread:.3f} ms, over {spread_ms}")
    elif report["runner_up_pairs"] > rival_share * pairs:
        report["refused"] = (f"ambiguous: another offset lines up "
                             f"{report['runner_up_pairs']} pairs "
                             f"against {pairs}")
    else:
        report["offset_s"] = floor
    return report


def tiling(intervals, prefix: str, lo: float, hi: float) -> dict | None:
    """How completely one thread's phases (the intervals named
    ``prefix...``) tile its time inside ``[lo, hi]``: ``coverage`` is
    their summed length over the stretch from the first one's start to
    the last one's end (``covered_s``: what the ring still holds of the
    window), ``phase_ms`` the sum by phase."""
    phases = [(name[len(prefix):], start, duration)
              for name, start, duration in intervals
              if name.startswith(prefix) and start >= lo
              and start + duration <= hi]
    if not phases:
        return None
    begin = min(start for _, start, _ in phases)
    end = max(start + duration for _, start, duration in phases)
    by_phase: dict = {}
    for name, _, duration in phases:
        by_phase[name] = by_phase.get(name, 0.0) + duration * 1000.0
    return {"covered_s": end - begin, "window_s": hi - lo,
            "coverage": sum(duration for _, _, duration in phases)
            / (end - begin) if end > begin else 1.0,
            "phase_ms": by_phase}


def _thread_of(name: str, micro_batched: set) -> str:
    etype, _, rest = name.partition(":")
    if etype == "llm_tick":
        return "llm"
    if etype in ("mb_run", "mb_finish"):
        return "mb:" + rest
    if etype == "fetch" and rest in micro_batched:
        return "mb:" + rest
    if name.startswith(GLOBAL_PREFIXES):
        return "*"
    return "loop"


def _owner(active, micro_batched):
    """Who a moment belongs to: each thread is in its innermost open
    interval; a collection or a build takes it whatever else runs; a
    working thread goes before a waiting one; the latest start breaks
    ties."""
    state: dict = {}
    for name, start in active:
        thread = _thread_of(name, micro_batched)
        if thread not in state or start > state[thread][0]:
            state[thread] = (start, name)
    if "*" in state:
        return state["*"][1]
    working = [entry for entry in state.values() if not is_wait(entry[1])]
    return max(working or state.values())[1]


def flatten(intervals, lo, hi) -> list[list]:
    """``[name, start, duration]`` intervals of several threads, nested
    and overlapping, as disjoint leaf segments inside ``[lo, hi)``
    sorted by start (the shape ``trace._covering_span`` expects of its
    host spans): work goes before wait, so a wait is entered only
    where no other thread works."""
    micro_batched = {name.partition(":")[2] for name, _, _ in intervals
                     if name.startswith(("mb_run:", "mb_finish:"))}
    edges = []
    for index, (_, start, duration) in enumerate(intervals):
        begin, end = max(start, lo), min(start + duration, hi)
        if end > begin:
            edges.append((begin, 1, index))
            edges.append((end, 0, index))
    edges.sort()
    active: dict = {}
    segments: list[list] = []
    previous = None
    for at, opens, index in edges:
        if active and at > previous:
            name = _owner(active.values(), micro_batched)
            if segments and segments[-1][0] == name \
                    and segments[-1][1] + segments[-1][2] == previous:
                segments[-1][2] = at - segments[-1][1]
            else:
                segments.append([name, previous, at - previous])
        if opens:
            active[index] = (intervals[index][0], intervals[index][1])
        else:
            del active[index]
        previous = at
    return segments


def idle_gaps(cut: dict) -> list[tuple[int, int]]:
    """The stretches of the slice in which no op ran on the first
    device, as ``(start_ns, end_ns)``."""
    lo, hi = trace.window_of(cut)
    entry = cut["devices"][sorted(cut["devices"])[0]]
    busy = trace.busy_intervals(entry, lo, hi)
    edges = [lo] + [edge for interval in busy for edge in interval] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(gaps, segments) -> dict:
    """Idle ns under each segment's name (``NO_SPAN`` for the rest);
    ``segments`` disjoint and sorted, as ``flatten`` leaves them."""
    totals: dict = {}
    first = 0
    for gap_lo, gap_hi in gaps:
        while first < len(segments) \
                and segments[first][1] + segments[first][2] <= gap_lo:
            first += 1
        named = 0
        for name, start, duration in segments[first:]:
            if start >= gap_hi:
                break
            overlap = min(start + duration, gap_hi) - max(start, gap_lo)
            if overlap > 0:
                totals[name] = totals.get(name, 0) + overlap
                named += overlap
        if gap_hi - gap_lo > named:
            totals[NO_SPAN] = totals.get(NO_SPAN, 0) \
                + gap_hi - gap_lo - named
    return totals
