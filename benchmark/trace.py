"""From a profiler trace to numbers: the reduction every PR shares.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into
plain lists (a "cut"); everything else works on cuts, so the tests
check it against a small recorded one (``benchmark/testdata/``).

What the trace of this program on a v5e looks like (PR 22 and PR 24
chip runs): plane ``/device:TPU:<n>`` with line ``XLA Modules`` (one
event per program run, named ``jit__decode_loop_jit(<hash>)`` ...) and
line ``XLA Ops`` (one event per HLO op, ``%fusion.265 = bf16[...]
fusion(...)``; a ``%while`` covers the ops of its body, so op events
nest); plane ``/host:CPU`` with one line per thread, where the
program's ``TraceAnnotation`` spans (``element:DET`` ...) land beside
the runtime's events -- when the host tracer is on, which stalls the
camera path (PERF.md section 5), so the harness takes the device trace
alone and gaps go unnamed until cheaper host spans exist.

A cut is ``{"devices": {plane: {"modules": [[name, start_ns, dur_ns],
...], "ops": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``.
"""

from __future__ import annotations

import glob
import os

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
# Host spans worth keeping: the program's annotations.
HOST_PREFIXES = ("element:", "segment:", "stage:", "hop:", "compile:")
NO_HOST_SPAN = "no-host-span"


def find_xplane(directory: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    cut = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            entry = cut["devices"][plane.name] = {"modules": [],
                                                  "ops": []}
            for line in plane.lines:
                kind = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(
                    line.name)
                if kind is not None:
                    # An op's name is its whole HLO line: keep the
                    # short form, a twentieth of the bytes.
                    name_of = short_name if kind == "ops" else str
                    entry[kind] = [
                        [name_of(event.name), int(event.start_ns),
                         int(event.duration_ns)]
                        for event in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for event in line.events:
                    name = event.name
                    if name.startswith(HOST_PREFIXES):
                        cut["host"].append([name, int(event.start_ns),
                                            int(event.duration_ns)])
    cut["host"].sort(key=lambda event: event[1])
    return cut


def short_name(op_name: str) -> str:
    """``%fusion.265 = bf16[...] fusion(...)`` -> ``fusion.265``."""
    return op_name.split(" = ")[0].lstrip("%")


def program_name(module_name: str) -> str:
    """``jit__decode_loop_jit(9594...)`` -> ``jit__decode_loop_jit``."""
    return module_name.split("(")[0]


def _merged(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clipped(events, lo, hi):
    for _, start, duration in events:
        begin, end = max(start, lo), min(start + duration, hi)
        if end > begin:
            yield begin, end


def window_of(cut: dict) -> tuple[int, int]:
    """The traced slice in ns: from the first device event to the end
    of the last."""
    starts, ends = [], []
    for entry in cut["devices"].values():
        for events in (entry["modules"], entry["ops"]):
            starts.extend(event[1] for event in events)
            ends.extend(event[1] + event[2] for event in events)
    if not starts:
        raise ValueError("no device event in the trace")
    return min(starts), max(ends)


def busy_intervals(entry: dict, lo: int, hi: int) -> list:
    """Where an operation ran on one device: the union of its op
    events (programs where the trace has no op line)."""
    events = entry["ops"] or entry["modules"]
    return _merged(_clipped(events, lo, hi))


def busy_and_window(cut: dict) -> tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    lo, hi = window_of(cut)
    busy = [sum(end - start for start, end in busy_intervals(entry, lo, hi))
            for entry in cut["devices"].values()]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def self_times(events) -> dict:
    """Op events of one line nest (a ``while`` covers its body): the
    time of each op that no op inside it covers, summed by short
    name."""
    totals: dict = {}
    stack = []          # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0) + max(0, own)

    for name, start, duration in sorted(
            events, key=lambda event: (event[1], -event[2])):
        close(start)
        if stack:
            stack[-1][2] -= duration
        stack.append([short_name(name), start + duration, duration])
    close(float("inf"))
    return totals


def top_device_ops(cut: dict, count: int = 10) -> list:
    """[name, seconds] of the ops with most self time, summed over the
    device planes."""
    totals: dict = {}
    for entry in cut["devices"].values():
        for name, own in self_times(entry["ops"]).items():
            totals[name] = totals.get(name, 0) + own
    ranked = sorted(totals.items(), key=lambda item: -item[1])[:count]
    return [[name, own / 1e9] for name, own in ranked]


def _covering_span(host, lo, hi):
    """The host span that covers most of ``[lo, hi)``."""
    best, best_overlap = NO_HOST_SPAN, 0
    for name, start, duration in host:
        if start >= hi:
            break
        overlap = min(start + duration, hi) - max(start, lo)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def idle_gaps(cut: dict, count: int = 10, host=None) -> list:
    """[host span, seconds] of the longest stretches in which no op
    ran on the first device, each named by the host span (of ``host``,
    sorted and on the cut's clock; by default the cut's own) that
    covers most of it."""
    lo, hi = window_of(cut)
    entry = cut["devices"][sorted(cut["devices"])[0]]
    busy = busy_intervals(entry, lo, hi)
    edges = [lo] + [edge for interval in busy for edge in interval] + [hi]
    gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    host = cut["host"] if host is None else host
    return [[_covering_span(host, start, end), length / 1e9]
            for length, start, end in gaps[:count]]


def program_durations(cut: dict, contains: str) -> list[float]:
    """Device seconds of every run (inside the window) of the programs
    whose name contains ``contains``, on the first device."""
    lo, hi = window_of(cut)
    entry = cut["devices"][sorted(cut["devices"])[0]]
    return [duration / 1e9 for name, start, duration in entry["modules"]
            if contains in program_name(name)
            and start >= lo and start + duration <= hi]


def programs_ms(cut: dict, count: int = 12) -> dict:
    """Runs, summed and median device ms of the ``count`` programs that
    took most of the first device's time."""
    import statistics
    programs: dict = {}
    entry = cut["devices"][sorted(cut["devices"])[0]]
    for name, _, duration in entry["modules"]:
        programs.setdefault(program_name(name), []).append(duration / 1e6)
    return {name: {"runs": len(runs), "sum": sum(runs),
                   "median": statistics.median(runs)}
            for name, runs in sorted(
                programs.items(), key=lambda kv: -sum(kv[1]))[:count]}


def op_seconds(cut: dict, prefix: str) -> float:
    """Device seconds (self time) of the ops whose short name starts
    with ``prefix``, summed over the device planes."""
    total = 0
    for entry in cut["devices"].values():
        for name, own in self_times(entry["ops"]).items():
            if name.startswith(prefix):
                total += own
    return total / 1e9


def cut_between(cut: dict, lo: int, hi: int) -> dict:
    """The events that start in ``[lo, hi)``: how a recorded trace is
    cut down to test data."""
    def keep(events):
        return [event for event in events if lo <= event[1] < hi]
    return {"devices": {plane: {kind: keep(events)
                                for kind, events in entry.items()}
                        for plane, entry in cut["devices"].items()},
            "host": keep(cut["host"])}
