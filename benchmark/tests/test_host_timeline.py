"""The host timeline beside the device trace (``benchmark/
host_timeline.py`` and the two readers built on it): a synthetic cut
and a synthetic ring with a known offset and jitter.  Nothing here needs
a device."""

import random

import pytest

from aiko_services_tpu.observability.recorder import FlightRecorder
from benchmark import host_timeline, run, trace

OFFSET_S = 39255.7          # perf_counter minus trace time
# The thresholds live in the metric's file and nowhere else.
ARGS = run.layer_metric("device.idle_host_bound_share")["args"]
PER_BLOCK = run.layer_metric("batcher.host_ms_per_block")["args"]
LIMITS = {"bounds": (float("-inf"), float("inf")),
          **{key: ARGS[key] for key in ("inlier_ms", "spread_ms",
                                        "least_pairs", "rival_share")}}
SLICE_S = 2.5               # the harness starts the trace this long
#                             before the window ends
WINDOW_END_S = OFFSET_S + SLICE_S - 0.003   # ... and 3 ms late
# The harness's stamps around ``start_trace``: entered 1.2 ms before the
# profiler session began (the trace's zero), returned 50 ms after.
STAMP = (OFFSET_S - 0.0012, OFFSET_S + 0.0500)


def _blocks(count, seed=3):
    """``count`` decode blocks, nearly periodic (44 ms) with a few ms
    of the host's own time and, before some, a prefill chunk (18 ms)
    between them: trace-clock (start_s, end_s) of each."""
    rng = random.Random(seed)
    at, blocks = 0.050, []
    for _ in range(count):
        at += 0.002 + 0.006 * rng.random() \
            + (0.018 if rng.random() < 0.4 else 0.0)
        blocks.append((at, at + 0.044))
        at += 0.044
    return blocks


def _returns(edges, before=7, after=5, jitter_ms=0.2, latency_ms=0.08,
             seed=5):
    """One blocked return per program edge, ``latency + jitter`` after
    it on the perf_counter clock, with ``before`` / ``after`` returns
    of blocks outside the slice around them."""
    rng = random.Random(seed)
    period = 0.05
    times = [edges[0] - period * (before - index) for index in range(before)] \
        + list(edges) \
        + [edges[-1] + period * (index + 1) for index in range(after)]
    return [at + OFFSET_S + (latency_ms + rng.random() * jitter_ms) / 1000.0
            for at in times]


def _pairs(blocks, **options):
    """Dispatches return at their block's start; every third block's
    fetch blocks and returns 1.2 ms after the block's end."""
    starts = [start for start, _ in blocks]
    ends = [end for _, end in blocks]
    return [(starts, _returns(starts, **options)),
            (ends, [end + OFFSET_S + 0.0012 for end in ends[::3]])]


# -- the alignment -----------------------------------------------------------

def test_alignment_recovers_the_offset_within_the_jitter():
    report = host_timeline.align(_pairs(_blocks(24)), **LIMITS)
    # 24 dispatch returns and 8 fetch returns line up; the floor sits
    # one launch latency (0.08 ms) and at most the jitter (0.2 ms)
    # above the true offset.
    assert report["pairs"] == 32
    assert 0.00008 <= report["offset_s"] - OFFSET_S <= 0.00028
    assert report["spread5_ms"] <= 0.2
    assert report["runner_up_pairs"] <= 0.75 * report["pairs"]
    assert "refused" not in report


@pytest.mark.parametrize("case,why", [
    ("few", "fewer than 5"),
    ("wide", "over 0.5"),
    ("periodic", "ambiguous"),
])
def test_alignment_refuses_what_it_cannot_know(case, why):
    blocks = _blocks(24)
    starts = [start for start, _ in blocks]
    if case == "few":           # only four returns blocked
        pairs = [(starts, [starts[index] + OFFSET_S
                           for index in (2, 9, 13, 20)])]
    elif case == "wide":        # the smallest differences 0.7 ms apart
        pairs = [(starts, [start + OFFSET_S + (index % 8) * 0.0007
                           for index, start in enumerate(starts)])]
    else:                       # exactly periodic blocks: any shift fits
        starts = [0.05 * index for index in range(1, 25)]
        pairs = [(starts, _returns(starts, jitter_ms=0.0))]
    report = host_timeline.align(
        pairs, **{**LIMITS, "inlier_ms": 1.5 if case != "wide" else 10.0})
    assert report["offset_s"] is None
    assert why in report["refused"]


# -- flattening: leaves, work before wait -----------------------------------------

def test_flatten_gives_leaves_and_prefers_work_over_wait():
    intervals = [
        ["llm_tick:retire_wait", 0.0, 10.0],    # the worker waits ...
        ["mb_run:R0", 2.0, 3.0],                # ... while R0 uploads
        ["mb_finish:DET", 6.0, 3.0],            # DET finishes: work, but
        ["fetch:DET", 6.5, 2.0],                # ... its fetch is a wait
        ["llm_tick:demux", 10.0, 1.0],
        ["gc:1", 10.2, 0.3],                    # a collection inside it
        ["llm_tick:wait_work", 11.0, 4.0],
        ["dispatch_done:CAP", 12.0, 0.5],
    ]
    segments = host_timeline.flatten(intervals, 0.0, 14.0)
    assert [(name, start, round(start + duration, 6))
            for name, start, duration in segments] == [
        ("llm_tick:retire_wait", 0.0, 2.0), ("mb_run:R0", 2.0, 5.0),
        ("llm_tick:retire_wait", 5.0, 6.0), ("mb_finish:DET", 6.0, 6.5),
        ("fetch:DET", 6.5, 8.5), ("mb_finish:DET", 8.5, 9.0),
        ("llm_tick:retire_wait", 9.0, 10.0), ("llm_tick:demux", 10.0, 10.2),
        ("gc:1", 10.2, 10.5), ("llm_tick:demux", 10.5, 11.0),
        ("llm_tick:wait_work", 11.0, 12.0),
        ("dispatch_done:CAP", 12.0, 12.5),
        ("llm_tick:wait_work", 12.5, 14.0)]
    # Disjoint and sorted: what ``trace._covering_span`` expects.
    for left, right in zip(segments, segments[1:]):
        assert left[1] + left[2] <= right[1]


def test_idle_by_span_splits_gaps_by_what_covers_them():
    segments = [["llm_tick:prefill", 0, 100], ["llm_tick:retire_wait", 100, 50],
                ["mb_run:R0", 200, 100]]
    totals = host_timeline.idle_by_span([(50, 120), (180, 260)], segments)
    assert totals == {"llm_tick:prefill": 50, "llm_tick:retire_wait": 20,
                      host_timeline.NO_SPAN: 20, "mb_run:R0": 60}


# -- the readers, end to end on a synthetic cut and ring ----------------------------

def _ring(events, capacity=4096):
    """A flight recorder holding ``[etype, name, end_s, ms]`` events
    as if it had stamped them itself."""
    ring = FlightRecorder(capacity=capacity)
    for etype, name, end, ms in sorted(events, key=lambda event: event[2]):
        ring._ring.append((end, etype, None, None, name, ms, None))
        ring.recorded += 1
    return ring


def _scene():
    """A 24-block slice in which the chip idles 2 ms before every
    block: the first half of each gap under the worker's ``prefill``,
    the rest of it under its ``dispatch`` (which returns when the block
    starts) -- and in every other gap the micro-batcher uploads
    meanwhile.  A frame's park-to-resume span covers it all."""
    blocks = _blocks(24)
    ops, modules, events = [], [], []
    for index, (start, end) in enumerate(blocks):
        # The device runs something (a prefill chunk) up to 2 ms
        # before the block, then nothing, then the block.
        previous = blocks[index - 1][1] if index else 0.045
        ops.append(["fusion.1", round(previous * 1e9),
                    round((start - 0.002 - previous) * 1e9)])
        ops.append(["while.2", round(start * 1e9),
                    round((end - start) * 1e9)])
        modules.append(["jit__decode_loop_jit(1)", round(start * 1e9),
                        round((end - start) * 1e9)])
        events.append(["llm_tick", "prefill", start - 0.001 + OFFSET_S, 1.0])
        if index % 2:
            events.append(["mb_run", "R0", start + OFFSET_S, 0.5])
    starts = [start for start, _ in blocks]
    for returned in _returns(starts, jitter_ms=0.1):
        events.append(["llm_tick", "dispatch", returned, 1.08])
    for _, end in blocks[::3]:
        events.append(["llm_tick", "retire_wait", end + OFFSET_S + 0.0012,
                       40.0])
    events.append(["resume", "LLM", blocks[-1][1] + OFFSET_S, 2000.0])
    cut = {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops}},
           "host": []}
    return cut, events


class _Context:
    """What ``benchmark.run.Context`` offers these readers."""

    def __init__(self, cut, stamp=STAMP):
        self.cut, self.trace_stamp, self.notes = cut, stamp, {}
        self.window = (WINDOW_END_S - 30.0, WINDOW_END_S)
        self.host = None
        self._values: dict = {}

    def metric(self, name):
        if name not in self._values:
            from benchmark import readers
            spec = run.layer_metric(name)
            self._values[name] = readers.load(spec["kind"]).read(
                spec.get("args", {}), self)
        return self._values[name]


def test_idle_by_host_span_names_the_gaps(monkeypatch):
    cut, events = _scene()
    ring = _ring(events)
    monkeypatch.setattr(host_timeline, "live_recorder", lambda: ring)
    before = trace.idle_gaps(cut)
    assert {name for name, _ in before} == {trace.NO_HOST_SPAN}
    ctx = _Context(cut)
    share = ctx.metric("device.idle_host_bound_share")
    notes = ctx.notes["host_timeline"]
    # The slice's first block has no past in the cut: 23 dispatch
    # returns and 8 fetch returns line up.
    assert (notes["runs"], notes["runs_after_idle"]) == (24, 23)
    assert notes["pairs"] == 31 and notes["spread5_ms"] < 0.5
    assert notes["offset_s"] == pytest.approx(OFFSET_S, abs=0.0003)
    # The bracket: from the stamp before ``start_trace`` to the margin
    # after it (the call returns later than that), the offset inside.
    low, high = notes["bounds_s"]
    assert low == STAMP[0] and high == pytest.approx(low + 0.015)
    assert low < notes["offset_s"] < high
    # 24 gaps of 2 ms: 1 ms of each under ``prefill``, the other under
    # ``dispatch`` -- but in 12 of them ``mb_run:R0`` starts 1.5 ms
    # into the gap and, starting later, takes that last half
    # millisecond.  All of it is some thread's work.  (The offset sits
    # up to 0.2 ms high, which moves that much of a gap between names.)
    assert share == pytest.approx(100.0, abs=5.0)
    by_span = notes["idle_ms_by_span"]
    assert by_span["llm_tick:prefill"] == pytest.approx(24.0, abs=6.0)
    assert by_span["llm_tick:dispatch"] == pytest.approx(18.0, abs=6.0)
    assert by_span["mb_run:R0"] == pytest.approx(6.0, abs=3.0)
    assert not any(name.startswith("resume:") for name in by_span)
    assert notes["idle_named_share"] > 0.95
    # The host spans are left beside the cut (not in it), sorted, on the
    # trace's clock: with them the harness's reduction names every gap.
    assert ctx.cut["host"] == []
    starts = [start for _, start, _ in ctx.host]
    assert starts == sorted(starts) and starts[0] >= 0
    assert all(isinstance(value, int) for _, start, duration
               in ctx.host for value in (start, duration))
    named = trace.idle_gaps(ctx.cut, host=ctx.host)
    assert len(named) == 10
    assert trace.NO_HOST_SPAN not in {name for name, _ in named}
    assert {name for name, _ in named} <= {
        "llm_tick:prefill", "mb_run:R0", "llm_tick:dispatch"}
    # ... and the worker's share of it per block: 42 of the 48 ms lie
    # under its phases, over 24 blocks.
    per_block = ctx.metric("batcher.host_ms_per_block")
    assert per_block == pytest.approx(42.0 / 24, abs=0.3)
    assert ctx.notes["idle_under_llm_tick"]["runs"] == 24


def test_only_offsets_the_stamp_allows_are_looked_at(monkeypatch):
    """The stamp around ``start_trace`` is the coarse anchor: the same
    cadence of returns one tick and ten seconds earlier -- rivals as
    good as the truth -- is never paired."""
    cut, events = _scene()
    ticks = [event for event in events if event[0] == "llm_tick"]
    rivals = [[etype, name, end - shift, ms] for shift in (0.25, 10.0)
              for etype, name, end, ms in ticks]
    ring = _ring(events + rivals)
    monkeypatch.setattr(host_timeline, "live_recorder", lambda: ring)
    ctx = _Context(cut)
    assert ctx.metric("device.idle_host_bound_share") is not None
    notes = ctx.notes["host_timeline"]
    assert notes["offset_s"] == pytest.approx(OFFSET_S, abs=0.0003)
    # (a rival's return may chance on another block's edge)
    assert 31 <= notes["pairs"] <= 33
    assert notes["runner_up_pairs"] <= 0.25 * notes["pairs"]
    # Without the bracket the three cannot be told apart.
    starts = [start / 1e9 for _, start, _ in
              cut["devices"]["/device:TPU:0"]["modules"]]
    returns = [end for _, name, end, _ in ticks + rivals
               if name == "dispatch"]
    assert host_timeline.align(
        [(starts, returns)], **LIMITS)["offset_s"] is None


@pytest.mark.parametrize("case", ["stamp", "phases"])
def test_an_offset_that_disagrees_with_the_stamp_is_not_found(
        monkeypatch, case):
    cut, events = _scene()
    stamp = STAMP
    if case == "stamp":         # the trace began 5 s after these stamps
        stamp = (STAMP[0] - 5.0, STAMP[1] - 5.0)
    else:                       # the worker's phases, 0.31 s out of step
        events = [[etype, name, end - 0.3137, ms]
                  for etype, name, end, ms in events
                  if etype == "llm_tick"]
    ring = _ring(events)
    monkeypatch.setattr(host_timeline, "live_recorder", lambda: ring)
    ctx = _Context(cut, stamp)
    assert ctx.metric("device.idle_host_bound_share") is None
    # No pair at all inside a bracket of a few ms.
    assert "fewer than 5" in ctx.notes["host_timeline"]["refused"]
    assert ctx.host is None
    assert ctx.metric("batcher.host_ms_per_block") is None


def test_program_runs_say_how_long_the_chip_had_been_idle():
    modules = [["jit__prefill_into_slot_jit(7)", 1_000_000, 2_000_000],
               ["jit__decode_loop_jit(1)", 3_004_000, 40_000_000],
               ["jit__lambda(3)", 43_500_000, 900_000],
               ["jit__decode_loop_jit(1)", 45_600_000, 40_000_000],
               ["jit__decode_loop_jit(1)", 85_600_000, 40_000_000]]
    assert host_timeline.program_runs(modules[::-1], "decode_loop") == [
        (0.003004, 0.043004, pytest.approx(4e-6)),
        (0.0456, 0.0856, pytest.approx(0.0012)), (0.0856, 0.1256, 0.0)]
    # The slice's first program has no past in the cut.
    assert host_timeline.program_runs(modules[1:], "decode_loop")[0][2] == 0


def test_blocks_that_start_back_to_back_are_not_paired(monkeypatch):
    """Where the worker is ahead of the chip its ``dispatch`` returns
    before the block starts (here up to 0.8 ms before, for a third of
    the blocks, which start as the program before them ends): paired
    with every block the floor is no longer the offset and the five
    smallest differences are too far apart; paired with the blocks the
    chip idled before, the offset is found."""
    cut, events = _scene()
    entry = cut["devices"]["/device:TPU:0"]
    ahead = set(range(2, 24, 3))
    for index in ahead:         # a prefill chunk right up to the block
        start = entry["modules"][index][1]
        entry["modules"].append(["jit__prefill_into_slot_jit(7)",
                                 start - 12_000_000, 11_996_000])
        entry["ops"].append(["fusion.9", start - 12_000_000, 11_996_000])
    dispatches = [event for event in events if event[1] == "dispatch"]
    for index in ahead:         # 7 returns before the slice's first block
        dispatches[7 + index][2] -= 0.0001 * (1 + index % 8)
    ring = _ring(events)
    monkeypatch.setattr(host_timeline, "live_recorder", lambda: ring)
    ctx = _Context(cut)
    assert ctx.metric("device.idle_host_bound_share") is not None
    notes = ctx.notes["host_timeline"]
    assert (notes["runs"], notes["runs_after_idle"]) == (24, 15)
    assert notes["offset_s"] == pytest.approx(OFFSET_S, abs=0.0003)
    everything = [start / 1e9 for name, start, _ in entry["modules"]
                  if "decode_loop" in name]
    report = host_timeline.align(
        [(everything, [event[2] for event in dispatches])],
        **{**LIMITS, "bounds": STAMP})
    assert report["offset_s"] is None or \
        report["offset_s"] < OFFSET_S - 0.0003


def test_the_offset_is_found_in_a_recorded_slice_with_blocks_back_to_back():
    """``camera-paced`` after PR 27 (``benchmark/testdata/
    camera_paced_alignment.json.gz``: the slice's programs, the LLM
    worker's phases around it and the stamps around ``start_trace``,
    kept from a chip run of PR 28): 5 of the slice's 12 blocks start
    within 0.4 ms of the program before them, and the ``dispatch`` of
    three of those returned 0.07-0.55 ms BEFORE its block started.
    Paired with every block the five smallest differences span
    0.58 ms and the alignment refuses, as three traced runs in five did
    (PERF.md section 6); paired with the blocks the chip idled before,
    the offset is found, 0.9 ms after ``start_trace`` was entered."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "testdata",
        "camera_paced_alignment.json.gz")
    with gzip.open(path, "rt") as stream:
        recorded = json.load(stream)
    entered, returned = recorded["trace_stamp"]
    assert 0.040 < returned - entered < 0.060
    limits = {**LIMITS,
              "bounds": (entered, entered + ARGS["anchor_margin_s"])}
    runs = host_timeline.program_runs(recorded["modules"], ARGS["program"])
    idle_ms = sorted(idle * 1000.0 for _, _, idle in runs)
    assert len(runs) == 12
    assert idle_ms[4] < 0.34 and idle_ms[5] > 0.99      # two kinds of block
    report = host_timeline.align(host_timeline.sync_pairs(
        runs, recorded["intervals"], ARGS["sync"], ARGS["idle_before_ms"]),
        **limits)
    assert "refused" not in report
    assert report["offset_s"] == pytest.approx(
        recorded["expected"]["offset_s"], abs=1e-6)   # stamps kept to 0.1 us
    assert 0.0007 < report["offset_s"] - entered < 0.0021
    assert report["pairs"] == 7 and report["spread5_ms"] < 0.2
    assert report["runner_up_pairs"] == 0
    everything = host_timeline.align(host_timeline.sync_pairs(
        runs, recorded["intervals"], ARGS["sync"], 0.0), **limits)
    assert everything["offset_s"] is None
    assert "over 0.5" in everything["refused"]


@pytest.mark.parametrize("case", ["wrapped", "no-cut", "no-ring",
                                  "no-stamp"])
def test_idle_by_host_span_returns_nothing_and_says_why(monkeypatch, case):
    cut, events = _scene()
    ring = _ring(events)
    if case == "wrapped":       # a full ring whose oldest event is inside
        ring = _ring(events, capacity=64)
    monkeypatch.setattr(host_timeline, "live_recorder",
                        lambda: None if case == "no-ring" else ring)
    ctx = _Context(None if case == "no-cut" else cut,
                   None if case == "no-stamp" else STAMP)
    assert ctx.metric("device.idle_host_bound_share") is None
    assert ctx.metric("batcher.host_ms_per_block") is None
    assert ctx.host is None
    if case == "wrapped":
        assert "wrapped" in ctx.notes["host_timeline"]["refused"]
    else:
        assert "host_timeline" not in ctx.notes


def test_live_recorder_finds_the_busiest_ring(monkeypatch):
    ring = FlightRecorder(capacity=64)
    ring.recorded = 10 ** 12
    assert host_timeline.live_recorder() is ring
    # A program from before the timeline (the driver lays these files
    # over the parent commit): nothing found, nothing raised.
    from aiko_services_tpu.observability import recorder
    monkeypatch.delattr(recorder, "live_recorders")
    assert host_timeline.live_recorder() is None


def test_tiling_arithmetic():
    # 20 ticks of 500 ms: 3 ms of launch and host phases and one 40 ms
    # retire_wait a tick, the rest waiting for work; another thread's
    # events beside them.
    intervals, at = [], 500.0
    for _ in range(20):
        for name, ms in (("admit", 0.5), ("prefill", 1.5),
                         ("dispatch", 0.7), ("retire_wait", 40.0),
                         ("demux", 0.3), ("wait_work", 457.0)):
            intervals.append([f"llm_tick:{name}", at, ms / 1000.0])
            at += ms / 1000.0
    intervals.append(["mb_run:R0", 504.0, 0.003])
    tiles = host_timeline.tiling(intervals, "llm_tick:", 499.0, 511.0)
    assert tiles["coverage"] == pytest.approx(1.0)
    assert tiles["covered_s"] == pytest.approx(10.0)
    assert tiles["phase_ms"]["prefill"] == pytest.approx(30.0)
    assert set(tiles["phase_ms"]) == {"admit", "prefill", "dispatch",
                                      "retire_wait", "demux", "wait_work"}
    # A hole in the thread's time shows as coverage lost; only whole
    # phases inside the stretch count; nothing there, nothing said.
    holed = [interval for interval in intervals
             if interval[0] != "llm_tick:retire_wait"]
    assert host_timeline.tiling(holed, "llm_tick:", 499.0, 511.0)[
        "coverage"] == pytest.approx(1.0 - 0.8 / 10.0, abs=0.005)
    assert 1.0 < host_timeline.tiling(
        intervals, "llm_tick:", 500.2, 502.2)["covered_s"] < 2.0
    assert host_timeline.tiling(intervals, "llm_tick:", 0.0, 1.0) is None
