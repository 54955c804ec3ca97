"""Flight recorder: an always-on bounded ring of typed engine events
(ISSUE 10 tentpole part 1).

The telemetry plane (PR 4) aggregates -- histograms know detect is
slow; traces know how long each span took.  Neither answers "what
happened, in order, to THIS frame" or "what was the engine doing in the
500 ms before that frame died".  The flight recorder does: every
engine seam (ingest, stage admit/credit-release, replica pick/failover,
hop dispatch, element/segment dispatch start+done, ledger fetch,
data-plane forward/claim/fallback, deadline/shed/breaker/replay
transitions) appends one typed, monotonic-stamped event to a bounded
per-pipeline ring.  So does every place where the chip's idle time is
decided between device programs (ISSUE 26): the LLM worker's tick
phases (``llm_tick``), the micro-batcher's dispatch and finish halves
(``mb_run`` / ``mb_finish``), a program build (``build``) and a
collection of the interpreter's (``gc``) -- all duration events.

This ring is the program's ONE host timeline: ``intervals()`` renders
its duration events as ``[name, start, duration]`` and ``clock()``
anchors ``perf_counter`` to the wall clock, so a reader
(the benchmark's ``idle_by_host_span``) can lay it beside a device
trace and name each idle gap.  Telemetry spans stay per-request and
cross-process; ``tpu/profiling.py``'s ``TraceAnnotation``s stay for
xprof by hand.

Cost model (the "always-on" contract):

- ``record`` is one ``time.perf_counter()`` call, one tuple allocation
  and one ``deque.append`` on a ``maxlen`` ring -- no lock, no dict
  unless the site passes ``info``.  Appends are safe from any thread
  (stage workers, batcher threads) under the GIL.
- When the pipeline runs with ``recorder: off`` the engine holds
  ``recorder = None`` and every emission site is behind an
  ``is not None`` guard -- the hot path pays one attribute load and a
  branch, nothing else (the same discipline as the unarmed FaultPlan).
- Readers (``explain_frame``, black-box dumps, tests) take an O(n)
  snapshot; they are debug/post-mortem surfaces, never per-frame work.

Events are 7-tuples ``(t, etype, stream, frame, name, ms, info)``:
``t`` is ``time.perf_counter()`` (the same clock every frame metric
stamp uses), ``ms`` an optional duration the site already measured
(hop dispatch, ledger fetch, pacing stall, a tick phase) -- the event
is stamped at the END of that interval -- ``info`` an optional SMALL
dict of primitives (replica index, path, reason).  Sites must only put
ids/names/numbers in events -- never tensors or payloads -- which is
what makes the black-box dump redacted by construction.

The **black-box dump** (:func:`write_blackbox`) snapshots the ring tail
plus the engine's in-flight frame states to a JSON file when something
goes wrong (deadline miss, replay, breaker open, replica failover,
stream error); the ``python -m aiko_services_tpu explain <dump>`` CLI
renders it offline.  Dumps are bounded: the newest ``limit`` files are
kept, oldest pruned.
"""

from __future__ import annotations

import gc
import json
import logging
import threading
import time
import weakref
from collections import deque
from pathlib import Path

__all__ = ["FlightRecorder", "write_blackbox", "events_as_dicts",
           "select_frame_events", "live_recorders",
           "RECORDER_CAPACITY_DEFAULT", "BLACKBOX_LIMIT_DEFAULT",
           "EVENT_TYPES", "GC_EVENT_MIN_MS"]

_logger = logging.getLogger("aiko.observability")

# The ring must hold a traced slice and its drain with room (ISSUE 26).
# Reckoned from the ledger's rates (PR 24): chat-batch, from the start
# of its 3 s slice to the end of its drain, is ~150 ticks x ~10 tick
# events + ~70 requests x ~20 engine events + prefill chunks ~= 3,000
# events; camera-paced ~600 a second for ~2 s.  Measured since (my chip
# runs, PR 26): camera-paced records ~470 events a second while it
# serves and a reader comes ~25 s after its slice began (the trace takes
# 21 s to write, traffic having stopped); chat-batch records ~4,000 in a
# whole run.  16,384 events are 35 s of camera-paced at full rate (the
# old 4,096 were 9 s); a slot is one 7-tuple, the full ring ~2.5 MB.
RECORDER_CAPACITY_DEFAULT = 16384
BLACKBOX_LIMIT_DEFAULT = 16
# A collection shorter than this costs two stamps and no event (the
# benchmark's gc.set_threshold(700, 1, 1) makes hundreds a second).
GC_EVENT_MIN_MS = 0.5
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"

#: the event vocabulary (documentation + the offline renderer's
#: ordering hints; ``record`` does not validate against it -- a typo'd
#: etype costs a confusing timeline, not a hot-path check).
EVENT_TYPES = (
    "ingest",          # frame entered stream.frames
    "pace",            # ingest blocked on the dispatch window (ms)
    "stage_wait",      # frame queued for a placed stage's credit
    "admit",           # stage credit granted (info.replica = slot)
    "release",         # stage credit returned
    "hop",             # stage-hop reshard dispatched (ms)
    "submit",          # handed to a stage worker's FIFO
    "dispatch",        # element/segment execution began
    "dispatch_done",   # element/segment execution finished (ms)
    "park",            # parked at an async/remote stage (info.kind)
    "resume",          # continuation resumed on the loop
    "fetch",           # counted ledger fetch (ms, name = element)
    "forward",         # remote-stage forward (info.path = pipe|mqtt)
    "response",        # remote response arrived (ms = round trip)
    "pipe_fallback",   # data-plane fallback to MQTT (info.reason)
    "claim_drop",      # pipe claim expired; envelope dropped
    "llm_tick",        # one phase of the LLM worker's tick (ms; name =
    #                    wait_work|drain|admit|prefill|fold|dispatch|
    #                    retire_wait|demux|publish): tiles the thread
    "mb_run",          # micro-batch dispatch half (ms, name = element)
    "mb_finish",       # micro-batch fetch + complete half (ms)
    "build",           # a program was built (ms, name = the function)
    "gc",              # a collection >= GC_EVENT_MIN_MS (name = gen)
    "deadline",        # frame_deadline_ms blew
    "shed",            # overload shed
    "breaker",         # circuit breaker transition (info.state)
    "breaker_reject",  # frame refused by an open breaker
    "replay",          # frame replayed after device loss (info.attempt)
    "failover",        # replica failover (info.replica)
    "replace",         # full device replacement (info.generation)
    "done",            # frame finished (info.ok)
    "stream_end",      # stream destroyed (incarnation boundary)
    "gw_admit",        # gateway admitted a request (name = tenant)
    "gw_reject",       # gateway refused one (info.reason)
    "gw_promote",      # a queued gateway frame promoted into a slot
    "drain",           # cooperative drain (info.phase = start|done)
    "adopt",           # a dead peer's stream adopted from its journal
    "journal_lag",     # journal fsync lag crossed its limit (ms)
    "slo_burn",        # a tenant's SLO burn alert (name = tenant)
    "version_swap",    # a stage replica swapped model version
    # The fleet controller adds ``controller_<action|refusal|...>``
    # (orchestration/controller.py, an f-string family).
)

# Every live recorder of the process (weak references), so that a
# reader, an exporter or the process-wide taps below find them without
# holding a pipeline.  The tuple is replaced whole under the lock and
# never mutated: the taps read it from whichever thread finishes a
# build or a collection, and must never raise into jax or the gc.
_live: tuple = ()
_live_lock = threading.Lock()
_gc_started = 0.0


def _enlist(recorder: "FlightRecorder") -> None:
    global _live
    with _live_lock:
        _install_taps()
        _live = tuple(ref for ref in _live if ref() is not None) \
            + (weakref.ref(recorder),)


def live_recorders() -> list["FlightRecorder"]:
    return [recorder for recorder in (ref() for ref in _live)
            if recorder is not None]


def _record_everywhere(etype, name, ms, info=None) -> None:
    for recorder in live_recorders():
        recorder.record(etype, None, None, name, ms, info)


def _on_build(event, duration, **facts) -> None:
    if event == BUILD_EVENT:
        _record_everywhere("build", str(facts.get("fun_name")),
                           float(duration) * 1000.0)


def _on_gc(phase, info) -> None:
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
        return
    ms = (time.perf_counter() - _gc_started) * 1000.0
    if ms >= GC_EVENT_MIN_MS:
        _record_everywhere("gc", str(info["generation"]), ms,
                           {"collected": info["collected"]})


def _install_taps() -> None:
    """The two process-wide taps, installed with the first recorder
    and never removed: a ``jax.monitoring`` duration listener (which
    program was built, and for how long) and a ``gc.callbacks`` entry."""
    if _on_gc in gc.callbacks:
        return
    gc.callbacks.append(_on_gc)
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_build)


class FlightRecorder:
    """Bounded, lock-free ring of engine events.

    One per Pipeline (``pipeline.recorder``; None under
    ``recorder: off``).  Appends from any thread; snapshots copy the
    ring (C-level ``list(deque)``, retried on the pathological
    concurrent-mutation case).
    """

    __slots__ = ("capacity", "_ring", "recorded", "__weakref__")

    def __init__(self, capacity: int = RECORDER_CAPACITY_DEFAULT):
        self.capacity = max(64, int(capacity))
        self._ring: deque = deque(maxlen=self.capacity)
        # Total events ever recorded.  Bumped without a lock from many
        # threads, so it can undercount slightly under contention --
        # it is a diagnostic ("did the ring wrap"), never accounting.
        self.recorded = 0
        _enlist(self)

    def record(self, etype: str, stream=None, frame=None, name=None,
               ms: float | None = None, info: dict | None = None) -> None:
        self._ring.append((time.perf_counter(), etype, stream, frame,
                           name, ms, info))
        self.recorded += 1

    def __len__(self) -> int:
        return len(self._ring)

    def snapshot(self, stream=None, frame=None,
                 tail: int | None = None) -> list[tuple]:
        """Copy of the ring (oldest first), optionally filtered to one
        stream and/or frame id, optionally only the last ``tail``
        events.  Global events (stream/frame None, e.g. ``llm_tick``)
        are excluded by a frame filter -- a frame's timeline holds only
        its own causality."""
        events = None
        for _ in range(8):
            try:
                events = list(self._ring)
                break
            except RuntimeError:        # mutated mid-copy (rare)
                continue
        if events is None:              # pragma: no cover
            # Never silent: an empty snapshot here would write an
            # event-less black-box dump during exactly the overload
            # episode it exists to explain.
            _logger.warning("flight-recorder snapshot failed after 8 "
                            "concurrent-mutation retries; returning "
                            "an empty event list")
            events = []
        if stream is not None:
            stream = str(stream)
            events = [e for e in events if str(e[2]) == stream]
        if frame is not None:
            frame = int(frame)
            events = [e for e in events
                      if e[3] is not None and int(e[3]) == frame]
        if tail is not None and tail > 0:
            events = events[-int(tail):]
        return events

    def frame_events(self, stream, frame) -> list[tuple]:
        """Events for ONE frame of ONE stream incarnation (see
        :func:`select_frame_events` -- shared with the offline dump
        renderer so both apply the same stale-same-id discipline)."""
        return select_frame_events(self.snapshot(stream=stream), frame,
                                   stream=stream)

    def intervals(self, since: float | None = None) \
            -> tuple[list[list], bool]:
        """The buffered duration events as ``[name, start, duration]``
        (``"<etype>:<name>"``, ``perf_counter`` seconds, oldest first)
        that end at or after ``since``; and whether the ring may have
        dropped one: it is full and its oldest event is younger than
        ``since`` (with ``since`` None: it is full)."""
        events = self.snapshot()
        wrapped = max(self.recorded, len(events)) >= self.capacity \
            and (since is None or not events or events[0][0] > since)
        return [[f"{etype}:{name}", t - ms / 1000.0, ms / 1000.0]
                for t, etype, _, _, name, ms, _ in events
                if ms is not None and (since is None or t >= since)], \
            wrapped

    @staticmethod
    def clock() -> tuple[int, int]:
        """``(perf_counter_ns, time_ns)`` read together, now: the
        tightest of a few back-to-back samples, so that a reader
        converts the ring's stamps to the wall clock with no more error
        than one sample's width (and, taken just after a run, without
        the wall clock's slew over it)."""
        best = None
        for _ in range(8):
            before = time.perf_counter_ns()
            wall = time.time_ns()
            after = time.perf_counter_ns()
            if best is None or after - before < best[0]:
                best = (after - before, (before + after) // 2, wall)
        return best[1:]

    @property
    def stats(self) -> dict:
        return {"capacity": self.capacity, "buffered": len(self._ring),
                "recorded": self.recorded}


def select_frame_events(events: list[tuple], frame,
                        stream=None) -> list[tuple]:
    """Events for ONE frame of ONE stream INCARNATION.  Frame ids
    restart when a same-id stream is recreated, so the (optionally
    pre-filtered) event list is split at ``stream_end`` markers
    (recorded at stream destroy) and the NEWEST segment holding the
    frame id wins -- a recreated stream's frame 0 never merges with
    (or terminates at) its dead predecessor's timeline, and a
    destroyed stream's last incarnation stays explainable
    post-mortem.  Shared by ``FlightRecorder.frame_events`` and the
    offline black-box renderer (the dump's ring tail carries the same
    markers)."""
    stream = None if stream is None else str(stream)
    segments: list[list] = [[]]
    for event in events:
        if event[1] == "stream_end" \
                and (stream is None or str(event[2]) == stream):
            segments.append([])
        else:
            segments[-1].append(event)
    frame = int(frame)
    for segment in reversed(segments):
        matched = [event for event in segment
                   if event[3] is not None and int(event[3]) == frame
                   and (stream is None or str(event[2]) == stream)]
        if matched:
            return matched
    return []


def events_as_dicts(events: list[tuple]) -> list[dict]:
    """Ring tuples -> JSON-ready dicts (the dump/export shape)."""
    dicts = []
    for t, etype, stream, frame, name, ms, info in events:
        entry = {"t": round(t, 6), "type": etype}
        if stream is not None:
            entry["stream"] = str(stream)
        if frame is not None:
            entry["frame"] = frame
        if name is not None:
            entry["name"] = str(name)
        if ms is not None:
            entry["ms"] = round(float(ms), 4)
        if info:
            entry.update({str(k): v for k, v in info.items()})
        dicts.append(entry)
    return dicts


def _json_safe(value):
    """Last-resort redaction: anything json cannot take (arrays,
    device buffers that leaked into an info dict) renders as its type
    name, never its contents."""
    return f"<{type(value).__name__}>"


def write_blackbox(directory, payload: dict,
                   limit: int = BLACKBOX_LIMIT_DEFAULT) -> str:
    """Write one black-box dump under ``directory`` and prune to the
    newest ``limit`` files.  Returns the written path.  The payload is
    JSON-serialized with a type-name fallback so a non-primitive that
    slipped into an event can never put tensor bytes on disk."""
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    stamp = time.strftime("%Y%m%d_%H%M%S")
    reason = str(payload.get("reason", "event"))
    base = f"blackbox_{stamp}_{reason}"
    path = directory / f"{base}.json"
    serial = 0
    while path.exists():                # same second, same reason
        serial += 1
        path = directory / f"{base}_{serial}.json"
    path.write_text(json.dumps(payload, indent=1, default=_json_safe))
    dumps = sorted(directory.glob("blackbox_*.json"),
                   key=lambda p: p.stat().st_mtime)
    for stale in dumps[:max(0, len(dumps) - max(1, int(limit)))]:
        try:
            stale.unlink()
        except OSError:                 # pragma: no cover
            pass
    return str(path)
