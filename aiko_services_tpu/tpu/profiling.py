"""``jax.profiler`` integration, routed through the hook system.

The reference instruments with hooks alone (reference:
src/aiko_services/main/hook.py:19-23, pipeline.py:1286-1289); on TPU the
interesting timeline lives in the XLA profiler, so this module bridges
the two (SURVEY.md §5.1 TPU-equiv note):

- :class:`Profiler` starts/stops a ``jax.profiler`` trace for the whole
  process (viewable in TensorBoard / xprof) and, when attached to a
  Pipeline, opens a ``jax.profiler.TraceAnnotation`` around every
  element execution via the ``pipeline.process_element:0`` (enter) and
  ``pipeline.process_element_post:0`` (exit) hooks — so each pipeline
  element shows up as a named span on the host timeline, aligned with
  the device ops it launched.
- :func:`profile_trace` is the context-manager form for scripts/tests.

Relation to the telemetry plane (``observability/``): the distributed
frame traces (``trace_id`` + span dicts in the ``TraceBuffer``) and the
annotations here describe the SAME events -- ``element:``/``segment:``/
``stage:``/``hop:`` names match one-for-one.  The telemetry spans carry
ids and cross process boundaries (a ``RemoteStage`` hop stitches both
processes into one trace); the xprof annotations align those events
with the device ops on the XLA timeline.  Debug latency with the
trace/histograms, then zoom into a span's device work with xprof.

Which timeline is for what (ISSUE 26).  The flight recorder
(``observability/recorder.py``) is the program's host timeline: always
on, and the one the benchmark lays on the device trace to name idle
gaps.  Telemetry spans are per-request and cross-process.  The
annotations here are for xprof by hand -- and they land in a trace only
with the profiler's host tracer on, which is known to stall 720p
camera uploads on the v5e (PERF.md section 5: 82-99 % device idle with
it against 18-20 % without), so do not take a measurement from a
profile made this way.

CLI: ``python -m aiko_services_tpu pipeline create DEF --profile DIR``.
"""

from __future__ import annotations

import contextlib

import jax

from ..utils import get_logger

__all__ = ["Profiler", "profile_trace"]

_logger = get_logger("aiko.profiling")


class Profiler:
    """Process-wide trace plus per-element trace annotations.

    With overlapped frame execution (async park/resume, cross-stream
    micro-batching) element spans INTERLEAVE: frame k+1's detect enter
    fires while frame k is still parked at the LLM, and the post hooks
    resume in completion order, not a stack order.  Spans are therefore
    keyed by (element, stream, frame) -- each ``TraceAnnotation`` is an
    independent timed event, so out-of-order exits are fine.  A
    dangling annotation (element raised, so the post hook never fired)
    is closed when the same (element, frame) re-enters (frame retry) or
    at ``detach()``.
    """

    def __init__(self):
        self._logdir: str | None = None
        self._pipelines: list = []
        self._open: dict = {}  # (element, stream, frame) -> annotation

    @property
    def active(self) -> bool:
        return self._logdir is not None

    # -- process-wide trace ------------------------------------------------

    def start(self, logdir: str):
        if self._logdir is not None:
            _logger.warning("profiler already tracing to %s", self._logdir)
            return
        jax.profiler.start_trace(logdir)
        self._logdir = logdir
        _logger.info("jax.profiler trace -> %s", logdir)

    def stop(self) -> str | None:
        logdir, self._logdir = self._logdir, None
        self._unwind()
        if logdir is not None:
            jax.profiler.stop_trace()
        return logdir

    # -- pipeline annotation hooks -----------------------------------------

    def attach(self, pipeline):
        """Annotate every element run -- and every fused-segment
        dispatch, stage occupancy window and stage hop -- of
        ``pipeline`` on the trace."""
        pipeline.add_hook_handler("pipeline.process_element:0",
                                  self._on_element)
        pipeline.add_hook_handler("pipeline.process_element_post:0",
                                  self._on_element_post)
        pipeline.add_hook_handler("pipeline.process_segment:0",
                                  self._on_segment)
        pipeline.add_hook_handler("pipeline.process_segment_post:0",
                                  self._on_segment_post)
        pipeline.add_hook_handler("pipeline.process_stage:0",
                                  self._on_stage)
        pipeline.add_hook_handler("pipeline.process_stage_post:0",
                                  self._on_stage_post)
        pipeline.add_hook_handler("pipeline.stage_hop:0",
                                  self._on_stage_hop)
        self._pipelines.append(pipeline)

    def detach(self):
        for pipeline in self._pipelines:
            pipeline.remove_hook_handler("pipeline.process_element:0",
                                         self._on_element)
            pipeline.remove_hook_handler("pipeline.process_element_post:0",
                                         self._on_element_post)
            pipeline.remove_hook_handler("pipeline.process_segment:0",
                                         self._on_segment)
            pipeline.remove_hook_handler("pipeline.process_segment_post:0",
                                         self._on_segment_post)
            pipeline.remove_hook_handler("pipeline.process_stage:0",
                                         self._on_stage)
            pipeline.remove_hook_handler("pipeline.process_stage_post:0",
                                         self._on_stage_post)
            pipeline.remove_hook_handler("pipeline.stage_hop:0",
                                         self._on_stage_hop)
        self._pipelines.clear()
        self._unwind()

    @staticmethod
    def _key(variables):
        # Stream id included: frame ids restart per stream, so two
        # overlapping streams' frame 5 must not share a span.
        return (variables.get("element"), variables.get("stream"),
                variables.get("frame"))

    def _on_element(self, component, hook, variables):
        key = self._key(variables)
        stale = self._open.pop(key, None)
        if stale is not None:   # same frame re-entered: close the
            stale.__exit__(None, None, None)    # dangling span
        annotation = jax.profiler.TraceAnnotation(f"element:{key[0]}")
        annotation.__enter__()
        self._open[key] = annotation

    def _on_element_post(self, component, hook, variables):
        annotation = self._open.pop(self._key(variables), None)
        if annotation is not None:
            annotation.__exit__(None, None, None)

    # -- fused-segment spans ------------------------------------------------

    @staticmethod
    def _segment_keys(variables):
        base = (variables.get("segment"), variables.get("stream"),
                variables.get("frame"))
        return ("segment",) + base, ("compile",) + base

    def _on_segment(self, component, hook, variables):
        """One span per fused dispatch; a first-use trace additionally
        opens a ``compile:`` span (keyed by segment name) so first-frame
        compile time is distinguishable from steady-state step time on
        the timeline."""
        seg_key, compile_key = self._segment_keys(variables)
        for key in (seg_key, compile_key):
            stale = self._open.pop(key, None)
            if stale is not None:       # same frame re-entered (retry)
                stale.__exit__(None, None, None)
        name = variables.get("segment")
        if variables.get("compile"):
            annotation = jax.profiler.TraceAnnotation(f"compile:{name}")
            annotation.__enter__()
            self._open[compile_key] = annotation
        annotation = jax.profiler.TraceAnnotation(f"segment:{name}")
        annotation.__enter__()
        self._open[seg_key] = annotation

    def _on_segment_post(self, component, hook, variables):
        seg_key, compile_key = self._segment_keys(variables)
        for key in (seg_key, compile_key):   # inner (segment) first
            annotation = self._open.pop(key, None)
            if annotation is not None:
                annotation.__exit__(None, None, None)

    # -- stage occupancy / hop spans -----------------------------------------

    @staticmethod
    def _stage_key(variables):
        return ("stage", variables.get("stage"), variables.get("stream"),
                variables.get("frame"))

    def _on_stage(self, component, hook, variables):
        """One ``stage:`` span per (stage, stream, frame) admission --
        overlapping spans for the same stage across frames (window
        depth >= 2), and concurrently-open spans for DIFFERENT stages,
        are exactly the stage-parallel signature on the timeline."""
        key = self._stage_key(variables)
        stale = self._open.pop(key, None)
        if stale is not None:           # same frame re-admitted (retry)
            stale.__exit__(None, None, None)
        annotation = jax.profiler.TraceAnnotation(
            f"stage:{variables.get('stage')}")
        annotation.__enter__()
        self._open[key] = annotation

    def _on_stage_post(self, component, hook, variables):
        annotation = self._open.pop(self._stage_key(variables), None)
        if annotation is not None:
            annotation.__exit__(None, None, None)

    @staticmethod
    def _on_stage_hop(component, hook, variables):
        # The hop already dispatched (device_put is async; the ICI copy
        # itself rides the device timeline): a zero-width ``hop:`` mark
        # locates it on the host track, with the dispatch cost carried
        # in the hook's ``ms`` variable.
        annotation = jax.profiler.TraceAnnotation(
            f"hop:{variables.get('stage')}")
        annotation.__enter__()
        annotation.__exit__(None, None, None)

    def _unwind(self):
        """Close every dangling annotation INNERMOST-FIRST.

        ``popitem()`` alone scrambled nested ``compile:``/``segment:``
        pairs: ``_on_segment`` opens the outer ``compile:`` before the
        inner ``segment:``, and a dict re-entry (same key popped and
        re-inserted) can leave an outer span AFTER its inner one in
        insertion order -- closing in raw pop order then exits the
        outer annotation first and corrupts xprof's span nesting.  So:
        all non-``compile`` spans close first (reverse insertion
        order), then the remaining ``compile:`` outers."""
        for key in [key for key in reversed(list(self._open))
                    if key[0] != "compile"]:
            self._open.pop(key).__exit__(None, None, None)
        while self._open:
            _, annotation = self._open.popitem()
            annotation.__exit__(None, None, None)


@contextlib.contextmanager
def profile_trace(logdir: str, *pipelines):
    """``with profile_trace("/tmp/trace", pipeline): ...``"""
    profiler = Profiler()
    profiler.start(logdir)
    for pipeline in pipelines:
        profiler.attach(pipeline)
    try:
        yield profiler
    finally:
        profiler.detach()
        profiler.stop()
