"""The program's one host timeline (ISSUE 26): the flight recorder's
duration events where the chip's idle time is decided -- the LLM
worker's tick phases, the micro-batcher's halves, program builds,
collections -- and what a reader needs to lay them beside a device
trace (``intervals``, ``clock``, ``live_recorders``)."""

import ast
import gc
import pathlib
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from aiko_services_tpu.models import batching, llama
from aiko_services_tpu.models.batching import (ContinuousBatcher,
                                               MicroBatcher, Request)
from aiko_services_tpu.models.tokenizer import ByteTokenizer
from aiko_services_tpu.observability import recorder as recorder_module
from aiko_services_tpu.observability.recorder import (
    EVENT_TYPES, GC_EVENT_MIN_MS, RECORDER_CAPACITY_DEFAULT, FlightRecorder,
    live_recorders)

PACKAGE = pathlib.Path(recorder_module.__file__).resolve().parents[1]
WAIT_PHASES = {"wait_work", "retire_wait"}
BATCHER_PHASES = {"admit", "prefill", "dispatch", "retire_wait", "demux"}


@pytest.fixture(scope="module")
def tiny():
    config = llama.LlamaConfig.tiny()
    return config, llama.init_params(jax.random.PRNGKey(0), config)


def _batcher(tiny, n_requests=6, max_new=9, trace=None, **kw):
    config, params = tiny
    tok = ByteTokenizer()
    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, trace=trace, **kw)
    requests = [Request(request_id=f"r{i}",
                        prompt_tokens=tok.encode(f"hello world {i}"),
                        max_new_tokens=max_new) for i in range(n_requests)]
    for request in requests:
        batcher.submit(request)
    return batcher, requests


def _busy(batcher):
    return (batcher.pending or batcher.active_count
            or batcher.blocks_in_flight)


# -- the batcher's tap ---------------------------------------------------------

@pytest.mark.parametrize("mode", [
    {"decode_block_tokens": 8},
    {"decode_block_tokens": 8, "kv_page_tokens": 16},
    {},
], ids=["device-loop", "device-loop-paged", "per-token"])
def test_tick_phases_tile_the_step(tiny, mode):
    """Each phase starts where the last one ended, so the phases of a
    step add up to the step; every ``retire_wait`` is one retired
    block; the phases carry their facts."""
    phases = []
    batcher, _ = _batcher(
        tiny, trace=lambda name, ms, info=None:
        phases.append((name, ms, info)), **mode)
    inside = covered = 0.0
    while _busy(batcher):
        before, seen = time.perf_counter(), len(phases)
        batcher.step()
        inside += (time.perf_counter() - before) * 1000.0
        covered += sum(ms for _, ms, _ in phases[seen:])
    assert {name for name, _, _ in phases} == BATCHER_PHASES \
        | ({"fold"} if batcher.device_loop else set())
    assert all(ms >= 0.0 for _, ms, _ in phases)
    assert covered <= inside and (inside - covered) / inside < 0.01
    retired = sum(1 for name, _, _ in phases if name == "retire_wait")
    if batcher.device_loop:
        assert retired == batcher.blocks_retired \
            == batcher.blocks_dispatched
    else:
        assert retired == batcher.steps
    dispatches = [info for name, _, info in phases if name == "dispatch"]
    assert sum(info["blocks"] for info in dispatches) == retired
    assert max(info["slots"] for info in dispatches) == batcher.max_slots
    if batcher.device_loop:
        # Every block's fold-in is stamped apart from its enqueue, with
        # the device calls it made: the packed upload and the program,
        # the page table where a row changed, and the first block's
        # fresh carries -- never more for more joiners.
        folds = [info for name, _, info in phases if name == "fold"]
        assert len(folds) == retired
        assert sum(info["joining"] for info in dispatches) == 6 \
            == sum(info["joining"] for info in folds)
        assert folds[0]["launches"] in (8, 9)
        assert {info["launches"] for info in folds[1:]} <= {2, 3}
    chunks = sum(info["chunks"] for name, _, info in phases
                 if name == "prefill")
    assert chunks == 6              # one 16-token chunk a request


def test_no_trace_takes_no_stamp(tiny, monkeypatch):
    """``trace=None``: the only clock reads are the per-request stamps
    (submit, first admission, each emitted token)."""
    reads = []

    class Clock:
        @staticmethod
        def perf_counter():
            reads.append(1)
            return time.perf_counter()

    monkeypatch.setattr(batching, "time", Clock)
    batcher, requests = _batcher(tiny, decode_block_tokens=8)
    batcher.run_until_drained()
    assert all(request.done for request in requests)
    assert len(reads) == 2 * len(requests) + batcher.tokens_emitted


@pytest.mark.parametrize("pressed", [False, True],
                         ids=["queued", "evicted"])
def test_ttft_splits_into_queue_wait_and_admit_to_first(tiny, pressed):
    """``queue_ms + admit_to_first_ms == ttft_ms`` per request; a
    request that waits for a slot has a queue wait; ``admit_time`` is
    the FIRST admission's and survives an eviction."""
    mode = {"decode_block_tokens": 4, "kv_page_tokens": 16, "kv_pages": 9} \
        if pressed else {"decode_block_tokens": 8}
    batcher, requests = _batcher(tiny, n_requests=4 if pressed else 6,
                                 max_new=24 if pressed else 9, **mode)
    first_admission = {}
    steps = 0
    while _busy(batcher) and steps < 3000:
        batcher.step()
        steps += 1
        for request in requests:
            if request.admit_time:
                first_admission.setdefault(request.request_id,
                                           request.admit_time)
    assert all(request.done for request in requests)
    assert bool(batcher.evictions) == pressed
    for request in requests:
        assert request.admit_time == first_admission[request.request_id]
        assert request.submit_time <= request.admit_time \
            <= request.first_time
    stats = batcher.take_request_stats()
    assert len(stats) == len(requests)
    for entry in stats:
        assert entry["queue_ms"] >= 0.0 and entry["admit_to_first_ms"] > 0.0
        assert entry["queue_ms"] + entry["admit_to_first_ms"] \
            == pytest.approx(entry["ttft_ms"], abs=0.003)
    if not pressed:
        # Six requests over four slots: the last two waited for a slot
        # through at least one whole decode block.
        waits = sorted(entry["queue_ms"] for entry in stats)
        assert waits[-2] > 10 * max(waits[3], 0.001)


def test_micro_batcher_stamps_its_two_halves():
    ring = FlightRecorder(capacity=64)
    done = threading.Event()

    def finish(context, key, entries, result):
        time.sleep(0.002)
        for complete, _ in entries:
            complete("ok", result)
        done.set()

    for recorder in (ring, None):
        done.clear()
        micro = MicroBatcher(
            run=lambda context, key, payloads: len(payloads),
            finish=finish, context=lambda: None,
            schedule_flush=lambda flush: None, name="DET",
            recorder=recorder)
        for payload in range(3):
            micro.submit("key", payload, lambda *args: None, max_batch=3)
        assert done.wait(10.0)
        micro.stop()
    deadline = time.monotonic() + 10.0
    while len(ring) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    events = ring.snapshot()
    assert [(event[1], event[4]) for event in events] \
        == [("mb_run", "DET"), ("mb_finish", "DET")]
    assert all(event[6] == {"groups": 1, "frames": 3} for event in events)
    assert events[1][5] >= 2.0      # the finish half slept 2 ms


# -- the ring as a timeline ----------------------------------------------------

@pytest.mark.parametrize("case", ["whole", "since", "wrapped"])
def test_intervals_round_trip_and_say_when_the_ring_wrapped(case):
    ring = FlightRecorder(capacity=64)
    count = 100 if case == "wrapped" else 20
    for index in range(count):
        ring.record("dispatch", "s", index, "el")          # no length
        ring.record("llm_tick", None, None, "demux", 1.5 + index)
    events = [event for event in ring.snapshot() if event[5] is not None]
    since = events[len(events) // 2][0] if case == "since" else None
    if case == "wrapped":
        since = events[0][0] - 1.0      # older than anything still held
    intervals, wrapped = ring.intervals(since=since)
    kept = [event for event in events if since is None or event[0] >= since]
    assert [name for name, _, _ in intervals] \
        == ["llm_tick:demux"] * len(kept)
    for (_, start, duration), event in zip(intervals, kept):
        assert duration == pytest.approx(event[5] / 1000.0)
        assert start + duration == pytest.approx(event[0])
    assert wrapped == (case == "wrapped")
    if case == "since":
        assert len(intervals) == len(events) - len(events) // 2
        # ... and a ring that is full but still holds ``since``:
        for _ in range(40):
            ring.record("llm_tick", None, None, "demux", 0.1)
        assert len(ring) == ring.capacity
        held = ring.snapshot()[0][0]
        assert not ring.intervals(since=held)[1]
        assert ring.intervals(since=held - 1.0)[1]
        assert ring.intervals()[1]


def test_clock_anchors_and_live_recorders():
    ring = FlightRecorder(capacity=64)
    assert ring in live_recorders()
    perf_ns, wall_ns = ring.clock()
    assert abs(time.perf_counter_ns() - perf_ns) < 50e6
    assert abs(time.time_ns() - wall_ns) < 50e6
    ident = id(ring)
    del ring
    gc.collect()
    assert ident not in {id(live) for live in live_recorders()}
    assert RECORDER_CAPACITY_DEFAULT >= 4 * 3000    # the arithmetic beside it


def test_taps_never_raise_while_recorders_come_and_go():
    """The build and gc taps read the set of live recorders from
    whichever thread builds or collects while another thread creates
    (or drops) a pipeline's recorder: the set is replaced whole, so a
    tap can never see it change under its feet."""
    stop = threading.Event()
    failures = []

    def churn():
        while not stop.is_set():
            held = [FlightRecorder(capacity=64) for _ in range(8)]
            del held

    def tap():
        try:
            while not stop.is_set():
                recorder_module._record_everywhere("gc", "2", 1.0)
                live_recorders()
        except Exception as error:      # noqa: BLE001 - the assertion
            failures.append(error)

    ring = FlightRecorder(capacity=4096)
    threads = [threading.Thread(target=churn) for _ in range(2)] \
        + [threading.Thread(target=tap) for _ in range(2)]
    for thread in threads:
        thread.start()
    time.sleep(0.3)
    stop.set()
    for thread in threads:
        thread.join(10.0)
    assert not failures
    assert ring in live_recorders() and len(ring) > 0


def test_build_event_for_a_first_seen_shape_only():
    ring = FlightRecorder(capacity=256)

    @jax.jit
    def timeline_probe(x):
        return jnp.tanh(x) * 3.0 + 1.0

    def builds():
        return [event for event in ring.snapshot()
                if event[1] == "build" and "timeline_probe" in event[4]]

    timeline_probe(jnp.ones((3, 7))).block_until_ready()
    first = builds()
    assert len(first) == 1 and first[0][5] > 0.0
    timeline_probe(jnp.ones((3, 7)) * 2.0).block_until_ready()
    assert len(builds()) == 1                       # a repeat builds nothing
    timeline_probe(jnp.ones((5, 7))).block_until_ready()
    assert len(builds()) == 2


def test_gc_event_only_for_a_collection_worth_naming():
    ring = FlightRecorder(capacity=256)

    def collections():
        return [event for event in ring.snapshot() if event[1] == "gc"]

    gc.collect()
    before = len(collections())
    gc.collect(0)                                   # nothing young: cheap
    assert len(collections()) == before
    cycles = []
    for _ in range(200_000):
        cell = []
        cell.append(cell)
        cycles.append(cell)
    del cycles, cell
    gc.collect()
    found = collections()[before:]
    assert found, "a 200,000-cycle collection left no gc event"
    assert found[-1][4] == "2" and found[-1][5] >= GC_EVENT_MIN_MS
    assert found[-1][6]["collected"] >= 200_000


# -- the vocabulary --------------------------------------------------------------

def _literal_etypes():
    """Every string literal passed first to ``_rec(`` / ``.record(``
    (or the recorder's own ``_record_everywhere(``) in the package."""
    found = {}
    for path in PACKAGE.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            callee = node.func
            name = callee.attr if isinstance(callee, ast.Attribute) \
                else getattr(callee, "id", None)
            first = node.args[0]
            if name in ("_rec", "record", "_record_everywhere") \
                    and isinstance(first, ast.Constant) \
                    and isinstance(first.value, str):
                found.setdefault(first.value, []).append(
                    f"{path.relative_to(PACKAGE)}:{node.lineno}")
    return found


def test_event_types_match_what_is_emitted():
    emitted = _literal_etypes()
    assert len(emitted) > 30, "the walk found too few emission sites"
    unknown = {etype: where for etype, where in emitted.items()
               if etype not in EVENT_TYPES}
    assert not unknown, f"emitted but not in EVENT_TYPES: {unknown}"
    silent = set(EVENT_TYPES) - set(emitted)
    assert not silent, f"in EVENT_TYPES but emitted nowhere: {silent}"
    assert "llm_block" not in EVENT_TYPES
    assert len(set(EVENT_TYPES)) == len(EVENT_TYPES)


# -- through the LLM element ---------------------------------------------------

def _llm_definition(name, pipeline_parameters):
    return {
        "version": 0, "name": name, "runtime": "jax",
        "parameters": pipeline_parameters, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 8, "max_seq": 64,
                           "decode_block_tokens": 4, "kv_page_tokens": 16},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}


def _serve(runtime, definition, prompts):
    import queue

    from aiko_services_tpu.pipeline import Pipeline
    from conftest import run_until

    responses = queue.Queue()
    pipeline = Pipeline(definition, runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=120.0)
    assert run_until(
        runtime, lambda: "llm_ttft_ms" in pipeline.metrics_text())
    return pipeline


@pytest.mark.parametrize("recorder", ["on", "off"])
def test_llm_worker_timeline_through_the_pipeline(runtime, recorder):
    """The worker thread's whole time is tiled by ``llm_tick`` phases
    on the pipeline's ring; the TTFT split reaches the registry; with
    ``recorder: off`` the batcher has no tap at all."""
    pipeline = _serve(runtime, _llm_definition(
        f"llm_timeline_{recorder}", {"recorder": recorder}),
        ["hello there", "general kenobi", "you are a bold one"])
    batcher = pipeline.graph.get_node("llm").element._batcher
    metrics = pipeline.metrics_text()
    assert "llm_queue_wait_ms" in metrics
    assert "llm_admit_to_first_ms" in metrics
    if recorder == "off":
        assert pipeline.recorder is None and batcher.trace is None
        pipeline.stop()
        return
    assert pipeline.recorder in live_recorders()
    intervals, wrapped = pipeline.recorder.intervals()
    assert not wrapped
    ticks = [(name.partition(":")[2], start, duration)
             for name, start, duration in intervals
             if name.startswith("llm_tick:")]
    assert {name for name, _, _ in ticks} == BATCHER_PHASES | {
        "fold", "wait_work", "drain", "publish"}
    # From the end of the first wait (the model build is inside the
    # first ``drain``) to the end of the last phase.
    begin = ticks[0][1] + ticks[0][2]
    end = max(start + duration for _, start, duration in ticks)
    covered = sum(duration for _, _, duration in ticks[1:])
    assert covered <= (end - begin) * 1.0001
    assert covered / (end - begin) > 0.98
    assert sum(1 for name, _, _ in ticks if name == "retire_wait") \
        == batcher.blocks_retired
    assert not any(event[1] == "llm_block"
                   for event in pipeline.recorder.snapshot())
    pipeline.stop()
