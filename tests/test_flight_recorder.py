"""Flight recorder + critical-path attribution (ISSUE 10): the event
ring's cost contract (no-op when off, <= 1% fps when on), bucket
attribution summing to measured e2e within 5%, explain()/explain_frame
surfaces (API + HTTP), and the black-box dump a device_kill leaves
behind -- with the offline CLI rendering it."""

import json
import queue
import time
import urllib.request

import numpy as np
import pytest

from conftest import run_until

from aiko_services_tpu.observability import (
    BUCKETS, FlightRecorder, MetricsServer, attribute_events,
    attribute_metrics, events_as_dicts, render_buckets,
    render_timeline, write_blackbox)
from aiko_services_tpu.pipeline import (Pipeline, PipelineElement,
                                        StreamEvent)

COMMON = "aiko_services_tpu.elements.common"


class Sleeper(PipelineElement):
    """Deterministic host-side work: fps is sleep-bound, so the
    recorder's per-event cost is measurable against it."""

    def process_frame(self, stream, x):
        sleep_ms, _ = self.get_parameter("sleep_ms", 4.0)
        time.sleep(float(sleep_ms) / 1000.0)
        return StreamEvent.OKAY, {"x": x}


def element(name, cls="StageWork", module=COMMON, parameters=None,
            placement=None):
    entry = {"name": name, "input": [{"name": "x"}],
             "output": [{"name": "x"}],
             "parameters": parameters or {},
             "deploy": {"local": {"module": module, "class_name": cls}}}
    if placement:
        entry["placement"] = placement
    return entry


def pump(runtime, pipeline, n, stream_id="s", value=None):
    responses = queue.Queue()
    for i in range(n):
        pipeline.process_frame_local(
            {"x": np.float32(i) if value is None else value},
            stream_id=stream_id, queue_response=responses)
    assert run_until(runtime, lambda: responses.qsize() >= n,
                     timeout=60.0)
    rows = [responses.get() for _ in range(n)]
    assert all(row[4] for row in rows), \
        [row[5] for row in rows if not row[4]]
    return rows


# -- recorder units ----------------------------------------------------------

def test_ring_bounds_and_snapshot_filters():
    recorder = FlightRecorder(capacity=64)
    for i in range(200):
        recorder.record("dispatch", "s", i % 4, "el")
    assert len(recorder) == 64                  # bounded
    assert recorder.recorded == 200
    only = recorder.snapshot(stream="s", frame=1)
    assert only and all(event[3] == 1 for event in only)
    assert recorder.snapshot(tail=5) == recorder.snapshot()[-5:]
    # global events (stream/frame None) never join a frame's timeline
    recorder.record("llm_tick", None, None, "dispatch")
    assert all(event[1] != "llm_tick"
               for event in recorder.snapshot(frame=1))


def test_record_cost_is_microseconds():
    """The always-on contract: one event is a tuple append -- if this
    regresses to dict/lock territory the e2e overhead gate follows."""
    recorder = FlightRecorder(capacity=4096)
    count = 20000
    start = time.perf_counter()
    for i in range(count):
        recorder.record("dispatch", "s", i, "el")
    per_event = (time.perf_counter() - start) / count
    assert per_event < 20e-6, f"{per_event * 1e6:.2f} us/event"


def test_events_as_dicts_and_blackbox_prune(tmp_path):
    recorder = FlightRecorder()
    recorder.record("ingest", "s", 0)
    recorder.record("hop", "s", 0, "det", 1.25, {"replica": 1})
    dicts = events_as_dicts(recorder.snapshot())
    assert dicts[1]["type"] == "hop" and dicts[1]["ms"] == 1.25
    assert dicts[1]["replica"] == 1
    for i in range(5):
        write_blackbox(tmp_path, {"reason": f"r{i}", "events": dicts},
                       limit=3)
    dumps = sorted(tmp_path.glob("blackbox_*.json"))
    assert len(dumps) == 3                      # oldest pruned
    payload = json.loads(dumps[-1].read_text())
    assert payload["reason"] == "r4"


def test_blackbox_redacts_unserializable():
    import pathlib
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        path = write_blackbox(tmp, {"reason": "x",
                                    "bad": np.zeros((2, 2))})
        payload = json.loads(pathlib.Path(path).read_text())
        assert payload["bad"] == "<ndarray>"    # type name, no bytes


# -- attribution units -------------------------------------------------------

def test_attribute_events_state_machine():
    base = 100.0
    events = [
        (base + 0.000, "ingest", "s", 0, None, None, None),
        (base + 0.004, "pace", "s", 0, None, 3.0, None),     # 3ms pace
        (base + 0.005, "dispatch", "s", 0, "A", None, None),
        (base + 0.015, "dispatch_done", "s", 0, "A", 10.0, None),
        (base + 0.016, "hop", "s", 0, "B", 1.0, None),       # 1ms hop
        (base + 0.017, "park", "s", 0, "R", None, {"kind": "remote"}),
        (base + 0.027, "response", "s", 0, "R", None, None),
        (base + 0.030, "done", "s", 0, None, None, {"ok": True}),
    ]
    report = attribute_events(events)
    buckets = report["buckets"]
    assert buckets["pacing"] == pytest.approx(3.0, abs=0.01)
    assert buckets["compute"] == pytest.approx(10.0, abs=0.01)
    assert buckets["hop"] == pytest.approx(1.0, abs=0.01)
    assert buckets["pipe"] == pytest.approx(10.0, abs=0.01)
    # totality: every interval lands in a bucket, sums == event span
    assert sum(buckets.values()) == pytest.approx(report["e2e_ms"],
                                                  abs=0.01)
    assert report["e2e_ms"] == pytest.approx(30.0, abs=0.01)
    assert len(report["timeline"]) == len(events)
    assert render_timeline(report["timeline"])  # renders without error


def test_attribute_events_replay_reclassifies():
    events = [
        (0.000, "ingest", "s", 0, None, None, None),
        (0.001, "dispatch", "s", 0, "A", None, None),
        (0.021, "replay", "s", 0, "A", None, {"attempt": 1}),
        (0.025, "dispatch", "s", 0, "A", None, None),
        (0.035, "done", "s", 0, None, None, {"ok": True}),
    ]
    report = attribute_events(events)
    # the 20ms of in-flight work the replay voided bills to replay,
    # the re-run's 10ms to compute
    assert report["buckets"]["replay"] == pytest.approx(20.0, abs=0.01)
    assert report["buckets"]["compute"] == pytest.approx(10.0, abs=0.01)


def test_attribute_metrics_classification():
    metrics = {"time_pipeline": 0.100,
               "A_time": 0.040, "A_time_start": 123.0,
               "stage_B_wait_ms": 10.0, "B_queue_ms": 5.0,
               "B_hop_ms": 2.0, "B_time": 0.020,
               "stage_B_replica": 1,
               "A_fetch_ms": 3.0, "remote_C_ms": 15.0,
               "ingest_pace_ms": 4.0, "replay_lost_ms": 1.0,
               "stage_B_ms": 999.0,     # residency total: NOT a bucket
               "deadline_missed": True}
    report = attribute_metrics(metrics)
    buckets = report["buckets"]
    assert buckets["compute"] == pytest.approx(60.0)
    assert buckets["queue"] == pytest.approx(15.0)
    assert buckets["hop"] == pytest.approx(2.0)
    assert buckets["fetch"] == pytest.approx(3.0)
    assert buckets["pipe"] == pytest.approx(15.0)
    assert buckets["pacing"] == pytest.approx(4.0)
    assert buckets["replay"] == pytest.approx(1.0)
    assert set(buckets) == set(BUCKETS)
    # per-stage carries the replica suffix
    assert report["stages"]["B#1"]["compute"] == pytest.approx(20.0)
    assert "stage_B_ms" not in str(report)      # residency not double-counted
    assert render_buckets(report)


# -- acceptance: buckets sum to measured e2e within 5% -----------------------

def placed_pipeline(runtime, name="p_sum", parameters=None):
    return Pipeline(
        {"version": 0, "name": name, "runtime": "jax",
         "graph": ["(sa (sb))"],
         "parameters": dict(parameters or {}),
         "elements": [
             element("sa", parameters={"busy_ms": 20.0},
                     placement={"mesh": {"dp": 4}}),
             element("sb", parameters={"busy_ms": 20.0},
                     placement={"mesh": {"dp": 4}})]},
        runtime=runtime)


def test_bucket_totals_sum_to_e2e_within_5pct(runtime):
    """The ISSUE 10 acceptance bar: per-frame bucket totals cover the
    measured e2e latency within 5% on a stage-parallel placed pipeline
    (compute on workers, admission waits, hops, worker queues)."""
    pipeline = placed_pipeline(runtime)
    pump(runtime, pipeline, 2)          # jit + fusion-plan warmup
    rows = pump(runtime, pipeline, 6)
    for *_, metrics, _okay, _diag in rows:
        report = attribute_metrics(metrics)
        assert report["e2e_ms"] > 0
        gap = abs(report["e2e_ms"] - report["attributed_ms"])
        assert gap / report["e2e_ms"] <= 0.05, (gap, report)
        assert report["buckets"]["compute"] >= 35.0   # 2 x 20ms busy
    # the aggregate view agrees
    explanation = pipeline.explain(top_k=3)
    assert explanation["frames"] >= 6
    assert explanation["top"][0]["bucket"] in ("compute", "queue")
    assert sum(explanation["buckets"].values()) > 0
    pipeline.stop()


def test_explain_frame_timeline_live(runtime):
    pipeline = placed_pipeline(runtime, name="p_tl")
    pump(runtime, pipeline, 2)
    pump(runtime, pipeline, 3)
    story = pipeline.explain_frame(3, "s")      # a post-warmup frame
    assert story is not None
    types = [entry["type"] for entry in story["timeline"]]
    assert types[0] == "ingest" and types[-1] == "done"
    for expected in ("stage_wait", "admit", "hop", "dispatch",
                     "dispatch_done", "release"):
        assert expected in types, (expected, types)
    assert story["buckets"]["compute"] > 0
    assert story["trace_id"] and story["spans"]
    # totality of the event timeline
    assert sum(story["buckets"].values()) == pytest.approx(
        story["e2e_ms"], rel=0.01)
    assert pipeline.explain_frame(99999, "s") is None
    pipeline.stop()


# -- overhead gate -----------------------------------------------------------

def test_recorder_overhead_under_1pct(runtime):
    """Recorder-on vs recorder-off fps on a sleep-bound pipeline:
    the event ring must cost <= 1% (it records ~6 events around two
    4 ms sleeps -- microseconds against milliseconds)."""
    def build(name, mode):
        return Pipeline(
            {"version": 0, "name": name, "runtime": "jax",
             "graph": ["(e1 (e2))"],
             "parameters": {"recorder": mode},
             "elements": [
                 element("e1", "Sleeper",
                         module="tests/test_flight_recorder.py",
                         parameters={"sleep_ms": 4.0}),
                 element("e2", "Sleeper",
                         module="tests/test_flight_recorder.py",
                         parameters={"sleep_ms": 4.0})]},
            runtime=runtime)

    def best_elapsed(pipeline, passes=3, frames=25):
        best = None
        for _ in range(passes):
            start = time.perf_counter()
            pump(runtime, pipeline, frames)
            elapsed = time.perf_counter() - start
            best = elapsed if best is None else min(best, elapsed)
        return best

    off = build("p_off", "off")
    on = build("p_on", "on")
    assert off.recorder is None and on.recorder is not None
    pump(runtime, off, 2)
    pump(runtime, on, 2)                # warm both
    # Wall-clock A/B at the ~1% scale is scheduler-jitter territory:
    # re-measure up to 3 times and pass on any clean attempt -- a
    # GENUINE >1% recorder cost fails all three, a background-load
    # blip on one attempt does not fail tier-1.
    overhead = None
    for _attempt in range(3):
        off_elapsed = best_elapsed(off)
        on_elapsed = best_elapsed(on)
        overhead = (on_elapsed - off_elapsed) / off_elapsed
        if overhead <= 0.01:
            break
    assert on.recorder.recorded > 0
    off.stop()
    on.stop()
    assert overhead <= 0.01, f"recorder overhead {overhead:.2%}"


def test_recorder_off_is_noop(runtime):
    pipeline = Pipeline(
        {"version": 0, "name": "p_noop", "runtime": "jax",
         "graph": ["(A)"],
         "parameters": {"recorder": "off"},
         "elements": [element("A", "Increment")]},
        runtime=runtime)
    rows = pump(runtime, pipeline, 3, value=1)
    assert rows[0][4]
    assert pipeline.recorder is None
    # no ring -> no recorder gauges, no event timeline; metric-based
    # attribution (telemetry) still works
    assert "aiko_recorder_events" not in pipeline.metrics_text()
    story = pipeline.explain_frame(0, "s")
    assert story is not None and "timeline" not in story
    assert story["buckets"]["compute"] >= 0
    pipeline.stop()


# -- black box: device_kill leaves a dump the CLI renders --------------------

def test_device_kill_blackbox_dump_and_cli(runtime, tmp_path):
    """Acceptance: an injected device_kill (FaultPlan) produces a
    black-box dump whose timeline contains the faulted frame's replay
    transition -- and the offline CLI renders it."""
    pipeline = Pipeline(
        {"version": 0, "name": "p_bb", "runtime": "jax",
         "graph": ["(sq)"],
         "parameters": {
             "blackbox_dir": str(tmp_path),
             "health_probe_timeout": 2.0,
             "fault_plan": {"rules": [
                 {"point": "element_raise", "target": "sq", "count": 1},
                 {"point": "device_kill", "target": "sq", "count": 1},
             ]}},
         "elements": [element("sq", "BusyStage",
                              module="tests/test_chaos.py",
                              parameters={"busy_ms": 0.0},
                              placement={"mesh": {"dp": 4}})]},
        runtime=runtime)
    rows = pump(runtime, pipeline, 1, stream_id="0",
                value=np.float32(3.0))
    assert rows[0][4], rows[0][5]
    assert pipeline.share["frames_replayed"] == 1
    assert pipeline.share["blackbox_dumps"] >= 1
    dumps = sorted(tmp_path.glob("blackbox_*.json"))
    assert dumps, "device_kill recovery wrote no black-box dump"
    payload = json.loads(dumps[0].read_text())
    assert payload["reason"] == "replay"
    assert payload["pipeline"] == "p_bb"
    replay_events = [event for event in payload["events"]
                     if event["type"] == "replay"]
    assert replay_events and replay_events[0]["frame"] == 0
    assert replay_events[0]["attempt"] == 1
    # redaction: frame states carry swag KEYS and numbers, no arrays
    for state in payload["frames"]:
        assert all(isinstance(v, (int, float, bool, str))
                   for v in state["metrics"].values())
    # the dispatch that died is on the timeline before the replay
    types = [event["type"] for event in payload["events"]
             if event.get("frame") == 0]
    assert "dispatch" in types[:types.index("replay")]
    pipeline.stop()

    from click.testing import CliRunner
    from aiko_services_tpu.cli import main as cli_main
    result = CliRunner().invoke(
        cli_main, ["explain", str(dumps[0]), "--frame", "0"])
    assert result.exit_code == 0, result.output
    assert "replay" in result.output
    assert "attribution:" in result.output
    assert "black box: replay" in result.output


def test_blackbox_debounced_per_reason(runtime, tmp_path):
    """A sustained failure episode (every frame missing its deadline)
    must cost ONE dump per cooldown window, not a serialize+glob on
    the event loop per failure."""
    pipeline = Pipeline(
        {"version": 0, "name": "p_db", "runtime": "jax",
         "graph": ["(A)"],
         "parameters": {"blackbox_dir": str(tmp_path)},
         "elements": [element("A", "Increment")]},
        runtime=runtime)
    for _ in range(5):
        pipeline._blackbox("deadline_miss", "s", 0)
    pipeline._blackbox("breaker_open", "s", 0)   # distinct reason
    assert pipeline.share["blackbox_dumps"] == 2
    assert len(list(tmp_path.glob("blackbox_*.json"))) == 2
    pipeline.stop()


def test_explain_frame_never_merges_same_id_streams(runtime):
    """Frame ids restart per stream: explain_frame(0) with no stream
    must pick ONE stream's frame 0 (the newest), never interleave two
    frames' events into a fictional timeline."""
    pipeline = Pipeline(
        {"version": 0, "name": "p_ids", "runtime": "jax",
         "graph": ["(A)"],
         "elements": [element("A", "Increment")]},
        runtime=runtime)
    pump(runtime, pipeline, 2, stream_id="a", value=1)
    pump(runtime, pipeline, 2, stream_id="b", value=1)
    story = pipeline.explain_frame(0)           # stream omitted
    assert story is not None
    raw = pipeline.recorder.snapshot(frame=0)
    assert {str(event[2]) for event in raw} == {"a", "b"}
    # ...but the story is single-stream (the newest: "b")
    assert story["stream"] == "b"
    assert len(story["timeline"]) == len(
        pipeline.recorder.snapshot(stream="b", frame=0))
    pipeline.stop()


def test_explain_frame_survives_stream_recreation(runtime):
    """A destroyed-and-recreated same-id stream restarts frame ids at
    0: explain_frame must use only the NEWEST incarnation's segment
    (split at the ring's stream_end marker), not merge both frame-0
    timelines or terminate at the dead incarnation's done event."""
    pipeline = Pipeline(
        {"version": 0, "name": "p_reinc", "runtime": "jax",
         "graph": ["(A)"],
         "elements": [element("A", "Increment")]},
        runtime=runtime)
    pump(runtime, pipeline, 1, stream_id="s", value=1)
    pipeline._destroy_stream_now("s")
    pump(runtime, pipeline, 1, stream_id="s", value=1)  # frame 0 again
    # the ring holds BOTH incarnations' frame-0 events...
    raw = pipeline.recorder.snapshot(stream="s", frame=0)
    assert sum(1 for event in raw if event[1] == "ingest") == 2
    # ...but the story is single-incarnation: one ingest, one done
    story = pipeline.explain_frame(0, "s")
    types = [entry["type"] for entry in story["timeline"]]
    assert types.count("ingest") == 1 and types.count("done") == 1
    assert types[-1] == "done"
    pipeline.stop()


def test_cli_interleaved_dump_skips_bogus_attribution(tmp_path):
    """A dump with no trigger frame (replica_failover) interleaves
    many frames: the CLI must render the raw timeline and point at
    --frame, NOT run the single-frame state machine across frames."""
    from click.testing import CliRunner
    from aiko_services_tpu.cli import main as cli_main

    dump = tmp_path / "blackbox_x_replica_failover.json"
    dump.write_text(json.dumps({
        "reason": "replica_failover", "pipeline": "p", "frame": None,
        "frames": [],
        "events": [
            {"t": 0.0, "type": "ingest", "stream": "s", "frame": 0},
            {"t": 0.01, "type": "ingest", "stream": "s", "frame": 1},
            {"t": 0.02, "type": "dispatch", "stream": "s", "frame": 0,
             "name": "A"},
            {"t": 0.03, "type": "done", "stream": "s", "frame": 0,
             "ok": True}]}))
    result = CliRunner().invoke(cli_main, ["explain", str(dump)])
    assert result.exit_code == 0, result.output
    assert "interleaved timeline" in result.output
    assert "\nattribution:" not in result.output   # no bucket table
    assert "re-run with --frame" in result.output
    assert "s/0" in result.output and "s/1" in result.output
    focused = CliRunner().invoke(
        cli_main, ["explain", str(dump), "--frame", "0"])
    assert focused.exit_code == 0, focused.output
    assert "attribution:" in focused.output


def test_cli_renders_saved_explain_frame_body(runtime, tmp_path):
    """A saved ``GET /explain?frame=`` body carries ``events`` as an
    integer COUNT -- the CLI must render its timeline, not mistake it
    for a black-box dump and iterate the int."""
    from click.testing import CliRunner
    from aiko_services_tpu.cli import main as cli_main

    pipeline = placed_pipeline(runtime, name="p_saved")
    pump(runtime, pipeline, 3)
    body = pipeline.explain_frame(1, "s")
    assert isinstance(body["events"], int)      # the collision shape
    saved = tmp_path / "explain_frame.json"
    saved.write_text(json.dumps(body))
    result = CliRunner().invoke(cli_main, ["explain", str(saved)])
    assert result.exit_code == 0, result.output
    assert "attribution:" in result.output
    assert "dispatch" in result.output
    pipeline.stop()


# -- HTTP surfaces -----------------------------------------------------------

def test_explain_http_route_and_traces_limit(runtime):
    pipeline = placed_pipeline(runtime, name="p_http10")
    pump(runtime, pipeline, 4)
    server = MetricsServer(pipeline, port=0, host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{server.port}"
        report = json.loads(urllib.request.urlopen(
            f"{base}/explain", timeout=5.0).read())
        assert report["frames"] >= 4 and report["top"]
        assert set(report["buckets"]) == set(BUCKETS)
        one = json.loads(urllib.request.urlopen(
            f"{base}/explain?frame=2&stream=s", timeout=5.0).read())
        assert one["frame"] == 2 and one["timeline"]
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            urllib.request.urlopen(f"{base}/explain?frame=424242",
                                   timeout=5.0)
        assert excinfo.value.code == 404
        # /traces?limit= (default 50) bounds the body
        payload = json.loads(urllib.request.urlopen(
            f"{base}/traces?limit=2", timeout=5.0).read())
        assert len(payload["traces"]) == 2
        payload = json.loads(urllib.request.urlopen(
            f"{base}/traces", timeout=5.0).read())
        assert len(payload["traces"]) <= 50
        for bad in ("0", "-3", "zzz"):
            with pytest.raises(urllib.error.HTTPError) as excinfo:
                urllib.request.urlopen(f"{base}/traces?limit={bad}",
                                       timeout=5.0)
            assert excinfo.value.code == 400
    finally:
        server.stop()
        pipeline.stop()
