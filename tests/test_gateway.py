"""Gateway front door (ISSUE 12): a REAL WebSocket client (stdlib,
loopback) streaming frames through a placed multi-stage pipeline --
session lifecycle (open/attach/backpressure/disconnect), in-order
delivery, HTTP request/response, per-tenant rate limiting, per-tenant/
class observability, and the open-loop load generator's shed-fairness
contract under 2x overload."""

import json
import queue
import socket
import threading
import time
import urllib.request

import pytest

from conftest import run_until

from aiko_services_tpu.gateway.client import GatewayClient
from aiko_services_tpu.gateway.loadgen import LoadSpec, run_loadgen
from aiko_services_tpu.gateway.server import decode_data, json_safe
from aiko_services_tpu.pipeline import Pipeline

COMMON = "aiko_services_tpu.elements.common"


def stage(name, busy_ms=5.0, factor=2.0, devices=4):
    return {"name": name, "input": [{"name": "x"}],
            "output": [{"name": "x"}],
            "parameters": {"busy_ms": busy_ms, "factor": factor},
            "placement": {"devices": devices},
            "deploy": {"local": {"module": COMMON,
                                 "class_name": "StageWork"}}}


def gateway_pipeline(runtime, qos=None, busy_ms=5.0):
    parameters = {"gateway": "on"}
    if qos is not None:
        parameters["qos"] = qos
    return Pipeline(
        {"version": 0, "name": "gw", "runtime": "jax",
         "graph": ["(detect llm)"],
         "parameters": parameters,
         "elements": [stage("detect", busy_ms),
                      stage("llm", busy_ms, factor=3.0)]},
        runtime=runtime)


def in_thread(target):
    """Run a blocking client interaction off the loop thread; returns
    (thread, box) where box collects the return value or error."""
    box: dict = {}

    def body():
        try:
            box["value"] = target()
        except Exception as error:      # surfaced by the test
            box["error"] = error
    thread = threading.Thread(target=body, daemon=True)
    thread.start()
    return thread, box


def finish(runtime, thread, box, timeout=60.0):
    run_until(runtime, lambda: not thread.is_alive(), timeout=timeout)
    assert not thread.is_alive(), "client interaction hung"
    if "error" in box:
        raise box["error"]
    return box.get("value")


# -- codec helpers ----------------------------------------------------------

def test_decode_data_and_json_safe_roundtrip():
    import numpy as np
    decoded = decode_data({"x": [[1.0, 2.0], [3.0, 4.0]],
                           "n": [1, 2, 3], "s": "hi", "f": 2.5,
                           "t": {"__tensor__": [1, 2],
                                 "dtype": "int8"}})
    assert decoded["x"].dtype == np.float32
    assert decoded["x"].shape == (2, 2)
    assert decoded["n"].dtype == np.int32
    assert decoded["s"] == "hi" and decoded["f"] == 2.5
    assert decoded["t"].dtype == np.int8
    safe = json_safe({"x": np.ones((2,), np.float32),
                      "o": object(), "b": b"ab"})
    assert safe["x"] == [1.0, 1.0]
    assert safe["o"] == "<object>" and safe["b"] == "ab"


# -- the tier-1 acceptance path ---------------------------------------------

def test_ws_client_streams_n_frames_in_order(runtime):
    """ISSUE 12 acceptance: a real WebSocket client opens a session,
    streams N frames through a placed two-stage pipeline, and
    receives N in-order results -- stdlib client, loopback, no
    external broker."""
    pipeline = gateway_pipeline(runtime)
    n_frames = 8
    # the front door is a discoverable capability of the Service: the
    # registrar record advertises it like the tensor pipe's tag.
    assert any(tag == f"gateway=127.0.0.1:{pipeline.gateway.port}"
               for tag in pipeline.tags), pipeline.tags
    assert pipeline.share["gateway_port"] == pipeline.gateway.port

    def interact():
        with GatewayClient("127.0.0.1", pipeline.gateway.port) as c:
            opened = c.open(session="s1", tenant="t1")
            assert opened["attached"] is False
            for i in range(n_frames):
                c.send_frame({"x": [float(i + 1)] * 4})
            return [c.next_result() for _ in range(n_frames)]

    thread, box = in_thread(interact)
    results = finish(runtime, thread, box)
    assert [r["frame"] for r in results] == list(range(n_frames))
    for i, result in enumerate(results):
        assert result["ok"], result
        # detect *2 then llm *3: the engine really ran the frame
        assert result["data"]["x"][0] == pytest.approx(6.0 * (i + 1))
    run_until(runtime, lambda: not pipeline.streams, timeout=30.0)
    assert pipeline.gateway.session_count() == 0


def test_ws_attach_takes_over_session(runtime):
    """``open`` with an existing session id attaches: the stream (and
    its frame numbering) continues; the old connection's death no
    longer destroys the session."""
    pipeline = gateway_pipeline(runtime)
    port = pipeline.gateway.port

    def interact():
        c1 = GatewayClient("127.0.0.1", port)
        c1.open(session="s2")
        c1.send_frame({"x": [1.0]})
        first = c1.next_result()
        # attach without the minted token is a refused hijack, not a
        # takeover -- session ids are client-chosen guessable strings.
        hijacker = GatewayClient("127.0.0.1", port)
        hijacker.send({"op": "open", "session": "s2",
                       "tenant": "mallory"})
        refused = hijacker.recv(timeout=10.0)
        hijacker.sock.close()
        assert refused["op"] == "error", refused
        c2 = GatewayClient("127.0.0.1", port)
        opened = c2.open(session="s2", token=c1.token)
        assert opened["attached"] is True
        c1.sock.close()                 # abrupt: no close handshake
        time.sleep(0.2)                 # let the server notice
        c2.send_frame({"x": [2.0]})
        second = c2.next_result()
        c2.close()
        return first, second

    thread, box = in_thread(interact)
    first, second = finish(runtime, thread, box)
    assert first["frame"] == 0 and second["frame"] == 1, \
        "attach did not continue the same stream"
    run_until(runtime, lambda: not pipeline.streams, timeout=30.0)
    assert not pipeline.streams


def test_ws_backpressure_busy_at_window(runtime):
    """The per-session window bounds in-flight frames: the overflow
    frame gets ``busy`` instead of queueing unboundedly."""
    pipeline = gateway_pipeline(runtime, busy_ms=60.0)

    def interact():
        with GatewayClient("127.0.0.1", pipeline.gateway.port) as c:
            c.open(session="s3", window=1)
            ops = []
            for i in range(3):
                c.send_frame({"x": [float(i)]}, tag=i)
            deadline = time.monotonic() + 30.0
            results = 0
            while results < 1 and time.monotonic() < deadline:
                message = c.recv(timeout=10.0)
                ops.append(message["op"])
                if message["op"] == "result":
                    results += 1
            return ops

    thread, box = in_thread(interact)
    ops = finish(runtime, thread, box)
    assert "busy" in ops, ops


def test_ws_disconnect_mid_stream_cleans_up(runtime):
    """A dangling disconnect destroys the session's pipeline stream:
    no leaked streams, no leaked sessions."""
    pipeline = gateway_pipeline(runtime, busy_ms=30.0)

    def interact():
        c = GatewayClient("127.0.0.1", pipeline.gateway.port)
        c.open(session="s4")
        for i in range(3):
            c.send_frame({"x": [float(i)]})
        c.sock.close()                  # mid-stream, no close op

    thread, box = in_thread(interact)
    finish(runtime, thread, box)
    run_until(runtime,
              lambda: not pipeline.streams
              and pipeline.gateway.session_count() == 0,
              timeout=30.0)
    assert not pipeline.streams, "disconnect leaked the stream"
    assert pipeline.gateway.session_count() == 0


def test_ws_malformed_data_and_window_clamp(runtime):
    """Review hardening: a malformed payload costs a ``rejected``
    reply (never the connection, never a window slot); a client-
    requested window is clamped to the policy's session_window
    ceiling."""
    pipeline = gateway_pipeline(runtime,
                                qos={"session_window": 4})

    def interact():
        with GatewayClient("127.0.0.1", pipeline.gateway.port) as c:
            opened = c.open(session="s6", window=1000000000)
            assert opened["window"] == 4, opened    # clamped
            c.send_frame({"x": [[1.0, 2.0], 3.0]})  # ragged mix
            reply = c.recv(timeout=10.0)
            # the connection survived: a good frame still works
            c.send_frame({"x": [2.0]})
            result = c.next_result()
            return reply, result

    thread, box = in_thread(interact)
    reply, result = finish(runtime, thread, box)
    assert reply["op"] in ("rejected", "result"), reply
    if reply["op"] == "rejected":
        assert reply["reason"] == "bad-data"
    assert result["ok"] and result["data"]["x"][0] == 12.0


def test_create_failure_after_bind_closes_the_gateway(runtime):
    """Review hardening: a create-time DefinitionError raised AFTER
    the gateway binds (qos parse, graph build) must not leak the
    listening socket serving a half-constructed pipeline."""
    from aiko_services_tpu.pipeline.definition import DefinitionError

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()                       # freed for the doomed pipeline
    definition = {
        "version": 0, "name": "gw_broken", "runtime": "jax",
        "graph": ["(detect llm)"],
        "parameters": {"gateway": "on", "gateway_port": port,
                       "preflight": "off",
                       "qos": {"tenants": {"a": {"class": "gold"}}}},
        "elements": [stage("detect"), stage("llm")]}
    with pytest.raises(DefinitionError):
        Pipeline(definition, runtime=runtime)
    with pytest.raises(OSError):
        socket.create_connection(("127.0.0.1", port), timeout=2.0)


def test_ws_payload_bound_kills_oversized_frames():
    """Review hardening: an attacker-chosen 64-bit frame length (or
    endless continuation fragments) must die at the codec bound, not
    buffer into RAM."""
    import socket as socket_module
    import struct

    from aiko_services_tpu.gateway import ws

    a, b = socket_module.socketpair()
    try:
        # FIN text frame claiming an 8 GiB payload
        a.sendall(bytes([0x81, 127]) + struct.pack(">Q", 8 << 30))
        with pytest.raises(ws.WsClosed, match="bound"):
            ws.recv_frame(b)
    finally:
        a.close()
        b.close()
    a, b = socket_module.socketpair()
    try:
        chunk = b"x" * 1024
        # non-FIN text frame, then continuation fragments past the cap
        a.sendall(bytes([0x01, 126]) + struct.pack(">H", len(chunk))
                  + chunk)
        for _ in range(4):
            a.sendall(bytes([0x00, 126]) + struct.pack(">H", len(chunk))
                      + chunk)

        with pytest.raises(ws.WsClosed, match="bound"):
            ws.recv_message(b, max_payload=2048)
    finally:
        a.close()
        b.close()


def test_lazy_tenant_cap_bounds_cardinality():
    """Unauthenticated tenant names must not grow scheduler state
    without bound: past LAZY_TENANT_CAP, unknown names share the
    default entry."""
    from aiko_services_tpu.gateway.qos import (LAZY_TENANT_CAP,
                                               QosScheduler)
    qos = QosScheduler({"tenants": {"alice": {"budget": 8}}})
    for index in range(LAZY_TENANT_CAP + 50):
        qos.tenant(f"rando-{index}")
    # configured + cap (+ the shared default overflow entry)
    assert len(qos.tenants) <= 1 + LAZY_TENANT_CAP + 1
    overflow = qos.tenant("rando-way-past-the-cap")
    assert overflow.name == "default"
    assert qos.tenant("alice").budget == 8      # configured untouched


# -- HTTP + admission -------------------------------------------------------

def test_http_frame_request_response_and_rate_limit(runtime):
    """POST /v1/frames runs one frame request/response; the tenant's
    token bucket rejects the over-rate call with 429."""
    pipeline = gateway_pipeline(
        runtime,
        qos={"tenants": {"meter": {"rate": 0.5, "burst": 1}}})
    port = pipeline.gateway.port

    def post(payload):
        request = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/frames",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(request, timeout=30) as reply:
                return reply.status, json.loads(reply.read())
        except urllib.error.HTTPError as error:
            return error.code, json.loads(error.read())

    def interact():
        first = post({"tenant": "meter", "data": {"x": [2.0, 2.0]}})
        second = post({"tenant": "meter", "data": {"x": [2.0]}})
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/stats", timeout=10).read())
        return first, second, health, stats

    thread, box = in_thread(interact)
    first, second, health, stats = finish(runtime, thread, box)
    status, body = first
    assert status == 200 and body["ok"]
    assert body["data"]["x"] == [12.0, 12.0]    # *2 then *3
    status, body = second
    assert status == 429 and body["reason"] == "rate"
    assert health["ok"] is True
    assert stats["qos"]["tenants"]["meter"]["rejected"] >= 1
    run_until(runtime, lambda: not pipeline.streams, timeout=30.0)


def test_ws_rate_limit_rejected_and_observability(runtime):
    """Over-rate WS frames get ``rejected`` (reason rate); admission
    and rejection both land on the metrics plane (labeled counters),
    the ring (gw_admit/gw_reject), and the telemetry rollup's tenant
    rows."""
    pipeline = gateway_pipeline(
        runtime,
        qos={"tenants": {"metered": {"rate": 1.0, "burst": 2,
                                     "class": "interactive"}}})

    def interact():
        with GatewayClient("127.0.0.1", pipeline.gateway.port) as c:
            c.open(session="s5", tenant="metered")
            for i in range(4):
                c.send_frame({"x": [1.0]})
            seen = {"result": 0, "rejected": 0}
            deadline = time.monotonic() + 30.0
            while sum(seen.values()) < 4 \
                    and time.monotonic() < deadline:
                message = c.recv(timeout=10.0)
                if message["op"] in seen:
                    seen[message["op"]] += 1
            return seen

    thread, box = in_thread(interact)
    seen = finish(runtime, thread, box)
    assert seen["result"] == 2 and seen["rejected"] == 2, seen
    text = pipeline.metrics_text()
    assert 'gateway_admits{cls="interactive",tenant="metered"}' in text
    assert 'gateway_rejects' in text and 'reason="rate"' in text
    assert "gateway_e2e_ms" in text
    assert 'qos_inflight{tenant="metered"}' in text
    events = {e[1] for e in pipeline.recorder.snapshot()}
    assert "gw_admit" in events and "gw_reject" in events
    rollup = pipeline.telemetry.rollup()
    assert rollup["tenants"]["metered"]["admitted"] == 2
    assert rollup["tenants"]["metered"]["rejected"] == 2
    assert "interactive" in rollup.get("gateway", {})


# -- load generator + overload fairness -------------------------------------

def test_loadgen_overload_sheds_batch_not_interactive(runtime):
    """2x overload through the REAL gateway: the interactive tenant
    (in budget) keeps 100% goodput while the over-budget batch tenant
    absorbs every shed -- the Vortex contract, measured by the
    ``loadgen`` command's generator."""
    pipeline = gateway_pipeline(
        runtime,
        qos={"classes": {"batch": {"device_inflight": 1}},
             "tenants": {"alice": {"class": "interactive",
                                   "budget": 32},
                         "bulk": {"class": "batch", "budget": 2}},
             "max_inflight": 8, "age_ms": 60000},
        busy_ms=15.0)
    # busy_ms=15 per stage bounds the pipeline near ~66 fps even with
    # every jit warm (suite order must not turn the overload into
    # headroom): ~105 fps offered is a genuine ~1.6x overload, with
    # the interactive tenant comfortably inside capacity.
    specs = [
        LoadSpec("alice", "interactive", rate=15.0, frames=30,
                 data={"x": [1.0] * 8}),
        LoadSpec("bulk", "batch", rate=90.0, frames=120,
                 data={"x": [1.0] * 8}),
    ]

    def interact():
        return run_loadgen("127.0.0.1", pipeline.gateway.port, specs)

    thread, box = in_thread(interact)
    report = finish(runtime, thread, box, timeout=180.0)
    assert report["errors"] == []
    alice = report["tenants"]["alice"]
    bulk = report["tenants"]["bulk"]
    assert alice["sent"] == 30 and bulk["sent"] == 120
    assert alice["ok"] == 30, alice      # interactive: zero loss
    assert alice["shed"] == 0
    assert bulk["shed"] >= 1, bulk       # batch absorbed the shedding
    stats = pipeline.qos_stats()
    assert stats["tenants"]["bulk"]["shed"] >= 1
    assert stats["tenants"]["alice"]["shed"] == 0
    classes = report["classes"]
    assert classes["interactive"]["p99_ms"] > 0
    assert classes["interactive"]["goodput_fps"] > 0


def test_loadgen_receiver_that_gives_up_is_an_error(runtime, monkeypatch):
    """ISSUE 21: a receiver that stops waiting with frames still owed
    reports it in ``errors`` -- it used to return silently
    (``socket.timeout`` is an OSError), so a cold compile of the first
    frame produced a short report and no failure."""
    from aiko_services_tpu.gateway import loadgen
    monkeypatch.setattr(loadgen, "RECV_SILENCE_S", 0.2)
    pipeline = gateway_pipeline(runtime, busy_ms=1500.0)   # "compiling"
    specs = [LoadSpec("alice", "interactive", rate=50.0, frames=2,
                      data={"x": [1.0] * 4})]

    thread, box = in_thread(
        lambda: run_loadgen("127.0.0.1", pipeline.gateway.port, specs))
    report = finish(runtime, thread, box, timeout=120.0)
    assert report["tenants"]["alice"]["sent"] == 2
    assert report["tenants"]["alice"]["ok"] < 2
    assert any("receiver gave up" in error
               for error in report["errors"]), report["errors"]
