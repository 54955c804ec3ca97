"""Guarded elastic fleet controller (ISSUE 20): the control loop's
guardrails -- hysteresis, cooldowns, bounded budget with loud refusal,
observe-mode dry run, fleet-epoch fencing -- plus the actuator seams
(stage/device inflight knobs, per-replica canary swap + rollback), the
FleetSupervisor respawn harness, and the pipeline integration (guarded
tick: controller death leaves the fleet serving).

The multi-process variant (real SIGKILL, real broker, a pilot whose
controller scales a real fleet) is the ``slow``-marked chaos driver
``--mode controller`` test at the bottom.
"""

import subprocess
import sys
import time

import pytest

from conftest import run_until

from aiko_services_tpu.orchestration.controller import (
    ACTION_KINDS, CONTROLLER_MODES, ControllerSpec, FleetController,
    FleetSupervisor, controller_spec_error, peer_definition)
from aiko_services_tpu.pipeline import DefinitionError, Pipeline
from aiko_services_tpu.pipeline.definition import \
    parse_pipeline_definition
from aiko_services_tpu.pipeline.stages import (REPLICA_DEAD,
                                               REPLICA_HALF_OPEN,
                                               REPLICA_LIVE,
                                               ReplicaGroup)

COMMON = "aiko_services_tpu.elements.common"


# -- fakes (the controller is duck-typed off the pipeline) ------------------

class Clock:
    def __init__(self, now=1000.0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


class FakeQos:
    def __init__(self):
        self.max_inflight = 2
        self.overloaded_flag = False
        self.inflight = 0
        self.slo = None

    def overloaded(self):
        return self.overloaded_flag

    def stats(self):
        return {"inflight_total": self.inflight}


class FakeSlo:
    def __init__(self, burn=0.0):
        self.burn = burn

    def burn_rates(self):
        return {"default": {"standard": {"burn": self.burn}}}


class FakeScheduler:
    def __init__(self, depth=2):
        self.depth = depth
        self.stages = []
        self.groups = {}

    def waiting(self, stage):
        return 0


class FakeSupervisor:
    def __init__(self):
        self.spawned = []
        self.retired = []
        self._retiring = set()
        self.respawns = 0

    @property
    def size(self):
        return len(self.spawned) - len(self.retired)

    def names(self):
        return sorted(set(self.spawned) - set(self.retired))

    def spawn(self, name):
        self.spawned.append(name)

    def retire(self, name):
        self._retiring.add(name)
        self.retired.append(name)

    def destroy(self, name):
        if name not in self.retired:
            self.retire(name)

    @property
    def stats(self):
        return {"peers": self.names(), "respawns": self.respawns,
                "retired": len(self.retired), "retiring": []}


class FakePipeline:
    name = "fake"

    def __init__(self):
        self.share = {}
        self.qos = FakeQos()
        self.stage_scheduler = FakeScheduler()
        self.gateway = None
        self.telemetry = None
        self._draining = False
        self.bucket = "queue"
        self.frames = 50
        self.records = []
        self.blackboxes = []
        self.stage_inflight_calls = []
        self.device_inflight_calls = []
        self.parameters = {"device_inflight": 2}
        self.overrides = {}

    def explain(self):
        return {"bucket_share": {self.bucket: 0.8},
                "frames": self.frames}

    def _rec(self, etype, *arguments):
        self.records.append((etype, arguments))

    def _blackbox(self, reason, detail=""):
        self.blackboxes.append(reason)

    def _has_elastic_replicas(self):
        return False

    def set_stage_inflight(self, depth):
        self.stage_inflight_calls.append(depth)
        self.stage_scheduler.depth = depth
        return True

    def set_device_inflight(self, depth):
        self.device_inflight_calls.append(depth)
        self.parameters["device_inflight"] = depth
        return True

    def autoscale_replicas(self):
        return {}

    def get_pipeline_parameter(self, name, default=None):
        return self.parameters.get(name, default)

    def swap_replica_version(self, stage, index, name, value,
                             canary=True):
        key = (stage, index, name)
        old = self.overrides.get(key)
        if value is None:
            self.overrides.pop(key, None)
        else:
            self.overrides[key] = value
        group = self.stage_scheduler.groups.get(stage)
        if canary and group is not None:
            group.reopen(index)
        return old


def controller(pipeline, clock, **spec_overrides):
    spec_overrides.setdefault("mode", "act")
    spec_overrides.setdefault("hysteresis_ticks", 1)
    spec_overrides.setdefault("cooldown_ms", 0)
    spec_overrides.setdefault("fence_s", 5.0)
    spec = ControllerSpec(**spec_overrides)
    return FleetController(pipeline, spec, time_fn=clock)


def journaled(pipeline, etype):
    return [arguments for name, arguments in pipeline.records
            if name == etype]


# -- spec validation (create-time twin) -------------------------------------

def test_controller_spec_error_twin():
    assert controller_spec_error(None) is None
    assert controller_spec_error("observe") is None
    assert controller_spec_error("on") is None
    assert controller_spec_error(
        {"mode": "act", "fleet_max": 2, "interval_ms": 100}) is None

    problem = controller_spec_error({"bogus": 1})
    assert problem is not None and "bogus" in problem \
        and "known:" in problem
    problem = controller_spec_error({"hysteresis_ticks": 0})
    assert problem is not None and "hysteresis_ticks" in problem
    problem = controller_spec_error({"dominance": 1.5})
    assert problem is not None and "<= 1" in problem
    problem = controller_spec_error({"interval_ms": "soon"})
    assert problem is not None and "expected a number" in problem
    problem = controller_spec_error(
        {"fleet_min": 3, "fleet_max": 2})
    assert problem is not None and "fleet_max" in problem
    problem = controller_spec_error("sideways")
    assert problem is not None and "off|on|observe|act" in problem
    assert controller_spec_error(3.5) is not None
    assert controller_spec_error("{not json") is not None


def test_spec_parse_modes_and_flat_overlay():
    assert ControllerSpec.parse("on").mode == "act"
    assert ControllerSpec.parse("observe").mode == "observe"
    assert ControllerSpec.parse(None).mode == "off"
    spec = ControllerSpec.parse(
        {"mode": "on", "fleet_max": 2},
        {"controller_interval_ms": "100",
         "controller_hysteresis_ticks": "2", "fleet_max": "3"})
    assert spec.mode == "act"
    assert spec.interval_ms == 100.0
    assert spec.hysteresis_ticks == 2
    assert spec.fleet_max == 3            # flat spelling wins
    with pytest.raises(ValueError):
        ControllerSpec.parse({"mode": "act"},
                             {"controller_interval_ms": "soon"})
    with pytest.raises(ValueError):
        ControllerSpec.parse({"fleet_min": 2},
                             {"fleet_max": "1"})


# -- guardrails -------------------------------------------------------------

def test_observe_mode_journals_but_never_actuates():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock, mode="observe",
                      hysteresis_ticks=2)
    for _ in range(10):
        loop.tick()
        clock.advance(1.0)
    assert loop.actions_taken == 0
    assert not pipeline.stage_inflight_calls
    assert not pipeline.device_inflight_calls
    would = journaled(pipeline, "controller_would_act")
    assert would, "observe mode must journal the decisions it held"
    assert loop.status()["mode"] == "observe"


def test_hysteresis_damps_oscillating_diagnosis():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock, hysteresis_ticks=2)
    for index in range(20):
        # Square-wave attribution: the dominant bucket flips every
        # tick, so no diagnosis ever persists hysteresis_ticks.
        pipeline.bucket = ("queue", "pacing")[index % 2]
        loop.tick()
        clock.advance(0.5)
    assert loop.actions_taken == 0
    assert not pipeline.stage_inflight_calls


def test_steady_pressure_actuates_then_budget_refuses_loudly():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock, action_budget=2,
                      budget_window_s=300.0, knob_cap=8)
    for _ in range(10):
        loop.tick()
        clock.advance(1.0)
    assert loop.actions_taken == 2        # budget cap, not 10
    assert pipeline.stage_inflight_calls == [3, 4]
    assert loop.refusals > 0
    assert journaled(pipeline, "controller_refusal")
    assert "controller_refusal" in pipeline.blackboxes
    assert loop.status()["budget_left"] == 0


def test_cooldown_spaces_repeat_actions():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock, cooldown_ms=10000)
    loop.tick()
    assert loop.actions_taken == 1
    for _ in range(5):
        clock.advance(1.0)
        loop.tick()
    assert loop.actions_taken == 1        # cooling down: quiet skip
    clock.advance(10.0)
    loop.tick()
    assert loop.actions_taken == 2


def test_fence_on_fleet_epoch_change():
    clock = Clock()
    pipeline = FakePipeline()

    class Gateway:
        failovers = 0
    pipeline.gateway = Gateway()
    loop = controller(pipeline, clock, fence_s=5.0)
    loop.tick()
    assert loop.actions_taken == 1
    pipeline.gateway.failovers = 1        # failover mid-flight
    clock.advance(1.0)
    loop.tick()
    assert loop.actions_taken == 1
    assert loop.last.get("fenced")
    assert journaled(pipeline, "controller_fenced")
    # force_action respects the fence too
    problem = loop.force_action("stage_inflight")
    assert problem is not None and "fenced" in problem
    clock.advance(10.0)                   # fence expired
    loop.tick()
    assert loop.actions_taken == 2


def test_draining_pipeline_never_actuates():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock)
    pipeline._draining = True
    loop.tick()
    loop.tick()
    assert loop.actions_taken == 0
    assert loop.last.get("draining")


def test_pause_resume_and_force_action():
    clock = Clock()
    pipeline = FakePipeline()
    loop = controller(pipeline, clock, cooldown_ms=60000,
                      hysteresis_ticks=99)
    loop.pause()
    for _ in range(5):
        loop.tick()
        clock.advance(1.0)
    assert loop.actions_taken == 0
    loop.resume()
    # forced action bypasses hysteresis (99 ticks) and cooldown
    assert loop.force_action("stage_inflight", to=5) is None
    assert pipeline.stage_inflight_calls == [5]
    problem = loop.force_action("warp_drive")
    assert problem is not None and "unknown action" in problem
    assert set(ACTION_KINDS) >= {"spawn", "retire", "swap",
                                 "rollback"}
    assert CONTROLLER_MODES == ("off", "observe", "act")


# -- diagnosis tiers --------------------------------------------------------

def test_fetch_dominated_widens_device_inflight():
    clock = Clock()
    pipeline = FakePipeline()
    pipeline.bucket = "fetch"
    loop = controller(pipeline, clock)
    loop.tick()
    assert pipeline.device_inflight_calls == [3]
    # device_inflight 0 is an operator opt-out: never widened
    pipeline.parameters["device_inflight"] = 0
    clock.advance(1.0)
    loop.tick()
    assert pipeline.device_inflight_calls == [3]


def test_pacing_dominated_widens_qos_admission():
    clock = Clock()
    pipeline = FakePipeline()
    pipeline.bucket = "pacing"
    loop = controller(pipeline, clock, action_budget=100)
    loop.tick()
    assert pipeline.qos.max_inflight == 3
    # lazily capped at 4x the initial window: from 2, cap is 8
    for _ in range(20):
        clock.advance(1.0)
        loop.tick()
    assert pipeline.qos.max_inflight == 8


def test_spawn_tier_needs_overload_and_burn():
    clock = Clock()
    pipeline = FakePipeline()
    pipeline.qos.slo = FakeSlo(burn=5.0)
    supervisor = FakeSupervisor()
    spec = ControllerSpec(mode="act", hysteresis_ticks=1,
                          cooldown_ms=0, fleet_max=2)
    loop = FleetController(pipeline, spec, supervisor=supervisor,
                           time_fn=clock)
    loop.tick()                           # burning but NOT overloaded
    assert not supervisor.spawned
    pipeline.qos.overloaded_flag = True
    clock.advance(1.0)
    loop.tick()
    assert supervisor.spawned == ["fake-peer1"]
    assert loop.fleet_size() == 2
    clock.advance(1.0)
    loop.tick()                           # at fleet_max: no more
    assert supervisor.spawned == ["fake-peer1"]


def test_retire_tier_needs_full_idle():
    clock = Clock()
    pipeline = FakePipeline()
    pipeline.frames = 0                   # no dominant bucket signal
    pipeline.qos.slo = FakeSlo(burn=0.0)
    supervisor = FakeSupervisor()
    supervisor.spawn("fake-peer1")
    spec = ControllerSpec(mode="act", hysteresis_ticks=1,
                          cooldown_ms=0, fleet_max=2)
    loop = FleetController(pipeline, spec, supervisor=supervisor,
                           time_fn=clock)
    pipeline.qos.inflight = 1             # still busy: no retire
    loop.tick()
    assert not supervisor.retired
    pipeline.qos.inflight = 0
    clock.advance(1.0)
    loop.tick()
    assert supervisor.retired == ["fake-peer1"]


# -- canary-gated swap ------------------------------------------------------

def swap_fixture(watch_ticks=1):
    clock = Clock()
    pipeline = FakePipeline()
    pipeline.qos.slo = FakeSlo(burn=0.0)
    group = ReplicaGroup("work", 2, depth=2)
    pipeline.stage_scheduler.groups["work"] = group
    loop = controller(pipeline, clock,
                      canary_watch_ticks=watch_ticks,
                      canary_burn_ratio=1.5)
    return clock, pipeline, group, loop


def test_canary_swap_walks_every_replica():
    clock, pipeline, group, loop = swap_fixture()
    assert loop.begin_swap("work", "version", "v2") is None
    assert loop.begin_swap("work", "version", "v3") is not None
    # replica 0: swapped, demoted half-open awaiting its canary
    loop.tick()
    assert pipeline.overrides[("work", 0, "version")] == "v2"
    assert group.states[0] == REPLICA_HALF_OPEN
    group.states[0] = REPLICA_LIVE        # canary delivered OK
    clock.advance(1.0)
    loop.tick()                           # watch tick passes
    clock.advance(1.0)
    loop.tick()                           # replica 1 swapped
    assert pipeline.overrides[("work", 1, "version")] == "v2"
    group.states[1] = REPLICA_LIVE
    clock.advance(1.0)
    loop.tick()
    clock.advance(1.0)
    loop.tick()
    assert loop.swap is None              # swap complete
    assert journaled(pipeline, "controller_swap_done")
    assert loop.rollbacks == 0


def test_canary_death_rolls_back_every_swapped_replica():
    clock, pipeline, group, loop = swap_fixture()
    pipeline.overrides[("work", 0, "version")] = "v1"
    pipeline.overrides[("work", 1, "version")] = "v1"
    assert loop.begin_swap("work", "version", "v2") is None
    loop.tick()                           # replica 0 swapped
    group.states[0] = REPLICA_LIVE
    clock.advance(1.0)
    loop.tick()
    clock.advance(1.0)
    loop.tick()                           # replica 1 swapped
    assert pipeline.overrides[("work", 1, "version")] == "v2"
    group.states[1] = REPLICA_DEAD        # its canary failed
    clock.advance(1.0)
    loop.tick()
    assert loop.swap is None
    assert loop.rollbacks == 1
    # BOTH replicas restored to the pre-swap value
    assert pipeline.overrides[("work", 0, "version")] == "v1"
    assert pipeline.overrides[("work", 1, "version")] == "v1"
    assert "canary_rollback" in pipeline.blackboxes
    assert journaled(pipeline, "controller_rollback")


def test_burn_above_baseline_ratio_rolls_back():
    clock, pipeline, group, loop = swap_fixture(watch_ticks=3)
    assert loop.begin_swap("work", "version", "v2") is None
    loop.tick()
    group.states[0] = REPLICA_LIVE
    clock.advance(1.0)
    loop.tick()                           # watch 1: burn fine
    pipeline.qos.slo.burn = 4.0           # canary burning the budget
    clock.advance(1.0)
    loop.tick()
    assert loop.swap is None
    assert loop.rollbacks == 1
    assert ("work", 0, "version") not in pipeline.overrides


def test_swap_refusals():
    clock, pipeline, group, loop = swap_fixture()
    assert "not replicated" in loop.begin_swap("decode", "v", 1)
    group.states[:] = [REPLICA_DEAD, REPLICA_DEAD]
    assert "no live replicas" in loop.begin_swap("work", "v", 1)
    loop.spec.mode = "observe"
    group.states[:] = [REPLICA_LIVE, REPLICA_LIVE]
    assert "refusing" in loop.begin_swap("work", "v", 1)


# -- FleetSupervisor (respawn-on-death harness) -----------------------------

def sleeper_spawner(log):
    def spawn(name):
        process = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"])
        log.append((name, process.pid))
        return process
    return spawn


def wait_until(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return predicate()


def test_supervisor_respawns_after_sigkill():
    log = []
    supervisor = FleetSupervisor(sleeper_spawner(log), engine=None,
                                 backoff_s=0.05)
    try:
        process = supervisor.spawn("peer1")
        assert supervisor.size == 1
        process.kill()
        assert wait_until(lambda: supervisor.respawns >= 1
                          and supervisor.manager.get("peer1")
                          is not None)
        assert [name for name, _ in log] == ["peer1", "peer1"]
        assert supervisor.stats["respawns"] >= 1
    finally:
        supervisor.stop_all(5.0)
    assert wait_until(
        lambda: all(subprocess.Popen.poll(
            supervisor.manager.get("peer1") or process) is not None
            for _ in (0,)), timeout=10.0)


def test_supervisor_retire_suppresses_respawn():
    log = []
    supervisor = FleetSupervisor(sleeper_spawner(log), engine=None,
                                 backoff_s=0.05)
    try:
        process = supervisor.spawn("peer1")
        supervisor.retire("peer1")
        process.kill()
        assert wait_until(lambda: supervisor.retired >= 1)
        time.sleep(0.3)                   # a respawn would land here
        assert supervisor.respawns == 0
        assert len(log) == 1
    finally:
        supervisor.stop_all(5.0)


def test_supervisor_backoff_doubles_then_caps():
    clock = Clock()
    supervisor = FleetSupervisor(lambda name: None, engine=None,
                                 backoff_s=0.5, backoff_max_s=4.0,
                                 stable_s=30.0, time_fn=clock)
    supervisor._started["x"] = clock()
    # Three quick deaths: the recorded next-delay doubles, capped.
    supervisor._backoff.pop("x", None)
    for expected in (1.0, 2.0, 4.0, 4.0):
        # simulate the bookkeeping _on_exit does, without processes
        delay = supervisor._backoff.get("x", supervisor.backoff_s)
        supervisor._backoff["x"] = min(supervisor.backoff_max_s,
                                       delay * 2.0)
        assert supervisor._backoff["x"] == expected


# -- peer_definition --------------------------------------------------------

def test_peer_definition_strips_singleton_planes():
    definition = parse_pipeline_definition({
        "version": 0, "name": "pilot", "runtime": "jax",
        "graph": ["(work)"],
        "parameters": {"journal": "on", "journal_dir": "/tmp/j",
                       "gateway": "on", "metrics_port": 0,
                       "controller": {"mode": "act", "fleet_max": 3},
                       "controller_interval_ms": 100,
                       "stage_inflight": 4},
        "elements": [{"name": "work", "input": [{"name": "x"}],
                      "output": [{"name": "x"}],
                      "parameters": {"busy_ms": 1.0},
                      "placement": {"devices": 2},
                      "deploy": {"local": {"module": COMMON,
                                           "class_name":
                                               "StageWork"}}}]})
    peer = peer_definition(definition, "pilot-peer1",
                           journal_dir="/tmp/j")
    assert peer["name"] == "pilot-peer1"
    assert peer["parameters"]["controller"] == "off"
    assert peer["parameters"]["gateway"] == "off"
    assert "controller_interval_ms" not in peer["parameters"]
    assert "metrics_port" not in peer["parameters"]
    assert peer["parameters"]["journal_dir"] == "/tmp/j"
    assert peer["parameters"]["stage_inflight"] == 4
    # round-trips through the parser (a spawned peer can load it)
    reparsed = parse_pipeline_definition(peer)
    assert reparsed.element("work").deploy_local["class_name"] \
        == "StageWork"


# -- no child's device is chosen by default (ISSUE 21) ----------------------

def test_device_env_is_the_only_platform_assignment():
    """``cpu`` -> the CPU backend; a chip index -> that one chip with
    one-chip process bounds (how libtpu confines a process on a
    multi-chip host)."""
    from aiko_services_tpu.orchestration.controller import device_env
    assert device_env("cpu") == {"JAX_PLATFORMS": "cpu"}
    chip = device_env(2)
    assert chip["JAX_PLATFORMS"] == "tpu"
    assert chip["TPU_VISIBLE_CHIPS"] == "2"
    assert chip["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert chip["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_fleet_devices_validation():
    from aiko_services_tpu.orchestration.controller import \
        fleet_devices_error
    assert fleet_devices_error(None) is None
    assert fleet_devices_error("cpu") is None
    assert fleet_devices_error([1, 2, "cpu"]) is None
    assert "one process" in fleet_devices_error([1, 1])
    assert fleet_devices_error("tpu") is not None
    assert fleet_devices_error([]) is not None
    assert fleet_devices_error([True]) is not None
    assert controller_spec_error(
        {"mode": "act", "fleet_max": 2, "fleet_devices": "gpu"}) \
        is not None
    spec = ControllerSpec.parse({"mode": "act", "fleet_max": 3},
                                {"fleet_devices": "[1, 2]"})
    assert spec.fleet_devices == [1, 2]           # flat/CLI spelling


def _pilot(extra):
    return parse_pipeline_definition({
        "version": 0, "name": "pilot", "runtime": "jax",
        "graph": ["(work)"], "parameters": extra,
        "elements": [{"name": "work", "input": [{"name": "x"}],
                      "output": [{"name": "x"}],
                      "deploy": {"local": {"module": COMMON,
                                           "class_name":
                                               "StageWork"}}}]})


def test_default_spawner_refuses_without_an_assignment(tmp_path):
    from aiko_services_tpu.orchestration.controller import \
        default_spawner
    with pytest.raises(ValueError, match="device assignment"):
        default_spawner(_pilot({}), workdir=str(tmp_path))
    with pytest.raises(ValueError, match="fleet_devices"):
        default_spawner(_pilot({}), workdir=str(tmp_path),
                        devices="tpu")


def test_default_spawner_tells_each_child_its_device(tmp_path,
                                                     monkeypatch):
    """Each child gets the entry no LIVE child holds, keeps it across a
    respawn, says so at the top of its log -- and a full list refuses
    the next child instead of doubling up on a chip."""
    from aiko_services_tpu.orchestration import controller

    class FakeProcess:
        def __init__(self, argv, env, **kwargs):
            self.env, self.alive, self.pid = env, True, 1

        def poll(self):
            return None if self.alive else 0

    monkeypatch.setattr(controller.subprocess, "Popen", FakeProcess)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")      # the pilot's own
    spawn = controller.default_spawner(
        _pilot({}), workdir=str(tmp_path), devices=[1, "cpu"])
    first, second = spawn("p1"), spawn("p2")
    assert first.env["TPU_VISIBLE_CHIPS"] == "1"
    assert first.env["JAX_PLATFORMS"] == "tpu"      # not the pilot's cpu
    assert second.env["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in second.env
    assert (tmp_path / "p1.log").read_text().startswith(
        "p1: assigned device 1")
    with pytest.raises(RuntimeError, match="no entry left"):
        spawn("p3")
    first.alive = False                             # p1 died ...
    assert spawn("p1").env["TPU_VISIBLE_CHIPS"] == "1"    # ... respawn
    everywhere = controller.default_spawner(
        _pilot({}), workdir=str(tmp_path), devices="cpu")
    assert all(everywhere(f"q{i}").env["JAX_PLATFORMS"] == "cpu"
               for i in range(5))


def test_fleet_pilot_without_fleet_devices_is_definition_error(runtime):
    """A pilot that would spawn peers must say where each runs: create
    fails instead of defaulting the children onto the CPU."""
    with pytest.raises(DefinitionError, match="fleet_devices"):
        serving(runtime, "nodevices",
                extra={"controller": {"mode": "act", "fleet_max": 2}})
    pipeline = serving(runtime, "saiddevices", extra={
        "controller": {"mode": "act", "fleet_max": 2,
                       "fleet_devices": "cpu"}})
    try:
        assert pipeline.controller.supervisor is not None
        assert pipeline.controller.spec.fleet_devices == "cpu"
    finally:
        pipeline.stop()


# -- pipeline integration ---------------------------------------------------

def stage(name, busy_ms=1.0, factor=2.0):
    return {"name": name, "input": [{"name": "x"}],
            "output": [{"name": "x"}],
            "parameters": {"busy_ms": busy_ms, "factor": factor},
            "placement": {"devices": 2},
            "deploy": {"local": {"module": COMMON,
                                 "class_name": "StageWork"}}}


def serving(runtime, name, extra=None):
    parameters = {"controller": "observe",
                  "controller_interval_ms": 50}
    parameters.update(extra or {})
    return Pipeline({"version": 0, "name": name, "runtime": "jax",
                     "graph": ["(work finish)"],
                     "parameters": parameters,
                     "elements": [stage("work"),
                                  stage("finish", factor=3.0)]},
                    runtime=runtime)


def stream_through(runtime, pipeline, count=3):
    import queue

    import numpy as np
    responses = queue.Queue()
    pipeline.create_stream_local("s1", queue_response=responses)
    for index in range(count):
        pipeline.process_frame_local(
            {"x": np.asarray([float(index + 1)], np.float32)},
            stream_id="s1")
    run_until(runtime, lambda: responses.qsize() >= count,
              timeout=30.0)
    return [responses.get() for _ in range(count)]


def test_bad_controller_block_is_definition_error(runtime, tmp_path):
    with pytest.raises(DefinitionError, match="bogus"):
        serving(runtime, "bad",
                extra={"controller": {"bogus": 1},
                       "preflight": "off"})
    with pytest.raises(DefinitionError, match="fleet_max"):
        serving(runtime, "bad2",
                extra={"controller": {"mode": "act", "fleet_min": 3,
                                      "fleet_max": 2},
                       "preflight": "off"})


def test_controller_death_leaves_pipeline_serving(runtime):
    pipeline = serving(runtime, "guarded")
    try:
        assert pipeline.controller is not None
        assert pipeline.controller.spec.mode == "observe"

        def explode():
            raise RuntimeError("controller bug")
        pipeline.controller.tick = explode
        pipeline._controller_tick()       # the guarded timer body
        assert pipeline.controller.paused is True
        # the fleet keeps serving exactly as tuned
        done = stream_through(runtime, pipeline)
        assert len(done) == 3
    finally:
        pipeline.stop()


def test_controller_ticks_on_live_pipeline(runtime):
    pipeline = serving(runtime, "ticking")
    try:
        run_until(runtime,
                  lambda: pipeline.controller.ticks >= 2,
                  timeout=10.0)
        assert pipeline.controller.ticks >= 2
        assert pipeline.share["fleet_size"] == 1
        status = pipeline.controller.status()
        assert status["mode"] == "observe"
        assert status["actions"] == 0
    finally:
        pipeline.stop()


def test_stage_and_device_inflight_knobs(runtime):
    pipeline = serving(runtime, "knobs",
                       extra={"stage_inflight": 2})
    try:
        scheduler = pipeline.stage_scheduler
        assert scheduler is not None and scheduler.depth == 2
        assert pipeline.set_stage_inflight(4) is True
        assert scheduler.depth == 4
        assert pipeline.get_pipeline_parameter("stage_inflight") == 4
        assert pipeline.set_stage_inflight(4) is False  # no-op
        assert pipeline.set_device_inflight(4) is True
        assert pipeline.get_pipeline_parameter("device_inflight") == 4
        done = stream_through(runtime, pipeline)
        assert len(done) == 3
    finally:
        pipeline.stop()


def test_replica_override_resolves_per_replica(runtime):
    pipeline = serving(runtime, "overrides")
    try:
        old = pipeline.swap_replica_version("work", 0, "factor", 5.0,
                                            canary=False)
        assert old is None
        value, found = pipeline.replica_override("work", 0, "factor")
        assert found and value == 5.0
        # the other replica index is untouched
        _, found = pipeline.replica_override("work", 1, "factor")
        assert not found
        # rollback round-trips through the returned previous value
        previous = pipeline.swap_replica_version(
            "work", 0, "factor", old, canary=False)
        assert previous == 5.0
        _, found = pipeline.replica_override("work", 0, "factor")
        assert not found
    finally:
        pipeline.stop()


def test_fleetctl_wire_surface(runtime):
    pipeline = serving(runtime, "wired")
    replies = []
    topic = "test/fleetctl/reply"

    def on_reply(topic_in, payload):
        replies.append(payload)

    runtime.add_message_handler(on_reply, topic)
    try:
        pipeline.fleetctl(topic, "status")
        run_until(runtime, lambda: len(replies) >= 2, timeout=5.0)
        assert any("fleetctl" in reply for reply in replies)
        import json as json_module

        from aiko_services_tpu.utils import parse
        payload = next(reply for reply in replies
                       if "fleetctl" in reply)
        command, parameters = parse(payload)
        report = json_module.loads(str(parameters[0]))
        assert report["mode"] == "observe"
        replies.clear()
        pipeline.fleetctl(topic, "pause")
        assert pipeline.controller.paused is True
        pipeline.fleetctl(topic, "resume")
        assert pipeline.controller.paused is False
        pipeline.fleetctl(topic, "bogus")
        run_until(runtime, lambda: len(replies) >= 6, timeout=5.0)
        last = json_module.loads(
            str(parse(replies[-1])[1][0]))
        assert "unknown fleetctl command" in last["error"]
    finally:
        runtime.remove_message_handler(on_reply, topic)
        pipeline.stop()


# -- multi-process walk (slow) ----------------------------------------------

@pytest.mark.slow
def test_chaos_controller_mode_converges():
    from aiko_services_tpu.faults.chaos import run_chaos

    result = run_chaos(frames=8, mode="controller", busy_ms=50.0,
                       timeout=240.0, echo=lambda *_: None)
    assert result["ok"], result
    assert result["fleet_grew"] and result["respawned"]
    assert result["dropped"] == 0
