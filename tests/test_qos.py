"""Unified QoS admission (ISSUE 12): ONE QosScheduler authority
consulted by all four former admission planes -- DeviceWindow pacing,
StageScheduler credits, ReplicaGroup slot pick, batcher admission --
plus promotion near deadline, over-budget-first shedding under 2x
overload, and bounded wait for the lowest class."""

import queue
import time
import types

import numpy as np
import pytest

from conftest import run_until

from aiko_services_tpu.gateway.qos import (QosScheduler, TokenBucket,
                                           qos_spec_error)
from aiko_services_tpu.models.batching import ContinuousBatcher, \
    MicroBatcher, Request
from aiko_services_tpu.pipeline import Pipeline
from aiko_services_tpu.pipeline.stages import ReplicaGroup, StageScheduler

COMMON = "aiko_services_tpu.elements.common"


def frame_stub(qos_class="standard", seq=0, deadline=None,
               wait_start=None, tenant="default"):
    return types.SimpleNamespace(qos_class=qos_class, qos_seq=seq,
                                 deadline=deadline,
                                 qos_wait_start=wait_start,
                                 qos_promoted=False, tenant=tenant)


# -- units: scheduler vocabulary --------------------------------------------

def test_token_bucket_rate_and_burst():
    bucket = TokenBucket(rate=10.0, burst=2.0)
    now = time.monotonic()
    assert bucket.take(now) and bucket.take(now)     # burst of 2
    assert not bucket.take(now)                      # drained
    assert bucket.take(now + 0.11)                   # 1 token refilled
    unlimited = TokenBucket(rate=0.0)
    assert all(unlimited.take() for _ in range(100))


def test_spec_validation_rejects_malformed_blocks():
    assert qos_spec_error({}) is None
    assert qos_spec_error({"tenants": {"a": {"rate": 5}}}) is None
    assert "unknown keys" in qos_spec_error({"priorities": {}})
    assert "class" in qos_spec_error(
        {"tenants": {"a": {"class": "gold"}}})
    assert "weight" in qos_spec_error(
        {"classes": {"interactive": {"weight": -1}}})
    assert "not a number" in qos_spec_error({"max_inflight": "many"})
    assert "unparseable" in qos_spec_error("{nope")
    with pytest.raises(ValueError):
        QosScheduler({"tenants": {"a": {"class": "gold"}}})
    assert QosScheduler.parse(None) is None
    assert QosScheduler.parse({}) is None


def test_class_ranks_follow_weights():
    qos = QosScheduler({"classes": {"realtime": {"weight": 100}}})
    assert qos.class_rank("realtime") == 0
    assert qos.class_rank("interactive") == 1
    assert qos.class_rank("batch") == 3
    assert qos.class_rank("unknown") == qos.class_rank("standard")


def test_rank_promotion_near_deadline_counts_once():
    qos = QosScheduler({"promote_ms": 50, "age_ms": 0})
    now = time.monotonic()
    batch = frame_stub("batch", seq=7, deadline=now + 0.02)
    rank, seq = qos.rank_frame(batch, now)
    assert (rank, seq) == (0, 7)            # promoted to the top class
    assert batch.qos_promoted and qos.promotions == 1
    qos.rank_frame(batch, now)
    assert qos.promotions == 1              # counted once per frame
    far = frame_stub("batch", seq=8, deadline=now + 10.0)
    assert qos.rank_frame(far, now)[0] == qos.class_rank("batch")


def test_rank_aging_bounds_lowest_class_wait():
    qos = QosScheduler({"age_ms": 100, "promote_ms": 0})
    now = time.monotonic()
    fresh = frame_stub("batch", seq=2, wait_start=now)
    waited = frame_stub("batch", seq=1, wait_start=now - 0.25)
    assert qos.rank_frame(fresh, now)[0] == qos.class_rank("batch")
    assert qos.rank_frame(waited, now)[0] == 0   # two steps up


def test_shed_key_over_budget_tenant_first_then_class_then_oldest():
    qos = QosScheduler({"tenants": {
        "hog": {"budget": 1}, "polite": {"budget": 8}}})
    for _ in range(3):
        qos.frame_started("hog")
    qos.frame_started("polite")
    hog = frame_stub("interactive", seq=1, tenant="hog")
    polite_batch = frame_stub("batch", seq=2, tenant="polite")
    # over-budget beats class: the hog's INTERACTIVE frame sheds
    # before an in-budget tenant's batch frame.
    assert qos.shed_key(hog) > qos.shed_key(polite_batch)
    older = frame_stub("batch", seq=3, tenant="polite")
    newer = frame_stub("batch", seq=9, tenant="polite")
    assert qos.shed_key(older) > qos.shed_key(newer)   # oldest first


def test_device_limit_per_class():
    qos = QosScheduler({"classes": {"batch": {"device_inflight": 1}}})
    assert qos.device_limit("batch", 3) == 1      # plane 1: capped
    assert qos.device_limit("interactive", 3) == 3
    assert qos.device_limit("batch", 0) == 1      # pacing off -> cap


def test_tenant_lazily_resolves_default_block():
    qos = QosScheduler({"default_tenant": {"budget": 2,
                                           "class": "batch"}})
    entry = qos.tenant("never-seen")
    assert entry.budget == 2 and entry.default_class == "batch"
    assert qos.resolve_class(None, "never-seen") == "batch"


# -- units: the four planes -------------------------------------------------

def test_replica_pick_least_loaded_probes_canaries_first():
    group = ReplicaGroup("s", 3, depth=2)
    group.admit(group.pick())               # rr: slot 0
    group.admit(group.pick())               # rr: slot 1
    assert group.pick(least_loaded=True) == 2
    group.active = [2, 1, 2]
    assert group.pick(least_loaded=True) == 1
    # a canary-READY half-open slot is probed before any live slot:
    # under pure latency-sensitive traffic the rebuilt replica must
    # not stay half-open (N-1 capacity) until a saturation burst.
    group.fail(0)
    group.rebuild(3, half_open=[0])
    group.active = [0, 1, 1]
    assert group.pick(least_loaded=True) == 0
    group.admit(0)                          # canary in flight now
    assert group.pick(least_loaded=True) == 1   # back to least-loaded


def test_resolve_class_consistent_before_lazy_entry_exists():
    qos = QosScheduler({"default_tenant": {"class": "interactive"}})
    # FIRST resolution (no lazy entry yet) must match the second
    first = qos.resolve_class(None, "bob")
    qos.tenant("bob")
    assert first == qos.resolve_class(None, "bob") == "interactive"


def test_stage_scheduler_pops_best_ranked_waiter():
    qos = QosScheduler({"age_ms": 0, "promote_ms": 0})
    scheduler = StageScheduler(["llm"], depth=1, qos=qos)
    assert scheduler.try_admit("llm")
    for seq, cls in enumerate(["batch", "batch", "interactive"]):
        scheduler.enqueue("llm",
                          ["s", seq, "llm", True,
                           frame_stub(cls, seq=seq)])
    waiter = scheduler.release("llm")       # release pops next waiter
    assert waiter[1] == 2                   # interactive overtakes
    scheduler.cancel_reservation("llm")
    assert scheduler.try_admit("llm")       # the popped token admits
    waiter = scheduler.release("llm")
    assert waiter[1] == 0                   # same class: FIFO by seq


def test_stage_credit_promotion_fires_on_promote_once():
    """ISSUE 18 satellite: a near-deadline batch frame promotes AT THE
    STAGE-CREDIT SEAM -- `_pop_ranked` lifts it over a standard frame
    queued ahead of it and fires ``on_promote`` exactly once (the
    callback Pipeline wires into ``share['qos_promotions']``), so the
    counter is reachable deterministically."""
    qos = QosScheduler({"promote_ms": 50, "age_ms": 0})
    promoted = []
    scheduler = StageScheduler(
        ["llm"], depth=1, qos=qos,
        on_promote=lambda sid, frame: promoted.append((sid, frame)))
    ahead = frame_stub("standard", seq=1)
    urgent = frame_stub("batch", seq=9,
                        deadline=time.monotonic() + 0.02)
    scheduler.enqueue("llm", ["s-ahead", 1, "llm", True, ahead])
    scheduler.enqueue("llm", ["s-urgent", 9, "llm", True, urgent])
    token = scheduler.next_waiter("llm")
    # batch (rank 3) promoted to rank 0 beats standard (rank 2)
    assert token[0] == "s-urgent"
    assert urgent.qos_promoted
    assert promoted == [("s-urgent", urgent)]
    assert qos.promotions == 1
    # the promoted frame requeues (stolen credit): re-ranking it must
    # NOT fire the callback or bump the counter a second time
    scheduler.cancel_reservation("llm")
    scheduler.enqueue("llm", token, front=True)
    again = scheduler.next_waiter("llm")
    assert again[0] == "s-urgent"
    assert len(promoted) == 1 and qos.promotions == 1


def test_stage_scheduler_fifo_without_qos():
    scheduler = StageScheduler(["llm"], depth=1)
    assert scheduler.try_admit("llm")
    for seq, cls in enumerate(["batch", "interactive"]):
        scheduler.enqueue("llm",
                          ["s", seq, "llm", True,
                           frame_stub(cls, seq=seq)])
    waiter = scheduler.release("llm")
    assert waiter[1] == 0                   # strict FIFO, no qos


def test_continuous_batcher_admits_best_rank():
    batcher = ContinuousBatcher.__new__(ContinuousBatcher)
    a = Request("a", [1], qos_rank=2)
    b = Request("b", [1], qos_rank=0)
    c = Request("c", [1], qos_rank=2)
    batcher.pending = [a, b, c]
    assert batcher._next_pending() is b     # plane 4: rank first
    assert batcher._next_pending() is a     # then queue order
    assert batcher._next_pending() is c


def test_microbatcher_dispatches_best_ranked_group_first():
    order = []

    def run(context, key, payloads):
        order.append(key)
        return payloads

    def finish(context, key, entries, result):
        for complete, payload in entries:
            complete("ok", {"x": payload})

    batcher = MicroBatcher(run=run, finish=finish,
                           context=lambda: None,
                           schedule_flush=lambda fn: None)
    done = []
    batcher.submit("batch", 1, lambda *a: done.append(a), rank=2)
    batcher.submit("interactive", 2, lambda *a: done.append(a), rank=0)
    batcher.flush()
    batcher.stop()
    deadline = time.monotonic() + 5.0
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert order == ["interactive", "batch"]


# -- integration: the engine honors one authority ---------------------------

def element(name, cls, inputs, outputs, parameters=None, placement=None,
            module=COMMON):
    definition = {"name": name,
                  "input": [{"name": n} for n in inputs],
                  "output": [{"name": n} for n in outputs],
                  "deploy": {"local": {"module": module,
                                       "class_name": cls}},
                  "parameters": parameters or {}}
    if placement:
        definition["placement"] = placement
    return definition


def qos_two_stage(qos, busy_ms=25.0, extra=None):
    parameters = {"qos": qos, "stage_inflight": 1}
    parameters.update(extra or {})
    return {
        "version": 0, "name": "p_qos", "runtime": "jax",
        "graph": ["(detect llm)"],
        "parameters": parameters,
        "elements": [
            element("detect", "StageWork", ["x"], ["x"],
                    {"busy_ms": busy_ms, "factor": 2.0}, {"devices": 4}),
            element("llm", "StageWork", ["x"], ["x"],
                    {"busy_ms": busy_ms, "factor": 3.0}, {"devices": 4}),
        ]}


def pump(pipeline, stream_id, n, responses, parameters=None):
    for i in range(n):
        pipeline.process_frame_local(
            {"x": np.full((8, 8), float(i + 1), np.float32)},
            stream_id=stream_id, queue_response=responses)


def drain(runtime, responses, n, timeout=120.0):
    collected = []

    def drained():
        while not responses.empty():
            collected.append(responses.get())
        return len(collected) >= n
    run_until(runtime, drained, timeout=timeout)
    return collected


def test_interactive_overtakes_queued_batch_at_every_seam(runtime):
    """THE acceptance invariant: with one QosScheduler, an
    interactive-class frame admitted after a queue of batch frames
    overtakes them at the stage-credit seam (ring ``admit`` events
    prove the admission order) while per-stream delivery stays in
    ingest order."""
    pipeline = Pipeline(qos_two_stage(
        {"classes": {"batch": {"device_inflight": 1}},
         "age_ms": 60000, "promote_ms": 0}), runtime=runtime)
    batch_q: queue.Queue = queue.Queue()
    inter_q: queue.Queue = queue.Queue()
    pipeline.create_stream_local("b", {"qos_class": "batch"},
                                 queue_response=batch_q)
    pipeline.create_stream_local("i", {"qos_class": "interactive"},
                                 queue_response=inter_q)
    pump(pipeline, "b", 6, batch_q)
    pump(pipeline, "i", 2, inter_q)
    batch_rows = drain(runtime, batch_q, 6)
    inter_rows = drain(runtime, inter_q, 2)
    assert len(batch_rows) == 6 and len(inter_rows) == 2
    for *_, okay, diagnostic in batch_rows + inter_rows:
        assert okay, diagnostic
    # per-stream in-order delivery holds on both streams
    assert [r[1] for r in batch_rows] == sorted(
        r[1] for r in batch_rows)
    assert [r[1] for r in inter_rows] == sorted(
        r[1] for r in inter_rows)
    # admission order at the placed stages: interactive frames admit
    # before batch frames that were QUEUED ahead of them.
    admits = [(e[2], e[3], e[4]) for e in pipeline.recorder.snapshot()
              if e[1] == "admit"]
    detect_admits = [(s, f) for s, f, stage in admits
                     if stage == "detect"]
    first_inter = detect_admits.index(("i", 0))
    batch_after = [entry for entry in detect_admits[first_inter:]
                   if entry[0] == "b"]
    assert len(batch_after) >= 2, (
        f"interactive never overtook queued batch frames: "
        f"{detect_admits}")
    # the same authority capped batch's dispatch window (plane 1)
    assert pipeline._device_limit(pipeline.streams["b"]) == 1
    assert pipeline._device_limit(pipeline.streams["i"]) == 3


def test_promotion_near_deadline_overtakes_and_is_recorded(runtime):
    """A batch frame close to its deadline promotes to rank 0 at the
    waiter pop: counted once (share + counter + ring event)."""
    pipeline = Pipeline(qos_two_stage(
        {"promote_ms": 60000, "age_ms": 0}), runtime=runtime)
    std_q: queue.Queue = queue.Queue()
    ddl_q: queue.Queue = queue.Queue()
    pipeline.create_stream_local("std", {"qos_class": "standard"},
                                 queue_response=std_q)
    pipeline.create_stream_local(
        "ddl", {"qos_class": "batch", "frame_deadline_ms": 30000},
        queue_response=ddl_q)
    pump(pipeline, "std", 5, std_q)
    pump(pipeline, "ddl", 2, ddl_q)
    std_rows = drain(runtime, std_q, 5)
    ddl_rows = drain(runtime, ddl_q, 2)
    for *_, okay, diagnostic in std_rows + ddl_rows:
        assert okay, diagnostic
    assert pipeline.share["qos_promotions"] >= 1
    promotes = [e for e in pipeline.recorder.snapshot()
                if e[1] == "gw_promote"]
    assert promotes and promotes[0][2] == "ddl"
    # promoted batch frames overtook queued standard frames
    admits = [(e[2], e[3]) for e in pipeline.recorder.snapshot()
              if e[1] == "admit" and e[4] == "detect"]
    first_ddl = admits.index(("ddl", 0))
    assert any(entry[0] == "std" for entry in admits[first_ddl:]), \
        f"promotion never overtook: {admits}"


def test_overload_sheds_over_budget_tenant_first(runtime):
    """Under ~2x overload (max_inflight), the over-budget tenant's
    frames shed FIRST: the in-budget tenant completes everything."""
    pipeline = Pipeline(qos_two_stage(
        {"tenants": {"hog": {"budget": 2, "class": "batch"},
                     "polite": {"budget": 16, "class": "batch"}},
         "max_inflight": 6, "age_ms": 60000, "promote_ms": 0},
        busy_ms=30.0), runtime=runtime)
    hog_q: queue.Queue = queue.Queue()
    polite_q: queue.Queue = queue.Queue()
    pipeline.create_stream_local("hog", {"tenant": "hog"},
                                 queue_response=hog_q)
    pipeline.create_stream_local("polite", {"tenant": "polite"},
                                 queue_response=polite_q)
    pump(pipeline, "hog", 8, hog_q)
    pump(pipeline, "polite", 4, polite_q)
    hog_rows = drain(runtime, hog_q, 8)
    polite_rows = drain(runtime, polite_q, 4)
    assert len(hog_rows) == 8 and len(polite_rows) == 4
    polite_failures = [d for *_, okay, d in polite_rows if not okay]
    assert polite_failures == [], polite_failures
    hog_shed = sum(1 for *_, okay, d in hog_rows
                   if not okay and "shed" in d)
    assert hog_shed >= 1, "over-budget tenant was never shed"
    stats = pipeline.qos_stats()
    assert stats["tenants"]["hog"]["shed"] >= 1
    assert stats["tenants"].get("polite", {}).get("shed", 0) == 0
    assert pipeline.share["qos_sheds"] == pipeline._qos_sheds


def test_lowest_class_is_not_starved_bounded_wait(runtime):
    """Aging: under a steady stream of interactive frames, a lone
    batch frame still completes (age_ms lifts its rank step by
    step)."""
    pipeline = Pipeline(qos_two_stage(
        {"age_ms": 50, "promote_ms": 0}, busy_ms=15.0), runtime=runtime)
    inter_q: queue.Queue = queue.Queue()
    batch_q: queue.Queue = queue.Queue()
    pipeline.create_stream_local("i", {"qos_class": "interactive"},
                                 queue_response=inter_q)
    pipeline.create_stream_local("b", {"qos_class": "batch"},
                                 queue_response=batch_q)
    pump(pipeline, "i", 4, inter_q)
    pump(pipeline, "b", 1, batch_q)
    pump(pipeline, "i", 8, inter_q)     # keep the pressure on
    batch_rows = drain(runtime, batch_q, 1)
    assert len(batch_rows) == 1 and batch_rows[0][4], \
        "batch frame starved"
    drain(runtime, inter_q, 12)


def test_malformed_qos_block_fails_at_create(runtime):
    """Create-time validation (and the preflight-off escape hatch is
    closed): a typo'd tenant block raises DefinitionError."""
    from aiko_services_tpu.pipeline.definition import DefinitionError
    definition = qos_two_stage(
        {"tenants": {"a": {"class": "gold"}}})
    definition["parameters"]["preflight"] = "off"
    with pytest.raises(DefinitionError, match="qos"):
        Pipeline(definition, runtime=runtime)


def test_qos_off_keeps_legacy_behavior(runtime):
    """No qos block: scheduler absent, seams run exactly as before."""
    definition = qos_two_stage({})
    del definition["parameters"]["qos"]
    pipeline = Pipeline(definition, runtime=runtime)
    assert pipeline.qos is None
    assert pipeline.qos_stats() == {"enabled": False}
    responses: queue.Queue = queue.Queue()
    pipeline.create_stream_local("s", {}, queue_response=responses)
    pump(pipeline, "s", 3, responses)
    rows = drain(runtime, responses, 3)
    assert [r[1] for r in rows] == [0, 1, 2]
    assert all(r[4] for r in rows)
