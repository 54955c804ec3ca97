"""Event engine tests: timers, mailbox priority, thread-safe posting."""

import threading
import time

from aiko_services_tpu.runtime import EventEngine


def test_timer_fires():
    engine = EventEngine()
    fired = []
    engine.add_oneshot_timer(lambda: fired.append(time.monotonic()), 0.01)
    engine.run(until=lambda: bool(fired), timeout=2.0)
    assert fired


def test_periodic_timer():
    engine = EventEngine()
    count = []
    engine.add_timer_handler(lambda: count.append(1), 0.005)
    engine.run(until=lambda: len(count) >= 3, timeout=2.0)
    assert len(count) >= 3


def test_mailbox_priority_preemption():
    """Items in the first-registered (control) mailbox drain before items
    in later mailboxes, even when queued afterwards."""
    engine = EventEngine()
    order = []
    engine.add_mailbox_handler(lambda item: order.append(("control", item)),
                               "control")
    engine.add_mailbox_handler(lambda item: order.append(("in", item)), "in")
    engine.mailbox_put("in", 1)
    engine.mailbox_put("in", 2)
    engine.mailbox_put("control", "c1")
    engine.run(until=lambda: len(order) == 3, timeout=2.0)
    assert order[0] == ("control", "c1")
    assert [o for o in order if o[0] == "in"] == [("in", 1), ("in", 2)]


def test_post_from_thread():
    engine = EventEngine()
    seen = []

    def worker():
        time.sleep(0.02)
        engine.post(seen.append, "from-thread")

    threading.Thread(target=worker, daemon=True).start()
    engine.run(until=lambda: bool(seen), timeout=2.0)
    assert seen == ["from-thread"]


def test_latency_under_reference_tick():
    """The reference's 10 ms tick is its latency floor; ours must be far
    below it."""
    engine = EventEngine()
    stamps = {}

    def sender():
        time.sleep(0.02)
        stamps["sent"] = time.perf_counter()
        engine.mailbox_put("mb", None)

    engine.add_mailbox_handler(
        lambda item: stamps.__setitem__("recv", time.perf_counter()), "mb")
    threading.Thread(target=sender, daemon=True).start()
    engine.run(until=lambda: "recv" in stamps, timeout=2.0)
    latency = stamps["recv"] - stamps["sent"]
    assert latency < 0.005, f"cross-thread latency {latency * 1e3:.2f} ms"


def test_terminate_from_handler():
    engine = EventEngine()
    engine.add_oneshot_timer(engine.terminate, 0.01)
    start = time.monotonic()
    engine.run(timeout=5.0)
    assert time.monotonic() - start < 1.0
