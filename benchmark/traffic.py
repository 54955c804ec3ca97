"""The one traffic generator: reads a workload file's parameters and a
seed, and drives ``GatewayClient`` sessions on threads of this process.

Structure after ``gateway/loadgen.py`` (one WebSocket session per
traffic source, a sender and a receiver thread each), with what that
one lacks: latency is the CLIENT's clock from the moment a request was
due (open loop) or sent (closed loop) to its result arriving, the
schedule is a function of the seed, and nothing waits without a limit.

A workload file (``benchmark/workloads/<traffic>.json``) gives:

- ``loop``: ``open`` (each session sends at ``rate / sessions`` per
  second from a seeded phase offset, whether or not answers came) or
  ``closed`` (each session sends its next request when the last one
  is answered);
- ``sessions``, ``tenant``, ``qos_class``, ``window``;
- ``rate`` (open loop; requests per second over all sessions);
  ``ramp_s`` (closed loop; sessions start spread evenly over it);
- ``payload``: field -> ``"$session"`` | ``"$index"`` | ``{"text_tokens":
  {"dist": "loguniform", "lo":, "hi":, "pool":}}`` (a prompt of that
  many ByteTokenizer tokens; the pool of lengths is the same for every
  seed, the seed gives the order);
- ``new_tokens``: tokens every answered request generated.

Every seed gives the same set of sizes and arrivals in another order,
so two seeds are the same work.
"""

from __future__ import annotations

import math
import socket
import threading
import time

import numpy as np

# A receiver wakes this often to look at its stop flag.
_POLL_S = 0.5


def seed31(seed) -> int:
    """``--seed`` may be wider than 31 bits; jax keys and numpy
    generators take this fold of it."""
    return int(seed) % 2147483647


def length_pool(spec: dict) -> list[int]:
    """The fixed pool of lengths of a distribution: ``pool`` values at
    evenly spaced quantiles, independent of any seed."""
    count = int(spec.get("pool", 256))
    lo, hi = float(spec["lo"]), float(spec["hi"])
    quantiles = (np.arange(count) + 0.5) / count
    if spec["dist"] == "loguniform":
        values = np.exp(math.log(lo) + quantiles * (math.log(hi / lo)))
    elif spec["dist"] == "uniform":
        values = lo + quantiles * (hi - lo)
    elif spec["dist"] == "fixed":
        values = np.full(count, lo)
    else:
        raise ValueError(f"length distribution {spec['dist']!r}")
    return [int(round(value)) for value in values]


def _text_of(tokens: int, rng) -> str:
    """ASCII text that ByteTokenizer turns into ``tokens`` tokens (one
    per byte plus BOS); words differ from request to request, so no
    two prompts share a prefix of any length worth caching."""
    letters = rng.integers(97, 123, max(0, tokens - 1), dtype=np.uint8)
    letters[rng.integers(3, 9)::7] = 32
    return letters.tobytes().decode("ascii")


class Payloads:
    """Request ``index`` of ``session`` -> its payload dict; a pure
    function of (workload file, seed, session, index)."""

    def __init__(self, spec: dict, seed: int):
        self.fields = spec["payload"]
        self.seed = seed31(seed)
        self.sessions = int(spec["sessions"])
        self.orders = {}
        for field, how in self.fields.items():
            if isinstance(how, dict) and "text_tokens" in how:
                pool = length_pool(how["text_tokens"])
                order = np.random.default_rng(
                    [self.seed, 17]).permutation(len(pool))
                self.orders[field] = [pool[i] for i in order]

    def tokens(self, field: str, session: int, index: int) -> int:
        order = self.orders[field]
        return order[(index * self.sessions + session) % len(order)]

    def make(self, session: int, index: int) -> dict:
        payload = {}
        for field, how in self.fields.items():
            if how == "$session":
                payload[field] = session
            elif how == "$index":
                payload[field] = index
            elif field in self.orders:
                rng = np.random.default_rng(
                    [self.seed, 23, session, index])
                payload[field] = _text_of(
                    self.tokens(field, session, index), rng)
            else:
                payload[field] = how
        return payload


def phase_offsets(spec: dict, seed: int, rate=None) -> list[float]:
    """Open loop: each session's first due time, in seconds.  The
    offsets are the same evenly spaced set for every seed (so the
    merged arrival process is the same); the seed decides which session
    gets which."""
    sessions = int(spec["sessions"])
    order = np.random.default_rng([seed31(seed), 29]).permutation(sessions)
    period = sessions / float(rate or spec["rate"])
    return [float(order[s]) / sessions * period for s in range(sessions)]


class _Session:
    def __init__(self, index: int, client):
        self.index = index
        self.client = client
        self.sent = []          # records, in send order
        self.replies = []       # (recv_s, message summary), in order
        self.next_index = 0
        self.lock = threading.Lock()
        self.answered = threading.Semaphore(0)


class Traffic:
    """Sessions against one gateway port.  ``open()`` once; then any
    number of ``start()`` / ``wait()`` rounds on the same sessions
    (frame ids and request indexes run on)."""

    def __init__(self, port: int, spec: dict, seed: int, keep=()):
        self.port, self.spec, self.seed = port, spec, seed
        self.payloads = Payloads(spec, seed)
        self.keep = tuple(keep)         # result data keys to keep
        self.sessions: list[_Session] = []
        self.threads: list[threading.Thread] = []
        self.stop_receivers = threading.Event()
        self.errors: list[str] = []
        self.epoch = time.perf_counter()
        self._receivers: list[threading.Thread] = []

    def now(self) -> float:
        return time.perf_counter() - self.epoch

    # -- sessions ----------------------------------------------------------

    def open(self):
        from aiko_services_tpu.gateway.client import GatewayClient
        spec = self.spec
        for index in range(int(spec["sessions"])):
            client = GatewayClient("127.0.0.1", self.port, timeout=30.0)
            client.open(session=f"bench-{index}",
                        tenant=spec.get("tenant", "default"),
                        qos_class=spec.get("qos_class"),
                        window=spec.get("window"))
            session = _Session(index, client)
            self.sessions.append(session)
            receiver = threading.Thread(
                target=self._receive, args=(session,), daemon=True,
                name=f"bench-recv-{index}")
            receiver.start()
            self._receivers.append(receiver)

    def close(self):
        self.stop_receivers.set()
        for receiver in self._receivers:
            receiver.join(timeout=5.0)
        for session in self.sessions:
            try:
                session.client.close(timeout=2.0)
            except Exception:                       # closing is best effort
                pass

    def _receive(self, session: _Session):
        from aiko_services_tpu.gateway import ws
        while not self.stop_receivers.is_set():
            try:
                message = session.client.recv(timeout=_POLL_S)
            except socket.timeout:
                continue
            except (ws.WsClosed, OSError) as error:
                if not self.stop_receivers.is_set():
                    self.errors.append(
                        f"session {session.index}: connection lost: "
                        f"{type(error).__name__}: {error}")
                return
            op = message.get("op")
            if op not in ("result", "busy", "rejected"):
                continue
            data = message.get("data") or {}
            summary = {"op": op, "ok": bool(message.get("ok")),
                       "frame": message.get("frame"),
                       "tag": message.get("tag"),
                       "trace": message.get("trace"),
                       "diagnostic": message.get("diagnostic")
                       or message.get("reason"),
                       "data": {key: data.get(key) for key in self.keep
                                if key in data}}
            with session.lock:
                session.replies.append((self.now(), summary))
            session.answered.release()

    # -- one round ---------------------------------------------------------

    def start(self, duration_s: float, burst: int | None = None,
              rate: float | None = None):
        """Start sending for ``duration_s`` from now; returns the
        round's start on the traffic clock.  ``burst``: instead, the
        first ``burst`` sessions send one request each, at once.
        ``rate``: an open loop's rate for this round, if not the
        file's."""
        begin = self.now() + 0.05
        offsets = [0.0] * len(self.sessions)
        if burst is not None:
            target, sessions = self._send_burst, self.sessions[:burst]
        elif self.spec["loop"] == "open":
            target, sessions = self._send_open, self.sessions
            rate = float(rate or self.spec["rate"])
            offsets = phase_offsets(self.spec, self.seed, rate)
        else:
            # Closed loop: sessions start spread over ``ramp_s``, so
            # that their requests do not complete in waves.
            target, sessions = self._send_closed, self.sessions
            order = np.random.default_rng(
                [seed31(self.seed), 37]).permutation(len(sessions))
            offsets = [float(order[s.index]) / len(sessions)
                       * float(self.spec.get("ramp_s", 0.0))
                       for s in sessions]
        self.threads = [
            threading.Thread(
                target=target, daemon=True,
                name=f"bench-send-{session.index}",
                args=(session, begin, duration_s, offsets[session.index],
                      rate))
            for session in sessions]
        for thread in self.threads:
            thread.start()
        return begin

    def _send(self, session: _Session, due_s: float | None):
        index = session.next_index
        session.next_index += 1
        payload = self.payloads.make(session.index, index)
        record = {"session": session.index, "index": index,
                  "due_s": due_s, "sent_s": None}
        with session.lock:
            session.sent.append(record)
        record["sent_s"] = self.now()
        try:
            session.client.send_frame(payload, tag=index)
        except OSError as error:
            self.errors.append(f"session {session.index}: send failed: "
                               f"{error}")
            return False
        return True

    def sleep_until(self, when_s: float):
        delay = when_s - self.now()
        if delay > 0:
            time.sleep(delay)

    def _send_open(self, session, begin, duration_s, offset, rate):
        period = len(self.sessions) / rate
        count = 0
        while True:
            due = begin + offset + count * period
            if due >= begin + duration_s:
                return
            self.sleep_until(due)
            if not self._send(session, due):
                return
            count += 1

    def _send_closed(self, session, begin, duration_s, offset, rate):
        self.sleep_until(begin + offset)
        while self.now() < begin + duration_s:
            # Stale releases of an earlier round's answers are drained
            # by ``wait``; here every acquire pairs with this send.
            if not self._send(session, None):
                return
            while not session.answered.acquire(timeout=_POLL_S):
                if self.stop_receivers.is_set() or self.errors:
                    return

    def _send_burst(self, session, begin, duration_s, offset, rate):
        self.sleep_until(begin)
        self._send(session, begin)

    def outstanding(self) -> list[dict]:
        """Requests sent and not yet replied to, oldest first."""
        owed = []
        for session in self.sessions:
            with session.lock:
                missing = len(session.sent) - len(session.replies)
                owed.extend(session.sent[len(session.sent) - missing:]
                            if missing > 0 else [])
        return sorted(owed, key=lambda r: r["sent_s"] or 0.0)

    def wait(self, limit_s: float) -> bool:
        """Until every sender has ended and every request sent has a
        reply, or ``limit_s`` has passed (False)."""
        deadline = time.perf_counter() + limit_s
        for thread in self.threads:
            thread.join(timeout=max(0.0, deadline - time.perf_counter()))
            if thread.is_alive():
                return False
        while self.outstanding():
            if time.perf_counter() > deadline or self.errors:
                return False
            time.sleep(0.02)
        if self.spec["loop"] != "open":
            for session in self.sessions:       # drop stale releases
                while session.answered.acquire(blocking=False):
                    pass
        return not self.errors

    # -- what came back ----------------------------------------------------

    def records(self) -> list[dict]:
        """Every request sent so far with its reply matched: refusals
        by the tag they echo, results by their frame id -- the door
        numbers admitted frames of a session consecutively, in the
        order they were sent."""
        matched = []
        for session in self.sessions:
            with session.lock:
                sent = list(session.sent)
                replies = list(session.replies)
            refused = {summary["tag"]: (at, summary)
                       for at, summary in replies
                       if summary["op"] != "result"}
            results = [(at, summary) for at, summary in replies
                       if summary["op"] == "result"]
            by_frame = {}
            for position, (at, summary) in enumerate(results):
                by_frame.setdefault(summary["frame"], []).append(
                    (position, at, summary))
            admitted = 0
            for record in sent:
                record = dict(record)
                if record["index"] in refused:
                    at, summary = refused[record["index"]]
                    record.update(recv_s=at, status=summary["op"],
                                  diagnostic=summary["diagnostic"])
                else:
                    hits = by_frame.get(admitted, [])
                    record["frame"] = admitted
                    record["answers"] = len(hits)
                    if hits:
                        position, at, summary = hits[0]
                        record.update(
                            recv_s=at, position=position,
                            trace=summary["trace"],
                            status="ok" if summary["ok"] else "error",
                            diagnostic=summary["diagnostic"],
                            data=summary["data"])
                    else:
                        record.update(recv_s=None, status="unanswered")
                    admitted += 1
                matched.append(record)
        return matched


def in_order(records: list[dict]) -> bool:
    """Every admitted request answered exactly once, and within each
    session in the order sent: among a session's results, the one for
    frame n + 1 is the one right after the one for frame n."""
    last = {}
    for record in sorted((r for r in records if "frame" in r),
                         key=lambda r: (r["session"], r["frame"])):
        if record.get("answers") != 1:
            return False
        before = last.get(record["session"])
        if before is not None and (
                record["frame"] - before[0]
                != record["position"] - before[1]):
            return False
        last[record["session"]] = (record["frame"], record["position"])
    return True


# -- arithmetic --------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (``q`` in 0..100) of a non-empty
    list, as ``numpy.percentile``'s default."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    rank = (len(ordered) - 1) * q / 100.0
    low = int(math.floor(rank))
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def samples_beyond(count: int, q: float) -> int:
    """How many of ``count`` samples lie beyond the q-th percentile: a
    tail wants at least ten."""
    return int(math.floor(count * (100.0 - q) / 100.0))


def window_numbers(records: list[dict], start_s: float, seconds: float,
                   new_tokens: int) -> dict:
    """The end-to-end numbers of one window: requests due (open loop)
    or sent (closed loop) in it, latency from then to the result, and
    the tokens of requests answered ok inside it."""
    end_s = start_s + seconds
    mine = [r for r in records
            if start_s <= (r["due_s"] if r["due_s"] is not None
                           else r["sent_s"]) < end_s]
    latencies = [
        (r["recv_s"] - (r["due_s"] if r["due_s"] is not None
                        else r["sent_s"])) * 1000.0
        for r in mine if r["status"] == "ok"]
    answered_inside = [r for r in records if r["status"] == "ok"
                       and start_s <= r["recv_s"] < end_s]
    late = [(r["sent_s"] - r["due_s"]) * 1000.0 for r in mine
            if r["due_s"] is not None]
    statuses: dict = {}
    for r in mine:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    return {
        "attempted": len(mine),
        "failed": sum(1 for r in mine if r["status"] != "ok"),
        "statuses": statuses,
        "latencies_ms": latencies,
        "tokens_per_s": len(answered_inside) * new_tokens / seconds,
        "answered_inside": len(answered_inside),
        "generator_late_ms": {
            "p50": percentile(late, 50), "max": max(late)}
        if late else None,
        "requests": mine,
    }
