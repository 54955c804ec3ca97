"""Overlapped frame execution: device-resident swag accounting and the
bounded per-stream dispatch window (ISSUE 1 tentpole; Vortex
arXiv:2511.02062 and the profiled-segmentation multi-TPU work
arXiv:2503.01025 both identify host/device overlap + device residency as
what turns component-fast pipelines into end-to-end-fast ones).

Two small engine-side mechanisms:

- :class:`TransferLedger` enforces and accounts the **device-resident
  swag contract**: between consecutive device elements swag values stay
  ``jax.Array`` in HBM; the host only sees them at a sink (wire
  response, process boundary) or at an input explicitly declared
  host-typed.  Device elements run under
  ``jax.transfer_guard_device_to_host`` with the configured policy
  (pipeline parameter ``transfer_guard``: ``allow`` | ``log`` |
  ``disallow``), every engine-initiated fetch is ONE counted
  ``jax.device_get`` of the whole tree, and a software residency check
  catches declared-``tensor`` outputs that come back as host arrays --
  the CPU backend's device-to-host "transfers" are zero-copy so the
  jax guard never fires there, but the residency check does, which is
  what lets tier-1 tests fail fast on host-sync regressions without
  TPU hardware.

- :class:`DeviceWindow` bounds how far dispatch runs ahead of compute:
  jitted elements return un-synced arrays and frames complete without a
  host sync, so a fast source could otherwise enqueue unbounded device
  work (and pin unbounded HBM in not-yet-computed results).  Each
  completed frame's device leaves are noted; ingesting a new frame
  paces the window by ``block_until_ready``-ing the OLDEST noted frame
  until at most ``device_inflight`` frames (default triple buffering)
  are outstanding -- classic double/triple buffering per stream.

The stage-keyed sibling of the DeviceWindow lives in
:mod:`~aiko_services_tpu.pipeline.stages`: multi-stage PLACED pipelines
additionally pace admission per placed stage (``stage_inflight``,
credit-based backpressure) so frames overlap ACROSS submeshes, while
this module's window keeps any one stream's dispatch bounded ahead of
compute.  The two compose: ingest pacing bounds total outstanding
device work, stage credits bound where in the pipeline it sits.

Unified QoS (ISSUE 12): the window depth ``pace()`` is called with is
no longer always the stream's raw ``device_inflight`` -- when the
pipeline carries a :class:`~aiko_services_tpu.gateway.qos.QosScheduler`
the limit is the stream's CLASS-capped depth
(``Pipeline._device_limit`` -> ``QosScheduler.device_limit``), so a
``batch``-class stream can be held to double buffering while
``interactive`` keeps the full window on the same pipeline.  The
window itself stays policy-free: it paces to whatever limit the one
scheduler resolves, which is exactly what makes this seam plane 1 of
the unified admission refactor.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

import jax
import numpy as np

__all__ = ["TransferLedger", "DeviceWindow", "device_leaves",
           "touches_devices", "DEVICE_INFLIGHT_DEFAULT"]

TRANSFER_POLICIES = ("allow", "log", "disallow")

# Default bounded async-dispatch window per stream (triple buffering);
# override with the ``device_inflight`` pipeline/stream parameter
# (0 disables pacing).
DEVICE_INFLIGHT_DEFAULT = 3


def device_leaves(tree) -> list:
    """Every ``jax.Array`` leaf of a swag/pytree (host values skipped)."""
    return [leaf for leaf in jax.tree_util.tree_leaves(tree)
            if isinstance(leaf, jax.Array)]


def touches_devices(tree, devices: set) -> bool:
    """True when any device leaf of ``tree`` lives (even partly) on one
    of ``devices`` -- the replay path's test for swag values stranded on
    dead chips.  A leaf whose device set cannot be read (deleted buffer,
    backend drift) counts as touching: recovery must treat it as
    compromised, not silently keep it."""
    for leaf in device_leaves(tree):
        try:
            if set(leaf.devices()) & devices:
                return True
        except Exception:
            return True
    return False


class TransferLedger:
    """Counts (and can forbid) host transfers on the frame path.

    ``implicit`` counts contract violations: transfers the engine did
    not initiate -- a jax transfer-guard error raised inside a device
    element (policy ``disallow`` on real hardware), or a
    declared-``tensor`` output arriving as a host ``np.ndarray`` (any
    policy except ``allow``, any backend).  Under ``log`` the jax-level
    guard only writes to jax's own log (nothing raises, so nothing can
    be counted from it); the residency check is what increments the
    counter there.  ``explicit`` counts engine-initiated fetches
    (host-typed inputs, process-boundary encodes), each ONE
    ``jax.device_get`` of the whole tree regardless of leaf count.
    Healthy pipelines keep ``implicit`` at 0.
    """

    def __init__(self, policy: str = "allow"):
        policy = str(policy or "allow").strip().lower()
        if policy not in TRANSFER_POLICIES:
            raise ValueError(f"transfer_guard={policy!r}: one of "
                             f"{TRANSFER_POLICIES}")
        self.policy = policy
        self.implicit = 0
        self.explicit = 0
        # Labeled sub-counts of ``explicit`` (e.g. the LLM element's
        # per-decode-block fetch, label "llm_block"): lets tests
        # assert a path pays EXACTLY one fetch per unit of work, not
        # merely "some" fetches.
        self.explicit_by_label: dict = {}
        # Counters are bumped from the event loop AND stage-worker
        # threads (pipeline/stages.py): unsynchronized += would lose
        # increments.
        self._count_lock = threading.Lock()

    @property
    def active(self) -> bool:
        return self.policy != "allow"

    @contextlib.contextmanager
    def guard(self):
        """Wrap one device element's event-loop execution.  Thread-local
        (jax config context), so an element's own fetch worker threads
        are unaffected -- fetching at the element's sink is its job."""
        if not self.active:
            yield
            return
        with jax.transfer_guard_device_to_host(self.policy):
            yield

    def record_implicit(self, count: int = 1):
        with self._count_lock:
            self.implicit += count

    @staticmethod
    def is_guard_error(error: BaseException) -> bool:
        message = str(error).lower()
        return "transfer" in message and "disallow" in message

    def fetch(self, tree, label: str | None = None):
        """ONE explicit host fetch of every device leaf in ``tree``
        (non-array leaves pass through untouched -- strings/lists/dicts
        in a swag must not become numpy).  Counted once per call, not
        per leaf -- under ``label`` too when given (the device-loop
        serving contract: one "llm_block" fetch per retired block);
        runs under an ``allow`` scope so the engine's own sinks never
        trip the guard they enforce."""
        leaves = device_leaves(tree)
        if not leaves:
            return tree
        with self._count_lock:
            self.explicit += 1
            if label:
                self.explicit_by_label[label] = \
                    self.explicit_by_label.get(label, 0) + 1
        with jax.transfer_guard_device_to_host("allow"):
            for leaf in leaves:
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()     # gather copies in flight
            fetched = iter(jax.device_get(leaves))
        return jax.tree_util.tree_map(
            lambda leaf: next(fetched)
            if isinstance(leaf, jax.Array) else leaf, tree)

    def residency_violations(self, element, outputs: dict) -> list[str]:
        """Declared device outputs (definition ``"type": "tensor"`` /
        ``"device"``) that came back host-resident: the software twin of
        the jax guard, effective on every backend."""
        declared = element.definition.output if element.definition else []
        violations = []
        for io in declared:
            io_type = str(io.get("type", "")).rstrip("?")
            if io_type not in ("tensor", "device"):
                continue
            value = outputs.get(io["name"])
            if value is not None and isinstance(value, np.ndarray):
                violations.append(io["name"])
        return violations

    @property
    def stats(self) -> dict:
        return {"policy": self.policy, "implicit": self.implicit,
                "explicit": self.explicit,
                "explicit_by_label": dict(self.explicit_by_label)}


class DeviceWindow:
    """Per-stream bounded in-flight accounting of dispatched-but-unsynced
    frames (double/triple buffering).  Owned by the event loop; no
    locking."""

    def __init__(self):
        self._inflight: deque = deque()      # (frame_id, device leaves)
        self.noted = 0                       # frames entering the window
        self.synced = 0                      # frames paced to completion
        self.invalidated = 0                 # entries dropped on dead chips

    def note(self, frame_id: int, swag) -> None:
        """Register a completed frame's outstanding device work."""
        leaves = device_leaves(swag)
        if leaves:
            self._inflight.append((frame_id, leaves))
            self.noted += 1

    def pace(self, limit) -> float:
        """Block (oldest-first) until at most ``limit - 1`` frames stay
        outstanding, so the frame about to dispatch makes ``limit``.
        ``limit`` <= 0 or None disables pacing (unbounded dispatch).
        Returns the seconds spent blocked (0.0 when nothing synced) --
        the telemetry plane's ``ingest_pace_ms`` histogram, i.e. how
        hard ingest is riding the dispatch window."""
        if not limit or limit <= 0:
            return 0.0
        if len(self._inflight) < limit:
            return 0.0
        start = time.perf_counter()
        while len(self._inflight) >= limit:
            _, leaves = self._inflight.popleft()
            jax.block_until_ready(leaves)
            self.synced += 1
        return time.perf_counter() - start

    def drain(self) -> None:
        """Sync everything outstanding (stream flush, tests)."""
        self.pace(1)

    def clear(self) -> None:
        """Drop bookkeeping without blocking (stream destroy)."""
        self._inflight.clear()

    def invalidate(self, failed: set) -> int:
        """Forget noted frames whose outstanding leaves sit on dead
        chips (device replacement OR a single replica's failover --
        ``failed`` is device-keyed, so retiring one replica submesh
        never touches a peer's entries): ``pace`` would otherwise
        ``block_until_ready`` a buffer whose device no longer exists --
        a raise at best, a hang at worst.  Returns how many noted
        frames were dropped."""
        keep, dropped = [], 0
        for frame_id, leaves in self._inflight:
            if touches_devices(leaves, failed):
                dropped += 1
            else:
                keep.append((frame_id, leaves))
        if dropped:
            self._inflight = deque(keep)
            self.invalidated += dropped
        return dropped

    @property
    def outstanding(self) -> int:
        return len(self._inflight)

    @property
    def stats(self) -> dict:
        return {"outstanding": self.outstanding, "noted": self.noted,
                "synced": self.synced, "invalidated": self.invalidated}
