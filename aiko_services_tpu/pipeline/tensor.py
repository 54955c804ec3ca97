"""TPU data-plane substrate: tensor frames, shape bucketing, jit caches,
stage placement on device submeshes (SURVEY.md section 7 step 5).

In the reference, frames crossing stages are S-expressions over MQTT and
bulk data rides ZMQ (reference main/pipeline.py:1328-1347,
elements/media/scheme_zmq.py:40-150).  Here the data plane is TPU-native:

- swag values are ``jax.Array``s resident in HBM between elements;
- a stage is *placed* on a submesh of the local chips
  (``StagePlacement``), and frames hop stages by ``jax.device_put`` --
  resharding over ICI, never through the host;
- XLA recompilation is controlled by bucketing dynamic shapes
  (``ShapeBucketer``) and by per-element compiled-function caches keyed
  on abstract shapes (``JitCache``);
- only when a frame must leave the process (remote stage over the
  control plane, ZMQ scheme) is it encoded host-side
  (``encode_array``/``decode_array``).
"""

from __future__ import annotations

import io
import json
import os
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import MeshPlan, NamedSharding, P, make_mesh
from .element import PipelineElement
from .stream import Stream, StreamEvent

__all__ = ["ShapeBucketer", "JitCache", "StagePlacement", "TPUElement",
           "encode_array", "decode_array", "tree_device_put",
           "device_sort_key", "distributed_mesh_spec",
           "ensure_distributed"]


# ---------------------------------------------------------------------------
# Multi-host mesh mode (ISSUE 9): one logical pipeline spanning
# processes/hosts via jax.distributed, so placed-stage hops ride
# ICI/DCN through the shared global mesh instead of the broker.

MESH_ENV_HOSTS = "AIKO_MESH_HOSTS"
MESH_ENV_COORDINATOR = "AIKO_MESH_COORDINATOR"
MESH_ENV_PROCESS_ID = "AIKO_MESH_PROCESS_ID"

_DISTRIBUTED_STATE = {"initialized": False}


def distributed_mesh_spec(parameters) -> dict | None:
    """The pipeline's multi-host mesh request, or None.

    Sources, in precedence order: the ``mesh`` pipeline parameter
    (``{"hosts": N, "coordinator": "host:port", "process_id": k}`` --
    a dict or its JSON string), then the ``AIKO_MESH_*`` environment
    (hosts / coordinator / process id), so a launcher can mesh-enable
    an unmodified definition per process.  Raises ValueError on a
    malformed spec -- the same validation the ``bad-parameter`` lint
    rule applies at create time."""
    spec = (parameters or {}).get("mesh")
    if isinstance(spec, str):
        try:
            spec = json.loads(spec)
        except json.JSONDecodeError as error:
            raise ValueError(f"mesh: unparseable JSON ({error})")
    if spec is None:
        hosts_env = os.environ.get(MESH_ENV_HOSTS)
        if not hosts_env:
            return None
        spec = {"hosts": hosts_env,
                "coordinator": os.environ.get(MESH_ENV_COORDINATOR),
                "process_id": os.environ.get(MESH_ENV_PROCESS_ID, 0)}
    if not isinstance(spec, dict) or "hosts" not in spec:
        raise ValueError(
            f"mesh: expected {{'hosts': N, ...}}, got {spec!r}")
    try:
        hosts = int(spec["hosts"])
    except (TypeError, ValueError):
        raise ValueError(f"mesh: hosts={spec['hosts']!r} is not an "
                         f"integer")
    if hosts < 1:
        raise ValueError(f"mesh: hosts must be >= 1, got {hosts}")
    try:
        process_id = int(spec.get("process_id") or 0)
    except (TypeError, ValueError):
        raise ValueError(f"mesh: process_id="
                         f"{spec.get('process_id')!r} is not an "
                         f"integer")
    return {"hosts": hosts,
            "coordinator": spec.get("coordinator") or None,
            "process_id": process_id}


def ensure_distributed(spec: dict | None) -> tuple[int, int]:
    """Bring up ``jax.distributed`` for a REAL multi-host mesh (a
    coordinator is configured and more than one host declared), once
    per process; afterwards ``jax.devices()`` is the GLOBAL pool and
    :class:`StagePlacement` groups it by ``device.process_index``.
    Single-process/virtual meshes (no coordinator -- the CI shape)
    skip the bring-up and carve virtual host groups instead.  Returns
    (process_index, process_count)."""
    if spec and spec.get("coordinator") and spec["hosts"] > 1 \
            and not _DISTRIBUTED_STATE["initialized"] \
            and jax.process_count() == 1:
        jax.distributed.initialize(
            coordinator_address=spec["coordinator"],
            num_processes=spec["hosts"],
            process_id=spec["process_id"])
        _DISTRIBUTED_STATE["initialized"] = True
    return jax.process_index(), jax.process_count()


# ---------------------------------------------------------------------------
# Shape bucketing: dynamic sizes -> small set of compiled shapes.

class ShapeBucketer:
    """Round ragged dimensions up to a bucket so XLA compiles once per
    bucket instead of once per length (SURVEY.md section 7 "shape
    polymorphism" hard part).

    Default buckets are powers of two from ``minimum``; an explicit
    bucket list wins.  ``pad(array, axis)`` returns (padded, true_size).
    """

    def __init__(self, buckets: Sequence[int] | None = None,
                 minimum: int = 16, maximum: int = 1 << 20):
        self._buckets = sorted(buckets) if buckets else None
        self._minimum = minimum
        self._maximum = maximum

    def bucket(self, size: int) -> int:
        if self._buckets:
            for b in self._buckets:
                if size <= b:
                    return b
            raise ValueError(f"size {size} exceeds largest bucket "
                             f"{self._buckets[-1]}")
        b = self._minimum
        while b < size:
            b <<= 1
            if b > self._maximum:
                raise ValueError(f"size {size} exceeds maximum bucket")
        return b

    def pad(self, array, axis: int = 0, fill=0):
        size = array.shape[axis]
        target = self.bucket(size)
        if target == size:
            return array, size
        widths = [(0, 0)] * array.ndim
        widths[axis] = (0, target - size)
        return jnp.pad(array, widths, constant_values=fill), size


# ---------------------------------------------------------------------------
# Per-element compiled-function cache.

class JitCache:
    """Cache ``jax.jit`` computations keyed on input avals.

    ``cache(fn)(*args)`` compiles once per distinct (shape, dtype)
    signature and replays thereafter; ``stats`` exposes hit/miss/entry
    counters for the Metrics element and the dashboard share dict
    (``Pipeline.jit_stats``).
    Donation and shardings pass through to ``jax.jit``.
    """

    def __init__(self, **jit_kwargs):
        self._jit_kwargs = jit_kwargs
        self._compiled: dict = {}
        self.hits = 0
        self.misses = 0

    def _key(self, fn, args, kwargs, context=None):
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        sig = tuple(
            (leaf.shape, str(leaf.dtype)) if hasattr(leaf, "shape")
            else repr(leaf) for leaf in leaves)
        return (id(fn), treedef, sig, context)

    def probe(self, fn, args: tuple, kwargs: dict | None = None,
              context=None) -> bool:
        """True when a call with these arguments would MISS (trace +
        compile) -- lets callers time/annotate first-use compiles
        without racing the counters.  ``context`` partitions the key
        space: a replicated stage's submeshes share avals but not
        executables (jax re-specializes per sharding), so dispatchers
        pass the replica index to keep hit/miss/probe accounting
        honest per replica."""
        return self._key(fn, args, kwargs or {}, context) \
            not in self._compiled

    def __call__(self, fn: Callable) -> Callable:
        jitted = jax.jit(fn, **self._jit_kwargs)

        def wrapper(*args, _cache_context=None, **kwargs):
            key = self._key(fn, args, kwargs, _cache_context)
            if key in self._compiled:
                self.hits += 1
            else:
                self.misses += 1
                self._compiled[key] = True
            return jitted(*args, **kwargs)

        wrapper.jitted = jitted
        return wrapper

    @property
    def entries(self) -> int:
        return len(self._compiled)

    @property
    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._compiled),
                "signatures": len(self._compiled)}


# ---------------------------------------------------------------------------
# Stage placement: pipeline stages onto disjoint chip submeshes.

def device_sort_key(device):
    """ICI-topology order for carving contiguous stage chunks: TPU chip
    ``coords`` (x, y, z) then core, so consecutive devices in the sorted
    pool are ICI neighbours and adjacent stages' chunks touch.  Devices
    without coords (CPU/GPU virtual devices) fall back to id order,
    which is the enumeration order of the virtual mesh."""
    coords = getattr(device, "coords", None)
    if coords is not None:
        try:
            return (0, tuple(int(c) for c in coords),
                    int(getattr(device, "core_on_chip", 0) or 0))
        except (TypeError, ValueError):
            pass
    return (1, (), int(getattr(device, "id", 0)))


class StagePlacement:
    """Carve the local device set into per-stage submeshes.

    The reference deploys stages into other OS processes found by
    ServiceFilter (reference pipeline.py:246-258); on TPU a stage lands
    on a group of local chips instead.  ``assign`` partitions the
    topology-sorted device pool contiguously (``device_sort_key``: chip
    coords, so "contiguous" means ICI neighbours and adjacent stages are
    ICI-adjacent) and returns a ``MeshPlan`` per stage; ``transfer``
    reshards a frame's tensors onto the next stage's mesh -- on TPU a
    pure ICI copy, dispatched asynchronously (``jax.device_put`` does
    not block) with the ``NamedSharding`` memoized per
    (stage, generation, spec) and already-resident leaves passed through
    untouched.

    A stage may request ``"auto"`` devices: after fixed requests are
    carved, the remaining pool splits across the auto stages
    proportionally to their measured per-element cost
    (``record_cost``, fed from profiled element spans; equal split
    until profiles exist).  ``replace()`` re-resolves auto splits
    against the survivors, so the balance tracks both the profile and
    the shrinking pool.

    Replicated stages (ISSUE 7): ``assign(..., replicas={stage: N})``
    splits a stage's allocation into N data-parallel **replica
    submeshes** -- contiguous slices of the topology-sorted chunk, each
    its own MeshPlan, so ICI locality holds within a replica.  Fixed
    requests describe ONE replica (total = prod(axes) * N, so
    power-of-two per-replica shapes stay power-of-two); ``auto``
    requests split the stage's cost-proportional share near-equally
    across the replicas.  ``drop_replica`` retires ONE replica's chips
    without touching any peer's submesh (the peer-shedding failover
    path: generation does NOT bump); ``reassign()`` re-fits the
    original requests to the surviving pool (shedding replicas down to
    ``replica_min`` before halving fixed axes) -- the background
    rebuild after a failover, and the autoscaler's re-split.
    """

    def __init__(self, devices: Sequence | None = None):
        self.devices = sorted(devices if devices is not None
                              else jax.devices(), key=device_sort_key)
        self.plans: dict[str, MeshPlan] = {}
        self._requests: dict = {}
        # Multi-host mesh mode (ISSUE 9): the pool partitions into
        # per-host device groups -- by ``device.process_index`` under a
        # real jax.distributed mesh, or N contiguous virtual groups of
        # the topology-sorted pool in a single process (the CI shape,
        # same carving code).  Stages land wholly inside ONE host's
        # group (``stage_hosts``), so a stage hop between same-host
        # stages is ICI and a cross-host hop is DCN through the shared
        # global mesh -- never the broker.
        self.hosts: int | None = None
        self.host_groups: list[list] = []
        self.stage_hosts: dict[str, int] = {}
        self._stage_host_pins: dict[str, int] = {}
        self.generation = 0             # bumped by every replace()
        self.costs: dict[str, float] = {}    # stage -> EMA seconds/frame
        self._shardings: dict = {}      # (stage, replica, gen, spec) memo
        self.transfer_puts = 0          # leaves actually moved
        self.transfer_skipped = 0       # leaves already resident
        # Replicated stages: stage -> [MeshPlan | None per slot] (None =
        # dead, retired by drop_replica), the DESIRED counts (what
        # reassign restores toward), and the floor replica counts the
        # fit loop respects when shedding.  ``replica_epoch`` bumps on
        # every drop/reassign so per-replica plan caches (TPUElement)
        # invalidate without a full-generation bump.
        self.replica_plans: dict[str, list] = {}
        self._replica_desired: dict[str, int] = {}
        self._replica_min: dict[str, int] = {}
        self.replica_epoch = 0

    # -- carving -----------------------------------------------------------

    @staticmethod
    def _normalize(stages: dict) -> dict:
        requests = {}
        for name, want in stages.items():
            if isinstance(want, str):
                if want.strip().lower() != "auto":
                    raise ValueError(
                        f"stage {name!r}: device request must be a chip "
                        f"count, a mesh dict, or 'auto', got {want!r}")
                requests[name] = "auto"
            else:
                requests[name] = {"dp": want} if isinstance(want, int) \
                    else dict(want)
        return requests

    def _resolve(self, requests: dict, pool: int,
                 replicas: dict | None = None) -> dict[str, int]:
        """Resolve every stage to a TOTAL device count against a pool of
        ``pool`` devices.  Fixed requests describe one replica, so a
        replicated fixed stage takes prod(axes) * N; ``auto`` stages
        split the free chips proportionally to recorded per-stage cost,
        floored at one chip per replica."""
        replicas = replicas or {}

        def floor_of(name):
            return max(1, replicas.get(name, 1))

        fixed = {name: int(np.prod(list(axes.values())))
                 * replicas.get(name, 1)
                 for name, axes in requests.items() if axes != "auto"}
        auto = [name for name, axes in requests.items() if axes == "auto"]
        fixed_total = sum(fixed.values())
        auto_floor = sum(floor_of(name) for name in auto)
        if fixed_total + auto_floor > pool:
            raise ValueError(
                f"stages want {fixed_total + auto_floor} devices, "
                f"have {pool}")
        shares: dict[str, int] = {}
        if auto:
            free = pool - fixed_total
            weights = {name: max(float(self.costs.get(name, 0.0)), 0.0)
                       for name in auto}
            if not any(weights.values()):
                weights = {name: 1.0 for name in auto}   # unprofiled
            else:
                # A stage with no profile yet gets the smallest known
                # weight rather than zero chips.
                floor = min(w for w in weights.values() if w > 0)
                weights = {name: (w if w > 0 else floor)
                           for name, w in weights.items()}
            total_w = sum(weights.values())
            shares = {name: max(floor_of(name),
                                int(free * weights[name] / total_w))
                      for name in auto}
            # Largest-remainder fit to exactly ``free`` chips.
            while sum(shares.values()) > free:
                name = max((n for n in auto
                            if shares[n] > floor_of(n)),
                           key=lambda n: shares[n])
                shares[name] -= 1
            while sum(shares.values()) < free:
                name = max(auto, key=lambda n: (
                    free * weights[n] / total_w - shares[n]))
                shares[name] += 1
        return {name: (shares[name] if axes == "auto" else fixed[name])
                for name, axes in requests.items()}

    def assign(self, stages: dict, costs: dict | None = None,
               replicas: dict | None = None,
               replica_min: dict | None = None,
               hosts: int | None = None,
               stage_hosts: dict | None = None) -> dict[str, MeshPlan]:
        """stages: name -> chip count, {axis: size} mesh request, or
        ``"auto"``.  ``costs`` (stage -> seconds) seeds the profile the
        auto split balances on.  ``replicas`` (stage -> N >= 1) splits
        those stages' allocations into N replica submeshes (a fixed
        request then describes ONE replica); ``replica_min`` floors the
        counts the fit loop may shed to under device loss.  ``hosts``
        > 1 enables mesh mode: the pool partitions into per-host
        groups and every stage carves wholly inside one group --
        pinned by ``stage_hosts`` (stage -> host index, the placement
        block's ``host`` key) or filled greedily in declaration
        order."""
        if costs:
            for name, seconds in costs.items():
                self.record_cost(name, float(seconds))
        requests = self._normalize(stages)
        replicas = {name: max(1, int(count))
                    for name, count in (replicas or {}).items()
                    if name in requests}
        self._requests = requests
        self._replica_desired = dict(replicas)
        if replica_min is not None:
            self._replica_min = {name: max(1, int(count))
                                 for name, count in replica_min.items()}
        self.hosts = int(hosts) if hosts and int(hosts) > 1 else None
        self._stage_host_pins = {name: int(index) for name, index
                                 in (stage_hosts or {}).items()}
        self._carve(requests, replicas)
        return self.plans

    # -- mesh mode: per-host device groups ---------------------------------

    def _host_groups_for(self, devices: list) -> list[list]:
        """Partition ``devices`` into per-host groups: by the real
        ``process_index`` when a jax.distributed mesh spans processes,
        else ``self.hosts`` contiguous chunks of the topology-sorted
        pool (virtual hosts -- single-process reproduction of the
        multi-host carve, same code path)."""
        by_process: dict[int, list] = {}
        for device in devices:
            by_process.setdefault(
                int(getattr(device, "process_index", 0) or 0),
                []).append(device)
        if len(by_process) > 1:
            return [by_process[key] for key in sorted(by_process)]
        count = self.hosts or 1
        base, rem = divmod(len(devices), count)
        groups, pos = [], 0
        for index in range(count):
            size = base + (1 if index < rem else 0)
            groups.append(devices[pos:pos + size])
            pos += size
        return groups

    def stage_host(self, stage: str) -> int | None:
        """Which host group a stage is placed on (None outside mesh
        mode)."""
        return self.stage_hosts.get(stage) if self.hosts else None

    def same_host(self, stage_a: str, stage_b: str) -> bool:
        """True when a hop between the stages stays inside one host's
        ICI domain (always true outside mesh mode: one host)."""
        if not self.hosts:
            return True
        return self.stage_hosts.get(stage_a) \
            == self.stage_hosts.get(stage_b)

    def _carve(self, requests: dict, replicas: dict) -> None:
        """Cut the topology-sorted pool into per-stage chunks (and
        per-replica sub-chunks) for already-fitted requests; in mesh
        mode every chunk comes wholly from one host group."""
        resolved = self._resolve(requests, len(self.devices), replicas)
        self.plans = {}
        self.replica_plans = {}
        if self.hosts:
            self._carve_hosted(requests, replicas, resolved)
            return
        cursor = 0
        for name, axes in requests.items():
            total = resolved[name]
            chunk = self.devices[cursor:cursor + total]
            cursor += total
            self._place_chunk(name, axes, chunk, replicas)

    def _carve_hosted(self, requests: dict, replicas: dict,
                      resolved: dict) -> None:
        groups = self._host_groups_for(self.devices)
        self.host_groups = groups
        self.stage_hosts = {}
        cursors = [0] * len(groups)
        fill = 0
        for name, axes in requests.items():
            total = resolved[name]
            pin = self._stage_host_pins.get(name)
            if pin is not None:
                if not 0 <= pin < len(groups):
                    raise ValueError(
                        f"stage {name!r}: host {pin} out of range "
                        f"(mesh has {len(groups)} hosts)")
                if len(groups[pin]) - cursors[pin] < total:
                    raise ValueError(
                        f"stage {name!r} wants {total} chips on host "
                        f"{pin}, which has "
                        f"{len(groups[pin]) - cursors[pin]} free")
                host = pin
            else:
                host = None
                for offset in range(len(groups)):
                    candidate = (fill + offset) % len(groups)
                    if len(groups[candidate]) - cursors[candidate] \
                            >= total:
                        host = candidate
                        break
                if host is None:
                    raise ValueError(
                        f"stage {name!r} wants {total} chips but no "
                        f"host group has that many free (a stage "
                        f"never spans hosts -- its submesh must fit "
                        f"one ICI domain)")
                fill = host
            chunk = groups[host][cursors[host]:cursors[host] + total]
            cursors[host] += total
            self.stage_hosts[name] = host
            self._place_chunk(name, axes, chunk, replicas)

    def _place_chunk(self, name: str, axes, chunk: list,
                     replicas: dict) -> None:
        """Build a stage's MeshPlan (and replica sub-plans) from its
        carved device chunk -- shared by the flat and hosted carves."""
        total = len(chunk)
        if name in replicas:
            count = replicas[name]
            subs, pos = [], 0
            base, rem = divmod(total, count)
            for index in range(count):
                size = base + (1 if index < rem else 0)
                sub = chunk[pos:pos + size]
                pos += size
                sub_axes = dict(axes) if axes != "auto" \
                    else {"dp": size}
                subs.append(MeshPlan(make_mesh(sub_axes, sub)))
            self.replica_plans[name] = subs
            # The whole-stage plan (stage_devices, default hops,
            # stats) spans every replica's chips as one dp pool.
            self.plans[name] = MeshPlan(
                make_mesh({"dp": total}, chunk))
        else:
            plan_axes = dict(axes) if axes != "auto" \
                else {"dp": total}
            self.plans[name] = MeshPlan(make_mesh(plan_axes, chunk))

    def record_cost(self, stage: str, seconds: float) -> None:
        """EMA of the measured per-frame cost of a stage (fed from the
        engine's element spans); ``devices: auto`` splits re-balance on
        it at the next assign()/replace()."""
        prior = self.costs.get(stage)
        self.costs[stage] = float(seconds) if prior is None \
            else 0.75 * prior + 0.25 * float(seconds)

    def _fit(self, pool_size: int) -> tuple[dict, dict]:
        """Shrink the ORIGINAL requests (and desired replica counts)
        until they fit ``pool_size`` devices: replicated stages shed
        replicas first (graceful N-1 degradation, floored at
        ``replica_min``), then fixed stages halve their largest axis
        (power-of-two steps keep dp/tp/fsdp shardings valid)."""
        requests = {name: (axes if axes == "auto" else dict(axes))
                    for name, axes in self._requests.items()}
        replicas = dict(self._replica_desired)

        def need():
            total = 0
            for name, axes in requests.items():
                count = replicas.get(name, 1)
                if axes == "auto":
                    total += max(1, count)
                else:
                    total += int(np.prod(list(axes.values()))) * count
            return total

        def stage_need(name):
            axes = requests[name]
            count = replicas.get(name, 1)
            return count if axes == "auto" \
                else int(np.prod(list(axes.values()))) * count

        while need() > pool_size:
            sheddable = [name for name, count in replicas.items()
                         if count > self._replica_min.get(name, 1)]
            if sheddable:
                name = max(sheddable, key=stage_need)
                replicas[name] -= 1
                continue
            shrinkable = [name for name, axes in requests.items()
                          if axes != "auto"
                          and int(np.prod(list(axes.values()))) > 1]
            if not shrinkable:
                raise RuntimeError(
                    f"cannot shrink stages below one device "
                    f"({pool_size} survivors for "
                    f"{len(requests)} stages)")
            name = max(shrinkable,
                       key=lambda n: int(np.prod(
                           list(requests[n].values()))))
            axes = requests[name]
            axis = max(axes, key=axes.get)
            axes[axis] = max(1, axes[axis] // 2)
        return requests, replicas

    def replace(self, failed_devices: Sequence) -> dict[str, MeshPlan]:
        """Re-place every stage onto the surviving devices (SURVEY.md
        §5.3 TPU-equiv: re-shard onto surviving chips).

        Failed devices leave the pool permanently (survivors keep their
        topology-sorted order, so chunks stay ICI-contiguous);
        replicated stages shed replicas first (down to ``replica_min``),
        then fixed stage requests shrink by halving their largest axis
        (power-of-two steps keep dp/tp/fsdp shardings valid) until the
        total fits, and ``auto`` stages re-split the remaining pool by
        recorded cost.  Plans are rebuilt in place -- elements must drop
        cached plans and re-put weights
        (``TPUElement.on_replacement``)."""
        failed = set(failed_devices)
        survivors = [d for d in self.devices if d not in failed]
        if len(survivors) == len(self.devices):
            return self.plans
        if not survivors:
            raise RuntimeError("no surviving devices to re-place onto")
        requests, replicas = self._fit(len(survivors))
        self.devices = survivors
        self._shardings.clear()
        self.generation += 1
        self.replica_epoch += 1
        self._carve(requests, replicas)
        return self.plans

    def reassign(self) -> dict[str, MeshPlan]:
        """Re-fit the ORIGINAL requests (desired replica counts
        included) onto the current pool and re-carve every stage: the
        background rebuild of a dropped replica, and the autoscaler's
        re-split after ``set_replicas``.  Bumps the generation --
        callers must invalidate plans/frames exactly as after
        ``replace()``."""
        requests, replicas = self._fit(len(self.devices))
        self._shardings.clear()
        self.generation += 1
        self.replica_epoch += 1
        self._carve(requests, replicas)
        return self.plans

    def plan(self, stage: str) -> MeshPlan:
        return self.plans[stage]

    # -- replicated stages -------------------------------------------------

    @property
    def has_replicas(self) -> bool:
        return bool(self.replica_plans)

    def replica_total(self, stage: str) -> int:
        """Slots (live or dead) of a replicated stage; 0 when the stage
        is not replicated."""
        return len(self.replica_plans.get(stage, ()))

    def live_replicas(self, stage: str) -> list[int]:
        return [index for index, plan
                in enumerate(self.replica_plans.get(stage, ()))
                if plan is not None]

    def replica_plan(self, stage: str, index: int) -> MeshPlan:
        plan = self.replica_plans[stage][index]
        if plan is None:
            raise KeyError(f"stage {stage!r} replica {index} is dead")
        return plan

    def replica_devices(self, stage: str, index: int) -> set:
        plans = self.replica_plans.get(stage, ())
        if index >= len(plans) or plans[index] is None:
            return set()
        return set(plans[index].mesh.devices.flat)

    def replica_of(self, stage: str, device) -> int | None:
        """Which live replica of ``stage`` owns ``device`` (None when
        the stage is not replicated or the device is not placed
        there)."""
        for index, plan in enumerate(self.replica_plans.get(stage, ())):
            if plan is not None and device in set(plan.mesh.devices.flat):
                return index
        return None

    def set_replicas(self, stage: str, count: int) -> None:
        """Update a replicated stage's DESIRED count (the autoscaler's
        knob); takes effect at the next ``reassign()``."""
        if stage not in self._replica_desired:
            raise KeyError(f"stage {stage!r} is not replicated")
        self._replica_desired[stage] = max(
            self._replica_min.get(stage, 1), int(count))

    def drop_replica(self, stage: str, index: int) -> set:
        """Retire ONE replica's chips (peer-shedding failover): the
        devices leave the pool permanently, the slot reads dead, and --
        the point -- no other submesh is touched: peers keep serving on
        their exact meshes, so ``generation`` does NOT bump (only
        ``replica_epoch``, which invalidates per-replica plan caches
        and this stage's memoized shardings).  Returns the retired
        device set (empty when the slot is unknown/already dead)."""
        subs = self.replica_plans.get(stage)
        if not subs or index >= len(subs) or subs[index] is None:
            return set()
        dead = set(subs[index].mesh.devices.flat)
        subs[index] = None
        self.devices = [d for d in self.devices if d not in dead]
        alive = [d for plan in subs if plan is not None
                 for d in plan.mesh.devices.flat]
        if alive:
            self.plans[stage] = MeshPlan(
                make_mesh({"dp": len(alive)}, alive))
        else:
            self.plans.pop(stage, None)
        self.replica_epoch += 1
        self._shardings = {key: value
                           for key, value in self._shardings.items()
                           if key[0] != stage}
        return dead

    def stage_devices(self, stage: str) -> set:
        """The devices a stage's submesh currently occupies (empty for
        an unknown stage) -- the chaos harness's ``device_kill`` target
        resolution and the replay path's blast-radius checks."""
        plan = self.plans.get(stage)
        if plan is None:
            return set()
        return set(plan.mesh.devices.flat)

    # -- stage hops --------------------------------------------------------

    def stage_sharding(self, stage: str, spec: tuple = (),
                       replica: int | None = None) -> NamedSharding:
        """The memoized NamedSharding frames reshard onto when hopping
        to ``stage`` (or one replica's submesh of it) -- built once per
        (stage, replica, generation, spec), not per frame."""
        key = (stage, replica, self.generation,
               tuple(spec) if spec else None)
        sharding = self._shardings.get(key)
        if sharding is None:
            plan = self.plans[stage] if replica is None \
                else self.replica_plan(stage, replica)
            sharding = plan.shard(*spec) if spec else plan.replicated()
            self._shardings[key] = sharding
        return sharding

    def transfer(self, value, to_stage: str, *spec,
                 replica: int | None = None):
        """Reshard ``value`` (array or pytree) onto a stage's mesh (a
        single replica's submesh when ``replica`` is given).

        Non-blocking: ``jax.device_put`` dispatches the ICI copy and
        returns immediately, so the hop overlaps the upstream stage's
        next-frame compute.  Leaves whose committed sharding already IS
        the target sharding pass through untouched (kills the per-frame
        no-op device_put walk for values resident on the stage)."""
        sharding = self.stage_sharding(to_stage, spec, replica=replica)

        def hop(leaf):
            if not hasattr(leaf, "shape"):
                return leaf
            if getattr(leaf, "sharding", None) == sharding:
                self.transfer_skipped += 1
                return leaf
            self.transfer_puts += 1
            return jax.device_put(leaf, sharding)

        return jax.tree_util.tree_map(hop, value)

    @property
    def stats(self) -> dict:
        result = {"generation": self.generation,
                  "stages": {name: int(plan.mesh.devices.size)
                             for name, plan in self.plans.items()},
                  "costs_ms": {name: round(cost * 1000.0, 3)
                               for name, cost in self.costs.items()},
                  "transfer_puts": self.transfer_puts,
                  "transfer_skipped": self.transfer_skipped,
                  "shardings_cached": len(self._shardings)}
        if self.replica_plans:
            result["replica_epoch"] = self.replica_epoch
            result["replicas"] = {
                name: [None if plan is None
                       else int(plan.mesh.devices.size)
                       for plan in plans]
                for name, plans in self.replica_plans.items()}
        if self.hosts:
            result["hosts"] = self.hosts
            result["host_groups"] = [len(group)
                                     for group in self.host_groups]
            result["stage_hosts"] = dict(self.stage_hosts)
        return result


def tree_device_put(tree, plan: MeshPlan, spec: P | None = None):
    """device_put every array leaf of a swag/pytree onto ``plan``."""
    sharding = plan.shard(spec) if spec is not None else plan.replicated()
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, sharding)
        if hasattr(leaf, "shape") else leaf, tree)


# ---------------------------------------------------------------------------
# Host-side array codec (only for frames leaving the process).

def encode_array(array) -> bytes:
    """jax/numpy array -> self-describing bytes (npy format)."""
    buffer = io.BytesIO()
    np.save(buffer, np.asarray(array), allow_pickle=False)
    return buffer.getvalue()


def decode_array(data: bytes) -> np.ndarray:
    return np.load(io.BytesIO(data), allow_pickle=False)


# ---------------------------------------------------------------------------
# TPU element base class.

# Sentinel for TPUElement's not-yet-computed placement-stage cache
# (None is a valid resolved value: "unplaced").
_UNRESOLVED = object()


class TPUElement(PipelineElement):
    """PipelineElement hosting jitted computation on a device mesh.

    Placement resolves from the ``placement`` parameter: ``"local"``
    (all local devices, default), a mesh request like
    ``{"dp": 2, "tp": 4}``, or a stage name previously assigned on the
    pipeline's StagePlacement.  Subclasses use ``self.jit`` for
    shape-keyed compiled caches and ``self.plan`` for shardings.

    TPU elements are ``device_resident``: outputs may stay un-synced
    ``jax.Array`` (the engine only syncs at sinks / the bounded dispatch
    window), and event-loop execution runs under the pipeline's
    transfer guard (pipeline/overlap.py).  The ``donation-alias`` lint
    rule (analysis/residency.py) keys off this attribute at ``pipeline
    create``: a graph mapping that reads a producer-qualified alias of
    a device output another element overwrites pins the buffer and
    blocks HBM donation for any fused segment containing it.
    """

    device_resident = True

    def __init__(self, context):
        super().__init__(context)
        self._plan: MeshPlan | None = None
        self._replica_plan_cache: dict = {}
        self._stage_name_cache = _UNRESOLVED
        self.jit_cache = JitCache()
        self.bucketer = ShapeBucketer()

    @property
    def plan(self) -> MeshPlan:
        # Replicated stages (ISSUE 7): while a stage worker executes
        # this element for a specific replica, ``self.plan`` IS that
        # replica's submesh -- an element-side put/shard lands on the
        # replica's chips, never on a peer's (or a dead slot's).  The
        # cache keys on the placement's replica_epoch so a
        # drop/reassign invalidates it without a full on_replacement.
        pipeline = self.pipeline
        placements = getattr(pipeline, "stage_placement", None)
        current = getattr(pipeline, "current_replica", None)
        context = current() if callable(current) else None
        if context is not None and placements is not None:
            stage, index = context
            if stage in placements.replica_plans \
                    and self._placement_stage() == stage:
                key = (stage, index, placements.generation,
                       placements.replica_epoch)
                plan = self._replica_plan_cache.get(key)
                if plan is None:
                    plan = placements.replica_plan(stage, index)
                    self._replica_plan_cache = {key: plan}
                return plan
        if self._plan is None:
            self._plan = self._resolve_placement()
        return self._plan

    def _placement_stage(self) -> str | None:
        """The placed-stage name this element's placement resolves to
        (None when unplaced) -- same lookup order as
        ``_resolve_placement``.  Cached: ``self.plan`` consults it on
        every access in the replica worker hot path, and the binding is
        structural (definition placement block / parameter), not
        per-frame.  Cleared by ``on_replacement``."""
        if self._stage_name_cache is not _UNRESOLVED:
            return self._stage_name_cache
        placements = getattr(self.pipeline, "stage_placement", None)
        if placements is None:
            return None                 # no placement yet: don't cache
        placement, _ = self.get_parameter("placement", "local")
        name = None
        for key in (placement, self.name):
            if isinstance(key, str) and (
                    key in placements.plans
                    or key in placements.replica_plans):
                name = key
                break
        self._stage_name_cache = name
        return name

    def _resolve_placement(self) -> MeshPlan:
        placement, _ = self.get_parameter("placement", "local")
        placements = getattr(self.pipeline, "stage_placement", None)
        if placements is not None:
            # A definition ``placement`` block registers the stage under
            # the element's own node name; the ``placement`` parameter
            # may also name another stage explicitly (shared submesh).
            for key in (placement, self.name):
                if isinstance(key, str) and key in placements.plans:
                    return placements.plan(key)
        # Device pool: the StagePlacement's (which excludes chips removed
        # by replace()) when one exists, else all local devices -- a
        # default-placed element must never re-resolve onto a dead chip.
        pool = list(placements.devices) if placements is not None \
            else list(jax.devices())
        if isinstance(placement, dict):
            axes = dict(placement)
            sizes = list(axes.values())
            if -1 not in sizes and int(np.prod(sizes)) <= len(pool):
                return MeshPlan(make_mesh(axes,
                                          pool[:int(np.prod(sizes))]))
            return MeshPlan(make_mesh(axes, pool))
        return MeshPlan(make_mesh({"dp": len(pool)}, pool))

    def jit(self, fn: Callable) -> Callable:
        """Shape-keyed compiled cache for this element."""
        return self.jit_cache(fn)

    def on_replacement(self):
        """Devices were re-placed under this element (chip failure ->
        ``StagePlacement.replace``): drop the cached plan and compiled
        functions so the next frame resolves the new submesh and
        recompiles there.  Model-hosting subclasses also drop their
        resident weights, which rebuild lazily -- from the
        ``checkpoint`` parameter when set, so recovery restores real
        weights, not random init."""
        self._plan = None
        self._replica_plan_cache = {}
        self._stage_name_cache = _UNRESOLVED
        self.jit_cache = JitCache()

    def put(self, value, *spec):
        """Place an array (or pytree) on this element's mesh."""
        sharding = (self.plan.shard(*spec) if spec
                    else self.plan.replicated())
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sharding)
            if hasattr(leaf, "shape") else leaf, value)

    def metrics(self) -> dict:
        return {"jit": self.jit_cache.stats,
                "mesh": dict(self.plan.mesh.shape)}
