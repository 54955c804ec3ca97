"""Device mesh management: inventory, named-axis meshes, submesh carving.

This layer is what the reference's "remote element deployment" becomes on
TPU (SURVEY.md section 2.5): instead of placing a pipeline stage in another
OS process reachable over MQTT, a stage is placed on a submesh of the local
pod's chips and data moves over ICI as jax.Arrays.  The Registrar carries
the inventory as service tags (``tpu=v5e``, ``chips=8``, ``mesh=2x4``) so
placement is discoverable exactly like any other service property.

Axis conventions (the scaling-book recipe):
- ``dp``  data parallel (batch split; gradients psum over it)
- ``fsdp`` parameter-sharded data parallel (params/optimizer scattered)
- ``tp``  tensor parallel (matmul column/row split; activations all-gather
          / reduce-scatter over it -- keep on the fastest ICI axis)
- ``sp``  sequence/context parallel (ring attention over it)
- ``ep``  expert parallel (MoE expert split)
- ``pp``  pipeline-stage parallel (microbatch pipelining)
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["device_inventory", "make_mesh", "MeshPlan", "submesh",
           "inventory_tags", "donate_argnums_supported",
           "P", "NamedSharding"]


def donate_argnums_supported(argnums: tuple) -> tuple:
    """Buffer donation on the CPU backend is at best ignored and at worst
    miscompiled (XLA raises ``Expected aliased input ... to have the same
    size`` for sharded train steps on the virtual-device mesh); on
    TPU/GPU it is the free HBM win.  Returns ``argnums`` on backends that
    support donation, ``()`` on CPU."""
    return () if jax.default_backend() == "cpu" else tuple(argnums)


AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


def device_inventory() -> dict:
    """Describe local accelerator devices for tags/placement."""
    devices = jax.devices()
    kinds = sorted({d.device_kind for d in devices})
    return {
        "platform": devices[0].platform if devices else "none",
        "device_kind": kinds[0] if kinds else "none",
        "device_count": len(devices),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
    }


def inventory_tags() -> list[str]:
    info = device_inventory()
    return [f"platform={info['platform']}",
            f"accelerator={info['device_kind'].replace(' ', '_')}",
            f"chips={info['device_count']}"]


def make_mesh(axes: dict[str, int] | None = None,
              devices: Sequence | None = None) -> Mesh:
    """Build a named-axis Mesh.

    ``axes`` maps axis name -> size, in AXIS_ORDER; sizes of -1 are
    inferred (at most one).  With no axes, returns a 1-axis ``dp`` mesh
    over all devices.  Axis sizes must multiply to the device count.
    """
    devices = list(devices if devices is not None else jax.devices())
    count = len(devices)
    if not axes:
        axes = {"dp": count}
    names = [a for a in AXIS_ORDER if a in axes]
    extras = [a for a in axes if a not in AXIS_ORDER]
    names += extras
    sizes = [axes[a] for a in names]
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis size may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if count % known:
            raise ValueError(f"cannot infer axis: {count} % {known} != 0")
        sizes[sizes.index(-1)] = count // known
    if int(np.prod(sizes)) != count:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs "
            f"{int(np.prod(sizes))} devices, have {count}")
    array = np.asarray(devices).reshape(sizes)
    return Mesh(array, axis_names=tuple(names))


def submesh(mesh: Mesh, axis: str, index: int) -> Mesh:
    """Carve the slice ``axis == index`` out of a mesh -- stage placement
    onto disjoint chip groups (e.g. stage A on tp block 0, stage B on
    block 1)."""
    axis_pos = mesh.axis_names.index(axis)
    devices = np.take(mesh.devices, index, axis=axis_pos)
    names = tuple(n for n in mesh.axis_names if n != axis)
    if devices.ndim == 0:
        devices = devices.reshape(1)
        names = ("dp",)
    return Mesh(devices, axis_names=names)


class MeshPlan:
    """A mesh plus the sharding vocabulary models use.

    ``plan.shard(spec)`` -> NamedSharding; axis names absent from the mesh
    are dropped from specs automatically, so the same model code runs on a
    1-chip dev box and a v5e-8 unchanged.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    @classmethod
    def build(cls, axes: dict[str, int] | None = None, devices=None) \
            -> "MeshPlan":
        return cls(make_mesh(axes, devices))

    def axis_size(self, name: str) -> int:
        return (self.mesh.shape[name]
                if name in self.mesh.axis_names else 1)

    def _filter_spec(self, spec: P) -> P:
        def keep(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry
                             if a in self.mesh.axis_names)
                return kept if kept else None
            return entry if entry in self.mesh.axis_names else None
        return P(*[keep(entry) for entry in spec])

    def shard(self, *spec) -> NamedSharding:
        if len(spec) == 1 and isinstance(spec[0], P):
            spec = spec[0]
        else:
            spec = P(*spec)
        return NamedSharding(self.mesh, self._filter_spec(spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def put(self, tree, spec_tree):
        """device_put a pytree with per-leaf PartitionSpecs (a single spec
        broadcasts)."""
        if isinstance(spec_tree, P):
            return jax.device_put(tree, self.shard(spec_tree))
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, self.shard(spec)),
            tree, spec_tree)

    def constraint(self, value, *spec):
        return jax.lax.with_sharding_constraint(value, self.shard(*spec))

    def __repr__(self):
        return f"MeshPlan({dict(self.mesh.shape)})"


def virtual_cpu_devices(count: int = 8):
    """For tests/dry-runs: requires XLA_FLAGS=--xla_force_host_platform_
    device_count=N set before jax initialises."""
    devices = jax.devices("cpu")
    if len(devices) < count:
        raise RuntimeError(
            f"need {count} cpu devices, have {len(devices)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={count} "
            f"before importing jax")
    return devices[:count]
