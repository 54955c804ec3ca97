"""Checkpoint / resume for model-hosting elements (orbax-backed).

The reference has NO checkpointing anywhere (SURVEY.md section 5.4:
storage.py is a sqlite stub; registrar history is in-memory only) -- this
is a required TPU-native addition: model parameters + optimizer state
live in HBM, sharded over a mesh, and must save/restore preserving
shardings so a restore onto the same (or a compatible) mesh never
round-trips through a single host replica.

``Checkpointer`` wraps orbax's async CheckpointManager with:
- step-numbered saves with retention (keep latest N),
- sharding-aware restore: pass a ``MeshPlan`` + partition specs and
  leaves are materialized directly as sharded ``jax.Array``s,
- a tiny JSON sidecar for framework metadata (config, step, wall time).
"""

from __future__ import annotations

import json
import pathlib
import time
from typing import Any

import jax
import orbax.checkpoint as ocp

from ..parallel.mesh import MeshPlan

__all__ = ["Checkpointer", "save_pytree", "restore_pytree",
           "maybe_restore"]


class Checkpointer:
    """Step-numbered checkpoints under a root directory.

    >>> ckpt = Checkpointer(path, keep=3)
    >>> ckpt.save(step, {"params": params, "opt_state": opt_state},
    ...           metadata={"config": dataclasses.asdict(config)})
    >>> state = ckpt.restore(plan=plan, specs={"params": specs, ...})
    """

    def __init__(self, directory: str | pathlib.Path, keep: int = 3):
        self.directory = pathlib.Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=keep, create=True, enable_async_checkpointing=True)
        self._manager = ocp.CheckpointManager(self.directory, options=options)

    # -- save ---------------------------------------------------------------

    def save(self, step: int, state: dict, metadata: dict | None = None,
             wait: bool = False) -> None:
        """Async save of a pytree of (possibly sharded) jax.Arrays."""
        meta = dict(metadata or {})
        meta.setdefault("step", step)
        meta.setdefault("saved_unix_time", time.time())
        meta = json.loads(json.dumps(meta, default=str))
        self._manager.save(step, args=ocp.args.Composite(
            state=ocp.args.StandardSave(state),
            aiko_metadata=ocp.args.JsonSave(meta)))
        if wait:
            self.wait()

    def wait(self) -> None:
        self._manager.wait_until_finished()

    # -- restore ------------------------------------------------------------

    @property
    def latest_step(self) -> int | None:
        return self._manager.latest_step()

    def all_steps(self) -> list[int]:
        return sorted(self._manager.all_steps())

    def restore(self, step: int | None = None, template: Any = None,
                plan: MeshPlan | None = None, specs: Any = None) -> dict:
        """Restore a checkpoint.

        template: pytree of arrays (or ShapeDtypeStructs) giving the
        structure; with ``plan``+``specs`` (matching pytrees of
        PartitionSpecs) leaves restore directly sharded onto the mesh.
        Without a template, restores with saved metadata (replicated).
        """
        step = self.latest_step if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        if template is None:
            result = self._manager.restore(step)
            return result["state"]
        if plan is not None and specs is not None:
            abstract = jax.tree_util.tree_map(
                lambda leaf, spec: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=plan.shard(spec)),
                template, specs)
        else:
            abstract = jax.tree_util.tree_map(
                lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                template)
        result = self._manager.restore(step, args=ocp.args.Composite(
            state=ocp.args.StandardRestore(abstract)))
        return result["state"]

    def metadata(self, step: int | None = None) -> dict:
        step = self.latest_step if step is None else step
        try:
            result = self._manager.restore(step, args=ocp.args.Composite(
                aiko_metadata=ocp.args.JsonRestore()))
            return dict(result["aiko_metadata"] or {})
        except (KeyError, FileNotFoundError, ValueError):
            return {}

    def close(self):
        self._manager.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def save_pytree(directory, state: dict, metadata: dict | None = None):
    """One-shot synchronous save (step 0)."""
    with Checkpointer(directory, keep=1) as ckpt:
        ckpt.save(0, state, metadata=metadata, wait=True)


def restore_pytree(directory, template=None, plan=None, specs=None) -> dict:
    with Checkpointer(directory) as ckpt:
        return ckpt.restore(template=template, plan=plan, specs=specs)


def maybe_restore(params, checkpoint: str | None):
    """The model-hosting elements' checkpoint contract: ``params`` is the
    freshly-initialized pytree (the restore template); if ``checkpoint``
    names an orbax directory, the fitted weights replace it."""
    if checkpoint:
        params = restore_pytree(checkpoint,
                                template={"params": params})["params"]
    return params
