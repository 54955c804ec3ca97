"""Shared tile/padding arithmetic for the Pallas kernel plane -- one
authority for the sublane/lane rounding every kernel module needs
(four drifting copies is exactly the class of duplication the
kernel-plane selfcheck rules exist to prevent)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pad_to", "round_up", "on_tpu", "interpret_off_chip"]


def on_tpu() -> bool:
    """The platform term of every ``auto`` probe
    (``aiko_services_tpu.ops``): the Pallas kernels are Mosaic
    programs, so ``auto`` engages them on the TPU backend ONLY.  Off
    the chip ``auto`` always resolves ``reference`` -- a process that
    landed on the CPU must never run the Pallas interpreter in its
    serving loop.  A kernel asked for BY NAME (``flash`` / ``pallas`` /
    ``kernel=True``) still runs off the chip, in interpret mode
    (:func:`interpret_off_chip`): that is how tier-1 checks the kernel
    bodies, and nothing else."""
    return jax.default_backend() == "tpu"


def interpret_off_chip(interpret: bool | None) -> bool:
    """Resolve a kernel entry point's ``interpret`` argument.  An
    explicit True/False wins (``chip_smoke.py`` passes False so Mosaic
    -- VMEM limit, tile alignment and all -- is what compiles).  None
    means "compile for the backend in use": Mosaic on the TPU, the
    Pallas interpreter anywhere else -- which no ``auto`` probe reaches
    (:func:`on_tpu`)."""
    return not on_tpu() if interpret is None else bool(interpret)


def round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def pad_to(x, axis: int, multiple: int):
    """Zero-pad ``x`` along ``axis`` up to the next multiple (no copy
    when already aligned)."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
