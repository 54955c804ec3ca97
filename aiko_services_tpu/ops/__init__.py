"""Op-level interfaces: the transformer building blocks (ops.layers)
plus the Pallas kernel plane behind CAPABILITY PROBES (ISSUE 11).

The kernel modules (pallas_attention, pallas_decode, pallas_matmul,
pallas_topk) are imported lazily at first use so the package import
never pays for jax.experimental.pallas; callers select an
implementation through the probes below instead of try/except around a
kernel that raises -- ``decode_backend`` replaced exactly such a
dead-end (flash-decode used to raise on paged caches).
"""

import jax

from .layers import (rms_norm, rope_frequencies, apply_rope, swiglu,
                     repeat_kv, attention_prefill, attention_decode,
                     attention_decode_append)
from .tiles import on_tpu

__all__ = ["rms_norm", "rope_frequencies", "apply_rope", "swiglu",
           "repeat_kv", "attention_prefill", "attention_decode",
           "attention_decode_append", "decode_backend",
           "matmul_backend", "topk", "on_tpu", "DECODE_BACKENDS"]

#: every value :func:`decode_backend` can return, in preference order.
DECODE_BACKENDS = ("paged-kernel", "dense-flash", "reference")


def decode_backend(requested: str = "auto", *, paged: bool = False,
                   extent: int | None = None, threshold: int = 1024,
                   distributed: bool = False,
                   page_tokens: int | None = None) -> str:
    """Capability probe for decode attention: which implementation
    serves a cache of this structure -- ``paged-kernel`` (the
    page-table-walking split-K Pallas kernel, ops/pallas_decode.py),
    ``dense-flash`` (the flat/stacked split-K kernel) or ``reference``
    (the dense einsum path, ops/layers.py).

    ``requested`` is the config's ``decode_attention``
    (dense|flash|auto); ``distributed`` forces the reference path
    (pallas_call has no GSPMD partitioning rules -- the caller decides
    whether an explicit 'flash' request on a sharded cache is an
    error); under ``auto`` the kernels engage on the TPU backend
    (:func:`on_tpu`) alone, and the cache's LAYOUT decides what
    ``extent`` means to them:

    - a DENSE (flat / stacked) cache has an extent the reference path
      reads in place, so the kernel engages once ``extent`` reaches
      ``threshold`` (measured on that layout: one fused dispatch wins
      under 1k) and is block-alignable;
    - a PAGED cache has no such path: the reference gathers a layer's
      pages to a ``[B, T, ...]`` view, and inside a layer scan XLA
      takes the layer's WHOLE K and V pool out of the stack to do it
      (1.1 ms of a 4.5 ms step at 16 slots x 512 on v5e, PR 37), so
      with sublane-aligned pages (``page_tokens % 8 == 0``) it
      resolves ``paged-kernel`` at ANY extent -- the kernel copies a
      row's live pages only and ``threshold`` is not consulted.  (Int8
      pools with pages under 128 tokens copy a lane-padded scale pool a
      step on that path, ``pallas_decode._split_paged``; nothing gates
      on it.)
    """
    if requested in ("dense", "reference") or distributed:
        return "reference"
    if requested == "flash":
        return "paged-kernel" if paged else "dense-flash"
    if not on_tpu():
        return "reference"
    if paged:
        return "paged-kernel" \
            if page_tokens and page_tokens % 8 == 0 else "reference"
    extent = extent or 0
    return "dense-flash" \
        if extent >= threshold and extent % 128 == 0 else "reference"


def matmul_backend(requested: str = "auto") -> str:
    """Capability probe for the fused int8 dequant-matmul
    (ops/pallas_matmul.py): ``pallas-int8`` or ``reference`` (the
    cast-into-the-dot XLA path).  ``auto`` engages the kernel on TPU
    backends only (:func:`on_tpu`)."""
    if requested == "pallas":
        return "pallas-int8"
    if requested == "auto" and on_tpu():
        return "pallas-int8"
    return "reference"


def topk(x, k: int, *, kernel: bool | None = None):
    """Top-k over the last axis: ``(values, indices)`` with
    ``jax.lax.top_k``'s ordering contract (descending values, ties to
    the lowest index).  ``kernel=None`` resolves to the Pallas kernel
    (ops/pallas_topk.py) on TPU and ``lax.top_k`` elsewhere
    (:func:`on_tpu`); pass True/False to ask by name (the equivalence
    tests ask for True off the chip, which runs interpret mode)."""
    if kernel is None:
        kernel = on_tpu()
    if kernel:
        from .pallas_topk import topk as pallas_topk
        return pallas_topk(x, int(k))
    return jax.lax.top_k(x, int(k))
