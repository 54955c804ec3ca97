"""The routed experts' grouped matmuls, shared by the families that
route (``models/deepseek.py``: sigmoid scores, selection bias, scaled
gates; ``models/sdar.py``: softmax scores, renormalised gates;
``models/command_a.py``: sigmoid scores, renormalised gates, a chip's
share of the experts).  A family's own router says which experts each
token goes to and with what gates; the sort, the grouped matmuls, the
unsort and the gate-weighted sum are :func:`routed_experts`, one code
for all three."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.tiles import on_tpu

__all__ = ["routed_experts"]

_GROUPED_ROWS = 128     # megablox's row tile
_TILE_BYTES = 6 << 20   # the largest expert-matrix tile megablox takes


def _tiling(weights) -> tuple:
    """megablox's tiling: one tile a whole expert matrix wide (the
    tiling that won the sweep on the v5e: PERF.md) where the matrix is
    at most ``_TILE_BYTES``, else its larger side halved until it is
    (a 4096 x 4096 expert's 32 MiB tile, double-buffered, is more fast
    memory than a kernel may take)."""
    depth, width = weights.shape[1:]
    while depth * width * weights.dtype.itemsize > _TILE_BYTES:
        if depth >= width:
            depth //= 2
        else:
            width //= 2
    return _GROUPED_ROWS, depth, width


def _megablox(rows, weights, groups):
    """``ragged_dot`` by the megablox kernel (:func:`_tiling`)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(rows, weights, groups, preferred_element_type=rows.dtype,
               tiling=_tiling(weights), interpret=not on_tpu())


def routed_experts(h, chosen, gates, experts, *, grouped_matmul: str,
                   valid=None, index=None, held=None):
    """The routed experts' part of a sparse layer over ``h [N, D]``,
    given the router's verdict (``chosen [N, k]`` expert ids, ``gates
    [N, k]`` float32): every token-expert pair computed, none dropped.
    Pairs are sorted by expert and multiplied by grouped matmuls over
    the experts' stacked weights (``ragged_dot``: rows of expert ``e``
    meet only ``W[e]``; an expert without rows costs nothing; the
    megablox kernel where ``grouped_matmul`` is ``megablox``, or
    ``auto`` on the TPU).  ``valid [N]`` marks the rows that count (a
    decode step's live rows): the others sort past the last group,
    touch no expert and give nought.

    ``experts`` is one layer's ``{w_gate, w_up, w_down}`` ``[E, ...]``
    or, with ``index`` (this layer's, traced), EVERY sparse layer's
    ``[Ls, E, ...]``: the grouped matmul then runs over all ``Ls * E``
    groups with only this layer's non-empty, and no layer's gigabyte of
    experts is sliced out of the stack in front of the kernel (XLA
    fuses a slice into an einsum, not into a custom call: the device
    loop copied all three matrices, every layer and step).

    ``held = (first, count)`` (static) is a chip's SHARE of the experts:
    ``chosen`` names experts of the whole layer, ``experts`` holds the
    ``count`` of them from ``first`` on, and a pair whose expert is not
    held sorts past the last group as an invalid row does and gives
    nought.  Gates are as the router gave them (normalised over all it
    chose), so the shares of a layer sum to the whole layer's routed
    part.

    Returns (out ``[N, D]``, rows per expert ``[E]``, of the held
    experts where ``held``)."""
    n, k = chosen.shape
    e = experts["w_up"].shape[0 if index is None else 1]
    expert_of = chosen.reshape(-1)                           # [N*k]
    keep = None
    if held is not None:
        expert_of = expert_of - held[0]
        keep = (expert_of >= 0) & (expert_of < held[1])
        if valid is not None:
            keep = keep & jnp.repeat(valid, k)
        expert_of = jnp.where(keep, expert_of, e)
    elif valid is not None:
        expert_of = jnp.where(jnp.repeat(valid, k), expert_of, e)
    megablox = grouped_matmul == "megablox" \
        or (grouped_matmul == "auto" and on_tpu())
    if megablox and (n * k) % _GROUPED_ROWS:
        # the kernel tiles the rows: pad with rows of no expert
        expert_of = jnp.pad(expert_of, (0, -(n * k) % _GROUPED_ROWS),
                            constant_values=e)
    order = jnp.argsort(expert_of, stable=True)
    rows = h[jnp.minimum(order // k, n - 1)]                 # [N*k', D]
    sizes = jnp.bincount(expert_of, length=e + 1)[:e].astype(jnp.int32)
    groups = sizes
    if index is not None:
        experts = jax.tree_util.tree_map(
            lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), experts)
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((experts["w_up"].shape[0],), jnp.int32), sizes,
            (index * e,))
        # rows of this layer's experts start at the stack's row 0: the
        # groups before them are empty
    matmul = _megablox if megablox else jax.lax.ragged_dot
    hidden = jax.nn.silu(matmul(rows, experts["w_gate"], groups)) \
        * matmul(rows, experts["w_up"], groups)
    out = matmul(hidden, experts["w_down"], groups)
    # back to token order, each pair weighted by its gate (float32);
    # rows past the last group hold whatever the kernel left there
    out = out[jnp.argsort(order)[:n * k]].reshape(n, k, -1) \
        .astype(jnp.float32)
    if keep is not None:
        out = jnp.where(keep.reshape(n, k, 1), out, 0.0)
    elif valid is not None:
        out = jnp.where(valid[:, None, None], out, 0.0)
    return (out * gates[..., None]).sum(1).astype(h.dtype), sizes
