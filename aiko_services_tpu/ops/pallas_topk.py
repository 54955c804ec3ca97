"""On-TPU top-k as a Pallas kernel (ISSUE 11; Vortex motivates keeping
the retrieval primitives on-device for latency-tight serving).

``lax.top_k`` lowers to a full sort on TPU -- O(V log V) over the whole
operand with the sorted vocab written back to HBM.  Serving wants the
k highest logits of a [B, V] row (top-k sampling, and ROADMAP item 4's
ANN search over an HBM-resident index wants exactly the same primitive
over similarity scores): one streaming pass, O(V * k) VPU work, nothing
but the [B, k] result leaving the chip.

Shape of the kernel: the grid is (row groups, V blocks).  Each step
loads one [rows, block_v] tile, extracts ITS top-k by k masked
max-passes, and folds them into a running [rows, 128] (value, index)
state in VMEM scratch -- one insertion per candidate against the
current weakest entry, ordered lexicographically by (value desc, index
asc) so ties resolve to the LOWEST index, matching ``lax.top_k``'s
stable contract (the equivalence test pins both, ties included).  The
last block sorts the k survivors and writes them out.  k is a static
trace constant <= 128 (one lane tile); sampling uses k in the single
digits.

Written for Mosaic, not for the interpreter (ISSUE 21): the state is
always a whole [rows, 128] lane tile with the first k lanes live under
an iota mask -- nothing is sliced or concatenated at a lane width that
is not a multiple of 128 -- the k passes are ``fori_loop``s, a row
group is one native tile of the operand's dtype (8 rows of f32, 16 of
bf16), and column indices ride as float32 (exact below 2**24, far
past any vocabulary) so every lane reduction is a float reduction.

Off the TPU the kernel runs in interpret mode when asked for by name
(the equivalence tests); the dispatching interface
(``aiko_services_tpu.ops.topk``) keeps ``lax.top_k`` there and
reserves the kernel for TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .tiles import (interpret_off_chip, pad_to as _pad_to,
                    round_up as _round_up)

__all__ = ["topk"]

#: kernel entry -> its tier-1 equivalence test (see the ``kernel-test``
#: selfcheck rule; the test forces ``interpret=True`` on the CPU mesh).
KERNEL_EQUIVALENCE_TESTS = {
    "topk": "test_kernel_plane.py::test_topk_matches_lax",
}

_NEG_INF = float("-inf")
_POS_INF = float("inf")
_LANES = 128       # scratch lane width (k <= _LANES)
# Index sentinel, as float32: above every real column (V < 2**24 is
# checked), and 2**24 + 2 * lane stays exactly representable, so the
# running state's k sentinels are DISTINCT.
_BIG = float(2 ** 24)


def _extract_max(s, col):
    """(max value [R, 1], its lowest column index [R, 1], s and col
    with that one entry CONSUMED).  Consumption masks BOTH the value
    (to -inf) and the column (to _BIG): value-only masking is a no-op
    on an entry that is already -inf, so a mostly-masked row (padded
    logits, ANN scores) would re-extract the same (-inf, col) pair
    every pass and emit duplicate indices -- the column mask makes the
    next pass pick the next-lowest unconsumed column instead, matching
    lax.top_k's ascending-index order over ties exactly."""
    m = jnp.max(s, axis=1, keepdims=True)
    hit = s == m
    idx = jnp.min(jnp.where(hit, col, _BIG), axis=1, keepdims=True)
    at = hit & (col == idx)
    return m, idx, jnp.where(at, _NEG_INF, s), jnp.where(at, _BIG, col)


def _insert(vals, idx, live, cand_v, cand_i):
    """Replace the weakest of the k live entries when the candidate
    ranks higher under (value desc, index asc).  ``live`` masks the
    first k lanes of the [R, 128] state."""
    weak_v = jnp.min(jnp.where(live, vals, _POS_INF), axis=1,
                     keepdims=True)
    weak_hit = live & (vals == weak_v)
    weak_i = jnp.max(jnp.where(weak_hit, idx, -1.0), axis=1,
                     keepdims=True)
    better = (cand_v > weak_v) | ((cand_v == weak_v) & (cand_i < weak_i))
    at = weak_hit & (idx == weak_i) & better
    return jnp.where(at, cand_v, vals), jnp.where(at, cand_i, idx)


def _topk_kernel(x_ref, ov_ref, oi_ref, vals_scr, idx_scr, *,
                 k: int, rows: int, block_v: int, v_len: int,
                 out_dtype):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    live = lane < k

    @pl.when(vi == 0)
    def _init():
        vals_scr[...] = jnp.full_like(vals_scr, _NEG_INF)
        # DISTINCT sentinel indices: every (value, index) pair in the
        # running state must be unique or the weakest-slot selection in
        # _insert matches several slots at once and the state
        # degenerates to k copies of one entry.  Real candidates carry
        # column indices < _BIG, so sentinels always lose ties.
        idx_scr[...] = _BIG + 2.0 * lane.astype(jnp.float32)

    col_i = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (rows, block_v), 1)
    s = jnp.where(col_i < v_len, x_ref[...].astype(jnp.float32),
                  _NEG_INF)

    # k masked max-passes pull the block's own top-k in order; each
    # candidate then displaces the running state's weakest entry (or
    # nothing).  Everything is [rows, <= block_v] VPU work on
    # VMEM-resident tiles -- the HBM traffic is the single streaming
    # read of x.
    def fold(_, carry):
        s, col, vals, idx = carry
        cand_v, cand_i, s, col = _extract_max(s, col)
        vals, idx = _insert(vals, idx, live, cand_v, cand_i)
        return s, col, vals, idx

    _, _, vals, idx = jax.lax.fori_loop(
        0, k, fold, (s, col_i.astype(jnp.float32), vals_scr[...],
                     idx_scr[...]))
    vals_scr[...] = vals
    idx_scr[...] = idx

    @pl.when(vi == nv - 1)
    def _finalize():
        def emit(j, carry):
            vals, idx, out_v, out_i = carry
            m = jnp.max(jnp.where(live, vals, _NEG_INF), axis=1,
                        keepdims=True)
            hit = live & (vals == m)
            pick = jnp.min(jnp.where(hit, idx, _BIG), axis=1,
                           keepdims=True)
            # Consume BOTH value and index (the _extract_max rule):
            # value-only masking leaves an already--inf entry's index
            # live and the next pass re-picks it.
            consumed = hit & (idx == pick)
            return (jnp.where(consumed, _NEG_INF, vals),
                    jnp.where(consumed, _BIG, idx),
                    jnp.where(lane == j, m, out_v),
                    jnp.where(lane == j, pick, out_i))

        zeros = jnp.zeros((rows, _LANES), dtype=jnp.float32)
        _, _, out_v, out_i = jax.lax.fori_loop(
            0, k, emit, (vals_scr[...], idx_scr[...], zeros, zeros))
        ov_ref[...] = out_v.astype(out_dtype)
        oi_ref[...] = out_i.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "block_v",
                                             "interpret"))
def topk(x, k: int, *, block_v: int = 2048,
         interpret: bool | None = None):
    """Top-k over the last axis of ``x`` [B, V] -> (values [B, k],
    indices [B, k] int32), descending, ties to the lowest index --
    ``lax.top_k``'s ordering contract, without the full sort."""
    interpret = interpret_off_chip(interpret)
    b, v = x.shape
    if not 0 < k <= min(v, _LANES):
        raise ValueError(
            f"topk: k={k} must be in [1, min(V={v}, {_LANES})]")
    if v >= _BIG:
        raise ValueError(f"topk: V={v} must be below 2**24 (column "
                         f"indices ride as float32)")
    # One native sublane tile of the operand's dtype per grid step:
    # 8 rows of a 4-byte dtype, 16 of bf16, 32 of int8.
    rows = 8 * max(1, 4 // x.dtype.itemsize)
    b_pad = _round_up(max(b, rows), rows)
    block_v = min(block_v, _round_up(max(v, _LANES), _LANES))
    x_p = _pad_to(_pad_to(x, 0, b_pad), 1, block_v)
    v_pad = x_p.shape[1]

    kernel = functools.partial(_topk_kernel, k=k, rows=rows,
                               block_v=block_v, v_len=v,
                               out_dtype=x.dtype)
    values, indices = pl.pallas_call(
        kernel,
        grid=(b_pad // rows, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((rows, block_v), lambda bi, vi: (bi, vi)),
        ],
        out_specs=[
            pl.BlockSpec((rows, _LANES), lambda bi, vi: (bi, 0)),
            pl.BlockSpec((rows, _LANES), lambda bi, vi: (bi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, _LANES), x.dtype),
            jax.ShapeDtypeStruct((b_pad, _LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x_p)
    return values[:b, :k], indices[:b, :k]
