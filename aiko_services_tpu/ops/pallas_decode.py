"""Flash-decode attention: split-K over the KV cache as a Pallas TPU
kernel (the long-context serving path).

Decode attention is bandwidth-bound -- each step streams the whole cache
once -- but the dense path (ops/layers.py:attention_decode_append)
materializes the [B, H, T] score/weight intermediates in HBM: at 8k
context that chain (logits write, mask, max, exp, sum, cast, dot) moves
more bytes than the cache itself, which is why measured HBM utilization
collapsed from 0.78 at 1k to 0.44 at 8k.  Here the cache is
the ONLY large HBM traffic: K/V blocks stream HBM->VMEM through the
BlockSpec pipeline, scores and online-softmax statistics live in VMEM
scratch across the T grid axis, and one [H, K*hd] accumulator is written
per batch row.

Layout choices (same trick as the dense path's docstring, kept because
it is the MXU-friendly formulation):

- the cache is consumed as [B, T, K*hd] -- its natural contiguous view
  -- and GQA is expressed as block-diagonal matmuls: queries are
  zero-padded to the full K*hd width (done once outside, q is tiny), so
  scores = q_pad @ k_blk^T contracts over K*hd (lane-aligned: 512 at
  llama head layout) and the weighted sum is [H, Tb] @ [Tb, K*hd];
- int8 caches are dequantized IN KERNEL: the HBM stream is int8 bytes
  (the entire point at long context), the VMEM cast rides the MXU
  shadow, and the per-(t, k) scales fold into the f32 scores (keys) and
  softmax weights (values) -- both EXACT, because each scale is constant
  along the contracted head_dim.  Unlike the dense int8 path there is
  NO query or softmax-weight quantization, so the diffuse-attention
  error mode of weight quantization (ADVICE r3) does not exist here;
- blocks wholly beyond a row's ``length`` clamp their DMA index to the
  last live block (fetch skipped, compute skipped via pl.when), so
  short rows in a ragged batch do not pay full-T bandwidth;
- block_t defaults to 2048 in the flat and stacked kernels: a grid
  step's fixed cost dominates below that (v5e at 8k: 233 GB/s at
  512, 367 at 1024, 410+ at 2048).  A dead step is not free
  either -- the pipeline evaluates every operand's index map and
  waits on its semaphore each step, ~0.2 us an operand (PR 30) -- which
  is why the PAGED kernel, whose blocks are pages of 128 tokens, does
  not ride the BlockSpec pipeline at all (below);
- the kernel returns UNNORMALIZED (acc, m, l) partial softmax stats;
  the caller merges the current token's self-attention term outside
  (exactly the split the dense path uses) -- see
  :func:`flash_decode_append`.

ISSUE 11 grew this module into the serving kernel PLANE: the same
split-K body now also runs over layer-STACKED caches (scan-invariant,
layer picked in the BlockSpecs -- no per-layer slice copy), over PAGED
page pools (``flash_decode_attention_paged``: the [B, pps] page table
is scalar-prefetched and walked in the kernel, so the logical row view
the gather-attention path materialized never exists and the cache
streams once.  Its grid is ``(B,)``: a row loops over its LIVE pages
only, a few at a time, copying them HBM -> VMEM itself one group ahead
of the products -- across rows too -- so a page a slot could hold but
does not costs nothing, and a row that does not decode costs its grid
step.  PR 30 measured the forms on v5e at 32 rows x 16 pages of
[128, 1024] bf16, 29 rows of ~610 tokens live, 97 us of bytes a
layer: a page a grid step 220 us; the pools handed to the pipeline
2-16 times over, dead pages repeating the index an operand held,
157-199 us; this form 111 us -- PERF.md section 6), and under the
speculative verify chunk
(``flash_verify_append``: all S draft positions share one cache
frontier, so the cache part is THIS kernel with S*H block-diagonal
query rows, and the chunk's own causal keys combine outside -- the
cache is read once per verify, not once per drafted token).  Backend
choice lives in ``aiko_services_tpu.ops.decode_backend`` (capability
probe, not try/except).

Off the TPU the kernels run in interpret mode when asked for by name
(``decode_attention: flash``), so tier-1 checks the kernel bodies on
the CPU mesh; ``auto`` never routes here off the chip
(``ops.on_tpu``), and Mosaic itself is checked on the chip by
``chip_smoke.py`` (``interpret=False``; all of flat, stacked, paged at
page sizes 8..256 x 4..64 pages a slot and the verify chunk compile on
v5e, bf16 and int8).  The paged kernel copies by hand, so off the chip
it takes the TPU interpreter (``pltpu.InterpretParams``), which models
DMAs and semaphores.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .tiles import (interpret_off_chip, pad_to as _pad_to,
                    round_up as _round_up)

__all__ = ["flash_decode_attention", "flash_decode_append",
           "flash_decode_attention_stacked", "flash_decode_append_stacked",
           "flash_decode_attention_paged", "flash_decode_append_paged",
           "flash_verify_append", "paged_pages_per_step",
           "paged_grid_steps"]

#: kernel entry -> its tier-1 equivalence test (``file::test``) -- the
#: ``kernel-test`` selfcheck rule requires every ``pl.pallas_call``
#: entry point in this module to appear here with a test that exists,
#: and the ``kernel-table`` rule keeps the README kernel-plane table in
#: sync with these keys.  All referenced tests force ``interpret=True``
#: paths on the CPU mesh, so the pairing gates PRs without TPU hardware.
KERNEL_EQUIVALENCE_TESTS = {
    "flash_decode_attention":
        "test_flash_decode.py::test_flash_matches_dense_bf16_cache",
    "flash_decode_attention_stacked":
        "test_flash_decode.py::test_decode_step_flash_matches_dense",
    "flash_decode_attention_paged":
        "test_kernel_plane.py::test_paged_kernel_bitwise_matches_dense_kernel",
    "flash_verify_append":
        "test_kernel_plane.py::test_chunk_verify_kernel_matches_dense",
}


def is_quantized(leaf) -> bool:
    """Quantized cache/weight leaf (same shape contract as
    models/quant.py:is_quantized; duplicated here so ops never imports
    the models package -- models imports ops)."""
    return isinstance(leaf, dict) and "int8" in leaf and "scale" in leaf

_NEG_INF = -1e30
_STAT_LANES = 128


def _group_onehot(h: int, n_kv: int, dtype, groups: int | None = None,
                  period: int | None = None):
    """[H, K] 0/1 matrix mapping query row -> its kv head (built from
    iotas so it also works inside the kernel).  ``groups`` is the TRUE
    queries-per-kv-head count -- it must be passed explicitly when ``h``
    is sublane-PADDED (padded rows map to no kv head: all-zero rows,
    harmless, sliced off outside).  ``period`` handles MULTI-QUERY row
    layouts (the verify chunk's [S*H] rows repeat the head pattern every
    H rows): row r maps through ``(r % period) // groups``.  Padded rows
    then DO land on a kv head -- still harmless (their queries are zero
    and their output rows are sliced off), so ``period`` is only for
    entry points that slice."""
    groups = groups or ((period or h) // n_kv)
    rows = jax.lax.broadcasted_iota(jnp.int32, (h, n_kv), 0)
    if period is not None:
        rows = rows % period
    rows = rows // groups
    cols = jax.lax.broadcasted_iota(jnp.int32, (h, n_kv), 1)
    return (rows == cols).astype(dtype)


def _scores_block(q_blk, k_blk, ks_blk, *, n_heads, n_kv, groups, period,
                  compute_dtype, quantized):
    """One KV block's score matrix [H, Tb] in f32 (shared by the flat,
    stacked and paged kernels -- the block refs are already stripped of
    their leading unit dims)."""
    if quantized:
        k_blk = k_blk.astype(compute_dtype)
    s = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [H, Tb]
    if quantized:
        # Key scales are constant along the contracted K*hd axis
        # (each head only reads its own kv block out of the
        # block-diagonal product), so applying them to the scores is
        # exact dequantization: scale_h = onehot @ ks  ([H, Tb]).
        onehot = _group_onehot(n_heads, n_kv, jnp.float32,
                               groups=groups, period=period)
        s = s * jax.lax.dot_general(
            onehot, ks_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return s


def _online_update(m_scr, l_scr, acc_scr, s, v_blk, vs_blk, *, n_heads,
                   n_kv, groups, period, compute_dtype, quantized,
                   p_mask=None):
    """Fold one block's scores into the VMEM online-softmax state
    (running max, denominator, unnormalized accumulator)."""
    m_prev = m_scr[:, :1]                             # [H, 1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)                           # [H, Tb] f32
    if p_mask is not None:
        p = jnp.where(p_mask, p, jnp.zeros_like(p))
    correction = jnp.exp(m_prev - m_safe)
    # The denominator sums the UNSCALED weights (the softmax
    # normalizer) -- value scales fold into the numerator only.
    l_scr[...] = jnp.broadcast_to(
        l_prev * correction
        + jnp.sum(p, axis=1, keepdims=True, dtype=jnp.float32),
        l_scr.shape)
    if quantized:
        # Value scales fold into the weights -- exact for the same
        # constant-along-hd reason; the weights themselves stay
        # float (NO int8 weight quantization: the dense path's
        # diffuse-tail truncation mode does not exist here).
        onehot = _group_onehot(n_heads, n_kv, jnp.float32,
                               groups=groups, period=period)
        p = p * jax.lax.dot_general(
            onehot, vs_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        v_blk = v_blk.astype(compute_dtype)
    pv = jax.lax.dot_general(
        p.astype(compute_dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # [H, K*hd]
    acc_scr[...] = acc_scr[...] * correction + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)


def _decode_kernel(meta_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *,
                   block_t, n_heads, n_kv, groups, compute_dtype,
                   quantized, layered, period=None):
    """meta_ref: scalar-prefetch i32 array -- ``lengths`` [B] in the
    per-layer form, ``[layer, *lengths]`` in the layered/paged forms
    (the cache refs then carry a leading layer dim the BlockSpecs index
    into).  One ``block_t``-sized stretch of the row per grid step; the
    paged form has its own body (:func:`_paged_kernel`) and shares
    :func:`_scores_block` and :func:`_online_update`."""
    b = pl.program_id(0)
    ti = pl.program_id(1)
    nt = pl.num_programs(1)
    length = meta_ref[1 + b] if layered else meta_ref[b]
    t_start = ti * block_t

    def kv_blk(ref):
        return ref[0, 0] if layered else ref[0]

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = t_start < length
    # Interior blocks (every position valid) skip the iota/mask VPU work
    # -- at full context that is all blocks but the last.
    interior = t_start + block_t <= length

    shared = dict(n_heads=n_heads, n_kv=n_kv, groups=groups,
                  period=period, compute_dtype=compute_dtype,
                  quantized=quantized)

    def _scores():
        return _scores_block(q_ref[0], kv_blk(k_ref), kv_blk(ks_ref),
                             **shared)

    @pl.when(jnp.logical_and(live, interior))
    def _compute_interior():
        _online_update(m_scr, l_scr, acc_scr, _scores(), kv_blk(v_ref),
                       kv_blk(vs_ref), **shared)

    @pl.when(jnp.logical_and(live, jnp.logical_not(interior)))
    def _compute_boundary():
        t_pos = t_start + jax.lax.broadcasted_iota(
            jnp.int32, (n_heads, block_t), 1)
        mask = t_pos < length
        _online_update(m_scr, l_scr, acc_scr,
                       jnp.where(mask, _scores(), _NEG_INF),
                       kv_blk(v_ref), kv_blk(vs_ref), p_mask=mask,
                       **shared)

    @pl.when(ti == nt - 1)
    def _finalize():
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def _fit_block(t: int, block_t: int, *, pad: bool, entry: str) -> int:
    """Resolve the usable time-block size for a cache extent ``t`` --
    the ONE extent check shared by every decode entry point.
    ``pad=True`` (flat per-batch caches) just clamps: the caller pads
    its operands to a block multiple, a copy of only the small per-call
    views.  ``pad=False`` (stacked/paged pools, which are NEVER padded
    -- that copy would be the whole cache) shrinks block_t to a divisor
    of t and raises when none >= 128 exists."""
    block_t = min(block_t, _round_up(max(t, 8), 8))
    if pad:
        return block_t
    while t % block_t and block_t > 128:
        block_t //= 2
    if t % block_t:
        # Callers gate on t % 128 == 0 (llama decode falls back to
        # dense); reaching here means an explicit misuse.
        raise ValueError(
            f"{entry}: cache extent {t} has no block-aligned divisor "
            f">= 128 (use a multiple of 128, or the dense/per-layer "
            f"path)")
    return block_t


def _require_matched_quantization(k_quantized: bool, v_quantized: bool,
                                  entry: str) -> None:
    """init_cache/init_paged_cache quantize k and v together; a mixed
    pair can only come from caller error, and the kernels key their
    in-kernel dequant on the K scales alone -- a raw v would be read as
    int8 garbage.  The shared invariant check of every append entry."""
    if k_quantized != v_quantized:
        raise ValueError(
            f"{entry}: k and v caches must share one quantization "
            f"state (both int8 layers or both raw arrays); got "
            f"k quantized={k_quantized}, v quantized={v_quantized}")


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def flash_decode_attention(q_pad, k_flat, v_flat, k_scale_t, v_scale_t,
                           lengths, *, block_t: int = 2048,
                           interpret: bool | None = None):
    """Split-K decode attention over the cache; returns partial stats.

    q_pad: [B, H, C] block-diagonal padded queries (C = K*hd), softmax
    scale already folded in; k_flat/v_flat: [B, T, C] cache views (bf16,
    or int8 when quantized); k_scale_t/v_scale_t: [B, K, T] f32
    per-position scales (quantized caches) or None; lengths: [B] valid
    positions.  Returns (acc [B, H, C] f32 unnormalized, m [B, H] f32
    running max, l [B, H] f32 denominator) -- merge the current token's
    self term with :func:`flash_decode_append`'s combine step.
    """
    interpret = interpret_off_chip(interpret)
    quantized = k_scale_t is not None
    b, h, c = q_pad.shape
    t = k_flat.shape[1]
    n_kv = k_scale_t.shape[1] if quantized else None

    h_pad = _round_up(max(h, 8), 8)
    q_pad = _pad_to(q_pad, 1, h_pad)
    block_t = _fit_block(t, block_t, pad=True,
                         entry="flash_decode_attention")
    k_flat = _pad_to(k_flat, 1, block_t)
    v_flat = _pad_to(v_flat, 1, block_t)
    t_pad = k_flat.shape[1]

    if not quantized:
        # n_kv only matters for scale expansion; any divisor works for
        # the (unused) onehot shape -- use 1 so H % n_kv always holds.
        n_kv = 1
        k_scale_t = jnp.zeros((b, 1, t_pad), dtype=jnp.float32)
        v_scale_t = jnp.zeros((b, 1, t_pad), dtype=jnp.float32)
    else:
        k_scale_t = _pad_to(k_scale_t, 2, block_t)
        v_scale_t = _pad_to(v_scale_t, 2, block_t)

    grid = (b, t_pad // block_t)
    compute_dtype = q_pad.dtype if q_pad.dtype != jnp.float32 \
        else jnp.float32

    def _clamped(bi, ti, lengths):
        # Blocks wholly beyond this row's length clamp to the last live
        # block: pl.when skips the compute, the repeated index skips
        # the HBM->VMEM DMA -- a short row in a ragged batch reads only
        # its own extent, not full T.
        last_live = jnp.maximum(
            pl.cdiv(lengths[bi], block_t) - 1, 0)
        return jnp.minimum(ti, last_live)

    def kv_block(bi, ti, lengths):
        return (bi, _clamped(bi, ti, lengths), 0)

    def scale_block(bi, ti, lengths):
        # Scales are [B, K, T]: the T axis is dim 2 here, not dim 1.
        return (bi, 0, _clamped(bi, ti, lengths))

    kernel = functools.partial(
        _decode_kernel, block_t=block_t, n_heads=h_pad, n_kv=n_kv,
        groups=max(h // n_kv, 1), compute_dtype=compute_dtype,
        quantized=quantized, layered=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h_pad, c), lambda bi, ti, lengths: (bi, 0, 0)),
            pl.BlockSpec((1, block_t, c), kv_block),
            pl.BlockSpec((1, block_t, c), kv_block),
            pl.BlockSpec((1, n_kv, block_t), scale_block),
            pl.BlockSpec((1, n_kv, block_t), scale_block),
        ],
        out_specs=[
            pl.BlockSpec((1, h_pad, c), lambda bi, ti, lengths: (bi, 0, 0)),
            pl.BlockSpec((1, h_pad, _STAT_LANES),
                         lambda bi, ti, lengths: (bi, 0, 0)),
            pl.BlockSpec((1, h_pad, _STAT_LANES),
                         lambda bi, ti, lengths: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, c), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h_pad, c), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(lengths, dtype=jnp.int32), q_pad, k_flat, v_flat,
      k_scale_t, v_scale_t)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret",
                                             "qrow_period"))
def flash_decode_attention_stacked(q_pad, k_flat, v_flat, k_scale_t,
                                   v_scale_t, layer, lengths, *,
                                   block_t: int = 2048,
                                   interpret: bool | None = None,
                                   qrow_period: int | None = None):
    """:func:`flash_decode_attention` over ONE layer of a STACKED cache.

    k_flat/v_flat: [L, B, T, C] -- the whole layer-stacked cache, passed
    scan-invariant; ``layer`` (traced scalar) selects which layer's
    blocks the BlockSpecs DMA.  This exists because a per-layer cache
    slice fed to ``pallas_call`` from inside the layer scan must
    MATERIALIZE (XLA fuses dynamic-slices into einsums but not into
    pallas calls, and the post-scan cache scatter keeps the stacked
    buffer live) -- measured ~0.3 ms/layer of hidden copy traffic at 8k
    on v5e, which erased the kernel's win.  Indexing the layer inside
    the grid spec reads the cache in place.  k_scale_t/v_scale_t:
    [L, B, K, T] f32 or None; lengths: [B].  T must be a multiple of
    block_t (block_t is shrunk to a divisor by the shared extent check
    -- padding a stacked cache would copy it).  ``qrow_period``: see
    :func:`flash_verify_append` (the [S*H]-row multi-query layout).
    """
    interpret = interpret_off_chip(interpret)
    quantized = k_scale_t is not None
    b, h, c = q_pad.shape
    t = k_flat.shape[2]
    n_kv = k_scale_t.shape[2] if quantized else None

    h_pad = _round_up(max(h, 8), 8)
    q_pad = _pad_to(q_pad, 1, h_pad)
    block_t = _fit_block(t, block_t, pad=False,
                         entry="flash_decode_attention_stacked")
    if not quantized:
        n_kv = 1
        k_scale_t = jnp.zeros((1, b, 1, t), dtype=jnp.float32)
        v_scale_t = jnp.zeros((1, b, 1, t), dtype=jnp.float32)

    grid = (b, t // block_t)
    compute_dtype = q_pad.dtype
    scale_layers = k_scale_t.shape[0]

    def _clamped(bi, ti, meta):
        last_live = jnp.maximum(pl.cdiv(meta[1 + bi], block_t) - 1, 0)
        return jnp.minimum(ti, last_live)

    def kv_block(bi, ti, meta):
        return (meta[0], bi, _clamped(bi, ti, meta), 0)

    def scale_block(bi, ti, meta):
        # Unquantized caches pass a [1, B, 1, T] dummy: clamp the layer
        # index so the spec never reads past it.
        return (jnp.minimum(meta[0], scale_layers - 1), bi, 0,
                _clamped(bi, ti, meta))

    kernel = functools.partial(
        _decode_kernel, block_t=block_t, n_heads=h_pad, n_kv=n_kv,
        groups=max((qrow_period or h) // n_kv, 1),
        compute_dtype=compute_dtype,
        quantized=quantized, layered=True, period=qrow_period)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h_pad, c), lambda bi, ti, meta: (bi, 0, 0)),
            pl.BlockSpec((1, 1, block_t, c), kv_block),
            pl.BlockSpec((1, 1, block_t, c), kv_block),
            pl.BlockSpec((1, 1, n_kv, block_t), scale_block),
            pl.BlockSpec((1, 1, n_kv, block_t), scale_block),
        ],
        out_specs=[
            pl.BlockSpec((1, h_pad, c), lambda bi, ti, meta: (bi, 0, 0)),
            pl.BlockSpec((1, h_pad, _STAT_LANES),
                         lambda bi, ti, meta: (bi, 0, 0)),
            pl.BlockSpec((1, h_pad, _STAT_LANES),
                         lambda bi, ti, meta: (bi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, c), jnp.float32),
        ],
    )
    meta = jnp.concatenate([
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.asarray(lengths, dtype=jnp.int32)])
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h_pad, c), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(meta, q_pad, k_flat, v_flat, k_scale_t, v_scale_t)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


#: VMEM the paged kernel's page buffers may take: K and V (and, for
#: int8 pools, their scale pages), two groups each -- the one being
#: folded and the one in flight.  ``_PAGED_MAX_PAGES`` bounds the
#: copies in flight (and the unrolled body) where pages are tiny;
#: ``_PAGED_VMEM_LIMIT`` is the call's scoped-VMEM limit: room for
#: those buffers beside the query rows, the accumulator and a page's
#: scores (the verify chunk's are S times a decode step's).
_PAGED_BUFFER_BYTES = 4 << 20
_PAGED_MAX_PAGES = 16
_PAGED_VMEM_LIMIT = 32 << 20


def paged_pages_per_step(view, pps: int) -> int:
    """How many logical pages the paged kernel copies and folds as one
    group: as many as the buffer budget holds twice over (four at
    [128, 1024] bf16 pages) -- derived from the static shapes alone
    (``view``: one pool side as :func:`_split_paged` returns it;
    ``pps``: pages a slot can hold).  1 where a single page already
    fills the budget."""
    payload, scale = view
    _, _, page_tokens, c = payload.shape
    page_bytes = page_tokens * c * payload.dtype.itemsize
    if scale is not None:
        page_bytes += scale.shape[2] * scale.shape[3] \
            * scale.dtype.itemsize
    fit = max(1, min(_PAGED_BUFFER_BYTES // (4 * page_bytes),
                     _PAGED_MAX_PAGES, pps))
    return -(-pps // -(-pps // fit))     # even groups, least padding


def paged_grid_steps(lengths, page_tokens: int, pps: int,
                     pages_per_step: int) -> tuple[int, int]:
    """On the host, from ``lengths`` [B] as the kernel is given them (0
    for a row that does not decode): (steps of the paged kernel that
    stream at least one live page, steps of the call), a step being one
    group of a row's pages -- the kernel loops over a row's LIVE groups
    only, so the steps that stream nothing are the grid steps of the
    rows with no page at all (``llm_decode_live_grid_share``)."""
    pages = -(-np.minimum(np.asarray(lengths), pps * page_tokens)
              // page_tokens)
    groups = -(-pages // pages_per_step)
    return int(groups.sum()), int(np.maximum(groups, 1).sum())


def _paged_kernel(layer_ref, lengths_ref, table_ref, q_ref, *refs,
                  page_tokens, pages, pps, n_heads, n_kv, groups,
                  compute_dtype, quantized, period, window=0):
    """One ROW of the paged form (grid ``(B,)``, walked in order).  The
    pools stay in HBM (``ANY`` space); the row loops over its LIVE page
    groups only -- ``pages`` consecutive logical pages a group -- and
    copies them itself, double-buffered: while group ``g`` is folded
    page by page into the online-softmax state (each page waited for
    just before its products, the op sequence of :func:`_decode_kernel`
    at ``block_t`` = one page), group ``g + 1`` is in flight -- at a
    row's last group, the next live row's first.  A dead page costs no
    copy, no product and no step; an inactive row (length 0) its grid
    step alone.

    refs: the pools (k, v[, k scales, v scales]); the outputs (acc, m,
    l); one ``[2, pages, ...]`` VMEM buffer a pool; DMA semaphores
    ``[pools, 2, pages]``; SMEM ``[slot to fold next, a first group is
    in flight]`` (carried across rows); the m, l, acc scratch."""
    sides = 4 if quantized else 2
    pools = refs[:sides]
    o_ref, m_ref, l_ref = refs[sides:sides + 3]
    bufs = refs[sides + 3:2 * sides + 3]
    sems, state, m_scr, l_scr, acc_scr = refs[2 * sides + 3:]
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    layer = layer_ref[0]

    def row_length(row):
        # (the table covers pps pages: the allocator's contract)
        return jnp.minimum(lengths_ref[row], pps * page_tokens)

    length = row_length(b)
    n_groups = pl.cdiv(length, page_tokens * pages)

    def row_start(row):
        # (the first position a row reads: 0 without a window)
        return jnp.maximum(row_length(row) + 1 - window, 0)

    def first_group(row):
        return row_start(row) // (page_tokens * pages) if window else 0

    def copies(row, logical, slot, index):
        physical = table_ref[row * pps + logical]
        return [pltpu.make_async_copy(
            pools[side].at[layer, physical], bufs[side].at[slot, index],
            sems.at[side, slot, index]) for side in range(sides)]

    def start_group(row, group, slot):
        limit = row_length(row)
        for index in range(pages):
            logical = group * pages + index
            wanted = logical * page_tokens < limit
            if window:
                wanted = jnp.logical_and(
                    wanted, (logical + 1) * page_tokens > row_start(row))

            @pl.when(wanted)
            def _start(index=index, logical=logical):
                for copy in copies(row, logical, slot, index):
                    copy.start()

    @pl.when(b == 0)
    def _first_row():
        state[0] = 0
        state[1] = 0

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(n_groups > 0, state[1] == 0))
    def _prime():
        # (no earlier row had a page: nobody prefetched this group)
        start_group(b, first_group(b), state[0])
        state[1] = 1

    shared = dict(n_heads=n_heads, n_kv=n_kv, groups=groups,
                  period=period, compute_dtype=compute_dtype,
                  quantized=quantized)

    def fold_group(group, carry):
        slot = state[0]
        other = 1 - slot

        @pl.when(group + 1 < n_groups)
        def _next_group():
            start_group(b, group + 1, other)

        @pl.when(group + 1 == n_groups)
        def _next_row():
            following = jax.lax.while_loop(
                lambda row: jnp.logical_and(
                    row < rows,
                    lengths_ref[jnp.minimum(row, rows - 1)] <= 0),
                lambda row: row + 1, b + 1)

            @pl.when(following < rows)
            def _():
                start_group(following, first_group(following), other)

        lo = row_start(b) if window else 0
        for index in range(pages):
            logical = group * pages + index
            t_start = logical * page_tokens
            live = t_start < length
            if window:
                live = jnp.logical_and(live, t_start + page_tokens > lo)

            @pl.when(live)
            def _live(index=index, logical=logical, t_start=t_start):
                for copy in copies(b, logical, slot, index):
                    copy.wait()

                def page(side):
                    if side >= sides:
                        return None
                    if side >= 2:       # a scale page, lane-padded
                        return bufs[side][slot, index, :, :page_tokens]
                    return bufs[side][slot, index]

                def scores():
                    return _scores_block(q_ref[0], page(0), page(2),
                                         **shared)
                interior = t_start + page_tokens <= length
                if window:
                    interior = jnp.logical_and(interior, t_start >= lo)

                @pl.when(interior)
                def _whole_page():
                    _online_update(m_scr, l_scr, acc_scr, scores(),
                                   page(1), page(3), **shared)

                @pl.when(jnp.logical_not(interior))
                def _last_page():
                    position = t_start + jax.lax.broadcasted_iota(
                        jnp.int32, (n_heads, page_tokens), 1)
                    mask = position < length
                    if window:
                        mask = jnp.logical_and(mask, position >= lo)
                    _online_update(m_scr, l_scr, acc_scr,
                                   jnp.where(mask, scores(), _NEG_INF),
                                   page(1), page(3), p_mask=mask,
                                   **shared)
        state[0] = other
        return carry

    jax.lax.fori_loop(first_group(b), n_groups, fold_group, 0)
    o_ref[0] = acc_scr[...]
    m_ref[0] = m_scr[...]
    l_ref[0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret", "qrow_period",
                                             "pages_per_step", "window"))
def flash_decode_attention_paged(q_pad, k_pool, v_pool, k_scale_t,
                                 v_scale_t, layer, page_table, lengths,
                                 *, interpret: bool | None = None,
                                 qrow_period: int | None = None,
                                 pages_per_step: int | None = None,
                                 window: int = 0):
    """:func:`flash_decode_attention` over ONE layer of a PAGED cache
    pool, the page table walked IN-KERNEL (ISSUE 11 tentpole).

    k_pool/v_pool: [L, P, pt, C] physical page pools (models/paged.py
    layout, layer-stacked and scan-invariant -- the same no-per-layer-
    slice discipline as the stacked kernel); k_scale_t/v_scale_t:
    [L, P, K, pt'] f32 per-page scale pools or None (int8 pools,
    dequantized in-kernel exactly like the flat kernel; ``pt'`` >= pt,
    padded to whole lane tiles: :func:`_split_paged`); ``layer``:
    traced scalar; page_table: [B, pps] int32 (entry 0 = the reserved
    trash page); lengths: [B] valid positions (0: the row reads
    nothing).

    The grid is (B,) and the pools never enter a BlockSpec pipeline:
    each row copies its own live pages HBM -> VMEM, a group of
    ``pages_per_step`` at a time and one group ahead
    (:func:`_paged_kernel`), the physical page read from the scalar-
    prefetched table -- so the pool is read in place, one page DMA per
    live logical page and nothing at all for a dead one (why not the
    pipeline: the module docstring).  No host-side ``gather_layer``
    materialization: the logical [B, T, C] row view never exists, which
    is exactly the 2x cache traffic the gather-attention paged path
    paid.  ``pages_per_step`` follows the
    pools' shapes (:func:`paged_pages_per_step`; the argument is for
    the tests and the probe): whatever it is, pages meet the float32
    statistics one by one, in order.  ``window`` > 0 (static) reads
    each row's last ``window - 1`` positions alone (a sliding-window
    layer's decode: :func:`_paged_kernel`); the pages before them need
    no entry in the table.  Returns the same partial (acc, m, l) stats
    as the flat kernel.
    """
    quantized = k_scale_t is not None
    b, h, c = q_pad.shape
    page_tokens = k_pool.shape[2]
    if page_tokens % 8:
        # Pages ARE the kernel's time blocks and the pool is never
        # padded (the stacked-cache discipline): a sublane-misaligned
        # page size would surface as an opaque Mosaic tiling error on
        # TPU, so refuse it by name on every backend -- the 'auto'
        # probe (ops.decode_backend) already steers such configs to
        # the reference path; only a forced request can reach here.
        raise ValueError(
            f"flash_decode_attention_paged: kv_page_tokens="
            f"{page_tokens} must be a multiple of 8 (one sublane "
            f"tile); use an aligned page size or the reference "
            f"gather path")
    pps = page_table.shape[1]
    pages = int(pages_per_step or paged_pages_per_step(
        (k_pool, k_scale_t), pps))
    n_kv = k_scale_t.shape[2] if quantized else 1

    h_pad = _round_up(max(h, 8), 8)
    q_pad = _pad_to(q_pad, 1, h_pad)

    def row(bi, *_):
        return (bi, 0, 0)

    kernel = functools.partial(
        _paged_kernel, page_tokens=page_tokens, pages=pages, pps=pps,
        n_heads=h_pad, n_kv=n_kv,
        groups=max((qrow_period or h) // n_kv, 1),
        compute_dtype=q_pad.dtype, quantized=quantized,
        period=qrow_period, **({"window": int(window)} if window else {}))

    # Scale pools ride as [L, P, K, pt] so the kernel's [K, Tb] block
    # matches the flat kernel's layout exactly.
    pools = [k_pool, v_pool] \
        + ([k_scale_t, v_scale_t] if quantized else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h_pad, c), row)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=[
            pl.BlockSpec((1, h_pad, c), row),
            pl.BlockSpec((1, h_pad, _STAT_LANES), row),
            pl.BlockSpec((1, h_pad, _STAT_LANES), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, pages) + pool.shape[2:], pool.dtype)
            for pool in pools] + [
            pltpu.SemaphoreType.DMA((len(pools), 2, pages)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, c), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h_pad, c), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PAGED_VMEM_LIMIT),
        # (the kernel copies by hand: off the chip it takes the TPU
        # interpreter, which models DMAs and semaphores)
        interpret=pltpu.InterpretParams()
        if interpret_off_chip(interpret) else False,
        name="flash_decode_attention_paged",
    )(jnp.asarray(layer, dtype=jnp.int32).reshape(1),
      jnp.asarray(lengths, dtype=jnp.int32),
      jnp.asarray(page_table, dtype=jnp.int32).reshape(-1), q_pad, *pools)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


def _split_paged(side):
    """One paged pool side (models/paged.py layout) -> ([L, P, pt, C]
    payload, [L, P, K, pt'] f32 scales or None).  Payloads are stored
    flat already; the scale transpose is a real copy, but of the small
    f32 scale pool, once per step -- the stacked-cache discipline.
    ``pt'`` is ``pt`` rounded up to a lane tile (128): the paged kernel
    copies a scale page by hand, and a copy out of HBM moves whole lane
    tiles (pages of 128 tokens and more are whole already)."""
    if is_quantized(side):
        return side["int8"], _pad_to(
            side["scale"][..., 0].transpose(0, 1, 3, 2)
            .astype(jnp.float32), 3, 128)
    return side, None


def _split_stacked(cache):
    """Stacked cache tree -> ([L, B, T, C] payload, [L, B, K, T] f32
    scales or None).  Payloads are stored flat already (llama
    init_cache); a grouped [L, B, T, K, hd] payload is collapsed (a
    contiguous-minor bitcast).  The scale transpose is a real copy, but
    of the small f32 scales, once per step."""
    if is_quantized(cache):
        payload = cache["int8"]
        scale = cache["scale"][..., 0].transpose(0, 1, 3, 2) \
            .astype(jnp.float32)
    else:
        payload, scale = cache, None
    if payload.ndim == 5:
        n_layers, b, t, kv, d = payload.shape
        payload = payload.reshape(n_layers, b, t, kv * d)
    return payload, scale


def _prep_query(q_flat, h: int, kv: int, d: int,
                period: int | None = None):
    """Scaled block-diagonal queries + (blocks, onehot) head maps.
    ``period`` maps multi-query row layouts ([S*H] verify rows) onto
    the repeating head pattern -- see :func:`_group_onehot`."""
    scale = d ** -0.5
    blocks = (jnp.arange(h) % (period or h)) \
        // ((period or h) // kv)                          # [H] kv head
    onehot = _group_onehot(h, kv, q_flat.dtype,
                           period=period)                 # [H, K]
    # Fold the softmax scale into the padded queries -- lossless when
    # d**-0.5 is a power of two (d = 64), otherwise folded in f32 and
    # rounded once (same rounding the dense path's f32 product takes).
    q_scaled = (q_flat.astype(jnp.float32) * scale).astype(q_flat.dtype) \
        if math.log2(scale).is_integer() \
        else (q_flat.astype(jnp.float32) * scale)
    q_pad = jnp.einsum("bhd,hk->bhkd", q_scaled,
                       onehot.astype(q_scaled.dtype)) \
        .reshape(q_flat.shape[0], h, kv * d)
    return q_pad, blocks, onehot, scale


def _combine_self(acc, m, l, q_flat, k_new, v_new, blocks, onehot,
                  scale, kv: int, d: int):
    """Merge the current token's self-attention term with the kernel's
    partial stats (exact two-part softmax combine, mirroring the dense
    path's cache/self split).  Returns [B, H, hd] f32."""
    b, h = q_flat.shape[:2]
    k_new_h = k_new[:, 0][:, blocks, :]                   # [B, H, hd]
    v_new_h = v_new[:, 0][:, blocks, :]
    self_logits = (q_flat.astype(jnp.float32)
                   * k_new_h.astype(jnp.float32)).sum(-1) * scale
    m_joint = jnp.maximum(m, self_logits)
    correction = jnp.where(m <= _NEG_INF / 2, 0.0,
                           jnp.exp(m - m_joint))          # [B, H]
    self_weight = jnp.exp(self_logits - m_joint)
    denominator = l * correction + self_weight
    # Select each head's own kv block out of the fused accumulator.
    cache_part = jnp.einsum(
        "bhkd,hk->bhd", acc.reshape(b, h, kv, d),
        onehot.astype(jnp.float32))                       # [B, H, hd]
    return (cache_part * correction[:, :, None]
            + self_weight[:, :, None] * v_new_h.astype(jnp.float32)) \
        / denominator[:, :, None]


def flash_decode_append(q, k_cache, v_cache, k_new, v_new, lengths, *,
                        block_t: int = 2048,
                        interpret: bool | None = None):
    """Drop-in replacement for
    :func:`~aiko_services_tpu.ops.layers.attention_decode_append`
    (same signature and semantics) built on the split-K kernel.

    q: [B, 1, H, hd]; k_cache/v_cache: [B, T, K, hd] grouped caches --
    raw bf16 arrays or int8-quantized layers (``{"int8", "scale"}``,
    dequantized IN KERNEL, see module docstring); k_new/v_new:
    [B, 1, K, hd] the current token's raw k/v (not yet written);
    lengths: [B] valid cache positions.  Returns [B, 1, H, hd].

    Inside a layer scan whose stacked cache is later scatter-updated,
    use :func:`flash_decode_append_stacked` instead -- feeding this
    function a scan slice materializes a per-layer cache copy.
    """
    b, _, h, d = q.shape
    _require_matched_quantization(is_quantized(k_cache),
                                  is_quantized(v_cache),
                                  "flash_decode_append")
    if is_quantized(k_cache):
        k_payload = k_cache["int8"]
        k_scale_t = k_cache["scale"][..., 0].transpose(0, 2, 1) \
            .astype(jnp.float32)                          # [B, K, T]
    else:
        k_payload, k_scale_t = k_cache, None
    if is_quantized(v_cache):
        v_payload = v_cache["int8"]
        v_scale_t = v_cache["scale"][..., 0].transpose(0, 2, 1) \
            .astype(jnp.float32)
    else:
        v_payload, v_scale_t = v_cache, None
    t, kv = k_payload.shape[1], k_payload.shape[2]
    c = kv * d

    q_flat = q[:, 0]                                      # [B, H, hd]
    q_pad, blocks, onehot, scale = _prep_query(q_flat, h, kv, d)
    acc, m, l = flash_decode_attention(
        q_pad, k_payload.reshape(b, t, c), v_payload.reshape(b, t, c),
        k_scale_t, v_scale_t, lengths,
        block_t=block_t, interpret=interpret)
    out = _combine_self(acc, m, l, q_flat, k_new, v_new, blocks,
                        onehot, scale, kv, d)
    return out.reshape(q.shape).astype(q.dtype)


def flash_decode_append_stacked(q, k_view, v_view, layer, k_new, v_new,
                                lengths, *, block_t: int = 2048,
                                interpret: bool | None = None):
    """Layer-scan form of :func:`flash_decode_append`: the cache stays
    STACKED and scan-invariant ([L, B, T, C] payload views +
    [L, B, K, T] scales from :func:`_split_stacked`), and the traced
    ``layer`` scalar picks the layer inside the kernel's BlockSpecs --
    no per-layer slice buffer, no hidden cache copy (see
    flash_decode_attention_stacked).  q/k_new/v_new/lengths as in
    flash_decode_append."""
    b, _, h, d = q.shape
    k_payload, k_scale_t = k_view
    v_payload, v_scale_t = v_view
    _require_matched_quantization(k_scale_t is not None,
                                  v_scale_t is not None,
                                  "flash_decode_append_stacked")
    kv = k_payload.shape[3] // d

    q_flat = q[:, 0]
    q_pad, blocks, onehot, scale = _prep_query(q_flat, h, kv, d)
    acc, m, l = flash_decode_attention_stacked(
        q_pad, k_payload, v_payload, k_scale_t, v_scale_t, layer,
        lengths, block_t=block_t, interpret=interpret)
    out = _combine_self(acc, m, l, q_flat, k_new, v_new, blocks,
                        onehot, scale, kv, d)
    return out.reshape(q.shape).astype(q.dtype)


def flash_decode_append_paged(q, k_view, v_view, layer, k_new, v_new,
                              page_table, lengths, *,
                              interpret: bool | None = None,
                              window: int = 0):
    """Paged twin of :func:`flash_decode_append_stacked`: the cache
    stays its PHYSICAL page pools ([L, P, pt, C] payload views +
    [L, P, K, pt] scales from :func:`_split_paged`, scan-invariant) and
    the kernel resolves each slot's pages from the [B, pps] table
    itself -- no host-side gather, no logical-row materialization.  The
    stacked-cache invariant differs here: the POOL extent never has to
    divide a block size (pages ARE the blocks), but the table must
    cover the logical extent the lengths claim -- the allocator's
    ``ensure`` contract -- from the window's first page on, with a
    ``window`` (:func:`flash_decode_attention_paged`).  q/k_new/v_new/
    lengths as in flash_decode_append."""
    b, _, h, d = q.shape
    k_payload, k_scale_t = k_view
    v_payload, v_scale_t = v_view
    _require_matched_quantization(k_scale_t is not None,
                                  v_scale_t is not None,
                                  "flash_decode_append_paged")
    kv = k_payload.shape[3] // d

    q_flat = q[:, 0]
    q_pad, blocks, onehot, scale = _prep_query(q_flat, h, kv, d)
    acc, m, l = flash_decode_attention_paged(
        q_pad, k_payload, v_payload, k_scale_t, v_scale_t, layer,
        page_table, lengths, interpret=interpret,
        **({"window": int(window)} if window else {}))
    out = _combine_self(acc, m, l, q_flat, k_new, v_new, blocks,
                        onehot, scale, kv, d)
    return out.reshape(q.shape).astype(q.dtype)


def _combine_chunk(acc, m, l, q, k_new, v_new, positions, scale,
                   kv: int, d: int, block_mask: bool = False):
    """Merge the verify chunk's own keys/values (the causal self part)
    with the kernel's cache-part stats -- the S-query generalization of
    :func:`_combine_self`.  acc [B, S*H, C], m/l [B, S*H]; q [B,S,H,hd]
    rope'd unscaled queries; k_new/v_new [B,S,K,hd]; positions [B,S]
    trash-clamped absolute positions (causality among chunk keys is
    ``key_pos <= query_pos``, exactly the dense concat path's mask).
    ``block_mask``: the chunk is ONE block of a block-causal mask, all
    of it visible to each of its queries, in both directions
    (models/sdar.py).  Returns [B, S, H, hd] f32."""
    b, s, h, _ = q.shape
    blocks = jnp.arange(h) // (h // kv)
    onehot = _group_onehot(h, kv, jnp.float32)               # [H, K]
    k_new_h = k_new[:, :, blocks, :].astype(jnp.float32)     # [B,S,H,hd]
    v_new_h = v_new[:, :, blocks, :].astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    chunk_logits = jnp.einsum("bshd,bthd->bsht", q32,
                              k_new_h) * scale               # [B,S,H,S]
    if block_mask:
        causal = jnp.ones((b, s, 1, s), dtype=bool)
    else:
        causal = positions[:, None, None, :] <= \
            positions[:, :, None, None]                      # [B,S,1,S]
    chunk_logits = jnp.where(causal, chunk_logits, _NEG_INF)
    m_k = m.reshape(b, s, h)
    l_k = l.reshape(b, s, h)
    m_joint = jnp.maximum(m_k, chunk_logits.max(-1))
    correction = jnp.where(m_k <= _NEG_INF / 2, 0.0,
                           jnp.exp(m_k - m_joint))           # [B,S,H]
    weights = jnp.where(causal,
                        jnp.exp(chunk_logits - m_joint[..., None]), 0.0)
    denominator = l_k * correction + weights.sum(-1)
    cache_part = jnp.einsum("bshkd,hk->bshd",
                            acc.reshape(b, s, h, kv, d), onehot)
    chunk_part = jnp.einsum("bsht,bthd->bshd", weights, v_new_h)
    return (cache_part * correction[..., None] + chunk_part) \
        / denominator[..., None]


def flash_verify_append(q, k_view, v_view, layer, k_new, v_new, starts,
                        positions, *, page_table=None,
                        block_t: int = 2048,
                        interpret: bool | None = None,
                        block_mask: bool = False):
    """Batched chunk-verify attention on the split-K kernels (ISSUE 11):
    the speculative multi-token target step's concat-attention with the
    cache read ONCE for all S draft positions -- not once per drafted
    token, and with no [B, H, S, T] HBM logits.

    All S queries of a row share one cache validity frontier
    (``t < starts[b]``: chunk causality over cache rows is implied by
    ``starts <= positions``), so the cache part IS the decode kernel
    with ``lengths = starts`` and the row axis carrying all S*H query
    rows block-diagonally (``qrow_period`` tiles the GQA head map
    every H rows).  The chunk's own k/v are the self part, combined
    outside with causal masking by the trash-clamped ``positions`` --
    the exact semantics of the dense concat path in
    ``models/llama.py:_chunk_verify``.  ``block_mask``: the chunk is one
    block of a block-causal mask instead (:func:`_combine_chunk`); the
    cache part is the same.

    q: [B, S, H, hd] rope'd queries; k_view/v_view: stacked cache views
    (:func:`_split_stacked`) or paged pool views (:func:`_split_paged`,
    with ``page_table`` [B, pps]); k_new/v_new: [B, S, K, hd] the
    chunk's rope'd k/v (not yet written); starts: [B]; positions:
    [B, S].  Returns [B, S, H, hd] in q's dtype.
    """
    b, s, h, d = q.shape
    k_payload, k_scale_t = k_view
    v_payload, v_scale_t = v_view
    _require_matched_quantization(k_scale_t is not None,
                                  v_scale_t is not None,
                                  "flash_verify_append")
    kv = k_payload.shape[3] // d
    q_rows = q.reshape(b, s * h, d)
    q_pad, _, _, scale = _prep_query(q_rows, s * h, kv, d, period=h)
    if page_table is not None:
        acc, m, l = flash_decode_attention_paged(
            q_pad, k_payload, v_payload, k_scale_t, v_scale_t, layer,
            page_table, starts, interpret=interpret, qrow_period=h)
    else:
        acc, m, l = flash_decode_attention_stacked(
            q_pad, k_payload, v_payload, k_scale_t, v_scale_t, layer,
            starts, block_t=block_t, interpret=interpret,
            qrow_period=h)
    out = _combine_chunk(acc, m, l, q, k_new, v_new, positions, scale,
                         kv, d, block_mask=block_mask)
    return out.astype(q.dtype)
