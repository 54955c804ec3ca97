"""Flash attention forward as a Pallas TPU kernel.

Blockwise causal attention with online softmax -- the same math as
``parallel.ring.blockwise_attention`` but scheduled by hand for the TPU
memory hierarchy: Q/K/V tiles staged HBM->VMEM by the BlockSpec pipeline,
S = Q.K^T on the MXU in float32, softmax statistics kept in VMEM scratch
that persists across the KV grid axis, one output tile written on the
last KV step.  GQA: each grid row is a KV head carrying its whole query
group's rows, so K/V tiles are fetched once per group (not once per
query head) and never materialized repeated.

Off the TPU the kernel runs in interpret mode when asked for by name
(``attention: flash``), so tier-1 checks the kernel body on the CPU
mesh; Mosaic itself -- VMEM limit, tile alignment -- is checked on the
chip by ``chip_smoke.py`` (``interpret=False``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .tiles import (interpret_off_chip, pad_to as _pad_to,
                    round_up as _round_up)

__all__ = ["flash_attention"]

#: kernel entry -> its tier-1 equivalence test (see the ``kernel-test``
#: selfcheck rule; the test runs interpret mode on the CPU mesh).
KERNEL_EQUIVALENCE_TESTS = {
    "flash_attention":
        "test_pallas_attention.py::test_flash_matches_dense",
}

_NEG_INF = -1e30
_STAT_LANES = 128      # softmax stats replicated across the lane dim


def _flash_kernel(offset_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  block_q, block_k, causal, kv_len, rows_per_head,
                  scale, frontier):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)
    # Rows are [group0 positions..., group1 positions, ...] per KV head
    # (GQA: all of a KV head's query heads share one grid row, so K/V
    # tiles are DMA'd once per group, not once per query head).  A q
    # block never straddles groups (rows_per_head % block_q == 0), so
    # the block's first POSITION is its row offset within its group.
    q_start = offset_ref[0] + (qi * block_q) % rows_per_head
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: skip KV blocks strictly above this Q block's last row
    # (``frontier(p)``: the last key position a query at ``p`` sees).
    live = (k_start <= frontier(q_start + block_q - 1)) if causal \
        else True
    # Interior blocks need NO masking: every key position is both
    # in-range and at-or-before every query position.  The mask path
    # (2 iotas + compares + 2 wheres on [bq, bk] f32) costs about as
    # much VPU time as the exp itself, and on a long prompt nearly all
    # blocks are interior -- splitting the paths roughly halves the
    # non-matmul work (the splash-attention trick).
    in_range = k_start + block_k <= kv_len
    interior = jnp.logical_and(
        in_range,
        (k_start + block_k - 1 <= frontier(q_start)) if causal else True)

    def _online_update(s, p_mask=None):
        m_prev = m_scr[:, :1]                           # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        # exp in bf16: the PV matmul consumes bf16 weights anyway and
        # the l-sum accumulates in f32, so the only cost is ~0.4%
        # relative error on individual softmax weights -- the same
        # order as the bf16 rounding of V itself -- while the [bq, bk]
        # transcendental (the largest VPU item in the loop) runs at
        # twice the f32 rate and the separate cast disappears.
        p = jnp.exp((s - m_safe).astype(v_ref.dtype))
        if p_mask is not None:
            p = jnp.where(p_mask, p, jnp.zeros_like(p))
        correction = jnp.exp(m_prev - m_safe)
        l_scr[...] = jnp.broadcast_to(
            l_prev * correction
            + jnp.sum(p, axis=1, keepdims=True, dtype=jnp.float32),
            l_scr.shape)
        pv = jax.lax.dot_general(
            p, v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, d]
        acc_scr[...] = acc_scr[...] * correction + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def _scores():
        # scale is None when the caller folded it into q losslessly
        # (d**-0.5 a power of two); otherwise applied to the f32
        # scores here (trace-time branch, no kernel cost when None).
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        return s if scale is None else s * scale

    @pl.when(jnp.logical_and(live, interior))
    def _compute_interior():
        _online_update(_scores())

    @pl.when(jnp.logical_and(live, jnp.logical_not(interior)))
    def _compute_boundary():
        s = _scores()
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= frontier(q_pos))
        _online_update(jnp.where(mask, s, _NEG_INF), p_mask=mask)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _frontier(block_length: int):
    """``position -> the last key position a query there sees``: itself
    (causal), or under a BLOCK-causal mask of ``block_length`` the last
    position of its own block -- a query sees whole earlier blocks and
    its own block in both directions.  At 1 the expression is the
    causal one to the letter (the callers that hand no block length
    keep their programs)."""
    if block_length == 1:
        return lambda position: position
    return lambda position: \
        (position // block_length + 1) * block_length - 1


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret", "pack_heads",
    "block_length"))
def flash_attention(q, k, v, q_offset=0, *, causal: bool = True,
                    block_q: int = 512, block_k: int = 2048,
                    interpret: bool | None = None,
                    pack_heads: bool = False, block_length: int = 1):
    """Causal flash attention.

    ``block_length`` > 1 makes the mask BLOCK-causal at absolute
    positions: the frontier of a query at ``p`` is ``k < (p //
    block_length + 1) * block_length`` (generation by diffusion over
    blocks, models/sdar.py); 1 is plain causality.

    q: [B, S, H, d]; k/v: [B, T, Hkv, d] with H % Hkv == 0 (GQA: each
    query head attends its group's KV head via the grouped grid rows,
    no repeat materialized).  ``q_offset`` is the absolute position of q
    row 0 (chunked prefill against a longer KV); it is a traced scalar,
    so sweeping offsets does not recompile.  Returns [B, S, H, d] in
    q's dtype; scores and softmax statistics (max/sum/correction) in
    float32, individual weights exponentiated in the value dtype (bf16
    for bf16 inputs -- ~0.4% per-weight, the same order as V's own
    rounding; see _online_update).

    Default blocks (512 x 2048) are tuned on v5e at head_dim 64 / 8k
    context -- a sweep with 600-iteration amortized min-of-3
    timing: 30.3% of chip peak at 512x2048 vs 26.0% at the old 512x1024
    default, 29.4% at 1024x1024, 15.8% at 512x512; non-power-of-two and
    larger-k blocks all lose (640x2048 23.8%, 768x2048 26.6%, 896x2048
    22.9%, 512x3072 23.4%); 1024x2048 exceeds VMEM (the f32 [block_q,
    block_k] score tile is the binding constraint: 512x2048x4 B = 4 MB
    fits, 8 MB does not).  Earlier
    claims of ~41% did not reproduce under this methodology and were
    revised down (28-30%).  The non-matmul gap is VPU softmax work, cut by the interior/boundary split (most blocks skip masking
    entirely), the bf16 exp, and folding the scale into q; the d=64
    contraction half-feeds the 128-wide MXU, putting the practical
    ceiling near 50%.

    ``pack_heads`` pairs two kv heads per grid row with block-diagonal
    queries, filling the 128-wide MXU dimension that a d=64 contraction
    leaves half-idle in BOTH kernel matmuls.  MEASURED on v5e: slightly
    SLOWER than unpacked (37.7% vs 40.9% of peak, same methodology) --
    the MXU pipelines 64-deep contractions without stalling, so packing
    only adds output-width traffic.  Kept as an option because the
    arithmetic is exact (tested) and other TPU generations may trade
    differently.
    """
    interpret = interpret_off_chip(interpret)
    b, s, h, d = q.shape
    t, h_kv = k.shape[1], k.shape[2]
    groups = h // h_kv
    if pack_heads and (h_kv % 2 or d > 64):
        pack_heads = False            # needs paired kv heads, d <= 64

    # Blocks clamp to the (padded) sequence but stay sublane-aligned.
    block_q = min(block_q, _round_up(max(s, 8), 8))
    block_k = min(block_k, _round_up(max(t, 8), 8))

    # Grid rows are (batch x KV head); each row stacks its whole GQA
    # group's queries as [G * S_pad, d] (padded per head so a q block
    # never straddles groups).  K/V tiles are then fetched once per
    # group instead of once per query head -- at G=4 that's 4x less KV
    # HBM traffic, which dominates long-context prefill.
    rows_per_head = _round_up(max(s, 8), block_q)
    q4 = _pad_to(q.transpose(0, 2, 1, 3), 2, rows_per_head)  # [B,H,S',d]
    if pack_heads:
        # Cross-head packing at head_dim 64: both kernel matmuls leave
        # half the 128-wide MXU dimension idle (QK contracts over d=64;
        # PV writes d=64-wide output).  Pack PAIRS of kv heads into one
        # grid row: queries go block-diagonal ([q | 0] rows for the
        # pair's first member, [0 | q] for the second) against the
        # pair's keys/values concatenated along d ([k_a | k_b]) -- the
        # zero halves kill the cross terms, the contraction becomes
        # 2d = 128, PV's output width becomes 128, and the grid has
        # half the rows at identical total DMA.  The kernel itself is
        # unchanged: it just sees d' = 2d and twice the head blocks
        # per row (rows_per_head periodicity still holds).
        sp = q4.shape[2]
        q6 = q4.reshape(b, h_kv // 2, 2, groups, sp, d)
        member0 = jnp.pad(q6[:, :, 0], ((0, 0),) * 4 + ((0, d),))
        member1 = jnp.pad(q6[:, :, 1], ((0, 0),) * 4 + ((d, 0),))
        q_r = jnp.stack([member0, member1], axis=2).reshape(
            b * (h_kv // 2), 2 * groups * sp, 2 * d)

        def pack_kv(x):                           # [B,T,K,d] -> paired
            x5 = x.transpose(0, 2, 1, 3).reshape(b, h_kv // 2, 2, t, d)
            x5 = x5.transpose(0, 1, 3, 2, 4)      # [B,K/2,T,2,d]
            return x5.reshape(b * (h_kv // 2), t, 2 * d)
        k_r = _pad_to(pack_kv(k), 1, block_k)
        v_r = _pad_to(pack_kv(v), 1, block_k)
        grid_rows = b * (h_kv // 2)
    else:
        q_r = q4.reshape(b * h_kv, groups * rows_per_head, d)
        k_r = _pad_to(k.transpose(0, 2, 1, 3).reshape(b * h_kv, t, d),
                      1, block_k)
        v_r = _pad_to(v.transpose(0, 2, 1, 3).reshape(b * h_kv, t, d),
                      1, block_k)
        grid_rows = b * h_kv
    rows_pad, t_pad = q_r.shape[1], k_r.shape[1]
    d_kernel = q_r.shape[2]

    # Fold the softmax scale into q when that is LOSSLESS in q's dtype
    # (d**-0.5 an exact power of two, e.g. 1/8 at d = 64) -- saving a
    # [bq, bk] VPU multiply per block; otherwise (d = 128: 2^-3.5) the
    # kernel scales the f32 scores as before.
    scale = d ** -0.5
    if math.log2(scale).is_integer():
        q_r = (q_r.astype(jnp.float32) * scale).astype(q_r.dtype)
        scale = None

    grid = (grid_rows, rows_pad // block_q, t_pad // block_k)
    frontier = _frontier(int(block_length))
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        causal=causal, kv_len=t, rows_per_head=rows_per_head,
        scale=scale, frontier=frontier)

    def kv_block(bh, qi, ki, offset):
        # Clamp dead KV blocks (fully above the causal frontier) to the
        # last live one: pl.when only skips COMPUTE, but a repeated
        # block index skips the HBM->VMEM DMA too -- early chunks of a
        # long prompt otherwise fetch the whole (mostly unwritten) KV
        # extent every layer.
        if not causal:
            return (bh, ki, 0)
        q_last = frontier(offset[0] + (qi * block_q) % rows_per_head
                          + block_q - 1)
        return (bh, jnp.minimum(ki, q_last // block_k), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_kernel),
                         lambda bh, qi, ki, offset: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d_kernel), kv_block),
            pl.BlockSpec((1, block_k, d_kernel), kv_block),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_kernel),
                               lambda bh, qi, ki, offset: (bh, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
            pltpu.VMEM((block_q, d_kernel), jnp.float32),
        ],
    )
    offset = jnp.asarray([q_offset], dtype=jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((grid_rows, rows_pad, d_kernel),
                                       q.dtype),
        interpret=interpret,
    )(offset, q_r, k_r, v_r)

    if pack_heads:
        # [B*K/2, 2*G*S', 2d]: member 0's rows hold their result in the
        # first d lanes, member 1's in the last d (the other half is the
        # partner head's weighted values -- discarded).  Selected with a
        # broadcast where rather than a stack of the two sliced lane
        # halves: XLA:TPU returns WRONG DATA for the sliced-stack form
        # of this pure data movement (re-checked on the local v5e
        # backend, jax 0.9.0 / libtpu 0.0.34, PR 21: under jit it
        # differs from the same expression in numpy, where the
        # where-select is exact; the CPU backend gets both right).
        out = out.reshape(b, h_kv // 2, 2, groups, rows_per_head, 2 * d)
        member = jax.lax.broadcasted_iota(jnp.int32, out.shape[:5] + (1,),
                                          2)
        out = jnp.where(member == 0, out[..., :d], out[..., d:])
        out = out.reshape(b, h_kv, groups, rows_per_head, d)[:, :, :, :s]
        return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    # [B*Hkv, G*S', d] -> [B, Hkv, G, S', d] -> [B, S, H, d]
    # (head h = kv*G + g, matching the q reshape above).
    out = out.reshape(b, h_kv, groups, rows_per_head, d)[:, :, :, :s]
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
