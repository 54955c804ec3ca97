"""Decode attention over LATENT pages as a Pallas TPU kernel (the
deepseek_v3 family's decode path, models/deepseek.py).

A latent cache (MLA) holds one row a token -- ``[c~; k_rope]``,
``kv_lora_rank + qk_rope_head_dim`` values -- shared by every head: one
KV "head" whose key is the whole row and whose value is its first
``kv_lora_rank`` lanes.  With the key projection absorbed into the
query, decode reads each live row ONCE for both products:

    s[h, t]   = q^[h, :] . row[t, :]            (scores, width W)
    acc[h, :] += p[h, t] * row[t, :]            (values: the same row)

The pool is ``[L, P, W, pt]`` (models/paged.py: a page's tokens along
its last axis, the v5e's own layout for a width that is no multiple of
128 lanes), so a page is a ``[W, pt]`` tile as it lies: the score
product is ``q [H, W] @ page [W, pt]`` and the value product contracts
the token axis of both operands.  The ``[B, pps]`` page table is
scalar-prefetched and walked inside the BlockSpec index maps (as
``flash_decode_attention_paged`` did until PR 30; that kernel now
copies its live pages by hand, a form that fits this one as it
stands: PERF.md section 7): the logical view the
gather path materialises -- every slot's whole extent, 302 MB a layer
at 32 slots x 8192 -- never exists, and a row of ``length`` tokens
reads ``ceil(length / pt)`` pages.

One grid step takes ``pages_per_step`` pages (the pool is handed over
that many times, each operand with its own index map): a 128-token
page of 576 bf16 lanes is 147 kB, and per-step overhead dominates a
grid of such steps.  Pages past a row's last live one clamp to it (the
repeated block index skips the DMA) and are masked.

Returns UNNORMALISED partial softmax statistics ``(acc [B, H, W] f32,
m [B, H], l [B, H])``: the caller merges the current token's own row,
which is not yet in the cache (the split ``ops/pallas_decode.py``
uses), and keeps ``acc[..., :kv_lora_rank]``.

Off the TPU the kernel runs in interpret mode when asked for by name
(``decode_attention: flash``); ``auto`` never routes here off the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import interpret_off_chip, pad_to as _pad_to, \
    round_up as _round_up

__all__ = ["latent_decode_attention_paged"]

#: kernel entry -> its tier-1 equivalence test (the ``kernel-test``
#: selfcheck rule; the test runs interpret mode on the CPU mesh).
KERNEL_EQUIVALENCE_TESTS = {
    "latent_decode_attention_paged":
        "test_deepseek.py::test_latent_decode_kernel_matches_dense",
}

_NEG_INF = -1e30
_STAT_LANES = 128


def _latent_kernel(meta_ref, q_ref, *refs, page_tokens, pages_per_step,
                   scale):
    """meta_ref: ``[layer, lengths[B], table.ravel()]`` (the table is
    read by the index maps only).  refs: ``pages_per_step`` page tiles
    ``[1, 1, W, pt]``, then the outputs (acc, m, l) and the scratch
    (m, l, acc)."""
    pages = refs[:pages_per_step]
    o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs[pages_per_step:]
    b = pl.program_id(0)
    gi = pl.program_id(1)
    length = meta_ref[1 + b]
    step_tokens = page_tokens * pages_per_step
    t_start = gi * step_tokens

    @pl.when(gi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(t_start < length)
    def _compute():
        query = q_ref[0]                                    # [H, W]
        heads = query.shape[0]
        scores = []
        for index, page in enumerate(pages):
            s = jax.lax.dot_general(
                query, page[0, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [H, pt]
            t_pos = t_start + index * page_tokens \
                + jax.lax.broadcasted_iota(jnp.int32,
                                           (heads, page_tokens), 1)
            scores.append(jnp.where(t_pos < length, s, _NEG_INF))
        m_prev = m_scr[:, :1]                               # [H, 1]
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        # (the step's first token is live, so m_new is a real score)
        correction = jnp.exp(m_prev - m_new)
        total = l_scr[:, :1] * correction
        acc = acc_scr[...] * correction
        for s, page in zip(scores, pages):
            p = jnp.exp(s - m_new)                          # masked: 0
            total = total + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                p.astype(query.dtype), page[0, 0],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [H, W]
        acc_scr[...] = acc
        l_scr[...] = jnp.broadcast_to(total, l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(gi == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


def latent_decode_attention_paged(query, pool, layer, page_table, lengths,
                                  *, scale: float,
                                  pages_per_step: int = 4,
                                  interpret: bool | None = None):
    """Absorbed decode attention over ONE layer of a latent page pool.

    query: ``[B, H, W]`` (``[W_kvb^K^T q_nope; q_rope]``); pool: ``[L,
    P, W, pt]``; ``layer``: traced scalar; page_table: ``[B, pps]``
    int32 (entry 0 = the trash page); lengths: ``[B]`` live tokens (0:
    the row reads nothing and returns ``m = -1e30, l = 0``); ``scale``
    multiplies the float32 scores.  Returns ``(acc [B, H, W], m [B, H],
    l [B, H])``, unnormalised (module docstring)."""
    interpret = interpret_off_chip(interpret)
    b, h, width = query.shape
    page_tokens = pool.shape[3]
    pps = page_table.shape[1]
    if pool.shape[2] != width:
        raise ValueError(
            f"latent_decode_attention_paged: query width {width} "
            f"against a pool of width {pool.shape[2]}")
    if page_tokens % 128 or width % 8:
        raise ValueError(
            f"latent_decode_attention_paged: kv_page_tokens="
            f"{page_tokens} must be a multiple of 128 (a page's tokens "
            f"are the lanes of its tile) and the width {width} of 8")
    pages_per_step = max(1, min(int(pages_per_step), pps))
    while pps % pages_per_step:
        pages_per_step -= 1
    h_pad = _round_up(max(h, 8), 8)
    query = _pad_to(query, 1, h_pad)

    def page_block(index):
        def block(bi, gi, meta):
            # Clamp dead logical pages to the row's last live one (the
            # repeated physical index skips the DMA; the body masks
            # them), then logical -> physical through the table.
            last_live = jnp.maximum(
                pl.cdiv(meta[1 + bi], page_tokens) - 1, 0)
            logical = jnp.minimum(gi * pages_per_step + index, last_live)
            return (meta[0], meta[1 + b + bi * pps + logical], 0, 0)
        return block

    kernel = functools.partial(
        _latent_kernel, page_tokens=page_tokens,
        pages_per_step=pages_per_step, scale=float(scale))
    row = lambda bi, gi, meta: (bi, 0, 0)               # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, pps // pages_per_step),
        in_specs=[pl.BlockSpec((1, h_pad, width), row)] + [
            pl.BlockSpec((1, 1, width, page_tokens), page_block(index))
            for index in range(pages_per_step)],
        out_specs=[
            pl.BlockSpec((1, h_pad, width), row),
            pl.BlockSpec((1, h_pad, _STAT_LANES), row),
            pl.BlockSpec((1, h_pad, _STAT_LANES), row)],
        scratch_shapes=[
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, _STAT_LANES), jnp.float32),
            pltpu.VMEM((h_pad, width), jnp.float32)],
    )
    meta = jnp.concatenate([
        jnp.asarray(layer, dtype=jnp.int32).reshape(1),
        jnp.asarray(lengths, dtype=jnp.int32),
        jnp.asarray(page_table, dtype=jnp.int32).reshape(-1)])
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h_pad, width), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h_pad, _STAT_LANES), jnp.float32)],
        interpret=interpret,
        name="latent_decode_attention_paged",
    )(meta, query, *([pool] * pages_per_step))
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]
