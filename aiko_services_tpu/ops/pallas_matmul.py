"""Fused int8 dequant-matmul as a Pallas TPU kernel (ISSUE 11).

The weight-only-int8 serving path (models/quant.py) computes
``(x @ w_int8.astype(x.dtype)) * scale`` -- XLA fuses the cast into the
dot's operand load, but the per-output-channel SCALE lands as a
separate HLO multiplying the full [M, F] product after an intermediate
write.  Here the whole thing is one kernel: int8 weight tiles stream
HBM->VMEM (half the bf16 bytes -- the entire point of weight-only int8
on a bandwidth-bound decode step), the cast rides the MXU operand
feed, partial products accumulate in an f32 VMEM scratch across the
contraction grid axis, and the scale folds into the FINAL store -- the
dequantized weight tensor and the unscaled product never exist in HBM.

Wired behind :func:`aiko_services_tpu.ops.matmul_backend`: the llama
unembed projection (``models/llama.py:_finish`` -- the single largest
serving matmul, and scan-invariant, so no per-layer slice materializes
in front of the pallas call) dispatches here for quantized trees,
which also covers the int8 self-draft decode steps of speculative
serving.  Off the TPU the kernel runs in interpret mode for the
equivalence tests (asked for by name); ``matmul_backend("auto")`` keeps
XLA's fused path there.

The blocks follow the weight (:func:`matmul_blocks`), so the head goes
to the ``pallas_call`` as the array the parameter tree holds.  Until
ISSUE 34 a width that was no multiple of ``block_f`` = 512 -- InternLM2's
92,544 = 128 x 723, llama3's 128,256 = 128 x 1,002 -- was padded inside
the jitted call, and XLA did not hoist the pad out of the decode loop:
every step copied the whole int8 head (190 MB read, 190 MB written at
2048 x 92,544: 0.56 ms of a 5.99 ms step on the v5e, beside 0.30 ms for
the kernel itself; PERF.md section 6), and every admission chunk cut the
padding off a ``[512, 92672]`` product again.  Now a width of whole
128-lane tiles is never padded; only a shape no block fits still is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .tiles import (interpret_off_chip, pad_to as _pad_to,
                    round_up as _round_up)

__all__ = ["int8_matmul", "matmul_blocks"]

#: kernel entry -> its tier-1 equivalence test (see the ``kernel-test``
#: selfcheck rule; the test forces ``interpret=True`` on the CPU mesh).
KERNEL_EQUIVALENCE_TESTS = {
    "int8_matmul": "test_kernel_plane.py::test_int8_matmul_matches_xla",
}


def _matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *,
                   compute_dtype, out_dtype):
    di = pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when(di == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # The int8->compute cast happens HERE, on the VMEM tile the MXU is
    # about to consume -- the HBM stream stays int8 bytes.
    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(compute_dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finalize():
        # Per-output-channel scale folds into the one store: no
        # unscaled [M, F] product ever reaches HBM.
        o_ref[...] = (acc_scr[...] * s_ref[...]).astype(out_dtype)


#: What one call's tiles may hold of the v5e's 16 MiB of scoped VMEM
#: (Mosaic's default limit) by :func:`tile_bytes`'s count; the rest is
#: the compiler's own (the cast and the product pass through in
#: pieces).  Compiled for the chip, 14.1 MiB by that count is accepted
#: and 17.3 MiB refused.
VMEM_BUDGET_BYTES = 14 * 2 ** 20

#: The blocks a caller does not name: the fastest of the chip probe at
#: the head's real shapes (``[M, 2048] x [2048, 92544]`` and ``x [2048,
#: 128256]``, M = 16, 32, 512; PERF.md section 6) -- the whole
#: contraction in one step, ragged column blocks 1,024 wide, a prefill
#: chunk's rows in one block so the weight streams and casts once.
DEFAULT_BLOCKS = (512, 2048, 1024)


def tile_bytes(block_m: int, block_d: int, block_f: int,
               itemsize: int) -> int:
    """VMEM one grid step holds: x, the int8 weight tile, the scales
    (eight sublanes) and the output tile, each double-buffered by the
    pipeline, and the f32 accumulator."""
    return (2 * block_m * block_d * itemsize + 2 * block_d * block_f
            + 2 * 8 * block_f * 4 + 2 * block_m * block_f * itemsize
            + block_m * block_f * 4)


def matmul_blocks(m: int, d: int, f: int, dtype=jnp.bfloat16, *,
                  block_m: int | None = None, block_d: int | None = None,
                  block_f: int | None = None):
    """``(block_m, block_d, block_f, padded_weight_bytes)`` of an
    ``[m, d] x [d, f]`` call on activations of ``dtype``: arithmetic on
    the shapes alone, and what :func:`int8_matmul` itself blocks by.  A
    block asked for by name is kept (clamped to the array); one left
    out is :data:`DEFAULT_BLOCKS`'s, with the row block halved until
    the tiles fit :data:`VMEM_BUDGET_BYTES` (float32 activations).

    The blocks FOLLOW THE WEIGHT, so that it reaches the kernel as the
    array the parameter tree holds.  Columns: where ``f`` is a multiple
    of the 128 lanes the grid is ``cdiv(f, block_f)`` over the weight
    as it is -- a column of the output depends on its own weight
    column alone, so whatever a ragged last block reads past the edge
    is never stored.  The contraction cannot be ragged: a ``block_d``
    that does not divide ``d`` becomes the largest multiple of 128
    under it that does.  Only where neither holds (an ``f`` or ``d``
    that is no multiple of 128: test vocabularies, GPT-2's 50,257) is
    the weight padded inside the call; ``padded_weight_bytes`` is the
    size of that per-call int8 copy, 0 otherwise."""
    shrink = block_m is None
    block_m = min(block_m or DEFAULT_BLOCKS[0], _round_up(max(m, 8), 8))
    block_d = min(block_d or DEFAULT_BLOCKS[1], _round_up(max(d, 8), 8))
    block_f = min(block_f or DEFAULT_BLOCKS[2],
                  _round_up(max(f, 128), 128))
    if d % block_d and d % 128 == 0:
        block_d = max(b for b in range(128, block_d + 1, 128)
                      if d % b == 0)
    itemsize = jnp.dtype(dtype).itemsize
    while shrink and block_m > 8 and tile_bytes(
            block_m, block_d, block_f, itemsize) > VMEM_BUDGET_BYTES:
        block_m = _round_up(block_m // 2, 8)
    d_pad = _round_up(d, block_d)
    f_pad = f if f % 128 == 0 else _round_up(f, block_f)
    padded = d_pad * f_pad if (d_pad, f_pad) != (d, f) else 0
    return block_m, block_d, block_f, padded


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "block_d", "interpret"))
def int8_matmul(x, w_int8, scale, *, block_m: int | None = None,
                block_f: int | None = None, block_d: int | None = None,
                interpret: bool | None = None):
    """``(x @ w_int8) * scale`` in ONE kernel.

    x: [M, D] activations (bf16/f32); w_int8: [D, F] int8 weights;
    scale: [1, F] (or [F]) f32 per-output-channel scales
    (models/quant.py:quantize_weight layout).  Returns [M, F] in x's
    dtype.  The grid is (M blocks, F blocks, D blocks) with D
    innermost: each (M, F) tile accumulates its partial products in
    f32 VMEM scratch across the contraction and writes once, scaled.
    M is blocked too -- decode calls are a handful of rows, but the
    quantized PREFILL unembed arrives with M = B*S rows, and an
    unblocked M would need VMEM tiles far past the ~16 MiB budget
    (x 8 MB + acc 8 MB at 8x512 tokens -- a Mosaic allocation failure
    interpret-mode tests cannot see).  The blocks are
    :func:`matmul_blocks`'s: they follow the weight's shape, so a
    weight whose width is a multiple of 128 is read where it lies --
    no pad of it, of its scales or of the output's columns.  Matches
    the XLA reference ``(x @ w.astype(x.dtype)) * scale`` to f32
    accumulation-order tolerance (exactly, for exactly-representable
    inputs -- the equivalence test pins both).
    """
    interpret = interpret_off_chip(interpret)
    m, d = x.shape
    d2, f = w_int8.shape
    if d2 != d:
        raise ValueError(
            f"int8_matmul: x contraction dim {d} != weight dim {d2}")
    out_dtype = x.dtype
    compute_dtype = x.dtype

    block_m, block_d, block_f, padded = matmul_blocks(
        m, d, f, x.dtype, block_m=block_m, block_d=block_d,
        block_f=block_f)
    x_p = _pad_to(_pad_to(x, 0, block_m), 1, block_d)
    scale = scale.reshape(1, -1).astype(jnp.float32)
    if padded:
        w_int8 = _pad_to(_pad_to(w_int8, 0, block_d), 1, block_f)
        scale = _pad_to(scale, 1, block_f)
    m_pad, d_pad = x_p.shape
    f_pad = w_int8.shape[1]

    kernel = functools.partial(_matmul_kernel,
                               compute_dtype=compute_dtype,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(m_pad // block_m, pl.cdiv(f_pad, block_f),
              d_pad // block_d),
        in_specs=[
            pl.BlockSpec((block_m, block_d),
                         lambda mi, fi, di: (mi, di)),
            pl.BlockSpec((block_d, block_f),
                         lambda mi, fi, di: (di, fi)),
            pl.BlockSpec((1, block_f), lambda mi, fi, di: (0, fi)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f),
                               lambda mi, fi, di: (mi, fi)),
        out_shape=jax.ShapeDtypeStruct((m_pad, f_pad), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_f), jnp.float32),
        ],
        interpret=interpret,
    )(x_p, w_int8, scale)
    return out[:m, :f]
