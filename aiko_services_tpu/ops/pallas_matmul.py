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

Known cost, left for ROADMAP S2: llama3's vocabulary (128,256) is not a
multiple of ``block_f`` (512), so the ``[2048, 128256]`` int8 unembed
and its scales are padded INSIDE the jitted call -- a full weight copy
on every step unless XLA hoists it.  It compiles and matches on v5e
(``chip_smoke.py``); it has not been measured.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .tiles import (interpret_off_chip, pad_to as _pad_to,
                    round_up as _round_up)

__all__ = ["int8_matmul"]

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


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "block_d", "interpret"))
def int8_matmul(x, w_int8, scale, *, block_m: int = 256,
                block_f: int = 512, block_d: int = 1024,
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
    interpret-mode tests cannot see).  At the defaults the resident
    tiles total ~1.8 MB.  Matches the XLA reference
    ``(x @ w.astype(x.dtype)) * scale`` to f32 accumulation-order
    tolerance (exactly, for exactly-representable inputs -- the
    equivalence test pins both).
    """
    interpret = interpret_off_chip(interpret)
    m, d = x.shape
    d2, f = w_int8.shape
    if d2 != d:
        raise ValueError(
            f"int8_matmul: x contraction dim {d} != weight dim {d2}")
    out_dtype = x.dtype
    compute_dtype = x.dtype

    block_m = min(block_m, _round_up(max(m, 8), 8))
    block_d = min(block_d, _round_up(max(d, 8), 8))
    block_f = min(block_f, _round_up(max(f, 128), 128))
    x_p = _pad_to(_pad_to(x, 0, block_m), 1, block_d)
    w_p = _pad_to(_pad_to(w_int8, 0, block_d), 1, block_f)
    scale_p = _pad_to(scale.reshape(1, -1).astype(jnp.float32),
                      1, block_f)
    m_pad = x_p.shape[0]
    d_pad, f_pad = w_p.shape

    kernel = functools.partial(_matmul_kernel,
                               compute_dtype=compute_dtype,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(m_pad // block_m, f_pad // block_f, d_pad // block_d),
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
    )(x_p, w_p, scale_p)
    return out[:m, :f]
