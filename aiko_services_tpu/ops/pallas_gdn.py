"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) for serving:
a chunk-parallel scan for admission and a state read-modify-write step
for decode, each a Pallas TPU kernel with a ``jax.numpy`` form of the
same arithmetic (``kernel=False``; the model chooses as
``deepseek.decode_kernel_on`` does: the kernel on the TPU backend, or
asked for by name and then interpreted off the chip).

Per head, with keys of unit length, ``alpha_t = exp(g_t)`` in (0, 1]
and ``beta_t`` in (0, 2), the state ``S`` in ``R^{d_k x d_v}``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

A token with ``g = 0`` and ``beta = 0`` leaves the state as it was:
that is how the caller masks a chunk's pad tail.

**The chunk scan** (:func:`gated_delta_chunk_scan`) is the WY form.
With ``u_t = beta_t (v_t - (alpha_t S_{t-1})^T k_t)`` the recurrence is
``S_t = alpha_t S_{t-1} + k_t u_t^T``, and inside a sub-chunk of ``c``
tokens that starts from ``S`` (``gamma_i`` the running sum of ``g``,
``Gamma = exp(gamma)``)::

    (I + A) U = beta (V - Gamma K S),  A_ij = beta_i (k_i . k_j)
                                              Gamma_i / Gamma_j  (j < i)
    O = (Gamma Q) S + ((Q K^T) Gamma_i / Gamma_j, j <= i) U
    S' = Gamma_c S + (K Gamma_c / Gamma)^T U

Every ratio of decays is the exponential of a difference that is <= 0,
so nothing overflows however fast a head forgets.  ``(I + A)^-1`` of
the unit lower-triangular 64 x 64 is built exactly, by blocks: the
8 x 8 diagonal blocks by ``(I - D)(I + D^2)(I + D^4)`` (``D^8 = 0``),
then three merges ``T <- T - T L T`` (``L`` the part of ``A`` between
the two halves of each doubled block).  All of that is batched over
heads and sub-chunks and runs as XLA einsums in float32 at the highest
precision; what is sequential -- the state handed from sub-chunk to
sub-chunk -- is the kernel (grid heads x sub-chunks, the state in VMEM
scratch), or a ``lax.scan`` in the ``jax.numpy`` form.

**The decode step** (:func:`gated_delta_decode_step`) reads every live
row's state once and writes it once, in place
(``input_output_aliases``), in the pool's own layout
(:func:`pack_state`: ``pack`` heads side by side along the lanes, so
that ``[d_k, pack * d_v]`` is whole (8, 128) tiles -- at d_v = 192 two
heads make 384 lanes; a ``[.., 96, 192]`` minor pair would be stored
as 256 lanes, a third more bytes on a path that is all bytes).  A row
that does not decode is skipped: its grid step maps to the block the
last live row left resident, so nothing is copied in or out for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import interpret_off_chip

__all__ = ["gated_delta_chunk_scan", "gated_delta_decode_step",
           "gated_delta_recurrence", "state_pack", "pack_state",
           "unpack_state"]

#: kernel entry -> its tier-1 equivalence test (the ``kernel-test``
#: selfcheck rule; both run the kernel bodies interpreted on the CPU).
KERNEL_EQUIVALENCE_TESTS = {
    "gated_delta_chunk_scan":
        "test_olmo_hybrid.py::test_chunk_scan_matches_recurrence",
    "gated_delta_decode_step":
        "test_olmo_hybrid.py::test_decode_step_kernel_matches_recurrence",
}

SUB_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST
_DECODE_VMEM_LIMIT = 48 << 20


# -- the state's layout -------------------------------------------------------

def state_pack(heads: int, value_dim: int) -> int:
    """How many heads the stored state lays side by side along its
    last axis: the fewest that make ``pack * value_dim`` whole 128-lane
    tiles and divide the head count; 1 where none does (the tail tile
    is then padded by the chip)."""
    for pack in range(1, heads + 1):
        if heads % pack == 0 and (pack * value_dim) % 128 == 0:
            return pack
    return 1


def pack_state(state, pack: int):
    """``[..., H, d_k, d_v]`` -> the stored ``[..., H / pack, d_k, pack
    * d_v]``."""
    *lead, heads, dk, dv = state.shape
    grouped = state.reshape(*lead, heads // pack, pack, dk, dv)
    return jnp.swapaxes(grouped, -3, -2).reshape(
        *lead, heads // pack, dk, pack * dv)


def unpack_state(stored, pack: int):
    """The inverse of :func:`pack_state`."""
    *lead, groups, dk, width = stored.shape
    split = stored.reshape(*lead, groups, dk, pack, width // pack)
    return jnp.swapaxes(split, -3, -2).reshape(
        *lead, groups * pack, dk, width // pack)


# -- the recurrence itself ----------------------------------------------------

def gated_delta_recurrence(q, k, v, g, beta, state):
    """Token by token, as the equations read: ``q, k [T, H, d_k]``, ``v
    [T, H, d_v]``, ``g, beta [T, H]``, ``state [H, d_k, d_v]`` ->
    (``o [T, H, d_v]``, state), float32.  What both kernels are tested
    against; the serving path never runs it."""
    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        decayed = state * jnp.exp(g_t)[:, None, None]
        predicted = jnp.einsum("hkv,hk->hv", decayed, k_t,
                               precision=_HIGHEST)
        update = b_t[:, None] * (v_t - predicted)
        state = decayed + k_t[:, :, None] * update[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t,
                                 precision=_HIGHEST)

    f32 = jnp.float32
    state, out = jax.lax.scan(
        step, state.astype(f32),
        (q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
         beta.astype(f32)))
    return out, state


# -- admission: the chunk scan ------------------------------------------------

def _unit_lower_inverse(a):
    """``(I + A)^-1`` for ``A [..., c, c]`` strictly lower triangular,
    ``c`` a multiple of 8: exact block algebra, no series beyond the
    8 x 8 diagonal blocks (module docstring)."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    eye = jnp.eye(c, dtype=a.dtype)

    def mm(x, y):
        return jnp.einsum("...ij,...jk->...ik", x, y, precision=_HIGHEST)

    diagonal = jnp.where(row // 8 == col // 8, a, 0.0)
    square = mm(diagonal, diagonal)
    inverse = mm(mm(eye - diagonal, eye + square),
                 eye + mm(square, square))
    size = 8
    while size < c:
        between = jnp.where((row // (2 * size) == col // (2 * size))
                            & (row // size != col // size), a, 0.0)
        inverse = inverse - mm(inverse, mm(between, inverse))
        size *= 2
    return inverse


def _chunk_prepare(q, k, v, g, beta, c: int):
    """Everything of the WY form that does not need the state, batched
    over heads and sub-chunks: (w ``[H, N, c, d_k]``, u0 ``[H, N, c,
    d_v]``, qg, p ``[H, N, c, c]``, kg, g_end ``[H, N]``), float32."""
    t, h = g.shape
    n = t // c
    f32 = jnp.float32

    def split(x):                       # [T, H, d] -> [H, N, c, d]
        return jnp.moveaxis(x.astype(f32), 1, 0).reshape(h, n, c, -1)

    q, k, v = split(q), split(k), split(v)
    g = jnp.moveaxis(g.astype(f32), 1, 0).reshape(h, n, c)
    beta = jnp.moveaxis(beta.astype(f32), 1, 0).reshape(h, n, c)
    gamma = jnp.cumsum(g, axis=-1)
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    # exp of a difference that is <= 0 wherever it is kept
    ratio = jnp.exp(jnp.where(
        row >= col, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk = jnp.einsum("hnid,hnjd->hnij", k, k, precision=_HIGHEST)
    a = jnp.where(row > col, beta[..., :, None] * ratio * kk, 0.0)
    inverse = _unit_lower_inverse(a)
    decay = jnp.exp(gamma)
    w = jnp.einsum("hnij,hnjd->hnid", inverse,
                   (beta * decay)[..., None] * k, precision=_HIGHEST)
    u0 = jnp.einsum("hnij,hnjd->hnid", inverse, beta[..., None] * v,
                    precision=_HIGHEST)
    p = ratio * jnp.einsum("hnid,hnjd->hnij", q, k, precision=_HIGHEST)
    qg = q * decay[..., None]
    kg = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    return w, u0, qg, p, kg, decay[..., -1]


def _chunk_kernel(w_ref, u0_ref, qg_ref, p_ref, kg_ref, end_ref, s_ref,
                  o_ref, s_out_ref, state):
    """One (head, sub-chunk) of the scan; the state rides the
    sub-chunk axis in VMEM scratch."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = s_ref[0]

    def mm(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32,
                       precision=_HIGHEST)

    s = state[...]
    u = u0_ref[0, 0] - mm(w_ref[0, 0], s)
    o_ref[0, 0] = mm(qg_ref[0, 0], s) + mm(p_ref[0, 0], u)
    kg = kg_ref[0, 0]
    s = s * end_ref[0, 0][:1] + jax.lax.dot_general(
        kg, u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)
    state[...] = s
    s_out_ref[0] = s


def _chunk_sequential_scan(w, u0, qg, p, kg, g_end, state):
    def step(s, xs):
        w, u0, qg, p, kg, end = xs
        u = u0 - jnp.einsum("hck,hkv->hcv", w, s, precision=_HIGHEST)
        out = jnp.einsum("hck,hkv->hcv", qg, s, precision=_HIGHEST) \
            + jnp.einsum("hij,hjv->hiv", p, u, precision=_HIGHEST)
        s = s * end[:, None, None] \
            + jnp.einsum("hck,hcv->hkv", kg, u, precision=_HIGHEST)
        return s, out

    state, out = jax.lax.scan(
        step, state,
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, u0, qg, p, kg, g_end)))
    return jnp.moveaxis(out, 0, 1), state


def gated_delta_chunk_scan(q, k, v, g, beta, state, *,
                           kernel: bool = False, sub_chunk: int = SUB_CHUNK,
                           interpret: bool | None = None):
    """The recurrence over ``T`` tokens of one sequence, chunk-parallel:
    ``q, k [T, H, d_k]`` (keys of unit length, queries scaled), ``v [T,
    H, d_v]``, ``g [T, H]`` (log decay, <= 0), ``beta [T, H]``, ``state
    [H, d_k, d_v]`` float32 -> (``o [T, H, d_v]`` float32, the state
    after token ``T - 1``).  ``T`` need not divide: the tail is padded
    with tokens that leave the state alone."""
    t, h = g.shape
    c = int(sub_chunk)
    pad = -t % c
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, pad),) + ((0, 0),)
                                    * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    prepared = _chunk_prepare(q, k, v, g, beta, c)
    state = state.astype(jnp.float32)
    if not kernel:
        out, state = _chunk_sequential_scan(*prepared, state)
    else:
        w, u0, qg, p, kg, g_end = prepared
        n, dk, dv = w.shape[1], w.shape[3], u0.shape[3]
        # the sub-chunk's last decay as rows of the state's width (the
        # kernel broadcasts one along the sublanes)
        end = jnp.broadcast_to(g_end[..., None, None], (h, n, 8, dv))

        def per_chunk(width):
            return pl.BlockSpec((1, 1, c, width),
                                lambda i, j: (i, j, 0, 0))

        per_head = pl.BlockSpec((1, dk, dv), lambda i, j: (i, 0, 0))
        out, state = pl.pallas_call(
            _chunk_kernel,
            grid=(h, n),
            in_specs=[per_chunk(dk), per_chunk(dv), per_chunk(dk),
                      per_chunk(c), per_chunk(dk),
                      pl.BlockSpec((1, 1, 8, dv),
                                   lambda i, j: (i, j, 0, 0)),
                      per_head],
            out_specs=[per_chunk(dv), per_head],
            out_shape=[jax.ShapeDtypeStruct((h, n, c, dv), jnp.float32),
                       jax.ShapeDtypeStruct((h, dk, dv), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret_off_chip(interpret),
            name="gated_delta_chunk_scan",
        )(w, u0, qg, p, kg, end, state)
    out = jnp.moveaxis(out.reshape(h, t + pad, -1), 0, 1)
    return out[:t], state


# -- decode: one token a row --------------------------------------------------

def _lane_groups(columns, pack: int, dv: int, lane):
    """``columns [d_k, >= pack]``: column ``r`` broadcast over the lanes
    ``[r d_v, (r + 1) d_v)`` -> ``[d_k, pack * d_v]``."""
    spread = columns[:, pack - 1:pack]
    for member in range(pack - 2, -1, -1):
        spread = jnp.where(lane < (member + 1) * dv,
                           columns[:, member:member + 1], spread)
    return spread


def _decode_kernel(layer_ref, source_ref, active_ref, k_ref, q_ref, v_ref,
                   alpha_ref, beta_ref, s_ref, o_ref, s_out_ref, *,
                   pack, dv):
    """One batch row: every head group's state block read, decayed,
    corrected by the delta rule and written back; the output is the new
    state's answer to the query."""
    row = pl.program_id(0)
    groups, dk, width = s_ref.shape[2:]
    live = active_ref[row] != 0

    @pl.when(live)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
        keys, queries = k_ref[0], q_ref[0]               # [d_k, H]
        for group in range(groups):
            first = group * pack
            k2 = _lane_groups(keys[:, first:first + pack], pack, dv, lane)
            q2 = _lane_groups(queries[:, first:first + pack], pack, dv,
                              lane)
            decayed = s_ref[0, 0, group] * alpha_ref[0, group]
            predicted = jnp.sum(decayed * k2, axis=0, keepdims=True)
            update = beta_ref[0, group] * (v_ref[0, group] - predicted)
            new = decayed + k2 * update
            s_out_ref[0, 0, group] = new
            o_ref[0, group] = jnp.sum(new * q2, axis=0, keepdims=True)

    # No live row at all: every step maps to the first block, which
    # goes back as it came.
    @pl.when(jnp.logical_not(live) & (active_ref[source_ref[row]] == 0))
    def _():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _resident_rows(active):
    """For each row the row whose blocks its grid step names: its own
    if it decodes, else the last live row before it (whose blocks are
    still resident: nothing is fetched or written back for the step),
    else the first live row (fetched early, computed when its own step
    comes), else row 0."""
    b = active.shape[0]
    index = jnp.arange(b, dtype=jnp.int32)
    last_live = jax.lax.cummax(jnp.where(active, index, -1))
    first_live = jnp.argmax(active).astype(jnp.int32)
    return jnp.where(last_live >= 0, last_live, first_live)


def _decode_step_plain(q, k, v, g, beta, pool, layer, active, pack):
    f32 = jnp.float32
    state = unpack_state(pool[layer], pack)               # [B, H, dk, dv]
    decayed = state * jnp.exp(g.astype(f32))[..., None, None]
    predicted = jnp.einsum("bhkv,bhk->bhv", decayed, k.astype(f32),
                           precision=_HIGHEST)
    update = beta.astype(f32)[..., None] * (v.astype(f32) - predicted)
    new = decayed + k.astype(f32)[..., None] * update[:, :, None, :]
    out = jnp.einsum("bhkv,bhk->bhv", new, q.astype(f32),
                     precision=_HIGHEST)
    keep = active[:, None, None, None]
    pool = jax.lax.dynamic_update_index_in_dim(
        pool, pack_state(jnp.where(keep, new, state), pack), layer, 0)
    return jnp.where(active[:, None, None], out, 0.0), pool


def gated_delta_decode_step(q, k, v, g, beta, pool, layer, active, *,
                            pack: int, kernel: bool = False,
                            interpret: bool | None = None):
    """One token of every decoding row: ``q, k [B, H, d_k]``, ``v [B,
    H, d_v]``, ``g, beta [B, H]``; ``pool [L, B, H / pack, d_k, pack *
    d_v]`` float32 is the stored state of every recurrent layer
    (:func:`pack_state`), ``layer`` (may be traced) the one this call
    advances, in place; ``active [B]`` bool -- a row that does not
    decode keeps its state, bit for bit, and gets nought.  Returns
    (``o [B, H, d_v]`` float32, pool)."""
    if not kernel:
        return _decode_step_plain(q, k, v, g, beta, pool, layer, active,
                                  pack)
    b, h, dk = k.shape
    dv = v.shape[-1]
    groups, width = h // pack, pack * dv
    f32 = jnp.float32

    def lanes(x):                       # [B, H] -> [B, H/pack, 1, width]
        return jnp.repeat(x.astype(f32), dv, axis=-1) \
            .reshape(b, groups, 1, width)

    def row_block(*shape):
        return pl.BlockSpec(
            (1,) + shape,
            lambda i, layer, source, active: (source[i],)
            + (0,) * len(shape))

    state_block = pl.BlockSpec(
        (1, 1, groups, dk, width),
        lambda i, layer, source, active: (layer[0], source[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row_block(dk, h), row_block(dk, h),
                  row_block(groups, 1, width), row_block(groups, 1, width),
                  row_block(groups, 1, width), state_block],
        out_specs=[row_block(groups, 1, width), state_block])
    out, pool = pl.pallas_call(
        functools.partial(_decode_kernel, pack=pack, dv=dv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, groups, 1, width), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # (operands count from the scalar-prefetch arguments on)
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DECODE_VMEM_LIMIT),
        interpret=interpret_off_chip(interpret),
        name="gated_delta_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), _resident_rows(active),
      active.astype(jnp.int32),
      jnp.swapaxes(k.astype(f32), 1, 2), jnp.swapaxes(q.astype(f32), 1, 2),
      v.astype(f32).reshape(b, groups, 1, width), lanes(jnp.exp(g)),
      lanes(beta), pool)
    out = jnp.where(active[:, None, None], out.reshape(b, h, dv), 0.0)
    return out, pool
