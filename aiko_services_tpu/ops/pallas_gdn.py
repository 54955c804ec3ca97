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
the two halves of each doubled block).  Every product is float32 at
the highest precision.  The kernel (grid: groups of heads x
sub-chunks, the states in VMEM scratch) builds a sub-chunk's WY form
in VMEM from ``q``, ``k``, ``v`` and the running sum of ``g`` --
nothing of it goes through HBM -- two heads' inverses side by side (a
pass of the MXU's 128-deep array contracts both), then applies the
states; the ``jax.numpy`` form builds the same form for every
sub-chunk at once in XLA and hands the state on in a ``lax.scan``.

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
# Heads a grid step of the chunk scan takes at most, in pairs: the
# products batched over them keep the MXU fed (on a v5e the kernel
# took 1.6 times as long at one pair a step as at three).
_STEP_HEADS = 6
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

def _dot(x, y, x_axis: int, y_axis: int):
    """``x`` times ``y`` summed over ``x``'s axis ``x_axis`` and ``y``'s
    ``y_axis`` (each -1 or -2), batched over the axes before the last
    two: float32 at the highest precision."""
    batch = tuple(range(x.ndim - 2))
    return jax.lax.dot_general(
        x, y, (((x.ndim + x_axis,), (y.ndim + y_axis,)), (batch, batch)),
        preferred_element_type=jnp.float32, precision=_HIGHEST)


def _pairs(x):
    """``[G, ...]`` -> (heads ``0, 2, ..``, heads ``1, 3, ..``), each
    ``[G / 2, ...]``."""
    paired = x.reshape(x.shape[0] // 2, 2, *x.shape[1:])
    return paired[:, 0], paired[:, 1]


def _side_by_side(x):
    """``[G, ..., r, w]`` -> ``[G / 2, ..., r, 2w]``: each pair of heads
    along the lanes."""
    return jnp.concatenate(_pairs(x), axis=-1)


def _lanes(x, width: int):
    """``[G, ..., r, 1]`` -> ``[G / 2, ..., r, 2 width]``: each pair's
    two columns, each over its own half."""
    first, second = _pairs(x)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * width), 1)
    return jnp.where(lane < width, first, second)


def _block_diagonal(y):
    """``[..., r, 2w]``, two heads side by side -> ``[..., 2r, 2w]``
    with the first head's half in the top rows and the second's below:
    a right operand that contracts each head of a pair with its own."""
    left = jax.lax.broadcasted_iota(jnp.int32, y.shape[-2:], 1) \
        < y.shape[-1] // 2
    return jnp.concatenate([jnp.where(left, y, 0.0),
                            jnp.where(left, 0.0, y)], axis=-2)


def _unit_lower_inverse(a):
    """``(I + A)^-1`` for ``A [..., c, c]`` strictly lower triangular,
    ``c`` a multiple of 8: exact block algebra, no series beyond the
    8 x 8 diagonal blocks (module docstring).  ``A [..., c, 2c]`` is a
    pair of heads side by side, and so is what comes back: a product
    then contracts both in one pass of the MXU's 128-deep array, where
    one head fills half of it."""
    c, width = a.shape[-2:]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1)
    col = jnp.where(lane >= c, lane - c, lane)      # within each head
    eye = (row == col).astype(a.dtype)

    def mm(x, y):
        return _dot(x, y if width == c else _block_diagonal(y), -1, -2)

    diagonal = jnp.where(row >> 3 == col >> 3, a, 0.0)
    square = mm(diagonal, diagonal)
    inverse = mm(mm(eye - diagonal, eye + square),
                 eye + mm(square, square))
    shift = 3
    while 1 << shift < c:
        between = jnp.where((row >> shift + 1 == col >> shift + 1)
                            & (row >> shift != col >> shift), a, 0.0)
        inverse = inverse - mm(inverse, mm(between, inverse))
        shift += 1
    return inverse


def _wy_parts(q, k, gamma_row, beta_row):
    """What of sub-chunks' WY form needs neither the state nor ``V``,
    batched over the axes before the last two: ``q, k [..., c, d_k]``,
    ``gamma_row`` (the running sum of ``g``) and ``beta_row [..., 1,
    c]`` laid along a row -> (``A [..., c, c]``, ``P = (Q K^T)
    Gamma_i / Gamma_j (j <= i)``; ``Gamma Q``, ``K Gamma_c / Gamma``,
    ``beta Gamma K [..., c, d_k]``; ``beta [..., c, 1]``; ``Gamma_c
    [..., 1, 1]``), float32.  The kernel body and the ``jax.numpy``
    form both call it."""
    c = q.shape[-2]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def column(x):                      # [.., 1, c] -> [.., c, 1], exact
        return jnp.sum(jnp.where(row == col, x, 0.0), axis=-1,
                       keepdims=True)

    gamma, beta = column(gamma_row), column(beta_row)
    # exp of a difference that is <= 0 wherever it is kept
    ratio = jnp.exp(jnp.where(row >= col, gamma - gamma_row, -jnp.inf))
    a = jnp.where(row > col, beta * ratio * _dot(k, k, -1, -1), 0.0)
    decay = jnp.exp(gamma)
    last = gamma_row[..., c - 1:]
    return (a, ratio * _dot(q, k, -1, -1), q * decay,
            k * jnp.exp(last - gamma), beta * decay * k, beta,
            jnp.exp(last))


def _sub_chunks(x, c: int):
    """``[T, H, ...]`` -> ``[H, T / c, c, d]``: each head's tokens in
    sub-chunks of ``c``."""
    t, h = x.shape[:2]
    return jnp.moveaxis(x, 1, 0).reshape(h, t // c, c, -1)


def _rows(x, c: int):
    """``[T, H]`` -> ``[H, T / c, 1, c]`` float32: each head's
    sub-chunks laid along rows."""
    t, h = x.shape
    return jnp.moveaxis(x.astype(jnp.float32), 1, 0).reshape(
        h, t // c, 1, c)


def _chunk_prepare(q, k, v, g, beta, c: int):
    """Everything of the WY form that does not need the state, batched
    over heads and sub-chunks: (w ``[H, N, c, d_k]``, u0 ``[H, N, c,
    d_v]``, qg, p ``[H, N, c, c]``, kg, g_end ``[H, N]``), float32."""
    q, k, v = (_sub_chunks(x.astype(jnp.float32), c) for x in (q, k, v))
    a, p, qg, kg, bk, beta, end = _wy_parts(
        q, k, jnp.cumsum(_rows(g, c), axis=-1), _rows(beta, c))
    inverse = _unit_lower_inverse(a)
    return (_dot(inverse, bk, -1, -2), _dot(inverse, beta * v, -1, -2),
            qg, p, kg, end[..., 0, 0])


def _chunk_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, s_ref, o_ref,
                  s_out_ref, state):
    """One sub-chunk of a few pairs of heads: their WY form built in
    VMEM, then their states applied; the states ride the sub-chunk axis
    in VMEM scratch.  ``v``, ``o`` and the states hold each pair side
    by side along the lanes (:func:`pack_state`'s layout), so the
    products that contract over the sub-chunk take a pair a pass."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = s_ref[...]

    f32 = jnp.float32
    heads = q_ref.shape[0]
    dv = v_ref.shape[-1] // heads
    a, p, qg, kg, bk, beta, end = _wy_parts(
        q_ref[:, 0].astype(f32), k_ref[:, 0].astype(f32),
        gamma_ref[:, 0], beta_ref[:, 0])
    inverse = _unit_lower_inverse(_side_by_side(a))      # [G/2, c, 2c]
    pairs = state[...]                                   # [G/2, dk, 2dv]
    # each head's state apart, and each pair's values side by side
    s = jnp.stack([pairs[..., :dv], pairs[..., dv:]], axis=1).reshape(
        heads, pairs.shape[1], dv)
    v = jnp.stack([v_ref[:, pair * 2 * dv:(pair + 1) * 2 * dv]
                   for pair in range(heads // 2)]).astype(f32)
    # U = (I + A)^-1 beta (V - Gamma K S), block-diagonal for the
    # products that take it
    u = _block_diagonal(_dot(inverse, _block_diagonal(
        _lanes(beta, dv) * v - _side_by_side(_dot(bk, s, -1, -2))), -1, -2))
    out = _side_by_side(_dot(qg, s, -1, -2)) \
        + _dot(_side_by_side(p), u, -1, -2)
    for pair in range(heads // 2):
        o_ref[:, pair * 2 * dv:(pair + 1) * 2 * dv] = out[pair]
    pairs = pairs * _lanes(end, dv) + _dot(
        jnp.concatenate(_pairs(kg), axis=-2), u, -2, -2)
    state[...] = pairs
    s_out_ref[...] = pairs


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
    state = state.astype(jnp.float32)
    if not kernel:
        out, state = _chunk_sequential_scan(
            *_chunk_prepare(q, k, v, g, beta, c), state)
        return jnp.moveaxis(out.reshape(h, t + pad, -1), 0, 1)[:t], state
    # an odd head count gets a head of zeros: the kernel takes pairs
    odd = h % 2
    if odd:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, 1))
                                    + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
        state = jnp.pad(state, ((0, 1), (0, 0), (0, 0)))
    length, heads = t + pad, h + odd
    dk, dv = q.shape[2], v.shape[2]
    step = max(size for size in range(2, _STEP_HEADS + 1, 2)
               if heads % size == 0)
    f32 = jnp.float32

    def per_chunk(rows, width):
        return pl.BlockSpec((step, 1, rows, width),
                            lambda i, j: (i, j, 0, 0))

    lanes = pl.BlockSpec((c, step * dv), lambda i, j: (j, i))
    per_pair = pl.BlockSpec((step // 2, dk, 2 * dv), lambda i, j: (i, 0, 0))
    out, state = pl.pallas_call(
        _chunk_kernel,
        grid=(heads // step, length // c),
        in_specs=[per_chunk(c, dk), per_chunk(c, dk), lanes,
                  per_chunk(1, c), per_chunk(1, c), per_pair],
        out_specs=[lanes, per_pair],
        out_shape=[jax.ShapeDtypeStruct((length, heads * dv), f32),
                   jax.ShapeDtypeStruct((heads // 2, dk, 2 * dv), f32)],
        scratch_shapes=[pltpu.VMEM((step // 2, dk, 2 * dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_off_chip(interpret),
        name="gated_delta_chunk_scan",
    )(_sub_chunks(q, c), _sub_chunks(k, c), v.reshape(length, heads * dv),
      jnp.cumsum(_rows(g, c), axis=-1), _rows(beta, c),
      pack_state(state, 2))
    return (out.reshape(length, heads, dv)[:t, :h],
            unpack_state(state, 2)[:h])


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
