"""The Mamba-2 state-space mixer's recurrence (SSD, arXiv:2405.21060) for
serving: a chunk scan for admission and a state read-modify-write step
for decode, each a Pallas TPU kernel with a ``jax.numpy`` form of the
same arithmetic (``kernel=False``; the model chooses as
``ops/pallas_gdn.py``'s callers do: the kernel on the TPU backend, or
asked for by name and then interpreted off the chip).

``H`` heads of ``P`` channels, ``G`` groups of ``H / G`` heads sharing
one ``B`` and one ``C`` of ``N`` (head ``h`` reads group ``h // (H /
G)``), per head a scalar ``A < 0`` and ``D``; a token brings ``x [H,
P]``, ``dt [H]`` (the step ``Delta >= 0``, after its softplus) and ``B,
C [G, N]``.  The state ``S [H, P, N]`` (float32)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

A token with ``dt = 0`` leaves the state as it was: that is how the
caller masks a chunk's pad tail.

**The chunk scan** (:func:`ssd_chunk_scan`), sub-chunks of ``c`` tokens
(the published ``mamba_chunk_size``), ``gamma_i`` the running sum of
``dt A`` inside the sub-chunk, ``S`` the state handed in::

    y_i = exp(gamma_i) C_i S^T
          + sum_{j <= i} (C_i . B_j) exp(gamma_i - gamma_j) dt_j x_j
          + D x_i
    S'  = exp(gamma_c) S + sum_j exp(gamma_c - gamma_j) dt_j x_j B_j^T

Every exponent is a difference that is <= 0 where it is kept, so nothing
overflows however fast a head forgets.  ALL of it is the kernel: grid
(groups, sub-chunks), a group's ``C B^T`` once for its heads, each
head's decay mask, the two products and the state carried from sub-chunk
to sub-chunk in VMEM scratch -- nothing is prepared outside in XLA, so
the kernel's device time is the whole scan's.

**The decode step** (:func:`ssd_decode_step`) reads every live row's
state once and writes it once, in place (``input_output_aliases``), from
the pool ``[L, slots, H, P, N]``; grid ``(B,)``.  A row that does not
decode is skipped: its grid step maps to the block the last live row
left resident, so nothing is copied in or out for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import interpret_off_chip

__all__ = ["ssd_chunk_scan", "ssd_decode_step", "ssd_recurrence"]

#: kernel entry -> its tier-1 equivalence test (the ``kernel-test``
#: selfcheck rule; both run the kernel bodies interpreted on the CPU).
KERNEL_EQUIVALENCE_TESTS = {
    "ssd_chunk_scan":
        "test_falcon_h1.py::test_ssd_kernels_match_recurrence",
    "ssd_decode_step":
        "test_falcon_h1.py::test_ssd_kernels_match_recurrence",
}

_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 48 << 20


def _per_head(values, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: each head its group's row."""
    return jnp.repeat(values, heads // values.shape[-2], axis=-2)


# -- the recurrence itself ----------------------------------------------------

def ssd_recurrence(x, dt, a, b, c, d, state):
    """Token by token, as the equations read: ``x [T, H, P]``, ``dt [T,
    H]``, ``a, d [H]``, ``b, c [T, G, N]``, ``state [H, P, N]`` -> (``y
    [T, H, P]``, state), float32.  What both kernels are tested against;
    the serving path never runs it."""
    f32 = jnp.float32
    heads = x.shape[1]

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, c_t, precision=_HIGHEST)
        return state, y + d[:, None] * x_t

    state, y = jax.lax.scan(
        step, state.astype(f32),
        (x.astype(f32), dt.astype(f32), _per_head(b.astype(f32), heads),
         _per_head(c.astype(f32), heads)))
    return y, state


# -- admission: the chunk scan ------------------------------------------------

def _chunk_plain(x, dt, a, b, c, d, state, chunk: int):
    """The chunk scan's arithmetic in ``jax.numpy``: a ``lax.scan`` over
    sub-chunks, each the kernel's products."""
    t, heads, p = x.shape
    n = t // chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def split(v):                       # [T, ...] -> [n, c, ...]
        return v.reshape(n, chunk, *v.shape[1:])

    def step(s, xs):
        x_c, dt_c, b_c, c_c = xs        # [c, H, P], [c, H], [c, H, N]
        gamma = jnp.cumsum(dt_c * a, axis=0)                   # [c, H]
        seg = gamma[:, None, :] - gamma[None, :, :]            # [i, j, H]
        decay = jnp.exp(jnp.where(lower[:, :, None], seg, -jnp.inf))
        scores = jnp.einsum("ihn,jhn->ijh", c_c, b_c,
                            precision=_HIGHEST) * decay
        xd = x_c * dt_c[:, :, None]
        y = jnp.einsum("ijh,jhp->ihp", scores, xd, precision=_HIGHEST) \
            + jnp.exp(gamma)[:, :, None] * jnp.einsum(
                "ihn,hpn->ihp", c_c, s, precision=_HIGHEST) \
            + d[None, :, None] * x_c
        last = gamma[-1]                                       # [H]
        s = jnp.exp(last)[:, None, None] * s + jnp.einsum(
            "jhp,jhn->hpn", xd * jnp.exp(last - gamma)[:, :, None], b_c,
            precision=_HIGHEST)
        return s, y

    state, y = jax.lax.scan(
        step, state,
        (split(x), split(dt), split(_per_head(b, heads)),
         split(_per_head(c, heads))))
    return y.reshape(t, heads, p), state


def _chunk_kernel(a_ref, d_ref, dt_ref, x_ref, b_ref, c_ref, s_ref,
                  y_ref, s_out_ref, state, *, heads_per_group, chunk, p):
    """One (group, sub-chunk): the group's ``C B^T`` once, then each of
    its heads (``A``, ``D`` read from SMEM); the group's states ride the
    sub-chunk axis in VMEM scratch."""
    group = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = s_ref[...]

    def mm(u, v, contract):
        return jax.lax.dot_general(
            u, v, (contract, ((), ())), preferred_element_type=jnp.float32,
            precision=_HIGHEST)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = rows >= cols
    b_rows, c_rows = b_ref[...], c_ref[...]                    # [c, N]
    scores = mm(c_rows, b_rows, ((1,), (1,)))                  # [c, c]
    steps = dt_ref[...]                                        # [c, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, steps.shape, 1)
    for member in range(heads_per_group):
        head = group * heads_per_group + member
        dt = jnp.sum(jnp.where(lane == head, steps, 0.0), axis=1,
                     keepdims=True)                            # [c, 1]
        # gamma_i along the rows, broadcast over the columns
        gamma = mm(lower.astype(jnp.float32),
                   jnp.broadcast_to(dt * a_ref[head], (chunk, chunk)),
                   ((1,), (0,)))
        decay = jnp.exp(jnp.where(lower, gamma - gamma.T, -jnp.inf))
        into = gamma[:, :1]                                    # [c, 1]
        # the sub-chunk's whole decay as a column of either height
        ends = jnp.broadcast_to(gamma[chunk - 1:chunk],
                                (max(chunk, p), chunk))[:, :1]
        x = x_ref[:, member * p:(member + 1) * p]              # [c, P]
        xd = x * dt
        s = state[member]                                      # [P, N]
        y_ref[:, member * p:(member + 1) * p] = (
            mm(scores * decay, xd, ((1,), (0,)))
            + jnp.exp(into) * mm(c_rows, s, ((1,), (1,)))
            + d_ref[head] * x)
        s = jnp.exp(ends[:p]) * s + mm(xd * jnp.exp(ends[:chunk] - into),
                                       b_rows, ((0,), (0,)))
        state[member] = s
        s_out_ref[member] = s


def ssd_chunk_scan(x, dt, a, b, c, d, state, *, chunk: int,
                   kernel: bool = False, interpret: bool | None = None):
    """The recurrence over ``T`` tokens of one sequence in sub-chunks of
    ``chunk``: ``x [T, H, P]``, ``dt [T, H]`` (0 where a token must leave
    the state alone), ``a, d [H]`` float32, ``b, c [T, G, N]``, ``state
    [H, P, N]`` float32 -> (``y [T, H, P]`` float32 with ``D x`` in, the
    state after token ``T - 1``).  ``T`` need not divide: the tail is
    padded with tokens that leave the state alone."""
    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n_state = b.shape[1], b.shape[2]
    pad = -t % chunk
    x, dt, b, c = (jnp.pad(v.astype(f32), ((0, pad),) + ((0, 0),)
                           * (v.ndim - 1)) for v in (x, dt, b, c))
    a, d, state = a.astype(f32), d.astype(f32), state.astype(f32)
    if not kernel:
        y, state = _chunk_plain(x, dt, a, b, c, d, state, chunk)
        return y[:t], state
    per = heads // groups
    length = t + pad
    rows_of = pl.BlockSpec((chunk, per * p), lambda g, j: (j, g))
    group_rows = pl.BlockSpec((chunk, n_state), lambda g, j: (j, g))
    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    states = pl.BlockSpec((per, p, n_state), lambda g, j: (g, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads_per_group=per, chunk=chunk,
                          p=p),
        grid=(groups, length // chunk),
        in_specs=[scalars, scalars,
                  pl.BlockSpec((chunk, heads), lambda g, j: (j, 0)),
                  rows_of, group_rows, group_rows, states],
        out_specs=[rows_of, states],
        out_shape=[jax.ShapeDtypeStruct((length, heads * p), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=[pltpu.VMEM((per, p, n_state), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_off_chip(interpret),
        name="ssd_chunk_scan",
    )(a, d, dt,
      x.reshape(length, heads * p), b.reshape(length, groups * n_state),
      c.reshape(length, groups * n_state), state)
    return y.reshape(length, heads, p)[:t], state


# -- decode: one token a row --------------------------------------------------

def _decode_kernel(layer_ref, source_ref, active_ref, x_ref, decay_ref,
                   b_ref, c_ref, s_ref, y_ref, s_out_ref, *,
                   heads_per_group):
    """One batch row: every head's state read, decayed, given the
    token's ``dt x B^T`` and written back; ``y = S C`` (``x`` comes in
    as ``dt x``, the decay as a row of the state's lanes)."""
    row = pl.program_id(0)
    live = active_ref[row] != 0

    @pl.when(live)
    def _():
        x = x_ref[0]                                           # [P, H]
        decays = decay_ref[0]                                  # [H, N]
        b_rows, c_rows = b_ref[0], c_ref[0]                    # [G, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        y = jnp.zeros(x.shape, jnp.float32)
        for head in range(x.shape[1]):
            group = head // heads_per_group
            new = s_ref[0, 0, head] * decays[head:head + 1] \
                + x[:, head:head + 1] * b_rows[group:group + 1]
            s_out_ref[0, 0, head] = new
            answer = jnp.sum(new * c_rows[group:group + 1], axis=1,
                             keepdims=True)                    # [P, 1]
            y = jnp.where(lane == head, answer, y)
        y_ref[0] = y

    # No live row at all: every step maps to the first block, which
    # goes back as it came.
    @pl.when(jnp.logical_not(live) & (active_ref[source_ref[row]] == 0))
    def _():
        s_out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


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


def _decode_plain(x, dt, a, b, c, d, pool, layer, active):
    heads = x.shape[1]
    state = pool[layer]                                   # [B, H, P, N]
    new = state * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * _per_head(b, heads)[:, :, None]
    y = jnp.einsum("bhpn,bhn->bhp", new, _per_head(c, heads),
                   precision=_HIGHEST) + d[:, None] * x
    keep = active[:, None, None, None]
    pool = jax.lax.dynamic_update_index_in_dim(
        pool, jnp.where(keep, new, state), layer, 0)
    return jnp.where(active[:, None, None], y, 0.0), pool


def ssd_decode_step(x, dt, a, b, c, d, pool, layer, active, *,
                    kernel: bool = False, interpret: bool | None = None):
    """One token of every decoding row: ``x [B, H, P]``, ``dt [B, H]``,
    ``a, d [H]``, ``b, c [B, G, N]``; ``pool [L, B, H, P, N]`` float32
    is the state of every layer, ``layer`` (may be traced) the one this
    call advances, in place; ``active [B]`` bool -- a row that does not
    decode keeps its state, bit for bit, and gets nought.  Returns (``y
    [B, H, P]`` float32 with ``D x`` in, pool)."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    a, d = a.astype(f32), d.astype(f32)
    if not kernel:
        return _decode_plain(x, dt, a, b, c, d, pool, layer, active)
    rows, heads, p = x.shape
    groups, n_state = b.shape[1:]

    def row_block(*shape):
        return pl.BlockSpec(
            (1,) + shape,
            lambda i, layer, source, active: (source[i],)
            + (0,) * len(shape))

    state_block = pl.BlockSpec(
        (1, 1, heads, p, n_state),
        lambda i, layer, source, active: (layer[0], source[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows,),
        in_specs=[row_block(p, heads), row_block(heads, n_state),
                  row_block(groups, n_state), row_block(groups, n_state),
                  state_block],
        out_specs=[row_block(p, heads), state_block])
    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, heads_per_group=heads // groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, p, heads), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # (operands count from the scalar-prefetch arguments on)
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_off_chip(interpret),
        name="ssd_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), _resident_rows(active),
      active.astype(jnp.int32), jnp.swapaxes(x * dt[..., None], 1, 2),
      jnp.broadcast_to(jnp.exp(dt * a)[..., None], (rows, heads, n_state)),
      b, c, pool)
    y = jnp.swapaxes(y, 1, 2) + d[:, None] * x
    y = jnp.where(active[:, None, None], y, 0.0)
    return y, pool
