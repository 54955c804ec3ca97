"""Sequence/context parallelism: ring attention, Ulysses, blockwise.

The reference scales *streams of frames* across processes but has no
within-model sequence scaling (SURVEY.md section 5.7: no ring attention /
context parallel / Ulysses anywhere in the tree).  On TPU, long-context
attention is a first-class concern, so this module provides the three
standard schemes over a named ``sp`` mesh axis:

- ``ring_attention``: K/V blocks rotate around the ring via ``ppermute``
  while each device accumulates its queries' output with an online
  (streaming) softmax.  Memory per device is O(S/n); compute overlaps
  communication on ICI.
- ``ulysses_attention``: all-to-all head-scatter / sequence-gather --
  each device ends up with the FULL sequence for H/n heads, runs dense
  attention locally, and all-to-alls back.  Cheaper for moderate S and
  many heads; requires heads % axis_size == 0.
- ``blockwise_attention``: single-device chunked online-softmax attention
  (the memory-efficient building block the ring scheme repeats per hop,
  and the reference semantics for the Pallas kernel in
  ``ops/pallas_attention.py``).

All three are causal, take absolute positions (so they compose with
paged/offset KV caches), compute softmax statistics in float32, and
return outputs in the query dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .mesh import P

__all__ = ["ring_attention", "ulysses_attention", "blockwise_attention",
           "ring_attention_sharded"]

_NEG_INF = -1e30


def _online_block(q, k, v, q_pos, kv_pos, m, l, o):
    """One online-softmax accumulation step against a K/V block.

    q: [B, Sq, H, d]; k/v: [B, Sk, H, d]; q_pos: [B, Sq]; kv_pos: [B, Sk];
    m/l: [B, H, Sq] float32 running max / normalizer; o: [B, Sq, H, d]
    float32 unnormalized output.  Returns updated (m, l, o).
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    causal = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]
    logits = jnp.where(causal, logits, _NEG_INF)

    m_block = jnp.max(logits, axis=-1)                      # [B, H, Sq]
    m_new = jnp.maximum(m, m_block)
    # Guard fully-masked blocks: exp(-inf - -inf) would be NaN.
    m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
    correction = jnp.exp(m - m_safe)                        # [B, H, Sq]
    p = jnp.exp(logits - m_safe[..., None])                 # [B, H, Sq, Sk]
    p = jnp.where(causal, p, 0.0)

    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _finish(l, o, dtype):
    denominator = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / denominator).astype(dtype)


def blockwise_attention(q, k, v, q_positions, kv_positions=None,
                        block_size: int = 512):
    """Memory-efficient causal attention by scanning K/V blocks.

    q: [B, S, H, d]; k/v: [B, T, H, d] (GQA-expanded); q_positions: [B, S]
    absolute; kv_positions: [B, T] (default arange).  Equivalent to dense
    ``attention_prefill`` but O(block_size) live logits.
    """
    b, t = k.shape[0], k.shape[1]
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    block_size = min(block_size, t)
    if t % block_size:
        pad = block_size - t % block_size
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=2**30)
        t += pad
    blocks = t // block_size
    k = k.reshape(b, blocks, block_size, *k.shape[2:]).swapaxes(0, 1)
    v = v.reshape(b, blocks, block_size, *v.shape[2:]).swapaxes(0, 1)
    kv_positions = kv_positions.reshape(b, blocks, block_size).swapaxes(0, 1)

    s, h = q.shape[1], q.shape[2]
    init = (jnp.full((b, h, s), _NEG_INF, dtype=jnp.float32),
            jnp.zeros((b, h, s), dtype=jnp.float32),
            jnp.zeros((b, s, h, q.shape[-1]), dtype=jnp.float32))

    def body(carry, xs):
        m, l, o = carry
        k_blk, v_blk, pos_blk = xs
        return _online_block(q, k_blk, v_blk, q_positions, pos_blk,
                             m, l, o), None

    (m, l, o), _ = jax.lax.scan(body, init, (k, v, kv_positions))
    return _finish(l, o, q.dtype)


def _ring_inner(q, k, v, q_pos, kv_pos, axis_name, axis_size):
    """Per-shard ring attention body (runs under shard_map over ``sp``)."""
    b, s, h, d = q.shape
    init_stats = (jnp.full((b, h, s), _NEG_INF, dtype=jnp.float32),
                  jnp.zeros((b, h, s), dtype=jnp.float32),
                  jnp.zeros((b, s, h, d), dtype=jnp.float32))
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(carry, _):
        (k_cur, v_cur, pos_cur), (m, l, o) = carry
        # Launch the rotation to the next device, then accumulate the
        # current block -- the ppermute is independent of the block's
        # FLOPs, so on TPU it rides ICI overlapped with compute.
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        pos_next = jax.lax.ppermute(pos_cur, axis_name, perm)
        m, l, o = _online_block(q, k_cur, v_cur, q_pos, pos_cur, m, l, o)
        return ((k_next, v_next, pos_next), (m, l, o)), None

    # n-1 rotate+accumulate hops, then the last arriving block is
    # accumulated without a wasted final ppermute.
    ((k_last, v_last, pos_last), stats), _ = jax.lax.scan(
        body, ((k, v, kv_pos), init_stats), None, length=axis_size - 1)
    m, l, o = _online_block(q, k_last, v_last, q_pos, pos_last, *stats)
    return _finish(l, o, q.dtype)


def ring_attention(q, k, v, q_positions, mesh, axis: str = "sp",
                   kv_positions=None, batch_axis=None, head_axis=None):
    """Causal ring attention over the ``axis`` mesh axis.

    q/k/v: [B, S, H, d] GLOBAL arrays, sequence dimension sharded over
    ``axis``; q_positions/kv_positions: [B, S] absolute positions.
    Each device holds S/n queries and rotates the K/V shards n times.
    ``batch_axis``/``head_axis`` name mesh axes the batch/head dims are
    already sharded over (dp/tp) so composition with data/tensor
    parallelism does not force gathers.
    """
    if kv_positions is None:
        kv_positions = q_positions
    n = mesh.shape[axis]
    spec_qkv = P(batch_axis, axis, head_axis, None)
    spec_pos = P(batch_axis, axis)
    inner = partial(_ring_inner, axis_name=axis, axis_size=n)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_pos, spec_pos),
        out_specs=spec_qkv, check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def _ulysses_inner(q, k, v, q_pos, kv_pos, axis_name):
    """Head-scatter / sequence-gather: trade the sequence shard for a head
    shard with one all-to-all each way, then dense attention locally."""
    # [B, S/n, H/ n-> ...]: split heads (axis 2), concat sequence (axis 1).
    qg = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)                 # [B, S, H/n, d]
    kg = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vg = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    q_pos_g = jax.lax.all_gather(q_pos, axis_name, axis=1, tiled=True)
    kv_pos_g = jax.lax.all_gather(kv_pos, axis_name, axis=1, tiled=True)

    scale = qg.shape[-1] ** -0.5
    logits = jnp.einsum("bshd,bthd->bhst", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    causal = kv_pos_g[:, None, None, :] <= q_pos_g[:, None, :, None]
    logits = jnp.where(causal, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", weights.astype(vg.dtype), vg)
    # Inverse all-to-all: gather heads back, scatter sequence.
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention(q, k, v, q_positions, mesh, axis: str = "sp",
                      kv_positions=None, batch_axis=None, head_axis=None):
    """Ulysses-style context parallelism (head-scatter all-to-all).

    Requires n_heads % mesh.shape[axis] == 0.  Same array contract as
    ``ring_attention``.
    """
    if kv_positions is None:
        kv_positions = q_positions
    n = mesh.shape[axis]
    local_heads = q.shape[2]
    if head_axis is not None and head_axis in mesh.axis_names:
        local_heads //= mesh.shape[head_axis]
    if local_heads % n:
        raise ValueError(
            f"ulysses needs local heads ({local_heads}) divisible by "
            f"axis '{axis}' size ({n})")
    spec_qkv = P(batch_axis, axis, head_axis, None)
    spec_pos = P(batch_axis, axis)
    inner = partial(_ulysses_inner, axis_name=axis)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_pos, spec_pos),
        out_specs=spec_qkv, check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def ring_attention_sharded(axis_name: str, axis_size: int):
    """Return the per-shard ring attention callable for use INSIDE an
    existing shard_map (e.g. a context-parallel model step that already
    runs under one).  Signature: fn(q, k, v, q_pos, kv_pos) with local
    shards."""
    return partial(_ring_inner, axis_name=axis_name, axis_size=axis_size)
