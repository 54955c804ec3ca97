"""Pallas flash attention (interpret mode on CPU) == dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.ops import attention_prefill, repeat_kv
from aiko_services_tpu.ops.pallas_attention import flash_attention


def _dense(q, k, v, q_offset=0):
    b, s = q.shape[:2]
    positions = q_offset + jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return attention_prefill(q, k, v, positions)


def test_flash_matches_dense():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 64, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 4, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 4, 16))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(out, _dense(q, k, v), atol=1e-5)


def test_flash_gqa_index_map():
    """4 query heads over 2 KV heads -- no repeated KV materialization."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 32, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 32, 2, 16))
    out = flash_attention(q, k, v, block_q=8, block_k=8)
    dense = _dense(q, repeat_kv(k, 2), repeat_kv(v, 2))
    np.testing.assert_allclose(out, dense, atol=1e-5)


def test_flash_ragged_lengths():
    """S and T not multiples of the block sizes (pad/mask path)."""
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (1, 37, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 37, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 37, 2, 16))
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(out, _dense(q, k, v), atol=1e-5)


def test_flash_chunked_prefill_offset():
    """Queries begin at absolute position 24 against a 56-long KV."""
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (1, 32, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 56, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 56, 2, 16))
    out = flash_attention(q, k, v, q_offset=24, block_q=16, block_k=16)
    np.testing.assert_allclose(out, _dense(q, k, v, q_offset=24),
                               atol=1e-5)


def test_flash_non_causal():
    key = jax.random.PRNGKey(6)
    q = jax.random.normal(key, (1, 16, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 16, 2, 16))
    out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8)
    scale = 16 ** -0.5
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    dense = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(out, dense, atol=1e-5)


def test_flash_bfloat16():
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (2, 32, 4, 16), dtype=jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 4, 16),
                          dtype=jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 32, 4, 16),
                          dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(_dense(q, k, v), dtype=np.float32), atol=6e-2)


def test_flash_pack_heads_matches_unpacked():
    """Cross-head packing (two kv heads per grid row, block-diagonal
    queries over a 128-wide contraction) is numerically exact vs the
    unpacked kernel -- including chunked-prefill offsets, GQA groups,
    and ragged shapes.  (Measured on v5e it is slightly slower, so it
    is an option, not the default -- see the flash_attention
    docstring.)"""
    key = jax.random.PRNGKey(11)
    for (s, t, hkv, g, d, off) in ((64, 256, 4, 2, 64, 192),
                                   (48, 100, 2, 3, 32, 52),
                                   (128, 128, 6, 1, 64, 0)):
        q = jax.random.normal(key, (2, s, hkv * g, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, t, hkv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, t, hkv, d))
        base = flash_attention(q, k, v, q_offset=off,
                               block_q=32, block_k=64)
        packed = flash_attention(q, k, v, q_offset=off,
                                 block_q=32, block_k=64,
                                 pack_heads=True)
        np.testing.assert_allclose(np.asarray(packed), np.asarray(base),
                                   atol=1e-5, rtol=1e-5)


def test_flash_pack_heads_falls_back_when_unpaired():
    """Odd kv-head counts / d > 64 silently use the unpacked path."""
    key = jax.random.PRNGKey(12)
    q = jax.random.normal(key, (1, 32, 3, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 32, 3, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 32, 3, 16))
    out = flash_attention(q, k, v, block_q=8, block_k=8, pack_heads=True)
    np.testing.assert_allclose(out, _dense(q, k, v), atol=1e-5)


def _block_dense(q, k, v, q_offset, block):
    """Block-causal attention in jax.numpy: a query at ``p`` sees keys
    up to the end of its own block of ``block``."""
    b, s = q.shape[:2]
    positions = q_offset + jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return attention_prefill(q, k, v, (positions // block + 1) * block - 1)


@pytest.mark.parametrize("offset,block_q,block_k", [
    (24, 16, 16),       # the chunk starts mid-way through a kernel block
    (0, 16, 32), (20, 8, 16)])
def test_flash_block_causal_matches_dense(offset, block_q, block_k):
    """``block_length`` 4 (generation by diffusion over blocks): the
    frontier of a query is the end of its own block, at absolute
    positions, GQA and a ragged key extent included."""
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 32, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 60, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 60, 2, 16))
    out = flash_attention(q, k, v, q_offset=offset, block_q=block_q,
                          block_k=block_k, block_length=4)
    np.testing.assert_allclose(
        out, _block_dense(q, repeat_kv(k, 2), repeat_kv(v, 2), offset, 4),
        atol=1e-5)
    # and it is not the causal answer
    causal = flash_attention(q, k, v, q_offset=offset, block_q=block_q,
                             block_k=block_k)
    assert float(jnp.abs(out - causal).max()) > 1e-2


def test_flash_block_length_one_is_the_causal_program():
    """A caller that hands no block length gets the program it always
    got: at 1 the frontier is the position itself, to the letter."""
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (1, 32, 4, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 56, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 56, 2, 16))

    def program(**block):
        return str(jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, q_offset=24, block_q=16, block_k=16, **block))(
                q, k, v))
    assert program() == program(block_length=1)
    assert program() != program(block_length=4)
    assert np.array_equal(
        flash_attention(q, k, v, q_offset=24, block_q=16, block_k=16),
        flash_attention(q, k, v, q_offset=24, block_q=16, block_k=16,
                        block_length=1))
