"""Transformer building blocks: RMSNorm, RoPE, attention, SwiGLU.

Functional JAX over explicit parameter pytrees -- no module framework in
the hot path, so everything traces clean under jit/shard_map and the same
code serves training and serving.  Compute dtype is bfloat16 (MXU-native);
normalization statistics and softmax run in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["rms_norm", "rope_frequencies", "apply_rope", "swiglu",
           "repeat_kv", "attention_prefill", "attention_decode",
           "attention_decode_append"]


def rms_norm(x: jax.Array, weight: jax.Array,
             epsilon: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                          + epsilon)
    return (x32 * scale).astype(dtype) * weight


def rope_frequencies(head_dim: int, max_positions: int,
                     theta: float = 500_000.0) -> jax.Array:
    """[max_positions, head_dim//2] complex-as-cos/sin table (float32)."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                          dtype=np.float32) / head_dim))
    positions = np.arange(max_positions, dtype=np.float32)
    angles = np.outer(positions, inv_freq)                 # [S, hd/2]
    return jnp.stack([np.cos(angles), np.sin(angles)])      # [2, S, hd/2]


def apply_rope(x: jax.Array, rope_table: jax.Array,
               positions: jax.Array) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S] absolute positions."""
    cos = rope_table[0][positions]                 # [B, S, hd/2]
    sin = rope_table[1][positions]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    gate = jax.nn.silu(x @ w_gate)
    return (gate * (x @ w_up)) @ w_down


def repeat_kv(kv: jax.Array, repeats: int) -> jax.Array:
    """[B, S, K, hd] -> [B, S, K*repeats, hd] for grouped-query attention."""
    if repeats == 1:
        return kv
    b, s, k, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :],
                            (b, s, k, repeats, d)).reshape(b, s,
                                                           k * repeats, d)


def _group_queries(q: jax.Array, kv_heads: int):
    """[B, S, H, hd] -> [B, S, K, G, hd] with H = K*G (GQA grouping)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, kv_heads, h // kv_heads, d)


def _is_quantized_kv(layer) -> bool:
    return isinstance(layer, dict) and "int8" in layer and "scale" in layer


def _split_kv(layer):
    """(raw payload [B, T, K, hd], per-position scale [B, T, K] or
    None).

    Quantized layers (models/quant.py:quantize_kv) come apart into the
    int8 payload -- which the caller casts to the compute dtype
    IMMEDIATELY BEFORE its matmul, keeping the convert adjacent to the
    dot so it fuses into the operand load and HBM streams int8 bytes --
    and the float32 scale, which applies OUTSIDE the matmuls (to score
    logits for keys, to softmax weights for values): exact, since each
    scale is constant along the contracted head_dim."""
    if _is_quantized_kv(layer):
        return layer["int8"], layer["scale"][..., 0].astype(jnp.float32)
    return layer, None


def attention_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_positions: jax.Array,
                      kv_length_mask: jax.Array | None = None,
                      kv_positions: jax.Array | None = None,
                      window: int = 0) -> jax.Array:
    """Causal attention for a prompt chunk.

    q: [B, S, H, hd]; k/v: [B, T, K, hd] where K divides H -- grouped
    (GQA) caches are consumed directly, queries grouped onto the kv
    heads, so the expanded [B, T, H, hd] cache is never materialized
    (at llama3-1b decode that materialization alone is ~4x the whole
    cache's HBM traffic per step); q_positions: [B, S] absolute
    positions of the queries (so chunked prefill against a longer cache
    works); kv_length_mask: [B, T] bool of valid cache slots;
    kv_positions: [B, T] absolute positions of the keys -- defaults to
    ``arange(T)`` (keys ARE the cache row); the speculative verify
    step passes an explicit vector because its key axis concatenates
    the cache row with the draft chunk's per-row offset positions
    (models/llama.py decode_loop).  ``window`` > 0 bands the causal
    mask: a query at ``p`` sees keys at ``p - window < j <= p`` (a
    sliding-window layer).  float32 softmax.

    k/v may be int8-quantized cache layers (``{"int8", "scale"}``,
    models/quant.py:quantize_kv): key scales multiply the score logits,
    value scales fold into the softmax weights -- exact (scales are
    constant along the contracted head_dim), and no dequantized cache
    tensor ever reaches HBM.
    """
    k, k_scale = _split_kv(k)
    v, v_scale = _split_kv(v)
    if k_scale is not None:
        k = k.astype(q.dtype)          # adjacent to the dot: fuses
    if v_scale is not None:
        v = v.astype(q.dtype)
    scale = q.shape[-1] ** -0.5
    grouped = _group_queries(q, k.shape[2])        # [B,S,K,G,hd]
    logits = jnp.einsum("bskgd,btkd->bkgst", grouped, k,
                        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:                        # [B,T,K] -> [B,K,1,1,T]
        logits = logits * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    t = k.shape[1]
    if kv_positions is None:
        key_pos = jnp.arange(t)[None, None, None, None, :]  # [1,1,1,1,T]
    else:
        key_pos = kv_positions[:, None, None, None, :]      # [B,1,1,1,T]
    causal = key_pos <= \
        q_positions[:, None, None, :, None]        # [B,1,1,S,T]
    if window:
        causal = jnp.logical_and(
            causal, key_pos > q_positions[:, None, None, :, None] - window)
    if kv_length_mask is not None:
        causal = jnp.logical_and(
            causal, kv_length_mask[:, None, None, None, :])
    logits = jnp.where(causal, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        weights = weights * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgst,btkd->bskgd", weights.astype(v.dtype), v)
    return out.reshape(q.shape)


def attention_decode_append(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, k_new: jax.Array,
                            v_new: jax.Array,
                            lengths: jax.Array) -> jax.Array:
    """Decode attention over the cache PLUS the current token's k/v,
    which is *not yet written* to the cache.

    Splitting the softmax into a cache part and a self part lets the
    layer scan treat the cache as read-only input: the stacked-output
    full-cache rewrite (536 MB/step at llama3-1b/2k) disappears, and the
    single post-scan scatter aliases in place under jit donation.

    TPU layout: the cache is consumed as [B, T, K*hd] -- its natural
    contiguous view -- and GQA is expressed as BLOCK-DIAGONAL matmuls
    over the fused K*hd axis: each query head is zero-padded to the full
    K*hd width with its values in its own kv head's block, so
    ``scores = q_pad @ k_flat^T`` contracts over K*hd (a multiple of the
    128-wide vector lanes) and the weighted sum is a plain
    ``[H, T] @ [T, K*hd]`` matmul.  A per-head grouped einsum instead
    contracts over hd=64 against a [B, T, K, hd] operand -- half-empty
    lanes and either a strided read or a full-cache transpose; measured
    on v5e this trick takes the per-step attention cost from ~1.9 ms to
    the cache-streaming floor.  The extra multiply-by-zero FLOPs are
    free: decode runs at ~2% MFU, bandwidth-bound.

    q: [B, 1, H, hd]; k_cache/v_cache: [B, T, K, hd] (grouped) -- or
    int8-quantized layers (``{"int8", "scale"}``): both cache matmuls
    then run as NATIVE int8 MXU dots so the cache streams int8 bytes
    (casting it up costs real VPU time -- the convert does not fuse
    into the dot).  That makes the quantized path bounded-approximate,
    not exact: the query quantizes per (batch, head) for the score
    dot, and the softmax weights (value scales folded) quantize for
    the weighted sum, each adding error at its int8 step size (~0.4%
    of the row maximum); the softmax denominator stays exact-float,
    so weight truncation can only shrink the output, never inflate it
    (see the inline sink-token analysis).

    DOCUMENTED WORST CASE (diffuse attention): the per-weight bound
    does NOT bound the aggregate dropped mass.  With one spike and a
    long tail of positions each under half the int8 step (weight <
    row_max/254), every tail weight quantizes to zero: at T=8k a
    tail carrying ~97% of the attention mass shrinks the output to
    the spike's few percent (tests/test_flash_decode.py::
    test_dense_int8_diffuse_tail_error_mode quantifies it).  Diffuse
    long-context attention is exactly the int8-KV regime, so on the
    chip the decode path defaults to a split-K Pallas kernel
    (ops/pallas_decode.py, decode_attention="auto": a flat cache of
    T >= LlamaConfig.flash_decode_threshold, a paged cache of any
    extent), which dequantizes IN KERNEL -- no
    query or weight quantization at all -- and this dense int8 path
    remains only an explicit short-context opt-in.  k_new/
    v_new: [B, 1, K, hd]; lengths: [B] valid cache positions (NOT
    counting the current token).  Returns [B, 1, H, hd].
    """
    b, _, h, d = q.shape
    k_cache, k_scale = _split_kv(k_cache)                    # [B,T,K]
    v_cache, v_scale = _split_kv(v_cache)
    t, kv = k_cache.shape[1], k_cache.shape[2]
    scale = d ** -0.5
    blocks = jnp.arange(h) // (h // kv)            # [H] kv head per head
    onehot = jax.nn.one_hot(blocks, kv, dtype=q.dtype)       # [H, K]
    q_flat = q[:, 0]                                         # [B, H, hd]
    q_pad = jnp.einsum("bhd,hk->bhkd", q_flat, onehot) \
        .reshape(b, h, kv * d)                               # [B, H, K*hd]
    k_flat = k_cache.reshape(b, t, kv * d)
    v_flat = v_cache.reshape(b, t, kv * d)
    if k_scale is not None:
        # NATIVE int8 score dot: casting the cache up costs real VPU
        # time (measured ~5.6 us per 8 M elements on v5e -- the convert
        # does NOT fuse into the dot's operand load), so instead the
        # QUERY quantizes (tiny: [B, H, C]) and the MXU contracts
        # int8 x int8 into s32.  Exact up to q's own quantization
        # (~0.4%): per-(b,h) dynamic q scales and per-(t,k) key scales
        # both sit outside the contraction.
        q_amax = jnp.maximum(
            jnp.abs(q_pad.astype(jnp.float32)).max(-1, keepdims=True),
            1e-8)
        q_int8 = jnp.clip(
            jnp.round(q_pad.astype(jnp.float32) / (q_amax / 127.0)),
            -127, 127).astype(jnp.int8)
        s32 = jnp.einsum("bhc,btc->bht", q_int8, k_flat,
                         preferred_element_type=jnp.int32)
        cache_logits = (s32.astype(jnp.float32)
                        * (q_amax / 127.0) * scale
                        * k_scale.transpose(0, 2, 1)[:, blocks, :])
    else:
        cache_logits = jnp.einsum(
            "bhc,btc->bht", q_pad, k_flat,
            preferred_element_type=jnp.float32) * scale      # [B, H, T]
    valid = jnp.arange(t)[None, None, :] < lengths[:, None, None]
    cache_logits = jnp.where(valid, cache_logits, -1e30)
    k_new_h = k_new[:, 0][:, blocks, :]            # [B, H, hd] gathered
    v_new_h = v_new[:, 0][:, blocks, :]
    self_logits = (q_flat.astype(jnp.float32)
                   * k_new_h.astype(jnp.float32)).sum(-1) * scale  # [B,H]
    peak = jnp.maximum(jnp.max(cache_logits, axis=-1), self_logits)
    cache_weights = jnp.exp(cache_logits - peak[:, :, None])  # [B,H,T]
    self_weights = jnp.exp(self_logits - peak)                # [B,H]
    if v_scale is not None:
        # Fold value scales into the weights (head h only reads its own
        # kv block out of `fused`, so scaling by that block's
        # per-position scale is exactly dequantization), then quantize
        # the WEIGHTS per (b, h) and contract int8 x int8 on the MXU --
        # the value cache streams int8 bytes, no cast of the big
        # operand (same rationale as the score dot above).
        v_scale_h = v_scale.transpose(0, 2, 1)[:, blocks, :]
        folded = cache_weights * v_scale_h
        w_step = jnp.maximum(folded.max(-1, keepdims=True),
                             1e-30) / 127.0
        w_int8 = jnp.clip(jnp.round(folded / w_step), 0,
                          127).astype(jnp.int8)
        # The denominator stays EXACT (the float weights): positions
        # whose folded weight rounds to zero lose their (sub-half-step)
        # value contribution from the numerator but keep their weight
        # in the normalizer, so the output can only shrink by the
        # dropped mass -- never inflate.  The alternative (denominator
        # from the quantized weights) renormalizes the diffuse-tail
        # case but systematically INFLATES whenever a large-weight,
        # small-value-norm position quantizes away -- and that shape
        # is exactly the attention-sink token real LLMs produce on
        # every step, so exact-denominator is the safe side.
        denominator = cache_weights.sum(-1) + self_weights    # [B,H]
        fused = jnp.einsum(
            "bht,btc->bhc", w_int8, v_flat,
            preferred_element_type=jnp.int32).astype(jnp.float32) \
            * w_step                                          # [B,H,K*hd]
    else:
        denominator = cache_weights.sum(-1) + self_weights    # [B,H]
        fused = jnp.einsum(
            "bht,btc->bhc", cache_weights.astype(v_cache.dtype), v_flat,
            preferred_element_type=jnp.float32)               # [B,H,K*hd]
    # Select each head's own block back out of the fused output.
    cache_part = jnp.einsum("bhkd,hk->bhd",
                            fused.reshape(b, h, kv, d),
                            onehot.astype(jnp.float32))       # [B,H,hd]
    out = (cache_part
           + self_weights[:, :, None] * v_new_h.astype(jnp.float32)) \
        / denominator[:, :, None]
    return out.reshape(q.shape).astype(q.dtype)


def attention_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """Single-token decode against the cache.

    q: [B, 1, H, hd]; k_cache/v_cache: [B, T, K, hd] where K divides H
    (grouped caches consumed directly, see attention_prefill); lengths:
    [B] number of valid positions (including the token just written).
    Returns [B, 1, H, hd].
    """
    scale = q.shape[-1] ** -0.5
    grouped = _group_queries(q, k_cache.shape[2])  # [B,1,K,G,hd]
    logits = jnp.einsum("bskgd,btkd->bkgst", grouped, k_cache,
                        preferred_element_type=jnp.float32) * scale
    t = k_cache.shape[1]
    valid = jnp.arange(t)[None, None, None, None, :] < \
        lengths[:, None, None, None, None]
    logits = jnp.where(valid, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd",
                     weights.astype(v_cache.dtype), v_cache)
    return out.reshape(q.shape)
