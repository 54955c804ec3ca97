"""Flash-decode (split-K Pallas) kernel: exactness vs the dense decode
path, int8 in-kernel dequantization, and the documented diffuse-attention
error mode of the dense int8 path (ADVICE r3)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.quant import dequantize_kv, quantize_kv
from aiko_services_tpu.ops.layers import attention_decode_append
from aiko_services_tpu.ops.pallas_decode import flash_decode_append


def _random_case(key, b=3, t=192, k=2, g=2, hd=32, dtype=jnp.float32):
    keys = jax.random.split(key, 5)
    h = k * g
    q = jax.random.normal(keys[0], (b, 1, h, hd), dtype=dtype)
    k_cache = jax.random.normal(keys[1], (b, t, k, hd), dtype=dtype)
    v_cache = jax.random.normal(keys[2], (b, t, k, hd), dtype=dtype)
    k_new = jax.random.normal(keys[3], (b, 1, k, hd), dtype=dtype)
    v_new = jax.random.normal(keys[4], (b, 1, k, hd), dtype=dtype)
    lengths = jnp.asarray([0, 17, t - 33][:b], dtype=jnp.int32)
    return q, k_cache, v_cache, k_new, v_new, lengths


def test_flash_matches_dense_bf16_cache():
    """Raw (unquantized) cache: flash == dense to float tolerance,
    including a zero-length row, a mid-block boundary, and a ragged
    final block (t not a multiple of block_t)."""
    case = _random_case(jax.random.PRNGKey(0))
    q, k_cache, v_cache, k_new, v_new, lengths = case
    dense = attention_decode_append(q, k_cache, v_cache, k_new, v_new,
                                    lengths)
    flash = flash_decode_append(q, k_cache, v_cache, k_new, v_new,
                                lengths, block_t=64)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_flash_int8_matches_dequantized_dense():
    """int8 cache: the kernel's in-kernel dequantization (scales folded
    into scores/weights) is EXACT relative to dequantizing the cache
    first and running the raw dense path -- no query or softmax-weight
    quantization exists on this path."""
    q, k_cache, v_cache, k_new, v_new, lengths = _random_case(
        jax.random.PRNGKey(1))
    k_q = quantize_kv(k_cache)
    v_q = quantize_kv(v_cache)
    reference = attention_decode_append(
        q, dequantize_kv(k_q, jnp.float32), dequantize_kv(v_q, jnp.float32),
        k_new, v_new, lengths)
    flash = flash_decode_append(q, k_q, v_q, k_new, v_new, lengths,
                                block_t=64)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(reference),
                               atol=1e-4, rtol=1e-4)


def _fixed_token_decode(config, steps=4):
    """Run prefill + several fixed-token decode steps; return stacked
    per-step logits."""
    params = llama.init_params(jax.random.PRNGKey(0), config)
    cache = llama.init_cache(config, 2)
    prompt = jnp.asarray([[5, 9, 2, 7], [1, 3, 3, 8]], dtype=jnp.int32)
    logits, cache = llama.prefill(params, config, prompt, cache,
                                  jnp.zeros(2, dtype=jnp.int32))
    lengths = jnp.asarray([4, 4], dtype=jnp.int32)
    outs = [logits[:, -1]]
    for step in range(steps):
        tokens = jnp.asarray([10 + step, 20 + step], dtype=jnp.int32)
        logits, cache = llama.decode_step(params, config, tokens, cache,
                                          lengths)
        lengths = lengths + 1
        outs.append(logits)
    return jnp.stack(outs)


def test_decode_step_flash_matches_dense():
    """decode_step with decode_attention='flash' evolves the same cache
    and produces the same logits as 'dense' over multiple steps."""
    base = llama.LlamaConfig.tiny(vocab_size=64, max_seq=64)
    dense = _fixed_token_decode(
        dataclasses.replace(base, decode_attention="dense"))
    flash = _fixed_token_decode(
        dataclasses.replace(base, decode_attention="flash"))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=5e-2, rtol=2e-2)


def test_decode_step_flash_int8_kv():
    """flash decode_step with an int8 cache stays close to the bf16
    dense path (error bounded by the cache's own storage quantization,
    not by weight truncation)."""
    base = llama.LlamaConfig.tiny(vocab_size=64, max_seq=64)
    dense = _fixed_token_decode(
        dataclasses.replace(base, decode_attention="dense"))
    flash_int8 = _fixed_token_decode(
        dataclasses.replace(base, decode_attention="flash",
                            kv_dtype="int8"))
    np.testing.assert_allclose(np.asarray(flash_int8), np.asarray(dense),
                               atol=0.15, rtol=0.15)


def test_auto_threshold_resolves_at_trace_time(monkeypatch):
    """On the TPU backend 'auto' uses dense below the threshold and
    flash at/above it -- both must produce correct results on the same
    config object.  max_seq=128: the auto gate also requires a
    block-aligned extent (cache_extent % 128 == 0), so 128 is the
    smallest extent where the flash side actually takes the kernel
    path.  Off the chip 'auto' is the reference path at ANY extent
    (ISSUE 21: no interpreter in a serving loop that landed on CPU)."""
    from aiko_services_tpu import ops
    from aiko_services_tpu.models.llama import resolve_decode_backend
    config = llama.LlamaConfig.tiny(
        vocab_size=64, max_seq=128)
    small = dataclasses.replace(config, flash_decode_threshold=32)
    assert resolve_decode_backend(
        small, llama.init_cache(small, 2)) == "reference"
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # as on the chip
    assert resolve_decode_backend(
        small, llama.init_cache(small, 2)) == "dense-flash"
    dense_logits = _fixed_token_decode(config)      # 128 < 1024: dense
    flash_logits = _fixed_token_decode(small)       # 128 >= 32: flash
    np.testing.assert_allclose(np.asarray(flash_logits),
                               np.asarray(dense_logits),
                               atol=5e-2, rtol=2e-2)


def test_sharded_cache_never_reaches_flash(monkeypatch):
    """ADVICE r4 (medium): pallas_call has no GSPMD partitioning rules,
    so a tp-sharded cache must never reach the flash kernel.  'auto'
    (the default) silently keeps dense for a distributed cache even at
    flash-eligible extents; explicit 'flash' raises eagerly instead of
    compiling a per-layer full-cache all-gather."""
    import pytest

    from aiko_services_tpu import ops
    from aiko_services_tpu.parallel import MeshPlan, make_mesh

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # as on the chip
    base = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, max_seq=128),
        flash_decode_threshold=32)          # 128 is flash-eligible
    params = llama.init_params(jax.random.PRNGKey(0), base)
    plan = MeshPlan(make_mesh({"tp": 2}, jax.devices()[:2]))
    cache = jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, plan.shard(*s)),
        llama.init_cache(base, 2), llama.cache_specs(base))
    tokens = jnp.asarray([3, 5], dtype=jnp.int32)
    lengths = jnp.asarray([4, 4], dtype=jnp.int32)

    flash = dataclasses.replace(base, decode_attention="flash")
    with pytest.raises(ValueError, match="resident"):
        llama.decode_step(params, flash, tokens, cache, lengths)

    auto = dataclasses.replace(base, decode_attention="auto")
    logits, _ = llama.decode_step(params, auto, tokens, cache, lengths)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    # The same extent with a RESIDENT cache still picks flash (the gate
    # only bites when the cache is actually distributed).
    resident = llama.init_cache(base, 2)
    from aiko_services_tpu.models.llama import _resolve_decode_flash
    assert _resolve_decode_flash(auto, resident) is True
    assert _resolve_decode_flash(auto, cache) is False


def test_mixed_quantization_cache_rejected():
    """ADVICE r4: the kernel keys its in-kernel dequant on the k scales
    alone; a half-quantized k/v pair is caller error and must raise, not
    silently misread v."""
    import pytest

    q, k_cache, v_cache, k_new, v_new, lengths = _random_case(
        jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="quantization state"):
        flash_decode_append(q, quantize_kv(k_cache), v_cache, k_new,
                            v_new, lengths)
    with pytest.raises(ValueError, match="quantization state"):
        flash_decode_append(q, k_cache, quantize_kv(v_cache), k_new,
                            v_new, lengths)


def test_dense_int8_diffuse_tail_error_mode():
    """ADVICE r3 (medium): the DENSE int8 path quantizes softmax weights
    per (b, h) with step = row_max / 127; a distribution with one spike
    and a diffuse tail (every tail weight below half the step) drops
    most of the attention mass from the numerator.  This test quantifies
    that worst case at T=8k -- and shows the flash path, which never
    quantizes weights, stays exact on the same input.  See the
    attention_decode_append docstring for the documented bound."""
    b, t, k, hd = 1, 8192, 1, 16
    # q aligned with the first k component: logits = cache[:, 0] / sqrt(hd)
    q = jnp.zeros((b, 1, 1, hd)).at[..., 0].set(hd ** 0.5)
    # One spike at position 0, a uniform tail whose exact softmax weight
    # is ~1/260 of the spike's: below half the int8 step (1/254).
    tail_logit = -np.log(260.0)
    k_vals = jnp.full((b, t, k, hd), 0.0).at[..., 0].set(tail_logit)
    k_vals = k_vals.at[:, 0, :, 0].set(0.0)
    v_vals = jnp.ones((b, t, k, hd))       # every position contributes 1
    k_new = jnp.full((b, 1, k, hd), -1e3)  # self term negligible
    v_new = jnp.zeros((b, 1, k, hd))
    lengths = jnp.asarray([t], dtype=jnp.int32)

    exact = attention_decode_append(q, k_vals, v_vals, k_new, v_new,
                                    lengths)
    # int8 cache whose stored values round-trip exactly (amax scales on
    # these constants introduce ~0.4% -- negligible next to the mode
    # under test).
    k_q, v_q = quantize_kv(k_vals), quantize_kv(v_vals)
    dense_int8 = attention_decode_append(q, k_q, v_q, k_new, v_new,
                                         lengths)
    flash_int8 = flash_decode_append(q, k_q, v_q, k_new, v_new, lengths)

    # All weights hit v=1, so the exact output is ~1.  The dense int8
    # path keeps only the spike's share of the numerator (~1/32 here:
    # spike 1 vs tail mass 8191/260) while the denominator stays exact:
    # output shrinks toward spike/total -- the documented shrink-only
    # failure.  The flash path stays at the exact value.
    exact_val = float(np.asarray(exact)[0, 0, 0, 0])
    dense_val = float(np.asarray(dense_int8)[0, 0, 0, 0])
    flash_val = float(np.asarray(flash_int8)[0, 0, 0, 0])
    assert abs(exact_val - 1.0) < 1e-3
    assert dense_val < 0.2 * exact_val      # the documented worst case
    assert abs(flash_val - exact_val) < 5e-3
