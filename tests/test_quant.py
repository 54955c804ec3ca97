"""int8 quantization (models/quant.py): weight-only exactness on
grid-aligned weights, bounded error on arbitrary ones, the serving
paths running unchanged on a quantized tree, TP/fsdp sharding of the
quantized tree (quantize_specs), and the int8 KV cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.quant import (QUANTIZED_LAYER_KEYS,
                                            dequantize_kv, is_quantized,
                                            quantize_kv, quantize_params,
                                            quantize_specs,
                                            quantize_weight)
from aiko_services_tpu.parallel import MeshPlan, P


def grid_aligned_params(config):
    """Params whose matmul weights sit exactly on an int8 grid, so
    quantization is lossless and quant-vs-raw forward must agree to
    float rounding only."""
    params = llama.init_params(jax.random.PRNGKey(0), config)
    key = jax.random.PRNGKey(42)

    def align(weight):
        nonlocal key
        key, sub1, sub2 = jax.random.split(key, 3)
        levels = jax.random.randint(sub1, weight.shape, -127, 128)
        # Pin level 127 in every output channel so quantization recovers
        # exactly this scale (scale = channel max / 127).
        levels = levels.at[..., 0, :].set(127)
        scale = jax.random.uniform(sub2, weight.shape[-1:],
                                   minval=0.5, maxval=2.0) / 127.0
        return (levels * scale).astype(weight.dtype) * 0.05

    layers = dict(params["layers"])
    for name in QUANTIZED_LAYER_KEYS:
        layers[name] = align(layers[name])
    params["layers"] = layers
    params["unembed"] = align(params["unembed"])
    return params


def test_quantize_tree_structure():
    config = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), config)
    quantized = quantize_params(params)
    for name in QUANTIZED_LAYER_KEYS:
        leaf = quantized["layers"][name]
        assert is_quantized(leaf)
        assert leaf["int8"].dtype == jnp.int8
        assert leaf["int8"].shape == params["layers"][name].shape
        assert leaf["scale"].shape[-1] == leaf["int8"].shape[-1]
    assert is_quantized(quantized["unembed"])
    assert not is_quantized(quantized["embed"])
    # ~2x smaller where it counts.
    raw = params["layers"]["w_gate"].nbytes
    packed = quantized["layers"]["w_gate"]["int8"].nbytes \
        + quantized["layers"]["w_gate"]["scale"].nbytes
    assert packed < raw * 0.55


def test_quantize_roundtrip_error_bounded():
    weight = jax.random.normal(jax.random.PRNGKey(1), (64, 128),
                               jnp.float32)
    q = quantize_weight(weight)
    rebuilt = q["int8"].astype(jnp.float32) * q["scale"].astype(
        jnp.float32)
    per_channel_max = jnp.abs(weight).max(axis=0)
    error = jnp.abs(rebuilt - weight).max(axis=0)
    # Symmetric int8: error <= half a step = max/254 per channel.
    assert bool((error <= per_channel_max / 254 + 1e-7).all())


def test_quantized_forward_matches_on_grid_weights():
    """Grid-aligned weights quantize losslessly: prefill + decode on the
    quantized tree match the raw tree to float tolerance."""
    config = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=256, max_seq=32),
        dtype="float32")
    params = grid_aligned_params(config)
    quantized = quantize_params(params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0, 256)

    raw_logits, raw_cache = llama.prefill(
        params, config, tokens[:, :8], llama.init_cache(config, 2, 32),
        jnp.zeros(2, dtype=jnp.int32))
    q_logits, q_cache = llama.prefill(
        quantized, config, tokens[:, :8],
        llama.init_cache(config, 2, 32), jnp.zeros(2, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(raw_logits),
                               np.asarray(q_logits), atol=2e-3)

    raw_step, _ = llama.decode_step(params, config, tokens[:, 8],
                                    raw_cache,
                                    jnp.full((2,), 8, jnp.int32))
    q_step, _ = llama.decode_step(quantized, config, tokens[:, 8],
                                  q_cache, jnp.full((2,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(raw_step),
                               np.asarray(q_step), atol=2e-3)


def test_batcher_serves_quantized_params():
    """The continuous batcher runs unchanged on a quantized tree (jit
    treats the {"int8","scale"} dicts as ordinary pytree leaves)."""
    from aiko_services_tpu.models import ContinuousBatcher, Request
    from aiko_services_tpu.models.tokenizer import ByteTokenizer

    config = llama.LlamaConfig.tiny()
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), config))
    tok = ByteTokenizer()
    out = []
    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=16)
    batcher.submit(Request("r1", tok.encode("aloha"), max_new_tokens=5,
                           emit=lambda r, t, f: out.append(t)))
    steps = batcher.run_until_drained(max_steps=200)
    assert steps < 200
    assert len(out) == 5


# -- TP / fsdp composition -----------------------------------------------


def test_quantize_specs_mirror_quantized_tree():
    """quantize_specs produces a spec tree with the quantized params'
    exact structure: tree_map over (params, specs) must not raise."""
    config = llama.LlamaConfig.tiny()
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), config))
    specs = quantize_specs(llama.partition_specs(config))
    paired = jax.tree_util.tree_map(lambda leaf, s: (leaf.shape, s),
                                    params, specs)
    wq = paired["layers"]["wq"]
    assert wq["int8"][1] == P(None, "fsdp", "tp")
    # Scale cannot shard its size-1 contraction axis.
    assert wq["scale"][1] == P(None, None, "tp")
    assert paired["unembed"]["scale"][1] == P(None, "tp")


def test_tp_decode_with_quantized_tree():
    """TP/fsdp-sharded quantized tree decodes on the 8-device mesh and
    matches the unsharded quantized decode."""
    config = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=256, max_seq=32),
        dtype="float32")
    params = quantize_params(grid_aligned_params(config))
    plan = MeshPlan.build({"dp": 2, "fsdp": 2, "tp": 2})
    sharded = plan.put(params, quantize_specs(
        llama.partition_specs(config)))
    cache_sharding = jax.tree_util.tree_map(
        plan.shard, llama.cache_specs(config))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0, 256)

    _, ref_cache = llama.prefill(params, config, tokens[:, :8],
                                 llama.init_cache(config, 2, 32),
                                 jnp.zeros(2, dtype=jnp.int32))
    ref_step, _ = llama.decode_step(params, config, tokens[:, 8],
                                    ref_cache,
                                    jnp.full((2,), 8, jnp.int32))

    cache = jax.device_put(llama.init_cache(config, 2, 32),
                           cache_sharding)
    _, cache = llama.prefill(sharded, config,
                             jax.device_put(tokens[:, :8],
                                            plan.shard(P("dp", None))),
                             cache, jnp.zeros(2, dtype=jnp.int32))
    tp_step, _ = llama.decode_step(sharded, config, tokens[:, 8], cache,
                                   jnp.full((2,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(tp_step, dtype=np.float32),
                               np.asarray(ref_step, dtype=np.float32),
                               atol=2e-3)


# -- int8 KV cache -------------------------------------------------------


def test_kv_quantized_attention_matches_dequantized():
    """Prefill over a quantized cache equals attention over the
    explicitly dequantized cache to float rounding (the scale folding
    is exact math).  The decode-append path ADDITIONALLY quantizes the
    query and the softmax weights so both cache matmuls run as native
    int8 MXU dots (ops/layers.py) -- bounded-approximate there, with
    error at the int8 step size, not float rounding."""
    from aiko_services_tpu.ops.layers import (attention_decode_append,
                                              attention_prefill)
    key = jax.random.PRNGKey(0)
    b, s, t, h, kv, hd = 2, 4, 16, 4, 2, 8
    q = jax.random.normal(key, (b, s, h, hd), dtype=jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, hd),
                          dtype=jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, hd),
                          dtype=jnp.float32)
    kq, vq = quantize_kv(k), quantize_kv(v)
    kd = dequantize_kv(kq, jnp.float32)
    vd = dequantize_kv(vq, jnp.float32)
    positions = jnp.tile(jnp.arange(4, 4 + s)[None, :], (b, 1))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(attention_prefill(q, kq, vq, positions)),
            np.asarray(attention_prefill(q, kd, vd, positions)),
            atol=1e-5)
        k_new = jax.random.normal(jax.random.fold_in(key, 3),
                                  (b, 1, kv, hd), dtype=jnp.float32)
        v_new = jax.random.normal(jax.random.fold_in(key, 4),
                                  (b, 1, kv, hd), dtype=jnp.float32)
        lengths = jnp.array([5, 9])
        np.testing.assert_allclose(
            np.asarray(attention_decode_append(q[:, :1], kq, vq, k_new,
                                               v_new, lengths)),
            np.asarray(attention_decode_append(q[:, :1], kd, vd, k_new,
                                               v_new, lengths)),
            atol=3e-2)


def test_kv_cache_int8_serving_paths():
    """kv_dtype="int8": prefill/prefill_into_slot/decode_step run on the
    quantized cache and track the bf16-cache logits closely (per-token
    scales bound the cache error at ~0.4%)."""
    base = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=256, max_seq=32),
        dtype="float32")
    int8 = dataclasses.replace(base, kv_dtype="int8")
    params = llama.init_params(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 9), 0, 256)

    logits_a, cache_a = llama.prefill(
        params, base, tokens[:, :8], llama.init_cache(base, 2, 32),
        jnp.zeros(2, dtype=jnp.int32))
    logits_b, cache_b = llama.prefill(
        params, int8, tokens[:, :8], llama.init_cache(int8, 2, 32),
        jnp.zeros(2, dtype=jnp.int32))
    assert cache_b["k"]["int8"].dtype == jnp.int8
    assert cache_b["k"]["scale"].shape == (base.n_layers, 2, 32,
                                           base.n_kv_heads, 1)
    np.testing.assert_allclose(np.asarray(logits_a),
                               np.asarray(logits_b), atol=5e-2)

    step_a, _ = llama.decode_step(params, base, tokens[:, 8], cache_a,
                                  jnp.full((2,), 8, jnp.int32))
    step_b, _ = llama.decode_step(params, int8, tokens[:, 8], cache_b,
                                  jnp.full((2,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(step_a), np.asarray(step_b),
                               atol=5e-2)

    # Slot admission writes the quantized cache in place.
    cache = llama.init_cache(int8, 2, 32)
    logits, cache = llama.prefill_into_slot(
        params, int8, tokens[:1, :8], cache, jnp.int32(1), jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(logits_b[0]), atol=5e-2)
    assert int(np.abs(np.asarray(cache["k"]["int8"][:, 0])).max()) == 0


def test_kv_cache_int8_halves_cache_bytes():
    int8 = dataclasses.replace(llama.LlamaConfig.tiny(),
                               kv_dtype="int8")
    cache = llama.init_cache(int8, 2, 32)
    bf16 = llama.init_cache(llama.LlamaConfig.tiny(), 2, 32)
    quantized_bytes = cache["k"]["int8"].nbytes \
        + cache["k"]["scale"].nbytes
    # Ratio = (hd + 4) / (2*hd): 0.625 at the tiny config's hd=16,
    # 0.53 at a real model's hd=64.
    hd = int8.head_dim
    assert quantized_bytes == bf16["k"].nbytes * (hd + 4) / (2 * hd)


def test_batcher_serves_int8_kv_cache():
    """End-to-end serving on int8 weights AND int8 KV cache, chained
    device-loop blocks included; token streams keep their budget/EOS
    semantics."""
    from aiko_services_tpu.models import ContinuousBatcher, Request
    from aiko_services_tpu.models.tokenizer import ByteTokenizer

    config = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 kv_dtype="int8")
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), config))
    tok = ByteTokenizer()
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=2)
    for i in range(3):
        batcher.submit(Request(f"r{i}", tok.encode(f"aloha {i}"),
                               max_new_tokens=6, emit=emit))
    steps = batcher.run_until_drained(max_steps=300)
    assert steps < 300
    assert sorted(emitted) == ["r0", "r1", "r2"]
    assert all(len(tokens) == 6 for tokens in emitted.values())


def test_batcher_tp_sharded_quantized_serving():
    """The flagship multichip serving config: TP-sharded quantized tree
    + TP-sharded cache through a real batcher drain on the 8-device
    mesh."""
    from aiko_services_tpu.models import ContinuousBatcher, Request

    config = llama.LlamaConfig.tiny()
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), config))
    plan = MeshPlan.build({"dp": 2, "fsdp": 2, "tp": 2})
    sharded = plan.put(params, quantize_specs(
        llama.partition_specs(config)))
    cache_sharding = jax.tree_util.tree_map(
        plan.shard, llama.cache_specs(config))
    out = []
    batcher = ContinuousBatcher(
        sharded, config, max_slots=2, max_seq=64, prefill_chunk=16,
        decode_block_tokens=4, inflight=2,
        cache_put=lambda c: jax.device_put(c, cache_sharding))
    batcher.submit(Request("r", [1, 2, 3], max_new_tokens=6,
                           emit=lambda r, t, f: out.append(t)))
    steps = batcher.run_until_drained(max_steps=200)
    assert steps < 200
    assert len(out) == 6
