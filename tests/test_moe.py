"""Mixture-of-experts FFN on the ``ep`` axis (models/llama.py
_moe_ffn + partition_specs; SURVEY §2.5: EP is a first-class axis of
the TPU build -- the reference has no parallelism at all, so this is
the build's own bar).

Covers: parameter/spec structure, exactness of the routed layer against
the dense FFN when routing is trivial (1 expert), ep-sharded vs
unsharded equivalence on the CPU mesh, capacity-drop semantics, the
load-balance aux loss, serving through the continuous batcher, int8
expert weights, and MoE training.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.quant import quantize_params, quantize_specs
from aiko_services_tpu.parallel import MeshPlan, P

def f32(config):
    return dataclasses.replace(config, dtype="float32")


def test_moe_param_and_spec_structure():
    config = llama.LlamaConfig.tiny_moe()
    params = llama.init_params(jax.random.PRNGKey(0), config)
    layers = params["layers"]
    e, d, f = config.n_experts, config.dim, config.hidden_dim
    assert layers["w_router"].shape == (config.n_layers, d, e)
    assert layers["w_gate"].shape == (config.n_layers, e, d, f)
    assert layers["w_down"].shape == (config.n_layers, e, f, d)
    specs = llama.partition_specs(config)
    # Structure matches: tree_map over (params, specs) must not raise.
    jax.tree_util.tree_map(lambda leaf, s: None, params, specs)
    assert specs["layers"]["w_gate"] == P(None, "ep", "fsdp", "tp")
    assert specs["layers"]["w_router"] == P(None, "fsdp", None)


def test_single_expert_equals_dense_ffn():
    """E=1, k=1 routing is the identity: the MoE block must reproduce
    the dense FFN exactly (gates renormalize to 1, capacity holds every
    token)."""
    dense_config = f32(llama.LlamaConfig.tiny(vocab_size=128,
                                              max_seq=32))
    moe_config = dataclasses.replace(dense_config, n_experts=1,
                                     n_experts_per_token=1)
    dense_params = llama.init_params(jax.random.PRNGKey(0), dense_config)
    moe_params = jax.tree_util.tree_map(lambda x: x, dense_params)
    layers = dict(moe_params["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        layers[name] = layers[name][:, None]        # [L,1,D,F]
    layers["w_router"] = jnp.zeros(
        (moe_config.n_layers, moe_config.dim, 1), dtype=jnp.float32)
    moe_params["layers"] = layers

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 128)
    with jax.default_matmul_precision("highest"):
        dense_logits, _ = llama.prefill(
            dense_params, dense_config, tokens,
            llama.init_cache(dense_config, 2, 32),
            jnp.zeros(2, dtype=jnp.int32))
        moe_logits, _ = llama.prefill(
            moe_params, moe_config, tokens,
            llama.init_cache(moe_config, 2, 32),
            jnp.zeros(2, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(moe_logits),
                               np.asarray(dense_logits), atol=1e-4)


def test_ep_sharded_matches_unsharded():
    """Expert weights sharded over ep on the 8-device mesh produce the
    same logits as the unsharded forward (XLA derives the expert
    collectives from the partition specs)."""
    config = f32(llama.LlamaConfig.tiny_moe(vocab_size=128, max_seq=32))
    params = llama.init_params(jax.random.PRNGKey(0), config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)

    with jax.default_matmul_precision("highest"):
        ref_logits, _ = llama.prefill(
            params, config, tokens, llama.init_cache(config, 2, 32),
            jnp.zeros(2, dtype=jnp.int32))

        plan = MeshPlan.build({"dp": 2, "ep": 4})
        sharded = plan.put(params, llama.partition_specs(config))
        cache = jax.device_put(
            llama.init_cache(config, 2, 32),
            jax.tree_util.tree_map(plan.shard, llama.cache_specs(config)))
        ep_logits, _ = llama.prefill(
            sharded, config,
            jax.device_put(tokens, plan.shard(P("dp", None))), cache,
            jnp.zeros(2, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(ep_logits),
                               np.asarray(ref_logits), atol=1e-4)


def test_capacity_drop_keeps_residual():
    """With a tiny capacity some (token, expert) routes drop; outputs
    stay finite and the dropped tokens keep their residual stream."""
    config = f32(dataclasses.replace(
        llama.LlamaConfig.tiny_moe(vocab_size=128, max_seq=32),
        capacity_factor=0.1))
    params = llama.init_params(jax.random.PRNGKey(0), config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    logits, _ = llama.prefill(params, config, tokens,
                              llama.init_cache(config, 2, 32),
                              jnp.zeros(2, dtype=jnp.int32))
    assert bool(jnp.isfinite(logits).all())
    # Capacity respects the config: 0.1 * 32 tokens * 2 / 4 experts
    # -> ceil to the 8-sublane tile.
    assert config.moe_capacity(32) == 8


def test_load_balance_aux():
    """Aux loss is exactly 1.0 under uniform router probabilities and
    approaches E/k as routing collapses onto one expert."""
    config = f32(llama.LlamaConfig.tiny_moe(vocab_size=128, max_seq=64))
    e, d, f = config.n_experts, config.dim, config.hidden_dim
    key = jax.random.PRNGKey(0)
    layer = {
        "w_router": jnp.zeros((d, e), dtype=jnp.float32),
        "w_gate": 0.02 * jax.random.normal(key, (e, d, f)),
        "w_up": 0.02 * jax.random.normal(jax.random.fold_in(key, 1),
                                         (e, d, f)),
        "w_down": 0.02 * jax.random.normal(jax.random.fold_in(key, 2),
                                           (e, f, d)),
    }
    # All-positive activations so a positive router column dominates.
    x = jax.random.uniform(jax.random.fold_in(key, 3), (1, 16, d),
                           minval=0.5, maxval=1.0)
    _, aux_uniform = llama._moe_ffn(config, x, layer)
    assert abs(float(aux_uniform) - 1.0) < 1e-5

    collapsed = dict(layer)
    collapsed["w_router"] = layer["w_router"].at[:, 0].set(10.0)
    _, aux_collapsed = llama._moe_ffn(config, x, collapsed)
    assert float(aux_collapsed) > 1.8      # -> E/k = 2 at full collapse


def test_moe_serving_through_batcher():
    """The continuous batcher serves an MoE config end to end (decode
    routes single tokens; chunked admission routes chunk tokens)."""
    from aiko_services_tpu.models import ContinuousBatcher, Request

    config = llama.LlamaConfig.tiny_moe()
    params = llama.init_params(jax.random.PRNGKey(0), config)
    emitted = {}
    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=2)
    for i in range(3):
        batcher.submit(Request(
            f"r{i}", list(range(1, 8 + i)), max_new_tokens=5,
            emit=lambda r, t, f: emitted.setdefault(r, []).append(t)))
    steps = batcher.run_until_drained(max_steps=300)
    assert steps < 300
    assert sorted(emitted) == ["r0", "r1", "r2"]
    assert all(len(t) == 5 for t in emitted.values())


def test_quantized_moe_forward():
    """Weight-only int8 quantizes the expert-stacked weights too
    (per-output-channel scales broadcast over the capacity axis); on
    grid-aligned weights the forward matches the raw tree."""
    config = f32(llama.LlamaConfig.tiny_moe(vocab_size=256, max_seq=32))
    params = _align_moe(
        llama.init_params(jax.random.PRNGKey(0), config))
    quantized = quantize_params(params)
    assert quantized["layers"]["w_gate"]["int8"].shape \
        == params["layers"]["w_gate"].shape
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 256)
    raw_logits, _ = llama.prefill(params, config, tokens,
                                  llama.init_cache(config, 2, 32),
                                  jnp.zeros(2, dtype=jnp.int32))
    q_logits, _ = llama.prefill(quantized, config, tokens,
                                llama.init_cache(config, 2, 32),
                                jnp.zeros(2, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(raw_logits),
                               np.asarray(q_logits), atol=2e-3)


def _align_moe(params):
    """Grid-align the quantizable weights of an MoE tree (see
    test_quant.grid_aligned_params; that helper builds its own dense
    params, so MoE re-applies the alignment here)."""
    from aiko_services_tpu.models.quant import QUANTIZED_LAYER_KEYS
    key = jax.random.PRNGKey(42)

    def align(weight):
        nonlocal key
        key, sub1, sub2 = jax.random.split(key, 3)
        levels = jax.random.randint(sub1, weight.shape, -127, 128)
        levels = levels.at[..., 0, :].set(127)
        scale = jax.random.uniform(sub2, weight.shape[-1:],
                                   minval=0.5, maxval=2.0) / 127.0
        return (levels * scale).astype(weight.dtype) * 0.05

    layers = dict(params["layers"])
    for name in QUANTIZED_LAYER_KEYS:
        layers[name] = align(layers[name])
    out = dict(params)
    out["layers"] = layers
    out["unembed"] = align(params["unembed"])
    return out


def test_quantized_moe_specs_shard():
    """quantize_specs maps the MoE layout (4-D expert weights) onto the
    quantized structure; the sharded tree decodes on the mesh."""
    config = llama.LlamaConfig.tiny_moe()
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), config))
    specs = quantize_specs(llama.partition_specs(config))
    assert specs["layers"]["w_gate"]["int8"] == P(None, "ep", "fsdp",
                                                  "tp")
    assert specs["layers"]["w_gate"]["scale"] == P(None, "ep", None,
                                                   "tp")
    plan = MeshPlan.build({"dp": 2, "ep": 2, "tp": 2})
    sharded = plan.put(params, specs)
    cache = jax.device_put(
        llama.init_cache(config, 2, 32),
        jax.tree_util.tree_map(plan.shard, llama.cache_specs(config)))
    logits, _ = llama.decode_step(sharded, config,
                                  jnp.zeros(2, dtype=jnp.int32), cache,
                                  jnp.zeros(2, dtype=jnp.int32))
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_moe_train_step_learns():
    """Sharded MoE training on a dp x ep x tp mesh: loss (CE + aux)
    decreases on a repeated batch."""
    from aiko_services_tpu.models.train import (init_train_state,
                                                make_train_step)

    config = llama.LlamaConfig.tiny_moe(vocab_size=128, max_seq=64)
    plan = MeshPlan.build({"dp": 2, "ep": 2, "tp": 2})
    params, opt_state, optimizer = init_train_state(
        jax.random.PRNGKey(0), config, plan)
    step = make_train_step(config, plan, optimizer=optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, 128)
    params, opt_state, loss1 = step(params, opt_state, tokens)
    params, opt_state, loss2 = step(params, opt_state, tokens)
    assert np.isfinite(float(loss1))
    assert float(loss2) < float(loss1)
