"""The DeepSeek-V3 family (models/deepseek.py: latent attention over a
paged latent cache, a leading dense layer, drop-less sigmoid-routed
experts) at a tiny preset on the CPU, against its plain float32
reference (benchmark/architectures/deepseek_v3.py), through the same
ContinuousBatcher and LLM element as the Llama family (ISSUE 29)."""

import dataclasses
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import deepseek, llama
from aiko_services_tpu.models.batching import (ContinuousBatcher, Request,
                                               model_family)
from aiko_services_tpu.models.families import family_spec_error
from aiko_services_tpu.models.paged import init_paged_cache, latent_pages
from benchmark.architectures import deepseek_v3 as reference

from conftest import run_until


def _tiny(dtype="float32", **fields):
    return dataclasses.replace(deepseek.DeepseekConfig.tiny(),
                               dtype=dtype, **fields)


@pytest.fixture(scope="module")
def weights():
    built = {}

    def get(dtype="float32", **fields):
        key = (dtype, tuple(sorted(fields.items())))
        if key not in built:
            config = _tiny(dtype, **fields)
            built[key] = (config, deepseek.init_params(
                jax.random.PRNGKey(3), config))
        return built[key]
    return get


def _batcher(config, params, chunk=64, page=32, **settings):
    return ContinuousBatcher(params, config, max_slots=3, max_seq=256,
                             prefill_chunk=chunk, kv_page_tokens=page,
                             **settings)


# (a) prefill then decode through latent pages == the reference's full
# forward pass, on logits, over chunk boundaries and page sizes.

@pytest.mark.parametrize("chunk,page,prompt", [
    (64, 32, 100), (32, 16, 100), (128, 64, 100), (64, 64, 64),
    (64, 16, 65)])
def test_latent_cache_matches_reference_float32(weights, chunk, page,
                                                prompt):
    config, params = weights()
    result = reference.compare(_batcher(config, params, chunk, page), 11,
                               prompt, 4)
    assert result["positions"] == 5
    assert result["max_abs_diff"] < 2e-4, result
    assert result["argmax_agree"] == 5
    assert result["router_flips"] == 0
    assert result["router_choices"] == 2 * (prompt + 4)


def test_kernels_by_name_match_reference(weights):
    """``attention: flash`` (the Pallas prefill kernel over the expanded
    keys and values, head width nope + rope, the values padded to it)
    and ``decode_attention: flash`` (``ops/pallas_latent.py``: the page
    table walked in-kernel), both interpreted off the chip, end to end
    at pages of 128 tokens."""
    config, params = weights(attention="flash", decode_attention="flash")
    result = reference.compare(_batcher(config, params, 128, 128), 11,
                               150, 4)
    assert result["max_abs_diff"] < 2e-4, result
    assert result["router_flips"] == 0


def test_latent_cache_matches_reference_bfloat16(weights):
    """As served (bfloat16 weights and cache): inside a tolerance the
    fp8-cache control of the benchmark fails."""
    config, params = weights("bfloat16")
    batcher = _batcher(config, params)
    served = reference.compare(batcher, 11, 100, 4, free=True)
    assert served["max_abs_diff"] < 0.1, served
    for control in ("fp8_cache", "fp8_activations"):
        failed = reference.compare(batcher, 11, 100, 4, control=control)
        assert failed["max_abs_diff"] > 1.5 * served["max_abs_diff"], (
            control, served, failed)
    # flips are counted, each a near-tie in the reference's own scores
    assert served["router_not_near_ties"] == 0
    assert served["router_flips"] <= served["router_choices"] // 10
    assert served["free_max_abs_diff"] < 0.2
    # the check left the batcher's pool as it found it
    assert batcher._pages.free_pages == batcher._pages.total - 1


def test_reference_follows_only_near_ties():
    """The reference takes the served side's selection where it is a
    near-tie in its own scores and keeps its own anywhere else."""
    scores = jnp.linspace(2.0, -2.0, 8)             # experts 0..7 falling
    w_router = jnp.zeros((4, 8)).at[0].set(scores)
    h = jnp.zeros((2, 4)).at[:, 0].set(1.0)
    sigma = np.asarray(jax.nn.sigmoid(scores))
    near = sigma[2] - sigma[3] + 0.001
    bias = jnp.zeros(8).at[3].set(near - 0.002)     # 3 just under 2
    given = jnp.asarray([[0, 1, 3], [0, 1, 7]])     # near-tie; far off
    _, routed, own, short = reference._route(
        h, w_router, bias, given, top_k=3, scale=1.0)
    assert np.asarray(own).tolist() == [[0, 1, 2], [0, 1, 2]]
    assert np.asarray(routed).tolist() == [[0, 1, 3], [0, 1, 2]]
    assert short[0] < reference.NEAR_TIE < short[1]


# (b) absorbed decode == expanded attention on the same cache rows.

def test_absorbed_equals_expanded(weights):
    config, params = weights()
    layer = jax.tree_util.tree_map(lambda leaf: leaf[0], params["sparse"])
    rng = np.random.default_rng(0)
    batch, extent, lengths = 2, 48, np.array([17, 40])
    rope_table = deepseek.rope_frequencies(
        config.qk_rope_head_dim, 256, config.rope_theta)
    hidden = jnp.asarray(rng.standard_normal((batch, extent, config.dim)),
                         jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(extent), (batch, extent))
    q_nope, q_rope, rows = deepseek._latent_entry(
        config, layer, hidden, rope_table, positions)
    at = jnp.asarray(lengths)
    pick = (jnp.arange(batch), at)
    pages = latent_pages(rows, 16)                  # the pool's own form
    # the query at position ``lengths``: expanded over rows 0..lengths
    expanded = deepseek._attend_expanded(
        config, layer, q_nope[pick][:, None], q_rope[pick][:, None],
        pages, at[:, None])[:, 0]
    absorbed = deepseek._attend_absorbed(
        config, layer, q_nope[pick], q_rope[pick], rows[pick],
        lambda query: deepseek._cached_dense(
            query, pages, at, config.qk_head_dim ** -0.5))
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5)


@pytest.mark.parametrize("pages_per_step", [1, 4])
def test_latent_decode_kernel_matches_dense(pages_per_step):
    """``ops/pallas_latent.py`` (interpreted): the page table walked
    in-kernel == the gathered-pages reference, on ragged lengths --
    an empty row, a row ending mid-page, a full one."""
    from aiko_services_tpu.ops.pallas_latent import \
        latent_decode_attention_paged
    rng = np.random.default_rng(5)
    layers, pool_pages, width, pt, heads, pps = 2, 11, 40, 128, 4, 4
    pool = jnp.asarray(rng.standard_normal(
        (layers, pool_pages, width, pt)), jnp.float32)
    table = jnp.asarray([[3, 7, 1, 9], [2, 0, 0, 0], [5, 4, 8, 6],
                         [0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([300, 77, 512, 0], jnp.int32)
    query = jnp.asarray(rng.standard_normal((4, heads, width)),
                        jnp.float32)
    got = latent_decode_attention_paged(
        query, pool, jnp.int32(1), table, lengths, scale=0.25,
        pages_per_step=pages_per_step, interpret=True)
    want = deepseek._cached_dense(query, pool[1][table], lengths, 0.25)
    for ours, theirs in zip(got, want):
        np.testing.assert_allclose(ours, theirs, rtol=2e-5, atol=2e-5)
    assert float(got[2][3].max()) == 0.0 and float(got[1][3].max()) < -1e29


# (c) the routed feed-forward == the dense every-expert form, under
# skewed routers too: no token is ever dropped.

@pytest.mark.parametrize("skew", ["uniform", "three-experts",
                                  "one-expert", "valid-mask",
                                  "megablox"])
def test_routed_ffn_drops_nothing(weights, skew):
    top_k = 1 if skew == "one-expert" else 3
    config, params = weights(n_experts_per_token=top_k)
    if skew == "megablox":      # the Pallas grouped matmul, interpreted
        config = dataclasses.replace(config, grouped_matmul="megablox")
    layer = jax.tree_util.tree_map(lambda leaf: leaf[1], params["sparse"])
    bias = np.zeros(config.n_experts, np.float32)
    if skew == "three-experts":
        bias[[1, 4, 6]] = 10.0
    if skew == "one-expert":
        bias[5] = 10.0
    layer = {**layer, "router_bias": jnp.asarray(bias)}
    tokens = 40
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (tokens, config.dim)), jnp.float32)
    valid = None
    if skew in ("valid-mask", "megablox"):
        valid = jnp.arange(tokens) % 3 != 0
    out, chosen, sizes = deepseek.routed_ffn(config, h, layer, valid)
    with jax.default_matmul_precision("highest"):
        share, expected_chosen, _, _ = reference._route(
            h, layer["w_router"], layer["router_bias"], top_k=top_k,
            scale=config.routed_scaling_factor)
        expected = reference._experts(h, share, layer["experts"])
    counted = tokens if valid is None else int(valid.sum())
    assert int(sizes.sum()) == counted * top_k        # every pair computed
    if skew == "three-experts":
        assert set(np.flatnonzero(sizes)) == {1, 4, 6}
    if skew == "one-expert":
        assert int(sizes[5]) == tokens
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(expected_chosen, -1))
    if valid is not None:
        expected = jnp.where(valid[:, None], expected, 0.0)
    np.testing.assert_allclose(out, expected, atol=2e-5)
    assert float(jnp.abs(expected).max()) > 0.01


# (d) selection by sigma + b, gates from sigma alone, scaled.

def test_bias_selects_but_does_not_gate():
    config = _tiny()
    experts = config.n_experts
    # one token whose scores fall from expert 0 to expert 7
    logits = jnp.linspace(2.0, -2.0, experts)
    w_router = jnp.zeros((config.dim, experts)).at[0].set(logits)
    h = jnp.zeros((1, config.dim)).at[0, 0].set(1.0)
    sigma = np.asarray(jax.nn.sigmoid(logits))
    plain, _ = deepseek.route(config, h, w_router, jnp.zeros(experts))
    assert sorted(np.asarray(plain)[0]) == [0, 1, 2]
    bias = jnp.zeros(experts).at[7].set(5.0)    # lifts the LAST expert
    chosen, gates = deepseek.route(config, h, w_router, bias)
    chosen, gates = np.asarray(chosen)[0], np.asarray(gates)[0]
    assert sorted(chosen) == [0, 1, 7]
    expected = config.routed_scaling_factor * sigma[chosen] \
        / sigma[chosen].sum()
    np.testing.assert_allclose(gates, expected, rtol=1e-6)
    np.testing.assert_allclose(gates.sum(), config.routed_scaling_factor,
                               rtol=1e-6)
    # had the bias leaked into the gate, expert 7 would weigh most
    assert gates[list(chosen).index(7)] == gates.min()


# (e) one process, both families, through one batcher class.

def _generate(batcher, prompts, new_tokens=6):
    out = {}
    for index, prompt in enumerate(prompts):
        batcher.submit(Request(
            request_id=str(index), prompt_tokens=list(prompt),
            max_new_tokens=new_tokens,
            emit=lambda rid, token, finished:
                out.setdefault(rid, []).append(token)))
    batcher.run_until_drained()
    return out


def test_batcher_serves_both_families(weights):
    prompts = [np.random.default_rng(i).integers(1, 500, 20 + 25 * i)
               for i in range(5)]
    tiny = llama.LlamaConfig.tiny()
    llama_params = llama.init_params(jax.random.PRNGKey(0), tiny)

    def llama_batcher():
        return ContinuousBatcher(llama_params, tiny, max_slots=3,
                                 prefill_chunk=64, kv_page_tokens=32,
                                 decode_block_tokens=4)
    before = _generate(llama_batcher(), prompts)
    config, params = weights()
    assert model_family(config) is deepseek and model_family(tiny) is llama
    latent = _batcher(config, params, decode_block_tokens=4)
    served = _generate(latent, prompts)
    assert all(len(tokens) == 6 for tokens in served.values())
    assert len(served) == len(prompts)
    assert latent._pages.free_pages == latent._pages.total - 1
    # the loop's device path == step-by-step decode of the same family
    assert served == _generate(_batcher(config, params), prompts)
    # and the Llama family answers as it did before the other was built
    assert _generate(llama_batcher(), prompts) == before


def test_decode_loop_counts_experts(weights):
    """The block statistics ride the block's fetch: experts that got a
    live row, the fullest expert's rows over the mean."""
    config, params = weights()
    batcher = _batcher(config, params, decode_block_tokens=4)
    events = []
    batcher.trace = lambda name, ms, info: events.append((name, info))
    _generate(batcher, [range(1, 40), range(5, 30)])
    blocks = batcher.take_block_stats()
    assert blocks and batcher.take_block_stats() == []
    for block in blocks:
        assert 1.0 <= block["moe_experts_touched"] <= config.n_experts
        assert block["moe_load_imbalance"] >= 1.0
    assert [info for name, info in events
            if name == "demux" and info] == blocks


# (f) what the latent family cannot serve raises at create time and
# names its parameter.

@pytest.mark.parametrize("settings,named", [
    ({"kv_page_tokens": 0}, "kv_page_tokens"),
    ({"speculative": "ngram", "decode_block_tokens": 8}, "speculative"),
    ({"speculative": "draft", "decode_block_tokens": 8}, "speculative"),
    ({"prefix_cache": "on"}, "prefix_cache"),
])
def test_batcher_refuses_by_name(weights, settings, named):
    config, params = weights()
    with pytest.raises(ValueError, match=named):
        ContinuousBatcher(params, config, max_slots=2, max_seq=256,
                          prefill_chunk=64,
                          **{"kv_page_tokens": 32, **settings})


def test_latent_pool_refuses_int8():
    with pytest.raises(ValueError, match="kv_dtype"):
        _tiny(kv_dtype="int8")
    config = _tiny()
    cache = init_paged_cache(config, 2, 256, 32)
    assert set(cache) == {"latent", "page_table"}
    assert cache["latent"].shape == (3, 2 * 8 + 1, 40, 32)


@pytest.mark.parametrize("parameters,named", [
    ({"family": "mamba"}, "family"),
    ({"widths": {"hidden_size": 64}}, "widths"),
    ({"family": "deepseek_v3", "widths": {"num_key_value_heads": 4}},
     "num_key_value_heads"),
    ({"family": "llama", "widths": {"kv_lora_rank": 4}}, "kv_lora_rank"),
    ({"family": "deepseek_v3", "widths": {"hidden_size": "wide"}},
     "hidden_size"),
    ({"family": "deepseek_v3", "quantize": "int8"}, "quantize"),
    ({"family": "deepseek_v3", "spec_tokens": 4}, "spec_tokens"),
    ({"family": "deepseek_v3", "speculative": "ngram"}, "speculative"),
    ({"family": "deepseek_v3", "prefix_cache": "on"}, "prefix_cache"),
    ({"family": "llama", "model": "tiny"}, "model"),
    ({"family": "deepseek_v3", "decode_block": 1}, "decode_block_tokens"),
])
def test_family_parameters_refused_by_name(parameters, named):
    assert named in family_spec_error(parameters)


def test_family_parameters_accepted():
    assert family_spec_error({"model": "tiny"}) is None
    assert family_spec_error({
        "family": "deepseek_v3", "quantize": "off", "speculative": "off",
        "widths": {"hidden_size": 64, "routed_scaling_factor": 2.5}}) \
        is None
    config = llama.LlamaConfig.from_widths(
        {"hidden_size": 64, "num_hidden_layers": 2, "rope_theta": 1e4})
    assert (config.dim, config.n_layers, config.rope_theta) == (64, 2, 1e4)


# (g) through the LLM element: family + widths, and the telemetry.

TINY_WIDTHS = {"hidden_size": 64, "num_hidden_layers": 3,
               "num_attention_heads": 4, "kv_lora_rank": 32,
               "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "v_head_dim": 16, "intermediate_size": 128,
               "moe_intermediate_size": 32, "n_routed_experts": 8,
               "num_experts_per_tok": 3, "n_shared_experts": 2,
               "first_k_dense_replace": 1, "vocab_size": 512}


def _definition(name, parameters):
    return {
        "version": 0, "name": name, "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 8, "max_seq": 128,
                           "decode_block_tokens": 4, "kv_page_tokens": 16,
                           **parameters},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}


def test_llm_element_serves_the_latent_family(runtime):
    from aiko_services_tpu.pipeline import Pipeline
    responses = queue.Queue()
    pipeline = Pipeline(_definition("latent_llm", {
        "family": "deepseek_v3", "widths": TINY_WIDTHS}), runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    prompts = ["hello there", "general kenobi", "you are a bold one"]
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=180.0)
    assert run_until(runtime, lambda: "llm_moe_experts_touched"
                     in pipeline.metrics_text())
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert isinstance(batcher.config, deepseek.DeepseekConfig)
    assert batcher.config.dim == 64 and batcher.config.max_seq == 128
    assert set(batcher.cache) == {"latent", "page_table"}
    registry = pipeline.telemetry.registry
    touched = registry.quantile("llm_moe_experts_touched", 0.5, None,
                                windowed=False)
    imbalance = registry.quantile("llm_moe_load_imbalance", 0.5, None,
                                  windowed=False)
    assert 1.0 <= touched <= 8.0 * 1.1 and imbalance >= 0.9
    # (the worker publishes a request's stamps after the tick that
    # finished it: the response may reach this thread first)
    assert run_until(runtime,
                     lambda: "llm_ttft_ms" in pipeline.metrics_text())
    demux = [event for event in pipeline.recorder.snapshot()
             if event[1] == "llm_tick" and event[4] == "demux"
             and event[6]]
    assert demux and "moe_experts_touched" in demux[0][6]
    pipeline.stop()


def test_llm_element_refuses_at_create_time():
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    findings = validate_element_parameters(
        "LLM", {"family": "deepseek_v3", "widths": TINY_WIDTHS,
                "quantize": "int8"}, "elements[0]",
        module="aiko_services_tpu.elements.llm")
    assert [finding.rule for finding in findings] == ["bad-parameter"]
    assert "quantize" in findings[0].message
    assert validate_element_parameters(
        "LLM", {"family": "deepseek_v3", "widths": TINY_WIDTHS},
        "elements[0]", module="aiko_services_tpu.elements.llm") == []
