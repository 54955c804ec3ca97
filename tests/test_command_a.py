"""The Command A family (models/command_a.py: window layers with GPT-J
rotary beside global NoPE layers, a parallel attention + experts block,
a chip's share of sigmoid-routed experts beside averaged shared ones)
at a tiny preset on the CPU, against its plain float32 reference
(benchmark/architectures/cohere2_moe.py), through the same
ContinuousBatcher and LLM element as the other families -- and the
window pages its cache gives back (models/paged.py)."""

import dataclasses
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import command_a, deepseek, moe, sdar
from aiko_services_tpu.models.batching import (ContinuousBatcher, Request,
                                               model_family)
from aiko_services_tpu.models.families import family_spec_error
from aiko_services_tpu.models.paged import PageAllocator
from aiko_services_tpu.ops.layers import apply_rope, attention_prefill
from benchmark.architectures import cohere2_moe as reference

from conftest import run_until


def _tiny(dtype="float32", **fields):
    return dataclasses.replace(command_a.CommandAConfig.tiny(),
                               dtype=dtype, **fields)


@pytest.fixture(scope="module")
def weights():
    """(config, params) by dtype and fields, by the serving init."""
    built = {}

    def get(dtype="float32", **fields):
        key = (dtype, tuple(sorted(fields.items())))
        if key not in built:
            config = _tiny(dtype, **fields)
            built[key] = (config, command_a.init_params(
                jax.random.PRNGKey(5), config))
        return built[key]
    return get


def _batcher(config, params, slots=3, **settings):
    """A window of 16 over pages of 8 and chunks of 16: a slot's window
    layers hold at most (16 + 16) / 8 + 1 = 5 of its 8 pages."""
    return ContinuousBatcher(
        params, config, max_slots=slots, max_seq=64, prefill_chunk=16,
        kv_page_tokens=8, **{"decode_block_tokens": 4, **settings})


def _serve(batcher, requests, before_step=None):
    """``requests``: (prompt, new tokens).  Returns the tokens emitted,
    by request, and the most window pages a slot held after any step."""
    out = {index: [] for index in range(len(requests))}
    for index, (prompt, new_tokens) in enumerate(requests):
        batcher.submit(Request(
            request_id=str(index), prompt_tokens=list(prompt),
            max_new_tokens=new_tokens,
            emit=lambda rid, token, finished:
                out[int(rid)].append(token)))
    steps = fullest = 0
    while batcher.pending or batcher.active_count \
            or batcher.blocks_in_flight:
        if before_step is not None:
            before_step(steps)
        batcher.step()
        fullest = max([fullest] + [batcher._pages.window_holds(slot)
                                   for slot in range(batcher.max_slots)])
        steps += 1
        assert steps < 10_000
    return out, fullest


# (a) prefill, then decode through the pages and the loop's own body,
# across window boundaries, against the reference's full forward.

@pytest.mark.parametrize("attention,kernels,prompt", [
    ("dense", "off", 37), ("flash", "on", 37), ("dense", "off", 9)])
def test_served_logits_match_reference(weights, attention, kernels,
                                       prompt):
    """A 37-token prompt is three chunks of 16, 21 past the window of
    16; twelve decode steps cross two more pages, at each of which a
    window page goes back -- every chunk's last logits and every step's
    within float32's rounding of the reference."""
    config, params = weights(attention=attention, kernels=kernels)
    result = reference.compare(_batcher(config, params), 7, prompt, 12)
    assert result["max_abs_diff"] < 2e-3 * result["logit_std"]
    assert result["argmax_agree"] == result["positions"]
    assert result["router_not_near_ties"] == 0
    assert result["window_pages_held"] <= 5
    if prompt > 16:
        assert result["window_pages_released"] > 0


def test_reference_controls_fail(weights):
    """The two controls the chip's check must fail: fp8 activations,
    and the window layers attending their whole causal past."""
    config, params = weights()
    batcher = _batcher(config, params)
    for control in ("fp8_activations", "causal_windows"):
        result = reference.compare(batcher, 7, 37, 12, control)
        assert result["max_abs_diff"] > 2e-2 * result["logit_std"], control


def test_served_loop_matches_reference(weights):
    """Eight prompts, most past the window, joining two a step through
    the batcher's own ``step``: window pages one slot gives back are
    taken by another's admission, and every slot's pages read back give
    the reference's logits."""
    config, params = weights()
    batcher = _batcher(config, params, slots=8)
    released = []
    allocate = batcher._pages.ensure

    def ensure(slot, pages, first=0):
        before = dict(batcher._pages._window_slots.get(slot, {}))
        done = allocate(slot, pages, first)
        taken = set(batcher._pages._window_slots.get(slot, {}).values()) \
            - set(before.values())
        released.append((slot, taken))
        return done
    batcher._pages.ensure = ensure
    result = reference.compare_loop(batcher, 3,
                                    [5, 40, 23, 31, 17, 44, 12, 28], 10, 2)
    assert result["loop_mismatches"] == 0
    assert result["loop_readback_max_abs_diff"] < 5e-3
    assert result["loop_window_pages_held_max"] <= 5
    assert batcher._pages.window_released > 0
    # a physical page given back by one slot, later taken by another
    owners: dict = {}
    handed = False
    for slot, taken in released:
        handed |= any(owners.get(page, slot) != slot for page in taken)
        owners.update({page: slot for page in taken})
    assert handed


# (b) the window layers' pages: bounded, given back, reused, recovered.

def test_window_pages_bounded_and_recover_resumes(weights):
    """Long prompts and long generations: no slot's window layers ever
    hold more than the bound; a chip death mid-serving re-admits every
    live request into a fresh pool and the tokens are the uninterrupted
    run's."""
    config, params = weights()
    prompts = [list(np.random.default_rng(length).integers(97, 123,
                                                           length))
               for length in (33, 7, 41, 20)]
    requests = [(prompt, 18) for prompt in prompts]
    plain, fullest = _serve(_batcher(config, params), requests)
    assert fullest <= 5
    batcher = _batcher(config, params)

    def kill(step):
        if step == 9:
            assert batcher.active_count
            assert batcher.recover() >= 1
            assert not any(batcher._pages.window_holds(slot)
                           for slot in range(3))
    served, fullest = _serve(batcher, requests, before_step=kill)
    assert batcher.recoveries == 1 and fullest <= 5
    assert served == plain


def test_allocator_gives_back_window_pages_and_refuses_atomically():
    pages = PageAllocator(9, 8, 2, window_pages=6)
    assert pages.ensure(0, 4, first=0)
    assert pages.window_holds(0) == 4 and pages.window_dirty[0][:4] \
        == [1, 2, 3, 4]
    assert pages.ensure(0, 6, first=3)          # pages 0-2 go back
    assert pages.window_holds(0) == 3 and pages.window_released == 3
    assert pages.window_dirty[0] == [0, 0, 0, 4, 3, 2, 0, 0]
    # slot 1 takes what slot 0 gave back; one page short: no change
    assert not pages.ensure(1, 4, first=0) and not pages.window_holds(1)
    assert pages.ensure(1, 2, first=0) and pages.window_holds(1) == 2
    pages.release(0)
    assert pages.window_dirty[0] == [0] * 8 and pages.leaked_pages() == 0
    pages.reset()
    assert pages.window_holds(1) == 0 and pages.ensure(1, 5, first=0)


# (c) GPT-J's interleaved rotary is rotate-half on permuted columns.

def test_gptj_rotary_is_rotate_half_on_permuted_columns():
    rng = np.random.default_rng(0)
    heads, width, length = 3, 16, 11
    x = jnp.asarray(rng.standard_normal((length, 32)), jnp.float32)
    w_q = jnp.asarray(rng.standard_normal((32, heads * width)), jnp.float32)
    w_k = jnp.asarray(rng.standard_normal((32, heads * width)), jnp.float32)
    positions = jnp.arange(length) * 7
    # published: interleaved pairs on the published columns
    q = reference.gptj_rotary((x @ w_q).reshape(length, heads, width),
                              positions, 50_000.0)
    k = reference.gptj_rotary((x @ w_k).reshape(length, heads, width),
                              positions, 50_000.0)
    # served: rotate-half on the permuted columns
    from aiko_services_tpu.ops.layers import rope_frequencies
    table = rope_frequencies(width, 128, 50_000.0)

    def served(w):
        rows = (x @ command_a.rotate_half_columns(w, heads, width)) \
            .reshape(1, length, heads, width)
        return apply_rope(rows, table, positions[None])[0]
    assert np.allclose(np.einsum("shd,thd->hst", q, k),
                       np.einsum("shd,thd->hst", served(w_q), served(w_k)),
                       atol=1e-3)
    # and the reference puts served columns back where they were
    assert np.array_equal(reference.published_columns(
        command_a.rotate_half_columns(w_q, heads, width), heads, width),
        w_q)


# (d) a chip's share of the experts: the shares sum to the whole layer.

@pytest.mark.parametrize("shares", [[(0, 4), (4, 4)],
                                    [(0, 3), (3, 3), (6, 2)]])
def test_expert_shares_sum_to_the_uncut_layer(weights, shares):
    config, params = weights()
    layer = jax.tree_util.tree_map(lambda leaf: leaf[1], params["layers"])
    a = jnp.asarray(np.random.default_rng(1).standard_normal(
        (20, config.dim)), jnp.float32)
    whole, chosen, _ = command_a.ffn(config, a, layer, layer["experts"])
    gates = command_a.route(config, a, layer["w_router"])[1]
    shared = command_a._swiglu(a, layer["shared"]) \
        / config.n_shared_experts
    total = shared
    for first, count in shares:
        part = jax.tree_util.tree_map(
            lambda leaf: leaf[first:first + count], layer["experts"])
        routed, sizes = moe.routed_experts(
            a, chosen, gates, part, grouped_matmul="xla",
            held=(first, count))
        assert int(sizes.sum()) == int(((chosen >= first)
                                        & (chosen < first + count)).sum())
        total = total + routed
    assert np.allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    # and a served share is the reference's share (8 experts over 2)
    shard = dataclasses.replace(config, n_experts=4, expert_shards=2)
    assert shard.held == (0, 4) and shard.router_experts == 8


def test_every_expert_held_is_the_other_families_result():
    """``held`` covering every expert gives what no ``held`` gives, for
    the routing of the two families that hold every expert."""
    rng = np.random.default_rng(2)
    for config, params, route in (
            (dataclasses.replace(deepseek.DeepseekConfig.tiny(),
                                 dtype="float32"), None, "deepseek"),
            (dataclasses.replace(sdar.SdarConfig.tiny(), dtype="float32"),
             None, "sdar")):
        module = deepseek if route == "deepseek" else sdar
        params = module.init_params(jax.random.PRNGKey(0), config)
        stack = params["sparse"] if route == "deepseek" \
            else params["layers"]
        layer = jax.tree_util.tree_map(lambda leaf: leaf[0], stack)
        h = jnp.asarray(rng.standard_normal((24, config.dim)), jnp.float32)
        routed = deepseek.route(config, h, layer["w_router"],
                                layer["router_bias"]) \
            if route == "deepseek" else sdar.route(config, h,
                                                   layer["w_router"])
        valid = jnp.arange(24) % 5 != 0
        plain = moe.routed_experts(h, *routed, layer["experts"],
                                   grouped_matmul="xla", valid=valid)
        held = moe.routed_experts(h, *routed, layer["experts"],
                                  grouped_matmul="xla", valid=valid,
                                  held=(0, config.n_experts))
        assert np.array_equal(np.asarray(plain[0]), np.asarray(held[0]))
        assert np.array_equal(np.asarray(plain[1]), np.asarray(held[1]))


def test_megablox_tiles_stay_whole_where_they_fit():
    """The tiling the two families that route already took, and a
    4096 x 4096 expert's halved to fit the fast memory."""
    def tiling(shape):
        return moe._tiling(jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert tiling((64, 2048, 1408)) == (128, 2048, 1408)
    assert tiling((128, 768, 2048)) == (128, 768, 2048)
    assert tiling((16, 4096, 4096)) == (128, 1024, 2048)


# (e) the kernels' windows against the banded einsums.

def test_paged_kernel_reads_only_the_window():
    """The paged decode kernel with a window against gathered rows under
    the banded mask -- and pages wholly behind the window hold NaN, so
    a kernel that copied or folded one would say so."""
    from aiko_services_tpu.ops.pallas_decode import (
        _split_paged, flash_decode_append_paged)
    rng = np.random.default_rng(4)
    rows, heads, kv, width, page, pps, window = 3, 4, 2, 16, 8, 6, 13
    pool = rng.standard_normal((2, 1 + rows * pps, page, kv * width))
    table = 1 + np.arange(rows * pps).reshape(rows, pps)
    lengths = np.array([0, 29, 47])
    for row, length in enumerate(lengths):
        behind = max(0, length + 1 - window) // page
        pool[:, table[row, :behind]] = np.nan
        table[row, :behind] = 0 if row else table[row, :behind]
    pool[:, 0] = np.nan
    pool = jnp.asarray(pool, jnp.float32)
    q = jnp.asarray(rng.standard_normal((rows, 1, heads, width)),
                    jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((rows, 1, kv, width)),
                        jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((rows, 1, kv, width)),
                        jnp.float32)
    got = flash_decode_append_paged(
        q, _split_paged(pool), _split_paged(pool * 0.5), 1, k_new, v_new,
        jnp.asarray(table), jnp.asarray(lengths), window=window,
        interpret=True)
    for row, length in enumerate(lengths):
        held = np.asarray(pool[1][table[row]]).reshape(-1, kv, width)
        keep = np.arange(held.shape[0])
        seen = (keep < length) & (keep > length - window)
        keys = np.concatenate([np.where(seen[:, None, None], held, 0.0),
                               np.asarray(k_new[row])])
        values = np.concatenate([np.where(seen[:, None, None],
                                          held * 0.5, 0.0),
                                 np.asarray(v_new[row])])
        want = attention_prefill(
            q[row:row + 1], jnp.asarray(keys)[None],
            jnp.asarray(values)[None], jnp.asarray([[length]]),
            kv_length_mask=jnp.asarray(np.append(seen, True))[None],
            kv_positions=jnp.asarray(np.append(keep, length))[None])
        assert np.allclose(np.asarray(got[row]), np.asarray(want[0]),
                           atol=1e-4), row


@pytest.mark.parametrize("offset,window", [(0, 40), (64, 40), (64, 200)])
def test_flash_attention_band_matches_dense(offset, window):
    """Admission's flash kernel banded by the window against the banded
    einsums, blocks small enough that whole key blocks fall behind the
    band and are skipped."""
    from aiko_services_tpu.ops.pallas_attention import flash_attention
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 16)), jnp.float32)
    got = flash_attention(q, k, v, q_offset=offset, window=window,
                          block_q=16, block_k=16, interpret=True)
    want = attention_prefill(q, k, v, (offset + jnp.arange(32))[None],
                             window=window)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# (f) widths, refusals and the LLM element.

SERVED = {"family": "cohere2_moe", "kv_page_tokens": 16,
          "decode_block_tokens": 8}
TINY_WIDTHS = {"hidden_size": 64, "num_hidden_layers": 4,
               "num_attention_heads": 8, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 32, "num_experts": 8,
               "num_experts_per_tok": 3, "num_shared_experts": 2,
               "sliding_window": 16, "vocab_size": 512,
               "layer_types": ["sliding_attention"] * 3
               + ["full_attention"]}


@pytest.mark.parametrize("parameters,named", [
    ({"quantize": "int8"}, "quantize"),
    ({"speculative": "ngram"}, "speculative"),
    ({"spec_tokens": 4}, "spec_tokens"),
    ({"spec_window": 16}, "spec_window"),
    ({"prefix_cache": "on"}, "prefix_cache"),
    ({"model": "tiny"}, "model"),
    ({"widths": {"kv_lora_rank": 4}}, "kv_lora_rank"),
    ({"widths": {"layer_types": ["linear_attention"]}}, "layer_types"),
    ({"family": "sdar_moe", "expert_shards": 8}, "expert_shards"),
])
def test_family_parameters_refused_by_name(parameters, named):
    assert named in family_spec_error({**SERVED, **parameters})


def test_family_widths_and_defaults():
    assert family_spec_error({**SERVED, "widths": TINY_WIDTHS,
                              "expert_shards": 2}) is None
    config = command_a.CommandAConfig.from_widths(TINY_WIDTHS, max_seq=256)
    assert config == _tiny("bfloat16")
    assert model_family(config) is command_a
    assert (config.period, config.n_window_layers,
            config.n_paged_layers) == (4, 3, 1)
    published = command_a.CommandAConfig()
    assert published.layer_types[:4] == ("sliding_attention",) * 3 \
        + ("full_attention",) and len(published.layer_types) == 32
    assert published.router_experts == 128 and published.held == (0, 128)
    for fields, named in (
            ({"layer_types": ("full_attention",) + ("sliding_attention",)
              * 3}, "layer_types"),
            ({"expert_shards": 0}, "expert_shards"),
            ({"kv_dtype": "int8"}, "kv_dtype"),
            ({"window": 0}, "sliding_window")):
        with pytest.raises(ValueError, match=named):
            _tiny(**fields)


@pytest.mark.parametrize("settings,named", [
    ({"kv_page_tokens": 0}, "kv_page_tokens"),
    ({"speculative": "ngram"}, "speculative"),
    ({"prefix_cache": "on"}, "prefix_cache"),
])
def test_batcher_refuses_by_name(weights, settings, named):
    config, params = weights()
    with pytest.raises(ValueError, match=named):
        ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                          **{"prefill_chunk": 8, "kv_page_tokens": 4,
                             "decode_block_tokens": 4, **settings})
    assert not hasattr(command_a, "prefill_into_slots")


def _definition(name, parameters):
    return {
        "version": 0, "name": name, "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 10, "max_seq": 128,
                           "decode_block_tokens": 8, "kv_page_tokens": 8,
                           **parameters},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}


def test_llm_element_serves_the_window_family(runtime):
    from aiko_services_tpu.pipeline import Pipeline
    responses = queue.Queue()
    pipeline = Pipeline(_definition("window_llm", {
        "family": "cohere2_moe", "widths": TINY_WIDTHS,
        "expert_shards": 2}), runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    prompts = ["hello there, a prompt past the window", "general kenobi",
               "you are a bold one"]
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=180.0)
    assert run_until(runtime, lambda: all(
        name in pipeline.metrics_text() for name in
        ("llm_window_pages_held", "llm_moe_experts_touched",
         "llm_moe_load_imbalance")))
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert isinstance(batcher.config, command_a.CommandAConfig)
    assert batcher.config.held == (0, 8)
    assert batcher.config.router_experts == 16
    assert {"window_k", "window_v", "window_table"} <= set(batcher.cache)
    registry = pipeline.telemetry.registry

    def median(name):
        return registry.quantile(name, 0.5, None, windowed=False)
    assert 0.0 < median("llm_window_pages_held") \
        <= batcher.window_pages_per_slot * 1.1
    assert 0.0 < median("llm_moe_experts_touched") <= 8 * 1.1
    assert median("llm_moe_load_imbalance") >= 1.0
    events = pipeline.recorder.snapshot()
    demux = [event[6] for event in events
             if event[1] == "llm_tick" and event[4] == "demux"
             and event[6]]
    assert demux and all("window_pages_released" in info
                         for info in demux)
    pools = [event[6] for event in events
             if event[1] == "build" and event[4] == "llm_cache"]
    assert pools and pools[0]["window_pages_per_slot"] \
        == batcher.window_pages_per_slot
    assert pools[0]["['window_k']"] == batcher.cache["window_k"].nbytes
    pipeline.stop()


def test_llm_element_refuses_at_create_time():
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    served = {"family": "cohere2_moe", "widths": TINY_WIDTHS,
              "kv_page_tokens": 16, "decode_block_tokens": 8}
    for extra, named in (({"prefix_cache": "on"}, "prefix_cache"),
                         ({"quantize": "int8"}, "quantize"),
                         ({"expert_shards": 0}, "expert_shards")):
        findings = validate_element_parameters(
            "LLM", {**served, **extra}, "elements[0]",
            module="aiko_services_tpu.elements.llm")
        assert [finding.rule for finding in findings] == ["bad-parameter"]
        assert named in findings[0].message
    assert validate_element_parameters(
        "LLM", {**served, "expert_shards": 8},
        "elements[0]", module="aiko_services_tpu.elements.llm") == []
