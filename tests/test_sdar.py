"""The SDAR family (models/sdar.py: generation by diffusion over blocks
under a block-causal mask, drop-less softmax-routed experts) at a tiny
preset on the CPU, against its plain float32 reference
(benchmark/architectures/sdar_moe.py: ``forward`` and the block loop
``generate``), through the same ContinuousBatcher and LLM element as the
other three families (ISSUE 36)."""

import dataclasses
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import deepseek, moe, sdar
from aiko_services_tpu.models.batching import (ContinuousBatcher, Request,
                                               model_family)
from aiko_services_tpu.models.families import family_spec_error
from aiko_services_tpu.models.paged import init_paged_cache
from benchmark.architectures import sdar_moe as reference

from conftest import run_until


def _tiny(dtype="float32", **fields):
    return dataclasses.replace(sdar.SdarConfig.tiny(), dtype=dtype,
                               **fields)


@pytest.fixture(scope="module")
def weights():
    """(config, params) by dtype and fields, by the serving init: it
    gives attention weight (``sdar.init_params``), so a mask's logits
    depend on its context and blocks do not all come out one token --
    equal tokens would prove little otherwise."""
    built = {}

    def get(dtype="float32", **fields):
        key = (dtype, tuple(sorted(fields.items())))
        if key not in built:
            config = _tiny(dtype, **fields)
            built[key] = (config, sdar.init_params(
                jax.random.PRNGKey(3), config))
        return built[key]
    return get


def _batcher(config, params, chunk=64, page=32, slots=3, **settings):
    return ContinuousBatcher(
        params, config, max_slots=slots, max_seq=256, prefill_chunk=chunk,
        kv_page_tokens=page,
        **{"decode_block_tokens": 8, **settings})


def _prompt(length, seed):
    rng = np.random.default_rng(seed)
    return [257] + rng.integers(97, 123, length - 1).tolist()


def _serve(batcher, requests, before_step=None):
    """``requests``: (prompt, new tokens, stop tokens).  Returns the
    tokens emitted, by request."""
    out = {index: [] for index in range(len(requests))}
    for index, (prompt, new_tokens, stops) in enumerate(requests):
        batcher.submit(Request(
            request_id=str(index), prompt_tokens=list(prompt),
            max_new_tokens=new_tokens, eos_tokens=tuple(stops),
            emit=lambda rid, token, finished:
                out[int(rid)].append(token)))
    steps = 0
    while batcher.pending or batcher.active_count \
            or batcher.blocks_in_flight:
        if before_step is not None:
            before_step(steps)
        batcher.step()
        steps += 1
        assert steps < 10_000
    return out


def _generate(config, params, prompt, new_tokens, stops=()):
    return reference.generate(
        params, reference.published_widths(config), prompt, new_tokens,
        block_length=config.block_length,
        denoising_steps=config.denoising_steps,
        mask_token=config.mask_token, stop_tokens=stops)


# (a) the reference's forward pass == served admission + passes, on
# logits, pass by pass, over chunk boundaries, page sizes and kernels.

@pytest.mark.parametrize("chunk,page,prompt,fields", [
    (64, 32, 101, {}), (32, 16, 70, {}), (64, 64, 66, {}), (64, 32, 6, {}),
    (128, 128, 150, {"attention": "flash", "kernels": "on"})])
def test_passes_match_reference_float32(weights, chunk, page, prompt,
                                        fields):
    config, params = weights(denoising_steps=2, **fields)
    batcher = _batcher(config, params, chunk, page)
    result = reference.compare(batcher, 11, prompt, 3)
    # three blocks: the first opens with the prompt's leftover
    assert result["passes"] >= 3 * 2 and result["positions"] \
        == 4 * result["passes"]
    assert result["max_abs_diff"] < 3e-4, result
    assert result["argmax_agree"] == result["positions"]
    assert result["router_flips"] == 0 and result["decide_flips"] == 0
    assert batcher._pages.free_pages == batcher._pages.total - 1


def test_passes_match_reference_bfloat16(weights):
    """As served (bfloat16 weights and K/V pages): inside a tolerance
    that both controls of the benchmark fail."""
    config, params = weights("bfloat16", denoising_steps=2)
    batcher = _batcher(config, params)
    served = reference.compare(batcher, 11, 10, 3, free=True)
    # (the init's sharp attention makes bfloat16 scores matter: at
    # these toy head widths the reading is ~0.4 on logits of unit scale)
    assert served["max_abs_diff"] < 0.6, served
    assert served["router_not_near_ties"] == 0
    for control in ("fp8_activations", "causal_in_block"):
        failed = reference.compare(batcher, 11, 10, 3, control=control)
        assert failed["max_abs_diff"] > 2.0 * served["max_abs_diff"], (
            control, served, failed)


# ... and in float32 the served TOKENS equal the plain block loop's.

@pytest.mark.parametrize("steps", [1, 2, 4])
def test_served_tokens_equal_generate(weights, steps):
    """Prompts of every ``P mod 4`` (one shorter than a block, one past
    an admission chunk), a budget that ends mid-block, every
    ``denoising_steps`` that divides the block, five requests over
    three slots (one joins while the others are mid-block) and two
    device blocks in flight while it waits."""
    config, params = weights(denoising_steps=steps)
    assert config.block_length == 4
    prompts = [_prompt(length, length) for length in (8, 69, 3, 14, 7)]
    batcher = _batcher(config, params, inflight=2)
    served = _serve(batcher, [(prompt, 10, ()) for prompt in prompts])
    for index, prompt in enumerate(prompts):
        assert served[index] == _generate(config, params, prompt,
                                          10), (index, steps)
    assert batcher._pages.free_pages == batcher._pages.total - 1
    assert batcher.tokens_emitted == 10 * len(prompts)
    # passes, not tokens: a block of four costs steps + 1 row-passes
    blocks = batcher.take_block_stats()
    row_passes = sum(block["row_passes"] for block in blocks)
    commits = sum(block["commits"] for block in blocks)
    assert batcher.steps == sum(block["passes"] for block in blocks) \
        < row_passes
    assert commits >= 3 * len(prompts)
    assert row_passes >= commits * 2


def test_model_steps_and_a_stop_token_inside_a_block(weights):
    config, params = weights(denoising_steps=2)
    prompt = _prompt(10, 5)
    free = _generate(config, params, prompt, 12)
    # a token first met in the middle of a generated block
    at = next(index for index, token in enumerate(free)
              if (len(prompt) + index) % 4 in (1, 2)
              and token not in free[:index])
    stop = free[at]
    want = _generate(config, params, prompt, 12, stops=(stop,))
    assert want == free[:at + 1] and len(want) < 12
    served = _serve(_batcher(config, params),
                    [(prompt, 12, (stop,)), (prompt, 12, ())])
    assert served[0] == want and served[1] == free


def test_recover_mid_block_resumes_at_a_block_boundary(weights):
    """A chip death while blocks are half decided: every live request
    is re-admitted with what it had emitted and the half-decided block
    is done again -- the tokens are the uninterrupted run's."""
    config, params = weights(denoising_steps=2)
    prompts = [_prompt(length, length) for length in (9, 70, 6)]
    batcher = _batcher(config, params, decode_block_tokens=4)

    def kill(step):
        if step == 3:
            assert batcher.tokens_emitted and batcher.active_count
            assert batcher.recover() == 3
    served = _serve(batcher, [(prompt, 14, ()) for prompt in prompts],
                    before_step=kill)
    assert batcher.recoveries == 1
    for index, prompt in enumerate(prompts):
        assert served[index] == _generate(config, params, prompt, 14)


# ... and the benchmark's check of the DEVICE LOOP itself, many slots at
# once through the batcher's own step: it passes on the program as it
# is, counts a near-tie in bfloat16, and fails on each fault it is there
# to find.

LOOP_PROMPTS = [5, 18, 39, 64, 77, 30]


@pytest.mark.parametrize("dtype,tie", [("float32", 2e-3), ("bfloat16", 0.4)])
def test_loop_check_passes_on_the_served_loop(weights, dtype, tie):
    config, params = weights(dtype, denoising_steps=2)
    batcher = _batcher(config, params, page=16, slots=6, inflight=2)
    result = reference.compare_loop(batcher, 7, LOOP_PROMPTS, 3, 2, tie)
    assert result["loop_mismatches"] == 0, result
    assert result["loop_blocks"] == 3 * len(LOOP_PROMPTS)
    assert result["loop_readback_max_abs_diff"] < tie / 2
    assert result["loop_worst_margin"] <= tie
    if dtype == "float32":
        assert result["loop_flips"] == 0
    # rows joined two a step: passes carried rows at mixed phases
    assert result["loop_passes"] < result["loop_row_passes"] \
        < result["loop_passes"] * len(LOOP_PROMPTS)
    assert result["loop_commits"] >= result["loop_blocks"]
    assert batcher._pages.free_pages == batcher._pages.total - 1
    assert not batcher.active_count and not batcher.blocks_in_flight


def _swapped_rows(batcher):
    """The retire hands two slots each other's ring."""
    fetch = batcher._fetch

    def swapped(tree):
        fetched = fetch(tree)
        emitted = np.array(fetched["emitted"])
        emitted[[0, 1]] = emitted[[1, 0]]
        return {**fetched, "emitted": emitted}
    batcher._fetch = swapped


def _neighbours_rows(batcher):
    """After the third step a page that slot 0 holds is written over."""
    step, count = batcher.step, [0]

    def stepped():
        active = step()
        count[0] += 1
        if count[0] == 3:
            page = batcher._pages._slots[0][0]
            assert page > 0
            batcher.cache = {**batcher.cache, "k": batcher.cache["k"]
                             .at[:, page].multiply(-1.0)}
        return active
    batcher.step = stepped


def _whole_block_a_pass(batcher, monkeypatch):
    """The loop's denoising pass decides every masked position."""
    decide = sdar._decide

    def greedy(c, blocks, logits, temperatures, done, key, top_k=0):
        return decide(dataclasses.replace(c, denoising_steps=1), blocks,
                      logits, temperatures, jnp.zeros_like(done), key,
                      top_k)
    monkeypatch.setattr(sdar, "_decide", greedy)


@pytest.mark.parametrize("fault", ["swapped_rows", "neighbours_rows",
                                   "whole_block_a_pass"])
def test_loop_check_fails_on_a_faulty_loop(monkeypatch, fault):
    # (a config of its own: the faulty trace must not meet another test)
    config = _tiny(denoising_steps=2,
                   norm_eps=1e-6 * (1 + len(fault) / 100))
    params = sdar.init_params(jax.random.PRNGKey(3), config)
    batcher = _batcher(config, params, page=16, slots=6)
    if fault == "whole_block_a_pass":
        _whole_block_a_pass(batcher, monkeypatch)
    else:
        {"swapped_rows": _swapped_rows,
         "neighbours_rows": _neighbours_rows}[fault](batcher)
    result = reference.compare_loop(batcher, 7, LOOP_PROMPTS, 3, 2, 2e-3)
    if fault == "neighbours_rows":
        assert result["loop_readback_max_abs_diff"] > 0.1, result
    else:
        assert result["loop_mismatches"] > 0, result


# (b) what a pass writes: a denoising pass nothing a slot holds, a
# commit pass its own block's rows alone.

def test_a_pass_writes_only_a_committed_block(weights):
    config, params = weights()
    slots, size = 3, config.block_length
    cache = init_paged_cache(config, slots, 256, page_tokens=32,
                             total_pages=13)
    rng = np.random.default_rng(0)
    cache["page_table"] = jnp.asarray(
        [[1, 2, 0, 0, 0, 0, 0, 0], [3, 4, 5, 0, 0, 0, 0, 0],
         [6, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    for side in ("k", "v"):
        cache[side] = jnp.asarray(
            rng.standard_normal(cache[side].shape), jnp.float32)
    before = {side: np.asarray(cache[side]) for side in ("k", "v")}
    blocks = jnp.asarray(rng.integers(0, 200, (slots, size)), jnp.int32)
    lengths = jnp.asarray([36, 68, 8], jnp.int32)
    none = jnp.zeros((slots,), bool)
    _, cache = sdar.decode_step(params, config, blocks, dict(cache),
                                lengths, none)
    for side in ("k", "v"):     # page 0 is the trash page
        assert np.array_equal(np.asarray(cache[side])[:, 1:],
                              before[side][:, 1:])
    _, cache = sdar.decode_step(params, config, blocks, dict(cache),
                                lengths, none.at[1].set(True))
    for side in ("k", "v"):
        after = np.asarray(cache[side])
        changed = np.argwhere((after != before[side]).any(-1))
        # slot 1's block at 68..71 lies in its third page (5), rows 4..7
        assert {tuple(row[1:]) for row in changed
                if row[1] != 0} == {(5, 4), (5, 5), (5, 6), (5, 7)}
        assert {int(row[0]) for row in changed} \
            == set(range(config.n_layers))


# (c) what a denoising pass decides, on crafted logits.

def _crafted(config, peaks):
    """Logits ``[1, 4, vocab]`` whose argmax at position ``i`` is token
    ``10 + i`` with probability ~``peaks[i]``; the mask token's own
    logit is the highest of all and must never be taken."""
    vocab = config.vocab_size
    logits = np.zeros((1, 4, vocab), np.float32)
    for index, peak in enumerate(peaks):
        # softmax over the unmasked ids: peak / (1 - peak) * (vocab - 2)
        logits[0, index, 10 + index] = np.log(
            peak / (1.0 - peak) * (vocab - 2))
    logits[0, :, config.mask_token] = 50.0
    return jnp.asarray(logits)


@pytest.mark.parametrize("state,done,total,decided", [
    ([-1, -1, -1, -1], 0, 2, [1, 3]),            # the two most confident
    ([-1, -1, -1, -1], 0, 4, [1]),
    ([-1, -1, -1, -1], 0, 3, [1, 3]),            # 4 = 2 + 1 + 1
    ([-1, -1, -1, -1], 1, 3, [1]),
    ([-1, -1, -1, -1], 2, 3, [1]),
    ([7, -1, 7, -1], 0, 1, [1, 3]),              # never more than masked
    ([7, 7, -1, -1], 1, 2, [2, 3]),
    ([7, -1, -1, -1], 1, 2, [1, 3]),
    ([-1, 7, 7, 7], 3, 4, [0]),
    ([7, 7, 7, 7], 0, 2, []),                    # nothing masked
])
def test_decide_on_crafted_logits(state, done, total, decided):
    config = _tiny(denoising_steps=total)
    peaks = [0.2, 0.9, 0.6, 0.7]
    mask = config.mask_token
    state = [mask if token < 0 else token for token in state]
    blocks, transfer = sdar.decide(
        config, jnp.asarray([state], jnp.int32), _crafted(config, peaks),
        jnp.zeros((1,)), jnp.asarray([done]), jax.random.PRNGKey(0))
    assert np.flatnonzero(np.asarray(transfer[0])).tolist() == decided
    want = [10 + index if index in decided else token
            for index, token in enumerate(state)]
    assert np.asarray(blocks[0]).tolist() == want
    # the plain rule of the reference decides alike
    _, chosen = reference.decide_positions(
        np.asarray(_crafted(config, peaks)[0]), state, mask,
        reference.quota(4, total, done))
    assert chosen == decided


def test_a_sample_is_never_the_mask(weights):
    config = _tiny(denoising_steps=1)       # a pass decides the block
    logits = jnp.zeros((2, 4, config.vocab_size)) \
        .at[:, :, config.mask_token].set(30.0)
    blocks, transfer = sdar.decide(
        config, jnp.full((2, 4), config.mask_token, jnp.int32), logits,
        jnp.asarray([0.0, 1.0]), jnp.zeros((2,), jnp.int32),
        jax.random.PRNGKey(1))
    assert bool(transfer.all())
    assert not bool((blocks == config.mask_token).any())


# (d) the grouped part is shared, and the latent family's is unmoved.

@pytest.mark.parametrize("stacked", [False, True])
def test_routed_experts_are_shared_and_the_latent_familys_unmoved(stacked):
    config = dataclasses.replace(deepseek.DeepseekConfig.tiny(),
                                 dtype="float32")
    params = deepseek.init_params(jax.random.PRNGKey(0), config)
    at = 1 if stacked else 0
    layer = jax.tree_util.tree_map(lambda leaf: leaf[at], params["sparse"])
    stack = (params["sparse"]["experts"], jnp.int32(at)) if stacked \
        else None
    h = jnp.asarray(np.random.default_rng(0).standard_normal(
        (24, config.dim)), jnp.float32)
    valid = jnp.arange(24) % 5 != 0
    own, chosen, sizes = deepseek.routed_ffn(config, h, layer, valid, stack)
    # the latent family's part is its router; the rest is the shared one
    routed = deepseek.route(config, h, layer["w_router"],
                            layer["router_bias"])
    assert np.array_equal(np.asarray(chosen), np.asarray(routed[0]))
    handed = moe.routed_experts(
        h, *routed, layer["experts"],
        grouped_matmul=config.grouped_matmul, valid=valid)
    assert np.array_equal(np.asarray(own), np.asarray(handed[0]))
    assert np.array_equal(np.asarray(sizes), np.asarray(handed[1]))
    assert int(sizes.sum()) == int(valid.sum()) \
        * config.n_experts_per_token
    # ... which is every pair's expert, none dropped, in plain einsums
    gates, experts = routed[1], layer["experts"]
    hidden = jax.nn.silu(jnp.einsum("nd,edf->nef", h, experts["w_gate"])) \
        * jnp.einsum("nd,edf->nef", h, experts["w_up"])
    every = jnp.einsum("nef,efd->ned", hidden, experts["w_down"])
    share = (jax.nn.one_hot(chosen, config.n_experts)
             * gates[..., None]).sum(1)
    want = jnp.where(valid[:, None],
                     jnp.einsum("ne,ned->nd", share, every), 0.0)
    assert np.allclose(np.asarray(own), np.asarray(want), atol=1e-5)
    # this family hands it its own routing: softmax, no bias, no scale
    tiny = _tiny()
    chosen, gates = sdar.route(
        tiny, h[:, :tiny.dim], jnp.asarray(np.random.default_rng(1)
                                           .standard_normal((tiny.dim, 8)),
                                           jnp.float32))
    assert chosen.shape == (24, 3)
    assert np.allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)


# (e) what the family cannot serve raises and names its parameter.

SERVED = {"family": "sdar_moe", "kv_page_tokens": 16,
          "decode_block_tokens": 8}


@pytest.mark.parametrize("parameters,named", [
    ({"quantize": "int8"}, "quantize"),
    ({"speculative": "ngram"}, "speculative"),
    ({"spec_tokens": 4}, "spec_tokens"),
    ({"spec_window": 16}, "spec_window"),
    ({"prefix_cache": "on"}, "prefix_cache"),
    ({"model": "tiny"}, "model"),
    ({"kv_page_tokens": 0}, "kv_page_tokens"),
    ({"decode_block_tokens": 0}, "decode_block_tokens"),
    ({"decode_block_tokens": 6}, "decode_block_tokens"),
    ({"block_length": 3}, "block_length"),
    ({"block_length": 0}, "block_length"),
    ({"kv_page_tokens": 16, "block_length": 32}, "block_length"),
    ({"denoising_steps": 0}, "denoising_steps"),
    ({"denoising_steps": 5}, "denoising_steps"),
    ({"block_length": 2, "denoising_steps": 3}, "denoising_steps"),
    ({"widths": {"kv_lora_rank": 4}}, "kv_lora_rank"),
    ({"family": "llama", "block_length": 4}, "block_length"),
    ({"family": "deepseek_v3", "denoising_steps": 2}, "denoising_steps"),
])
def test_family_parameters_refused_by_name(parameters, named):
    assert named in family_spec_error({**SERVED, **parameters})


TINY_WIDTHS = {"hidden_size": 64, "num_hidden_layers": 3,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "moe_intermediate_size": 32,
               "num_experts": 8, "num_experts_per_tok": 3,
               "vocab_size": 512}


def test_family_parameters_accepted():
    assert family_spec_error({
        **SERVED, "quantize": "off", "speculative": "off",
        "block_length": 8, "denoising_steps": 3,
        "widths": TINY_WIDTHS}) is None
    config = sdar.SdarConfig.from_widths(TINY_WIDTHS, max_seq=256)
    assert config == _tiny("bfloat16")
    assert config.mask_token == 511 and config.denoising_steps == 4
    assert sdar.SdarConfig().mask_token == 151_669
    assert model_family(config) is sdar


@pytest.mark.parametrize("settings,named", [
    ({"kv_page_tokens": 0}, "kv_page_tokens"),
    ({"decode_block_tokens": 0}, "decode_block_tokens"),
    ({"decode_block_tokens": 6}, "decode_block_tokens"),
    ({"speculative": "ngram"}, "speculative"),
    ({"prefix_cache": "on"}, "prefix_cache"),
    ({"kv_page_tokens": 2, "prefill_chunk": 64}, "block_length"),
])
def test_batcher_refuses_by_name(weights, settings, named):
    config, params = weights()
    with pytest.raises(ValueError, match=named):
        ContinuousBatcher(
            params, config, max_slots=2, max_seq=256,
            **{"prefill_chunk": 64, "kv_page_tokens": 32,
               "decode_block_tokens": 8, **settings})
    assert not hasattr(sdar, "prefill_into_slots")
    with pytest.raises(ValueError, match="denoising_steps"):
        _tiny(denoising_steps=5)


# (f) through the LLM element: family + widths + the family's own two
# parameters, and the telemetry.

def _definition(name, parameters):
    return {
        "version": 0, "name": name, "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 10, "max_seq": 128,
                           "decode_block_tokens": 8, "kv_page_tokens": 16,
                           **parameters},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}


def test_llm_element_serves_the_diffusion_family(runtime):
    from aiko_services_tpu.pipeline import Pipeline
    responses = queue.Queue()
    pipeline = Pipeline(_definition("diffusion_llm", {
        "family": "sdar_moe", "widths": TINY_WIDTHS,
        "denoising_steps": 2}), runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    prompts = ["hello there", "general kenobi", "you are a bold one"]
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=180.0)
    assert run_until(runtime, lambda: all(
        name in pipeline.metrics_text() for name in
        ("llm_diffusion_tokens_per_row_pass", "llm_ttft_ms")))
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert isinstance(batcher.config, sdar.SdarConfig)
    assert batcher.config.block_length == 4
    assert batcher.config.denoising_steps == 2
    assert set(batcher.cache) == {"k", "v", "page_table"}
    assert batcher.tokens_emitted <= 10 * len(prompts)
    registry = pipeline.telemetry.registry

    def median(name):
        return registry.quantile(name, 0.5, None, windowed=False)
    # two denoising passes and a commit a block of four (log buckets;
    # a first block's prompt tokens and a budget's cut tail are not
    # emitted, so a short request reads under 4 / 3)
    assert 0.7 <= median("llm_diffusion_tokens_per_row_pass") <= 1.5
    assert 30.0 <= median("llm_diffusion_commit_pass_share") <= 37.0
    assert 0.0 < median("llm_moe_experts_touched") <= 8 * 1.1
    # a first token arrived, with the first block's commit
    assert "llm_ttft_ms" in pipeline.metrics_text()
    events = pipeline.recorder.snapshot()
    demux = [event[6] for event in events
             if event[1] == "llm_tick" and event[4] == "demux"
             and event[6]]
    assert demux and {"passes", "row_passes", "commits", "decided"} \
        <= set(demux[0])
    assert sum(info["passes"] for info in demux) == batcher.steps
    commits = sum(info["commits"] for info in demux)
    assert commits >= 3 * len(prompts)
    assert sum(info["row_passes"] for info in demux) >= 2 * commits
    pools = [event[6] for event in events
             if event[1] == "build" and event[4] == "llm_cache"]
    assert pools and pools[0]["['k']"] == batcher.cache["k"].nbytes
    pipeline.stop()


def test_llm_element_refuses_at_create_time():
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    served = {"family": "sdar_moe", "widths": TINY_WIDTHS,
              "kv_page_tokens": 16, "decode_block_tokens": 8}
    for extra, named in (({"prefix_cache": "on"}, "prefix_cache"),
                         ({"block_length": 3}, "block_length"),
                         ({"denoising_steps": 9}, "denoising_steps")):
        findings = validate_element_parameters(
            "LLM", {**served, **extra}, "elements[0]",
            module="aiko_services_tpu.elements.llm")
        assert [finding.rule for finding in findings] == ["bad-parameter"]
        assert named in findings[0].message
    assert validate_element_parameters(
        "LLM", {**served, "block_length": 4, "denoising_steps": 2},
        "elements[0]", module="aiko_services_tpu.elements.llm") == []
