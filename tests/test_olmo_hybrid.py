"""The Olmo-Hybrid family (models/olmo_hybrid.py: gated delta-rule
layers with a per-slot recurrent state beside full-attention layers over
K/V pages) at a tiny preset on the CPU -- d_k != d_v, six heads --
against its plain float32 reference
(benchmark/architectures/olmo_hybrid.py: the recurrence token by token),
through the same ContinuousBatcher and LLM element as the other two
families (ISSUE 33)."""

import dataclasses
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import batching, deepseek, llama, olmo_hybrid
from aiko_services_tpu.models.batching import (ContinuousBatcher, Request,
                                               model_family)
from aiko_services_tpu.models.families import family_spec_error
from aiko_services_tpu.models.paged import init_paged_cache
from aiko_services_tpu.ops import pallas_gdn
from benchmark.architectures import olmo_hybrid as reference

from conftest import run_until


def _tiny(dtype="float32", **fields):
    return dataclasses.replace(olmo_hybrid.OlmoHybridConfig.tiny(),
                               dtype=dtype, **fields)


@pytest.fixture(scope="module")
def weights():
    built = {}

    def get(dtype="float32", **fields):
        key = (dtype, tuple(sorted(fields.items())))
        if key not in built:
            config = _tiny(dtype, **fields)
            built[key] = (config, olmo_hybrid.init_params(
                jax.random.PRNGKey(3), config))
        return built[key]
    return get


def _batcher(config, params, chunk=64, page=16, max_seq=256, slots=3,
             **settings):
    return ContinuousBatcher(params, config, max_slots=slots,
                             max_seq=max_seq, prefill_chunk=chunk,
                             kv_page_tokens=page, **settings)


# (a) prefill then decode through the page pools and the state pool ==
# the reference's full forward pass, on logits: over chunk boundaries
# (the state handed on), a padded last chunk, page sizes, both kernels
# interpreted.

@pytest.mark.parametrize("chunk,page,prompt,kernels", [
    (64, 16, 150, "off"), (32, 16, 100, "off"), (128, 64, 100, "off"),
    (64, 64, 64, "off"), (64, 16, 65, "off"), (64, 16, 150, "on")])
def test_served_matches_reference_float32(weights, chunk, page, prompt,
                                          kernels):
    """float32 served against the float32 reference: what is left is
    the order of the sums (chunk scan against the recurrence, paged
    softmax against the whole one): 1e-4 on logits of unit scale."""
    config, params = weights(kernels=kernels)
    result = reference.compare(_batcher(config, params, chunk, page), 11,
                               prompt, 4)
    assert result["max_abs_diff"] < 1e-4, result
    assert result["argmax_agree"] == result["positions"] == 5


def test_served_matches_reference_bfloat16(weights):
    """As served (bfloat16 weights, activations and pages, a float32
    state): inside a tolerance the reference computed in fp8 -- the
    nearest precision below -- fails by far.  Tiny widths average no
    rounding away: the served reading is ~0.11, the control's > 0.9."""
    config, params = weights("bfloat16")
    batcher = _batcher(config, params)
    served = reference.compare(batcher, 11, 150, 4)
    assert served["max_abs_diff"] < 0.25, served
    control = reference.compare(batcher, 11, 150, 4, "fp8_activations")
    assert control["max_abs_diff"] > 0.5, control
    # the second control moves the reference too (how far depends on
    # how long the heads remember: not the deciding one)
    assert reference.compare(batcher, 11, 150, 4, "bf16_state")[
        "max_abs_diff"] != served["max_abs_diff"]


# (b) the two kernels against the recurrence itself.

def _recurrence_inputs(tokens, heads=6, dk=24, dv=64, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (tokens, heads, dk))
    k = jax.random.normal(keys[1], (tokens, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (tokens, heads, dv))
    # decays from "forgets nothing" to "forgets everything in a token"
    g = -jnp.exp(jax.random.uniform(keys[3], (tokens, heads),
                                    minval=-7.0, maxval=2.0))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        keys[4], (tokens, heads)))
    state = jax.random.normal(keys[5], (heads, dk, dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("tokens,sub_chunk", [
    (128, 64), (100, 64), (64, 16), (37, 8), (130, 32), (5, 64)])
def test_chunk_scan_matches_recurrence(tokens, sub_chunk, kernel):
    """Chunk sizes and lengths that do not divide, from a state that is
    not zero: float32 against float32, 2e-5."""
    inputs = _recurrence_inputs(tokens)
    want_out, want_state = pallas_gdn.gated_delta_recurrence(*inputs)
    out, state = pallas_gdn.gated_delta_chunk_scan(
        *inputs, kernel=kernel, sub_chunk=sub_chunk)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)


def test_chunk_scan_masks_a_pad_tail():
    """g = 0, beta = 0 past the last real token: the state is the one
    after that token, whatever the pad holds."""
    q, k, v, g, beta, state = _recurrence_inputs(64)
    real = jnp.arange(64)[:, None] < 41
    _, masked = pallas_gdn.gated_delta_chunk_scan(
        q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
        state)
    _, want = pallas_gdn.gated_delta_recurrence(
        q[:41], k[:41], v[:41], g[:41], beta[:41], state)
    np.testing.assert_allclose(masked, want, atol=2e-5)


@pytest.mark.parametrize("active", [
    [1, 0, 1, 1, 0], [0, 0, 1, 0, 1], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1]])
def test_decode_step_kernel_matches_recurrence(active):
    """One step of the recurrence a live row, in place in the stored
    layout; a row that sits out keeps its state bit for bit, and so
    does every other layer of the pool."""
    rows, heads, dk, dv = 5, 6, 24, 64
    q, k, v, g, beta, _ = _recurrence_inputs(rows, heads, dk, dv, seed=1)
    logical = jax.random.normal(jax.random.PRNGKey(9),
                                (3, rows, heads, dk, dv))
    pack = pallas_gdn.state_pack(heads, dv)
    assert pack == 2
    stored = pallas_gdn.pack_state(logical, pack)
    assert stored.shape == (3, rows, 3, dk, 128)
    np.testing.assert_array_equal(
        pallas_gdn.unpack_state(stored, pack), logical)
    active = jnp.asarray(active, bool)
    results = [pallas_gdn.gated_delta_decode_step(
        q, k, v, g, beta, stored, jnp.int32(1), active, pack=pack,
        kernel=kernel) for kernel in (False, True)]
    for out, pool in results:
        after = pallas_gdn.unpack_state(pool, pack)
        np.testing.assert_array_equal(after[0], logical[0])
        np.testing.assert_array_equal(after[2], logical[2])
        for row in range(rows):
            want_out, want_state = pallas_gdn.gated_delta_recurrence(
                q[row:row + 1], k[row:row + 1], v[row:row + 1],
                g[row:row + 1], beta[row:row + 1], logical[1, row])
            if active[row]:
                np.testing.assert_allclose(after[1, row], want_state,
                                           atol=1e-5)
                np.testing.assert_allclose(out[row], want_out[0],
                                           atol=1e-5)
            else:
                np.testing.assert_array_equal(after[1, row],
                                              logical[1, row])
                assert not np.asarray(out[row]).any()


def test_state_pack_follows_the_lanes():
    assert pallas_gdn.state_pack(30, 192) == 2      # the published widths
    assert pallas_gdn.state_pack(6, 64) == 2
    assert pallas_gdn.state_pack(8, 128) == 1
    assert pallas_gdn.state_pack(3, 64) == 1        # no pack divides 3


# (c) what a state does not forgive: each hazard served through the
# batcher, its logits equal to an undisturbed run's.

class _Logits:
    """The logits behind every token the batcher's per-token tick
    emits, by request id: admission's sampled position, then a row of
    each decode step."""

    def __init__(self, batcher, monkeypatch):
        self.rows: dict[str, list] = {}
        self.batcher = batcher
        sample = batcher._sample
        select = batching._select_tokens

        def sampled(logits, temperature):
            request = self.batcher.slots[self.admitting()]
            self.rows.setdefault(request.request_id, []).append(
                np.asarray(logits[0], np.float32))
            return sample(logits, temperature)

        def selected(key, logits, temperatures, top_k=0):
            for slot, request in enumerate(self.batcher.slots):
                if request is not None and self.batcher.decoding[slot]:
                    self.rows.setdefault(request.request_id, []).append(
                        np.asarray(logits[slot], np.float32))
            return select(key, logits, temperatures, top_k=top_k)

        monkeypatch.setattr(batcher, "_sample", sampled)
        monkeypatch.setattr(batching, "_select_tokens", selected)

    def admitting(self):
        """The slot whose last chunk is being sampled: the one
        occupied, not decoding, whose prompt is written."""
        return next(
            slot for slot, request in enumerate(self.batcher.slots)
            if request is not None and not self.batcher.decoding[slot]
            and request.prefill_pos >= len(request.prompt_tokens))


def _request(name, prompt, new_tokens=6):
    return Request(request_id=name, prompt_tokens=list(prompt),
                   max_new_tokens=new_tokens)


def _prompt(length, seed):
    return np.random.default_rng(seed).integers(1, 500, length).tolist()


def _alone(config, params, prompt, new_tokens, monkeypatch, **shape):
    batcher = _batcher(config, params, **shape)
    logits = _Logits(batcher, monkeypatch)
    batcher.submit(_request("a", prompt, new_tokens))
    batcher.run_until_drained()
    return logits.rows["a"]


def _admit_padded(batcher, prompt, pad):
    """``prompt`` into slot 0 chunk by chunk through the batcher's own
    admission program, the last chunk's pad filled with ``pad``; then
    one decode step.  Returns (admission's logits, the step's, the
    slot's state and tail)."""
    family, chunk = batcher._family, batcher.prefill_chunk
    batcher._pages.ensure(0, batcher._pages.pps)
    batcher._sync_page_table()
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        padded = np.full((1, chunk), pad, dtype=np.int32)
        padded[0, :len(piece)] = piece
        logits, batcher.cache = family.prefill_into_slot(
            batcher.params, batcher.config, jnp.asarray(padded),
            batcher.cache, jnp.int32(0), jnp.int32(start),
            jnp.int32(len(piece) - 1))
    lengths = jnp.full((batcher.max_slots,), batcher.max_seq - 1,
                       jnp.int32).at[0].set(len(prompt))
    step, batcher.cache = family.decode_step(
        batcher.params, batcher.config,
        jnp.zeros((batcher.max_slots,), jnp.int32).at[0].set(9),
        batcher.cache, lengths)
    return (np.asarray(logits[0, 0]), np.asarray(step[0]),
            np.asarray(batcher.cache["state"][:, 0]),
            np.asarray(batcher.cache["conv"][:, 0]))


@pytest.mark.parametrize("hazard", [
    "pad_tail", "clamped_start", "decode_between_chunks",
    "reuse_after_free", "reuse_after_evict", "reuse_after_recover"])
def test_state_hazards(weights, monkeypatch, hazard):
    config, params = weights()
    if hazard == "pad_tail":
        # whatever the pad of the last chunk holds (an earlier
        # occupant's tokens, had the buffer been reused) enters neither
        # the state nor the tail nor any logit
        clean = _admit_padded(_batcher(config, params), _prompt(70, 5), 0)
        dirty = _admit_padded(_batcher(config, params), _prompt(70, 5), 77)
        for ours, theirs in zip(dirty, clean):
            np.testing.assert_array_equal(ours, theirs)
        return
    shape = {"max_seq": 240} if hazard == "clamped_start" else {}
    length = 230 if hazard == "clamped_start" else 150
    prompt, tokens = _prompt(length, 5), 6
    want = _alone(config, params, prompt, tokens, monkeypatch, **shape)
    batcher = _batcher(config, params, **shape)
    logits = _Logits(batcher, monkeypatch)
    if hazard == "clamped_start":
        # a last chunk that spills past the slot is NOT moved back
        request = _request("a", prompt, tokens)
        request.prefill_pos = 192
        assert batcher._admission_chunk(request)[0] == 192
        assert 192 + batcher.prefill_chunk > batcher.max_seq
        # ... and the whole run agrees with the plain reference
        full = reference.forward(
            params, reference.published_widths(config), prompt, [229])
        np.testing.assert_allclose(want[0], full[0], atol=1e-4)
    if hazard == "decode_between_chunks":
        # another request decodes while "a" is admitted, a chunk a tick
        batcher.submit(_request("b", _prompt(40, 6), 12))
        for _ in range(3):
            batcher.step()
        assert batcher.decoding.any()
    if hazard == "reuse_after_free":
        # every slot has held another request's state and tail
        for index in range(3):
            batcher.submit(_request(f"b{index}", _prompt(90, 7 + index), 3))
        batcher.run_until_drained()
        assert float(jnp.abs(batcher.cache["state"]).max()) > 0
    batcher.submit(_request("a", prompt, tokens))
    if hazard in ("reuse_after_evict", "reuse_after_recover"):
        while len(logits.rows.get("a", ())) < 3:
            batcher.step()
        slot = batcher.slots.index(next(
            request for request in batcher.slots if request is not None))
        if hazard == "reuse_after_evict":
            batcher._evict_slot(slot)
            assert batcher.evictions == 1
        else:
            assert batcher.recover() == 1
            assert not float(jnp.abs(batcher.cache["state"]).max())
    batcher.run_until_drained()
    got = logits.rows["a"]
    assert len(got) == len(want) == tokens
    for index, (ours, theirs) in enumerate(zip(got, want)):
        # (re-admission computes by the chunk scan what decode computed
        # step by step: float32 sums in another order)
        np.testing.assert_allclose(ours, theirs, atol=2e-4,
                                   err_msg=f"token {index}")


def test_prefill_phase_counts_state_carried(weights):
    config, params = weights()
    batcher = _batcher(config, params, decode_block_tokens=4)
    events = []
    batcher.trace = lambda name, ms, info: events.append((name, info))
    batcher.submit(_request("a", _prompt(150, 1), 4))
    batcher.run_until_drained()
    chunks = [info for name, info in events
              if name == "prefill" and info["chunks"]]
    assert [info["state_carried"] for info in chunks] == [0, 1, 1]
    blocks = batcher.take_block_stats()
    assert blocks and all(0.0 < block["state_traffic_share"] < 100.0
                          for block in blocks)


# (d) one process, three families, one batcher class.

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


def test_batcher_serves_all_three_families(weights):
    prompts = [_prompt(20 + 25 * index, index) for index in range(5)]
    tiny = llama.LlamaConfig.tiny()
    llama_params = llama.init_params(jax.random.PRNGKey(0), tiny)
    latent = deepseek.DeepseekConfig.tiny()
    latent_params = deepseek.init_params(jax.random.PRNGKey(0), latent)
    config, params = weights()
    families = {llama: (tiny, llama_params),
                deepseek: (latent, latent_params),
                olmo_hybrid: (config, params)}

    def serve(family, **settings):
        family_config, family_params = families[family]
        assert model_family(family_config) is family
        batcher = ContinuousBatcher(
            family_params, family_config, max_slots=3, max_seq=256,
            prefill_chunk=64, kv_page_tokens=32, **settings)
        served = _generate(batcher, prompts)
        assert batcher._pages.free_pages == batcher._pages.total - 1
        return served

    before = {family: serve(family, decode_block_tokens=4)
              for family in (llama, deepseek)}
    served = serve(olmo_hybrid, decode_block_tokens=4)
    assert len(served) == len(prompts)
    assert all(len(tokens) == 6 for tokens in served.values())
    # the loop's device path == step-by-step decode of the same family
    assert served == serve(olmo_hybrid)
    # and the other two answer as they did before the third was built
    for family, answers in before.items():
        assert serve(family, decode_block_tokens=4) == answers


# (e) the cache: two kinds behind one init_paged_cache.

def test_cache_holds_pages_and_a_state_pool():
    config = _tiny()
    cache = init_paged_cache(config, 4, 256, 16)
    assert set(cache) == {"k", "v", "page_table", "state", "conv"}
    # only the full-attention layers own pages
    assert cache["k"].shape == (2, 4 * 16 + 1, 16, 6 * 16)
    assert cache["state"].shape == (4, 4, 3, 24, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (4, 4, 3, 6 * (2 * 24 + 64))
    published = olmo_hybrid.OlmoHybridConfig()
    assert published.n_paged_layers == 8 \
        and published.n_linear_layers == 24
    assert published.slot_state["state"][1] == (15, 96, 384)
    assert published.conv_width == 11_520
    with pytest.raises(ValueError, match="kv_dtype"):
        _tiny(kv_dtype="int8")
    with pytest.raises(ValueError, match="layer_types"):
        _tiny(layer_types=("linear_attention",) * 6)
    with pytest.raises(ValueError, match="whole periods"):
        _tiny(layer_types=("linear_attention", "full_attention",
                           "full_attention") * 2)


# (f) what the family cannot serve raises and names its parameter, at
# run time (the batcher) and at create time (the parameter check).

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
    assert not hasattr(olmo_hybrid, "prefill_into_slots")


@pytest.mark.parametrize("parameters,named", [
    ({"family": "olmo_hybrid", "widths": {"kv_lora_rank": 4}},
     "kv_lora_rank"),
    ({"family": "olmo_hybrid", "widths": {"layer_types": 3}},
     "layer_types"),
    ({"family": "olmo_hybrid",
      "widths": {"layer_types": ["linear_attention", "sliding"]}},
     "layer_types"),
    ({"family": "olmo_hybrid",
      "widths": {"linear_allow_neg_eigval": 1}}, "linear_allow_neg_eigval"),
    ({"family": "olmo_hybrid", "widths": {"hidden_size": True}},
     "hidden_size"),
    ({"family": "llama", "widths": {"layer_types": ["full_attention"]}},
     "layer_types"),
    ({"family": "olmo_hybrid", "quantize": "int8"}, "quantize"),
    ({"family": "olmo_hybrid", "spec_tokens": 4}, "spec_tokens"),
    ({"family": "olmo_hybrid", "speculative": "ngram"}, "speculative"),
    ({"family": "olmo_hybrid", "prefix_cache": "on"}, "prefix_cache"),
    ({"family": "olmo_hybrid", "model": "tiny"}, "model"),
])
def test_family_parameters_refused_by_name(parameters, named):
    assert named in family_spec_error(parameters)


TINY_WIDTHS = {"hidden_size": 96, "num_hidden_layers": 6,
               "num_attention_heads": 6, "num_key_value_heads": 6,
               "intermediate_size": 160,
               "layer_types": ["linear_attention", "linear_attention",
                               "full_attention"] * 2,
               "linear_num_key_heads": 6, "linear_num_value_heads": 6,
               "linear_key_head_dim": 24, "linear_value_head_dim": 64,
               "linear_allow_neg_eigval": True, "vocab_size": 512}


def test_family_parameters_accepted():
    assert family_spec_error({
        "family": "olmo_hybrid", "quantize": "off", "speculative": "off",
        "widths": TINY_WIDTHS}) is None
    config = olmo_hybrid.OlmoHybridConfig.from_widths(TINY_WIDTHS,
                                                      max_seq=128)
    assert config == dataclasses.replace(_tiny(), dtype="bfloat16",
                                         max_seq=128)
    # the pattern left out is the published one over the depth given
    assert olmo_hybrid.OlmoHybridConfig.from_widths(
        {"num_hidden_layers": 8}).layer_types == (
        ("linear_attention",) * 3 + ("full_attention",)) * 2


# (g) through the LLM element: family + widths, and the telemetry.

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


def test_llm_element_serves_the_hybrid_family(runtime):
    from aiko_services_tpu.pipeline import Pipeline
    responses = queue.Queue()
    pipeline = Pipeline(_definition("hybrid_llm", {
        "family": "olmo_hybrid", "widths": TINY_WIDTHS}), runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    prompts = ["hello there", "general kenobi", "you are a bold one"]
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=180.0)
    assert run_until(runtime, lambda: "llm_state_traffic_share"
                     in pipeline.metrics_text())
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert isinstance(batcher.config, olmo_hybrid.OlmoHybridConfig)
    assert batcher.config.dim == 96 and batcher.config.max_seq == 128
    assert set(batcher.cache) == {"k", "v", "page_table", "state", "conv"}
    share = pipeline.telemetry.registry.quantile(
        "llm_state_traffic_share", 0.5, None, windowed=False)
    assert 0.0 < share <= 100.0 * 1.1
    events = pipeline.recorder.snapshot()
    pools = [event[6] for event in events
             if event[1] == "build" and event[4] == "llm_cache"]
    assert pools and pools[0]["['state']"] == batcher.cache["state"].nbytes
    assert {"['k']", "['v']", "['conv']"} <= set(pools[0])
    carried = [event[6]["state_carried"] for event in events
               if event[1] == "llm_tick" and event[4] == "prefill"
               and event[6] and event[6]["chunks"]]
    assert carried and set(carried) == {0}      # one-chunk prompts
    pipeline.stop()


def test_llm_element_refuses_at_create_time():
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    findings = validate_element_parameters(
        "LLM", {"family": "olmo_hybrid", "widths": TINY_WIDTHS,
                "prefix_cache": "on", "kv_page_tokens": 16},
        "elements[0]", module="aiko_services_tpu.elements.llm")
    assert [finding.rule for finding in findings] == ["bad-parameter"]
    assert "prefix_cache" in findings[0].message
    assert validate_element_parameters(
        "LLM", {"family": "olmo_hybrid", "widths": TINY_WIDTHS},
        "elements[0]", module="aiko_services_tpu.elements.llm") == []
