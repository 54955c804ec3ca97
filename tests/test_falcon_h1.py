"""The Falcon-H1 family (models/falcon_h1.py: attention over K/V pages and
a Mamba-2 mixer over a per-slot float32 state in every layer, under muP
multipliers) at a tiny preset on the CPU -- a query group of 5, four
mixer heads in two groups, sub-chunks of 8 -- against its plain float32
reference (benchmark/architectures/falcon_h1.py: the recurrence token by
token), through the same ContinuousBatcher and LLM element as the other
families."""

import dataclasses
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import falcon_h1
from aiko_services_tpu.models.batching import ContinuousBatcher, Request
from aiko_services_tpu.models.families import family_spec_error
from aiko_services_tpu.models.paged import init_paged_cache
from aiko_services_tpu.ops import pallas_ssd
from benchmark.architectures import falcon_h1 as reference

from conftest import run_until

PROMPT = 80         # three chunks of 32, the last one padded


def _tiny(**fields):
    return dataclasses.replace(falcon_h1.FalconH1Config.tiny(),
                               dtype="float32", **fields)


@pytest.fixture(scope="module")
def params():
    # (one program: the init leaf by leaf compiles a dozen)
    return jax.jit(falcon_h1.init_params, static_argnums=1)(
        jax.random.PRNGKey(3), _tiny())


def _batcher(config, params, chunk=32, page=16, max_seq=128, slots=3,
             **settings):
    return ContinuousBatcher(params, config, max_slots=slots,
                             max_seq=max_seq, prefill_chunk=chunk,
                             kv_page_tokens=page, **settings)


def _prompt(length, seed):
    return [257] + np.random.default_rng(seed).integers(
        97, 123, length - 1).tolist()


# (a) prefill then decode through the page pools and the state pool ==
# the reference's full forward pass, on logits: chunks that hand the
# state on, a padded last chunk, decode across a page boundary, the
# kernels interpreted.

@pytest.mark.parametrize("kernels,attention", [("off", "dense"),
                                               ("on", "flash")])
def test_served_matches_reference(params, kernels, attention):
    """float32 served against the float32 reference: what is left is
    the order of the sums (chunk scan against the recurrence, paged
    softmax against the whole one): 1e-4 on logits of unit scale."""
    config = _tiny(kernels=kernels, attention=attention)
    result = reference.compare(_batcher(config, params), 11, PROMPT, 6)
    assert result["max_abs_diff"] < 1e-4, result
    assert result["logit_std"] > 0.3, result
    assert result["argmax_agree"] == result["positions"]


# (b) the two kernels against the recurrence itself.

def _ssd_inputs(tokens, heads=4, p=8, groups=2, n=16, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    # steps from "forgets nothing" to "forgets most in a token"
    dt = jnp.asarray(np.exp(rng.uniform(-7.0, 0.5, (tokens, heads))),
                     jnp.float32)
    a = jnp.asarray(-np.exp(rng.uniform(0.0, 2.8, heads)), jnp.float32)
    return (normal(tokens, heads, p), dt, a, normal(tokens, groups, n),
            normal(tokens, groups, n), jnp.linspace(0.5, 1.5, heads),
            normal(heads, p, n))


@pytest.mark.parametrize("kernel", [False, True])
def test_ssd_kernels_match_recurrence(kernel):
    x, dt, a, b, c, d, state = _ssd_inputs(20)
    want_y, want_state = pallas_ssd.ssd_recurrence(x, dt, a, b, c, d, state)
    y, got_state = pallas_ssd.ssd_chunk_scan(x, dt, a, b, c, d, state,
                                             chunk=8, kernel=kernel)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)
    # a pad tail with dt = 0 leaves the state where the real tokens did
    masked = dt.at[13:].set(0.0)
    _, padded = pallas_ssd.ssd_chunk_scan(x, masked, a, b, c, d, state,
                                          chunk=8, kernel=kernel)
    _, real = pallas_ssd.ssd_recurrence(x[:13], dt[:13], a, b[:13],
                                        c[:13], d, state)
    np.testing.assert_allclose(padded, real, atol=2e-5, rtol=1e-5)
    # decode: one token a live row, dead rows untouched bit for bit
    rows = 5
    pool = jnp.asarray(np.random.default_rng(9).standard_normal(
        (3, rows, 4, 8, 16)), jnp.float32)
    active = jnp.array([False, True, False, True, True])
    out, new_pool = pallas_ssd.ssd_decode_step(
        x[:rows], dt[:rows], a, b[:rows], c[:rows], d, pool, 1, active,
        kernel=kernel)
    for row in range(rows):
        if not active[row]:
            np.testing.assert_array_equal(new_pool[1, row], pool[1, row])
            assert not np.any(out[row])
            continue
        want_y, want_state = pallas_ssd.ssd_recurrence(
            x[row:row + 1], dt[row:row + 1], a, b[row:row + 1],
            c[row:row + 1], d, pool[1, row])
        np.testing.assert_allclose(out[row], want_y[0], atol=2e-5)
        np.testing.assert_allclose(new_pool[1, row], want_state, atol=2e-5)
    np.testing.assert_array_equal(new_pool[0], pool[0])


# (c) the state handed from chunk to chunk: 1, 2 or 3 chunks, a padded
# last one each time, and a slot that held another request.

def _admit(batcher, prompt, slot=0):
    """``prompt`` into ``slot`` chunk by chunk through the batcher's own
    admission program (pad tokens 77: they must enter nothing).  Returns
    (the last chunk's logits, the slot's state, its tail)."""
    chunk = batcher.prefill_chunk
    batcher._pages.ensure(slot, batcher._pages.pps)
    batcher._sync_page_table()
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        padded = np.full((1, chunk), 77, dtype=np.int32)
        padded[0, :len(piece)] = piece
        logits, batcher.cache = falcon_h1.prefill_into_slot(
            batcher.params, batcher.config, jnp.asarray(padded),
            batcher.cache, jnp.int32(slot), jnp.int32(start),
            jnp.int32(len(piece) - 1))
    return (np.asarray(logits[0, 0]),
            np.asarray(batcher.cache["state"][:, slot]),
            np.asarray(batcher.cache["conv"][:, slot]))


@pytest.fixture(scope="module")
def one_chunk(params):
    return _admit(_batcher(_tiny(), params, chunk=96), _prompt(PROMPT, 5))


@pytest.mark.parametrize("chunk,reused", [(48, False), (32, False),
                                          (32, True)])
def test_chunked_admission_and_reuse_agree(params, one_chunk, chunk,
                                           reused):
    batcher = _batcher(_tiny(), params, chunk=chunk)
    if reused:
        # slot 0 has held another request's state and tail
        batcher.submit(Request(request_id="before",
                               prompt_tokens=_prompt(70, 8),
                               max_new_tokens=3))
        batcher.run_until_drained()
        assert float(jnp.abs(batcher.cache["state"][:, 0]).max()) > 0
    got = _admit(batcher, _prompt(PROMPT, 5))
    for ours, theirs in zip(got, one_chunk):
        # (the chunk scan in other sub-chunks: float32 sums in another
        # order)
        np.testing.assert_allclose(ours, theirs, atol=2e-5, rtol=1e-5)
    if reused:
        fresh = _admit(_batcher(_tiny(), params, chunk=chunk),
                       _prompt(PROMPT, 5))
        for ours, theirs in zip(got, fresh):
            np.testing.assert_array_equal(ours, theirs)


# (d) every multiplier is on the served path.

SCALARS = ("embedding_multiplier", "lm_head_multiplier", "key_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "ssm_in_multiplier", "ssm_out_multiplier", "mlp_multipliers:0",
           "mlp_multipliers:1")


def _logits(config, params):
    """One chunk of 16 into a fresh cache: the last position's logits."""
    tokens = jnp.asarray([_prompt(16, 6)], jnp.int32)
    logits, _ = falcon_h1.prefill_into_slot(
        params, config, tokens, init_paged_cache(config, 1, 16, 16),
        jnp.int32(0), jnp.int32(0), jnp.int32(15))
    return np.asarray(logits[0, 0])


@pytest.fixture(scope="module")
def baseline(params):
    return _logits(_tiny(), params)


@pytest.mark.parametrize("removed", SCALARS + tuple(
    f"ssm_multipliers:{segment}" for segment in range(5)))
def test_every_multiplier_moves_the_logits(params, baseline, removed):
    """A multiplier set to 1 moves the logits by far more than (a)'s
    tolerance of 1e-4: none of them is dropped on the way."""
    config = _tiny()
    name, _, index = removed.partition(":")
    value = 1.0
    if index:
        value = list(getattr(config, name))
        value[int(index)] = 1.0
    assert getattr(config, name) != value
    moved = _logits(dataclasses.replace(config, **{name: value}), params)
    assert np.abs(moved - baseline).max() > 1e-2


# (e) the cache: pages for every layer and a state pool beside them.

def test_cache_holds_pages_and_state_for_every_layer():
    cache = init_paged_cache(_tiny(), 4, 128, 16)
    assert set(cache) == {"k", "v", "page_table", "state", "conv"}
    assert cache["k"].shape == (2, 4 * 8 + 1, 16, 16)
    assert cache["state"].shape == (2, 4, 4, 8, 16)
    assert cache["conv"].shape == (2, 4, 3, 32 + 64)
    assert cache["state"].dtype == cache["conv"].dtype == jnp.float32
    published = falcon_h1.FalconH1Config(n_layers=6)
    lead, shape, _ = published.slot_state["state"]
    assert lead == (6,) and np.prod(shape) * 4 == 4_194_304
    lead, shape, _ = published.slot_state["conv"]
    assert np.prod(shape) * 4 == 61_440
    assert published.in_width == 9248 and published.n_paged_layers == 6


# (f) widths and what the family refuses, by name.

TINY_WIDTHS = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 5, "num_key_value_heads": 1, "head_dim": 16,
    "intermediate_size": 96, "mamba_d_ssm": 32, "mamba_n_heads": 4,
    "mamba_d_head": 8, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_chunk_size": 8, "rope_theta": 10_000.0,
    "attention_in_multiplier": 0.7, "key_multiplier": 0.011048543456039804,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738]}


@pytest.mark.parametrize("parameters,named", [
    ({"widths": {**TINY_WIDTHS, "ssm_multipliers": [0.5] * 4}},
     "ssm_multipliers"),
    ({"widths": {**TINY_WIDTHS, "mlp_multipliers": 0.5}},
     "mlp_multipliers"),
    ({"widths": {**TINY_WIDTHS, "mlp_multipliers": [0.5, True]}},
     "mlp_multipliers"),
    ({"widths": {**TINY_WIDTHS, "key_multiplier": "0.01"}},
     "key_multiplier"),
    ({"widths": {"layer_types": ["full_attention"]}}, "layer_types"),
    ({"quantize": "int8"}, "quantize"),
    ({"speculative": "ngram"}, "speculative"),
    ({"spec_tokens": 4}, "spec_tokens"),
    ({"prefix_cache": "on"}, "prefix_cache"),
    ({"model": "tiny"}, "model"),
    (None, None),
])
def test_family_widths_and_refusals(params, parameters, named):
    if parameters is None:
        assert family_spec_error({"family": "falcon_h1",
                                  "widths": TINY_WIDTHS}) is None
        config = falcon_h1.FalconH1Config.from_widths(
            TINY_WIDTHS, max_seq=256, dtype="float32")
        assert config == _tiny()
        assert isinstance(config.rope_theta, float)
        for settings, name in [({"kv_page_tokens": 0}, "kv_page_tokens"),
                               ({"speculative": "ngram",
                                 "decode_block_tokens": 8}, "speculative"),
                               ({"prefix_cache": "on"}, "prefix_cache")]:
            with pytest.raises(ValueError, match=name):
                ContinuousBatcher(params, config, max_slots=2, max_seq=128,
                                  prefill_chunk=32,
                                  **{"kv_page_tokens": 16, **settings})
        assert not hasattr(falcon_h1, "prefill_into_slots")
        with pytest.raises(ValueError, match="ssm_multipliers"):
            _tiny(ssm_multipliers=(1.0,) * 4)
        return
    assert named in family_spec_error({"family": "falcon_h1",
                                       **parameters})


# (g) through the LLM element: family + widths, and the telemetry.

def test_llm_element_serves_the_family(runtime):
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    from aiko_services_tpu.pipeline import Pipeline
    assert validate_element_parameters(
        "LLM", {"family": "falcon_h1", "widths": TINY_WIDTHS},
        "elements[0]", module="aiko_services_tpu.elements.llm") == []
    responses = queue.Queue()
    pipeline = Pipeline({
        "version": 0, "name": "falcon_llm", "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 8, "max_seq": 128,
                           "decode_block_tokens": 4, "kv_page_tokens": 16,
                           "family": "falcon_h1",
                           "widths": TINY_WIDTHS},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}, runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    prompts = ["hello there", "general kenobi", "you are a bold one"]
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=180.0)
    assert run_until(runtime, lambda: "llm_state_traffic_share"
                     in pipeline.metrics_text())
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert isinstance(batcher.config, falcon_h1.FalconH1Config)
    share = pipeline.telemetry.registry.quantile(
        "llm_state_traffic_share", 0.5, None, windowed=False)
    assert 0.0 < share <= 100.0
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
