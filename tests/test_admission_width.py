"""Admission programs as wide as their chunks: a family that declares
``ADMISSION_NARROW_CHUNKS`` (the Llama family) pads a chunk to the
least width that holds its real tokens -- the page times a power of
two, capped at ``prefill_chunk`` -- and serves the same tokens as
``prefill_chunk``-wide admission; the other families keep their one
width.  Float32 on the device loop, where the two widths' programs
agree to the greedy token."""

import dataclasses
import math

import jax
import numpy as np
import pytest

from aiko_services_tpu.models import (deepseek, llama, olmo_hybrid,
                                      sdar)
from aiko_services_tpu.models.batching import ContinuousBatcher, Request

PAGE, CHUNK, MAX_SEQ = 8, 32, 48
PROGRAMS = ("prefill_into_slot", "prefill_into_slots")


@pytest.fixture(scope="module")
def tiny_f32():
    config = dataclasses.replace(llama.LlamaConfig.tiny(), dtype="float32")
    return config, llama.init_params(jax.random.PRNGKey(0), config)


def _prompt(length, seed=0):
    rng = np.random.default_rng([length, seed])
    return [257] + rng.integers(3, 500, length - 1).tolist()


def _serve(config, params, prompts, narrow, monkeypatch, max_new=6,
           fault_at=None, serial_first=False, **kw):
    """Drain ``prompts`` through one batcher, with narrow admission or
    with every chunk ``prefill_chunk`` wide -> ({request: tokens},
    batcher, [(program, launch info)]).  ``kv_page_tokens=0`` is the
    reference that shares no page or width logic: a dense cache, whose
    narrowest admission (128 tokens) is wider than ``CHUNK``."""
    emitted, launched, probed = {}, [], {"blocks": 0}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    def tap(name, ms, info=None):
        if ms is None and name in PROGRAMS:
            launched.append((name, info))

    def probe(point):
        probed["blocks"] += 1
        if probed["blocks"] == fault_at:
            raise RuntimeError("injected chip death")

    with monkeypatch.context() as patch:
        if not narrow:
            patch.setattr(llama, "ADMISSION_NARROW_CHUNKS", False)
        batcher = ContinuousBatcher(
            params, config, trace=tap, fault_probe=probe,
            **{"max_slots": 4, "max_seq": MAX_SEQ, "prefill_chunk": CHUNK,
               "kv_page_tokens": PAGE, "decode_block_tokens": 8,
               "inflight": 1, **kw})
    for index, prompt in enumerate(prompts):
        batcher.submit(Request(request_id=f"r{index}",
                               prompt_tokens=list(prompt),
                               max_new_tokens=max_new, emit=emit))
        if serial_first and index == 0:
            assert batcher.run_until_drained(max_steps=500) < 500
    steps = 0
    while (batcher.pending or batcher.active_count
           or batcher.blocks_in_flight) and steps < 1000:
        try:
            batcher.step()
        except RuntimeError:
            assert batcher.recover() >= 1
        steps += 1
    assert steps < 1000
    return emitted, batcher, launched


# Prompts of 1, page - 1, page, page + 1, chunk - 1, chunk, chunk + 1
# and 40 tokens; the last two's second chunks are moved back to fit the
# slot (start 16, not 32: ``max_seq - prefill_chunk``).
@pytest.mark.parametrize("length", [1, PAGE - 1, PAGE, PAGE + 1,
                                    CHUNK - 1, CHUNK, CHUNK + 1, 40])
def test_narrow_admission_serves_the_full_width_tokens(tiny_f32, length,
                                                       monkeypatch):
    config, params = tiny_f32
    prompts = [_prompt(length)]
    full, _, wide = _serve(config, params, prompts, False, monkeypatch)
    dense, _, _ = _serve(config, params, prompts, False, monkeypatch,
                         kv_page_tokens=0)
    narrow, batcher, launched = _serve(config, params, prompts, True,
                                       monkeypatch)
    assert narrow == full == dense and len(narrow["r0"]) == 6
    assert {info["width"] for _, info in wide} == {CHUNK}
    # Each chunk ran at the least width that holds it.
    chunks = [info["tokens"] for _, info in launched]
    assert [info["width"] for _, info in launched] == [
        min(w for w in (8, 16, 32) if w >= tokens) for tokens in chunks]
    assert batcher.prefill_tokens == length
    if length > CHUNK:
        # the last chunk starts at 16, and re-writes 16 tokens
        assert chunks == [CHUNK, length - 16]


@pytest.mark.parametrize("mode", ["prefix_cache", "recover", "burst"])
def test_narrow_admission_holds_where_a_chunk_does_not_start_a_prompt(
        tiny_f32, mode, monkeypatch):
    """The same tokens where admission starts past adopted prefix
    pages, where ``recover()`` re-prefills prompt + committed tokens
    after a device loss (both one slot, so one chunk a program), and
    through the batched multi-slot admission (dense attention), whose
    burst runs at its widest row's width."""
    config, params = tiny_f32
    shared = _prompt(17)[:16]                      # two whole pages
    prompts = {"prefix_cache": [shared + _prompt(5, seed) for seed in
                                range(3)],
               "recover": [_prompt(n) for n in (3, 12, 29, 35)],
               "burst": [_prompt(n) for n in (3, 12, 5, 9)]}[mode]
    settings = {"prefix_cache": {"prefix_cache": True,
                                 "prefix_min_tokens": PAGE,
                                 "serial_first": True, "max_slots": 1},
                "recover": {"fault_at": 2, "max_slots": 1},
                "burst": {}}[mode]
    unfaulted = {k: v for k, v in settings.items() if k != "fault_at"}
    full, _, _ = _serve(config, params, prompts, False, monkeypatch,
                        **unfaulted)
    dense, _, _ = _serve(config, params, prompts, False, monkeypatch,
                         **{**unfaulted, "kv_page_tokens": 0,
                            "prefix_cache": False})
    narrow, batcher, launched = _serve(config, params, prompts, True,
                                       monkeypatch, **settings)
    assert narrow == full == dense
    widths = [info["width"] for _, info in launched]
    assert min(widths) < CHUNK
    if mode == "prefix_cache":
        assert batcher.prefix_shared_tokens >= 32
        assert batcher._pages.leaked_pages() == 0
    if mode == "recover":
        assert batcher.recoveries == 1
    programs = {name for name, _ in launched}
    if mode == "burst":
        # one burst of four chunks, its widest 12 tokens
        assert programs == {"prefill_into_slots"} and widths == [16]
    else:
        assert programs == {"prefill_into_slot"}


@pytest.mark.parametrize("page,chunk,max_seq,widths", [
    (8, 32, 48, (8, 16, 32)),
    (128, 512, 512, (128, 256, 512)),
    (16, 16, 64, (16,)),
    (0, 512, 512, (128, 256, 512)),         # dense: from 128 tokens
    (0, 64, 64, (64,)),
])
def test_admission_widths(tiny_f32, page, chunk, max_seq, widths):
    config, params = tiny_f32
    batcher = ContinuousBatcher(params, config, max_slots=2,
                                max_seq=max_seq, prefill_chunk=chunk,
                                kv_page_tokens=page)
    assert batcher.admission_widths == widths
    assert len(widths) <= math.log2(chunk / (page or 128)) + 1 \
        or widths == (chunk,)
    assert batcher.prefill_chunk == chunk


def test_every_width_is_built_before_traffic_and_none_after(tiny_f32):
    """``warm_admission`` builds the served program (the one that
    picks the first token) once a width and nothing else, and serving
    prompts of every length after it builds no admission program --
    none of the served program's, and no eager slice, sample or
    first-token write around it."""
    config, params = tiny_f32
    config = dataclasses.replace(config, max_seq=200)  # shapes of its own
    builds = []

    def listen(event, duration, **facts):
        if event == "/jax/core/compile/backend_compile_duration":
            builds.append(str(facts.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # (one slot: every chunk takes the one-slot program, as under
        # flash attention)
        batcher = ContinuousBatcher(
            params, config, max_slots=1, max_seq=MAX_SEQ,
            prefill_chunk=CHUNK, kv_page_tokens=PAGE,
            decode_block_tokens=8)
        cached = llama._prefill_into_slot_jit._cache_size
        before, seen = cached(), len(builds)
        batcher.warm_admission()
        admission = [name for name in builds
                     if "prefill_into_slot" in name]
        assert builds[seen:] == admission
        assert len(admission) == len(batcher.admission_widths) \
            <= math.log2(CHUNK / PAGE) + 1
        warmed = cached()
        assert warmed - before == len(admission)
        assert batcher._pages.free_pages == batcher._pages.total - 1
        seen = len(builds)
        for length in (1, 7, 9, 20, 31, 33, 40):
            batcher.submit(Request(request_id=str(length),
                                   prompt_tokens=_prompt(length),
                                   max_new_tokens=3))
        assert batcher.run_until_drained(max_steps=500) < 500
        assert [name for name in builds
                if "prefill_into_slot" in name] == admission
        # (the programs warm-up built are all that traffic called)
        assert cached() == warmed
        assert not [name for name in builds[seen:]
                    if any(eager in name for eager in
                           ("_set_first", "dynamic_slice", "argmax"))]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_the_element_counts_each_chunk_against_its_width(runtime):
    """Through the LLM element: the model build records the widths it
    built, every admission launch carries its ``width`` beside its
    ``tokens``, ``llm_admission_fill`` is observed once a chunk (real
    tokens over width, %), and no admission program is built after
    the first request's."""
    import queue

    from aiko_services_tpu.pipeline import Pipeline
    from conftest import run_until

    definition = {
        "version": 0, "name": "llm_widths", "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 4, "max_seq": 128,
                           "max_slots": 1, "decode_block_tokens": 4,
                           "kv_page_tokens": 16},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}
    prompts = ["hello there", "a caption of forty bytes, give or take"]
    responses = queue.Queue()
    pipeline = Pipeline(definition, runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=120.0)
    registry = pipeline.telemetry.registry

    def fills():
        return [summary["count"] for name, _, summary
                in registry.summaries(windowed=False)
                if name == "llm_admission_fill"]

    assert run_until(runtime, lambda: fills() == [2])
    events = pipeline.recorder.snapshot()
    widths = [event[6] for event in events
              if event[1] == "build" and event[4] == "llm_admission_widths"]
    assert [info["widths"] for info in widths] == [[16, 32, 64, 128]]
    chunks = [(event[0], event[6]) for event in events
              if event[1] == "launch" and event[4] == "prefill_into_slot"]
    assert [(info["tokens"], info["width"]) for _, info in chunks] == [
        (12, 16), (39, 64)]
    summary = next(summary for name, _, summary
                   in registry.summaries(windowed=False)
                   if name == "llm_admission_fill")
    assert (summary["min_ms"], summary["max_ms"]) == pytest.approx(
        (100.0 * 39 / 64, 100.0 * 12 / 16), abs=1e-4)
    assert not [event for event in events
                if event[1] == "build" and event[0] > chunks[0][0]
                and "prefill_into_slot" in str(event[4])]
    pipeline.stop()


@pytest.mark.parametrize("family", [deepseek, olmo_hybrid, sdar],
                         ids=lambda module: module.__name__.split(".")[-1])
def test_a_family_without_the_capability_keeps_full_chunks(family):
    """The families with admission programs of their own (latent keys
    expanded over the slot, a chunk scan that hands a state on, whole
    blocks under a block-causal mask) launch ``prefill_chunk``-wide
    chunks whatever the prompt, and build nothing ahead of traffic."""
    config_class = {deepseek: deepseek.DeepseekConfig,
                    olmo_hybrid: olmo_hybrid.OlmoHybridConfig,
                    sdar: sdar.SdarConfig}[family]
    config = dataclasses.replace(config_class.tiny(), dtype="float32")
    params = family.init_params(jax.random.PRNGKey(3), config)
    launched = []
    batcher = ContinuousBatcher(
        params, config, max_slots=2, max_seq=256, prefill_chunk=64,
        kv_page_tokens=16 if family is olmo_hybrid else 32,
        decode_block_tokens=8,
        trace=lambda name, ms, info=None: launched.append(info)
        if ms is None and name == "prefill_into_slot" else None)
    assert not hasattr(family, "ADMISSION_NARROW_CHUNKS")
    assert batcher.admission_widths == (64,)
    batcher.warm_admission()
    assert launched == []
    batcher.submit(Request(request_id="short", prompt_tokens=_prompt(9),
                           max_new_tokens=2))
    batcher._admit()
    assert batcher._prefill_tick() == 1
    assert [info["width"] for info in launched] == [64]
    assert launched[0]["tokens"] == (8 if family is sdar else 9)
    assert batcher.take_admission_fills() == [
        pytest.approx(100.0 * launched[0]["tokens"] / 64)]
