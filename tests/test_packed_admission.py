"""Llama-family admission as one upload and one program
(``ADMISSION_PICKS_FIRST``): a chunk's tokens and scalars go up as ONE
packed int32 array, and the program runs the head at the chunk's last
real position alone and chooses a prompt's first token by the
batcher's own rule (``ContinuousBatcher._sample``), writing it into
the first-token vector.  Toy widths in float32, where the one-row head
and the full-logit head agree to the sampled token."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.batching import ContinuousBatcher, Request

PAGE, CHUNK, MAX_SEQ = 8, 32, 48


@pytest.fixture(scope="module")
def tiny_f32():
    config = dataclasses.replace(llama.LlamaConfig.tiny(), dtype="float32")
    return config, llama.init_params(jax.random.PRNGKey(0), config)


def _prompt(length, seed=0):
    rng = np.random.default_rng([length, seed])
    return [257] + rng.integers(3, 500, length - 1).tolist()


@pytest.mark.parametrize("rule", ["greedy", "top_k", "temperature"])
@pytest.mark.parametrize("final", [True, False], ids=["final", "not_final"])
@pytest.mark.parametrize("width", [8, 16, 32])
def test_the_program_picks_the_token_sample_would(tiny_f32, width, final,
                                                  rule):
    """At every admission width: the served program's first token is
    ``_sample`` of row ``last`` of the full-logit
    ``llama.prefill_into_slot`` -- the argmax at temperature 0, a draw
    from the same split of a fixed key (under ``sample_top_k`` or not)
    otherwise -- written at the slot and nowhere else, with the key
    the batcher goes on with; a chunk that does not end its prompt
    writes nothing and leaves the key.  Both forms write the same
    cache."""
    config, params = tiny_f32
    temperature = 0.0 if rule == "greedy" else 1.3
    batcher = ContinuousBatcher(
        params, config, max_slots=4, max_seq=MAX_SEQ, prefill_chunk=CHUNK,
        kv_page_tokens=PAGE, sample_top_k=5 if rule == "top_k" else 0)
    assert width in batcher.admission_widths == (8, 16, 32)
    slot, start, tokens = 2, 8, _prompt(width - 3, seed=width)
    assert batcher._ensure_pages(slot, start + width, start)
    batcher._sync_page_table()
    key = jax.random.PRNGKey(width + 7)
    firsts = jnp.arange(4, dtype=jnp.int32) + 1000

    padded = np.zeros((1, width), dtype=np.int32)
    padded[0, :len(tokens)] = tokens
    copy = jax.tree_util.tree_map(jnp.copy, batcher.cache)
    logits, full_cache = llama.prefill_into_slot(
        params, config, jnp.asarray(padded), copy, jnp.int32(slot),
        jnp.int32(start))
    assert logits.shape == (1, width, config.vocab_size)
    batcher._key = key
    expected = batcher._sample(logits[:, len(tokens) - 1, :], temperature)

    packed = llama.pack_admission(tokens, width, slot, start, final,
                                  temperature)
    assert packed.shape == (1, width + 5) and packed.dtype == np.int32
    cache, firsts_out, token, key_out = llama.admit_packed(
        params, config, jax.device_put(packed), batcher.cache, firsts, key,
        top_k=batcher.sample_top_k)
    written = np.array(firsts)
    if final:
        assert int(token[0]) == int(expected[0])
        written[slot] = int(expected[0])
        np.testing.assert_array_equal(np.asarray(key_out),
                                      np.asarray(batcher._key))
    else:
        np.testing.assert_array_equal(np.asarray(key_out), np.asarray(key))
    np.testing.assert_array_equal(np.asarray(firsts_out), written)
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[side]),
                                      np.asarray(full_cache[side]))


def _serve(config, params, prompts, picks, monkeypatch, **kw):
    """Drain greedy ``prompts`` through one batcher, its admission the
    served program or (``picks`` False) the full-logit program with
    the first token sampled eagerly -> {request: tokens}."""
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    with monkeypatch.context() as patch:
        if not picks:
            patch.setattr(llama, "ADMISSION_PICKS_FIRST", False)
        batcher = ContinuousBatcher(
            params, config, **{"max_slots": 3, "max_seq": MAX_SEQ,
                               "prefill_chunk": CHUNK,
                               "kv_page_tokens": PAGE, **kw})
        for index, prompt in enumerate(prompts):
            batcher.submit(Request(request_id=f"r{index}",
                                   prompt_tokens=list(prompt),
                                   max_new_tokens=5, emit=emit))
        assert batcher.run_until_drained(max_steps=800) < 800
    return emitted


@pytest.mark.parametrize("block", [8, 0], ids=["device_loop", "per_token"])
def test_served_admission_serves_the_full_logit_tokens(tiny_f32, block,
                                                       monkeypatch):
    """A mixed-length batch -- one token to a prompt that takes two
    chunks, more prompts than slots -- emits the same greedy streams
    whether the admission program picks the first token or the
    full-logit program's last row is sampled after it, on the device
    loop and on the per-token tick."""
    config, params = tiny_f32
    prompts = [_prompt(length) for length in (1, 7, 9, 20, 31, 33, 40)]
    served = _serve(config, params, prompts, True, monkeypatch,
                    decode_block_tokens=block)
    full = _serve(config, params, prompts, False, monkeypatch,
                  decode_block_tokens=block)
    assert served == full
    assert sorted(served) == [f"r{index}" for index in range(7)]
    assert all(len(tokens) == 5 for tokens in served.values())


@pytest.mark.parametrize("page,length,width", [
    (0, 5, 128), (0, 200, 256), (0, 400, 512),      # no page table
    (PAGE, 5, 8), (PAGE, 12, 16), (PAGE, 30, 32)])
def test_admission_is_two_launches_whatever_the_width(tiny_f32, page,
                                                      length, width):
    """The admission twin of the fold's two launches: a one-chunk
    admission's ``prefill`` phase makes one upload and one program
    whatever its width (``info.launches`` 2), and one upload more where
    the chunk took pages and so changed a page-table row (3)."""
    config, params = tiny_f32
    config = dataclasses.replace(config, max_seq=512)
    phases = []
    batcher = ContinuousBatcher(
        params, config, max_slots=2, max_seq=512 if not page else MAX_SEQ,
        prefill_chunk=512 if not page else CHUNK, kv_page_tokens=page,
        decode_block_tokens=4,
        trace=lambda name, ms, info=None: phases.append((name, ms, info)))
    batcher.submit(Request(request_id="r", prompt_tokens=_prompt(length),
                           max_new_tokens=3))
    assert batcher.run_until_drained(max_steps=100) < 100
    chunks = [info for name, ms, info in phases
              if name == "prefill" and info["chunks"]]
    launched = [info for name, ms, info in phases
                if ms is None and name == "prefill_into_slot"]
    assert [info["width"] for info in launched] == [width]
    assert [info["launches"] for info in chunks] == [3 if page else 2]
    assert all(info["launches"] == 0 for name, ms, info in phases
               if name == "prefill" and not info["chunks"])
