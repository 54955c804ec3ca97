"""The speech models LEARN: a tiny ASR fitted on a
synthetic tone corpus transcribes held-out audio exactly, the KV-cached
greedy decode is self-consistent with the teacher-forced decoder, and
streaming transcription emits per-chunk text with exactly one compiled
dispatch per chunk (bounded live latency)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aiko_services_tpu.models import asr as asr_model

# 4 "words", each a pure tone; the fitted model maps tone -> letter.
TONES = {"a": 400.0, "b": 800.0, "c": 1600.0, "d": 3000.0}


def tone_chunk(config, freq: float, rng: np.random.Generator):
    """One chunk of a tone with random phase + noise (so held-out draws
    differ from training draws)."""
    t = np.arange(int(config.sample_rate * config.chunk_seconds),
                  dtype=np.float32) / config.sample_rate
    phase = rng.uniform(0, 2 * np.pi)
    wave = 0.5 * np.sin(2 * np.pi * freq * t + phase)
    return (wave + rng.normal(0, 0.01, wave.shape)).astype(np.float32)


def targets_for(config, letters):
    rows = np.full((len(letters), config.max_text), 259, dtype=np.int32)
    for i, letter in enumerate(letters):
        text = asr_model.encode_text(config, letter) + [config.eos_token]
        rows[i, :len(text)] = text
    return jnp.asarray(rows)


@pytest.fixture(scope="module")
def fitted_asr():
    """Train the tiny ASR on the tone corpus until it is exact on its
    training draws (fresh jitter every step, so 'exact' already means
    generalizing over phase/noise)."""
    config = dataclasses.replace(asr_model.AsrConfig.tiny(),
                                 dtype="float32")
    params = asr_model.init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(7)
    letters = list(TONES)
    targets = targets_for(config, letters)
    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state, audio):
        loss, grads = jax.value_and_grad(asr_model.asr_loss)(
            params, config, audio, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def batch():
        return jnp.asarray(np.stack(
            [tone_chunk(config, TONES[letter], rng)
             for letter in letters]))

    loss = None
    for step in range(400):
        params, opt_state, loss = train_step(params, opt_state, batch())
        if step % 25 == 24:
            decoded = [asr_model.decode_text(config, row)
                       for row in np.asarray(asr_model.transcribe(
                           params, config, batch()))]
            if decoded == letters:
                break
    else:
        pytest.fail(f"tone ASR did not converge (loss {float(loss)})")
    return config, params


def test_fitted_asr_transcribes_heldout_exactly(fitted_asr):
    config, params = fitted_asr
    rng = np.random.default_rng(12345)          # unseen draws
    letters = ["c", "a", "d", "b", "a"]
    audio = jnp.asarray(np.stack(
        [tone_chunk(config, TONES[letter], rng) for letter in letters]))
    tokens = np.asarray(asr_model.transcribe(params, config, audio))
    decoded = [asr_model.decode_text(config, row) for row in tokens]
    assert decoded == letters


def test_cached_decode_consistent_with_teacher_forcing(fitted_asr):
    """The KV-cached greedy loop must make exactly the choices the
    teacher-forced decoder would make on its own output -- the
    correctness contract of the O(S) rewrite."""
    config, params = fitted_asr
    rng = np.random.default_rng(99)
    audio = jnp.asarray(np.stack(
        [tone_chunk(config, TONES["b"], rng)]))
    tokens = np.asarray(asr_model.transcribe(params, config, audio))[0]

    encoded = asr_model.encode(params, config,
                               asr_model.log_mel(config, audio))
    inputs = jnp.asarray(
        np.concatenate([[config.bos_token], tokens[:-1]])[None])
    logits = asr_model._decode_states(params, config, inputs, encoded)
    rechecked = np.asarray(jnp.argmax(logits[0], axis=-1))
    for position, token in enumerate(tokens):
        assert rechecked[position] == token, \
            f"divergence at {position}"
        if token == config.eos_token:
            break


def test_streaming_transcription(fitted_asr):
    """Live mode: mic-sized pushes emit text exactly at chunk
    boundaries; every chunk costs one dispatch of the one compiled
    transcribe program (no recompilation as the stream runs -- the
    bounded-latency property)."""
    config, params = fitted_asr
    rng = np.random.default_rng(31)
    streamer = asr_model.StreamingAsr(params, config)
    say = ["a", "d", "c"]
    audio = np.concatenate(
        [tone_chunk(config, TONES[letter], rng) for letter in say])

    pieces, text = np.array_split(audio, 10), ""
    for piece in pieces:
        text += streamer.push(piece)
    text += streamer.flush()
    assert text == "adc"
    assert streamer.chunks_transcribed == 3

    cache_before = asr_model.transcribe._cache_size()
    text2 = streamer.push(tone_chunk(config, TONES["b"], rng))
    assert text2 == "b"
    assert asr_model.transcribe._cache_size() == cache_before


def test_streaming_element_live_path(fitted_asr, runtime):
    """mic-style frames through the real pipeline: the ASR element in
    streaming mode emits chunk text as frames arrive."""
    import queue

    from aiko_services_tpu.pipeline import Pipeline

    config, params = fitted_asr
    rng = np.random.default_rng(17)
    definition = {
        "version": 0, "name": "asr_stream", "runtime": "jax",
        "graph": ["(ASR)"],
        "parameters": {},
        "elements": [{
            "name": "ASR",
            "input": [{"name": "audio"}, {"name": "sample_rate"}],
            "output": [{"name": "text"}],
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.speech",
                "class_name": "ASR"}},
            "parameters": {"streaming": True},
        }]}
    pipeline = Pipeline(definition, runtime=runtime)
    # Inject the fitted float32 model (the element would otherwise
    # init bfloat16 random weights).
    asr_element = pipeline.graph.get_node("ASR").element
    asr_element._params = params
    asr_element._config = config

    responses: "queue.Queue" = queue.Queue()
    collected = []

    def drain(target):
        while not responses.empty():
            *_, swag, _metrics, okay, _diag = responses.get()
            assert okay
            collected.append(swag["text"])
        return len(collected) >= target

    audio = np.concatenate(
        [tone_chunk(config, TONES[letter], rng) for letter in "ba"])
    for piece in np.array_split(audio, 4):
        pipeline.process_frame_local(
            {"audio": piece, "sample_rate": config.sample_rate},
            stream_id="live", queue_response=responses)
    runtime.run(until=lambda: drain(4), timeout=60.0)
    assert "".join(collected) == "ba"


def test_tts_fits_mel_targets():
    """The TTS model learns too (the other half of the speech-path
    proof): fitted on synthetic (text, mel) pairs, it reproduces each
    text's target mel far better than it reproduces the WRONG text's
    target -- the mapping is text-conditional, not memorized noise."""
    import optax

    from aiko_services_tpu.models import tts as tts_model

    config = tts_model.TtsConfig.tiny()
    params = tts_model.init_params(jax.random.PRNGKey(0), config)
    texts = ["aa", "bb", "cc", "dd"]
    tokens = jnp.asarray(np.stack(
        [tts_model.encode_text(config, text) for text in texts]))
    # Distinct smooth mel patterns per text (sinusoid gratings).
    frames, mels = config.n_frames, config.n_mels
    grid_f = np.arange(frames)[:, None] / frames
    grid_m = np.arange(mels)[None, :] / mels
    targets = jnp.asarray(np.stack(
        [np.sin(2 * np.pi * ((i + 1) * grid_f + i * grid_m))
         for i in range(len(texts))], dtype=np.float32))

    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)

    @jax.jit
    def train_step(params, opt_state):
        loss, grads = jax.value_and_grad(tts_model.tts_loss)(
            params, config, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    loss = None
    for _ in range(300):
        params, opt_state, loss = train_step(params, opt_state)
        if float(loss) < 0.08:
            break
    assert float(loss) < 0.15, f"TTS did not fit (loss {float(loss)})"

    mel = tts_model.synthesize_mel(params, config, tokens)
    own = np.abs(np.asarray(mel) - np.asarray(targets)).mean()
    crossed = np.abs(np.asarray(mel)
                     - np.asarray(targets)[::-1]).mean()
    assert own * 3 < crossed        # conditional on the text

    # And the full path still yields a bounded waveform.
    wave = tts_model.synthesize(params, config, "ab")
    assert np.isfinite(wave).all() and np.abs(wave).max() <= 1.0 + 1e-5


def test_subchunk_streaming_partial_latency(fitted_asr):
    """With hop_seconds set, a live hypothesis is
    produced every hop -- per-push latency is bounded by the HOP, not
    chunk_seconds -- and the finalized text still equals the whole-chunk
    decode exactly."""
    config, params = fitted_asr
    rng = np.random.default_rng(41)
    hop_seconds = config.chunk_seconds / 4
    streamer = asr_model.StreamingAsr(params, config,
                                      hop_seconds=hop_seconds)
    chunk_audio = tone_chunk(config, TONES["a"], rng)
    reference = asr_model.decode_text(
        config, np.asarray(asr_model.transcribe(
            params, config, jnp.asarray(chunk_audio[None])))[0])

    pieces = np.array_split(chunk_audio, 4)
    final = streamer.push(pieces[0])
    # A quarter-chunk push already produced a live hypothesis: the
    # first-word latency is one hop, not the 10x longer chunk.
    assert final == ""
    assert streamer.partial_decodes >= 1
    assert isinstance(streamer.partial_text, str)
    first_partial = streamer.partial_text

    final += streamer.push(pieces[1])
    # Two consecutive hypotheses over the same tone agree: the stable
    # prefix holds the agreed text.
    if streamer.partial_text == first_partial:
        assert streamer.stable_text == first_partial
    final += streamer.push(pieces[2])
    final += streamer.push(pieces[3])
    assert final == reference           # finalized == whole-chunk decode
    assert streamer.partial_text == ""  # partial state reset at finalize


def test_streaming_endpoint_finalizes_early(fitted_asr):
    """Energy endpointing: speech followed by trailing silence
    finalizes the utterance immediately -- no waiting for the chunk to
    fill."""
    config, params = fitted_asr
    rng = np.random.default_rng(43)
    chunk = int(config.sample_rate * config.chunk_seconds)
    streamer = asr_model.StreamingAsr(params, config,
                                      endpoint_silence=0.1,
                                      endpoint_threshold=0.05)
    speech = tone_chunk(config, TONES["b"], rng)[:int(chunk * 0.4)]
    silence = np.zeros(int(chunk * 0.15), dtype=np.float32)

    assert streamer.push(speech) == ""          # no endpoint yet
    text = streamer.push(silence)               # trailing quiet >= 0.1 s
    reference = asr_model.decode_text(
        config, np.asarray(asr_model.transcribe(
            params, config, jnp.asarray(asr_model.pad_audio(
                config, np.concatenate([speech, silence]))[None])))[0])
    assert text == reference and text != ""     # finalized early, exact
    assert len(streamer._pending) == 0          # utterance consumed
    # Pure silence afterwards never endpoints (no speech to finalize).
    assert streamer.push(np.zeros(chunk // 2, np.float32)) == ""
