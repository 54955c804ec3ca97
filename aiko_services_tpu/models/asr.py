"""Speech recognition, TPU-first (BASELINE config 5; reference
equivalent: examples/speech/speech_elements.py:203-239 PE_WhisperX, which
wraps the external whisperx/CUDA model -- here the ASR model is the
framework's own, functional JAX with weights resident in HBM).

Whisper-class shape, house architecture (shared with models/llama.py):

- **log-mel frontend** in pure jnp: frame -> Hann window -> rfft ->
  mel filterbank -> log, all static shapes, jittable on device;
- **encoder**: two strided 1-D convs (4x subsampling) + sinusoidal
  positions + a ``lax.scan`` over pre-norm transformer layers
  (bidirectional attention, RMSNorm + SwiGLU -- the same blocks the
  rest of the framework uses, ops/layers.py);
- **decoder**: byte-level tokens, causal self-attention plus
  cross-attention to the encoder output, scanned layers;
- **greedy transcribe** runs the whole decode as one ``lax.scan`` with
  a static token budget (no data-dependent Python control flow; EOS
  handled by masking) -- one trace, one compile per audio bucket.
  The decode is KV-CACHED: cross-attention K/V are projected once per
  utterance, self-attention K/V append to a cache (the same split-
  softmax read-only-cache pattern as models/llama.py decode), so a
  transcription costs O(S) decoder work, not the O(S^2) of re-running
  the teacher-forced decoder per emitted token;
- **StreamingAsr** transcribes live audio incrementally: push samples,
  full chunks each cost exactly one compiled dispatch (bounded
  per-chunk latency for the mic -> text path).

Audio is right-padded to a fixed chunk (``chunk_seconds``) so every
utterance compiles to the same shapes (the ShapeBucketer idea applied
to sound).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.layers import attention_decode_append, rms_norm, swiglu

__all__ = ["AsrConfig", "init_params", "log_mel", "encode",
           "transcribe", "asr_loss", "partition_specs",
           "StreamingAsr"]


@dataclasses.dataclass(frozen=True)
class AsrConfig:
    # audio frontend
    sample_rate: int = 16_000
    chunk_seconds: float = 10.0
    n_fft: int = 400              # 25 ms window
    hop: int = 160                # 10 ms hop
    n_mels: int = 80
    # model
    vocab_size: int = 260         # bytes + BOS/EOS/PAD specials
    dim: int = 384
    n_heads: int = 6
    n_encoder_layers: int = 4
    n_decoder_layers: int = 4
    hidden_dim: int = 1536
    max_text: int = 128           # static decode budget (tokens)
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    bos_token: int = 257
    eos_token: int = 258

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_frames(self) -> int:
        """Mel frames per chunk (before conv subsampling)."""
        return int(self.sample_rate * self.chunk_seconds) // self.hop

    @property
    def n_audio_positions(self) -> int:
        return self.n_frames // 4    # two stride-2 convs

    @classmethod
    def base(cls) -> "AsrConfig":
        return cls()

    @classmethod
    def tiny(cls) -> "AsrConfig":
        """Test-size: milliseconds on a CPU mesh."""
        return cls(chunk_seconds=1.0, n_mels=16, dim=32, n_heads=2,
                   n_encoder_layers=2, n_decoder_layers=2, hidden_dim=64,
                   max_text=16)


def _dtype(config):
    return jnp.dtype(config.dtype)


# ---------------------------------------------------------------------------
# Log-mel frontend (static shapes, on-device).

def _mel_filterbank(config: AsrConfig) -> np.ndarray:
    """[n_fft//2+1, n_mels] triangular filters (host-side constant)."""
    n_bins = config.n_fft // 2 + 1
    f_max = config.sample_rate / 2

    def hz_to_mel(f):
        return 2595.0 * np.log10(1.0 + f / 700.0)

    def mel_to_hz(m):
        return 700.0 * (10.0 ** (m / 2595.0) - 1.0)

    mel_points = np.linspace(0.0, hz_to_mel(f_max), config.n_mels + 2)
    hz_points = mel_to_hz(mel_points)
    bins = np.floor((config.n_fft + 1) * hz_points
                    / config.sample_rate).astype(int)
    bank = np.zeros((n_bins, config.n_mels), dtype=np.float32)
    for m in range(1, config.n_mels + 1):
        left, centre, right = bins[m - 1], bins[m], bins[m + 1]
        for k in range(left, centre):
            if centre > left:
                bank[k, m - 1] = (k - left) / (centre - left)
        for k in range(centre, right):
            if right > centre:
                bank[k, m - 1] = (right - k) / (right - centre)
    return bank


def log_mel(config: AsrConfig, samples: jax.Array) -> jax.Array:
    """waveform [B, T] float32 (T = chunk worth of samples, pre-padded)
    -> log-mel [B, n_frames, n_mels]."""
    frames = config.n_frames
    window = jnp.asarray(np.hanning(config.n_fft).astype(np.float32))
    bank = jnp.asarray(_mel_filterbank(config))
    pad = config.n_fft // 2
    padded = jnp.pad(samples, ((0, 0), (pad, pad)), mode="reflect")
    # Gather strided frames: [B, n_frames, n_fft].
    starts = jnp.arange(frames) * config.hop
    index = starts[:, None] + jnp.arange(config.n_fft)[None, :]
    stacked = padded[:, index]                      # [B, F, n_fft]
    spectrum = jnp.fft.rfft(stacked * window, axis=-1)
    power = jnp.abs(spectrum) ** 2                  # [B, F, bins]
    mel = power @ bank                              # [B, F, n_mels]
    log_spec = jnp.log10(jnp.maximum(mel, 1e-10))
    log_spec = jnp.maximum(log_spec, log_spec.max() - 8.0)
    return (log_spec + 4.0) / 4.0


def pad_audio(config: AsrConfig, samples: np.ndarray) -> np.ndarray:
    """Right-pad/trim a mono waveform to exactly one chunk."""
    want = int(config.sample_rate * config.chunk_seconds)
    samples = np.asarray(samples, dtype=np.float32).reshape(-1)[:want]
    if len(samples) < want:
        samples = np.pad(samples, (0, want - len(samples)))
    return samples


# ---------------------------------------------------------------------------
# Parameters.

def init_params(key: jax.Array, config: AsrConfig) -> dict:
    c = config
    dtype = _dtype(c)
    keys = iter(jax.random.split(key, 24))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def layer_stack(n, with_cross: bool):
        hd = c.head_dim
        stack = {
            "wq": dense((n, c.dim, c.n_heads * hd), c.dim),
            "wk": dense((n, c.dim, c.n_heads * hd), c.dim),
            "wv": dense((n, c.dim, c.n_heads * hd), c.dim),
            "wo": dense((n, c.n_heads * hd, c.dim), c.n_heads * hd),
            "w_gate": dense((n, c.dim, c.hidden_dim), c.dim),
            "w_up": dense((n, c.dim, c.hidden_dim), c.dim),
            "w_down": dense((n, c.hidden_dim, c.dim), c.hidden_dim),
            "attn_norm": jnp.ones((n, c.dim), dtype=dtype),
            "mlp_norm": jnp.ones((n, c.dim), dtype=dtype),
        }
        if with_cross:
            stack.update({
                "xq": dense((n, c.dim, c.n_heads * hd), c.dim),
                "xk": dense((n, c.dim, c.n_heads * hd), c.dim),
                "xv": dense((n, c.dim, c.n_heads * hd), c.dim),
                "xo": dense((n, c.n_heads * hd, c.dim), c.n_heads * hd),
                "cross_norm": jnp.ones((n, c.dim), dtype=dtype),
            })
        return stack

    return {
        "conv1": {"w": dense((3, c.n_mels, c.dim), 3 * c.n_mels),
                  "b": jnp.zeros((c.dim,), dtype=dtype)},
        "conv2": {"w": dense((3, c.dim, c.dim), 3 * c.dim),
                  "b": jnp.zeros((c.dim,), dtype=dtype)},
        "encoder": layer_stack(c.n_encoder_layers, with_cross=False),
        "encoder_norm": jnp.ones((c.dim,), dtype=dtype),
        "embed": dense((c.vocab_size, c.dim), c.dim),
        "decoder": layer_stack(c.n_decoder_layers, with_cross=True),
        "decoder_norm": jnp.ones((c.dim,), dtype=dtype),
    }


def partition_specs(config: AsrConfig) -> dict:
    """TP layout mirroring models/llama.py: heads/hidden over tp."""
    from ..parallel.mesh import P

    def layer_specs(with_cross: bool):
        spec = {
            "wq": P(None, None, "tp"), "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"), "wo": P(None, "tp", None),
            "w_gate": P(None, None, "tp"), "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
            "attn_norm": P(None, None), "mlp_norm": P(None, None),
        }
        if with_cross:
            spec.update({"xq": P(None, None, "tp"),
                         "xk": P(None, None, "tp"),
                         "xv": P(None, None, "tp"),
                         "xo": P(None, "tp", None),
                         "cross_norm": P(None, None)})
        return spec

    return {
        "conv1": {"w": P(None, None, "tp"), "b": P("tp")},
        "conv2": {"w": P(None, None, "tp"), "b": P("tp")},
        "encoder": layer_specs(False),
        "encoder_norm": P(None),
        "embed": P(None, None),
        "decoder": layer_specs(True),
        "decoder_norm": P(None),
    }


# ---------------------------------------------------------------------------
# Model body.

def _attention(q, k, v, n_heads: int, causal: bool):
    """q [B,S,D'], k/v [B,T,D'] already projected; multi-head dense
    attention with optional causal mask; float32 softmax."""
    b, s, _ = q.shape
    t = k.shape[1]
    hd = q.shape[-1] // n_heads
    q = q.reshape(b, s, n_heads, hd)
    k = k.reshape(b, t, n_heads, hd)
    v = v.reshape(b, t, n_heads, hd)
    logits = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, t), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", weights.astype(v.dtype), v)
    return out.reshape(b, s, n_heads * hd)


def _sinusoid(positions: int, dim: int) -> np.ndarray:
    pos = np.arange(positions)[:, None]
    idx = np.arange(dim // 2)[None, :]
    angle = pos / (10_000 ** (2 * idx / dim))
    return np.concatenate([np.sin(angle), np.cos(angle)],
                          axis=-1).astype(np.float32)


def _conv1d(params, x, stride: int):
    """x [B, T, C] -> [B, T/stride, C'] with 'SAME' padding + GELU."""
    out = jax.lax.conv_general_dilated(
        x, params["w"].astype(x.dtype), window_strides=(stride,),
        padding="SAME", dimension_numbers=("NWC", "WIO", "NWC"))
    return jax.nn.gelu(out + params["b"].astype(x.dtype))


def encode(params: dict, config: AsrConfig, mel: jax.Array) -> jax.Array:
    """log-mel [B, F, n_mels] -> encoder states [B, F/4, D]."""
    c = config
    x = mel.astype(_dtype(c))
    x = _conv1d(params["conv1"], x, stride=2)
    x = _conv1d(params["conv2"], x, stride=2)
    positions = jnp.asarray(_sinusoid(x.shape[1], c.dim))
    x = x + positions[None].astype(x.dtype)

    def layer_step(hidden, layer):
        h = rms_norm(hidden, layer["attn_norm"], c.norm_eps)
        attn = _attention(h @ layer["wq"], h @ layer["wk"],
                          h @ layer["wv"], c.n_heads, causal=False)
        hidden = hidden + attn @ layer["wo"]
        h = rms_norm(hidden, layer["mlp_norm"], c.norm_eps)
        hidden = hidden + swiglu(h, layer["w_gate"], layer["w_up"],
                                 layer["w_down"])
        return hidden, None

    x, _ = jax.lax.scan(layer_step, x, params["encoder"])
    return rms_norm(x, params["encoder_norm"], c.norm_eps)


def _decode_states(params: dict, config: AsrConfig, tokens: jax.Array,
                   encoded: jax.Array) -> jax.Array:
    """Teacher-forced decoder pass: tokens [B, S] -> logits [B, S, V]."""
    c = config
    hidden = params["embed"][tokens]
    positions = jnp.asarray(_sinusoid(tokens.shape[1], c.dim))
    hidden = hidden + positions[None].astype(hidden.dtype)

    def layer_step(hidden, layer):
        h = rms_norm(hidden, layer["attn_norm"], c.norm_eps)
        attn = _attention(h @ layer["wq"], h @ layer["wk"],
                          h @ layer["wv"], c.n_heads, causal=True)
        hidden = hidden + attn @ layer["wo"]
        h = rms_norm(hidden, layer["cross_norm"], c.norm_eps)
        cross = _attention(h @ layer["xq"], encoded @ layer["xk"],
                           encoded @ layer["xv"], c.n_heads, causal=False)
        hidden = hidden + cross @ layer["xo"]
        h = rms_norm(hidden, layer["mlp_norm"], c.norm_eps)
        hidden = hidden + swiglu(h, layer["w_gate"], layer["w_up"],
                                 layer["w_down"])
        return hidden, None

    hidden, _ = jax.lax.scan(layer_step, hidden, params["decoder"])
    hidden = rms_norm(hidden, params["decoder_norm"], c.norm_eps)
    return hidden @ params["embed"].T


@partial(jax.jit, static_argnames=("config",))
def transcribe(params: dict, config: AsrConfig,
               samples: jax.Array) -> jax.Array:
    """Greedy decode: waveform [B, T_chunk] -> token ids [B, max_text].

    KV-cached O(S) decode (the models/llama.py pattern applied to the
    encoder-decoder): cross-attention keys/values are projected ONCE
    per utterance, each step's self-attention reads the read-only cache
    via the split-softmax append (ops/layers.py
    attention_decode_append, with K = H: plain multi-head), and the
    step's k/v pair is written back with one dynamic_update_slice.  The
    loop is a single ``lax.scan`` with a static budget; after EOS a row
    keeps emitting EOS (masked), so shapes stay static and the whole
    transcription compiles once per audio bucket.
    """
    c = config
    dtype = _dtype(c)
    encoded = encode(params, c, log_mel(c, samples))
    batch = samples.shape[0]
    hd = c.head_dim

    # Cross-attention K/V once per utterance: [L, B, T_enc, D'].
    def cross_step(_, layer):
        return None, (encoded @ layer["xk"], encoded @ layer["xv"])
    _, (xk_all, xv_all) = jax.lax.scan(cross_step, None,
                                       params["decoder"])

    cache_shape = (c.n_decoder_layers, batch, c.max_text, c.n_heads, hd)
    cache_k = jnp.zeros(cache_shape, dtype=dtype)
    cache_v = jnp.zeros(cache_shape, dtype=dtype)
    pos_table = jnp.asarray(_sinusoid(c.max_text, c.dim))
    current = jnp.full((batch,), c.bos_token, dtype=jnp.int32)
    finished = jnp.zeros((batch,), dtype=bool)

    def step(carry, i):
        current, finished, cache_k, cache_v = carry
        hidden = params["embed"][current][:, None, :] \
            + pos_table[i][None, None, :].astype(dtype)
        lengths = jnp.full((batch,), i, dtype=jnp.int32)

        def layer_step(hidden, xs):
            layer, k_cache, v_cache, xk, xv = xs
            h = rms_norm(hidden, layer["attn_norm"], c.norm_eps)
            q = (h @ layer["wq"]).reshape(batch, 1, c.n_heads, hd)
            k = (h @ layer["wk"]).reshape(batch, 1, c.n_heads, hd)
            v = (h @ layer["wv"]).reshape(batch, 1, c.n_heads, hd)
            attn = attention_decode_append(q, k_cache, v_cache, k, v,
                                           lengths)
            hidden = hidden + attn.reshape(batch, 1, -1) @ layer["wo"]
            h = rms_norm(hidden, layer["cross_norm"], c.norm_eps)
            cross = _attention(h @ layer["xq"], xk, xv, c.n_heads,
                               causal=False)
            hidden = hidden + cross @ layer["xo"]
            h = rms_norm(hidden, layer["mlp_norm"], c.norm_eps)
            hidden = hidden + swiglu(h, layer["w_gate"], layer["w_up"],
                                     layer["w_down"])
            return hidden, (k, v)

        hidden, (k_new, v_new) = jax.lax.scan(
            layer_step, hidden,
            (params["decoder"], cache_k, cache_v, xk_all, xv_all))
        hidden = rms_norm(hidden, params["decoder_norm"], c.norm_eps)
        logits = (hidden @ params["embed"].T)[:, 0]
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        next_token = jnp.where(finished, c.eos_token, next_token)
        finished = finished | (next_token == c.eos_token)
        # k_new/v_new: [L, B, 1, H, hd] -- one DUS writes every layer's
        # token at position i (read-only inside the layer scan, exactly
        # the llama decode cache discipline).
        cache_k = jax.lax.dynamic_update_slice(cache_k, k_new,
                                               (0, 0, i, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v_new,
                                               (0, 0, i, 0, 0))
        return (next_token, finished, cache_k, cache_v), next_token

    (_, _, _, _), emitted = jax.lax.scan(
        step, (current, finished, cache_k, cache_v),
        jnp.arange(c.max_text))
    return emitted.T                                # [B, max_text]


def decode_text(config: AsrConfig, token_row) -> str:
    """Token ids -> text (byte-level; specials stripped)."""
    data = bytearray()
    for token in np.asarray(token_row).tolist():
        if token == config.eos_token:
            break
        if 0 <= token < 256:
            data.append(token)
    return data.decode("utf-8", errors="replace")


def encode_text(config: AsrConfig, text: str) -> list[int]:
    return list(text.encode("utf-8"))[:config.max_text - 1]


class StreamingAsr:
    """Incremental transcription for live audio (the ``mic://`` -> text
    path; reference equivalent: examples/speech/speech_elements.py
    PE_WhisperX's LRU sliding window at :53-84, which batch-reprocesses
    the window -- here each decode costs exactly ONE compiled dispatch).

    Usage::

        streamer = StreamingAsr(params, config, hop_seconds=1.0,
                                endpoint_silence=0.5)
        final = streamer.push(mic_samples)   # FINALIZED text (see below)
        live = streamer.partial_text         # revisable hypothesis
        final += streamer.flush()            # finalize the tail

    Three latency mechanisms:

    - **sub-chunk partial decode**: with ``hop_seconds`` set, every
      hop's worth of new audio re-decodes the buffered (zero-padded)
      window -- the rolling re-encode strategy, one compiled shape --
      updating ``partial_text`` (the current revisable hypothesis) and
      ``stable_text`` (the prefix two consecutive hypotheses agree on).
      First-word latency is bounded by the hop, not ``chunk_seconds``.
    - **energy endpointing**: with ``endpoint_silence`` set, a trailing
      silence of that many seconds after detected speech finalizes the
      utterance immediately instead of waiting for the chunk to fill.
    - **chunk completion**: a full ``chunk_seconds`` window always
      finalizes (the round-3 behavior).

    ``push`` RETURNS only finalized text: exactly the whole-buffered-
    window decode, never a partial hypothesis -- so concatenated push/
    flush output equals whole-chunk transcription and is never
    retracted.  Chunks are independent utterance windows (no
    cross-chunk decoder state): a word split across a boundary may be
    mis-recognized, the standard chunked-streaming trade-off.
    """

    def __init__(self, params, config: AsrConfig,
                 hop_seconds: float | None = None,
                 endpoint_silence: float | None = None,
                 endpoint_threshold: float = 0.01):
        self.params = params
        self.config = config
        rate = config.sample_rate
        self.chunk = int(rate * config.chunk_seconds)
        self.hop = int(rate * hop_seconds) if hop_seconds else None
        self.endpoint = int(rate * endpoint_silence) \
            if endpoint_silence else None
        self.endpoint_threshold = float(endpoint_threshold)
        self._pending = np.zeros((0,), dtype=np.float32)
        self._since_partial = 0
        self.partial_text = ""        # latest (revisable) hypothesis
        self.stable_text = ""         # agreed prefix of last two partials
        self.chunks_transcribed = 0
        self.partial_decodes = 0

    def _transcribe_one(self, chunk_samples: np.ndarray) -> str:
        tokens = transcribe(self.params, self.config,
                            jnp.asarray(chunk_samples[None]))
        self.chunks_transcribed += 1
        return decode_text(self.config, np.asarray(tokens)[0])

    def _reset_partial(self):
        self._since_partial = 0
        self.partial_text = ""
        self.stable_text = ""

    def _partial_decode(self):
        """Re-decode the buffered window (zero-padded: one compiled
        shape); keep the stable prefix = agreement with the previous
        hypothesis."""
        previous = self.partial_text
        hypothesis = self._transcribe_one(
            pad_audio(self.config, self._pending))
        self.chunks_transcribed -= 1          # partials are not chunks
        self.partial_decodes += 1
        agree = 0
        for a, b in zip(previous, hypothesis):
            if a != b:
                break
            agree += 1
        self.stable_text = hypothesis[:agree]
        self.partial_text = hypothesis
        self._since_partial = 0

    def _endpoint_reached(self) -> bool:
        """Speech followed by >= endpoint_silence of trailing quiet."""
        if self.endpoint is None \
                or len(self._pending) <= self.endpoint:
            return False
        tail = self._pending[-self.endpoint:]
        head = self._pending[:-self.endpoint]
        tail_rms = float(np.sqrt(np.mean(tail * tail)))
        head_peak = float(np.abs(head).max()) if len(head) else 0.0
        return (tail_rms < self.endpoint_threshold
                and head_peak >= self.endpoint_threshold)

    def push(self, samples) -> str:
        """Append samples; returns newly FINALIZED text ('' while the
        window fills -- watch ``partial_text``/``stable_text`` for the
        sub-chunk live hypothesis)."""
        samples = np.asarray(samples, dtype=np.float32).reshape(-1)
        self._pending = np.concatenate([self._pending, samples])
        self._since_partial += len(samples)
        emitted = []
        while len(self._pending) >= self.chunk:
            chunk, self._pending = (self._pending[:self.chunk],
                                    self._pending[self.chunk:])
            emitted.append(self._transcribe_one(chunk))
            self._reset_partial()
        if emitted:
            return "".join(emitted)
        if self._endpoint_reached():
            return self.flush()
        if self.hop and len(self._pending) \
                and self._since_partial >= self.hop:
            self._partial_decode()
        return ""

    def flush(self) -> str:
        """Finalize whatever partial window remains (zero-padded)."""
        if not len(self._pending):
            return ""
        tail, self._pending = self._pending, \
            np.zeros((0,), dtype=np.float32)
        self._reset_partial()
        return self._transcribe_one(pad_audio(self.config, tail))


def asr_loss(params: dict, config: AsrConfig, samples: jax.Array,
             targets: jax.Array) -> jax.Array:
    """Teacher-forced cross-entropy; targets [B, S] padded with PAD=259
    (ignored).  The training objective for fitting the ASR model."""
    c = config
    encoded = encode(params, c, log_mel(c, samples))
    bos = jnp.full((targets.shape[0], 1), c.bos_token, dtype=jnp.int32)
    inputs = jnp.concatenate([bos, targets[:, :-1]], axis=1)
    logits = _decode_states(params, c, inputs,
                            encoded).astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(log_probs, targets[..., None],
                                 axis=-1)[..., 0]
    mask = (targets != 259).astype(jnp.float32)
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)
