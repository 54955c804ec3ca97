"""Pipeline elements the benchmark deploys through a definition's
``deploy.local.module`` -- the seams a benchmark PR may add without
touching the program.

- :class:`CameraSource` turns a ``{"camera": k, "frame": i}`` request
  into a decoded 720p frame from a per-camera pool made from the seed,
  as ``VideoReadFile`` or an RTSP source hands one over: the image
  never crosses the door as JSON.
- :class:`ConfiguredLLM` serves the widths of a configuration file
  through the program's own ``LLM`` element (the program only knows
  Llama presets; PERF.md section 7 asks for "widths from a file" so
  this subclass can go).
- :class:`ResultTrim` keeps tensors out of the result message.
"""

from __future__ import annotations

import json
import time

import numpy as np

from aiko_services_tpu.elements.llm import LLM
from aiko_services_tpu.models import llama
from aiko_services_tpu.models.tokenizer import ByteTokenizer
from aiko_services_tpu.pipeline import PipelineElement, StreamEvent

from benchmark.traffic import seed31

# Published config.json key -> LlamaConfig field.
WIDTH_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "dim",
                "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "hidden_dim",
                "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def llama_config(widths: dict) -> llama.LlamaConfig:
    fields = {}
    for key, field in WIDTH_FIELDS.items():
        kind = float if field in ("rope_theta", "norm_eps") else int
        fields[field] = kind(widths[key])
    return llama.LlamaConfig(**fields)


class _NoStopTokenizer(ByteTokenizer):
    """Random weights know no end of sequence: every request decodes
    exactly ``max_new_tokens`` (the configuration's ``assumed`` says
    so), as serving benchmarks do with ``ignore_eos``."""

    @property
    def eos_tokens(self) -> tuple:
        return ()


class ConfiguredLLM(LLM):
    """The program's LLM element with the architecture taken from the
    ``widths`` parameter (a configuration file's published keys).

    ``LLM._ensure_model`` looks its presets up on ``llama.LlamaConfig``
    at call time, so the file's widths stand in for ``llama3-1b`` for
    the length of that one call; everything else -- init, int8,
    batcher, paged cache, kernels -- is the program's own path."""

    _MODEL_PARAMS = LLM._MODEL_PARAMS + ("widths", "ignore_eos")

    def _ensure_model(self, settings: dict | None = None):
        if self._batcher is not None:
            return
        if settings is None:
            settings = self._resolve_model_params()
        started = time.perf_counter()
        settings = dict(settings)
        widths = settings.pop("widths", None)
        ignore_eos = settings.pop("ignore_eos", False)
        if widths:
            config = llama_config(widths)
            preset = llama.LlamaConfig.__dict__["llama3_1b"]
            llama.LlamaConfig.llama3_1b = classmethod(lambda cls: config)
            try:
                super()._ensure_model({**settings, "model": "llama3-1b"})
            finally:
                llama.LlamaConfig.llama3_1b = preset
        else:
            super()._ensure_model(settings)
        if ignore_eos:
            self._tokenizer = _NoStopTokenizer()
        built = time.perf_counter()
        self._warm_first_token_joins()
        print(json.dumps({"note": "detail", "phase": "model build",
                          "seconds": built - started,
                          "first_token_joins_s":
                              time.perf_counter() - built}), flush=True)

    def _warm_first_token_joins(self):
        """The batcher concatenates the first tokens of the admissions
        that join a decode block, eagerly, so each count of joiners
        (1..max_slots) is a program of its own and traffic cannot be
        made to produce every count.  Build them here, in set-up."""
        import jax.numpy as jnp
        first = jnp.zeros((1,), dtype=jnp.int32)
        for count in range(1, self._batcher.max_slots + 1):
            jnp.concatenate([first] * count).block_until_ready()


class CameraSource(PipelineElement):
    """``camera``, ``frame`` -> ``image``: uint8 ``[height, width, 3]``
    on the host, frame ``i`` of camera ``k`` being entry ``i mod
    pool_frames`` of that camera's pool."""

    def __init__(self, context):
        super().__init__(context)
        self._pools = None

    def _ensure_pools(self):
        if self._pools is None:
            cameras, _ = self.get_parameter("cameras", 8)
            frames, _ = self.get_parameter("pool_frames", 8)
            height, _ = self.get_parameter("height", 720)
            width, _ = self.get_parameter("width", 1280)
            seed, _ = self.get_parameter("seed", 0)
            rng = np.random.default_rng(seed31(seed))
            self._pools = rng.integers(
                0, 256, (int(cameras), int(frames), int(height),
                         int(width), 3), dtype=np.uint8)
        return self._pools

    def start_stream(self, stream, stream_id):
        self._ensure_pools()
        return StreamEvent.OKAY, {}

    def process_frame(self, stream, camera=None, frame=None, **inputs):
        pools = self._ensure_pools()
        pool = pools[int(camera) % len(pools)]
        return StreamEvent.OKAY, {"image": pool[int(frame) % len(pool)]}


class ResultTrim(PipelineElement):
    """Last element of a graph: overwrites the swag keys named by
    ``drop`` with None, so the door's result message (every bare swag
    key, fetched and JSON-encoded) carries no tensor."""

    def process_frame(self, stream, **inputs):
        drop, _ = self.get_parameter("drop", [])
        return StreamEvent.OKAY, {name: None for name in drop}
