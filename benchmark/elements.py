"""Pipeline elements the benchmark deploys through a definition's
``deploy.local.module`` -- the seams a benchmark PR may add without
touching the program.

- :class:`CameraSource` turns a ``{"camera": k, "frame": i}`` request
  into a decoded 720p frame from a per-camera pool made from the seed,
  as ``VideoReadFile`` or an RTSP source hands one over: the image
  never crosses the door as JSON.
- :class:`ConfiguredLLM` is the Llama family's element class
  (``benchmark/architectures/llama.py`` hands it ``widths``): it serves
  the widths of a configuration file through the program's own ``LLM``
  element (the program only knows presets; PERF.md section 7 asks for
  "widths from a file" so this subclass can go).
- :class:`EveryBucketResize` and :class:`EveryBucketDetector` are the
  program's ``ImageResize`` and ``Detector`` with every micro-batch
  bucket built on the first frame, and each frame's bucket named in
  its result: which group sizes a run's traffic forms is a matter of
  timing, a size first met in the window is a program built in the
  window, and a bucket of 4 rounds otherwise than one of 1 (PERF.md
  section 6, PR 28).
- :class:`ResultTrim` keeps tensors out of the result message.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np

from aiko_services_tpu.elements.detect import Detector
from aiko_services_tpu.elements.image import ImageResize
from aiko_services_tpu.elements.llm import LLM
from aiko_services_tpu.models import llama
from aiko_services_tpu.models.batching import pad_to_bucket
from aiko_services_tpu.models.tokenizer import ByteTokenizer
from aiko_services_tpu.pipeline import PipelineElement, StreamEvent

from benchmark.architectures.llama import llama_config
from benchmark.traffic import seed31


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


class _EveryBucket:
    """Over a ``MicroBatchElement``, two things a run's timing must not
    decide.

    The first group of a key also runs that key's every bucket (1, 2,
    4 .. ``max_batch``, a power of two as the program's default 8 is,
    copies of the group's first payload) through the
    element's own ``batch_run`` and ``batch_finish``, on the
    micro-batcher's worker, and drops what they complete.  The
    traffic's first request is in set-up, so every program a group of
    any size needs is built there, whatever sizes the warm-up forms.

    And every frame's outputs say which bucket it rode in, under
    ``<element name>_group``: on the chip a bucket of 4 or 8 rounds
    otherwise than one of 1 or 2 (PERF.md section 6, PR 28), so "the
    same input gives the same answer" holds per bucket and is checked
    per bucket (a workload file's ``same_answer.key``)."""

    def batch_run(self, context, key, payloads):
        seen = self.__dict__.setdefault("_buckets_built", set())
        if key not in seen:
            seen.add(key)
            max_batch, _ = self.get_parameter("max_batch", 8)
            size = 1
            while size <= int(max_batch):
                group = [payloads[0]] * size
                super().batch_finish(
                    context, key,
                    [(_drop, payload) for payload in group],
                    super().batch_run(context, key, group))
                size *= 2
        return super().batch_run(context, key, payloads)

    def batch_finish(self, context, key, entries, result):
        tag = {f"{self.name}_group": len(pad_to_bucket(entries))}
        super().batch_finish(
            context, key,
            [(functools.partial(_tagged, complete, tag), payload)
             for complete, payload in entries], result)


def _drop(event, outputs):
    """``complete`` of a frame nobody sent."""


def _tagged(complete, tag, event, outputs):
    complete(event, {**outputs, **tag})


class EveryBucketResize(_EveryBucket, ImageResize):
    pass


class EveryBucketDetector(_EveryBucket, Detector):
    pass


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
