"""LLM serving: actor + pipeline element (BASELINE config 3; reference
equivalent: examples/llm/elements.py:92-212, which forwards chat turns to
an external Ollama/CUDA server via LangChain).

Here serving is native to the framework:

- :class:`LLMService` is an Actor owning a :class:`ContinuousBatcher`
  (models/batching.py): weights and the batched KV cache live in HBM;
  any number of remote callers stream generations concurrently.  Wire
  protocol on ``topic/in``::

      (generate response_topic request_id prompt max_new_tokens temp)

  replies on ``response_topic``::

      (token request_id fragment)     per decode step
      (complete request_id full_text)

  The decode loop rides the event engine: while work is pending the
  service re-posts its pump, so decode ticks interleave with message
  handling instead of blocking the process (the "batching mailbox
  between the actor layer and the device loop" flagged in SURVEY §7).

- :class:`LLM` is a PipelineElement producing ``text`` out of ``text``
  frames, hosting its own model in-process.  To share one model (one
  set of HBM weights) across many pipelines, wrap this element in a
  small pipeline and reference it from the others as a remote stage
  (``deploy: remote``) -- the framework's pause/resume continuation
  carries the frame across, exactly like any other remote element.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time

import jax

from ..models import deepseek, llama, olmo_hybrid, sdar
from ..models.batching import ContinuousBatcher, Request
from ..models.checkpoint import maybe_restore as _restore
from ..models.families import FAMILY_PARAMETERS, family_spec_error
from ..models.paged import is_paged, pool_page_tokens
from ..models.quant import is_quantized
from ..models.tokenizer import ByteTokenizer, load_tokenizer
from ..pipeline import PipelineElement, StreamEvent
from ..services import Actor
from ..utils import generate, get_logger, parse_bool, parse_number

__all__ = ["LLMService", "LLM", "DetectionCaption", "PROTOCOL_LLM"]

_logger = get_logger("aiko.llm")

PROTOCOL_LLM = "llm:0"


def _collector(tokenizer, collected: list):
    """Emit callback appending non-EOS tokens to ``collected``."""
    eos = set(tokenizer.eos_tokens)

    def emit(request_id, token, finished):
        if token not in eos:
            collected.append(token)
    return emit


class LLMService(Actor):
    """Continuous-batching generation server."""

    def __init__(self, name: str = "llm", runtime=None,
                 config: llama.LlamaConfig | None = None,
                 params=None, tokenizer=None, max_slots: int = 8,
                 checkpoint: str | None = None, seed: int = 0):
        super().__init__(name, PROTOCOL_LLM, tags=["ec=true"],
                         runtime=runtime)
        if config is None:
            config = llama.LlamaConfig.tiny()
        if params is None:
            params = _restore(
                llama.init_params(jax.random.PRNGKey(seed), config),
                checkpoint)
        self.tokenizer = tokenizer or ByteTokenizer()
        # One-step dispatches, so the wire-facing server streams its
        # tokens per step.
        self.batcher = ContinuousBatcher(params, config,
                                         max_slots=max_slots)
        # Keyed by (response_topic, request_id): two callers independently
        # choosing the same request_id (both starting at "1") must not
        # collide -- the response topic is the caller's identity.
        self._texts: dict[tuple[str, str], list[int]] = {}
        self._pumping = False
        self.share.update({"model_layers": config.n_layers,
                           "max_slots": max_slots,
                           "active": 0, "queued": 0,
                           "tokens_emitted": 0})

    # -- wire API ----------------------------------------------------------

    def generate(self, response_topic, request_id, prompt,
                 max_new_tokens="128", temperature="0"):
        """(generate response_topic request_id prompt max tokens temp)"""
        key = (str(response_topic), str(request_id))
        self._texts[key] = []
        self.batcher.submit(Request(
            request_id="\x00".join(key),
            prompt_tokens=self.tokenizer.encode(str(prompt)),
            max_new_tokens=int(parse_number(max_new_tokens, 128)),
            temperature=float(parse_number(temperature, 0.0)),
            eos_tokens=self.tokenizer.eos_tokens,
            emit=self._on_token))
        self._start_pump()

    # -- decode pump -------------------------------------------------------

    def _start_pump(self):
        if not self._pumping:
            self._pumping = True
            self.runtime.engine.post_deferred(self._pump)

    def _pump(self):
        active = self.batcher.step()
        self.ec_producer.update("active", self.batcher.active_count)
        self.ec_producer.update("queued", self.batcher.queue_depth)
        self.ec_producer.update("tokens_emitted",
                                self.batcher.tokens_emitted)
        if active or self.batcher.queue_depth \
                or self.batcher.blocks_in_flight:
            # Deferred, not synchronous: new (generate ...) messages
            # interleave between decode ticks and join the batch.
            self.runtime.engine.post_deferred(self._pump)
        else:
            self._pumping = False

    def _on_token(self, batcher_id: str, token: int, finished: bool):
        reply_topic, _, request_id = batcher_id.partition("\x00")
        key = (reply_topic, request_id)
        tokens = self._texts.setdefault(key, [])
        if token not in self.tokenizer.eos_tokens:
            tokens.append(token)
            fragment = self.tokenizer.decode([token])
            self.runtime.message.publish(
                reply_topic,
                generate("token", [request_id, fragment]))
        if finished:
            text = self.tokenizer.decode(tokens)
            self.runtime.message.publish(
                reply_topic, generate("complete", [request_id, text]))
            self._texts.pop(key, None)

    # -- local API ---------------------------------------------------------

    def generate_local(self, prompt: str, max_new_tokens: int = 128,
                       temperature: float = 0.0) -> str:
        """Synchronous generation (drains the batcher inline): for
        single-process callers and tests."""
        collected: list[int] = []
        self.batcher.submit(Request(
            request_id="local",
            prompt_tokens=self.tokenizer.encode(prompt),
            max_new_tokens=max_new_tokens, temperature=temperature,
            eos_tokens=self.tokenizer.eos_tokens,
            emit=_collector(self.tokenizer, collected)))
        self.batcher.run_until_drained()
        return self.tokenizer.decode(collected)


class DetectionCaption(PipelineElement):
    """``detections`` (Detector output dicts) -> ``text`` prompt for a
    downstream LLM stage -- the detect->describe bridge of the
    video->detect->caption pipeline (BASELINE config 4; reference
    equivalent: examples/llm/elements.py:204 Detection, which formats
    detections into the Ollama prompt).

    Parameter ``template`` wraps the summary (``{detections}``
    placeholder)."""

    def process_frame(self, stream, detections=None, **inputs):
        detections = detections or []
        counts: dict[str, int] = {}
        for detection in detections:
            name = str(detection.get("class", "object"))
            counts[name] = counts.get(name, 0) + 1
        summary = ", ".join(
            f"{count} {name}" if count > 1 else name
            for name, count in sorted(counts.items())) or "nothing"
        template, _ = self.get_parameter(
            "template", "Describe a scene containing: {detections}.")
        # Plain replace, not str.format: templates may legitimately
        # contain literal braces (JSON-shaped prompts).
        return StreamEvent.OKAY, {
            "text": str(template).replace("{detections}", summary)}


class LLM(PipelineElement):
    """``text`` -> generated ``text``.

    Parameters: ``max_new_tokens``, ``temperature``, ``system_prompt``,
    ``tokenizer`` (HF directory), ``checkpoint`` (orbax dir),
    ``vocab_size``/``max_seq``/``seed`` (local tiny config),
    ``attention`` (``dense`` | ``flash`` -- the Pallas long-context
    prefill path, 2.5x dense at 8k context), ``quantize`` (weight-only
    int8: halves decode's HBM stream), ``inflight`` (keep N device-loop
    blocks in flight while requests wait for a slot, chained
    device-side: hides the dispatch round trip behind device compute), ``max_slots`` (device batch width:
    size to the expected concurrent-frame count; decode is
    weight-HBM-bound at short context, so wider batches decode more
    frames' requests per block at nearly the same step time).

    Device-resident serving (ISSUE 8): ``decode_block_tokens`` > 0
    moves generation into ``llama.decode_loop`` -- on-device sampling,
    per-slot stop detection and an emitted-token ring, ONE counted
    ledger fetch per block (the batcher's ``fetch`` is wired to the
    pipeline TransferLedger, and the worker runs decode ticks under
    the ledger's transfer guard, so a stray per-token host sync FAILS
    under ``transfer_guard: disallow`` instead of silently capping
    tok/s).  ``speculative: off|ngram|draft`` layers multi-token
    decoding onto the loop (``spec_tokens`` drafts per step);
    ``kv_page_tokens`` > 0 switches the KV cache to fixed-size pages
    with a per-slot page table (``kv_pages`` caps the physical pool).
    A device loss mid-generation (or a chaos ``decode_block`` fault)
    replays every live request from its last emitted block: the
    batcher re-prefills prompt + committed tokens and generation
    continues -- nothing already streamed is re-emitted.

    MODEL FAMILIES: ``model`` picks a preset of the Llama family
    (``tiny`` | ``tiny-moe`` | ``llama3-1b`` | ``llama3-8b``);
    ``family`` (``llama`` | ``deepseek_v3`` | ``olmo_hybrid`` |
    ``sdar_moe``) +
    ``widths`` (the family's published ``config.json`` keys -> numbers;
    ``olmo_hybrid`` also takes the list ``layer_types`` and the boolean
    ``linear_allow_neg_eigval``: models/families.py) build any config
    of a family -- a key the family lacks is refused, one left out
    keeps its default.  ``deepseek_v3``
    (models/deepseek.py) is latent attention over a paged latent pool,
    a leading dense layer and drop-less routed experts, served by the
    same batcher; ``attention: flash`` and ``decode_kernel`` choose its
    kernels as they do the Llama family's.  What it does not serve is
    REFUSED when the model is built (and by the create-time parameter
    check), each by its parameter's name: ``quantize: int8``,
    ``speculative`` / ``spec_tokens`` / ``spec_window``,
    ``prefix_cache: on``, ``kv_page_tokens: 0``,
    ``model``, a ``placement`` of more than one chip.  Its retired
    decode blocks also observe ``llm_moe_experts_touched`` and
    ``llm_moe_load_imbalance`` into the telemetry registry (and the
    recorder's ``llm_tick:demux`` info).  Where the Llama family's
    paged decode kernel serves decode (``decode_kernel``), a retired
    block observes ``llm_decode_live_grid_share`` the same way.
    ``olmo_hybrid`` (models/olmo_hybrid.py) is gated delta-rule layers
    with a per-slot recurrent state beside full-attention layers over
    K/V pages, in the published ``layer_types`` pattern; it refuses the
    same parameters by name (a prefix or a draft would need a snapshot
    of the state), its retired decode blocks observe
    ``llm_state_traffic_share``, and the recorder gets one
    ``build:llm_cache`` event whose info is the bytes of each cache
    pool.  ``sdar_moe`` (models/sdar.py) generates by DIFFUSION OVER
    BLOCKS over drop-less softmax-routed experts: ``block_length``
    (default 4) positions a block under a block-causal mask,
    ``denoising_steps`` (1..``block_length``, default
    ``block_length``) denoising passes a block, each deciding the most confident masked positions, then a
    pass that commits the block's K/V to the pages and emits it -- so a
    pass of the device loop yields several tokens a row or none, and a
    request's first token arrives when its first block commits.  It
    serves by the device loop alone (``decode_block_tokens`` a multiple
    of ``block_length``, which must divide ``kv_page_tokens``) and
    refuses the same parameters by name; its retired blocks observe
    ``llm_diffusion_tokens_per_row_pass``,
    ``llm_diffusion_commit_pass_share`` and the two ``llm_moe_*``
    histograms.  An int8 model of the Llama family also gets
    ``build:llm_unembed``: the fused unembed's blocks at the decode
    width and ``padded_weight_bytes``, what each of its calls copies
    to pad the head (0 unless no block fits: ops/pallas_matmul.py).
    Every model of the Llama family gets ``build:llm_decode_backend``:
    the decode attention the probe resolved for its cache
    (``backend``: ops.DECODE_BACKENDS), the cache's ``extent``, its
    ``page_tokens`` and the paged kernel's ``pages_per_step`` (None
    where the cache is dense or another backend decodes).

    ASYNC by default: each frame parks and its request hops to the
    element's device WORKER THREAD, which owns the model and the shared
    :class:`ContinuousBatcher` -- model build (weight init plus the
    first jit compiles of a 1B model take tens of seconds from a cold
    compile cache), admission, the decode
    loop, and the retire fetches all run OFF the event loop, so they
    never block other stages' frames (detect of frame k+1 proceeds
    while the LLM compiles or decodes).  Requests from many in-flight
    frames/streams decode together in one device batch (continuous
    batching across frames, not per-frame drains); completions post
    back through the engine's thread-safe continuation.  Set parameter
    ``synchronous: true`` for the blocking per-frame path.

    PLACEMENT: a definition ``placement`` block on this element
    (``{"mesh": {"tp": 2}}``, ``{"devices": 1}``) is where the model
    LIVES -- parameters are put with ``llama.partition_specs``
    (``quant.quantize_specs`` for an int8 tree), the KV cache with
    ``llama.cache_specs``, and every leaf of both sits inside the
    stage's submesh (``model_devices()``); unplaced, they sit on the
    process's default device.  On a submesh of more than one chip the
    Pallas kernels do not apply (jit refuses to partition a Mosaic
    kernel): the decode and matmul probes resolve ``reference`` there,
    and ``attention: flash`` is refused at model build rather than left
    to fail inside the first prefill.  The placed configuration that
    runs on a four-chip v5e host (``chip_smoke.py --placed``):
    ``placement: {"mesh": {"tp": 2}}``, ``attention: dense``, the rest
    of the serving parameters unchanged (device loop, paged KV, int8).
    """

    is_async = True

    def __init__(self, context):
        super().__init__(context)
        self._batcher: ContinuousBatcher | None = None
        self._tokenizer = None
        self._request_seq = 0
        # request_id -> complete for parked async frames, so a failing
        # worker can error them out instead of leaving them parked.
        # Owned by the WORKER thread (cancels arrive via the queue).
        self._completes: dict = {}
        # ("request", stream_id, text, complete, request_params,
        # model_params) | ("cancel", prefix); created lazily with the
        # daemon worker thread.
        self._work: queue.Queue | None = None
        # Serializes device access between the worker and the blocking
        # process_frame path (a per-stream ``synchronous: true`` can
        # run while another stream uses the async worker).
        self._device_lock = threading.RLock()
        # Device-loss recovery bookkeeping: consecutive failed decode
        # ticks before the worker gives up replaying (reset by any
        # successful tick), and the telemetry counters' published
        # watermarks (deltas feed the registry).
        self._recover_streak = 0
        self._published_accepted = 0
        self._published_drafted = 0
        self._published_prefix_hits = 0
        self._published_prefix_lookups = 0
        # Where the worker thread's last ``llm_tick`` phase ended.
        self._tick_mark = 0.0

    # Model-config parameters, resolved ON THE EVENT LOOP (stream
    # parameter precedence reads the pipeline's current-stream context,
    # which only the loop thread maintains) and shipped to the worker.
    # ``decode_block`` (the fused-block driver's knob, gone) is resolved
    # only so that model build refuses it by name instead of serving by
    # the per-token tick in silence (families.family_spec_error).
    _MODEL_PARAMS = ("checkpoint", "tokenizer", "vocab_size", "max_seq",
                     "seed", "attention", "model", "family", "widths",
                     "quantize", "block_length", "denoising_steps",
                     "decode_block", "inflight", "max_slots",
                     "decode_block_tokens", "speculative", "spec_tokens",
                     "spec_window", "kv_page_tokens", "kv_pages",
                     "decode_kernel", "sample_top_k", "prefix_cache",
                     "prefix_min_tokens", "spec_autoprobe")

    def _resolve_model_params(self) -> dict:
        resolved = {}
        for name in self._MODEL_PARAMS:
            value, found = self.get_parameter(name, None)
            if found and value is not None:
                resolved[name] = value
        return resolved

    def _resolve_request_params(self) -> dict:
        max_new, _ = self.get_parameter("max_new_tokens", 32)
        temperature, _ = self.get_parameter("temperature", 0.0)
        system_prompt, _ = self.get_parameter("system_prompt", "")
        return {"max_new_tokens": int(max_new),
                "temperature": float(temperature),
                "system_prompt": str(system_prompt or "")}

    def _ensure_model(self, settings: dict | None = None):
        if self._batcher is not None:
            return
        if settings is None:
            settings = self._resolve_model_params()
        tokenizer_path = settings.get("tokenizer")
        self._tokenizer = load_tokenizer(tokenizer_path) \
            if tokenizer_path else ByteTokenizer()
        vocab = settings.get("vocab_size")
        # ``family`` + ``widths`` build any config of either family
        # (models/families.py: the same check as at create time).
        problem = family_spec_error(settings)
        if problem is not None:
            raise ValueError(problem)
        if settings.get("family") is not None:
            family = str(settings["family"]).strip().lower()
            if family in self._ONE_CHIP_FAMILIES:
                return self._ensure_one_chip_family(settings, family)
            base = llama.LlamaConfig.from_widths(
                settings.get("widths") or {})
            if vocab is not None:
                base = dataclasses.replace(base, vocab_size=int(vocab))
            return self._ensure_llama_model(settings, base)
        # "flash" routes chunked admission through the Pallas kernel --
        # the long-context setting (2.5x dense at 8k on v5e).
        model = settings.get("model", "tiny")
        bases = {"tiny": llama.LlamaConfig.tiny,
                 "tiny-moe": llama.LlamaConfig.tiny_moe,
                 "llama3-1b": llama.LlamaConfig.llama3_1b,
                 "llama3-8b": llama.LlamaConfig.llama3_8b}
        if str(model) not in bases:
            raise ValueError(f"model={model!r}: one of {sorted(bases)}")
        base = bases[str(model)]()
        # An explicit vocab_size always wins (it must match the
        # tokenizer/checkpoint); otherwise tiny configs follow the
        # tokenizer and the llama configs keep their own vocab.
        if vocab is not None:
            base = dataclasses.replace(base, vocab_size=int(vocab))
        elif str(model).startswith("tiny"):
            base = dataclasses.replace(
                base, vocab_size=self._tokenizer.vocab_size)
        self._ensure_llama_model(settings, base)

    def _ensure_llama_model(self, settings: dict,
                            base: llama.LlamaConfig):
        """Build the Llama family's model of ``base`` widths and the
        batcher that serves it."""
        config = dataclasses.replace(
            base, max_seq=int(settings.get("max_seq", 256)),
            attention=str(settings.get("attention", "dense")))
        # ``decode_kernel`` selects the decode-attention backend in the
        # ops capability-probe vocabulary (ops.decode_backend):
        # paged-kernel / dense-flash force the Pallas kernel plane
        # (which one actually engages follows the cache's structure),
        # reference forces the dense einsum path, auto follows the
        # cache's layout on the chip (a paged cache takes the paged
        # kernel at any extent; a dense one takes flash from the extent
        # threshold up).  Domain-validated at create time
        # (analysis/params.py ELEMENT_PARAMETERS).
        decode_kernel = str(settings.get("decode_kernel",
                                         "auto")).strip().lower()
        kernel_to_attention = {"auto": "auto", "paged-kernel": "flash",
                               "dense-flash": "flash",
                               "reference": "dense"}
        if decode_kernel not in kernel_to_attention:
            raise ValueError(
                f"decode_kernel={decode_kernel!r}: one of "
                f"{'|'.join(sorted(kernel_to_attention))}")
        if decode_kernel != "auto":
            config = dataclasses.replace(
                config,
                decode_attention=kernel_to_attention[decode_kernel])
        plan = self._stage_plan()
        if plan is not None and plan.mesh.size > 1 \
                and config.attention == "flash":
            # Found on the four-chip v5e host (PR 21): with operands on
            # a two-chip mesh -- head-sharded OR replicated -- jit
            # refuses the kernel ("Mosaic kernels cannot be
            # automatically partitioned. Please wrap the call in a
            # shard_map."), which would otherwise surface inside the
            # first prefill and be replayed as a device loss.
            raise ValueError(
                f"attention=flash on a {plan.mesh.size}-chip placement "
                f"{dict(plan.mesh.shape)}: Mosaic kernels cannot be "
                f"automatically partitioned; use attention: dense with "
                f"a multi-chip placement (or place the LLM on one "
                f"chip)")
        quantize = settings.get("quantize", False)
        normalized = str(quantize).strip().lower()
        int8 = parse_bool(quantize) or normalized == "int8"
        if not int8 and normalized not in ("false", "0", "no", "off",
                                           "none", ""):
            # A typo must not silently serve bf16 at half the promised
            # decode rate.
            raise ValueError(
                f"quantize={quantize!r}: use true/false or int8")
        # Both callers (the worker, the blocking path) hold
        # ``_device_scope``: the build's transients land on this
        # element's own chips.
        params = _restore(
            llama.init_params(
                jax.random.PRNGKey(int(settings.get("seed", 0))), config),
            settings.get("checkpoint"))
        specs = llama.partition_specs(config)
        if int8:
            # Weight-only int8 (models/quant.py): halves decode's
            # HBM stream; activations/cache stay bf16.
            from ..models.quant import quantize_params, quantize_specs
            params = quantize_params(params)
            specs = quantize_specs(specs)
        cache_put = None
        if plan is not None:
            # The model lives on THIS stage's submesh, not on the
            # process's default device (which may belong to another
            # stage): weights by the Megatron layout, cache by
            # llama.cache_specs, kept there by donation.
            params = plan.put(params, specs)

            def cache_put(cache):
                return plan.put(cache, llama.cache_specs(
                    config, paged=is_paged(cache)))
        # Requests beyond max_slots queue (sizing rationale: class
        # docstring).  The pipeline TransferLedger counts the one
        # explicit host fetch each retired device-loop block pays;
        # the chaos probe arms the ``decode_block`` injection point.
        self._build_batcher(params, config, settings, cache_put)

    # The families built from ``widths`` on ONE chip: the module, its
    # config class, the config field ``decode_kernel`` sets with the
    # value each choice means, and what has no partition specs.
    _ONE_CHIP_FAMILIES = {
        "deepseek_v3": (
            deepseek, deepseek.DeepseekConfig, "decode_attention",
            {"auto": "auto", "paged-kernel": "flash",
             "reference": "dense"},
            "its latent pool or its experts"),
        "olmo_hybrid": (
            olmo_hybrid, olmo_hybrid.OlmoHybridConfig, "kernels",
            {"auto": "auto", "paged-kernel": "on", "reference": "off"},
            "its state pool"),
        "sdar_moe": (
            sdar, sdar.SdarConfig, "kernels",
            {"auto": "auto", "paged-kernel": "on", "reference": "off"},
            "its experts"),
    }

    def _ensure_one_chip_family(self, settings: dict, family: str):
        """A family of the published ``widths`` that serves unquantized
        on ONE chip -- ``deepseek_v3`` (latent attention over a latent
        page pool, routed experts; models/deepseek.py) or
        ``olmo_hybrid`` (gated delta-rule layers with a per-slot
        float32 state beside full-attention layers over K/V pages;
        models/olmo_hybrid.py) or ``sdar_moe`` (generation by diffusion
        over blocks, softmax-routed experts; models/sdar.py, with its
        own ``block_length`` and ``denoising_steps``).  What the family
        cannot serve was refused above, by the parameter's name."""
        module, config_class, kernel_field, kernel_values, unsharded = \
            self._ONE_CHIP_FAMILIES[family]
        plan = self._stage_plan()
        if plan is not None and plan.mesh.size > 1:
            raise ValueError(
                f"placement={dict(plan.mesh.shape)}: the {family} "
                f"family has no partition specs for {unsharded}; place "
                f"it on one chip")
        decode_kernel = str(settings.get("decode_kernel",
                                         "auto")).strip().lower()
        if decode_kernel not in kernel_values:
            raise ValueError(
                f"decode_kernel={decode_kernel!r}: with the {family} "
                f"family one of {'|'.join(sorted(kernel_values))}")
        fields = {"max_seq": int(settings.get("max_seq", 256)),
                  "attention": str(settings.get("attention", "dense")),
                  kernel_field: kernel_values[decode_kernel]}
        if settings.get("vocab_size") is not None:
            fields["vocab_size"] = int(settings["vocab_size"])
        for name, field in FAMILY_PARAMETERS.get(family, {}).items():
            if settings.get(name) is not None:
                fields[field] = int(settings[name])
        config = config_class.from_widths(
            settings.get("widths") or {}, **fields)
        params = _restore(
            module.init_params(
                jax.random.PRNGKey(int(settings.get("seed", 0))), config),
            settings.get("checkpoint"))
        self._build_batcher(params, config, settings, None)

    def _build_batcher(self, params, config, settings: dict, cache_put):
        ledger = self._ledger()
        kv_pages = settings.get("kv_pages")
        started = time.perf_counter()
        self._batcher = ContinuousBatcher(
            params, config,
            max_slots=int(settings.get("max_slots", 8)),
            inflight=int(settings.get("inflight", 2)),
            decode_block_tokens=int(
                settings.get("decode_block_tokens", 0)),
            speculative=str(settings.get("speculative", "off")),
            spec_tokens=int(settings.get("spec_tokens", 4)),
            spec_window=int(settings.get("spec_window", 32)),
            kv_page_tokens=int(settings.get("kv_page_tokens", 0)),
            kv_pages=None if kv_pages is None else int(kv_pages),
            sample_top_k=int(settings.get("sample_top_k", 0)),
            prefix_cache=settings.get("prefix_cache", False),
            prefix_min_tokens=int(settings.get("prefix_min_tokens", 64)),
            spec_autoprobe=settings.get("spec_autoprobe", True),
            fetch=None if ledger is None
            else (lambda tree: ledger.fetch(tree, label="llm_block")),
            fault_probe=self._fault_probe,
            trace=None if self._recorder() is None else self._trace_tick,
            cache_put=cache_put)
        recorder = self._recorder()
        if recorder is not None:
            # What the cache holds, pool by pool, in bytes.
            pools = {
                jax.tree_util.keystr(path): int(leaf.nbytes)
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    self._batcher.cache)}
            recorder.record("build", None, None, "llm_cache",
                            (time.perf_counter() - started) * 1000.0,
                            pools)
            if isinstance(config, llama.LlamaConfig):
                # Which decode attention the probe chose for this
                # cache (ops.decode_backend follows its layout), and
                # what it saw: None where a field does not apply.
                served, cache = self._batcher.config, self._batcher.cache
                recorder.record(
                    "build", None, None, "llm_decode_backend", 0.0,
                    {"backend": llama.resolve_decode_backend(served, cache),
                     "extent": llama.cache_extent(cache),
                     "page_tokens": pool_page_tokens(cache)
                     if is_paged(cache) else None,
                     "pages_per_step": llama.paged_decode_pages(
                         served, cache)})
            unembed = params.get("unembed")
            if is_quantized(unembed) and unembed["int8"].ndim == 2:
                # How the fused int8 unembed (ops/pallas_matmul.py)
                # blocks this head at the decode width, and what a call
                # copies to get there: 0 unless the kernel has to pad.
                from ..ops.pallas_matmul import matmul_blocks
                block_m, block_d, block_f, padded = matmul_blocks(
                    self._batcher.max_slots, *unembed["int8"].shape)
                recorder.record(
                    "build", None, None, "llm_unembed", 0.0,
                    {"block_m": block_m, "block_d": block_d,
                     "block_f": block_f, "padded_weight_bytes": padded})

    def _stage_plan(self):
        """The MeshPlan of this element's placed stage (its definition
        ``placement`` block), or None when unplaced / outside a
        pipeline."""
        placements = getattr(getattr(self, "pipeline", None),
                             "stage_placement", None)
        return None if placements is None \
            else placements.plans.get(self.name)

    def _device_scope(self):
        """Default-device scope for everything this element allocates:
        the first chip of its placed stage, so neither the model build's
        transients nor the decode loop's small host uploads land on
        another stage's chip.  A no-op when unplaced."""
        plan = self._stage_plan()
        if plan is None:
            return contextlib.nullcontext()
        return jax.default_device(plan.mesh.devices.flat[0])

    def model_devices(self) -> dict:
        """Where the built model lives: the device sets holding any
        parameter leaf and any KV-cache leaf (``chip_smoke.py`` and the
        config-4 tests assert both sit inside the stage's submesh)."""
        def devices(tree):
            found = set()
            for leaf in jax.tree_util.tree_leaves(tree):
                found |= set(leaf.sharding.device_set)
            return found
        batcher = self._batcher
        return {"params": devices(batcher.params),
                "cache": devices(batcher.cache)}

    def _recorder(self):
        """The pipeline's flight recorder (None under ``recorder: off``
        and outside a pipeline)."""
        return getattr(getattr(self, "pipeline", None), "recorder", None)

    def _trace_tick(self, name: str, ms: float, info=None) -> None:
        """Host-timeline tap (ISSUE 26): one ``llm_tick`` duration
        event per phase of the worker thread's time -- the batcher's
        (``admit``, ``prefill``, ``fold``, ``dispatch``, ``retire_wait``,
        ``demux``) and this element's own (``wait_work``, ``drain``,
        ``publish``) -- global events (no stream/frame: one block
        serves many), stamped at the phase's end on the worker thread,
        so that serving cadence is on the frames' timeline in a
        black-box dump and the benchmark can lay it beside the device
        trace.  The next phase of the element's own starts here."""
        recorder = self._recorder()
        if recorder is not None:
            recorder.record("llm_tick", None, None, name, ms, info)
            self._tick_mark = time.perf_counter()

    def _own_phase(self, name: str) -> None:
        """One of the element's own phases ended now (it began where
        the last phase of either kind ended)."""
        if self._recorder() is not None:
            self._trace_tick(
                name, (time.perf_counter() - self._tick_mark) * 1000.0)

    def _make_request(self, stream_id, text,
                      request_params: dict) -> tuple[Request, list[int]]:
        system_prompt = request_params["system_prompt"]
        prompt = f"{system_prompt}{text}" if system_prompt else str(text)
        self._request_seq += 1
        collected: list[int] = []
        return Request(
            request_id=f"{stream_id}/{self._request_seq}",
            prompt_tokens=self._tokenizer.encode(prompt),
            max_new_tokens=request_params["max_new_tokens"],
            temperature=request_params["temperature"],
            eos_tokens=self._tokenizer.eos_tokens,
            emit=_collector(self._tokenizer, collected)), collected

    def process_frame_start(self, stream, complete, text=None, **inputs):
        self._start_worker()
        # Parameters resolve HERE (loop thread, current-stream context
        # intact); the worker consumes pre-resolved values.  The model
        # settings ride along until the first request builds it.  The
        # stream's QoS identity rides too (ISSUE 12): the batcher's
        # slot admission is the fourth plane of the unified scheduler.
        model_params = None if self._batcher is not None \
            else self._resolve_model_params()
        qos = getattr(self.pipeline, "qos", None)
        qos_info = (getattr(stream, "tenant", None),
                    getattr(stream, "qos_class", None),
                    0 if qos is None
                    else qos.class_rank(getattr(stream, "qos_class",
                                                None)))
        # Process fault domain (ISSUE 13): the frame identity keys the
        # journal's per-token commits, and an adopted frame's journaled
        # committed prefix resumes generation instead of re-running it.
        pipeline = getattr(self, "pipeline", None)
        frame = None
        current = getattr(pipeline, "current_frame", None)
        if callable(current):
            frame = current()
        journal_key = None
        resume = None
        if frame is not None:
            if getattr(pipeline, "journal", None) is not None \
                    and getattr(stream, "journal", False):
                journal_key = (str(stream.stream_id),
                               int(frame.frame_id))
            take = getattr(pipeline, "take_journal_resume", None)
            if callable(take):
                resume = take(stream.stream_id, frame.frame_id)
        self._work.put(("request", str(stream.stream_id), text, complete,
                        self._resolve_request_params(), model_params,
                        qos_info, journal_key, resume))

    def stop_stream(self, stream, stream_id):
        """Cancel the stream's outstanding requests: a frame parked here
        when its stream is destroyed must stop decoding (it would
        otherwise run to max_new_tokens in a device batch slot) and its
        parked ``complete`` must not fire later.  Routed through the
        worker queue -- the batcher and the completes registry are
        worker-owned."""
        if self._work is not None:
            self._work.put(("cancel", f"{stream.stream_id}/"))
        return StreamEvent.OKAY, {}

    def drain_requests(self):
        """Migrate-in-place for ``Pipeline.drain`` (ISSUE 13): cancel
        every live request (committed prefixes are already journaled
        token by token) and drop the parked frames without responding,
        leaving them undelivered in the journal -- the adopting peer
        replays each frame and its LLM request resumes at the
        committed prefix via ``ContinuousBatcher.resume_request``."""
        if self._work is not None:
            self._work.put(("drain",))

    # -- device worker -----------------------------------------------------

    def _start_worker(self):
        if self._work is None:
            self._work = queue.Queue()
            threading.Thread(target=self._worker, args=(self._work,),
                             daemon=True,
                             name=f"llm-worker-{self.name}").start()

    def _handle(self, item):
        """One queue item, on the worker thread.  A failing REQUEST
        (bad model parameter, broken checkpoint) errors ITS OWN frame
        and is swallowed -- one bad frame must not strand the others."""
        if item[0] == "request":
            (_, stream_id, text, complete, request_params, model_params,
             qos_info, journal_key, resume) = item
            try:
                self._ensure_model(model_params)
                request, collected = self._make_request(
                    stream_id, text, request_params)
                request.tenant, request.qos_class, request.qos_rank = \
                    qos_info
            except Exception as error:
                self.logger.exception("LLM request setup failed")
                complete(StreamEvent.ERROR,
                         {"diagnostic": f"llm: {error}"})
                return
            tokenizer, inner_emit = self._tokenizer, request.emit
            journal = getattr(self.pipeline, "journal", None) \
                if journal_key is not None else None

            def emit(request_id, token, finished):
                inner_emit(request_id, token, finished)
                if journal is not None:
                    # Committed-prefix commit point (ISSUE 13): every
                    # emitted token becomes durable, so an adopter
                    # resumes generation exactly here.  Worker-thread
                    # safe; the fsync is batched.
                    journal.llm_token(journal_key[0], journal_key[1],
                                      int(token))
                if finished:
                    self._completes.pop(request_id, None)
                    complete(StreamEvent.OKAY,
                             {"text": tokenizer.decode(collected)})

            request.emit = emit
            self._completes[request.request_id] = complete
            self._batcher.submit(request)
            if resume:
                # Adopted frame: fold the journaled committed prefix
                # in (prompt + committed re-prefill, budget arithmetic
                # preserved) and pre-seed the collector, so the final
                # text is byte-identical to an uninterrupted run at
                # temperature 0 -- tokens already streamed are never
                # re-generated.
                eos = set(self._tokenizer.eos_tokens)
                collected.extend(int(token) for token in resume
                                 if int(token) not in eos)
                if not self._batcher.resume_request(request, resume):
                    # The prefix already finished the request (the
                    # process died between the final emit and
                    # delivery): complete from the committed tokens
                    # -- resuming would decode a spurious tail.
                    self._completes.pop(request.request_id, None)
                    complete(StreamEvent.OKAY,
                             {"text": tokenizer.decode(collected)})
        elif item[0] == "drain":
            # Cooperative drain (ISSUE 13): every live request's
            # committed prefix is already journaled per token; cancel
            # them and DROP the parked frames -- no response is sent
            # (the adopter's replay is the response), so the client
            # sees each result exactly once, from the peer.
            completes, self._completes = self._completes, {}
            for request_id, complete in completes.items():
                if self._batcher is not None:
                    self._batcher.cancel(request_id)
                complete(StreamEvent.DROP_FRAME, {})
        else:                           # ("cancel", stream prefix)
            prefix = item[1]
            for request_id in [rid for rid in self._completes
                               if str(rid).startswith(prefix)]:
                self._completes.pop(request_id, None)
                if self._batcher is not None:
                    self._batcher.cancel(request_id)

    def _drain_work(self, work: "queue.Queue"):
        while True:
            try:
                self._handle(work.get_nowait())
            except queue.Empty:
                return

    def _ledger(self):
        """The pipeline's TransferLedger (None outside a pipeline --
        direct construction in tests)."""
        return getattr(getattr(self, "pipeline", None),
                       "transfer_ledger", None)

    def _fault_probe(self, point: str):
        """Chaos injection point ``decode_block`` (faults/plan.py):
        consulted by the batcher before every device-loop block
        dispatch.  A matched rule with ``delay_ms`` hangs the
        dispatch; without, it raises FaultInjected standing in for the
        XLA error a dying chip surfaces mid-generation -- driving the
        same recovery path (``ContinuousBatcher.recover``)."""
        plan = getattr(getattr(self, "pipeline", None), "_faults", None)
        if plan is None:
            return
        rule = plan.should(point, target=self.name)
        if rule is None:
            return
        if rule.delay_ms:
            time.sleep(rule.delay_ms / 1000.0)
            return
        from ..faults import FaultInjected
        raise FaultInjected(
            f"{point} kill injected at {self.name}")

    def _tick(self, batcher):
        """One batcher step.  Device-loop ticks run under the
        transfer-ledger guard: on hardware backends a stray per-token
        device-to-host sync then RAISES under ``transfer_guard:
        disallow`` -- the batcher's only legal host read is the ledger-
        counted per-block fetch it was built with."""
        ledger = self._ledger()
        if batcher.device_loop and ledger is not None:
            with ledger.guard():
                batcher.step()
        else:
            batcher.step()
        self._recover_streak = 0
        self._publish_serving_stats(batcher)
        self._own_phase("publish")

    def _recover(self, batcher, error) -> bool:
        """Replay-from-last-emitted-block after a device-level failure:
        rebuild the cache/page pool and re-queue every live request at
        its committed prefix (ContinuousBatcher.recover).  Gives up --
        letting the worker's error path fail the parked frames -- on
        the THIRD consecutive failed tick (a persistently dying
        device), resetting the streak so the next workload gets its
        own replay attempts."""
        self._recover_streak += 1
        if self._recover_streak > 2:
            self._recover_streak = 0
            return False
        revived = batcher.recover()
        self.logger.warning(
            "LLM decode failed (%s); replaying %d request(s) from "
            "their last emitted block", error, revived)
        telemetry = getattr(self.pipeline, "telemetry", None)
        if telemetry is not None:
            telemetry.registry.count("llm_loop_recoveries")
        return True

    def _publish_serving_stats(self, batcher):
        """Per-request latency histograms + speculation counters into
        the telemetry plane (registry is thread-safe; share updates
        marshal onto the event loop)."""
        telemetry = getattr(self.pipeline, "telemetry", None)
        stats = batcher.take_request_stats()
        block_stats = batcher.take_block_stats()
        if telemetry is not None:
            for entry in stats:
                # Tenant/class labels (ISSUE 19): the per-tenant SLO
                # view needs decode latency split the same way the
                # gateway splits e2e.  Unlabeled when the request
                # carried no QoS context (direct element use).
                labels = {}
                if entry.get("tenant"):
                    labels["tenant"] = str(entry["tenant"])
                if entry.get("cls"):
                    labels["cls"] = str(entry["cls"])
                telemetry.registry.observe("llm_ttft_ms",
                                           entry["ttft_ms"], **labels)
                telemetry.registry.observe("llm_queue_wait_ms",
                                           entry["queue_ms"], **labels)
                telemetry.registry.observe("llm_admit_to_first_ms",
                                           entry["admit_to_first_ms"],
                                           **labels)
                if entry["tokens"] > 1:
                    telemetry.registry.observe("llm_tpot_ms",
                                               entry["tpot_ms"],
                                               **labels)
            for observed in block_stats:
                if "moe_experts_touched" in observed:
                    # What the latent family counted in a retired
                    # block (models/deepseek.py:loop_stats).
                    telemetry.registry.observe(
                        "llm_moe_experts_touched",
                        observed["moe_experts_touched"])
                    telemetry.registry.observe(
                        "llm_moe_load_imbalance",
                        observed["moe_load_imbalance"])
                if "diffusion_tokens_per_row_pass" in observed:
                    # Tokens emitted a live row and pass, and the share
                    # of row-passes that only stored a block's K/V
                    # (models/sdar.py:loop_stats).
                    telemetry.registry.observe(
                        "llm_diffusion_tokens_per_row_pass",
                        observed["diffusion_tokens_per_row_pass"])
                    telemetry.registry.observe(
                        "llm_diffusion_commit_pass_share",
                        observed["diffusion_commit_pass_share"])
                if "state_traffic_share" in observed:
                    # Of the cache bytes a retired block's steps moved,
                    # the share that was recurrent state
                    # (models/olmo_hybrid.py:loop_stats).
                    telemetry.registry.observe(
                        "llm_state_traffic_share",
                        observed["state_traffic_share"])
                if "paged_grid_steps" in observed:
                    # How much of the paged decode kernel's grid had a
                    # live page to stream at the block's first step.
                    telemetry.registry.observe(
                        "llm_decode_live_grid_share",
                        100.0 * observed["paged_grid_steps_live"]
                        / observed["paged_grid_steps"])
        changed = False
        hits = batcher.prefix_hits
        lookups = batcher.prefix_lookups
        if hits != self._published_prefix_hits \
                or lookups != self._published_prefix_lookups:
            changed = True
            if telemetry is not None:
                telemetry.registry.count(
                    "llm_prefix_hits",
                    hits - self._published_prefix_hits)
                telemetry.registry.count(
                    "llm_prefix_lookups",
                    lookups - self._published_prefix_lookups)
            self._published_prefix_hits = hits
            self._published_prefix_lookups = lookups
        accepted = batcher.accepted_tokens
        drafted = batcher.draft_tokens
        if accepted != self._published_accepted \
                or drafted != self._published_drafted:
            changed = True
            if telemetry is not None:
                telemetry.registry.count(
                    "llm_accepted_tokens",
                    accepted - self._published_accepted)
                telemetry.registry.count(
                    "llm_draft_tokens",
                    drafted - self._published_drafted)
            self._published_accepted = accepted
            self._published_drafted = drafted
        if not changed:
            return
        pipeline = self.pipeline

        def update_share():
            pipeline.ec_producer.update("llm_accepted_tokens", accepted)
            pipeline.ec_producer.update("llm_draft_tokens", drafted)
            pipeline.ec_producer.update("llm_prefix_hits", hits)
            pipeline.ec_producer.update("llm_prefix_lookups", lookups)
            pipeline.ec_producer.update("llm_spec_probe_ratio",
                                        batcher.spec_probe_ratio)
        pipeline.runtime.engine.post_deferred(update_share)

    def _worker(self, work: "queue.Queue"):
        """Owns every device interaction: lazy model build, admission,
        the decode loop, retire fetches.  Blocks on the queue while
        idle; while decoding, new queue items (requests from frames
        resumed meanwhile, stream cancels) are drained BETWEEN ticks so
        they join the live device batch."""
        self._tick_mark = time.perf_counter()
        while True:
            item = work.get()
            self._own_phase("wait_work")
            with self._device_lock, self._device_scope():
                try:
                    self._handle(item)
                    self._drain_work(work)
                    self._own_phase("drain")
                    batcher = self._batcher
                    while batcher is not None and (
                            batcher.active_count or batcher.queue_depth
                            or batcher.blocks_in_flight):
                        try:
                            self._tick(batcher)
                        except Exception as error:
                            # Device loss mid-generation: replay every
                            # live request from its last emitted block
                            # (ISSUE 8) before the error path below
                            # gets to fail the parked frames.
                            if not self._recover(batcher, error):
                                raise
                        self._drain_work(work)
                        self._own_phase("drain")
                except Exception as error:
                    # A failing decode tick must FAIL the parked frames,
                    # not leave them parked forever -- the async
                    # analogue of the engine's per-element try/except.
                    # Their requests are CANCELLED too: an errored
                    # frame's request left active would keep decoding
                    # to max_new_tokens in a device batch slot,
                    # crowding out the next frames' requests.
                    self.logger.exception("LLM worker failed")
                    completes, self._completes = self._completes, {}
                    for request_id, complete in completes.items():
                        if self._batcher is not None:
                            self._batcher.cancel(request_id)
                        complete(StreamEvent.ERROR,
                                 {"diagnostic": f"llm worker: {error}"})

    def process_frame(self, stream, text=None, **inputs):
        """Blocking path (``synchronous: true`` or direct invocation):
        drains the batcher inline, serialized against the async worker
        through the device lock."""
        with self._device_lock, self._device_scope():
            self._ensure_model()
            request, collected = self._make_request(
                str(stream.stream_id), text, self._resolve_request_params())
            self._batcher.submit(request)
            self._batcher.run_until_drained()
            return StreamEvent.OKAY, {
                "text": self._tokenizer.decode(collected)}
