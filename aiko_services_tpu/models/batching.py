"""Continuous batching for the LLM serving element (BASELINE config 3).

The reference's chat element forwards to an external Ollama server
(reference examples/llm/elements.py:92-212); here serving is native: a
slot-based continuous batcher owns a batched KV cache in HBM and a decode
loop on-device.

Design (the "hard part" flagged in SURVEY.md section 7): many actor
requests merge into device batches and de-multiplex back to per-request
token streams.

- ``max_slots`` sequences decode together as one [B] ``decode_step``;
- admission is CHUNKED and INTERLEAVED: prompt tokens are written
  chunk-at-a-time straight into the admitted slot's region of the
  batched cache (``llama.prefill_into_slot``; no scratch cache: the
  donated cache is written once per chunk, after the layer scan, and
  only where the chunk lands), interleaved with decode ticks.  On the
  per-token tick each ``step()`` prefills at most ONE
  ``prefill_chunk`` -- a long prompt never stalls active decodes beyond
  one chunk's latency.  On the device loop (below) a burst of
  admissions prefills one chunk PER admitting slot per step: the
  chunks are async dispatches chained on the cache, so a burst costs
  device time, not host round trips, and decode stall is bounded by
  one block's latency anyway;
- finished sequences (EOS or token budget) free their slot immediately;
  a long generation never blocks a short one (continuous, not static,
  batching);
- with ``decode_block_tokens > 0`` (ISSUE 8) generation is DEVICE
  RESIDENT: ``step()`` dispatches ``llama.decode_loop`` blocks -- a
  ``lax.while_loop`` with on-device sampling, per-slot stop detection
  (EOS + budget + cache boundary) and an emitted-token ring in the
  carry -- and the host pays ONE counted fetch per retired block (the
  ``fetch`` hook, wired to the pipeline's TransferLedger by the LLM
  element) instead of one round trip per token.  While requests wait
  for a slot the batcher keeps ``inflight`` blocks in flight (one
  otherwise), chaining each dispatch off the previous block's
  DEVICE-side carries, so the host never blocks on a device result
  between dispatches; a request's tokens past its
  EOS/budget inside in-flight blocks are discarded host-side.
  Admission and eviction happen only at block boundaries; ``speculative:
  ngram|draft`` layers multi-token decoding onto the loop with
  acceptance bookkeeping entirely on-device;
- with ``kv_page_tokens > 0`` the KV cache is PAGED (models/paged.py):
  slots borrow fixed-size pages from a shared pool as their sequences
  actually grow, a finished/evicted slot returns them, and a pool
  under pressure preempts the youngest slot (its generation resumes
  later from its committed tokens -- the same resume path
  :meth:`ContinuousBatcher.recover` uses after a device loss);
- the engine is synchronous and thread-agnostic: ``step()`` advances one
  tick and invokes per-request ``emit`` callbacks.  The serving element
  runs it on the event engine and pushes tokens to actor queues.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from collections import deque
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import deepseek, llama, olmo_hybrid, sdar
from .paged import PageAllocator, init_paged_cache, pages_per_slot
from .quant import draft_params
from ..utils.misc import next_power_of_two

__all__ = ["Request", "ContinuousBatcher", "MicroBatcher",
           "MicroBatchElement", "pad_to_bucket"]

# Batched admission advances at most this many slots per tick: compile
# buckets stay {1, 2, 4, 8} regardless of max_slots (an [8*chunk, dim]
# prefill matmul already feeds the MXU; wider bursts would only add
# power-of-two compile shapes, each a fresh jit of the full model).
_ADMISSION_BURST_MAX = 8

# ``speculative: auto`` enables draft speculation only when the startup
# micro-probe measures at least this tokens/s ratio over plain decode.
SPEC_AUTO_MIN_RATIO = 1.2
# Probe shape: warmup block (compile, off the clock) + timed blocks per
# arm, best-of so a GC hiccup cannot flip the verdict.
_SPEC_PROBE_BLOCKS = 3


def _knob_on(value, default: bool) -> bool:
    """on/off|true/false|bool -> bool, the same normalization the
    create-time domain check applies to choice parameters."""
    if isinstance(value, bool):
        return value
    text = str(value).strip().lower()
    if not text:
        return default
    return text in ("on", "true", "1", "yes")


def pad_to_bucket(rows: list) -> list:
    """Pad a ragged admission burst to its power-of-two compile bucket
    by repeating the first row -- idempotent device work (same inputs
    recompute the same values), no uninitialized rows, at most doubles
    a ragged batch.  Shared by the ContinuousBatcher's batched prefill
    and every MicroBatcher dispatch."""
    bucket = next_power_of_two(len(rows))
    return list(rows) + [rows[0]] * (bucket - len(rows))


@dataclasses.dataclass
class Request:
    request_id: str
    prompt_tokens: list[int]
    max_new_tokens: int = 128
    temperature: float = 0.0
    eos_tokens: tuple = ()
    emit: Callable | None = None     # fn(request_id, token_id, finished)
    # runtime state
    slot: int = -1
    prefill_pos: int = 0             # prompt tokens already written
    generated: int = 0
    done: bool = False
    # resume state: the submitted prompt, every token emitted so far,
    # and how many of those have been folded back into prompt_tokens
    # (recover()/page-pool preemption re-prefill prompt + committed and
    # keep generating -- already-delivered tokens are never re-emitted,
    # and ``rebased`` keeps the budget/boundary arithmetic honest).
    base_prompt: list = dataclasses.field(default_factory=list)
    committed: list = dataclasses.field(default_factory=list)
    rebased: int = 0
    admit_seq: int = -1              # admission order (eviction picks
    #                                  the youngest victim)
    submit_time: float = 0.0         # llm_ttft_ms / llm_tpot_ms stamps
    admit_time: float = 0.0          # first slot given (kept across an
    #                                  eviction and re-admission)
    first_time: float = 0.0
    # Unified QoS admission (ISSUE 12, gateway/qos.py): the owning
    # frame's tenant/class, and the pre-computed class rank slot
    # admission sorts by (lower = more urgent; equal ranks keep
    # submission order, so the default 0 everywhere is exactly the
    # old FIFO).  Plane 4 of the one-scheduler refactor: the batcher
    # admits by the same class vocabulary as the stage credits.
    tenant: str | None = None
    qos_class: str | None = None
    qos_rank: int = 0


_select_tokens = jax.jit(llama.select_tokens,
                         static_argnames=("top_k",))

# Columns of the device loop's packed fold input, one row a slot; the
# slot's stop tokens follow (``eos_width`` columns), then its n-gram
# history tail (``spec_window`` columns, ``speculative: ngram`` only).
_FOLD_JOIN, _FOLD_PLEN, _FOLD_BUDGET, _FOLD_MAY_DECODE, \
    _FOLD_FORCE_INACTIVE, _FOLD_TEMPERATURE, _FOLD_EOS = range(7)


@partial(jax.jit, static_argnames=("eos_width",))
def _fold_joiners(tokens, lengths, active, budget, history, firsts,
                  packed, *, eos_width: int):
    """The device loop's fold-in as ONE program of fixed shape: the
    chained carries ``[max_slots]`` (``history`` ``[max_slots, W]``),
    the first tokens admission left on the device and the host's packed
    int32 rows (the ``_FOLD_*`` columns) give the carries the next
    block starts from, plus the temperatures and the stop table its
    loop reads.  A freed slot goes inactive; a joiner takes its first
    token, prompt length, budget and history tail, and decodes on
    unless the host says it may not or its first token is one of its
    stop tokens -- compared here, so the token is never fetched.  A
    slot freed and joined again in the same block is a joiner."""
    join = packed[:, _FOLD_JOIN] != 0
    eos = packed[:, _FOLD_EOS:_FOLD_EOS + eos_width]
    tail = packed[:, _FOLD_EOS + eos_width:]
    may_decode = (packed[:, _FOLD_MAY_DECODE] != 0) \
        & ~(firsts[:, None] == eos).any(axis=1)
    active = jnp.where(
        join, may_decode,
        active & (packed[:, _FOLD_FORCE_INACTIVE] == 0))
    if tail.shape[1]:
        history = jnp.where(join[:, None], tail, history)
    temperatures = jax.lax.bitcast_convert_type(
        packed[:, _FOLD_TEMPERATURE], jnp.float32)
    return (jnp.where(join, firsts, tokens),
            jnp.where(join, packed[:, _FOLD_PLEN], lengths), active,
            jnp.where(join, packed[:, _FOLD_BUDGET], budget), history,
            temperatures, eos)


@jax.jit
def _set_first(firsts, slot, first):
    """One admission's sampled first token ``[1]`` into the
    ``[max_slots]`` vector the fold reads."""
    return firsts.at[slot].set(first[0])


def model_family(config):
    """The model family that serves ``config``, chosen by its type: the
    module whose functions the batcher calls (``init_cache``,
    ``cache_array``, ``prefill_into_slot(s)``, ``decode_step``,
    ``decode_loop``, ``check_serving``,
    ``_matmul_safe_config``; sampling is shared).  A family may also
    say ``ADMISSION_LOGITS_AT_LAST`` (its admission computes the logits
    of one chunk position, which the batcher then names) and
    ``ADMISSION_CARRIES_STATE`` (its chunks hand a recurrent state on:
    a last chunk is handed over where it starts and not moved back to
    fit the slot -- the overlap would be applied twice, so the family
    takes a chunk that spills -- and the recorder's ``prefill`` info
    counts the chunks that were handed a state: ``state_carried``),
    leave out
    ``prefill_into_slots`` (admission stays one slot a program) and
    return block statistics after ``decode_loop``'s twelve results,
    which its ``loop_stats`` turns into what the LLM element observes;
    ``paged_decode_pages`` says how many pages a grid step its paged
    decode kernel takes where that kernel serves the cache (the batcher
    then counts how much of each block's grid streams a live page).
    ``BLOCK_DIFFUSION`` (models/sdar.py) says that generation is by
    diffusion over blocks of ``config.block_length``: admission
    prefills a prompt's whole blocks (``admitted_length``) and yields
    NO token; a joiner enters the device loop with a block
    (``joiner_carry``, ``carry_width`` columns of the chained carry the
    Llama family calls ``history``); a pass of the loop emits several
    tokens a row or none, so the ring needs room for a block a row, the
    pages are assured a block past the stored positions, a first token
    arrives when the first block commits, and ``steps`` counts passes
    (the family's ``loop_stats`` counts the rows in them)."""
    if isinstance(config, deepseek.DeepseekConfig):
        return deepseek
    if isinstance(config, olmo_hybrid.OlmoHybridConfig):
        return olmo_hybrid
    if isinstance(config, sdar.SdarConfig):
        return sdar
    return llama


def _prefetch(tree) -> None:
    """Start the device-to-host copies of a dispatched block's result
    tree, so they overlap newer blocks' compute and the retire's ONE
    counted fetch finds them done.  Explicit by intent, so it runs in
    an ``allow`` scope: a bare ``copy_to_host_async`` is subject to
    jax's device-to-host transfer guard, and the serving element runs
    decode ticks under ``disallow`` precisely to catch STRAY per-token
    syncs -- on the TPU the batcher's own block copy would otherwise
    raise there and be replayed as a device loss (the CPU backend never
    fires the guard, so tier-1 cannot see this)."""
    with jax.transfer_guard_device_to_host("allow"):
        for leaf in jax.tree_util.tree_leaves(tree):
            leaf.copy_to_host_async()


class _LoopBlock:
    """One dispatched-but-unretired device-resident generation block
    (llama.decode_loop).  ``tree`` holds every device array the retire
    needs -- emitted ring, counts, carries, accept counters, the
    first-token vector its joiners were folded in from -- fetched in
    ONE counted host copy."""
    __slots__ = ("tree", "snapshot", "firsts_meta", "grid")

    def __init__(self, tree, snapshot, firsts_meta, grid=None):
        self.tree = tree
        self.snapshot = snapshot      # [(slot, request)] in the block
        self.firsts_meta = firsts_meta  # [(slot, request)] admissions
        # (live, all) grid steps of the paged decode kernel at the
        # block's first step, or None where it does not serve decode
        self.grid = grid


class ContinuousBatcher:
    def __init__(self, params,
                 config: llama.LlamaConfig | deepseek.DeepseekConfig
                 | olmo_hybrid.OlmoHybridConfig | sdar.SdarConfig,
                 max_slots: int = 8, max_seq: int | None = None,
                 prefill_chunk: int = 512, rng_seed: int = 0,
                 inflight: int = 2,
                 cache_put: Callable | None = None,
                 decode_block_tokens: int = 0,
                 speculative: str = "off", spec_tokens: int = 4,
                 spec_window: int = 32, kv_page_tokens: int = 0,
                 kv_pages: int | None = None,
                 fetch: Callable | None = None,
                 fault_probe: Callable | None = None,
                 trace: Callable | None = None,
                 sample_top_k: int = 0,
                 prefix_cache: bool | str = False,
                 prefix_min_tokens: int = 64,
                 spec_autoprobe: bool | str = True):
        self.params = params
        self._family = family = model_family(config)
        # A pre-sharded (TP/fsdp) quantized tree must keep XLA's
        # matmul path -- resolved here, where the concrete leaves'
        # sharding is visible (llama._matmul_safe_config).
        self.config = family._matmul_safe_config(config, params)
        self.max_slots = max_slots
        self.max_seq = max_seq or config.max_seq
        self.prefill_chunk = min(prefill_chunk, self.max_seq)
        # How many device-loop blocks to keep in flight while requests
        # wait for a slot (one otherwise: ``step``).  Each dispatch
        # chains off the previous block's device carries, so depth d
        # hides up to d * block_compute of host round-trip latency
        # behind device work.
        self.inflight = max(1, int(inflight))
        # Device-resident generation (ISSUE 8): > 0 sizes the emitted
        # ring of llama.decode_loop blocks -- sampling, stop detection
        # and (optionally) speculation run inside one dispatch, the
        # host fetches once per block.  0 is the per-token tick.
        self.decode_block_tokens = max(0, int(decode_block_tokens))
        self.device_loop = self.decode_block_tokens > 0
        # Normalized exactly as the create-time domain check
        # (analysis/params.py _check_value) normalizes, so a value
        # that passes preflight cannot fail here on case/whitespace.
        self.speculative = str(speculative or "off").strip().lower()
        if self.speculative not in ("off", "ngram", "draft", "auto"):
            raise ValueError(f"speculative={speculative!r}: one of "
                             f"off|ngram|draft|auto")
        # ``auto`` (ISSUE 18): measure draft speculation against plain
        # decode in a startup micro-probe and enable it only on a
        # >= SPEC_AUTO_MIN_RATIO win -- auto never raises and never
        # enables a losing config, so configs explicit ``draft`` would
        # refuse (no device loop, ring too small) just resolve to off.
        self.spec_autoprobe = _knob_on(spec_autoprobe, default=True)
        self.spec_probe_ratio = 0.0
        if self.speculative == "auto" and (
                not self.device_loop
                or self.decode_block_tokens < max(1, int(spec_tokens)) + 1
                or not self.spec_autoprobe):
            self.speculative = "off"
        if self.speculative != "off" and not self.device_loop:
            raise ValueError(
                "speculative decoding rides the device loop: set "
                "decode_block_tokens > 0")
        self.spec_tokens = max(1, int(spec_tokens))
        if self.speculative != "off" \
                and self.decode_block_tokens < self.spec_tokens + 1:
            # The loop's room test needs one worst-case speculative
            # emission (spec_tokens + 1) to fit the ring; a smaller
            # ring would dispatch blocks that run ZERO iterations --
            # a silent no-progress wedge, so refuse it up front.
            raise ValueError(
                f"decode_block_tokens={self.decode_block_tokens} "
                f"cannot hold one speculative emission (spec_tokens + "
                f"1 = {self.spec_tokens + 1}); raise the ring or "
                f"lower spec_tokens")
        self.spec_window = max(4, int(spec_window))
        # Restrict sampled rows to the k highest logits (0 = full
        # categorical).  Static per-trace: rides llama.select_tokens /
        # decode_loop through the ops top-k interface
        # (the Pallas kernel on TPU, lax.top_k elsewhere); greedy rows
        # are unaffected either way.  Bounded at build to the kernel's
        # lane cap so a CPU-tested config cannot blow up mid-serving
        # on TPU (the create-time domain check mirrors this bound).
        self.sample_top_k = max(0, int(sample_top_k))
        if self.sample_top_k > 128:
            raise ValueError(
                f"sample_top_k={self.sample_top_k}: the on-TPU top-k "
                f"kernel holds candidates in one 128-lane tile; use "
                f"k <= 128 (0 = full-vocab categorical)")
        # What the config's family cannot serve is refused here, by the
        # parameter's name; nothing falls back silently.
        family.check_serving(
            speculative=self.speculative,
            prefix_cache=_knob_on(prefix_cache, default=False),
            kv_page_tokens=max(0, int(kv_page_tokens)))
        self._draft = draft_params(params) \
            if self.speculative == "draft" else None
        # Paged KV cache (models/paged.py): fixed-size pages + per-slot
        # page table; 0 keeps the monolithic [slots, max_seq] cache.
        self.kv_page_tokens = max(0, int(kv_page_tokens))
        # Generation by diffusion over blocks (``model_family``): the
        # block length, 0 for a family that emits a token a row a step.
        self._block = config.block_length \
            if getattr(family, "BLOCK_DIFFUSION", False) else 0
        if self._block:
            if not self.device_loop \
                    or self.decode_block_tokens % self._block:
                raise ValueError(
                    f"decode_block_tokens={self.decode_block_tokens}: "
                    f"generation by diffusion over blocks rides the "
                    f"device loop; a multiple of block_length "
                    f"({self._block})")
            if self.kv_page_tokens % self._block \
                    or self.prefill_chunk % self._block:
                raise ValueError(
                    f"block_length={self._block}: must divide "
                    f"kv_page_tokens ({self.kv_page_tokens}) and "
                    f"prefill_chunk ({self.prefill_chunk}), so that a "
                    f"block lies inside one page and one chunk")
        # The longest a sequence grows: the slot's last block is the
        # trash block where K/V is written a block at a time.
        self._seq_limit = self.max_seq - self._block
        # Shared-prefix page cache (ISSUE 18): requests whose prompts
        # share leading pages map ONE physical copy, refcounted, and
        # skip prefill over the shared span.  Rides the page table, so
        # it requires the paged cache.
        self.prefix_cache = _knob_on(prefix_cache, default=False)
        self.prefix_min_tokens = max(1, int(prefix_min_tokens))
        if self.prefix_cache and not self.kv_page_tokens:
            raise ValueError(
                "prefix_cache: on shares KV at page granularity: set "
                "kv_page_tokens > 0")
        self._pages: PageAllocator | None = None
        if self.kv_page_tokens:
            pps = pages_per_slot(self.max_seq, self.kv_page_tokens)
            if self.prefill_chunk % self.kv_page_tokens:
                raise ValueError(
                    f"kv_page_tokens={self.kv_page_tokens} must divide "
                    f"prefill_chunk ({self.prefill_chunk}) so admission "
                    f"chunks stay page-aligned")
            self.cache = init_paged_cache(
                config, max_slots, self.max_seq, self.kv_page_tokens,
                kv_pages)
            pool = family.cache_array(self.cache).shape[1]
            self._pages = PageAllocator(
                pool, pps, max_slots, prefix_cache=self.prefix_cache,
                prefix_min_tokens=self.prefix_min_tokens)
        else:
            self.cache = family.init_cache(config, max_slots, self.max_seq)
        # Multichip serving: ``cache_put`` places the initial KV cache
        # onto the serving mesh (e.g. ``lambda c: plan.put(c,
        # llama.cache_specs(config))`` for TP-sharded kv heads) --
        # donation keeps that sharding across every subsequent dispatch,
        # so one placement at init is enough.  Params are pre-sharded by
        # the caller the same way (quantized trees via
        # quant.quantize_specs).
        self._cache_put = cache_put
        if cache_put is not None:
            self.cache = cache_put(self.cache)
        # Pages a grid step of the family's paged decode kernel, where
        # it serves this cache (None: another backend decodes).
        paged_pages = getattr(family, "paged_decode_pages", None)
        self._paged_pages = paged_pages(config, self.cache) \
            if paged_pages is not None and self._pages is not None \
            else None
        # One explicit host fetch per retired device-loop block; the
        # LLM element wires the pipeline TransferLedger's counted fetch
        # here so serving obeys the device-resident swag contract.
        self._fetch = fetch if fetch is not None else jax.device_get
        # Armed-chaos probe called before every device-loop block
        # dispatch (the ``decode_block`` injection point); None = cold.
        self._fault_probe = fault_probe
        # Host-timeline tap (ISSUE 26): ``trace(name, ms, info)`` fires
        # at the END of every phase of ``step()`` -- ``admit``,
        # ``prefill``, ``fold`` (the device loop's fold-in of joiners
        # and freed slots, up to the block's enqueue; its ``launches``
        # counts the device calls it made), ``dispatch`` (the
        # enqueue and the start of its host copies), ``retire_wait``
        # (only the blocking fetch of the oldest block), ``demux`` --
        # each phase starting where the last one ended, so they tile
        # the tick.  ``prefill``, ``fold`` and ``dispatch`` launch
        # device programs: where the runtime bounds the launches
        # outstanding (32 on the v5e, PERF.md section 6) a launch
        # blocks until the oldest finishes, so these phases hold the
        # worker's waits for a busy chip as well as its work.  The LLM
        # element wires the tap to the pipeline's flight recorder; None
        # (the default) takes no stamp and costs one branch a phase.
        self.trace = trace
        self._mark = 0.0
        self.lengths = np.zeros(max_slots, dtype=np.int32)
        self.current = np.zeros(max_slots, dtype=np.int32)
        self.temperatures = np.zeros(max_slots, dtype=np.float32)
        self.decoding = np.zeros(max_slots, dtype=bool)
        self.slots: list[Request | None] = [None] * max_slots
        self.pending: list[Request] = []
        self._prefilling: list[int] = []      # slot FIFO, round-robin
        self._key = jax.random.PRNGKey(rng_seed)
        # device-loop state: the admissions whose prefill completed
        # and that no dispatch has folded in yet, their sampled first
        # tokens (on the device, unfetched, one entry a slot), the
        # chained carries of the latest loop block, the in-flight
        # loop-block queue, host mirrors of per-slot eos rows and of
        # the page table, and a conservative length upper bound for
        # page allocation while blocks are in flight.
        self._pending_first: dict[int, Request] = {}     # by slot
        self._firsts = self._no_firsts()
        self._slot_index = [jnp.int32(slot) for slot in range(max_slots)]
        self._page_rows = None if self._pages is None else np.zeros(
            (max_slots, self._pages.pps), dtype=np.int32)
        # Device calls (programs and explicit uploads) made by the
        # fold-in and the page-table sync, counted where they are made.
        self._launches = 0
        # Single-slot chunks that did not start their prompt (a family
        # with a recurrent state was handed one by the chunk before).
        self._state_carried = 0
        self._loop_chain: dict | None = None
        self._loop_inflight: deque[_LoopBlock] = deque()
        self._eos_width = 1
        self._eos_rows = np.full((max_slots, 1), -1, dtype=np.int32)
        self._lengths_upper = np.zeros(max_slots, dtype=np.int32)
        self._admit_seq = 0
        # Slots whose chained ``active`` flag must drop at the next
        # dispatch (host-side finish/cancel/eviction the device hasn't
        # seen yet).
        self._force_inactive: set[int] = set()
        # perf counters
        self.tokens_emitted = 0
        self.steps = 0
        self.prefill_tokens = 0
        self.blocks_dispatched = 0
        self.blocks_retired = 0
        self.accepted_tokens = 0
        self.draft_tokens = 0
        self.evictions = 0
        self.recoveries = 0
        # prefix-cache accounting (ISSUE 18): prompt tokens admission
        # skipped because their pages were adopted from the index.
        self.prefix_shared_tokens = 0
        # per-request latency stamps drained by the serving element
        # into the telemetry plane (llm_ttft_ms / llm_tpot_ms), and the
        # family's per-block observations (``loop_stats``), drained
        # the same way.
        self._request_stats: list[dict] = []
        self._block_stats: list[dict] = []
        # ``speculative: auto``: measure, then commit to draft or off.
        if self.speculative == "auto":
            self.spec_probe_ratio = self._spec_probe()
            if self.spec_probe_ratio >= SPEC_AUTO_MIN_RATIO:
                self.speculative = "draft"
                self._draft = draft_params(params)
            else:
                self.speculative = "off"

    # -- admission ---------------------------------------------------------

    def _no_firsts(self):
        """The first-token vector before any admission: noughts, or,
        where admission yields no token, an id no stop token has (the
        fold compares it with the slot's stop tokens, whose pad is
        -1)."""
        return jnp.full(self.max_slots, -2 if self._block else 0,
                        dtype=jnp.int32)

    def submit(self, request: Request):
        if len(request.prompt_tokens) >= self._seq_limit:
            request.prompt_tokens = \
                request.prompt_tokens[-(self.max_seq // 2):]
        # An empty prompt still needs one position of context to sample
        # from; condition it on a single pad token rather than indexing
        # into uninitialised padding.
        if not request.prompt_tokens:
            request.prompt_tokens = [0]
        request.base_prompt = list(request.prompt_tokens)
        request.submit_time = time.perf_counter()
        self.pending.append(request)

    def _next_pending(self) -> Request:
        """Pop the next request to admit: the best ``qos_rank`` (ISSUE
        12 -- the batcher is the fourth admission plane the unified
        scheduler reaches), queue position breaking ties so the
        all-default case is EXACTLY the old FIFO and an evicted
        request's front re-insert still wins its class."""
        best = min(range(len(self.pending)),
                   key=lambda index: (self.pending[index].qos_rank,
                                      index))
        return self.pending.pop(best)

    def _admit(self):
        """Assign free slots to pending requests (no device work: the
        prompt is written chunk-at-a-time by ``_prefill_tick``)."""
        for slot, occupant in enumerate(self.slots):
            if occupant is not None or not self.pending:
                continue
            request = self._next_pending()
            request.slot = slot
            request.prefill_pos = 0
            if self._pages is not None and self.prefix_cache:
                # Shared-prefix adoption (ISSUE 18): map the longest
                # indexed page chain matching this prompt read-only
                # and start prefill past it -- the skipped span never
                # touches the device.
                shared = self._pages.adopt_prefix(
                    slot, request.prompt_tokens, self.kv_page_tokens)
                if shared:
                    request.prefill_pos = shared
                    self.prefix_shared_tokens += shared
            request.admit_seq = self._admit_seq
            self._admit_seq += 1
            if not request.admit_time:
                request.admit_time = time.perf_counter()
            self.slots[slot] = request
            self.lengths[slot] = 0
            self._lengths_upper[slot] = 0
            self.current[slot] = 0
            self.temperatures[slot] = request.temperature
            self.decoding[slot] = False
            self._set_eos_row(slot, request.eos_tokens)
            self._prefilling.append(slot)

    def _set_eos_row(self, slot: int, eos_tokens) -> None:
        """Mirror one slot's stop-token set into the host eos table
        (uploaded with every device-loop dispatch; -1 pads never match
        a real token id).  A wider set than any seen before grows the
        table -- a new compile shape, once per distinct width."""
        width = max(1, len(eos_tokens or ()))
        if width > self._eos_width:
            grown = np.full((self.max_slots, width), -1, dtype=np.int32)
            grown[:, :self._eos_width] = self._eos_rows
            self._eos_rows = grown
            self._eos_width = width
        self._eos_rows[slot] = -1
        for column, token in enumerate(eos_tokens or ()):
            self._eos_rows[slot, column] = int(token)

    def _prefill_tick(self):
        """Advance admissions by one chunk (<= prefill_chunk tokens)
        each.  Device loop: every admitting slot advances -- a
        multi-slot burst runs as ONE batched dispatch
        (``llama.prefill_into_slots``: the [N*S, dim] matmuls feed the
        MXU far better than N serialized [S, dim] dispatches), falling
        back to per-slot dispatches for the flash-attention config.
        Per-token tick: at most ONE chunk total, preserving the
        one-chunk decode-stall bound (each chunk's completion fetch
        blocks the host there).  Returns the number of chunks
        written."""
        if (self.device_loop and len(self._prefilling) > 1
                and hasattr(self._family, "prefill_into_slots")
                and self.config.attention != "flash"):
            return self._prefill_tick_batched()
        budget = len(self._prefilling) if self.device_loop \
            else min(1, len(self._prefilling))
        chunks = 0
        for _ in range(budget):
            if not self._prefilling:
                break           # shrunk by a pressure eviction below
            slot = self._prefilling.pop(0)
            request = self.slots[slot]
            if request is None:     # cancelled/evicted while waiting
                continue
            start, chunk_tokens = self._admission_chunk(request)
            if not chunk_tokens:
                # (a prompt shorter than a block: nothing to prefill)
                self._admission_advance(slot, request, start, 0, None)
                continue
            if not self._ensure_pages(slot, start + self.prefill_chunk):
                self._prefilling.append(slot)   # pool pressure: wait
                continue
            self._sync_page_table()
            padded = np.zeros((1, self.prefill_chunk), dtype=np.int32)
            padded[0, :len(chunk_tokens)] = chunk_tokens
            logits, self.cache = self._family.prefill_into_slot(
                self.params, self.config, jnp.asarray(padded),
                self.cache, jnp.int32(slot), jnp.int32(start),
                *self._admission_last(len(chunk_tokens)))
            self._admission_advance(slot, request, start,
                                    len(chunk_tokens), logits)
            chunks += 1
            self._state_carried += start > 0
        return chunks

    def _prefill_tick_batched(self):
        """One chunk for EVERY admitting slot in a single batched
        dispatch.  N is padded up to a power-of-two compile bucket by
        duplicating the first row (idempotent: same slot, same start,
        same tokens -- see llama.prefill_into_slots)."""
        admitting = []
        for _ in range(len(self._prefilling)):
            if not self._prefilling:
                break           # shrunk by a pressure eviction below
            slot = self._prefilling.pop(0)
            if self.slots[slot] is None:    # cancelled/evicted
                continue
            start, _ = self._admission_chunk(self.slots[slot])
            if not self._ensure_pages(slot, start + self.prefill_chunk):
                self._prefilling.append(slot)   # pool pressure: wait
                continue
            admitting.append(slot)
        # A LATER slot's ensure may have preempted an EARLIER admitted
        # one for its pages: drop evicted slots before dispatching.
        admitting = [s for s in admitting if self.slots[s] is not None]
        # Overflow waits one tick (FIFO rotation keeps chunk fairness);
        # see _ADMISSION_BURST_MAX for why the burst is capped.
        self._prefilling.extend(admitting[_ADMISSION_BURST_MAX:])
        admitting = admitting[:_ADMISSION_BURST_MAX]
        if not admitting:
            return 0
        self._sync_page_table()
        n = len(admitting)
        rows = pad_to_bucket(admitting)
        bucket = len(rows)
        tokens = np.zeros((bucket, self.prefill_chunk), dtype=np.int32)
        slot_rows = np.zeros(bucket, dtype=np.int32)
        starts = np.zeros(bucket, dtype=np.int32)
        metas = []
        for i, slot in enumerate(rows):
            request = self.slots[slot]
            start, chunk_tokens = self._admission_chunk(request)
            tokens[i, :len(chunk_tokens)] = chunk_tokens
            slot_rows[i] = slot
            starts[i] = start
            metas.append((slot, request, start, len(chunk_tokens)))
        logits, self.cache = self._family.prefill_into_slots(
            self.params, self.config, jnp.asarray(tokens), self.cache,
            jnp.asarray(slot_rows), jnp.asarray(starts))
        for i, (slot, request, start, chunk_len) in enumerate(metas[:n]):
            self._admission_advance(slot, request, start, chunk_len,
                                    logits[i:i + 1])
        return n

    def _admission_chunk(self, request: Request):
        """(start, chunk tokens) of the request's next prefill chunk.
        The write start clamps so a full chunk always fits inside the
        cache (a spilling dynamic_update_slice would clamp internally
        and corrupt earlier positions); a clamped start re-writes the
        overlap with byte-identical KV (same tokens, same positions), so
        correctness is unaffected and only the final chunk pays.  The
        chunk is always PADDED to prefill_chunk by the caller: one
        compiled shape per admission; pad positions hold garbage KV, but
        decode writes each position before the length mask ever admits
        it, and the causal prefill mask never looks past the query
        position.  A family with a recurrent state takes the chunk
        where it starts (``ADMISSION_CARRIES_STATE``): the overlap of
        a moved chunk would enter the state twice, so its admission
        copes with a chunk that spills past the slot instead."""
        start = request.prefill_pos
        if not getattr(self._family, "ADMISSION_CARRIES_STATE", False):
            start = min(start, self.max_seq - self.prefill_chunk)
        return start, request.prompt_tokens[
            start:min(start + self.prefill_chunk,
                      self._admission_length(request))]

    def _admission_length(self, request: Request) -> int:
        """How much of the prompt admission writes: all of it, or its
        whole blocks where generation is by diffusion over blocks (the
        tokens left over open the first generated block)."""
        if self._block:
            return self._family.admitted_length(
                self.config, len(request.prompt_tokens))
        return len(request.prompt_tokens)

    def _admission_advance(self, slot: int, request: Request,
                           start: int, chunk_len: int, logits):
        """Account one written chunk; on the FINAL chunk, sample the
        first generated token from the last real prompt position's
        logits ([1, S, vocab] row) and hand the slot to decode --
        without fetching on the device loop (the device scalar folds
        into the next block dispatch and emits when that block
        retires)."""
        prompt = request.prompt_tokens
        self.prefill_tokens += start + chunk_len - request.prefill_pos
        request.prefill_pos = start + chunk_len
        if self._pages is not None and self.prefix_cache:
            # Index every whole prompt page now written: the content
            # is position-deterministic, so the pages can serve any
            # later prompt sharing this prefix (register as we go --
            # even a mid-admission chain is adoptable).
            self._pages.register_prefix(slot, prompt,
                                        request.prefill_pos,
                                        self.kv_page_tokens)
        if request.prefill_pos < self._admission_length(request):
            self._prefilling.append(slot)       # more chunks to go
            return
        if self._block:
            # Admission yields no token: the slot joins the next block
            # dispatch with its first block (``_fold_rows``), and its
            # first token arrives when that block commits.
            self.lengths[slot] = request.prefill_pos
            self._lengths_upper[slot] = len(prompt)
            self.decoding[slot] = True
            self._pending_first[slot] = request
            return
        # (a family that computes the sampled position alone hands
        # back one position)
        last = min(len(prompt) - start - 1, logits.shape[1] - 1)
        first = self._sample(logits[:, last, :], request.temperature)
        self.lengths[slot] = len(prompt)
        self._lengths_upper[slot] = len(prompt)
        self.decoding[slot] = True
        if self.device_loop:
            # No host copy here: the token stays on the device, in the
            # vector the next dispatch folds in and its block's retire
            # fetches whole.
            self._firsts = _set_first(self._firsts,
                                      self._slot_index[slot], first)
            self._pending_first[slot] = request
        else:
            first_token = int(jax.device_get(first)[0])
            self.current[slot] = first_token
            self._emit(request, first_token)

    # -- decode ------------------------------------------------------------

    def _sample(self, logits, temperature: float):
        if temperature and temperature > 0:
            self._key, sub = jax.random.split(self._key)
            if self.sample_top_k:
                # The admission's first token obeys the same top-k
                # restriction as every decode-loop token after it.
                return _select_tokens(
                    sub, logits,
                    jnp.full((logits.shape[0],), temperature,
                             dtype=jnp.float32),
                    top_k=self.sample_top_k)
            return llama.temperature_sample(sub, logits, temperature)
        return llama.greedy_sample(logits)

    def _admission_last(self, chunk_len: int) -> tuple:
        """The chunk's last real position, for a family whose admission
        computes that position's logits alone; nothing otherwise."""
        if getattr(self._family, "ADMISSION_LOGITS_AT_LAST", False):
            return (jnp.int32(chunk_len - 1),)
        return ()

    def step(self) -> int:
        """Admit pending requests, advance one prefill chunk per
        admitting slot, dispatch/retire decode work across all
        generating slots, emit tokens.  Returns the number of occupied
        slots (prefilling + decoding)."""
        traced = self.trace is not None
        if traced:
            self._mark = time.perf_counter()
        self._admit()
        if traced:
            self._phase("admit")
        carried = self._state_carried
        chunks = self._prefill_tick()
        if traced:
            info = {"chunks": chunks}
            if getattr(self._family, "ADMISSION_CARRIES_STATE", False):
                info["state_carried"] = self._state_carried - carried
            self._phase("prefill", info)
        decoding = [i for i in range(self.max_slots) if self.decoding[i]]
        if self.device_loop:
            if decoding or self._pending_first or self._loop_inflight:
                blocks = 0
                # Blocks queued ahead of the running one hide the
                # host's time between blocks, and make everything
                # enqueued after them -- a joiner's prefill, another
                # element's program on the same chip -- wait a block
                # longer.  Worth it only where requests wait for a
                # slot: there the batcher is what bounds throughput.
                # (Until the fold-in was one launch the worker never
                # got ahead of the chip; once it did, a part-full
                # batch beside a detector took the whole chip for
                # near-empty blocks: PERF.md section 6, PR 32.)
                depth = self.inflight if self.pending else 1
                while len(self._loop_inflight) < depth:
                    if not self._dispatch_loop_block():
                        break
                    blocks += 1
                if traced:
                    new = [self._loop_inflight[-1 - index]
                           for index in range(blocks)]
                    self._phase("dispatch", {
                        "blocks": blocks,
                        "slots": len(new[0].snapshot) if new else 0,
                        "joining": sum(len(block.firsts_meta)
                                       for block in new)})
                if self._loop_inflight:
                    self._retire_loop_block()
        elif decoding:
            self._decode_tick(decoding)
        return self.active_count

    def _phase(self, name: str, info: dict | None = None) -> None:
        """A tick phase ended now: hand its name and length to the
        ``trace`` tap; the next one starts here."""
        now = time.perf_counter()
        self.trace(name, (now - self._mark) * 1000.0, info)
        self._mark = now

    def _decode_tick(self, decoding: list[int]):
        if self._pages is not None:
            for slot in decoding:
                if not self._ensure_pages(slot,
                                          int(self.lengths[slot]) + 2):
                    # Unreachable while the pool holds one full slot
                    # (pps + 1, enforced at init): preempt the slot
                    # itself rather than let its write land on the
                    # trash page (it resumes from committed tokens).
                    self._evict_slot(slot)
            self._sync_page_table()
            # An ensure may have preempted another decoding slot:
            # refresh the list (and the write mask reads the flags).
            decoding = [i for i in decoding if self.decoding[i]]
            if not decoding:
                return
        tokens = jnp.asarray(self.current)
        # Rows not decoding (empty or mid-prefill) still flow through the
        # batched step; route their KV write to the trash position
        # max_seq-1, which real content never occupies (decode finishes
        # at lengths >= max_seq-1, so its last write is max_seq-2, and
        # the masks never admit max_seq-1 for a live row).
        write_positions = np.where(self.decoding, self.lengths,
                                   self.max_seq - 1).astype(np.int32)
        logits, self.cache = self._family.decode_step(
            self.params, self.config, tokens, self.cache,
            jnp.asarray(write_positions))
        self._key, sub = jax.random.split(self._key)
        selected = _select_tokens(
            sub, logits, jnp.asarray(self.temperatures),
            top_k=self.sample_top_k)
        if self.trace is not None:
            self._phase("dispatch", {"blocks": 1, "slots": len(decoding)})
        next_tokens = np.asarray(jax.device_get(selected), dtype=np.int32)
        if self.trace is not None:
            self._phase("retire_wait")
        self.steps += 1
        for i in decoding:
            request = self.slots[i]
            if request is None:                 # freed mid-dispatch
                continue
            self.lengths[i] += 1
            token = int(next_tokens[i])
            self.current[i] = token
            self._emit(request, token)
        if self.trace is not None:
            self._phase("demux")

    # -- speculative auto-probe (ISSUE 18) ---------------------------------

    def _spec_probe(self) -> float:
        """Measure draft speculation against plain decode on a SCRATCH
        cache (identical shapes to serving; ``self.cache`` is never
        touched) and return spec tokens/s over plain tokens/s.  Each
        arm pays one warmup block for compile, then the best of
        ``_SPEC_PROBE_BLOCKS`` timed blocks counts -- a host hiccup on
        one block must not flip the verdict."""
        ring = self.decode_block_tokens
        draft = draft_params(self.params)
        tokens = jnp.zeros(self.max_slots, dtype=jnp.int32)
        lengths = jnp.full(self.max_slots, self.max_seq // 2,
                           dtype=jnp.int32)
        active = jnp.ones(self.max_slots, dtype=bool)
        temps = jnp.zeros(self.max_slots, dtype=jnp.float32)
        eos = jnp.full((self.max_slots, 1), -1, dtype=jnp.int32)
        history = jnp.full((self.max_slots, 1), -1, dtype=jnp.int32)
        rates = {}
        for mode, dparams in (("off", None), ("draft", draft)):
            cache = self._probe_cache()
            key = jax.random.PRNGKey(0)
            best = 0.0
            for index in range(_SPEC_PROBE_BLOCKS + 1):
                budget = jnp.full(self.max_slots, ring,
                                  dtype=jnp.int32)
                begin = time.perf_counter()
                (_, counts, tokens, _, _, _, history, key, _, _, _,
                 cache) = self._family.decode_loop(
                    self.params, self.config, tokens, cache, lengths,
                    active, budget, temps, eos, history, key,
                    ring=ring, speculative=mode,
                    spec_tokens=self.spec_tokens,
                    spec_window=self.spec_window, draft=dparams,
                    top_k=self.sample_top_k)
                emitted = int(np.asarray(jax.device_get(counts)).sum())
                elapsed = time.perf_counter() - begin
                if index and elapsed > 0:       # block 0 = compile
                    best = max(best, emitted / elapsed)
            rates[mode] = best
        return rates["draft"] / rates["off"] if rates["off"] else 0.0

    def _probe_cache(self):
        """A scratch serving cache for the probe.  Paged configs get a
        fully-mapped table (each slot's logical pages spread over the
        pool) so the probe pays real gather/scatter traffic instead of
        the all-trash-page fast case."""
        if not self.kv_page_tokens:
            cache = self._family.init_cache(self.config, self.max_slots,
                                            self.max_seq)
        else:
            cache = init_paged_cache(
                self.config, self.max_slots, self.max_seq,
                self.kv_page_tokens, self._pages.total)
            pps = self._pages.pps
            table = (np.arange(self.max_slots * pps, dtype=np.int32)
                     % max(1, self._pages.total - 1)) + 1
            cache["page_table"] = jnp.asarray(
                table.reshape(self.max_slots, pps))
        if self._cache_put is not None:
            cache = self._cache_put(cache)
        return cache

    # -- device-resident generation loop (ISSUE 8) -------------------------

    def _host_state(self):
        """Fresh device carries from the host mirrors (first dispatch
        and post-recover; every later block chains device-side)."""
        self._launches += 6             # one a statement below
        self._key, loop_key = jax.random.split(self._key)
        history_width = self._carry_width() or 1
        return {
            "tokens": jnp.asarray(self.current),
            "lengths": jnp.asarray(self.lengths),
            "active": jnp.zeros(self.max_slots, dtype=bool),
            "budget": jnp.zeros(self.max_slots, dtype=jnp.int32),
            "history": jnp.full((self.max_slots, history_width), -1,
                                dtype=jnp.int32),
            "key": loop_key,
        }

    def _carry_width(self) -> int:
        """Columns a joiner brings into the chained ``history`` carry:
        its n-gram history tail under ``speculative: ngram``, its first
        block and phase where generation is by diffusion over blocks
        (the family's ``carry_width``), else none."""
        if self._block:
            return self._family.carry_width(self.config)
        return self.spec_window if self.speculative == "ngram" else 0

    def _fold_rows(self, firsts_meta: list) -> np.ndarray:
        """The fold-in's host side, packed for one upload: a row a
        slot (the ``_FOLD_*`` columns) saying which slots were freed,
        which join with what prompt length and budget, whether a
        joiner may decode past its first token at all (budget and
        cache boundary; the stop-token part of that verdict is the
        device's), every slot's temperature (as its bits) and stop
        tokens, and under ``speculative: ngram`` each joiner's history
        tail -- or, where generation is by diffusion over blocks, the
        block it enters with (the family's ``joiner_carry``): it joins
        at the positions admission stored, with its whole budget, no
        token having been emitted for it.  Clears the freed set."""
        window = self._carry_width()
        eos_end = _FOLD_EOS + self._eos_width
        packed = np.zeros((self.max_slots, eos_end + window),
                          dtype=np.int32)
        packed[list(self._force_inactive), _FOLD_FORCE_INACTIVE] = 1
        self._force_inactive.clear()
        packed[:, _FOLD_TEMPERATURE] = self.temperatures.view(np.int32)
        packed[:, _FOLD_EOS:eos_end] = self._eos_rows
        for slot, request in firsts_meta:
            plen = len(request.prompt_tokens)
            left = request.max_new_tokens - request.generated
            packed[slot, _FOLD_JOIN] = 1
            if self._block:
                packed[slot, _FOLD_PLEN] = request.prefill_pos
                packed[slot, _FOLD_BUDGET] = max(left, 1)
                packed[slot, _FOLD_MAY_DECODE] = 1
                packed[slot, eos_end:] = self._family.joiner_carry(
                    self.config, request.prompt_tokens)
                continue
            packed[slot, _FOLD_PLEN] = plen
            packed[slot, _FOLD_BUDGET] = left - 1
            packed[slot, _FOLD_MAY_DECODE] = \
                left > 1 and plen + 1 < self.max_seq
            if window:
                recent = request.prompt_tokens[-window:]
                packed[slot, eos_end:] = -1
                packed[slot, packed.shape[1] - len(recent):] = recent
        return packed

    def _dispatch_loop_block(self) -> bool:
        """Chain one ``decode_loop`` block off the previous block's
        device carries.  Completed admissions, freed slots and new
        page-table rows are folded in first, by host arithmetic on the
        numpy mirrors and a fixed number of device calls whatever the
        number of joiners: the page table's upload where a row changed
        (:meth:`_sync_page_table`), ONE packed upload
        (:meth:`_fold_rows`) and ONE program (:func:`_fold_joiners`;
        the first token, budget, stop verdict and draft history ride
        device-side -- no host round trip).  The v5e runtime lets 32
        launches be outstanding and the chip waits for the rest, so a
        fold of a few launches a joiner left it idle (PERF.md section
        6, PR 26/32).  Returns False when there is nothing to decode,
        outstanding blocks already cover every request's budget, or
        page-pool pressure wants the in-flight blocks retired before
        an eviction can free room."""
        ring = self.decode_block_tokens
        # (a block's commit stores a whole block, and the first one's
        # part of it is prompt that the ring does not count)
        spec_extra = 2 * self._block if self._block \
            else self.spec_tokens + 1 if self.speculative != "off" else 1
        live = [i for i in range(self.max_slots) if self.decoding[i]]
        joining = sorted(self._pending_first)
        if not live and not joining:
            return False
        if not joining and self._loop_inflight:
            # Outstanding blocks already cover every live request's
            # remaining budget (EOS may cut a row shorter -- the loop's
            # own stop detection idles it, so overshoot blocks cost
            # almost nothing device-side).
            remaining = max(
                (self.slots[i].max_new_tokens - self.slots[i].generated
                 for i in live if self.slots[i] is not None), default=0)
            if len(self._loop_inflight) * ring >= remaining:
                return False
        for slot in sorted({*live, *joining}):
            if self.slots[slot] is None:
                continue                # evicted by an earlier ensure
            upto = int(self._lengths_upper[slot]) + ring + spec_extra
            if not self._ensure_pages(slot, upto):
                return False            # retire in-flight blocks first
        # An ensure above may have PREEMPTED a just-admitted slot for
        # its pages (the youngest occupant is usually a joining one):
        # re-snapshot both lists so the fold-in below never touches an
        # evicted slot's popped _pending_first entry.
        live = [i for i in range(self.max_slots) if self.decoding[i]]
        joining = sorted(self._pending_first)
        if not live and not joining:
            return False
        if self._fault_probe is not None:
            self._fault_probe("decode_block")
        launched = self._launches
        state = self._loop_chain or self._host_state()
        firsts_meta = [(slot, self._pending_first.pop(slot))
                       for slot in joining]
        self._sync_page_table()
        packed = jnp.asarray(self._fold_rows(firsts_meta))
        (tokens, lengths, active, budget, history, temps_dev,
         eos_dev) = _fold_joiners(
            state["tokens"], state["lengths"], state["active"],
            state["budget"], state["history"], self._firsts, packed,
            eos_width=self._eos_width)
        key = state["key"]
        self._launches += 2
        if self.trace is not None:
            self._phase("fold", {"joining": len(firsts_meta),
                                 "launches": self._launches - launched})
        (emitted, counts, tokens_next, lengths_next, active_next,
         budget_next, history_next, key_next, accepted, drafted, steps,
         self.cache, *stats) = self._family.decode_loop(
            self.params, self.config, tokens, self.cache, lengths,
            active, budget, temps_dev, eos_dev, history, key,
            ring=ring, speculative=self.speculative,
            spec_tokens=self.spec_tokens,
            spec_window=self.spec_window, draft=self._draft,
            top_k=self.sample_top_k)
        # Only what the retire actually reads rides the counted fetch
        # (the active/budget/history carries chain device-side).
        tree = {"emitted": emitted, "counts": counts,
                "lengths": lengths_next,
                "accepted": accepted, "drafted": drafted, "steps": steps}
        if stats:
            tree["stats"] = stats[0]    # the family's block statistics
        if firsts_meta and not self._block:
            tree["firsts"] = self._firsts
        _prefetch(tree)                 # overlap newer blocks
        self._loop_chain = {"tokens": tokens_next,
                            "lengths": lengths_next,
                            "active": active_next, "budget": budget_next,
                            "history": history_next, "key": key_next}
        snapshot = sorted({*live, *joining})
        grid = None
        if self._paged_pages is not None:
            # The rows of this block at its first step, from the host's
            # own lengths: how much of the kernel's grid has a page.
            from ..ops.pallas_decode import paged_grid_steps
            rows = np.zeros(self.max_slots, dtype=np.int64)
            rows[snapshot] = self._lengths_upper[snapshot]
            grid = paged_grid_steps(rows, self.kv_page_tokens,
                                    self._pages.pps, self._paged_pages)
        for slot in snapshot:
            self._lengths_upper[slot] = min(
                int(self._lengths_upper[slot]) + ring, self.max_seq)
        self._loop_inflight.append(_LoopBlock(
            tree, [(i, self.slots[i]) for i in snapshot], firsts_meta,
            grid))
        self.blocks_dispatched += 1
        return True

    def _retire_loop_block(self):
        """Fetch the OLDEST in-flight loop block -- ONE counted host
        copy of its whole result tree (the ``fetch`` hook; the async
        copies have been overlapping newer blocks' compute) -- and
        de-multiplex: folded first tokens, then each slot's ring
        prefix.  The host-side finish test in ``_emit`` is the
        authority; the device's stop detection never stops a row
        EARLIER than it, so truncation here only ever discards
        overshoot."""
        blk = self._loop_inflight.popleft()
        fetched = self._fetch(blk.tree)
        if self.trace is not None:
            self._phase("retire_wait", {"slots": len(blk.snapshot)})
        emitted = np.asarray(fetched["emitted"])
        counts = np.asarray(fetched["counts"])
        self.steps += int(fetched["steps"])
        self.blocks_retired += 1
        self.accepted_tokens += int(np.asarray(fetched["accepted"]).sum())
        self.draft_tokens += int(np.asarray(fetched["drafted"]).sum())
        if "firsts" in fetched:
            first_tokens = np.asarray(fetched["firsts"])
            for slot, request in blk.firsts_meta:
                if self.slots[slot] is request and not request.done:
                    token = int(first_tokens[slot])
                    self.current[slot] = token
                    self._emit(request, token)
        for slot, request in blk.snapshot:
            if request is None or self.slots[slot] is not request:
                continue
            for index in range(int(counts[slot])):
                if self.slots[slot] is not request or request.done:
                    break
                token = int(emitted[slot, index])
                self.current[slot] = token
                self._emit(request, token)
        lengths_fetched = np.asarray(fetched["lengths"])
        for slot, request in blk.snapshot:
            if request is not None and self.slots[slot] is request \
                    and not request.done:
                self.lengths[slot] = int(lengths_fetched[slot])
        if not self._loop_inflight:
            self._lengths_upper = self.lengths.copy()
        observed = self._family.loop_stats(fetched["stats"]) \
            if "stats" in fetched else None
        if blk.grid is not None:
            live_steps, grid_steps = blk.grid
            observed = {**(observed or {}),
                        "paged_grid_steps_live": live_steps,
                        "paged_grid_steps": grid_steps,
                        "paged_pages_per_step": self._paged_pages}
        if observed:
            self._block_stats.append(observed)
        if self.trace is not None:
            self._phase("demux", observed)

    # -- paged-cache bookkeeping -------------------------------------------

    def _ensure_pages(self, slot: int, upto_tokens: int) -> bool:
        """Cover the slot's logical positions [0, upto_tokens) with
        physical pages.  Under pool pressure: with blocks in flight the
        caller must retire them first (their writes still route through
        the already-dispatched table), otherwise the YOUNGEST other
        occupant is preempted -- its generation resumes later from its
        committed tokens, exactly like :meth:`recover`."""
        if self._pages is None:
            return True
        pages = self._pages.pages_for(
            min(int(upto_tokens), self.max_seq), self.kv_page_tokens)
        if self._pages.ensure(slot, pages):
            return True
        if self._loop_inflight:
            return False
        while True:
            victims = [(occupant.admit_seq, index)
                       for index, occupant in enumerate(self.slots)
                       if occupant is not None and index != slot]
            if not victims:
                return False
            self._evict_slot(max(victims)[1])
            if self._pages.ensure(slot, pages):
                return True

    def _sync_page_table(self) -> None:
        """Bring the device page table to the allocator's rows: its
        dirty rows go into the host mirror, and the mirror goes up
        whole, in ONE explicit copy (it rides the next dispatch);
        nothing where no row changed.  The copy is placed as the table
        it replaces -- across the serving mesh where ``cache_put`` put
        that one there, uncommitted otherwise -- so the programs that
        take the cache see the arguments they were built for."""
        if self._pages is None or not self._pages.dirty:
            return
        for slot, row in self._pages.dirty.items():
            self._page_rows[slot] = row
        self._pages.dirty.clear()
        table = self.cache["page_table"]
        # (a copy of the mirror: the device may read the host's buffer
        # after the call returns)
        self.cache["page_table"] = jax.device_put(
            self._page_rows.copy(),
            table.sharding if table.committed else None)
        self._launches += 1

    def _evict_slot(self, slot: int) -> None:
        """Preempt one slot for its pages: rebase the request onto its
        committed tokens and put it at the FRONT of the queue, so it
        re-admits (re-prefilling prompt + committed, emitting nothing
        twice) as soon as the pool breathes."""
        request = self.slots[slot]
        if request is None:
            return
        self._rebase(request)
        request.slot = -1
        request.prefill_pos = 0
        self._pending_first.pop(slot, None)
        self._prefilling = [s for s in self._prefilling if s != slot]
        self._free_slot(slot)
        self.pending.insert(0, request)
        self.evictions += 1

    def _rebase(self, request: Request) -> None:
        """Fold the request's committed tokens into its prompt so a
        fresh admission resumes generation where it left off.  The sum
        always fits: ``prompt + committed`` IS the host finish test's
        total, and a request at ``max_seq`` has already finished."""
        request.prompt_tokens = list(request.base_prompt) \
            + [int(token) for token in request.committed]
        request.rebased = len(request.committed)

    def recover(self) -> int:
        """Rebuild device state after a device-level failure (an XLA
        raise mid-block, a chaos ``decode_block`` kill): drop every
        in-flight block and chained carry, reset the cache and page
        pool, and re-queue each live request to resume from its LAST
        EMITTED token -- prompt + committed re-prefill and generation
        continues under the remaining budget; nothing already delivered
        is re-emitted.  Returns how many requests were revived."""
        revived = []
        for slot in range(self.max_slots):
            request, self.slots[slot] = self.slots[slot], None
            if request is None or request.done:
                continue
            self._rebase(request)
            request.slot = -1
            request.prefill_pos = 0
            revived.append(request)
        self.pending = revived + self.pending
        self._prefilling.clear()
        self._pending_first.clear()
        self._firsts = self._no_firsts()
        self._loop_inflight.clear()
        self._loop_chain = None
        self._force_inactive.clear()
        self.lengths[:] = 0
        self._lengths_upper[:] = 0
        self.current[:] = 0
        self.temperatures[:] = 0.0
        self.decoding[:] = False
        if self._pages is not None:
            self._pages.reset()
            self._page_rows[:] = 0
            self.cache = init_paged_cache(
                self.config, self.max_slots, self.max_seq,
                self.kv_page_tokens, self._pages.total)
        else:
            self.cache = self._family.init_cache(
                self.config, self.max_slots, self.max_seq)
        if self._cache_put is not None:
            self.cache = self._cache_put(self.cache)
        self.recoveries += 1
        return len(revived)

    def resume_request(self, request: Request, committed) -> bool:
        """Fold an externally journaled committed prefix into a
        just-submitted request (process-level adoption/migration,
        ISSUE 13): the same ``_rebase`` discipline page-pool
        preemption and ``recover()`` use, applied across a process
        boundary -- prompt + committed re-prefill, generation
        continues under the remaining budget, nothing already
        streamed is re-emitted (the caller pre-seeds its collector
        with the committed tokens instead).

        Returns False when the prefix already FINISHED the request
        (its last token is EOS, the budget is spent, or the sequence
        is at max_seq -- the process died between the final emit and
        delivery): the request is withdrawn, not resumed -- decoding
        past a finished prefix would append a spurious tail to text
        the contract promises byte-identical.  The caller completes
        from the committed tokens it already holds."""
        request.committed = [int(token) for token in committed]
        request.generated = len(request.committed)
        if request.generated:
            # ttft/tpot stamps would span the failover, not serving:
            # a resumed request reports no latency stats.
            request.submit_time = 0.0
        self._rebase(request)
        finished = bool(request.committed) and (
            request.committed[-1] in request.eos_tokens
            or request.generated >= request.max_new_tokens
            or len(request.prompt_tokens) >= self._seq_limit)
        if finished:
            request.done = True
            if request in self.pending:
                self.pending.remove(request)
        return not finished

    def export_state(self) -> list[dict]:
        """Committed state of every live (not finished) request --
        the drain/migration handoff record.  Each entry is enough for
        :meth:`import_state` on a peer to resume the request at its
        committed prefix."""
        entries = []
        live = [request for request in self.slots
                if request is not None] + list(self.pending)
        for request in live:
            if request.done:
                continue
            entries.append({
                "request_id": request.request_id,
                "prompt": [int(t) for t in request.base_prompt],
                "committed": [int(t) for t in request.committed],
                "max_new_tokens": int(request.max_new_tokens),
                "temperature": float(request.temperature),
                "eos_tokens": [int(t) for t in request.eos_tokens]})
        return entries

    def import_state(self, entries, emit_factory=None) -> int:
        """Resume exported requests at their committed prefix.
        ``emit_factory(entry) -> emit`` wires each request's token
        callback (None = no emission).  Returns how many were
        queued."""
        count = 0
        for entry in entries:
            request = Request(
                request_id=str(entry["request_id"]),
                prompt_tokens=list(entry["prompt"]),
                max_new_tokens=int(entry.get("max_new_tokens", 128)),
                temperature=float(entry.get("temperature", 0.0)),
                eos_tokens=tuple(entry.get("eos_tokens", ())))
            if emit_factory is not None:
                request.emit = emit_factory(entry)
            self.submit(request)
            self.resume_request(request, entry.get("committed", ()))
            count += 1
        return count

    @property
    def prefix_hits(self) -> int:
        """Prompt pages adopted from the shared-prefix index."""
        return self._pages.prefix_hits if self._pages is not None else 0

    @property
    def prefix_lookups(self) -> int:
        """Whole prompt pages the index was consulted for."""
        return self._pages.prefix_lookups \
            if self._pages is not None else 0

    def prefix_hit_rate(self) -> float:
        """Adopted fraction of looked-up prompt pages (0.0 when the
        cache is off or nothing was looked up)."""
        lookups = self.prefix_lookups
        return self.prefix_hits / lookups if lookups else 0.0

    def take_request_stats(self) -> list[dict]:
        """Drain per-request latency stamps ({"ttft_ms", "queue_ms",
        "admit_to_first_ms", "tpot_ms", "tokens"}) recorded at finish
        -- the serving element feeds them to the telemetry plane.
        ``queue_ms`` (submit to the first slot given) and
        ``admit_to_first_ms`` (from there to the first token seen by
        the host: prefill chunks, then the fetch of the block that
        carries the token) add up to ``ttft_ms``."""
        stats, self._request_stats = self._request_stats, []
        return stats

    def take_block_stats(self) -> list[dict]:
        """Drain the family's per-block observations (one dict a
        retired block, the family's ``loop_stats``; none for a family
        that counts nothing in its loop)."""
        stats, self._block_stats = self._block_stats, []
        return stats

    def _emit(self, request: Request, token: int):
        request.generated += 1
        self.tokens_emitted += 1
        now = time.perf_counter()
        if request.generated == 1:
            request.first_time = now
        request.committed.append(token)
        # Cache position of the token currently being generated is
        # len(prompt) + generated - 1; the last usable write position is
        # max_seq - 2 (max_seq - 1 is the trash row), so finish once the
        # sequence would need to write past it.  ``rebased`` backs out
        # tokens recover()/eviction folded into the prompt, so a
        # resumed request keeps the original arithmetic.
        total_len = len(request.prompt_tokens) + request.generated \
            - request.rebased
        finished = (token in request.eos_tokens
                    or request.generated >= request.max_new_tokens
                    or total_len >= self._seq_limit)
        if request.emit is not None:
            request.emit(request.request_id, token, finished)
        if finished:
            request.done = True
            if request.submit_time:
                ttft_ms = (request.first_time - request.submit_time) \
                    * 1000.0
                tpot_ms = (now - request.first_time) * 1000.0 \
                    / (request.generated - 1) \
                    if request.generated > 1 else 0.0
                self._request_stats.append(
                    {"ttft_ms": round(ttft_ms, 3),
                     "queue_ms": round((request.admit_time
                                        - request.submit_time) * 1000.0, 3),
                     "admit_to_first_ms": round(
                         (request.first_time - request.admit_time)
                         * 1000.0, 3),
                     "tpot_ms": round(tpot_ms, 3),
                     "tokens": request.generated,
                     "tenant": request.tenant,
                     "cls": request.qos_class})
            self._free_slot(request.slot)

    def _free_slot(self, slot: int):
        """Release a slot's host-side state (finish, cancel and
        eviction share this -- any new per-slot bookkeeping belongs
        here)."""
        self.slots[slot] = None
        self.lengths[slot] = 0
        self._lengths_upper[slot] = 0
        self.current[slot] = 0
        self.temperatures[slot] = 0.0
        self.decoding[slot] = False
        if self.device_loop:
            self._force_inactive.add(slot)
        if self._pages is not None:
            self._pages.release(slot)

    def cancel(self, request_id: str) -> bool:
        """Abandon a request by id: pending requests leave the queue; an
        admitted request frees its slot immediately, so it stops
        occupying a device batch row from the next dispatch on.  Tokens
        for it inside already-in-flight blocks are discarded at
        retire via the snapshot identity check -- the same overshoot
        semantics a finished request has.  ``emit`` is never called for
        a cancelled request.  Returns True when a request was found."""
        found = False
        for request in list(self.pending):
            if request.request_id == request_id:
                self.pending.remove(request)
                request.done = True
                found = True
        for slot, request in enumerate(self.slots):
            if request is None or request.request_id != request_id:
                continue
            request.done = True
            self._free_slot(slot)
            # A first-token sample parked for the next block dispatch
            # belongs to this slot's (now cancelled) occupant.
            self._pending_first.pop(slot, None)
            found = True
        return found

    # -- introspection -----------------------------------------------------

    @property
    def active_count(self) -> int:
        return sum(1 for r in self.slots if r is not None)

    @property
    def queue_depth(self) -> int:
        return len(self.pending)

    @property
    def blocks_in_flight(self) -> int:
        """Dispatched-but-unretired device-loop blocks; drive step()
        until this reaches 0 to drain them."""
        return len(self._loop_inflight)

    def run_until_drained(self, max_steps: int = 100_000) -> int:
        steps = 0
        while (self.pending or self.active_count
               or self._loop_inflight) and steps < max_steps:
            self.step()
            steps += 1
        return steps


# ---------------------------------------------------------------------------
# Cross-stream micro-batching for async pipeline elements.

class MicroBatcher:
    """Cross-stream micro-batching admission for async pipeline elements.

    Generalizes the Detector's parked-frame admission (r5) so ANY async
    element coalesces frames parked at its stage -- from every stream in
    the process -- into one batched device call.  It shares the
    ContinuousBatcher's admission discipline: frames submitted in one
    event-loop burst flush together (``schedule_flush`` defers to the
    engine's mailbox drain, so a lone frame pays no added latency),
    groups form per signature key (stacking float16 with float32 frames
    would silently promote; mixed shapes cannot stack at all), ragged
    groups pad to power-of-two compile buckets (:func:`pad_to_bucket`),
    and all device work runs on a single daemon worker thread -- the
    event loop never blocks on a dispatch, a fetch, or a first-use jit
    compile.

    The element supplies three callables:

    - ``run(context, key, payloads) -> result``: stack + dispatch ONE
      batched device call for a same-key group (worker thread; raising
      errors every frame of that group only);
    - ``finish(context, key, entries, result)``: fetch + complete each
      parked frame from its row (worker thread; ``entries`` is
      ``[(complete, payload), ...]`` in submission order);
    - ``context()``: model snapshot taken at flush time -- a queued
      batch must dispatch against the weights it was built with (or
      fail cleanly if their devices died), never a half-swapped model.

    The worker dispatches EVERY group of a flush before fetching any
    (device work pipelines across groups).  Submit/flush/stop run on
    the event loop; only the queue crosses threads.

    Scope note: a micro-batched
    element on a REPLICATED placed stage is not yet supported -- the
    replica hop lands each parked frame's inputs on ITS replica's
    submesh, and a cross-replica group would stack arrays from
    different device sets into one dispatch (XLA rejects the mix).
    Replicate synchronous stages; async elements already spread load
    through their own cross-stream batching.
    """

    def __init__(self, run: Callable, finish: Callable,
                 context: Callable, schedule_flush: Callable,
                 logger=None, name: str = "microbatch", recorder=None):
        self._run = run
        self._finish = finish
        self._context = context
        self._schedule_flush = schedule_flush
        self._logger = logger
        self.name = name
        # Flight recorder (anything with its ``record``), or None: the
        # worker stamps the two halves of ``_run_groups`` there.
        self._recorder = recorder
        self._pending: list[tuple] = []  # (rank, seq, key, payload, complete)
        self._flush_scheduled = False
        self._queue: queue.Queue | None = None
        # perf counters (tests assert dispatches < frames)
        self.submitted = 0
        self.dispatches = 0
        self.flushes = 0

    def submit(self, key, payload, complete, max_batch: int = 8,
               rank: int = 0):
        """Park one frame's work.  Flushes immediately at ``max_batch``
        pending, otherwise once the engine's mailboxes drain -- every
        frame of the burst joins the same batched dispatch.  ``rank``
        is the frame's QoS class rank (ISSUE 12): a flush dispatches
        best-ranked groups first, so an interactive frame's batch hits
        the device before a batch-class group parked in the same
        burst; all-equal ranks keep submission order exactly."""
        self._ensure_worker()
        self._pending.append((int(rank), self.submitted, key, payload,
                              complete))
        self.submitted += 1
        if len(self._pending) >= int(max_batch):
            self.flush()
        elif not self._flush_scheduled:
            self._flush_scheduled = True
            self._schedule_flush(self._flush_deferred)

    def _ensure_worker(self):
        if self._queue is None:
            self._queue = queue.Queue()
            threading.Thread(target=self._worker, args=(self._queue,),
                             daemon=True,
                             name=f"microbatch-{self.name}").start()

    def _flush_deferred(self):
        self._flush_scheduled = False
        self.flush()

    def flush(self):
        """Group pending frames by key (submission order preserved
        within a group) and hand the burst to the worker.  Groups
        dispatch in best-(rank, submission) order -- the QoS plane;
        with all-default ranks that IS first-submission order, the
        pre-QoS behavior."""
        pending, self._pending = self._pending, []
        if not pending:
            return
        if self._queue is None:             # stopped mid-burst
            for _, _, _, _, complete in pending:
                complete_error(complete, f"{self.name} stopped")
            return
        pending.sort(key=lambda entry: entry[:2])
        groups: dict = {}
        for _, _, key, payload, complete in pending:
            groups.setdefault(key, []).append((complete, payload))
        self.flushes += 1
        self.dispatches += len(groups)
        self._queue.put((self._context(), list(groups.items())))

    def stop(self):
        """Flush pending frames, then retire the worker (in-flight
        batches drain first).  A later submit lazily starts a fresh
        worker -- without this the thread would pin the element (and
        its device weights) forever."""
        self.flush()
        work, self._queue = self._queue, None
        if work is not None:
            work.put(None)                  # drain-then-exit sentinel

    # -- worker side -------------------------------------------------------

    def _worker(self, work: "queue.Queue"):
        while True:
            item = work.get()
            if item is None:
                return
            self._run_groups(*item)

    def _run_groups(self, context, groups):
        """Dispatch every group first, then fetch/complete each.  A
        failing dispatch errors every frame of ITS group -- anything
        not completed here would stay parked forever.  With a
        recorder the two halves are duration events: ``mb_run`` (stack,
        upload, program calls) and ``mb_finish`` (fetch, complete)."""
        recorder = self._recorder
        if recorder is not None:
            started = time.perf_counter()
            info = {"groups": len(groups),
                    "frames": sum(len(entries) for _, entries in groups)}
        dispatched = []
        for key, entries in groups:
            try:
                result = self._run(context, key,
                                   [payload for _, payload in entries])
            except Exception as error:
                if self._logger is not None:
                    self._logger.exception(
                        "%s: batched dispatch failed", self.name)
                for complete, _ in entries:
                    complete_error(complete,
                                   f"{self.name} dispatch: {error}")
                continue
            dispatched.append((key, entries, result))
        if recorder is not None:
            ran = time.perf_counter()
            recorder.record("mb_run", None, None, self.name,
                            (ran - started) * 1000.0, info)
        for key, entries, result in dispatched:
            try:
                self._finish(context, key, entries, result)
            except Exception as error:      # pragma: no cover - defensive
                if self._logger is not None:
                    self._logger.exception(
                        "%s: batch finish failed", self.name)
                for complete, _ in entries:
                    complete_error(complete, str(error))
        if recorder is not None:
            recorder.record("mb_finish", None, None, self.name,
                            (time.perf_counter() - ran) * 1000.0, info)


def complete_error(complete: Callable, diagnostic: str):
    """Error one parked frame (import-cycle-free StreamEvent access)."""
    from ..pipeline.stream import StreamEvent
    complete(StreamEvent.ERROR, {"diagnostic": diagnostic})


class MicroBatchElement:
    """Mixin holding the one copy of the element-side MicroBatcher glue
    (lazy creation against the engine's drain callback, key-failure
    error path, ``max_batch`` resolution on the event loop, stop/teardown)
    shared by the Detector, ImageResize, and AudioFFT.

    Subclasses implement ``batch_key(payload)`` (grouping signature,
    resolved on the event loop; raising errors ONLY that frame),
    ``batch_run(context, key, payloads)`` and
    ``batch_finish(context, key, entries, result)`` (worker thread),
    and optionally ``batch_context()`` (model snapshot at flush time).
    """

    _batcher: MicroBatcher | None = None

    def batch_context(self):
        return None

    def batch_key(self, payload):
        raise NotImplementedError

    def batch_run(self, context, key, payloads):
        raise NotImplementedError

    def batch_finish(self, context, key, entries, result):
        raise NotImplementedError

    def submit_microbatch(self, complete, payload,
                          diagnostic: str = "bad input"):
        if self._batcher is None:
            self._batcher = MicroBatcher(
                run=self.batch_run, finish=self.batch_finish,
                context=self.batch_context,
                schedule_flush=(self.pipeline.runtime.engine
                                .post_when_drained),
                logger=self.logger, name=self.name,
                recorder=getattr(self.pipeline, "recorder", None))
        max_batch, _ = self.get_parameter("max_batch", 8)
        try:
            key = self.batch_key(payload)
        except Exception as error:      # malformed frame: only ITS
            complete_error(complete,     # complete errors
                           f"{diagnostic}: {error}")
            return
        # Unified QoS admission (ISSUE 12): the parked frame's class
        # rank orders the flush, so the batcher honors the same
        # priority vocabulary as the stage credits.  Resolved on the
        # event loop where the current-stream context is intact.
        rank = 0
        qos = getattr(self.pipeline, "qos", None)
        if qos is not None:
            stream = self.pipeline.current_stream()
            if stream is not None:
                rank = qos.class_rank(getattr(stream, "qos_class",
                                              None))
        self._batcher.submit(key, payload, complete,
                             max_batch=int(max_batch), rank=rank)

    def stop_microbatcher(self):
        """Flush + retire (a later submit lazily starts a fresh one)."""
        batcher, self._batcher = self._batcher, None
        if batcher is not None:
            batcher.stop()

    def stop_stream(self, stream, stream_id):
        if self._batcher is not None:
            # The stopping stream's parked frames must not linger in a
            # half-collected burst.  The batcher itself is SHARED
            # across streams: retire the worker only when this was the
            # last live stream (the engine pops the stream before
            # stop_stream fires), so sibling streams keep their warm
            # worker and the cross-stream batching counters.
            self._batcher.flush()
            if not self.pipeline.streams:
                self.stop_microbatcher()
        return super().stop_stream(stream, stream_id)
