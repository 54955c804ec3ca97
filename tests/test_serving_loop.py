"""Device-resident LLM serving loop (ISSUE 8): decode_loop
equivalence against the host loop, paged KV cache invariants,
speculative multi-token decoding, and replay-from-last-emitted-block
recovery.

The equivalence contract: at temperature 0 the device loop emits
TOKEN-IDENTICAL streams to the host loop for the same prompts -- the
loop's on-device stop detection mirrors the host finish test exactly
and may only run LONGER (overshoot is truncated at retire).  Plain and
paged loops share the host loop's decode math bit-for-bit, so bf16 is
exact there; the speculative verify step attends through a different
(concat) path whose bf16 argmax can flip on near-ties, so the
speculation contract is pinned in float32 where the math is exact.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama, ContinuousBatcher, Request
from aiko_services_tpu.models.paged import (PageAllocator, gather_slot,
                                            init_paged_cache,
                                            pages_per_slot)
from aiko_services_tpu.models.tokenizer import ByteTokenizer
from aiko_services_tpu.pipeline.overlap import TransferLedger
from conftest import all_eqns


@pytest.fixture(scope="module")
def tiny():
    config = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


@pytest.fixture(scope="module")
def tiny_f32():
    config = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


def _run(params, config, n_requests=6, max_new=9, max_steps=800,
         prompts=None, **kw):
    """Drain ``n_requests`` greedy requests through one batcher ->
    ({request_id: [tokens]}, batcher)."""
    tok = ByteTokenizer()
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, **kw)
    for i in range(n_requests):
        text = prompts[i] if prompts else f"hello world {i}"
        batcher.submit(Request(request_id=f"r{i}",
                               prompt_tokens=tok.encode(text),
                               max_new_tokens=max_new, emit=emit))
    steps = batcher.run_until_drained(max_steps=max_steps)
    assert steps < max_steps
    return emitted, batcher


# -- equivalence: device loop == host loop at temperature 0 ----------------


def test_device_loop_matches_host_loop(tiny):
    """ISSUE 8 acceptance: the lax.while_loop serving path is
    token-identical to the per-token host loop (bf16: same decode
    math, same argmax)."""
    config, params = tiny
    host, _ = _run(params, config)
    loop, batcher = _run(params, config, decode_block_tokens=8)
    assert host == loop
    assert batcher.blocks_dispatched >= 1
    assert batcher.blocks_retired == batcher.blocks_dispatched
    # The loop batches up to ring tokens PER SLOT per dispatch: far
    # fewer host round trips than tokens emitted.
    assert batcher.blocks_retired < batcher.tokens_emitted / 4


def test_device_loop_paged_matches_host_loop(tiny):
    """Page-table gather/scatter equals the dense cache path
    token-for-token (the paged half of the equivalence criterion)."""
    config, params = tiny
    host, _ = _run(params, config)
    paged, batcher = _run(params, config, decode_block_tokens=8,
                          kv_page_tokens=16)
    assert host == paged
    assert batcher._pages is not None


def test_device_loop_int8_kv_matches_host_loop(tiny):
    """int8 KV (per-token scales) through the device loop and the
    paged pool equals the host loop's int8 path token-for-token."""
    config, params = tiny
    config8 = dataclasses.replace(config, kv_dtype="int8")
    host, _ = _run(params, config8)
    loop, _ = _run(params, config8, decode_block_tokens=8)
    assert host == loop
    paged, _ = _run(params, config8, decode_block_tokens=8,
                    kv_page_tokens=16)
    assert host == paged


def test_device_loop_chains_blocks_inflight(tiny):
    """inflight > 1 keeps several loop blocks chained device-side;
    retire order preserves the emitted stream exactly."""
    config, params = tiny
    host, _ = _run(params, config, max_new=17)
    loop, batcher = _run(params, config, max_new=17,
                         decode_block_tokens=4, inflight=3)
    assert host == loop
    assert batcher.blocks_retired >= 4


def test_device_loop_respects_eos(tiny):
    """On-device EOS detection stops a row exactly where the host
    finish test does, including an EOS landing on the FIRST token."""
    config, params = tiny
    tok = ByteTokenizer()

    def run(eos, **kw):
        emitted = {}

        def emit(request_id, token, finished):
            emitted.setdefault(request_id, []).append((token, finished))

        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    max_seq=64, prefill_chunk=16, **kw)
        for i in range(3):
            batcher.submit(Request(
                request_id=f"r{i}", prompt_tokens=tok.encode(f"eos {i}"),
                max_new_tokens=12, eos_tokens=eos, emit=emit))
        assert batcher.run_until_drained(max_steps=800) < 800
        return emitted

    reference = run(())
    # Pick each stream's 3rd token as its stop set: the device loop
    # must cut exactly there, finished flag on the stop token.
    eos = tuple({tokens[2][0] for tokens in reference.values()})
    host = run(eos)
    loop = run(eos, decode_block_tokens=8)
    assert host == loop
    for tokens in loop.values():
        assert tokens[-1][1] is True
        assert len(tokens) <= 12


# -- the fold-in: one program whatever the joiners (ISSUE 32) --------------

FOLD_SLOTS = 8


def _fold_batcher(params, config, emitted, phases=None, **kw):
    """Eight slots, chunks of 32 over pages of 16: a short prompt's
    admission takes both pages its whole generation needs, so no fold
    of these tests finds the page table changed."""
    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    trace = None if phases is None else \
        lambda name, ms, info: phases.append((name, info))
    batcher = ContinuousBatcher(params, config, max_slots=FOLD_SLOTS,
                                max_seq=64, prefill_chunk=32,
                                trace=trace, **kw)
    return batcher, emit


@pytest.mark.parametrize("paged", [0, 16], ids=["dense", "paged"])
@pytest.mark.parametrize("joiners", [0, 1, 2, 5, FOLD_SLOTS])
def test_fold_is_two_launches_whatever_the_joiners(tiny, joiners, paged):
    """``joiners`` admissions complete in one tick and fold into one
    block: the streams are the per-token tick's, the fold phase makes
    the same two device calls (one packed upload, one program) for
    every count, and once a first round has run, a second builds no
    program -- there is none per count of joiners."""
    from aiko_services_tpu.observability.recorder import FlightRecorder
    config, params = tiny
    tok = ByteTokenizer()
    count = max(joiners, 1)     # 0: the second tick of a lone joiner

    def submit(batcher, emit, tag):
        for i in range(count):
            batcher.submit(Request(
                request_id=f"{tag}{i}", prompt_tokens=tok.encode(f"fold {i}"),
                max_new_tokens=12, emit=emit))

    host = {}
    batcher, emit = _fold_batcher(params, config, host)
    submit(batcher, emit, "a")
    assert batcher.run_until_drained(max_steps=800) < 800

    loop, phases = {}, []
    ring = FlightRecorder(capacity=4096)
    batcher, emit = _fold_batcher(params, config, loop, phases,
                                  decode_block_tokens=4,
                                  kv_page_tokens=paged)

    def builds():
        return [event[4] for event in ring.snapshot()
                if event[1] == "build"]

    for tag in "ab":
        seen, built = len(phases), builds()
        submit(batcher, emit, tag)
        for _ in range(1 if joiners else 2):
            batcher.step()
        measured = [info for name, info in phases[seen:] if name == "fold"]
        assert batcher.run_until_drained(max_steps=800) < 800
    assert builds() == built                # the second round built none
    assert joiners in [info["joining"] for info in measured]
    assert measured[0]["joining"] == count
    assert [info["launches"] for info in measured] == [2] * len(measured)
    chained = [info["launches"] for name, info in phases
               if name == "fold"][1:]       # the first made the carries
    assert set(chained) <= {2, 3}           # 3: the page table went up too
    for tag in "ab":
        for i in range(count):
            assert loop[f"{tag}{i}"] == host[f"a{i}"]


def test_fold_joiner_that_finishes_on_its_first_token(tiny):
    """A joiner whose first token is a stop token, one with
    ``max_new_tokens`` 1 and one whose prompt ends a position short of
    ``max_seq`` emit that one token and leave the fold inactive on the
    device; the joiner beside them decodes on."""
    config, params = tiny
    tok = ByteTokenizer()
    prompts = {"stop": tok.encode("stops at once"),
               "one": tok.encode("one token"),
               "edge": [1 + i % 200 for i in range(63)],
               "goes": tok.encode("goes on")}

    def run(stop=(), **kw):
        emitted = {}
        batcher, emit = _fold_batcher(params, config, emitted, **kw)
        requests = {
            name: Request(request_id=name, prompt_tokens=list(prompt),
                          max_new_tokens=1 if name == "one" else 6,
                          eos_tokens=stop if name == "stop" else (),
                          emit=emit)
            for name, prompt in prompts.items()}
        for request in requests.values():
            batcher.submit(request)
        return emitted, batcher, requests

    free, batcher, _ = run()
    assert batcher.run_until_drained(max_steps=800) < 800
    stop = (free["stop"][0],)
    host, batcher, _ = run(stop)
    assert batcher.run_until_drained(max_steps=800) < 800
    loop, batcher, requests = run(stop, decode_block_tokens=4, inflight=1)
    inactive_at_join = {}

    def tap(name, ms, info):
        """After each block's enqueue: the carries it hands on."""
        if name == "dispatch" and info["joining"]:
            active = np.asarray(batcher._loop_chain["active"])
            for slot, request in batcher._loop_inflight[-1].firsts_meta:
                inactive_at_join[request.request_id] = not active[slot]

    batcher.trace = tap
    assert batcher.run_until_drained(max_steps=800) < 800
    assert loop == host
    assert inactive_at_join == {"stop": True, "one": True, "edge": True,
                                "goes": False}
    assert [len(loop[name]) for name in ("stop", "one", "edge")] == [1, 1, 1]
    assert len(loop["goes"]) == 6
    assert all(request.done for request in requests.values())


def test_fold_freed_and_rejoined_slot_decodes_the_new_occupant(tiny):
    """One slot: its occupant is cancelled mid-generation and the next
    request admitted into it within the same tick, so the fold sees the
    slot both freed and joining.  The joiner wins: its stream is its
    own per-token stream, and the cancelled request emits nothing
    more."""
    config, params = tiny
    tok = ByteTokenizer()

    def run(**kw):
        emitted = {}

        def emit(request_id, token, finished):
            emitted.setdefault(request_id, []).append(token)

        batcher = ContinuousBatcher(params, config, max_slots=1,
                                    max_seq=64, prefill_chunk=16, **kw)
        old = Request(request_id="old", prompt_tokens=tok.encode("leaves"),
                      max_new_tokens=40, emit=emit)
        new = Request(request_id="new", prompt_tokens=tok.encode("arrives"),
                      max_new_tokens=9, emit=emit)
        return emitted, batcher, old, new

    host, batcher, _, new = run()
    batcher.submit(new)
    assert batcher.run_until_drained(max_steps=800) < 800

    loop, batcher, old, new = run(decode_block_tokens=4, inflight=2)
    batcher.submit(old)
    batcher.step()
    batcher.submit(new)             # waits for the slot: blocks run ahead
    for _ in range(2):
        batcher.step()
    assert batcher.blocks_in_flight and not old.done
    before = list(loop["old"])
    assert batcher.cancel("old")
    assert batcher._force_inactive == {0}
    batcher.step()      # re-admitted behind the block still in flight
    assert new.slot == 0 and 0 in batcher._pending_first
    assert batcher._force_inactive == {0}
    batcher.step()                  # freed and joining: one fold
    assert not batcher._force_inactive and not batcher._pending_first
    assert batcher.run_until_drained(max_steps=800) < 800
    assert loop["old"] == before
    assert loop["new"] == host["new"]


def test_blocks_run_ahead_only_while_requests_wait(tiny):
    """``inflight`` blocks are kept queued on the device while a request
    waits for a slot; with none waiting the next block is enqueued when
    the last has retired, so whatever else was enqueued meanwhile (a
    joiner's prefill, another element's program) runs before it.  The
    streams are the per-token tick's either way."""
    config, params = tiny
    host, _ = _run(params, config, n_requests=6, max_new=17)
    depths = []

    def tap(name, ms, info):
        if name == "dispatch":
            depths.append((bool(batcher.pending), batcher.blocks_in_flight))

    tok = ByteTokenizer()
    loop = {}
    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=3, trace=tap)
    for i in range(6):
        batcher.submit(Request(
            request_id=f"r{i}", prompt_tokens=tok.encode(f"hello world {i}"),
            max_new_tokens=17,
            emit=lambda rid, token, done: loop.setdefault(rid, [])
            .append(token)))
    assert batcher.run_until_drained(max_steps=800) < 800
    assert loop == host
    assert max(depth for waiting, depth in depths if waiting) == 3
    assert {depth for waiting, depth in depths if not waiting} == {1}
    assert any(not waiting for waiting, _ in depths)


def test_device_page_table_follows_the_allocator(tiny_f32):
    """After every tick the device page table holds the allocator's
    rows (but those still waiting for the next upload): through
    admission, decode crossing a page, release at finish and the
    preemption of a joining slot under pool pressure (the regression
    scenario above)."""
    config, params = tiny_f32
    tok = ByteTokenizer()
    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=8,
                                kv_page_tokens=16, kv_pages=6)
    for i in range(4):
        batcher.submit(Request(request_id=f"r{i}",
                               prompt_tokens=tok.encode(f"hello world {i}"),
                               max_new_tokens=12))
    pages, held = batcher._pages, set()
    for _ in range(3000):
        if not (batcher.pending or batcher.active_count
                or batcher.blocks_in_flight):
            break
        batcher.step()
        table = np.asarray(batcher.cache["page_table"])
        assert table.shape == (4, pages.pps) and table.dtype == np.int32
        for slot in range(4):
            if slot not in pages.dirty:
                assert list(table[slot]) == pages._row(slot)
            held.add(pages.holds(slot))
    assert not (batcher.pending or batcher.active_count)
    assert batcher.evictions >= 1
    assert held >= {0, 1, 2}        # empty, admitted, grown across a page
    batcher._sync_page_table()
    assert not np.asarray(batcher.cache["page_table"]).any()
    assert not batcher._page_rows.any()


# -- speculative decoding --------------------------------------------------


@pytest.mark.parametrize("mode", ["ngram", "draft"])
@pytest.mark.parametrize("paged", [0, 16])
def test_speculative_matches_host_loop_f32(tiny_f32, mode, paged):
    """Lossless speculation: greedy rows accept only verified-matching
    drafts, so the emitted stream is token-identical to the host loop
    (float32: the verify chunk's concat attention is exact there)."""
    config, params = tiny_f32
    host, _ = _run(params, config)
    spec, batcher = _run(params, config, decode_block_tokens=8,
                         speculative=mode, kv_page_tokens=paged)
    assert host == spec
    assert batcher.draft_tokens > 0


def test_draft_speculation_accepts_tokens(tiny_f32):
    """The int8 self-draft agrees with its own target often enough to
    accept a useful fraction (the speculation win exists at all)."""
    config, params = tiny_f32
    _, batcher = _run(params, config, decode_block_tokens=8,
                      speculative="draft")
    assert batcher.accepted_tokens > 0
    assert batcher.accepted_tokens <= batcher.draft_tokens


def test_speculative_requires_device_loop(tiny):
    config, params = tiny
    with pytest.raises(ValueError, match="device loop"):
        ContinuousBatcher(params, config, speculative="ngram")
    with pytest.raises(ValueError, match="off|ngram|draft"):
        ContinuousBatcher(params, config, decode_block_tokens=8,
                          speculative="banana")
    # A ring too small for one worst-case speculative emission would
    # dispatch blocks that run zero loop iterations (a silent
    # no-progress wedge): refused at construction.
    with pytest.raises(ValueError, match="speculative emission"):
        ContinuousBatcher(params, config, decode_block_tokens=4,
                          speculative="ngram", spec_tokens=4)


@pytest.mark.parametrize("settings,probed", [
    ({}, False),                                    # no device loop
    ({"decode_block_tokens": 8, "spec_autoprobe": "off"}, False),
    ({"decode_block_tokens": 4, "spec_tokens": 4}, False),   # ring < k+1
    ({"decode_block_tokens": 8}, True),
], ids=["host-loop", "probe-off", "ring-too-small", "probed"])
def test_speculative_auto_resolves_at_build(tiny_f32, settings, probed):
    """``speculative: auto`` never raises and never stays ``auto``: a
    configuration explicit ``draft`` would refuse resolves to ``off``
    unprobed; otherwise the startup probe measures draft against plain
    decode and commits to one of them, and the batcher then serves the
    host loop's streams either way (speculation is lossless)."""
    config, params = tiny_f32
    host, _ = _run(params, config)
    auto, batcher = _run(params, config, speculative="auto", **settings)
    assert auto == host
    assert batcher.speculative in (("draft", "off") if probed
                                   else ("off",))
    assert (batcher.spec_probe_ratio > 0.0) == probed
    assert (batcher.draft_tokens > 0) == (batcher.speculative == "draft")


# -- paged KV cache invariants ---------------------------------------------


def test_page_allocator_units():
    alloc = PageAllocator(total_pages=9, pages_per_slot=4, max_slots=3)
    assert alloc.free_pages == 8                 # page 0 is trash
    assert alloc.pages_for(0, 16) == 0
    assert alloc.pages_for(1, 16) == 1
    assert alloc.pages_for(17, 16) == 2
    assert alloc.pages_for(10_000, 16) == 4      # clamped to pps
    assert alloc.ensure(0, 2) and alloc.holds(0) == 2
    assert alloc.dirty[0][:2] != [0, 0]
    assert alloc.ensure(0, 2)                    # idempotent
    assert alloc.missing(0, 4) == 2
    assert alloc.ensure(1, 4) and alloc.ensure(2, 2)
    assert alloc.free_pages == 0
    # Atomic failure: nothing allocated, nothing dirtied.
    alloc.dirty.clear()
    assert not alloc.ensure(0, 4)
    assert alloc.holds(0) == 2 and not alloc.dirty
    assert alloc.release(1) == 4
    assert alloc.free_pages == 4
    assert alloc.dirty[1] == [0] * 4             # row reset to trash
    assert alloc.ensure(0, 4)
    alloc.reset()
    assert alloc.free_pages == 8 and alloc.holds(0) == 0


def test_paged_prefill_matches_dense(tiny_f32):
    """prefill_into_slot through a page table produces the same logits
    AND the same cache bytes (gathered) as the dense path."""
    config, params = tiny_f32
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0,
                                config.vocab_size)
    dense = llama.init_cache(config, 2, 32)
    logits_d, dense = llama.prefill_into_slot(
        params, config, tokens, dense, slot=1,
        start=jnp.int32(0))
    paged = init_paged_cache(config, 2, 32, page_tokens=8)
    table = paged["page_table"].at[1].set(jnp.arange(1, 5))
    paged["page_table"] = table
    logits_p, paged = llama.prefill_into_slot(
        params, config, tokens, paged, slot=1,
        start=jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(logits_d),
                                  np.asarray(logits_p))
    # gather_slot works on one layer's pool view; compare each layer's
    # gathered row to the dense row.
    for layer in range(config.n_layers):
        row_d = np.asarray(dense["k"][layer, 1])           # [T, K*hd]
        row_p = np.asarray(gather_slot(paged["k"][layer],
                                       paged["page_table"][1])[0])
        np.testing.assert_array_equal(row_d[:16], row_p[:16])


def _noise(array, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), array.shape,
                             dtype=array.dtype)


# Chunk starts of one 40-token admission at prefill_chunk 16, pages of
# 8, max_seq 40 (ContinuousBatcher._admission_chunk): the third chunk
# clamps to 40 - 16 = 24 and rewrites positions 24..31.
@pytest.mark.parametrize("starts", [(0,), (0, 16), (0, 16, 24)],
                         ids=["first", "second", "clamped"])
def test_paged_admission_writes_only_its_pages(tiny_f32, starts):
    """The post-scan page write (ISSUE 27) against the one prefill
    that still writes in-scan, ``llama.prefill`` on a dense cache --
    the ``xs``/``ys`` discipline admission shared before: (a) logits
    equal the dense admission path's, (b) the slot's pages hold exactly
    the bytes the in-scan write lays down, (c) every page the slot's
    table row does not name -- other slots' pages, the free pages, the
    trash page -- is byte-identical before and after."""
    config, params = tiny_f32
    extent, chunk, page = 40, 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, extent), 0,
                                config.vocab_size)
    slot, mine = 1, [5, 9, 2, 14, 11]
    paged = init_paged_cache(config, 3, extent, page_tokens=page,
                             total_pages=17)
    paged["k"], paged["v"] = _noise(paged["k"], 1), _noise(paged["v"], 2)
    paged["page_table"] = paged["page_table"] \
        .at[0].set(jnp.asarray([1, 3, 4, 6, 7])) \
        .at[slot].set(jnp.asarray(mine))
    before = {side: np.asarray(paged[side]) for side in ("k", "v")}
    dense = llama.init_cache(config, 3, extent)
    in_scan = llama.init_cache(config, 1, extent)
    for start in starts:
        piece = tokens[:, start:start + chunk]
        logits_p, paged = llama.prefill_into_slot(
            params, config, piece, paged, jnp.int32(slot),
            jnp.int32(start))
        logits_d, dense = llama.prefill_into_slot(
            params, config, piece, dense, jnp.int32(slot),
            jnp.int32(start))
        logits_x, in_scan = llama.prefill(
            params, config, piece, in_scan, jnp.asarray([start]))
        np.testing.assert_array_equal(np.asarray(logits_p),
                                      np.asarray(logits_d))
        np.testing.assert_array_equal(np.asarray(logits_p),
                                      np.asarray(logits_x))
    written = starts[-1] + chunk
    others = [p for p in range(17) if p not in mine[:written // page]]
    for side in ("k", "v"):
        pool = np.asarray(paged[side])                 # [L, P, pt, C]
        row = pool[:, mine].reshape(config.n_layers, extent, -1)
        np.testing.assert_array_equal(
            row[:, :written], np.asarray(in_scan[side])[:, 0, :written])
        np.testing.assert_array_equal(
            row[:, :written], np.asarray(dense[side])[:, slot, :written])
        np.testing.assert_array_equal(pool[:, others],
                                      before[side][:, others])


def _scans(jaxpr, length):
    """Every ``scan`` of ``length`` steps in a jaxpr, nested ones too."""
    return [eqn for eqn in all_eqns(jaxpr)
            if eqn.primitive.name == "scan"
            and eqn.params["length"] == length]


@pytest.mark.parametrize("program", ["prefill_into_slot",
                                     "prefill_into_slots"])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_admission_keeps_the_cache_out_of_the_layer_scan(tiny_f32, paged,
                                                         program):
    """Structural guard (ISSUE 27): in the admission programs the KV
    cache is never a scanned input or a stacked output of the layer
    scan -- the pool may appear only closed over (a constant of the
    scan) or carried.  As ``xs``/``ys`` every chunk sliced each layer
    out of the pool and restacked a fresh one: 53 of a chunk's 71 ms on
    the v5e beside a 5.2 GB pool (PERF.md, PR 27).  The compiled
    program's temporaries stay well under one side of a cache sized far
    above the chunk (8 MB a side here; the chunk's own are ~1 MB)."""
    config, params = tiny_f32  # the CPU backend widens bf16 updates
    chunk, page, extent = 16, 8, 64
    cache = init_paged_cache(config, 8, extent, page_tokens=page,
                             total_pages=8193) if paged \
        else llama.init_cache(config, 1031, extent)
    layer_shape = llama.cache_array(cache).shape[1:]
    if program == "prefill_into_slot":
        args = (jnp.zeros((1, chunk), jnp.int32), cache, jnp.int32(1),
                jnp.int32(16))
    else:
        args = (jnp.zeros((4, chunk), jnp.int32), cache,
                jnp.asarray([1, 2, 3, 1]), jnp.asarray([16, 0, 8, 16]))
    jitted = getattr(llama, f"_{program}_jit")
    closed = jax.make_jaxpr(jitted, static_argnums=1)(
        params, config, *args)
    (scan,) = list(_scans(closed.jaxpr, config.n_layers))
    consts, carry = scan.params["num_consts"], scan.params["num_carry"]
    scanned = [v.aval.shape[1:] for v in scan.invars[consts + carry:]]
    stacked = [v.aval.shape[1:] for v in scan.outvars[carry:]]
    assert layer_shape not in scanned, scanned
    assert layer_shape not in stacked, stacked
    assert tuple(llama.cache_array(cache).shape) in \
        [v.aval.shape for v in scan.invars[:consts + carry]]
    stats = jitted.lower(params, config, *args).compile() \
        .memory_analysis()
    if stats is not None:
        side_bytes = llama.cache_array(cache).nbytes
        assert stats.temp_size_in_bytes < side_bytes // 2, stats


def test_pool_pressure_preempts_youngest_and_resumes(tiny_f32):
    """An under-provisioned pool preempts the YOUNGEST slot; its
    generation resumes from committed tokens and every request still
    emits the exact host-loop stream (nothing dropped or re-emitted)."""
    config, params = tiny_f32
    host, _ = _run(params, config, n_requests=4, max_new=24)
    # Each request wants ~3 pages (prompt + 24 new tokens); 4 slots
    # want 12, the pool holds 8 usable -- guaranteed preemption churn.
    pressed, batcher = _run(params, config, n_requests=4, max_new=24,
                            max_steps=3000, decode_block_tokens=4,
                            kv_page_tokens=16, kv_pages=9)
    assert host == pressed
    assert batcher.evictions >= 1
    assert batcher._pages.free_pages >= 0


def test_admit_evict_keeps_untouched_slot_bytes_identical(tiny_f32):
    """Mid-generation admissions and pool-pressure evictions of OTHER
    slots never touch a live slot's cache bytes (the page-table
    isolation invariant)."""
    config, params = tiny_f32
    tok = ByteTokenizer()
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    batcher = ContinuousBatcher(params, config, max_slots=3, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=1, kv_page_tokens=16,
                                kv_pages=7)
    batcher.submit(Request(request_id="r0",
                           prompt_tokens=tok.encode("long runner"),
                           max_new_tokens=40, emit=emit))
    while len(emitted.get("r0", ())) < 6:
        batcher.step()
    assert batcher.blocks_in_flight == 0         # inflight=1 quiesces
    slot = batcher.slots.index(
        next(r for r in batcher.slots if r is not None))
    valid = int(batcher.lengths[slot])

    def snapshot():
        table_row = batcher.cache["page_table"][slot]
        k = np.stack([np.asarray(gather_slot(batcher.cache["k"][layer],
                                             table_row)[0])[:valid]
                      for layer in range(config.n_layers)])
        v = np.stack([np.asarray(gather_slot(batcher.cache["v"][layer],
                                             table_row)[0])[:valid]
                      for layer in range(config.n_layers)])
        return k, v

    before = snapshot()
    # Two more long requests under a ~2-slot pool: admissions write
    # neighboring pages and pressure preempts the youngest.
    for i in (1, 2):
        batcher.submit(Request(
            request_id=f"r{i}", prompt_tokens=tok.encode(f"rival {i}"),
            max_new_tokens=24, emit=emit))
    for _ in range(5):
        batcher.step()
    assert batcher.slots[slot] is not None       # r0 was never evicted
    assert batcher.slots[slot].request_id == "r0"
    # The churn was real: another request occupies a slot (or was
    # already preempted for pages).
    assert batcher.evictions or any(
        r is not None and r.request_id != "r0" for r in batcher.slots)
    after = snapshot()
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])
    assert batcher.run_until_drained(max_steps=3000) < 3000
    assert all(len(tokens) in (40, 24) for tokens in emitted.values())


def test_pressure_eviction_of_joining_slot_during_dispatch(tiny_f32):
    """Regression: the dispatch's page-ensure loop can preempt a
    JUST-ADMITTED slot (the youngest occupant) for pages -- the fold-in
    must re-snapshot the joining list instead of popping the evicted
    slot's _pending_first entry (KeyError before the fix).  All
    requests still emit the exact host-loop streams."""
    config, params = tiny_f32
    host, _ = _run(params, config, n_requests=4, max_new=12)
    # 5 usable pages: four one-page admissions burst in together, then
    # the dispatch ensure (2 pages per slot) must evict a joining slot.
    pressed, batcher = _run(params, config, n_requests=4, max_new=12,
                            max_steps=3000, decode_block_tokens=8,
                            kv_page_tokens=16, kv_pages=6)
    assert host == pressed
    assert batcher.evictions >= 1


def test_pressure_eviction_during_batched_admission(tiny_f32):
    """Regression: a multi-chunk admission burst under pool pressure
    can preempt a slot that is itself admitting (still in the prefill
    queue or already collected into the batched dispatch) -- the tick
    must drop evicted slots instead of crashing (IndexError /
    AttributeError before the fix), and every request still emits the
    exact host-loop stream."""
    config, params = tiny_f32
    prompts = ["abcdefghijklmnopqrstuvwx" + str(i) for i in range(4)]
    host, _ = _run(params, config, n_requests=4, max_new=8,
                   prompts=prompts)
    pressed, batcher = _run(params, config, n_requests=4, max_new=8,
                            max_steps=3000, prompts=prompts,
                            decode_block_tokens=8, kv_page_tokens=16,
                            kv_pages=5)
    assert host == pressed
    assert batcher.evictions >= 1


def test_pressure_eviction_during_sync_decode_tick(tiny_f32):
    """Regression: the synchronous decode path (decode_block == 1,
    paged) crossing a page boundary can preempt the OTHER decoding
    slot -- the tick must refresh its slot list instead of emitting
    into the evicted slot's None request (AttributeError before the
    fix)."""
    config, params = tiny_f32
    prompts = ["page walker", "page rival"]
    host, _ = _run(params, config, n_requests=2, max_new=24,
                   prompts=prompts)
    pressed, batcher = _run(params, config, n_requests=2, max_new=24,
                            max_steps=3000, prompts=prompts,
                            kv_page_tokens=16, kv_pages=5)
    assert host == pressed
    assert batcher.evictions >= 1


# -- recovery: replay from the last emitted block --------------------------


def test_recover_resumes_from_last_emitted_block(tiny):
    """A device loss mid-generation (fault probe raising at dispatch,
    standing in for a dying chip's XLA error): recover() re-queues
    every live request at its committed prefix, and the drained stream
    is token-identical to an unfaulted run -- nothing lost, nothing
    re-emitted."""
    config, params = tiny
    host, _ = _run(params, config, max_new=13)

    tok = ByteTokenizer()
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    fired = {"n": 0}

    def probe(point):
        assert point == "decode_block"
        fired["n"] += 1
        if fired["n"] == 3:                      # blocks already retired
            raise RuntimeError("injected chip death")

    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=1, fault_probe=probe)
    for i in range(6):
        batcher.submit(Request(
            request_id=f"r{i}",
            prompt_tokens=tok.encode(f"hello world {i}"),
            max_new_tokens=13, emit=emit))
    steps = 0
    while (batcher.pending or batcher.active_count
           or batcher.blocks_in_flight) and steps < 2000:
        try:
            batcher.step()
        except RuntimeError:
            revived = batcher.recover()
            assert revived >= 1
        steps += 1
    assert steps < 2000
    assert emitted == host
    assert batcher.recoveries == 1
    assert fired["n"] > 3                        # generation continued


def test_recover_paged_speculative(tiny_f32):
    """recover() rebuilds the page pool and speculation state too."""
    config, params = tiny_f32
    host, _ = _run(params, config, max_new=11)

    tok = ByteTokenizer()
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    boom = {"armed": False}

    def probe(point):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected chip death")

    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=8,
                                inflight=1, speculative="ngram",
                                kv_page_tokens=16, fault_probe=probe)
    for i in range(6):
        batcher.submit(Request(
            request_id=f"r{i}",
            prompt_tokens=tok.encode(f"hello world {i}"),
            max_new_tokens=11, emit=emit))
    steps = 0
    while (batcher.pending or batcher.active_count
           or batcher.blocks_in_flight) and steps < 2000:
        if steps == 1:
            # The first wave is mid-generation here: each request has
            # its first block (<= 9 of 11 tokens) and none is done.  (At
            # step 6, where this used to arm, speculation has drained
            # all six and the probe never fires.)
            boom["armed"] = True
        try:
            batcher.step()
        except RuntimeError:
            assert any(emitted.values()) and batcher.recover() >= 1
        steps += 1
    assert steps < 2000
    assert emitted == host
    assert batcher.recoveries == 1


# -- shared-prefix KV: COW page sharing (ISSUE 18) -------------------------


_SHARED_PREFIX = [3 + (i % 40) for i in range(32)]     # 2 whole pages


def _drive_prefix(params, config, prompts, max_new=8,
                  serial_first=False, **kw):
    """Drain token-list prompts through one batcher ->
    ({request_id: [tokens]}, batcher).  ``serial_first`` drains the
    first request alone (priming the prefix index) before the rest."""
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    defaults = dict(max_slots=4, max_seq=64, prefill_chunk=16,
                    decode_block_tokens=8, kv_page_tokens=16,
                    prefix_cache=True, prefix_min_tokens=16)
    defaults.update(kw)
    batcher = ContinuousBatcher(params, config, **defaults)
    for i, prompt in enumerate(prompts):
        batcher.submit(Request(request_id=f"r{i}",
                               prompt_tokens=list(prompt),
                               max_new_tokens=max_new, emit=emit))
        if serial_first and i == 0:
            assert batcher.run_until_drained(max_steps=3000) < 3000
    assert batcher.run_until_drained(max_steps=3000) < 3000
    return emitted, batcher


def test_prefix_cache_warm_matches_cold(tiny_f32):
    """The tentpole equivalence contract: a request admitted onto
    SHARED prefix pages (prefill skipped for the whole shared span)
    emits the exact token stream of an unshared cold prefill, the
    index serves the warm request (hits recorded), and no page leaks."""
    config, params = tiny_f32
    prompts = [_SHARED_PREFIX + [100 + i, 50 + i, 7, 11 + i, 2, 9, 4, 1]
               for i in range(3)]
    cold, cold_b = _drive_prefix(params, config, prompts,
                                 serial_first=True, prefix_cache=False)
    warm, warm_b = _drive_prefix(params, config, prompts,
                                 serial_first=True)
    assert cold == warm
    # r0 primes the index; r1/r2 adopt both shared pages each.
    assert warm_b.prefix_hits >= 4
    assert warm_b.prefix_shared_tokens >= 64
    assert warm_b.prefix_hit_rate() > 0.0
    assert cold_b.prefix_hits == 0            # off = no index traffic
    assert warm_b._pages.leaked_pages() == 0
    assert cold_b._pages.leaked_pages() == 0


def test_prefix_divergence_cow_leaves_donor_untouched(tiny_f32):
    """COW at the divergence point: the adopter maps the donor's
    shared pages PHYSICALLY (same table entries), allocates a fresh
    page where the prompts diverge, and the donor's cache bytes over
    the shared span stay bit-identical while both keep generating."""
    config, params = tiny_f32
    pA = _SHARED_PREFIX + [100 + i for i in range(8)]
    pB = _SHARED_PREFIX + [70 + i for i in range(8)]
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    batcher = ContinuousBatcher(params, config, max_slots=3, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=1, kv_page_tokens=16,
                                prefix_cache=True, prefix_min_tokens=16)
    batcher.submit(Request(request_id="A", prompt_tokens=list(pA),
                           max_new_tokens=20, emit=emit))
    while len(emitted.get("A", ())) < 4:
        batcher.step()
    assert batcher.blocks_in_flight == 0         # inflight=1 quiesces
    slot_a = next(i for i, r in enumerate(batcher.slots)
                  if r is not None and r.request_id == "A")

    def snapshot():
        row = batcher.cache["page_table"][slot_a]
        k = np.stack([np.asarray(gather_slot(batcher.cache["k"][layer],
                                             row)[0])[:32]
                      for layer in range(config.n_layers)])
        v = np.stack([np.asarray(gather_slot(batcher.cache["v"][layer],
                                             row)[0])[:32]
                      for layer in range(config.n_layers)])
        return k, v

    before = snapshot()
    batcher.submit(Request(request_id="B", prompt_tokens=list(pB),
                           max_new_tokens=6, emit=emit))
    slot_b = None
    for _ in range(100):
        batcher.step()
        slot_b = next((i for i, r in enumerate(batcher.slots)
                       if r is not None and r.request_id == "B"), None)
        if slot_b is not None:
            break
    assert slot_b is not None
    table = np.asarray(jax.device_get(batcher.cache["page_table"]))
    # the shared span is the SAME physical pages; the divergent page
    # (logical 2, where the prompts' tails differ) is a fresh copy.
    np.testing.assert_array_equal(table[slot_a][:2], table[slot_b][:2])
    assert table[slot_b][2] not in (0, table[slot_a][2])
    while len(emitted.get("B", ())) < 6:         # B finishes; A lives
        batcher.step()
    assert batcher.slots[slot_a] is not None
    assert batcher.slots[slot_a].request_id == "A"
    after = snapshot()
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])
    assert batcher.run_until_drained(max_steps=2000) < 2000
    # B's stream equals an unshared run of the same prompts.
    cold, _ = _drive_prefix(params, config, [pA, pB],
                            max_new=6, prefix_cache=False,
                            decode_block_tokens=4, inflight=1,
                            max_slots=3)
    assert emitted["B"] == cold["r1"]
    assert batcher._pages.leaked_pages() == 0


def test_prefix_cache_refcounts_survive_eviction_and_recover(tiny_f32):
    """Refcounts reach zero on every exit path: pool-pressure
    eviction of shared-prefix requests, stream drain, and a full
    recover() all leave zero leaked pages -- and the pressured shared
    run still emits the exact unshared streams."""
    config, params = tiny_f32
    prompts = [_SHARED_PREFIX + [120 + i, 8, 90 + i, 5, 60 + i, 3,
                                 40 + i, 2] for i in range(4)]
    cold, _ = _drive_prefix(params, config, prompts, max_new=24,
                            serial_first=True, prefix_cache=False)
    pressed, batcher = _drive_prefix(params, config, prompts,
                                     max_new=24, serial_first=True,
                                     decode_block_tokens=4,
                                     kv_pages=8)
    assert cold == pressed
    assert batcher.evictions >= 1
    assert batcher._pages.leaked_pages() == 0
    batcher.recover()                            # cold cache, no leaks
    assert batcher._pages.leaked_pages() == 0
    assert batcher._pages.free_pages == batcher._pages.total - 1
    assert batcher._pages.stats["prefix_pages"] == 0


def test_prefix_chaos_kill_and_journal_adoption_no_leaks(tiny_f32):
    """The chaos walk of the acceptance criteria: a ``decode_block``
    kill mid-generation over SHARED pages, recover(), then journal
    adoption (``resume_request``) of a shared-prefix request -- the
    adopted request rides the re-registered index, emits exactly its
    remaining budget, and the pool ends with zero leaked pages."""
    config, params = tiny_f32
    prompts = [_SHARED_PREFIX + [100 + i, 9, 80 + i, 6, 30 + i, 1,
                                 20 + i, 4] for i in range(4)]
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append(token)

    fired = {"n": 0}

    def probe(point):
        assert point == "decode_block"
        fired["n"] += 1
        if fired["n"] == 3:
            raise RuntimeError("injected chip death")

    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=1, kv_page_tokens=16,
                                prefix_cache=True, prefix_min_tokens=16,
                                fault_probe=probe)
    for i, prompt in enumerate(prompts):
        batcher.submit(Request(request_id=f"r{i}",
                               prompt_tokens=list(prompt),
                               max_new_tokens=10, emit=emit))
    steps = 0
    while (batcher.pending or batcher.active_count
           or batcher.blocks_in_flight) and steps < 3000:
        try:
            batcher.step()
        except RuntimeError:
            assert batcher.recover() >= 1        # refcounts reset too
            assert batcher._pages.leaked_pages() == 0
        steps += 1
    assert steps < 3000 and batcher.recoveries == 1
    host, _ = _drive_prefix(params, config, prompts, max_new=10,
                            prefix_cache=False, kv_page_tokens=0)
    assert emitted == host                       # kill lost nothing
    # journal adoption: a peer's shared-prefix request resumes at its
    # committed prefix and generates only the remaining budget.
    adopted = Request(request_id="adopted",
                      prompt_tokens=_SHARED_PREFIX + [100, 9, 80, 6,
                                                      30, 1, 20, 4],
                      max_new_tokens=10, emit=emit)
    batcher.submit(adopted)
    committed = host["r0"][:4]
    assert batcher.resume_request(adopted, committed)
    assert batcher.run_until_drained(max_steps=2000) < 2000
    assert emitted["adopted"] == host["r0"][4:]
    assert batcher.prefix_hits >= 1              # rode the warm index
    assert batcher._pages.leaked_pages() == 0


def test_prefix_page_allocator_units():
    """Allocator-level arithmetic for the prefix index: hash-chain
    agreement, match capped one page short, adoption refcounts,
    release keeping indexed pages warm, and leaf-first reclaim under
    pool pressure."""
    from aiko_services_tpu.models.paged import prefix_page_keys

    tokens = list(range(40))
    keys = prefix_page_keys(tokens, 16)
    assert len(keys) == 2                        # whole pages only
    assert prefix_page_keys(tokens[:32], 16) == keys
    divergent = tokens[:16] + [999] * 24
    other = prefix_page_keys(divergent, 16)
    assert other[0] == keys[0] and other[1] != keys[1]

    alloc = PageAllocator(total_pages=9, pages_per_slot=4, max_slots=3,
                          prefix_cache=True, prefix_min_tokens=16)
    assert alloc.match_prefix(tokens, 16) == 0   # nothing indexed yet
    assert alloc.ensure(0, 3)
    alloc.register_prefix(0, tokens, 40, 16)     # indexes 2 pages
    assert alloc.match_prefix(tokens, 16) == 2
    assert alloc.match_prefix(tokens[:33], 16) == 2
    assert alloc.match_prefix(tokens[:32], 16) == 1   # 1 token must
    assert alloc.match_prefix(divergent, 16) == 1  # . . . prefill
    assert alloc.match_prefix(tokens[:8], 16) == 0    # below minimum
    assert alloc.adopt_prefix(1, tokens, 16) == 32
    assert alloc.holds(1) == 2 and alloc.prefix_hits == 2
    # donor release: indexed pages stay warm (index ref), the
    # unregistered third page frees; adopter release drops to
    # index-only; nothing leaks at any point.
    assert alloc.release(0) == 3
    assert alloc.match_prefix(tokens, 16) == 2
    assert alloc.leaked_pages() == 0
    assert alloc.release(1) == 2
    assert alloc.match_prefix(tokens, 16) == 2   # still warm
    assert alloc.leaked_pages() == 0
    # pool pressure reclaims the index-only pages (leaf first) rather
    # than failing the allocation.
    assert alloc.ensure(2, 4)
    assert alloc.ensure(0, 4)
    assert alloc.match_prefix(tokens, 16) == 0   # index reclaimed
    assert alloc.leaked_pages() == 0
    alloc.reset()
    assert alloc.free_pages == 8 and alloc.leaked_pages() == 0


# -- the one-counted-fetch-per-block serving contract ----------------------


def test_one_labeled_ledger_fetch_per_retired_block(tiny):
    """The device-resident swag contract for serving: every retired
    loop block pays exactly ONE explicit ledger fetch (label
    ``llm_block``), and the ledger sees no other explicit fetches from
    the decode path."""
    config, params = tiny
    ledger = TransferLedger(policy="log")
    _, batcher = _run(params, config, decode_block_tokens=8,
                      fetch=lambda tree: ledger.fetch(tree,
                                                      label="llm_block"))
    assert batcher.blocks_retired >= 1
    stats = ledger.stats
    assert stats["explicit_by_label"]["llm_block"] \
        == batcher.blocks_retired
    assert stats["explicit"] == batcher.blocks_retired


# -- through the pipeline element ------------------------------------------


def _llm_definition(name, parameters, pipeline_parameters=None):
    return {
        "version": 0, "name": name, "runtime": "jax",
        "parameters": pipeline_parameters or {},
        "graph": ["(llm)"],
        "elements": [{
            "name": "llm",
            "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"max_new_tokens": 8, "max_seq": 64,
                           **parameters},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}


def _pipe_generate(runtime, definition, prompts):
    import queue

    from aiko_services_tpu.pipeline import Pipeline
    from conftest import run_until

    responses = queue.Queue()
    pipeline = Pipeline(definition, runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    for text in prompts:
        pipeline.create_frame_local(stream, {"text": text})
    assert run_until(runtime, lambda: responses.qsize() >= len(prompts),
                     timeout=120.0)
    texts = []
    while not responses.empty():
        _, _, swag, _, okay, diagnostic = responses.get()
        assert okay, diagnostic
        texts.append(swag["text"])
    return sorted(texts), pipeline


def test_llm_element_device_loop_end_to_end(runtime):
    """The serving contract through a real pipeline under
    ``transfer_guard: disallow``: device-loop generation completes,
    emits the same text as the host loop, and the transfer ledger
    counts EXACTLY one labeled fetch per retired block."""
    prompts = ["hello there", "general kenobi"]
    host, host_pipe = _pipe_generate(
        runtime, _llm_definition("llm_host", {}), prompts)
    host_pipe.stop()
    loop, pipeline = _pipe_generate(
        runtime, _llm_definition(
            "llm_loop",
            {"decode_block_tokens": 4, "kv_page_tokens": 16},
            pipeline_parameters={"transfer_guard": "disallow"}),
        prompts)
    assert loop == host
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert batcher.device_loop and batcher.blocks_retired >= 1
    stats = pipeline.transfer_stats()
    assert stats["explicit_by_label"]["llm_block"] \
        == batcher.blocks_retired
    assert stats["implicit"] == 0
    # Serving latency histograms reached the telemetry plane.  The
    # worker publishes AFTER the tick that finishes the last request,
    # racing the frame response this test just consumed -- wait for
    # the publish instead of sampling once (flaky before).
    from conftest import run_until
    assert run_until(runtime,
                     lambda: "llm_ttft_ms" in pipeline.metrics_text())
    metrics = pipeline.metrics_text()
    assert "llm_ttft_ms" in metrics
    assert "llm_tpot_ms" in metrics
    pipeline.stop()


def test_llm_element_speculative_telemetry(runtime):
    """Speculation counters flow to metrics_text() and share keys."""
    from conftest import run_until

    texts, pipeline = _pipe_generate(
        runtime, _llm_definition(
            "llm_spec",
            {"decode_block_tokens": 8, "speculative": "ngram"}),
        ["anaphora anaphora"])
    assert texts and isinstance(texts[0], str)
    batcher = pipeline.graph.get_node("llm").element._batcher
    assert batcher.draft_tokens > 0
    metrics = pipeline.metrics_text()
    assert "llm_draft_tokens" in metrics
    assert run_until(
        runtime,
        lambda: pipeline.share.get("llm_draft_tokens")
        == batcher.draft_tokens, timeout=10.0)
    assert pipeline.share.get("llm_accepted_tokens") \
        == batcher.accepted_tokens
    pipeline.stop()


def test_llm_element_rejects_bad_mode_at_create(runtime):
    """The ELEMENT_PARAMETERS domain check (analysis/params.py) fails
    a typo'd speculative mode at CREATE time, not at frame N."""
    from aiko_services_tpu.pipeline import DefinitionError, Pipeline

    with pytest.raises(DefinitionError, match="off|ngram|draft"):
        Pipeline(_llm_definition("llm_bad", {"speculative": "banana"}),
                 runtime=runtime)


@pytest.mark.parametrize("where", ["batcher", "create", "model-build"])
def test_decode_block_is_refused_by_name(tiny, runtime, where):
    """``decode_block`` chose the fused-block driver, which is gone.
    An unknown element parameter is ignored, and this one would then
    decode by the per-token tick: it is refused instead, at create
    time and at model build, by a message that names
    ``decode_block_tokens``; the batcher no longer takes it."""
    from aiko_services_tpu.pipeline import DefinitionError, Pipeline

    if where == "batcher":
        config, params = tiny
        with pytest.raises(TypeError, match="decode_block"):
            ContinuousBatcher(params, config, decode_block=4)
    elif where == "create":
        with pytest.raises(DefinitionError, match="decode_block_tokens"):
            Pipeline(_llm_definition("llm_old_knob", {"decode_block": 4}),
                     runtime=runtime)
    else:
        pipeline = Pipeline(
            _llm_definition("llm_old_knob_build", {"decode_block": 4},
                            pipeline_parameters={"preflight": "off"}),
            runtime=runtime)
        element = pipeline.graph.get_node("llm").element
        with pytest.raises(ValueError, match="decode_block_tokens"):
            element._ensure_model(element._resolve_model_params())
        pipeline.stop()
