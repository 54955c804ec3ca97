"""Model + mesh tests on the 8-device virtual CPU mesh: llama math,
sharded train step, continuous batching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama, ContinuousBatcher, Request
from aiko_services_tpu.models.tokenizer import ByteTokenizer
from aiko_services_tpu.parallel import MeshPlan, make_mesh, submesh, P


@pytest.fixture(scope="module")
def tiny():
    config = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), config)
    return config, params


def test_prefill_decode_consistency(tiny):
    """Prefill of N+1 tokens == prefill N + decode 1 (same logits)."""
    config, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 9), 0,
                                config.vocab_size)
    full_cache = llama.init_cache(config, 1, 32)
    full_logits, _ = llama.prefill(params, config, tokens, full_cache,
                                   jnp.zeros(1, dtype=jnp.int32))

    cache = llama.init_cache(config, 1, 32)
    _, cache = llama.prefill(params, config, tokens[:, :8], cache,
                             jnp.zeros(1, dtype=jnp.int32))
    decode_logits, _ = llama.decode_step(
        params, config, tokens[:, 8], cache,
        jnp.full((1,), 8, dtype=jnp.int32))
    # bf16 logits; decode's two-part softmax (attention_decode_append)
    # accumulates in a different order than prefill, so agreement is a
    # few bf16 ulps.  Exact-semantics coverage is the float32 variant
    # below.
    np.testing.assert_allclose(
        np.asarray(full_logits[:, -1], dtype=np.float32),
        np.asarray(decode_logits, dtype=np.float32), atol=5e-2)


def test_prefill_decode_consistency_f32():
    """Same consistency check in float32: tight tolerance proves the
    append-form decode attention is semantically exact, not just close
    in bf16."""
    import dataclasses

    config = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=256, max_seq=32),
        dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), config)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 9), 0,
                                config.vocab_size)
    full_logits, _ = llama.prefill(
        params, config, tokens, llama.init_cache(config, 1, 32),
        jnp.zeros(1, dtype=jnp.int32))
    cache = llama.init_cache(config, 1, 32)
    _, cache = llama.prefill(params, config, tokens[:, :8], cache,
                             jnp.zeros(1, dtype=jnp.int32))
    decode_logits, _ = llama.decode_step(
        params, config, tokens[:, 8], cache,
        jnp.full((1,), 8, dtype=jnp.int32))
    np.testing.assert_allclose(
        np.asarray(full_logits[:, -1], dtype=np.float32),
        np.asarray(decode_logits, dtype=np.float32), atol=1e-4)


def test_mesh_construction():
    mesh = make_mesh({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh2 = make_mesh({"dp": -1, "tp": 2})
    assert mesh2.shape["dp"] == 4
    sub = submesh(mesh, "dp", 0)
    assert sub.devices.size == 4
    with pytest.raises(ValueError):
        make_mesh({"dp": 3, "tp": 3})


def test_meshplan_filters_absent_axes():
    plan = MeshPlan.build({"dp": 8})
    sharding = plan.shard(P("dp", "tp", None))     # tp absent -> dropped
    assert sharding.spec == P("dp", None, None)


def test_sharded_prefill_on_mesh(tiny):
    """Params in TP layout on a 2x2x2 mesh; prefill runs under jit with
    sharded inputs and produces the same logits as single-device."""
    config, params = tiny
    plan = MeshPlan.build({"dp": 2, "fsdp": 2, "tp": 2})
    sharded_params = plan.put(params, llama.partition_specs(config))
    cache_sharding = jax.tree_util.tree_map(
        plan.shard, llama.cache_specs())
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                config.vocab_size)
    cache = jax.device_put(llama.init_cache(config, 2, 32),
                           cache_sharding)
    logits, _ = llama.prefill(sharded_params, config,
                              jax.device_put(tokens,
                                             plan.shard(P("dp", None))),
                              cache, jnp.zeros(2, dtype=jnp.int32))

    ref_cache = llama.init_cache(config, 2, 32)
    ref_logits, _ = llama.prefill(params, config, tokens, ref_cache,
                                  jnp.zeros(2, dtype=jnp.int32))
    # bf16 matmuls reduce in different orders across the tp/fsdp split;
    # tolerance sized to observed noise (~0.06 on logits of O(1-10)).
    np.testing.assert_allclose(np.asarray(logits, dtype=np.float32),
                               np.asarray(ref_logits, dtype=np.float32),
                               atol=1.5e-1)


def test_sharded_train_step(tiny):
    from aiko_services_tpu.models.train import (make_train_step,
                                                init_train_state)
    config, _ = tiny
    plan = MeshPlan.build({"dp": 2, "fsdp": 2, "tp": 2})
    params, opt_state, optimizer = init_train_state(
        jax.random.PRNGKey(0), config, plan)
    step = make_train_step(config, plan, optimizer=optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                config.vocab_size)
    params, opt_state, loss1 = step(params, opt_state, tokens)
    params, opt_state, loss2 = step(params, opt_state, tokens)
    assert float(loss2) < float(loss1)      # it learns the batch
    assert np.isfinite(float(loss1))


def test_continuous_batching(tiny):
    config, params = tiny
    tok = ByteTokenizer()
    batcher = ContinuousBatcher(params, config, max_slots=4, max_seq=64,
                                prefill_chunk=16)
    emitted = {}

    def emit(request_id, token, finished):
        emitted.setdefault(request_id, []).append((token, finished))

    for i in range(6):      # more requests than slots: queueing + reuse
        batcher.submit(Request(
            request_id=f"r{i}",
            prompt_tokens=tok.encode(f"hello {i}"),
            max_new_tokens=5, emit=emit))
    steps = batcher.run_until_drained(max_steps=500)
    assert steps < 500
    assert len(emitted) == 6
    for request_id, tokens in emitted.items():
        assert len(tokens) == 5
        assert tokens[-1][1] is True            # finished flag on last
        assert not any(f for _, f in tokens[:-1])
    assert batcher.active_count == 0 and batcher.queue_depth == 0
    assert batcher.tokens_emitted == 30


def test_chunked_prefill_matches_single_chunk(tiny):
    """A prompt admitted over several prefill chunks must produce exactly
    the tokens a one-chunk admission produces (greedy)."""
    config, params = tiny
    prompt = list(range(1, 29))        # 28 tokens

    def run(chunk):
        out = []
        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    max_seq=64, prefill_chunk=chunk)
        batcher.submit(Request("r", list(prompt), max_new_tokens=8,
                               emit=lambda r, t, f: out.append(t)))
        batcher.run_until_drained(max_steps=200)
        return out

    assert run(8) == run(64)           # 4 chunks vs 1 chunk

def test_prefill_admission_does_not_stall_decode(tiny):
    """While a long prompt admits chunk-by-chunk, an active generation
    must keep emitting a token on (almost) every step -- the head-of-line
    property the chunked/interleaved design exists for."""
    config, params = tiny
    ticks = []
    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=256,
                                prefill_chunk=8)
    batcher.submit(Request("active", [1, 2], max_new_tokens=60,
                           emit=lambda r, t, f: ticks.append(
                               ("active", batcher.steps))))
    batcher.step()                     # admit + prefill + first decode
    batcher.step()
    # Now admit a prompt needing 6 chunks of prefill.
    batcher.submit(Request("late", list(range(1, 48)), max_new_tokens=4,
                           emit=lambda r, t, f: ticks.append(
                               ("late", batcher.steps))))
    for _ in range(8):                 # the admission window
        batcher.step()
    active_steps = [s for who, s in ticks if who == "active"]
    # One emission per decode tick throughout the admission window: no
    # step gap wider than 1 (a stalled design would show a 6-step hole).
    gaps = [b - a for a, b in zip(active_steps, active_steps[1:])]
    assert gaps and max(gaps) <= 1
    batcher.run_until_drained(max_steps=300)
    assert [who for who, _ in ticks].count("late") == 4

def test_batching_interleaves_long_and_short(tiny):
    """A long generation must not block later short ones (continuous
    batching, not static)."""
    config, params = tiny
    order = []
    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=16)
    batcher.submit(Request("long", [1, 2, 3], max_new_tokens=40,
                           emit=lambda r, t, f: order.append((r, f))))
    batcher.submit(Request("short1", [4, 5], max_new_tokens=3,
                           emit=lambda r, t, f: order.append((r, f))))
    batcher.submit(Request("short2", [6], max_new_tokens=3,
                           emit=lambda r, t, f: order.append((r, f))))
    batcher.run_until_drained(max_steps=500)
    finish_order = [r for r, f in order if f]
    assert finish_order.index("short1") < finish_order.index("long")
    assert finish_order.index("short2") < finish_order.index("long")


def test_prefill_into_slot_flash_matches_dense():
    """The Pallas flash prefill (interpret mode on CPU) produces the
    same logits and cache as the dense path for chunked admission."""
    import dataclasses

    base = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=256, max_seq=64),
        dtype="float32")       # f32: any mismatch is semantic, not ulps
    params = llama.init_params(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)

    results = {}
    for impl in ("dense", "flash"):
        config = dataclasses.replace(base, attention=impl)
        cache = llama.init_cache(config, 2, 64)
        # Two chunks into slot 1, second offset by the first's length.
        logits1, cache = llama.prefill_into_slot(
            params, config, tokens[:, :8], cache, jnp.int32(1),
            jnp.int32(0))
        logits2, cache = llama.prefill_into_slot(
            params, config, tokens[:, 8:], cache, jnp.int32(1),
            jnp.int32(8))
        results[impl] = (np.asarray(logits2, dtype=np.float32),
                         np.asarray(cache["k"], dtype=np.float32))

    np.testing.assert_allclose(results["dense"][0], results["flash"][0],
                               atol=1e-4)
    np.testing.assert_allclose(results["dense"][1], results["flash"][1],
                               atol=1e-4)


def test_loop_blocks_match_single_steps(tiny):
    """decode_block_tokens=K (the device loop) emits exactly the token
    streams the per-token tick produces (greedy), including requests
    whose budgets end mid-block (overshoot discarded) and staggered
    lengths."""
    from aiko_services_tpu.models import ContinuousBatcher, Request
    from aiko_services_tpu.models.tokenizer import ByteTokenizer

    config, params = tiny
    tok = ByteTokenizer()

    def run(block):
        out = {}
        batcher = ContinuousBatcher(params, config, max_slots=4,
                                    max_seq=64, prefill_chunk=16,
                                    decode_block_tokens=block)
        for i, budget in enumerate((5, 9, 4)):     # none divisible by 4
            batcher.submit(Request(
                f"r{i}", tok.encode(f"prompt {i}"),
                max_new_tokens=budget,
                emit=lambda r, t, f: out.setdefault(r, []).append(t)))
        steps = batcher.run_until_drained(max_steps=500)
        assert steps < 500
        assert batcher.active_count == 0
        return out

    single = run(0)
    blocked = run(4)
    assert single == blocked
    assert [len(v) for v in blocked.values()] == [5, 9, 4]


def test_batched_admission_burst_capped(tiny):
    """Batched admission advances at most _ADMISSION_BURST_MAX slots per
    tick: compile buckets stay {1,2,4,8} for ANY max_slots (a wide
    max_slots must not introduce 16/32-row prefill compile shapes),
    with the overflow admitted on following ticks -- every request
    still completes."""
    from aiko_services_tpu.models.batching import _ADMISSION_BURST_MAX

    config, params = tiny
    tok = ByteTokenizer()
    out: dict = {}
    batcher = ContinuousBatcher(params, config, max_slots=20, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=2)
    for i in range(20):
        batcher.submit(Request(f"r{i}", tok.encode(f"burst {i}"),
                               max_new_tokens=20,
                               emit=lambda r, t, f:
                               out.setdefault(r, []).append(t)))
    for expected in (8, 16, 20):         # one burst of <= 8 per tick
        batcher.step()
        assert int(np.sum(batcher.decoding)) == expected
    steps = batcher.run_until_drained(max_steps=300)
    assert steps < 300
    assert len(out) == 20
    assert all(len(tokens) == 20 for tokens in out.values())


def test_cancel_frees_slot_and_stops_emits(tiny):
    """ADVICE r4: cancel() removes a queued request, frees an admitted
    request's slot immediately, and suppresses every later emit for it
    -- including tokens for it inside already-in-flight loop blocks."""
    config, params = tiny
    tok = ByteTokenizer()
    out: dict = {}

    def emit(r, t, f):
        out.setdefault(r, []).append((t, f))

    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=16, decode_block_tokens=4,
                                inflight=2)
    for i in range(3):                       # r2 queues behind 2 slots
        batcher.submit(Request(f"r{i}", tok.encode(f"cancel {i}"),
                               max_new_tokens=12, emit=emit))
    assert batcher.cancel("r2") is True      # still pending
    assert batcher.queue_depth == 2          # r0, r1 remain queued
    batcher.step()                           # admit + one block in flight
    assert batcher.cancel("r0") is True      # admitted, mid-decode
    emitted_at_cancel = len(out.get("r0", []))
    assert batcher.active_count == 1         # slot freed immediately
    batcher.run_until_drained(max_steps=200)
    assert batcher.cancel("missing") is False
    assert len(out.get("r0", [])) == emitted_at_cancel   # no late emits
    assert "r2" not in out                   # never admitted
    assert [f for _, f in out["r1"]][-1] is True         # r1 unaffected
    assert len(out["r1"]) == 12


def test_pipelined_blocks_match_single_steps(tiny):
    """The in-flight pipelined decode (inflight > 1, device-chained
    dispatches) emits exactly the streams the synchronous single-step
    batcher produces -- including a mid-stream admission into a freed
    slot, an EOS cut mid-block, and queueing beyond max_slots."""
    from aiko_services_tpu.models import ContinuousBatcher, Request
    from aiko_services_tpu.models.tokenizer import ByteTokenizer

    config, params = tiny
    tok = ByteTokenizer()

    def run(block, inflight):
        out = {}
        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    max_seq=64, prefill_chunk=16,
                                    decode_block_tokens=block,
                                    inflight=inflight)
        for i, budget in enumerate((7, 18, 5, 11)):   # 4 reqs, 2 slots
            batcher.submit(Request(
                f"r{i}", tok.encode(f"pipelined prompt {i}"),
                max_new_tokens=budget,
                emit=lambda r, t, f: out.setdefault(r, []).append(
                    (t, f))))
        steps = batcher.run_until_drained(max_steps=500)
        assert steps < 500
        assert batcher.active_count == 0
        assert batcher.blocks_in_flight == 0
        return out

    reference = run(0, 1)
    pipelined = run(4, 3)
    assert reference == pipelined
    assert [len(v) for v in pipelined.values()] == [7, 18, 5, 11]
    for stream in pipelined.values():               # finished flags
        assert stream[-1][1] is True
        assert not any(f for _, f in stream[:-1])


def test_batched_admission_matches_single():
    """A burst of admissions with very different prompt lengths (1 to
    3 chunks each, batched multi-slot prefill + power-of-two padding,
    device-loop blocks after it) delivers the same token streams and
    budgets and writes the same KV cache as one-at-a-time admission on
    the per-token tick (tests/admission_check.py).  Float32, as the
    other equivalence tests: the two paths are different XLA programs
    whose ~1-ulp rounding can flip a bfloat16 greedy argmax on a
    random-init near-tie, after which streams -- and the cache at the
    decode positions -- legitimately diverge (in bfloat16 the check
    failed one run in three or four under six workers).

    Runs in a SUBPROCESS deliberately: in-process, the property is
    intermittently CORRUPTED by an earlier interpret-mode int8 Pallas
    test (bisected to test_flash_decode.py::
    test_flash_int8_matches_dequantized_dense; whole cache rows read
    back wrong by >3.0) -- a jax-0.9 CPU-backend buffer interaction,
    not framework logic.  The check itself additionally pins
    single-threaded GEMMs + highest matmul precision (see
    admission_check.py's docstring)."""
    import pathlib
    import subprocess
    import sys as _sys

    script = pathlib.Path(__file__).with_name("admission_check.py")
    result = subprocess.run(
        [_sys.executable, str(script)], capture_output=True, text=True,
        timeout=600,
        env={"PATH": "/usr/bin:/bin", "HOME": "/tmp",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(script.parent.parent),
             "AIKO_LOG_LEVEL": "ERROR"})
    assert result.returncode == 0, result.stdout + result.stderr

def test_pipelined_blocks_respect_eos(tiny):
    """EOS inside an in-flight block truncates the stream and frees the
    slot; speculative tokens already dispatched are discarded."""
    from aiko_services_tpu.models import ContinuousBatcher, Request

    config, params = tiny

    def run(block, inflight):
        out = []
        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    max_seq=64, prefill_chunk=16,
                                    decode_block_tokens=block,
                                    inflight=inflight)
        batcher.submit(Request(
            "r", [1, 2, 3], max_new_tokens=40,
            emit=lambda r, t, f: out.append((t, f))))
        batcher.run_until_drained(max_steps=300)
        return out

    reference = run(0, 1)
    eos = reference[4][0]       # make the 5th greedy token the EOS

    def run_eos(block, inflight):
        out = []
        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    max_seq=64, prefill_chunk=16,
                                    decode_block_tokens=block,
                                    inflight=inflight)
        batcher.submit(Request(
            "r", [1, 2, 3], max_new_tokens=40, eos_tokens=(eos,),
            emit=lambda r, t, f: out.append((t, f))))
        batcher.run_until_drained(max_steps=300)
        return out

    expected = reference[:4] + [(eos, True)]
    expected = [(t, i == 4) for i, (t, _) in enumerate(expected)]
    assert run_eos(4, 3) == expected
    assert run_eos(0, 1) == expected


def test_loop_blocks_interleave_with_admission(tiny):
    """A request submitted while a blocked decode is running still
    admits (prefill chunks interleave between loop-block dispatches)
    and both streams complete."""
    from aiko_services_tpu.models import ContinuousBatcher, Request
    from aiko_services_tpu.models.tokenizer import ByteTokenizer

    config, params = tiny
    tok = ByteTokenizer()
    out = {}
    batcher = ContinuousBatcher(params, config, max_slots=2, max_seq=64,
                                prefill_chunk=8, decode_block_tokens=4)
    batcher.submit(Request(
        "first", tok.encode("hello"), max_new_tokens=12,
        emit=lambda r, t, f: out.setdefault(r, []).append(t)))
    for _ in range(2):
        batcher.step()                   # first is generating
    batcher.submit(Request(
        "late", tok.encode("a much longer prompt arriving late"),
        max_new_tokens=6,
        emit=lambda r, t, f: out.setdefault(r, []).append(t)))
    steps = batcher.run_until_drained(max_steps=500)
    assert steps < 500
    assert len(out["first"]) == 12
    assert len(out["late"]) == 6


def test_remat_training_matches_and_microbatching_averages(tiny):
    """LlamaConfig(remat=True) must not change the loss (it only
    re-computes activations in the backward pass), and gradient
    accumulation over microbatches must produce the same first-step
    loss as the full batch (same tokens, averaged grads)."""
    import dataclasses

    from aiko_services_tpu.models.train import (init_train_state,
                                                make_train_step)

    config, _ = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                config.vocab_size)

    def first_loss(cfg, accumulate):
        plan = MeshPlan.build({"dp": 2, "fsdp": 2, "tp": 2})
        params, opt_state, optimizer = init_train_state(
            jax.random.PRNGKey(0), cfg, plan)
        step = make_train_step(cfg, plan, optimizer=optimizer,
                               accumulate_steps=accumulate)
        params, opt_state, loss = step(params, opt_state, tokens)
        _, _, loss2 = step(params, opt_state, tokens)
        assert float(loss2) < float(loss)       # still learns
        return float(loss)

    plain = first_loss(config, 1)
    remat = first_loss(dataclasses.replace(config, remat=True), 1)
    accumulated = first_loss(config, 2)
    assert abs(plain - remat) < 1e-2            # identical computation
    # Microbatch average equals batch mean CE up to bf16 noise.
    assert abs(plain - accumulated) < 5e-2
