"""Benchmark suite: control plane + TPU model path (BASELINE configs 1-3).

Sections, each timed on the hardware the driver runs on (one TPU chip):

1. ``control_fps`` -- the 3-stage chained pipeline (park/forward/resume
   over loopback), the only metric with a reference number: multitude's
   ~50 frames/sec ceiling (reference examples/pipeline/multitude/
   run_small.sh:10,21; BASELINE.json).
2. ``detect_fps`` / ``detect_mfu`` -- the JAX detector (BASELINE config
   2) at 640x640: single-image latency-shaped and batched
   throughput-shaped, with MFU = XLA-counted FLOPs / time / chip peak.
3. ``llm_tokens_per_sec`` / ``llm_mfu`` -- Llama-1B-class serving
   (BASELINE config 3): batched ``decode_step`` rate and chunked-prefill
   rate, plus the end-to-end ContinuousBatcher host loop.

Measurement methodology: the chip is local to this process, so
``block_until_ready`` waits for the device and a timing closed by it
(or by a host fetch) is a device timing plus one dispatch+fetch
overhead (``dispatch_rtt_ms`` in the output, a fraction of a
millisecond locally).  Model-path timings run N steps INSIDE one jit
(``lax.scan`` with a data dependency chaining iterations so XLA cannot
elide or hoist the body) and fetch one scalar at the end, which
amortizes that overhead; the measured value is still subtracted once
(arithmetic kept as it was; ROADMAP S1 replaces this file).
Host-driven loops (the batcher serving path, the control plane) are
reported as measured.

The reference publishes no TPU/model numbers (BASELINE.json:
``published = {}``), so the model-path values ARE the record; ``vs_baseline`` compares
the control path against the 50 Hz ceiling.

Prints ONE JSON line with all keys.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import sys
import time

os.environ.setdefault("AIKO_LOG_LEVEL", "ERROR")

BASELINE_FPS = 50.0            # reference multitude run_small.sh ceiling
CONTROL_FRAMES = 2000
WARMUP = 50

# bf16 peak FLOP/s per chip, by device_kind substring (first match wins;
# "v5 lite" must precede "v5").
_PEAKS = [("v6 lite", 918e12), ("v6", 918e12), ("v5 lite", 197e12),
          ("v5e", 197e12), ("v5p", 459e12), ("v5", 459e12),
          ("v4", 275e12), ("v3", 123e12), ("v2", 45e12)]

# HBM peak bytes/s per chip (same matching rules).  Decode is
# bandwidth-bound; achieved GB/s against this peak is the honest
# utilization metric there, not MFU.
_HBM_PEAKS = [("v6 lite", 1640e9), ("v6", 1640e9), ("v5 lite", 819e9),
              ("v5e", 819e9), ("v5p", 2765e9), ("v5", 2765e9),
              ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9)]


def _match_peak(table) -> float | None:
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for substring, peak in table:
        if substring in kind:
            return peak
    return None


def chip_peak_flops() -> float | None:
    return _match_peak(_PEAKS)


def chip_peak_hbm() -> float | None:
    return _match_peak(_HBM_PEAKS)


def compiled_flops(lowered) -> float | None:
    """XLA's own FLOP count for a lowered computation -- valid only for
    computations WITHOUT ``lax.scan``/``fori_loop`` over layers: XLA's
    cost analysis counts a loop body ONCE, so a scanned N-layer model is
    undercounted by ~N x (verified empirically: 336 GFLOP reported vs
    1.27 TFLOP hand-counted for a llama3-1b 512-token prefill chunk).
    The detector (straight-line convs) uses this; the llama paths use
    :func:`llama_flops_per_token`."""
    try:
        analysis = lowered.compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0]
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def llama_flops_per_token(config, context: float) -> float:
    """Analytic matmul+attention FLOPs for one token at the given
    average attended context length (hand count; see compiled_flops for
    why XLA's number can't be used on the scanned model)."""
    c = config
    hd = c.head_dim
    linear = 2 * (c.dim * c.n_heads * hd            # wq
                  + 2 * c.dim * c.n_kv_heads * hd   # wk, wv
                  + c.n_heads * hd * c.dim          # wo
                  + 3 * c.dim * c.hidden_dim)       # gate, up, down
    attention = 2 * 2 * c.n_heads * hd * context    # scores + values
    return c.n_layers * (linear + attention) + 2 * c.dim * c.vocab_size


def tree_bytes(tree) -> int:
    import jax
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def metrics_p50(rows, key) -> float:
    """Median of one metrics key over (metrics, okay) response rows."""
    values = sorted(metrics.get(key, 0.0) for metrics, _ in rows)
    return values[len(values) // 2] if values else 0.0


# ---------------------------------------------------------------------------
# 1. Control plane: 3-stage chained pipelines (the multitude topology).

def element(name, cls, inputs, outputs, parameters=None,
            module="aiko_services_tpu.elements.common", lint=None):
    entry = {"name": name,
             "input": [{"name": n} for n in inputs],
             "output": [{"name": n} for n in outputs],
             "deploy": {"local": {
                 "module": module,
                 "class_name": cls}},
             "parameters": parameters or {}}
    if lint:
        entry["lint"] = list(lint)
    return entry


def remote(name, target, inputs, outputs):
    return {"name": name,
            "input": [{"name": n} for n in inputs],
            "output": [{"name": n} for n in outputs],
            "deploy": {"remote": {"name": target}}}


def bench_control() -> dict:
    from aiko_services_tpu.runtime import init_process
    from aiko_services_tpu.services import Registrar
    from aiko_services_tpu.pipeline import Pipeline

    runtime = init_process(transport="loopback")
    runtime.initialize()
    Registrar(runtime=runtime, primary_search_timeout=0.05)

    def definition(graph, elements, name):
        return {"version": 0, "name": name, "runtime": "jax",
                "graph": graph, "parameters": {}, "elements": elements}

    Pipeline(definition(["(C1)"],
                        [element("C1", "Increment", ["x"], ["x"])],
                        "bench_c"), runtime=runtime)
    Pipeline(definition(
        ["(B1 (RC (x: x)))"],
        [element("B1", "Increment", ["x"], ["x"]),
         remote("RC", "bench_c", ["x"], ["x"])],
        "bench_b"), runtime=runtime)
    head = Pipeline(definition(
        ["(A1 (RB (x: x)))"],
        [element("A1", "Increment", ["x"], ["x"]),
         remote("RB", "bench_b", ["x"], ["x"])],
        "bench_a"), runtime=runtime)

    stages = [head.graph.get_node("RB").element]
    runtime.run(until=lambda: all(s.remote_topic_path for s in stages),
                timeout=10.0)

    responses: "queue.Queue" = queue.Queue()
    done = {"count": 0, "okay": 0}

    def pump(n):
        for i in range(n):
            head.process_frame_local({"x": i}, stream_id="bench",
                                     queue_response=responses)

    def drain(target):
        while not responses.empty():
            *_, okay, _diag = responses.get()
            done["count"] += 1
            done["okay"] += bool(okay)
        return done["count"] >= target

    pump(WARMUP)
    runtime.run(until=lambda: drain(WARMUP), timeout=30.0)
    if done["count"] < WARMUP:
        return {"error": "control warmup stalled"}

    start = time.perf_counter()
    pump(CONTROL_FRAMES)
    runtime.run(until=lambda: drain(WARMUP + CONTROL_FRAMES),
                timeout=120.0)
    elapsed = time.perf_counter() - start
    completed = done["count"] - WARMUP
    fps = completed / elapsed if elapsed > 0 else 0.0
    runtime.terminate()
    return {"control_fps": round(fps, 1),
            "control_frames": completed,
            "control_elapsed_s": round(elapsed, 3)}


# ---------------------------------------------------------------------------
# Device-loop timing helpers.

def measure_rtt() -> float:
    """Median dispatch+fetch round trip for a trivial op (seconds)."""
    import jax
    import jax.numpy as jnp
    bump = jax.jit(lambda a: a + 1.0)
    value = jnp.float32(0.0)
    float(bump(value))                                 # compile
    samples = []
    for _ in range(5):
        start = time.perf_counter()
        float(bump(value))
        samples.append(time.perf_counter() - start)
    return sorted(samples)[len(samples) // 2]


def time_device_loop(run, rtt: float, samples: int = 1) -> float:
    """Run ``run()`` (one dispatch ending in a host fetch, which waits
    for the device) and return its wall time less ``rtt``, the measured
    dispatch+fetch overhead; with ``samples`` > 1, the MINIMUM over
    that many runs -- host interference only ever ADDS time, so the
    min is the device figure (r4's int8-KV record read 4.26 ms/step
    off one disturbed sample where 3.1 reproduces)."""
    best = None
    for _ in range(max(1, samples)):
        start = time.perf_counter()
        run()
        elapsed = max(time.perf_counter() - start - rtt, 1e-9)
        best = elapsed if best is None else min(best, elapsed)
    return best


# ---------------------------------------------------------------------------
# 2. Detector at 640x640 (BASELINE config 2).

def bench_detect(peak: float | None, rtt: float) -> dict:
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax import lax
    from aiko_services_tpu.models import detector

    import dataclasses

    result = {}
    # YOLO-n scale (width 32) and YOLO-s scale (width 64, depth 2):
    # the wider config feeds the MXU better (channel dims 128-512 vs
    # 64-256), which is where the conv MFU comes from.
    for scale, config, runs in (
            ("", detector.DetectorConfig(),
             (("", 1, 500), ("_batch8", 8, 200))),
            ("_s", dataclasses.replace(detector.DetectorConfig(),
                                       width=64, depth=2),
             (("_batch8", 8, 100),))):
        params = detector.init_params(jax.random.PRNGKey(0), config)
        for suffix, batch, iters in runs:
            tag = f"detect{scale}{suffix}"
            images = jax.random.uniform(
                jax.random.PRNGKey(1), (batch, 640, 640, 3),
                dtype=jnp.bfloat16)
            flops = compiled_flops(
                detector.detect.lower(params, config, images))

            @partial(jax.jit, static_argnames=())
            def loop(params, images, n=iters, config=config):
                # Perturb the input per iteration (data dependency on
                # the loop index) so XLA cannot hoist the body.
                def body(i, acc):
                    shifted = images + (i.astype(images.dtype) * 1e-6)
                    out = detector.detect.__wrapped__(params, config,
                                                      shifted)
                    return acc + out["scores"].sum().astype(jnp.float32)
                return lax.fori_loop(0, n, body, jnp.float32(0.0))

            float(loop(params, images))                # compile + warm
            elapsed = time_device_loop(
                lambda: float(loop(params, images)), rtt, samples=3)
            fps = batch * iters / elapsed
            result[f"{tag}_fps"] = round(fps, 1)
            if flops and peak:
                result[f"{tag}_mfu"] = round(
                    flops * iters / elapsed / peak, 4)
    result["detect_resolution"] = 640
    return result


# ---------------------------------------------------------------------------
# 3. LLM serving (BASELINE config 3): batched decode + chunked prefill
#    device rates, then the end-to-end batcher host loop.

def bench_llm(peak: float | None, rtt: float) -> dict:
    import dataclasses
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.models.batching import (ContinuousBatcher,
                                                   Request)

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        max_seq, slots, prompt_len, max_new = 1024, 8, 384, 256
        decode_iters = 256
        config = dataclasses.replace(llama.LlamaConfig.llama3_1b(),
                                     max_seq=max_seq)
    else:
        # cpu-smoke profile: the SAME serving code paths at a shape the
        # CPU mesh finishes in seconds, recorded with llm_profile so a
        # cpu round's figures are never mistaken for TPU numbers (the
        # TPU-only subsections -- long-context, 8k decode, kernel
        # %-of-peak -- are skipped, not faked).
        max_seq, slots, prompt_len, max_new = 512, 4, 96, 32
        decode_iters = 16
        config = llama.LlamaConfig(
            vocab_size=2048, dim=256, n_layers=4, n_heads=8,
            n_kv_heads=4, hidden_dim=512, max_seq=max_seq,
            rope_theta=10_000.0)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    result = {"llm_model": "llama3-1b-class" if on_tpu
              else "cpu-smoke-4L-256d",
              "llm_profile": "tpu" if on_tpu else "cpu-smoke",
              "llm_batch": slots, "llm_prompt_len": prompt_len,
              "llm_max_new": max_new}

    # -- batched decode: N steps inside one jit (cache chains them) ------
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, slots),
                         dtype=jnp.int32)
    lengths = jnp.full((slots,), prompt_len, dtype=jnp.int32)
    # Analytic per-step cost: every weight byte + the whole KV cache
    # stream through HBM once per decode step, and FLOPs follow the
    # hand count (XLA undercounts the scanned layers; see
    # llama_flops_per_token).  Average attended context over the run =
    # prompt + half the generated tokens.
    avg_context = prompt_len + decode_iters / 2
    step_flops = slots * llama_flops_per_token(config, avg_context)
    hbm_peak = chip_peak_hbm()

    @jax.jit
    def decode_loop(params, tokens, cache, lengths):
        def body(carry, _):
            tokens, cache, lengths = carry
            logits, cache = llama.decode_step.__wrapped__(
                params, config, tokens, cache, lengths)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (tokens, cache, lengths + 1), None
        (tokens, cache, _), _ = lax.scan(
            body, (tokens, cache, lengths), None, length=decode_iters)
        return tokens.sum()

    cache = llama.init_cache(config, slots, max_seq)
    # Bytes streamed per decode step: every weight EXCEPT the embed
    # table (decode gathers B rows of it, not the whole tensor; the
    # unembed matmul does read its full [dim, vocab]) plus the whole
    # KV cache.
    cache_bytes = tree_bytes(cache)

    def decode_bytes(tree):
        return (tree_bytes(tree) - tree_bytes(tree["embed"])
                + slots * config.dim * 2 + cache_bytes)
    step_bytes = decode_bytes(params)
    int(decode_loop(params, tokens, cache, lengths))   # compile + warm
    cache = llama.init_cache(config, slots, max_seq)
    elapsed = time_device_loop(
        lambda: int(decode_loop(params, tokens, cache, lengths)), rtt,
        samples=3)
    result["llm_tokens_per_sec"] = round(
        slots * decode_iters / elapsed, 1)
    result["llm_decode_step_ms"] = round(
        elapsed / decode_iters * 1000, 3)
    if peak:
        result["llm_mfu"] = round(
            step_flops * decode_iters / elapsed / peak, 4)
    if hbm_peak:
        result["llm_decode_hbm_gbps"] = round(
            step_bytes * decode_iters / elapsed / 1e9, 1)
        result["llm_decode_hbm_util"] = round(
            step_bytes * decode_iters / elapsed / hbm_peak, 3)

    # -- chunked prefill rate: admit a full prompt chunk-by-chunk --------
    chunk = 512 if on_tpu else 128
    chunk_flops = chunk * llama_flops_per_token(config, chunk / 2)
    # 48 chunks ~= 420 ms of device work: long enough that run-to-run
    # variance of the dispatch+fetch overhead stays a small share of
    # the measurement.
    prefill_iters = 48 if on_tpu else 4

    @jax.jit
    def prefill_loop(params, cache, chunk_tokens):
        def body(carry, i):
            cache, acc = carry
            logits, cache = llama.prefill_into_slot.__wrapped__(
                params, config, chunk_tokens + i, cache,
                i % slots, jnp.int32(0))
            return (cache, acc + logits.sum().astype(jnp.float32)), None
        (cache, acc), _ = lax.scan(
            body, (cache, jnp.float32(0.0)),
            jnp.arange(prefill_iters, dtype=jnp.int32))
        return acc

    chunk_tokens = jnp.asarray(
        rng.integers(0, config.vocab_size - prefill_iters, (1, chunk)),
        dtype=jnp.int32)
    cache = llama.init_cache(config, slots, max_seq)
    float(prefill_loop(params, cache, chunk_tokens))   # compile + warm
    cache = llama.init_cache(config, slots, max_seq)
    elapsed = time_device_loop(
        lambda: float(prefill_loop(params, cache, chunk_tokens)), rtt,
        samples=3)
    result["llm_prefill_tokens_per_sec"] = round(
        chunk * prefill_iters / elapsed, 1)
    if peak:
        result["llm_prefill_mfu"] = round(
            chunk_flops * prefill_iters / elapsed / peak, 4)
    del cache

    # -- weight-only int8 decode: same loop, quantized tree ---------------
    from aiko_services_tpu.models.quant import quantize_params

    qparams = quantize_params(params)
    qcache = llama.init_cache(config, slots, max_seq)
    qstep_bytes = decode_bytes(qparams)
    int(decode_loop(qparams, tokens, qcache, lengths))   # compile + warm
    qcache = llama.init_cache(config, slots, max_seq)
    elapsed = time_device_loop(
        lambda: int(decode_loop(qparams, tokens, qcache, lengths)), rtt,
        samples=3)
    result["llm_int8_tokens_per_sec"] = round(
        slots * decode_iters / elapsed, 1)
    result["llm_int8_decode_step_ms"] = round(
        elapsed / decode_iters * 1000, 3)
    if hbm_peak:
        result["llm_int8_decode_hbm_gbps"] = round(
            qstep_bytes * decode_iters / elapsed / 1e9, 1)
        result["llm_int8_decode_hbm_util"] = round(
            qstep_bytes * decode_iters / elapsed / hbm_peak, 3)
    del qparams, qcache

    # -- long-context prefill (BASELINE config 5 shape): one 8k prompt
    # admitted chunk-by-chunk, Pallas flash kernel vs dense attention.
    # Dense materializes the [S, T] logits per layer; flash streams
    # KV blocks through VMEM -- this is where the kernel pays off.
    long_seq, long_chunk = 8192, 2048
    for impl in (("flash", "dense") if on_tpu else ()):
        try:
            lc = dataclasses.replace(config, max_seq=long_seq,
                                     attention=impl)
            lc_tokens = jnp.asarray(
                rng.integers(0, config.vocab_size - 8, (1, long_chunk)),
                dtype=jnp.int32)

            @jax.jit
            def longctx_loop(params, cache, tokens):
                def body(i, carry):
                    cache, acc = carry
                    logits, cache = llama.prefill_into_slot.__wrapped__(
                        params, lc, tokens + i, cache, jnp.int32(0),
                        i * long_chunk)
                    return (cache,
                            acc + logits.sum().astype(jnp.float32))
                cache, acc = lax.fori_loop(
                    0, long_seq // long_chunk, body,
                    (cache, jnp.float32(0.0)))
                return acc

            # longctx_loop does not donate its cache arg: allocate once
            # OUTSIDE the timed window (the lambda must stay a single
            # dispatch + fetch for the RTT subtraction to hold).
            lc_cache = llama.init_cache(lc, 1, long_seq)
            float(longctx_loop(params, lc_cache, lc_tokens))   # warm
            elapsed = time_device_loop(
                lambda: float(longctx_loop(params, lc_cache,
                                           lc_tokens)), rtt, samples=3)
            result[f"llm_longctx8k_{impl}_tokens_per_sec"] = round(
                long_seq / elapsed, 1)
        except Exception as error:                # e.g. dense OOM at 8k
            result[f"llm_longctx8k_{impl}_error"] = \
                f"{type(error).__name__}: {error}"[:200]

    # -- long-context decode: at 8 slots x 8k context the KV cache
    # (2.1 GB bf16) outweighs the int8 weights (1.24 GB), so the int8
    # cache (kv_dtype, models/quant.py:quantize_kv) directly cuts the
    # dominant byte stream.  Both runs use int8 weights (the serving
    # config); the cache matmuls run as native int8 MXU dots
    # (ops/layers.py attention_decode_append).
    # 256 iters x min-of-3: at 64 iters the ~3-5 ms/step signal sat in a
    # ~0.25 s window where one disturbed sample mis-read int8-KV by
    # 1.4x (BENCH_r04 4.26 ms vs 3.1 reproduced).
    lc_slots, lc_ctx, lc_iters = 8, 8192, 256
    lc_tokens_arr = jnp.asarray(
        rng.integers(0, config.vocab_size, lc_slots), dtype=jnp.int32)
    lc_lengths = jnp.full((lc_slots,), lc_ctx - lc_iters - 1,
                          dtype=jnp.int32)
    qp = quantize_params(params)
    for kv_tag, kv_dtype in ((("bf16kv", "bfloat16"),
                              ("int8kv", "int8")) if on_tpu else ()):
        lc_config = dataclasses.replace(config, max_seq=lc_ctx,
                                        kv_dtype=kv_dtype)

        @jax.jit
        def lc_decode_loop(qp, tokens, cache, lengths):
            def body(carry, _):
                tokens, cache, lengths = carry
                logits, cache = llama.decode_step.__wrapped__(
                    qp, lc_config, tokens, cache, lengths)
                tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tokens, cache, lengths + 1), None
            (tokens, cache, _), _ = lax.scan(
                body, (tokens, cache, lengths), None, length=lc_iters)
            return tokens.sum()

        lc_cache = llama.init_cache(lc_config, lc_slots, lc_ctx)
        int(lc_decode_loop(qp, lc_tokens_arr, lc_cache, lc_lengths))
        lc_cache = llama.init_cache(lc_config, lc_slots, lc_ctx)
        elapsed = time_device_loop(
            lambda: int(lc_decode_loop(qp, lc_tokens_arr, lc_cache,
                                       lc_lengths)), rtt, samples=5)
        result[f"llm_decode8k_{kv_tag}_step_ms"] = round(
            elapsed / lc_iters * 1000, 3)
        if hbm_peak:
            lc_bytes = decode_bytes(qp) - cache_bytes \
                + tree_bytes(lc_cache)
            result[f"llm_decode8k_{kv_tag}_hbm_util"] = round(
                lc_bytes * lc_iters / elapsed / hbm_peak, 3)
        del lc_cache
    del qp

    # -- flash kernel in isolation: % of chip peak on the fully-live
    # causal region (last 2k chunk of an 8k prompt, llama3-1b heads).
    if peak:
        try:
            from aiko_services_tpu.ops.pallas_attention import \
                flash_attention
            fs, ft = 2048, 8192
            fq = jax.random.normal(jax.random.PRNGKey(7),
                                   (1, fs, 32, 64), jnp.bfloat16)
            fk = jax.random.normal(jax.random.PRNGKey(8),
                                   (1, ft, 8, 64), jnp.bfloat16)
            fv = jax.random.normal(jax.random.PRNGKey(9),
                                   (1, ft, 8, 64), jnp.bfloat16)
            # 600 iterations (~0.9 s of device work at 40% peak): the
            # per-dispatch fixed overhead and its variance, at 50
            # iterations (75 ms of work), mis-measured the kernel by up
            # to 1.5x across rounds (28.2 recorded vs 40.9 amortized);
            # at 600 the same absolute noise is <3% of the window.
            fiters = 600

            @jax.jit
            def flash_loop(fq, fk, fv):
                def body(i, acc):
                    out = flash_attention(
                        fq + (i * 1e-6).astype(fq.dtype), fk, fv,
                        q_offset=ft - fs)
                    return acc + out.astype(jnp.float32).sum()
                return lax.fori_loop(0, fiters, body, jnp.float32(0.0))

            attended = sum(range(ft - fs + 1, ft + 1))
            fl = 4 * 32 * 64 * attended

            @jax.jit
            def flash_loop_packed(fq, fk, fv):
                def body(i, acc):
                    out = flash_attention(
                        fq + (i * 1e-6).astype(fq.dtype), fk, fv,
                        q_offset=ft - fs, pack_heads=True)
                    return acc + out.astype(jnp.float32).sum()
                return lax.fori_loop(0, fiters, body, jnp.float32(0.0))

            # Best of 3: host interference only ever adds time.
            for key, loop_fn in (
                    ("flash_kernel_pct_peak", flash_loop),
                    # The cross-head q-packing
                    # variant (two query heads per 128-wide
                    # contraction), measured -- on v5e it runs
                    # SLIGHTLY SLOWER than the unpacked kernel (the
                    # MXU pipelines 64-deep contractions; packing just
                    # adds output-width traffic), so this key is the
                    # recorded refutation, not the default path.
                    ("flash_kernel_packed_pct_peak", flash_loop_packed)):
                float(loop_fn(fq, fk, fv))          # compile + warm
                elapsed = min(time_device_loop(
                    lambda: float(loop_fn(fq, fk, fv)), rtt)
                    for _ in range(3))
                result[key] = round(fl * fiters / elapsed / peak * 100, 1)
        except Exception as error:
            result["flash_kernel_error"] = \
                f"{type(error).__name__}: {error}"[:200]

    # -- serving as one dispatch train: the WHOLE serving
    # workload -- batched chunked admission of `slots` prompts plus the
    # full fused decode of max_new tokens per slot with per-step
    # sampling -- as ONE dispatch train (a single jit), fetching the
    # emitted token block once at the end.  This is exactly the device
    # work the ContinuousBatcher schedules (prefill_into_slots burst +
    # decode_block chains, models/batching.py); what it removes is the
    # host-side scheduling between dispatches, so the gap between this
    # figure and the host-driven ones IS the host loop's cost (ROADMAP
    # S3).  Steady-state serving rate = generated tokens
    # / (admission + decode) time; the honest host-driven loops are
    # recorded alongside under *_host_* keys.
    serve_max_new = 128 if on_tpu else 32   # same budget as the host loop

    def serve_device(serve_params):
        prompts = jnp.asarray(
            rng.integers(0, config.vocab_size, (slots, prompt_len)),
            dtype=jnp.int32)

        @jax.jit
        def serving_train(params, cache, prompts, key):
            padded = jnp.zeros((slots, chunk), dtype=jnp.int32) \
                .at[:, :prompt_len].set(prompts)
            logits, cache = llama.prefill_into_slots.__wrapped__(
                params, config, padded, cache,
                jnp.arange(slots, dtype=jnp.int32),
                jnp.zeros((slots,), dtype=jnp.int32))
            first = jnp.argmax(
                logits[:, prompt_len - 1, :], axis=-1).astype(jnp.int32)
            emitted, *_ = llama.decode_block.__wrapped__(
                params, config, first, cache,
                jnp.full((slots,), prompt_len, dtype=jnp.int32),
                jnp.ones((slots,), dtype=bool),
                jnp.zeros((slots,), dtype=jnp.float32), key,
                # What the batcher resolves at this shape: 'auto' picks
                # the flash-decode kernel at a 1024 resident cache
                # (dense below the threshold on the cpu-smoke profile).
                num_steps=serve_max_new - 1,
                use_flash=max_seq >= config.flash_decode_threshold)
            return emitted.sum() + first.sum()

        key = jax.random.PRNGKey(0)
        cache = llama.init_cache(config, slots, max_seq)
        int(serving_train(serve_params, cache, prompts, key))  # compile
        elapsed = time_device_loop(
            lambda: int(serving_train(serve_params, cache, prompts,
                                      key)), rtt, samples=3)
        return round(slots * serve_max_new / elapsed, 1)

    result["llm_serving_blocked_tokens_per_sec"] = serve_device(params)
    result["llm_serving_int8_tokens_per_sec"] = serve_device(
        quantize_params(params))

    # -- end-to-end serving host loop (one blocking fetch per token) -----
    batcher = ContinuousBatcher(params, config, max_slots=slots,
                                max_seq=max_seq, prefill_chunk=chunk)
    batcher.submit(Request("warm", list(rng.integers(
        0, config.vocab_size, 8)), max_new_tokens=2))
    batcher.run_until_drained(max_steps=50)
    emitted = {"n": 0}

    def emit(request_id, token, finished):
        emitted["n"] += 1

    start = time.perf_counter()
    for i in range(slots):
        batcher.submit(Request(
            f"r{i}", list(rng.integers(0, config.vocab_size, prompt_len)),
            max_new_tokens=serve_max_new, emit=emit))  # same budget
    batcher.run_until_drained(max_steps=10_000)
    elapsed = time.perf_counter() - start
    result["llm_serving_host_loop_tokens_per_sec"] = round(
        emitted["n"] / elapsed, 1)

    # -- same loop with PIPELINED fused decode blocks: 32 decode steps
    # per dispatch, up to 6 blocks in flight chained device-side,
    # emitted tokens copied back asynchronously.  Block sizing swept on
    # v5e round 4 (the flat-cache decode step cut block compute ~40%,
    # so deeper pipelines of smaller blocks hide the per-dispatch host
    # latency better than round 3's 64x3: int8 best 1950 tok/s at 32x6 vs 1830 at
    # 64x3, with the 128-token budget capping coverage at 4 blocks).
    def serve(serve_params, label):
        batcher = ContinuousBatcher(params=serve_params, config=config,
                                    max_slots=slots, max_seq=max_seq,
                                    prefill_chunk=chunk,
                                    decode_block=32, inflight=6)
        # Warm a full admission burst so the batched-prefill N=8 bucket
        # and the fused decode block both compile outside the timer.
        for i in range(slots):
            batcher.submit(Request(f"warm{i}", list(rng.integers(
                0, config.vocab_size, 8)),
                max_new_tokens=80 if on_tpu else 16))
        batcher.run_until_drained(max_steps=400)

        def one_run(tag):
            emitted["n"] = 0
            start = time.perf_counter()
            for i in range(slots):
                batcher.submit(Request(
                    f"{label}{tag}{i}",
                    list(rng.integers(0, config.vocab_size,
                                      prompt_len)),
                    max_new_tokens=serve_max_new,
                    emit=emit))          # same budget as blocked
            batcher.run_until_drained(max_steps=10_000)
            return emitted["n"] / (time.perf_counter() - start)

        # Best of 2: this loop is bound by host latency per dispatch,
        # and a single disturbed sample can halve the recorded figure.
        return round(max(one_run("a"), one_run("b")), 1)

    # Host-driven pipelined loop (the real batcher, host-scheduled):
    # RETIRED to legacy_ keys by ISSUE 8 -- the device-resident loop
    # below supersedes it as the real serving hot path (rounds 2-4
    # history: these were the headline `llm_serving_{blocked,int8}`
    # keys and swung 2x between rounds).
    result["legacy_llm_serving_host_pipelined_tokens_per_sec"] = serve(
        params, "b")
    result["legacy_llm_serving_host_pipelined_int8_tokens_per_sec"] = \
        serve(quantize_params(params), "q")

    # -- DEVICE-RESIDENT serving loop (ISSUE 8): generation inside
    # llama.decode_loop blocks -- on-device sampling, stop detection
    # and (optionally) speculation in a lax.while_loop, the host
    # paying ONE counted ledger fetch per retired block.  Runs under
    # ``transfer_guard: disallow`` (a stray per-token sync would RAISE
    # on hardware backends), so the figure is structurally incapable
    # of hiding per-token host round trips; host work is per BLOCK.
    from aiko_services_tpu.pipeline.overlap import TransferLedger

    def serve_loop(serve_params, label, **kw):
        ledger = TransferLedger(policy="disallow")
        batcher = ContinuousBatcher(
            params=serve_params, config=config, max_slots=slots,
            max_seq=max_seq, prefill_chunk=chunk,
            decode_block_tokens=64, inflight=4,
            fetch=lambda tree: ledger.fetch(tree, label="llm_block"),
            **kw)
        for i in range(slots):           # compile outside the timer
            batcher.submit(Request(f"warm{label}{i}", list(rng.integers(
                0, config.vocab_size, 8)),
                max_new_tokens=80 if on_tpu else 16))
        batcher.run_until_drained(max_steps=400)

        def one_run(tag):
            emitted["n"] = 0
            start = time.perf_counter()
            for i in range(slots):
                batcher.submit(Request(
                    f"loop{label}{tag}{i}",
                    list(rng.integers(0, config.vocab_size,
                                      prompt_len)),
                    max_new_tokens=serve_max_new, emit=emit))
            with ledger.guard():
                batcher.run_until_drained(max_steps=10_000)
            return emitted["n"] / (time.perf_counter() - start)

        rate = round(max(one_run("a"), one_run("b")), 1)
        return rate, batcher, ledger

    rate, batcher, ledger = serve_loop(params, "d")
    result["llm_serving_device_loop_tokens_per_sec"] = rate
    result["llm_serving_device_loop_block_fetches"] = \
        ledger.stats["explicit_by_label"].get("llm_block", 0)
    result["llm_serving_device_loop_vs_blocked"] = round(
        rate / result["llm_serving_blocked_tokens_per_sec"], 3)
    rate, _, _ = serve_loop(quantize_params(params), "i")
    result["llm_serving_device_loop_int8_tokens_per_sec"] = rate
    rate, batcher, _ = serve_loop(params, "p", kv_page_tokens=128)
    result["llm_serving_device_loop_paged_tokens_per_sec"] = rate
    # Speculative multi-token decoding: the int8 self-draft verified
    # by one batched target step; greedy rows accept matching drafts
    # only, so the stream stays token-identical to plain decode.
    rate, batcher, _ = serve_loop(params, "s", speculative="draft",
                                  spec_tokens=4)
    result["llm_serving_device_loop_spec_tokens_per_sec"] = rate
    result["llm_speculative_accept_rate"] = round(
        batcher.accepted_tokens / max(1, batcher.draft_tokens), 3)

    # -- shared-prefix KV cache (ISSUE 18): warm-vs-cold TTFT for a
    # 1k-token shared system prompt, hit rate and unique KV bytes at
    # ~90% prompt overlap.  Requests run serially so every warm
    # request finds the cold request's pages already indexed (a burst
    # admits before anything registers, which is the pessimal case,
    # not the system-prompt case this measures).
    sys_len, tail_len, prefix_gen = 1024, 96, 4
    prefix_pt = 32
    prompt_total = sys_len + tail_len
    sys_prompt = list(rng.integers(0, config.vocab_size, sys_len))
    prefix_seq = ((prompt_total + 2 * prefix_gen) // prefix_pt + 2) \
        * prefix_pt                       # page-aligned, room to finish
    pb = ContinuousBatcher(
        params=params, config=config, max_slots=2, max_seq=prefix_seq,
        prefill_chunk=96, kv_page_tokens=prefix_pt,
        prefix_cache=True, prefix_min_tokens=256)
    # Warm with a 160-token prompt (below prefix_min_tokens, so it is
    # never indexed): compiles the 96-token prefill bucket and the
    # decode step so the cold request's clock starts compile-free.
    pb.submit(Request("warmx", list(rng.integers(
        0, config.vocab_size, 160)), max_new_tokens=2))
    pb.run_until_drained(max_steps=400)
    pb.take_request_stats()

    def prefix_run(name):
        pb.submit(Request(name, sys_prompt + list(rng.integers(
            0, config.vocab_size, tail_len)),
            max_new_tokens=prefix_gen))
        pb.run_until_drained(max_steps=2_000)
        return pb.take_request_stats()[0]["ttft_ms"]

    cold_ttft = prefix_run("cold")
    pb.reset_prefix_stats()
    shared_base = pb.prefix_shared_tokens
    warm_ttft = min(prefix_run(f"warm{i}") for i in range(3))
    shared_per_req = (pb.prefix_shared_tokens - shared_base) / 3
    result["llm_cold_prefix_ttft_ms"] = round(cold_ttft, 2)
    result["llm_warm_prefix_ttft_ms"] = round(warm_ttft, 2)
    result["llm_warm_prefix_ttft_frac"] = round(warm_ttft / cold_ttft, 3)
    result["llm_prefix_cache_hit_rate"] = round(pb.prefix_hit_rate(), 3)
    # Unique KV footprint a warm request actually writes: whole pages
    # not adopted from the index, in cache-dtype bytes.
    per_token_kv = (config.n_layers * 2 * config.n_kv_heads
                    * (config.dim // config.n_heads)
                    * jnp.zeros((), config.dtype).dtype.itemsize)
    total_pages = -(-(prompt_total + prefix_gen) // prefix_pt)
    fresh_pages = total_pages - int(shared_per_req) // prefix_pt
    result["llm_hbm_bytes_per_request"] = \
        fresh_pages * prefix_pt * per_token_kv
    result["llm_hbm_bytes_per_request_cold"] = \
        total_pages * prefix_pt * per_token_kv

    # -- speculation auto-probe (ISSUE 18): build a `speculative: auto`
    # batcher and record the measured draft-vs-plain ratio honestly --
    # auto keeps draft only on a >= 1.2x win, otherwise plain decode.
    probe = ContinuousBatcher(
        params=params, config=config, max_slots=slots, max_seq=max_seq,
        prefill_chunk=chunk, decode_block_tokens=64, inflight=4,
        speculative="auto", spec_tokens=4)
    result["llm_spec_vs_plain_ratio"] = round(probe.spec_probe_ratio, 3)
    result["llm_spec_auto_mode"] = probe.speculative

    # Deltas: against the same key in the previous recorded round, or
    # (first round of a renamed/new key) against its predecessor
    # serving measure, so the dispatch-discipline win is visible.
    previous = _previous_bench()
    for key, fallback in (
            ("llm_serving_device_loop_tokens_per_sec",
             "llm_serving_host_pipelined_tokens_per_sec"),
            ("llm_serving_device_loop_int8_tokens_per_sec",
             "llm_serving_host_pipelined_int8_tokens_per_sec"),
            ("llm_serving_device_loop_spec_tokens_per_sec",
             "llm_serving_host_pipelined_tokens_per_sec"),
            ("llm_speculative_accept_rate", None),
            ("llm_warm_prefix_ttft_ms", None),
            ("llm_prefix_cache_hit_rate", None),
            ("llm_hbm_bytes_per_request", None),
            ("llm_spec_vs_plain_ratio", None)):
        prior = previous.get(key) or (previous.get(fallback)
                                      if fallback else None)
        if prior:
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 3b. Kernel plane (ISSUE 11): the paged flash-decode, chunk-verify,
#     int8 dequant-matmul and top-k kernels against their XLA/dense
#     references.  On TPU this measures the real kernels at serving
#     shapes; on CPU every Pallas call runs in INTERPRET mode (an
#     emulated grid loop), so the figures are recorded honestly under
#     kernel_bench_profile=cpu-interpret -- correctness smoke + key
#     wiring, NOT a performance claim (interpret overhead dominates and
#     the ratios typically favor the XLA reference there).

def bench_kernels(peak: float | None, rtt: float) -> dict:
    import dataclasses
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.models.paged import init_paged_cache
    from aiko_services_tpu.models.quant import quantize_weight

    on_tpu = jax.default_backend() == "tpu"
    hbm_peak = chip_peak_hbm()
    result = {"kernel_bench_profile": "tpu" if on_tpu else
              "cpu-interpret"}
    rng = np.random.default_rng(0)

    if on_tpu:
        config = dataclasses.replace(llama.LlamaConfig.llama3_1b(),
                                     max_seq=8192)
        slots, iters, pt = 8, 64, 128
        verify_iters, spec = 16, 4
        mm_shape, mm_iters = (8, 2048, 128_256), 50
        tk_shape, tk_k, tk_iters = (8, 128_256), 8, 50
    else:
        config = llama.LlamaConfig(
            vocab_size=512, dim=128, n_layers=2, n_heads=8,
            n_kv_heads=2, hidden_dim=256, max_seq=2048,
            rope_theta=10_000.0)
        slots, iters, pt = 4, 8, 128
        verify_iters, spec = 4, 4
        mm_shape, mm_iters = (8, 128, 2048), 20
        tk_shape, tk_k, tk_iters = (8, 8192), 8, 20
    ctx = config.max_seq
    params = llama.init_params(jax.random.PRNGKey(0), config)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size, slots),
                         dtype=jnp.int32)
    lengths = jnp.full((slots,), ctx - iters - 1, dtype=jnp.int32)

    def fully_mapped_paged():
        cache = init_paged_cache(config, slots, ctx, pt)
        pps = ctx // pt
        table = np.arange(1, slots * pps + 1,
                          dtype=np.int32).reshape(slots, pps)
        cache["page_table"] = jnp.asarray(table)
        return cache

    def decode_rate(cache_fn, use_flash):
        @jax.jit
        def loop(params, tokens, cache, lengths):
            def body(carry, _):
                tokens, cache, lengths = carry
                logits, cache = llama.decode_step.__wrapped__(
                    params, config, tokens, cache, lengths,
                    use_flash=use_flash)
                tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tokens, cache, lengths + 1), None
            (tokens, cache, _), _ = lax.scan(
                body, (tokens, cache, lengths), None, length=iters)
            return tokens.sum()

        cache = cache_fn()
        int(loop(params, tokens, cache, lengths))       # compile + warm
        cache = cache_fn()
        elapsed = time_device_loop(
            lambda: int(loop(params, tokens, cache, lengths)), rtt,
            samples=3)
        return slots * iters / elapsed, elapsed

    # -- paged flash-decode: the kernel walking the page table vs the
    # gather-attention reference vs the dense-flash path on a dense
    # cache of the same extent (the ISSUE 11 gate: paged >= dense).
    paged_rate, paged_elapsed = decode_rate(fully_mapped_paged, True)
    gather_rate, _ = decode_rate(fully_mapped_paged, False)
    dense_flash_rate, _ = decode_rate(
        lambda: llama.init_cache(config, slots, ctx), True)
    result["llm_decode8k_paged_tokens_per_sec"] = round(paged_rate, 1)
    result["llm_decode8k_paged_gather_tokens_per_sec"] = \
        round(gather_rate, 1)
    result["llm_decode8k_dense_flash_tokens_per_sec"] = \
        round(dense_flash_rate, 1)
    result["llm_decode8k_paged_vs_dense_flash"] = round(
        paged_rate / dense_flash_rate, 3)
    result["llm_decode8k_paged_vs_gather"] = round(
        paged_rate / gather_rate, 3)
    if on_tpu and hbm_peak:
        # Decode is bandwidth-bound: the honest %-of-peak for the
        # paged kernel is achieved HBM bytes (weights sans embed + the
        # LIVE cache pages, streamed once per step) against chip peak.
        cache = fully_mapped_paged()
        step_bytes = (tree_bytes(params) - tree_bytes(params["embed"])
                      + tree_bytes(cache))
        result["llm_kernel_pct_peak"] = round(
            step_bytes * iters / paged_elapsed / hbm_peak * 100, 1)
        del cache
    else:
        result["llm_kernel_pct_peak"] = None
        result["llm_kernel_pct_peak_note"] = \
            "needs TPU hardware (cpu-interpret round)"

    # -- batched chunk-verify: the speculative target step's
    # concat-attention, kernel vs dense, on a dense stacked cache.
    trash = ctx - 1
    starts = jnp.full((slots,), ctx - iters - spec - 2,
                      dtype=jnp.int32)
    chunk = jnp.asarray(rng.integers(0, config.vocab_size,
                                     (slots, spec + 1)),
                        dtype=jnp.int32)

    def verify_time(use_flash):
        @jax.jit
        def loop(cache, chunk, starts):
            def body(i, carry):
                cache, acc = carry
                logits, cache = llama._chunk_verify(
                    params, config, chunk + i, cache, starts, trash,
                    use_flash=use_flash)
                return (cache, acc + logits.sum().astype(jnp.float32))
            cache, acc = lax.fori_loop(0, verify_iters, body,
                                       (cache, jnp.float32(0.0)))
            return acc

        cache = llama.init_cache(config, slots, ctx)
        float(loop(cache, chunk, starts))               # compile + warm
        cache = llama.init_cache(config, slots, ctx)
        elapsed = time_device_loop(
            lambda: float(loop(cache, chunk, starts)), rtt, samples=3)
        return elapsed / verify_iters * 1000.0

    result["chunk_verify_kernel_ms"] = round(verify_time(True), 3)
    result["chunk_verify_dense_ms"] = round(verify_time(False), 3)
    result["chunk_verify_vs_dense"] = round(
        result["chunk_verify_dense_ms"]
        / result["chunk_verify_kernel_ms"], 3)

    # -- fused int8 dequant-matmul vs the XLA cast-into-dot + scale
    # pair, at the unembed projection's shape.
    from aiko_services_tpu.ops.pallas_matmul import int8_matmul

    m, d, f = mm_shape
    weight = quantize_weight(jnp.asarray(
        rng.normal(size=(d, f)), jnp.float32))
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)

    @jax.jit
    def mm_kernel(x, w, s):
        def body(i, acc):
            out = int8_matmul(x + (i * 1e-6).astype(x.dtype), w, s)
            return acc + out.astype(jnp.float32).sum()
        return lax.fori_loop(0, mm_iters, body, jnp.float32(0.0))

    @jax.jit
    def mm_xla(x, w, s):
        def body(i, acc):
            xi = x + (i * 1e-6).astype(x.dtype)
            out = (xi @ w.astype(xi.dtype)) * s.astype(xi.dtype)
            return acc + out.astype(jnp.float32).sum()
        return lax.fori_loop(0, mm_iters, body, jnp.float32(0.0))

    for key, fn in (("int8_matmul_ms", mm_kernel),
                    ("int8_matmul_xla_ms", mm_xla)):
        float(fn(x, weight["int8"], weight["scale"]))    # compile
        elapsed = time_device_loop(
            lambda: float(fn(x, weight["int8"], weight["scale"])), rtt,
            samples=3)
        result[key] = round(elapsed / mm_iters * 1000.0, 4)
    result["int8_matmul_vs_xla"] = round(
        result["int8_matmul_xla_ms"] / result["int8_matmul_ms"], 3)

    # -- on-TPU top-k vs lax.top_k at the sampling shape.
    from aiko_services_tpu.ops.pallas_topk import topk as pallas_topk

    logits = jnp.asarray(rng.normal(size=tk_shape), jnp.float32)

    def tk_loop(impl):
        @jax.jit
        def loop(logits):
            def body(i, acc):
                values, _ = impl(logits + i * 1e-6, tk_k)
                return acc + values.sum()
            return lax.fori_loop(0, tk_iters, body, jnp.float32(0.0))
        float(loop(logits))                              # compile
        elapsed = time_device_loop(lambda: float(loop(logits)), rtt,
                                   samples=3)
        return elapsed / tk_iters * 1000.0

    pallas_ms = tk_loop(lambda x, k: pallas_topk(x, k))
    lax_ms = tk_loop(lambda x, k: jax.lax.top_k(x, k))
    result["topk_pallas_ms"] = round(pallas_ms, 4)
    result["topk_lax_ms"] = round(lax_ms, 4)
    # kernel minus lax: NEGATIVE = the kernel is faster.
    result["topk_vs_lax_ms"] = round(pallas_ms - lax_ms, 4)

    previous = _previous_bench()
    for key in ("llm_decode8k_paged_tokens_per_sec",
                "llm_kernel_pct_peak", "chunk_verify_vs_dense",
                "int8_matmul_vs_xla"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    # topk_vs_lax_ms is a SIGNED difference (negative = kernel faster):
    # a ratio against the prior round flips sign or inflates across
    # zero, so its baseline delta is a subtraction (negative = this
    # round is faster than the last).
    prior = previous.get("topk_vs_lax_ms")
    if prior is not None and result.get("topk_vs_lax_ms") is not None:
        result["topk_vs_lax_ms_vs_baseline"] = round(
            result["topk_vs_lax_ms"] - prior, 4)
    return result


# ---------------------------------------------------------------------------
# 4. End-to-end pipeline (BASELINE config 4, single-chip): synthetic
#    video frames -> Detector -> DetectionCaption -> LLM caption through
#    the REAL engine, measuring whole-pipeline frames/s and p50 per-stage
#    latency out of frame.metrics -- the framework overhead AROUND the
#    models, which the device-loop sections above deliberately exclude.

E2E_FRAMES = 24
E2E_WARMUP = 2
# CPU-feasible profile knobs: the default llama3-1b-class config is
# the honest serving shape but takes >10 minutes of compile+decode on
# the virtual CPU mesh (r06 skipped the section for exactly that).
# AIKO_BENCH_E2E_MODEL=tiny swaps the LLM for the test-scale config
# and AIKO_BENCH_E2E_REPLICAS=N runs the Detector stage replicated
# (placement {devices:1, replicas:N} -- the post-PR-7 shape the
# ROADMAP wants the e2e/device ratio re-measured under).  Non-default
# values are recorded on pipeline_e2e_model / pipeline_e2e_replicas
# and SKIP the _vs_baseline wiring -- a tiny-model fps must never be
# ratioed against a 1B-model baseline.
E2E_MODEL = os.environ.get("AIKO_BENCH_E2E_MODEL", "llama3-1b")
E2E_REPLICAS = int(os.environ.get("AIKO_BENCH_E2E_REPLICAS", "0"))
# Square frame edge: 640 is the serving shape, but it is only run
# BY DEFAULT on an accelerator mesh.  On CPU, llama3-1b at 640x640
# runs minutes per frame: r08 ran this section at 640 (r07's run had
# exported AIKO_BENCH_E2E_IMAGE=224) and pipeline_e2e_p99_ms blew up
# 135x (1533 -> 206992 ms), dragging neighbouring sections with it
# (the gateway interactive p99 "regression", 38 -> 254 ms, reproduces
# at 37.6 ms in isolation at the same commit).  Auto-sizing by
# backend keeps the default round runnable on every mesh; an explicit
# AIKO_BENCH_E2E_IMAGE always wins.


def _e2e_image_default() -> int:
    try:
        import jax
        platform = jax.default_backend()
    except Exception:                       # pragma: no cover
        platform = "cpu"
    return 640 if platform in ("tpu", "gpu") else 224


E2E_IMAGE = int(os.environ.get("AIKO_BENCH_E2E_IMAGE", "0")) \
    or _e2e_image_default()


def bench_pipeline_e2e() -> dict:
    import numpy as np
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()

    definition = {
        "version": 0, "name": "bench_e2e", "runtime": "jax",
        "graph": ["(DET (CAP (LLM)))"],
        # transfer_guard=disallow: an implicit host sync on the
        # device-element path FAILS the run (and shows up in
        # swag_host_transfers) instead of silently halving fps;
        # device_inflight=3 bounds async dispatch at triple buffering.
        "parameters": {"transfer_guard": "disallow",
                       "device_inflight": 3},
        "elements": [
            # lint: image/overlay are response-swag deliverables, not
            # graph inputs -- dead-output is the point here.
            element("DET", "Detector", ["image"],
                    ["image", "overlay", "detections"],
                    module="aiko_services_tpu.elements.detect",
                    lint=["dead-output"]),
            element("CAP", "DetectionCaption", ["detections"], ["text"],
                    module="aiko_services_tpu.elements.llm"),
            element("LLM", "LLM", ["text"], ["text"],
                    # The serving-shaped decode config: llama3-1b-class
                    # weights, int8, fused blocks (3 in flight).
                    # decode_block=16 measured better than 32 here
                    # (9.8 vs 4.9 device fps across two windows): with
                    # the whole 32-token budget in one block the
                    # pipeline holds only one block in flight per wave,
                    # so retires cannot overlap the next dispatch.
                    # max_slots=24: every in-flight frame's request
                    # decodes in ONE device batch (decode is
                    # weight-HBM-bound at 512 ctx, so 24 rows cost
                    # nearly the same per step as 8) -- one wave of
                    # fused blocks instead of three.
                    {"model": E2E_MODEL, "max_seq": 512,
                     "quantize": "int8", "decode_block": 16,
                     "inflight": 3, "max_new_tokens": 32,
                     "max_slots": E2E_FRAMES},
                    module="aiko_services_tpu.elements.llm"),
        ]}
    if E2E_REPLICAS > 0:
        definition["elements"][0]["placement"] = \
            {"devices": 1, "replicas": E2E_REPLICAS}
    # Create-time pre-flight cost (ISSUE 6): the full dataflow +
    # residency lint over this e2e definition, cold AST cache --
    # the acceptance bar is < 100 ms so strict pre-flight is free at
    # `pipeline create` scale.
    from aiko_services_tpu.analysis import ModuleIndex, lint_definition
    from aiko_services_tpu.pipeline import parse_pipeline_definition

    parsed = parse_pipeline_definition(definition)
    preflight_report = lint_definition(parsed, ModuleIndex())
    preflight_ms = round(preflight_report.elapsed_ms, 1)

    pipeline = Pipeline(parsed, runtime=runtime)

    rng = np.random.default_rng(0)
    responses: "queue.Queue" = queue.Queue()
    collected: list = []

    def pump(count):
        for _ in range(count):
            image = rng.integers(0, 255, (E2E_IMAGE, E2E_IMAGE, 3),
                                 dtype=np.uint8)
            pipeline.process_frame_local({"image": image},
                                         stream_id="bench_e2e",
                                         queue_response=responses)

    def drain(target):
        while not responses.empty():
            *_, metrics, okay, _diag = responses.get()
            collected.append((metrics, okay))
        return len(collected) >= target

    # Warm EVERY micro-batch bucket the run can hit (the Detector
    # flushes parked bursts as batched dispatches padded to power-of-two
    # buckets): waves of 8/4/2/1 compile buckets 8, 4, 2 and 1 -- plus
    # the LLM's batched-admission buckets -- outside the timed window.
    # The first wave carries the bulk of the jit compiles (detector
    # buckets, llama3-1b prefill/decode blocks); from a cold compile
    # cache that is minutes, so the warmup budget is generous -- it
    # buys a compile-free timed window.
    warmed = 0
    for index, wave in enumerate((8, 4, 2, 1)):
        pump(wave)
        warmed += wave
        runtime.run(until=lambda: drain(warmed),
                    timeout=1800.0 if index == 0 else 600.0)
    if len(collected) < warmed:
        runtime.terminate()
        return {"pipeline_e2e_error":
                f"warmup stalled at {len(collected)}/{warmed}"}
    collected.clear()
    if pipeline.telemetry is not None:
        # Percentiles must describe the timed passes, not the warmup's
        # compile frames.
        pipeline.telemetry.registry.reset()

    def timed_best_of(passes, pump_fn):
        """Run ``passes`` timed 24-frame passes, keep the fastest
        COMPLETE one.  Best-of-N because a transient stall during the
        ~3-10 s window can halve the recorded figure (observed
        1.5-7.7 fps same-day on identical code); a pass that
        fails transiently is ignored when an earlier pass already
        succeeded.  Returns ((elapsed, frames) or None, error)."""
        best = None
        error = None
        for _ in range(passes):
            collected.clear()
            start = time.perf_counter()
            pump_fn(E2E_FRAMES)
            runtime.run(until=lambda: drain(E2E_FRAMES), timeout=900.0)
            elapsed = time.perf_counter() - start
            okay_count = sum(1 for _, okay in collected if okay)
            if not collected or okay_count < len(collected) \
                    or len(collected) < E2E_FRAMES:
                error = (f"{okay_count} ok of {len(collected)} "
                         f"completed / {E2E_FRAMES} pumped "
                         f"in {elapsed:.0f}s")
                # The stream may have been destroyed by a frame error;
                # stop rather than pump into a broken stream.
                break
            if best is None or elapsed < best[0]:
                best = (elapsed, list(collected))
        return best, error

    best, error = timed_best_of(3, pump)
    if best is None:
        runtime.terminate()
        return {"pipeline_e2e_error": error}
    elapsed, snapshot = best
    host_elapsed, host_snapshot = elapsed, snapshot

    def p50(key, rows=None):
        return metrics_p50(rows or snapshot, key)

    result = {
        "pipeline_e2e_fps": round(len(snapshot) / elapsed, 2),
        "pipeline_e2e_model": E2E_MODEL,
        "pipeline_e2e_replicas": E2E_REPLICAS,
        "pipeline_e2e_image": E2E_IMAGE,
        "pipeline_e2e_frames": len(snapshot),
        "pipeline_e2e_p50_ms": round(p50("time_pipeline") * 1000, 1),
        "pipeline_e2e_p50_detect_ms": round(p50("DET_time") * 1000, 1),
        "pipeline_e2e_p50_caption_ms": round(p50("CAP_time") * 1000, 2),
        "pipeline_e2e_p50_llm_ms": round(p50("LLM_time") * 1000, 1),
        "pipeline_preflight_ms": preflight_ms,
    }

    # -- device-resident-input variant: the SAME engine
    # path, but frames reference a pre-uploaded ring of device-resident
    # images -- no per-frame 1.2 MB host->device upload -- and all
    # frames are pumped at once so the async stages
    # (park/resume Detector + cross-frame-batching LLM) overlap.  The
    # residual per-frame cost is the engine walk + the small
    # boxes/text fetches; this is the number that exposes the
    # FRAMEWORK's own overhead rather than the upload's.
    import jax
    import jax.numpy as jnp
    ring = [jax.device_put(jnp.asarray(
        rng.integers(0, 255, (E2E_IMAGE, E2E_IMAGE, 3),
                     dtype=np.uint8)))
        for _ in range(8)]
    jax.block_until_ready(ring)
    collected.clear()

    def pump_device(count):
        for i in range(count):
            pipeline.process_frame_local({"image": ring[i % len(ring)]},
                                         stream_id="bench_e2e",
                                         queue_response=responses)

    pump_device(E2E_WARMUP)
    runtime.run(until=lambda: drain(E2E_WARMUP), timeout=600.0)
    device_best, device_error = timed_best_of(3, pump_device)
    # Device-resident swag accounting: implicit transfers (violations
    # of the residency contract -- 0 when healthy; the run FAILS under
    # transfer_guard=disallow if one sneaks onto the device path) and
    # engine-explicit counted fetches.
    transfer = pipeline.transfer_stats()
    result["swag_host_transfers"] = transfer["implicit"]
    result["swag_explicit_fetches"] = transfer["explicit"]
    # Telemetry-plane percentiles (ISSUE 4): p99s out of the streaming
    # histograms, not just medians of one pass -- the tail is where
    # host stalls and batching stalls live.  Cumulative over the
    # timed passes (registry reset after warmup).
    if pipeline.telemetry is not None:
        registry = pipeline.telemetry.registry

        def hist(name, q, labels=None):
            value = registry.quantile(name, q, labels, windowed=False)
            return None if value is None else round(value, 2)

        result["pipeline_e2e_p99_ms"] = hist("frame_latency_ms", 0.99)
        for element_name, tag in (("DET", "detect"), ("CAP", "caption"),
                                  ("LLM", "llm")):
            result[f"pipeline_e2e_p99_{tag}_ms"] = hist(
                "element_latency_ms", 0.99, {"element": element_name})
        previous = _previous_bench() \
            if E2E_MODEL == "llama3-1b" and E2E_REPLICAS == 0 \
            else {}              # never ratio an off-default profile
        #                          (smoke model, replicated detect)
        #                          against the default prior
        if previous.get("pipeline_e2e_image") not in (None, E2E_IMAGE):
            previous = {}        # image-size change (e.g. the CPU
        #                          auto-size) invalidates the ratio:
        #                          r08 ratioed a 640 round against a
        #                          224 prior and reported 135x
        for key in ("pipeline_e2e_p99_ms", "pipeline_e2e_p99_detect_ms",
                    "pipeline_e2e_p99_caption_ms",
                    "pipeline_e2e_p99_llm_ms"):
            prior = previous.get(key)
            if prior and result.get(key):
                result[f"{key}_vs_baseline"] = round(
                    result[key] / prior, 2)
        # Critical-path attribution (ISSUE 10): the aggregate bucket
        # split over the run's traces -- the e2e/device fps gap ships
        # with a NAMED cause (detect compute vs queue wait vs hop vs
        # fetch ...), not just per-element percentiles.
        explanation = pipeline.explain(top_k=3)
        if explanation.get("top"):
            top = explanation["top"][0]
            result["pipeline_e2e_top_bucket"] = \
                f"{top['stage']}:{top['bucket']}"
            result["pipeline_e2e_bucket_ms"] = {
                bucket: round(ms, 1) for bucket, ms
                in explanation["buckets"].items()}
            result["pipeline_e2e_attribution_coverage"] = \
                explanation.get("coverage")
    runtime.terminate()
    if device_best is None:
        result["pipeline_e2e_device_error"] = device_error
        return result
    elapsed, snapshot = device_best
    device_fps = len(snapshot) / elapsed
    result.update({
        "pipeline_e2e_device_fps": round(device_fps, 2),
        "pipeline_e2e_device_p50_ms": round(
            p50("time_pipeline", snapshot) * 1000, 1)})
    # Host/device gap, whole-pipeline and per-element: the per-frame
    # cost the host-driven path pays over the device-resident path
    # (uploads, host mapping, response marshalling).  The per-element
    # keys localize a regression to the stage that grew it.
    host_fps = len(host_snapshot) / host_elapsed
    if host_fps > 0 and device_fps > 0:
        result["pipeline_e2e_host_overhead_ms"] = round(
            (1.0 / host_fps - 1.0 / device_fps) * 1000, 1)
    for element_name in ("DET", "CAP", "LLM"):
        gap = (p50(f"{element_name}_time", host_snapshot)
               - p50(f"{element_name}_time", snapshot))
        result[f"pipeline_e2e_gap_{element_name.lower()}_ms"] = round(
            gap * 1000, 2)
    return result


# ---------------------------------------------------------------------------
# 4b. Fused device-segment compilation (ISSUE 2): the same engine over a
#     3-element synchronous device chain (ImageResize x2 + sync
#     Detector), ``fuse: auto`` vs ``fuse: off`` side by side.  The gap
#     is pure dispatch/segmentation overhead -- the cost the fuser
#     removes -- reported per frame as
#     ``pipeline_e2e_dispatch_overhead_ms``, with jit-cache and
#     cold/warm compile-time keys so recompile regressions and the
#     persistent compile cache's effect are visible across rounds.

FUSION_FRAMES = 24
FUSION_PASSES = 3


def _previous_bench() -> dict:
    """Latest recorded BENCH_r*.json, for the ``*_vs_baseline`` deltas
    on keys first recorded by this round's new sections.

    Records come in two shapes: the raw JSON line bench.py prints, or
    the driver's wrapper ``{n, cmd, rc, tail, parsed}`` whose ``tail``
    holds the (possibly front-truncated) printed line -- unwrap that,
    re-prefixing ``{"`` when the capture cut mid-key, so the deltas
    keep working against driver-recorded rounds."""
    import glob
    records = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json")))
    if not records:
        return {}
    try:
        with open(records[-1]) as fh:
            record = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {}
    if not isinstance(record, dict):
        return {}
    if "tail" not in record or "metric" in record:
        return record                            # raw bench record
    if isinstance(record.get("parsed"), dict):
        return record["parsed"]
    for line in reversed(str(record.get("tail", "")).splitlines()):
        line = line.strip()
        if not line.endswith("}"):
            continue
        for candidate in (line, '{"' + line):
            try:
                parsed = json.loads(candidate)
            except json.JSONDecodeError:
                continue
            if isinstance(parsed, dict):
                return parsed
        break
    return {}


def bench_pipeline_fusion() -> dict:
    import numpy as np
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()

    def definition(mode):
        return {
            "version": 0, "name": f"bench_fusion_{mode}",
            "runtime": "jax",
            "graph": ["(R1 (R2 (DET)))"],
            # disallow: the fused path must stay transfer-clean; the
            # Detector's slate postprocess rides the engine's counted
            # finalize fetch.
            "parameters": {"transfer_guard": "disallow",
                           "device_inflight": 3, "fuse": mode},
            "elements": [
                element("R1", "ImageResize", ["image"], ["image"],
                        {"width": 512, "height": 512,
                         "synchronous": True},
                        module="aiko_services_tpu.elements.image"),
                element("R2", "ImageResize", ["image"], ["image"],
                        {"width": 640, "height": 640,
                         "synchronous": True},
                        module="aiko_services_tpu.elements.image"),
                element("DET", "Detector", ["image"],
                        ["image", "overlay", "detections"],
                        {"synchronous": True},
                        module="aiko_services_tpu.elements.detect"),
            ]}

    rng = np.random.default_rng(0)
    frames = [rng.integers(0, 255, (576, 576, 3), dtype=np.uint8)
              for _ in range(4)]

    def run_mode(mode):
        pipeline = Pipeline(definition(mode), runtime=runtime)
        responses: "queue.Queue" = queue.Queue()
        collected: list = []

        def pump(count):
            for i in range(count):
                pipeline.process_frame_local(
                    {"image": frames[i % len(frames)]},
                    stream_id=f"fusion_{mode}",
                    queue_response=responses)

        def drain(target):
            while not responses.empty():
                *_, metrics, okay, _diag = responses.get()
                collected.append((metrics, okay))
            return len(collected) >= target

        timings = {}
        # Cold/warm per-frame wall time: frame 1 pays the segment trace
        # + XLA compile (or a persistent-cache hit when the compile
        # cache is warm), frame 2 replays.
        for key in ("cold", "warm"):
            start = time.perf_counter()
            pump(1)
            runtime.run(until=lambda: drain(len(collected) + 1),
                        timeout=1800.0)
            timings[key] = (time.perf_counter() - start) * 1000.0
        if len(collected) < 2 or not all(ok for _, ok in collected):
            return None, timings, {}, (
                f"{mode} warmup stalled at {len(collected)}/2")

        best = None
        for _ in range(FUSION_PASSES):
            collected.clear()
            start = time.perf_counter()
            pump(FUSION_FRAMES)
            runtime.run(until=lambda: drain(FUSION_FRAMES),
                        timeout=900.0)
            elapsed = time.perf_counter() - start
            if len(collected) < FUSION_FRAMES \
                    or not all(ok for _, ok in collected):
                return None, timings, {}, f"{mode} pass incomplete"
            if best is None or elapsed < best[0]:
                best = (elapsed, list(collected))
        share = {key: pipeline.share.get(key) for key in
                 ("fused_segments", "fused_dispatches",
                  "jit_cache_hits", "jit_cache_misses",
                  "jit_cache_entries")}
        pipeline.stop()
        return best, timings, share, None

    result: dict = {}
    fused, fused_timings, fused_share, error = run_mode("auto")
    if error:
        runtime.terminate()
        return {"pipeline_fusion_error": error}
    off, _off_timings, _off_share, error = run_mode("off")
    runtime.terminate()
    if error:
        return {"pipeline_fusion_error": error}

    def per_frame(rows, key):
        values = [metrics.get(key, 0) for metrics, _ in rows]
        return sum(values) / max(1, len(values))

    fused_elapsed, fused_rows = fused
    off_elapsed, off_rows = off
    fused_fps = FUSION_FRAMES / fused_elapsed
    off_fps = FUSION_FRAMES / off_elapsed
    result.update({
        "pipeline_e2e_fused_fps": round(fused_fps, 2),
        "pipeline_e2e_fuse_off_fps": round(off_fps, 2),
        # The dispatch/segmentation overhead the fuser removes: the
        # per-frame cost gap between the per-element walk and the
        # single-dispatch segment walk of the SAME chain.
        "pipeline_e2e_dispatch_overhead_ms": round(
            (1.0 / off_fps - 1.0 / fused_fps) * 1000.0, 2),
        "fused_segments": fused_share.get("fused_segments"),
        "fused_dispatches_per_frame": round(
            per_frame(fused_rows, "device_dispatches"), 2),
        "fuse_off_dispatches_per_frame": round(
            per_frame(off_rows, "device_dispatches"), 2),
        "jit_cache_hits": fused_share.get("jit_cache_hits"),
        "jit_cache_misses": fused_share.get("jit_cache_misses"),
        "jit_cache_entries": fused_share.get("jit_cache_entries"),
        "fused_compile_cold_ms": round(fused_timings.get("cold", 0), 1),
        "fused_compile_warm_ms": round(fused_timings.get("warm", 0), 1),
    })
    # Deltas against the previous recorded round, so the next bench
    # shows whether the dispatch-overhead win and compile times moved.
    previous = _previous_bench()
    for key in ("pipeline_e2e_dispatch_overhead_ms",
                "pipeline_e2e_fused_fps",
                "fused_compile_cold_ms", "fused_compile_warm_ms"):
        prior = previous.get(key)
        if prior:
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4b'. Binary data plane (ISSUE 9): a remote-stage hop through the real
#      engine with a 6 MB uint8 frame, the tensor-pipe path vs the
#      MQTT/base64 path side by side -- per-hop round-trip p50/p99,
#      wire bytes per frame (forward + response vs 2x raw payload), and
#      cross-process pipelined e2e fps.

TRANSPORT_TENSOR_SHAPE = (1024, 2048, 3)          # 6 MB uint8, exactly
TRANSPORT_HOP_FRAMES = {"tensor_pipe": 10, "mqtt": 6}
TRANSPORT_FPS_FRAMES = 12


def bench_pipeline_transport() -> dict:
    import numpy as np
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.services import Registrar
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()
    Registrar(runtime=runtime, primary_search_timeout=0.05)

    def remote_pair(mode):
        identity = element("ID", "Identity", ["x"], ["x"],
                           module="aiko_services_tpu.elements.common")
        back = Pipeline(
            {"version": 0, "name": f"bench_tp_back_{mode}",
             "runtime": "jax", "graph": ["(ID)"],
             "parameters": {"data_plane": mode},
             "elements": [identity]}, runtime=runtime)
        front = Pipeline(
            {"version": 0, "name": f"bench_tp_front_{mode}",
             "runtime": "jax", "graph": ["(fwd)"],
             "parameters": {"data_plane": mode},
             "elements": [
                 {"name": "fwd", "input": [{"name": "x"}],
                  "output": [{"name": "x"}],
                  "deploy": {"remote":
                             {"name": f"bench_tp_back_{mode}"}}}]},
            runtime=runtime)
        stage = front.graph.get_node("fwd").element
        runtime.run(until=lambda: stage.remote_topic_path is not None,
                    timeout=30.0)
        return front, back

    tensor = np.random.default_rng(0).integers(
        0, 255, TRANSPORT_TENSOR_SHAPE, dtype=np.uint8)
    raw_round_trip = 2 * tensor.nbytes    # forward + response payloads

    def run_mode(mode):
        front, back = remote_pair(mode)
        responses: "queue.Queue" = queue.Queue()

        def round_trip():
            front.process_frame_local({"x": tensor}, stream_id="s",
                                      queue_response=responses)
            runtime.run(until=lambda: not responses.empty(),
                        timeout=300.0)
            row = responses.get()
            if not row[4]:
                raise RuntimeError(f"{mode} hop failed: {row[5]}")

        start = time.perf_counter()
        round_trip()                      # warm: discovery + first hop
        warm_ms = (time.perf_counter() - start) * 1000.0
        laps = []
        for _ in range(TRANSPORT_HOP_FRAMES[mode]):
            start = time.perf_counter()
            round_trip()
            laps.append((time.perf_counter() - start) * 1000.0)
        laps.sort()
        # Pipelined: every frame in flight at once, wall-clock fps.
        start = time.perf_counter()
        for _ in range(TRANSPORT_FPS_FRAMES):
            front.process_frame_local({"x": tensor}, stream_id="s",
                                      queue_response=responses)
        done: list = []

        def drained():
            while not responses.empty():
                done.append(responses.get())
            return len(done) >= TRANSPORT_FPS_FRAMES

        runtime.run(until=drained, timeout=600.0)
        fps = len(done) / (time.perf_counter() - start)
        stats_front = front.data_plane_stats()
        stats_back = back.data_plane_stats()
        frames = (stats_front["pipe_frames"] + stats_front["mqtt_frames"]
                  + stats_back["pipe_frames"]
                  + stats_back["mqtt_frames"]) / 2.0
        wire_bytes = (stats_front["pipe_bytes"]
                      + stats_front["mqtt_bytes"]
                      + stats_back["pipe_bytes"]
                      + stats_back["mqtt_bytes"])
        per_frame = wire_bytes / max(1.0, frames)
        front.stop()
        back.stop()
        return {"p50": laps[len(laps) // 2], "p99": laps[-1],
                "warm_ms": warm_ms, "fps": fps,
                "bytes_per_frame": per_frame,
                "ratio": per_frame / raw_round_trip,
                "fallbacks": stats_front["fallbacks"]
                + stats_back["fallbacks"],
                "pipe_frames": stats_front["pipe_frames"]
                + stats_back["pipe_frames"]}

    result: dict = {}
    try:
        pipe = run_mode("tensor_pipe")
        mqtt = run_mode("mqtt")
    except Exception as error:
        runtime.terminate()
        return {"pipeline_transport_error":
                f"{type(error).__name__}: {error}"}
    runtime.terminate()
    result.update({
        "remote_hop_p50_ms": round(pipe["p50"], 2),
        "remote_hop_p99_ms": round(pipe["p99"], 2),
        "remote_hop_p50_ms_mqtt": round(mqtt["p50"], 2),
        "remote_hop_p99_ms_mqtt": round(mqtt["p99"], 2),
        # >= 2x is the ISSUE 9 acceptance bar for the pipe path.
        "remote_hop_speedup_vs_mqtt": round(
            mqtt["p50"] / max(pipe["p50"], 1e-6), 2),
        "remote_hop_bytes_per_frame": int(pipe["bytes_per_frame"]),
        "remote_hop_bytes_per_frame_mqtt": int(mqtt["bytes_per_frame"]),
        # wire bytes / raw payload bytes (forward + response): ~1.005x
        # on the pipe vs ~1.33x base64 -- the byte-tax acceptance bar.
        "remote_hop_payload_ratio": round(pipe["ratio"], 4),
        "remote_hop_payload_ratio_mqtt": round(mqtt["ratio"], 4),
        "pipeline_remote_e2e_fps": round(pipe["fps"], 2),
        "pipeline_remote_e2e_fps_mqtt": round(mqtt["fps"], 2),
        "data_plane_pipe_frames": pipe["pipe_frames"],
        "data_plane_fallbacks": pipe["fallbacks"],
    })
    previous = _previous_bench()
    for key in ("remote_hop_p50_ms", "remote_hop_p99_ms",
                "remote_hop_payload_ratio", "pipeline_remote_e2e_fps",
                "remote_hop_speedup_vs_mqtt"):
        prior = previous.get(key)
        if prior:
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4c. Stage-parallel execution (ISSUE 3): a 2-stage PLACED pipeline
#     (detect submesh -> llm submesh) through the real engine, the
#     stage-parallel scheduler vs the serial stage-by-stage walk
#     (``stage_pipeline: off``) side by side.  The synthetic StageWork
#     stages carry a host-blocking wait standing in for a stage whose
#     wall time is waiting on its chips -- exactly the shape the serial
#     walk serializes and per-stage workers overlap.  Records per-stage
#     occupancy over the timed window, the hop dispatch cost, and the
#     hop-overlap window (time a frame's resharded inputs sat behind
#     the previous frame's stage compute -- hop riding along for free).

STAGE_FRAMES = 24
STAGE_BUSY_MS = 20.0


def bench_pipeline_stages() -> dict:
    import numpy as np
    import jax

    if len(jax.devices()) < 2:
        return {"pipeline_stages_skipped":
                f"needs >= 2 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()
    n = len(jax.devices())

    def definition(mode):
        return {
            "version": 0, "name": f"bench_stages_{mode}",
            "runtime": "jax",
            "graph": ["(detect llm)"],
            "parameters": {"transfer_guard": "disallow",
                           "device_inflight": 3,
                           "stage_pipeline": mode},
            "elements": [
                {**element("detect", "StageWork", ["x"], ["x"],
                           {"busy_ms": STAGE_BUSY_MS, "factor": 2.0}),
                 "placement": {"devices": n // 2}},
                {**element("llm", "StageWork", ["x"], ["x"],
                           {"busy_ms": STAGE_BUSY_MS, "factor": 3.0}),
                 "placement": {"devices": n - n // 2}},
            ]}

    rng = np.random.default_rng(0)
    frames = [rng.standard_normal((64, 64)).astype(np.float32)
              for _ in range(4)]

    def run_mode(mode):
        pipeline = Pipeline(definition(mode), runtime=runtime)
        responses: "queue.Queue" = queue.Queue()
        collected: list = []

        def pump(count):
            for i in range(count):
                pipeline.process_frame_local(
                    {"x": frames[i % len(frames)]},
                    stream_id=f"stages_{mode}",
                    queue_response=responses)

        def drain(target):
            while not responses.empty():
                collected.append(responses.get())
            return len(collected) >= target

        pump(4)                                     # warm the jits
        runtime.run(until=lambda: drain(4), timeout=600.0)
        if len(collected) < 4:
            pipeline.stop()
            return None, {}, f"{mode} warmup stalled"
        collected.clear()
        if pipeline.stage_scheduler is not None:
            pipeline.stage_scheduler.reset_window()
        if pipeline.telemetry is not None:
            pipeline.telemetry.registry.reset()     # timed pass only
        start = time.perf_counter()
        pump(STAGE_FRAMES)
        runtime.run(until=lambda: drain(STAGE_FRAMES), timeout=600.0)
        elapsed = time.perf_counter() - start
        stats = pipeline.stage_stats()
        if pipeline.telemetry is not None:
            registry = pipeline.telemetry.registry
            for q, tag in ((0.5, "p50"), (0.99, "p99")):
                value = registry.quantile("frame_latency_ms", q,
                                          windowed=False)
                if value is not None:
                    stats[f"pipeline_stages_{tag}_ms"] = round(value, 2)
            for stage in ("detect", "llm"):
                value = registry.quantile("element_latency_ms", 0.99,
                                          {"element": stage},
                                          windowed=False)
                if value is not None:
                    stats[f"stage_{stage}_p99_ms"] = round(value, 2)
        ordered = [row[1] for row in collected]
        okay = all(row[4] for row in collected)
        pipeline.stop()
        if len(collected) < STAGE_FRAMES or not okay:
            return None, {}, f"{mode} pass incomplete"
        rows = [(row[3], row[4]) for row in collected]
        return (elapsed, rows, ordered == sorted(ordered)), stats, None

    result: dict = {}
    pipelined, stage_stats, error = run_mode("auto")
    if error:
        runtime.terminate()
        return {"pipeline_stages_error": error}
    serial, _stats_off, error = run_mode("off")
    runtime.terminate()
    if error:
        return {"pipeline_stages_error": error}

    pipelined_elapsed, pipelined_rows, in_order = pipelined
    serial_elapsed, _serial_rows, _ = serial
    fps = STAGE_FRAMES / pipelined_elapsed
    serial_fps = STAGE_FRAMES / serial_elapsed
    result.update({
        "pipeline_stages_fps": round(fps, 2),
        "pipeline_stages_serial_fps": round(serial_fps, 2),
        # The acceptance ratio: steady-state throughput approaching the
        # slower stage's solo rate instead of the sum of both stages.
        "pipeline_stages_speedup": round(fps / serial_fps, 2)
        if serial_fps else None,
        "pipeline_stages_in_order": bool(in_order),
        "stage_occupancy_detect":
            stage_stats.get("detect", {}).get("occupancy"),
        "stage_occupancy_llm":
            stage_stats.get("llm", {}).get("occupancy"),
        # Hop dispatch cost on the loop (device_put is async) and the
        # overlap window the hop rides: queue time behind the previous
        # frame's stage compute.
        "stage_hop_dispatch_ms": round(
            metrics_p50(pipelined_rows, "llm_hop_ms"), 3),
        "hop_overlap_ms": round(
            metrics_p50(pipelined_rows, "llm_queue_ms"), 2),
    })
    # Histogram percentiles from the telemetry plane (timed pass only).
    for key in ("pipeline_stages_p50_ms", "pipeline_stages_p99_ms",
                "stage_detect_p99_ms", "stage_llm_p99_ms"):
        if key in stage_stats:
            result[key] = stage_stats.pop(key)
    previous = _previous_bench()
    for key in ("pipeline_stages_fps", "pipeline_stages_speedup",
                "hop_overlap_ms", "pipeline_stages_p50_ms",
                "pipeline_stages_p99_ms", "stage_detect_p99_ms",
                "stage_llm_p99_ms"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4b'. Flight recorder + critical-path attribution (ISSUE 10): the
#      always-on event ring's e2e fps cost (recorder on vs off on the
#      same stage-parallel pipeline -- the overhead gate is <= 1%), and
#      the aggregate bucket attribution (where the time went) for the
#      timed pass.

EXPLAIN_FRAMES = 32
EXPLAIN_PASSES = 3


def bench_pipeline_explain() -> dict:
    import numpy as np
    import jax

    if len(jax.devices()) < 2:
        return {"pipeline_explain_skipped":
                f"needs >= 2 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()
    n = len(jax.devices())

    def definition(mode):
        return {
            "version": 0, "name": f"bench_explain_{mode}",
            "runtime": "jax",
            "graph": ["(detect llm)"],
            "parameters": {"transfer_guard": "disallow",
                           "device_inflight": 3,
                           "recorder": mode},
            "elements": [
                {**element("detect", "StageWork", ["x"], ["x"],
                           {"busy_ms": STAGE_BUSY_MS, "factor": 2.0}),
                 "placement": {"devices": n // 2}},
                {**element("llm", "StageWork", ["x"], ["x"],
                           {"busy_ms": STAGE_BUSY_MS, "factor": 3.0}),
                 "placement": {"devices": n - n // 2}},
            ]}

    rng = np.random.default_rng(0)
    frames = [rng.standard_normal((64, 64)).astype(np.float32)
              for _ in range(4)]

    def run_mode(mode):
        pipeline = Pipeline(definition(mode), runtime=runtime)
        responses: "queue.Queue" = queue.Queue()
        collected: list = []

        def pump(count):
            for i in range(count):
                pipeline.process_frame_local(
                    {"x": frames[i % len(frames)]},
                    stream_id=f"explain_{mode}",
                    queue_response=responses)

        def drain(target):
            while not responses.empty():
                collected.append(responses.get())
            return len(collected) >= target

        pump(4)                                     # warm the jits
        runtime.run(until=lambda: drain(4), timeout=600.0)
        if len(collected) < 4:
            pipeline.stop()
            return None, None, f"{mode} warmup stalled"
        best = None
        for _ in range(EXPLAIN_PASSES):             # min-of-N denoises
            collected.clear()
            start = time.perf_counter()
            pump(EXPLAIN_FRAMES)
            runtime.run(until=lambda: drain(EXPLAIN_FRAMES),
                        timeout=600.0)
            elapsed = time.perf_counter() - start
            if len(collected) < EXPLAIN_FRAMES \
                    or not all(row[4] for row in collected):
                pipeline.stop()
                return None, None, f"{mode} pass incomplete"
            best = elapsed if best is None else min(best, elapsed)
        explanation = pipeline.explain(top_k=3)
        pipeline.stop()
        return best, explanation, None

    result: dict = {}
    off_elapsed, _, error = run_mode("off")
    if error:
        runtime.terminate()
        return {"pipeline_explain_error": error}
    on_elapsed, explanation, error = run_mode("on")
    runtime.terminate()
    if error:
        return {"pipeline_explain_error": error}
    fps_off = EXPLAIN_FRAMES / off_elapsed
    fps_on = EXPLAIN_FRAMES / on_elapsed
    result.update({
        "pipeline_explain_fps_recorder_off": round(fps_off, 2),
        "pipeline_explain_fps_recorder_on": round(fps_on, 2),
        # The gate: <= 1% (negative = within noise, recorder free).
        "pipeline_explain_recorder_overhead_pct": round(
            (fps_off - fps_on) / fps_off * 100.0, 2) if fps_off else None,
    })
    if explanation and explanation.get("top"):
        top = explanation["top"][0]
        result["pipeline_explain_top_bucket"] = \
            f"{top['stage']}:{top['bucket']}"
        result["pipeline_explain_buckets"] = {
            bucket: round(ms, 1) for bucket, ms
            in explanation["buckets"].items()}
        result["pipeline_explain_coverage"] = explanation.get("coverage")
    previous = _previous_bench()
    for key in ("pipeline_explain_fps_recorder_on",
                "pipeline_explain_recorder_overhead_pct"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4b. Failure recovery under injected faults (ISSUE 5): how fast the
#     pipeline recovers from a mid-stream chip death (replace + frame
#     replay), what throughput costs under overload shedding, and the
#     remote circuit breaker's open -> half-open -> close walk.

FAULT_FRAMES = 24


def bench_pipeline_faults() -> dict:
    import numpy as np
    import jax

    if len(jax.devices()) < 4:
        return {"pipeline_faults_skipped":
                f"needs >= 4 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.services import Registrar
    from aiko_services_tpu.transport import reset_broker

    result: dict = {}
    n = len(jax.devices())
    rng = np.random.default_rng(0)
    frames = [rng.standard_normal((32, 32)).astype(np.float32)
              for _ in range(4)]

    def fresh_runtime():
        reset_broker()
        reset_process()
        runtime = init_process(transport="loopback")
        runtime.initialize()
        return runtime

    def stage_element(name, devices, busy_ms=STAGE_BUSY_MS):
        return {**element(name, "StageWork", ["x"], ["x"],
                          {"busy_ms": busy_ms, "factor": 2.0}),
                "placement": {"devices": devices}}

    def run_frames(runtime, pipeline, count, stream_id, timeout=300.0):
        responses: "queue.Queue" = queue.Queue()
        collected: list = []
        for i in range(count):
            pipeline.process_frame_local({"x": frames[i % len(frames)]},
                                         stream_id=stream_id,
                                         queue_response=responses)

        def drain():
            while not responses.empty():
                collected.append(responses.get())
            return len(collected) >= count
        runtime.run(until=drain, timeout=timeout)
        return collected

    # -- chip-death recovery: wall time from the replacement event to
    # the first frame completing on the replacement submeshes.
    runtime = fresh_runtime()
    pipeline = Pipeline(
        {"version": 0, "name": "bench_faults", "runtime": "jax",
         "graph": ["(detect llm)"],
         "parameters": {"transfer_guard": "disallow",
                        "replay_limit": 3},
         "elements": [stage_element("detect", n // 2),
                      stage_element("llm", n - n // 2)]},
        runtime=runtime)
    warm = run_frames(runtime, pipeline, 4, "warm")
    if len(warm) < 4:
        runtime.terminate()
        return {"pipeline_faults_error": "warmup stalled"}
    marks: dict = {}
    pipeline.add_hook_handler(
        "pipeline.replacement:0",
        lambda component, hook, variables:
            marks.setdefault("replaced", time.perf_counter()))
    dead = list(pipeline.stage_placement.plans["detect"]
                .mesh.devices.flat)[:2]
    responses: "queue.Queue" = queue.Queue()
    collected: list = []
    for i in range(FAULT_FRAMES):
        pipeline.process_frame_local({"x": frames[i % len(frames)]},
                                     stream_id="kill",
                                     queue_response=responses)
    pipeline.post_self("replace_failed_devices", [dead], delay=0.05)

    def drain_kill():
        while not responses.empty():
            collected.append(responses.get())
            if "replaced" in marks and "recovered" not in marks:
                marks["recovered"] = time.perf_counter()
        return len(collected) >= FAULT_FRAMES
    runtime.run(until=drain_kill, timeout=300.0)
    replayed = pipeline.share.get("frames_replayed", 0)
    okay = all(row[4] for row in collected)
    runtime.terminate()
    if len(collected) < FAULT_FRAMES or not okay:
        return {"pipeline_faults_error": "chip-death pass incomplete"}
    if "replaced" in marks and "recovered" in marks:
        result["fault_recovery_ms"] = round(
            (marks["recovered"] - marks["replaced"]) * 1000.0, 1)
    result["fault_frames_replayed"] = replayed

    # -- overload shedding: fps and shed fraction with a queue-depth
    # bound sized to shed roughly 10% of a 2x ingest burst.
    runtime = fresh_runtime()
    pipeline = Pipeline(
        {"version": 0, "name": "bench_shed", "runtime": "jax",
         "graph": ["(detect llm)"],
         # The whole burst lands before the first completion (ingest
         # turns are instant, stage work is not), so a burst of N with
         # limit N-3 sheds ~3 frames: the ~10%-shedding operating
         # point the fps figure is quoted at.
         "parameters": {"transfer_guard": "disallow",
                        "stage_inflight": 1,
                        "overload_policy": "shed_oldest",
                        "overload_limit": FAULT_FRAMES - 3},
         "elements": [stage_element("detect", n // 2),
                      stage_element("llm", n - n // 2)]},
        runtime=runtime)
    warm = run_frames(runtime, pipeline, 4, "warm")
    if len(warm) < 4:
        runtime.terminate()
        return result | {"pipeline_faults_error": "shed warmup stalled"}
    start = time.perf_counter()
    rows = run_frames(runtime, pipeline, FAULT_FRAMES, "shed")
    elapsed = time.perf_counter() - start
    shed = pipeline.share.get("frames_shed", 0)
    in_order = [row[1] for row in rows] == sorted(row[1] for row in rows)
    runtime.terminate()
    if len(rows) == FAULT_FRAMES:
        delivered = len([row for row in rows if row[4]])
        result.update({
            "fault_shed_fps": round(delivered / elapsed, 2),
            "fault_shed_fraction": round(shed / FAULT_FRAMES, 3),
            "fault_shed_in_order": bool(in_order)})

    # -- circuit breaker walk: deadline misses open it, the half-open
    # probe recloses it; latencies come off the recorded transitions.
    runtime = fresh_runtime()
    Registrar(runtime=runtime, primary_search_timeout=0.05)
    back = Pipeline(
        {"version": 0, "name": "bench_back", "runtime": "jax",
         "graph": ["(inc)"],
         "elements": [element("inc", "Increment", ["x"], ["x"])]},
        runtime=runtime)
    front = Pipeline(
        {"version": 0, "name": "bench_front", "runtime": "jax",
         "graph": ["(inc fwd)"],
         "parameters": {"frame_deadline_ms": 150,
                        "breaker_threshold": 2,
                        "breaker_cooldown_ms": 200},
         "elements": [element("inc", "Increment", ["x"], ["x"]),
                      remote("fwd", "bench_back", ["x"], ["x"])]},
        runtime=runtime)
    responses = queue.Queue()
    front.create_stream_local("w", {"frame_deadline_ms": 0},
                              queue_response=responses)
    front.ingest_local("w", {"x": 0}, queue_response=responses)
    runtime.run(until=lambda: not responses.empty(), timeout=30.0)
    if responses.empty() or not responses.get()[4]:
        runtime.terminate()
        return result | {"pipeline_faults_error": "breaker warmup "
                         "stalled"}
    front.create_stream_local("b", queue_response=responses)
    front.arm_faults({"rules": [
        {"point": "wire_drop", "target": "process_frame_response",
         "count": 2}]})
    deadline = time.monotonic() + 30.0

    def breaker_closed_again():
        breaker = front.breakers.get("fwd")
        return breaker is not None and len(breaker.transitions) >= 3 \
            and breaker.transitions[-1][0] == "closed"

    while time.monotonic() < deadline and not breaker_closed_again():
        front.ingest_local("b", {"x": 0}, queue_response=responses)
        runtime.run(until=lambda: not responses.empty(), timeout=10.0)
        while not responses.empty():
            responses.get()
        time.sleep(0.05)
    breaker = front.breakers.get("fwd")
    if breaker is not None and breaker_closed_again():
        walk = breaker.transitions
        states = [state for state, _ in walk]
        opened = walk[states.index("open")][1]
        half = walk[states.index("half_open")][1]
        closed = walk[len(states) - 1 - states[::-1].index("closed")][1]
        result.update({
            "breaker_walk": "->".join(states),
            "breaker_open_to_halfopen_ms": round(
                (half - opened) * 1000.0, 1),
            "breaker_halfopen_to_close_ms": round(
                (closed - half) * 1000.0, 1),
            "breaker_deadline_misses":
                front.share.get("deadline_misses", 0)})
    else:
        result["pipeline_faults_error"] = "breaker never reclosed"
    runtime.terminate()

    previous = _previous_bench()
    for key in ("fault_recovery_ms", "fault_shed_fps",
                "breaker_open_to_halfopen_ms",
                "breaker_halfopen_to_close_ms"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4e. Replicated stages (ISSUE 7): dp-N fps scaling of a replicated
#     stage (the designed path to the >= 0.8 e2e/device fps ratio --
#     detect is the e2e bottleneck and ``replicas`` lets it scale out),
#     and the robustness dividend measured head-to-head:
#     ``replica_failover_ms`` (kill one of N under load, peers keep
#     serving) vs ``replica_full_replace_ms`` (the stop-the-world
#     rebuild the same load pays without replication).

REPLICA_FRAMES = 24


def bench_pipeline_replicas() -> dict:
    import numpy as np
    import jax

    n = len(jax.devices())
    if n < 4:
        return {"pipeline_replicas_skipped":
                f"needs >= 4 devices, have {n}"}
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    result: dict = {}
    rng = np.random.default_rng(0)
    frames = [rng.standard_normal((32, 32)).astype(np.float32)
              for _ in range(4)]

    def fresh_runtime():
        reset_broker()
        reset_process()
        runtime = init_process(transport="loopback")
        runtime.initialize()
        return runtime

    def run_frames(runtime, pipeline, count, stream_id, on_row=None,
                   timeout=300.0):
        responses: "queue.Queue" = queue.Queue()
        collected: list = []
        for i in range(count):
            pipeline.process_frame_local({"x": frames[i % len(frames)]},
                                         stream_id=stream_id,
                                         queue_response=responses)

        def drain():
            while not responses.empty():
                collected.append(responses.get())
                if on_row is not None:
                    on_row()
            return len(collected) >= count
        runtime.run(until=drain, timeout=timeout)
        return collected

    # -- dp-N fps scaling: the same stage, one chip per replica, at
    # replicas 1 / 2 / 4 -- per-replica workers run frames of one
    # stream concurrently, so fps scales with the live replica count.
    scaling: dict[int, float] = {}
    for count in (1, 2, 4):
        if count > n:
            continue
        runtime = fresh_runtime()
        pipeline = Pipeline(
            {"version": 0, "name": f"bench_dp{count}", "runtime": "jax",
             "graph": ["(detect)"],
             "parameters": {"transfer_guard": "disallow"},
             "elements": [
                 {**element("detect", "StageWork", ["x"], ["x"],
                            {"busy_ms": STAGE_BUSY_MS, "factor": 2.0}),
                  "placement": {"devices": 1, "replicas": count}}]},
            runtime=runtime)
        warm = run_frames(runtime, pipeline, 4, "warm")
        if len(warm) < 4:
            runtime.terminate()
            return result | {"pipeline_replicas_error":
                             f"dp{count} warmup stalled"}
        start = time.perf_counter()
        rows = run_frames(runtime, pipeline, REPLICA_FRAMES, "timed")
        elapsed = time.perf_counter() - start
        okay = all(row[4] for row in rows)
        in_order = [row[1] for row in rows] == sorted(
            row[1] for row in rows)
        runtime.terminate()
        if len(rows) < REPLICA_FRAMES or not okay or not in_order:
            return result | {"pipeline_replicas_error":
                             f"dp{count} pass incomplete"}
        scaling[count] = len(rows) / elapsed
        result[f"replica_fps_dp{count}"] = round(scaling[count], 2)
    top = max(scaling)
    if scaling.get(1):
        result["replica_dp_scaling"] = round(
            scaling[top] / scaling[1], 2)

    # -- failover vs full replace, same shape, same load: detect at
    # ``replicas: 3`` plus an unreplicated llm.  Pass 1 kills ONE
    # detect replica (peer-shed: kill -> first completion after the
    # shed).  Pass 2 kills an llm chip -- outside any replica, so the
    # same pipeline pays for the stop-the-world replace() -- measured
    # kill -> first completion identically.
    per = max(1, n // 4)
    runtime = fresh_runtime()
    pipeline = Pipeline(
        {"version": 0, "name": "bench_failover", "runtime": "jax",
         "graph": ["(detect llm)"],
         "parameters": {"transfer_guard": "disallow",
                        "replay_limit": 4,
                        "replica_rebuild_ms": 0},
         "elements": [
             {**element("detect", "StageWork", ["x"], ["x"],
                        {"busy_ms": STAGE_BUSY_MS, "factor": 2.0}),
              "placement": {"devices": per, "replicas": 3}},
             {**element("llm", "StageWork", ["x"], ["x"],
                        {"busy_ms": STAGE_BUSY_MS / 4, "factor": 3.0}),
              "placement": {"devices": n - 3 * per}}]},
        runtime=runtime)
    warm = run_frames(runtime, pipeline, 4, "warm")
    if len(warm) < 4:
        runtime.terminate()
        return result | {"pipeline_replicas_error": "failover warmup "
                         "stalled"}
    marks: dict = {}
    pipeline.add_hook_handler(
        "pipeline.replica_failover:0",
        lambda component, hook, variables:
            marks.setdefault("shed", time.perf_counter()))
    pipeline.add_hook_handler(
        "pipeline.replacement:0",
        lambda component, hook, variables:
            marks.setdefault("replaced", time.perf_counter()))

    def note_recovery():
        if "shed" in marks and "shed_recovered" not in marks:
            marks["shed_recovered"] = time.perf_counter()
        if "replaced" in marks and "replace_recovered" not in marks:
            marks["replace_recovered"] = time.perf_counter()

    pipeline.post_self("fail_replica", ["detect", 1], delay=0.05)
    rows = run_frames(runtime, pipeline, REPLICA_FRAMES, "kill",
                      on_row=note_recovery)
    if len(rows) < REPLICA_FRAMES or not all(row[4] for row in rows):
        runtime.terminate()
        return result | {"pipeline_replicas_error":
                         "failover pass incomplete"}
    if "shed" in marks and "shed_recovered" in marks:
        result["replica_failover_ms"] = round(
            (marks["shed_recovered"] - marks["shed"]) * 1000.0, 1)
    result["replica_failover_shed_ms"] = \
        pipeline.share.get("replica_failover_ms")
    result["replica_failover_replayed"] = \
        pipeline.share.get("frames_replayed", 0)
    result["replica_live_after_failover"] = \
        len(pipeline.stage_placement.live_replicas("detect"))

    dead = list(pipeline.stage_placement.plans["llm"]
                .mesh.devices.flat)[:1]
    pipeline.post_self("replace_failed_devices", [dead], delay=0.05)
    rows = run_frames(runtime, pipeline, REPLICA_FRAMES, "replace",
                      on_row=note_recovery)
    okay = all(row[4] for row in rows)
    runtime.terminate()
    if len(rows) >= REPLICA_FRAMES and okay \
            and "replaced" in marks and "replace_recovered" in marks:
        result["replica_full_replace_ms"] = round(
            (marks["replace_recovered"] - marks["replaced"]) * 1000.0, 1)

    previous = _previous_bench()
    for key in ("replica_fps_dp1", "replica_fps_dp2", "replica_fps_dp4",
                "replica_dp_scaling", "replica_failover_ms",
                "replica_full_replace_ms"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# 4e. Gateway front door + unified QoS (ISSUE 12): the open-loop load
#     generator drives mixed-tenant WebSocket traffic through the REAL
#     gateway -- capacity first, then 2x overload: per-class p99,
#     goodput, and the shed-fairness contract (the over-budget batch
#     tenant absorbs the shedding while interactive keeps its SLO).

GATEWAY_BUSY_MS = 6.0
GATEWAY_CAL_FRAMES = 48
GATEWAY_LOAD_SECONDS = 5.0


def bench_pipeline_gateway() -> dict:
    import threading

    import jax

    if len(jax.devices()) < 2:
        return {"pipeline_gateway_skipped":
                f"needs >= 2 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.gateway.loadgen import LoadSpec, run_loadgen
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()
    n = len(jax.devices())
    pipeline = Pipeline(
        {"version": 0, "name": "bench_gateway", "runtime": "jax",
         "graph": ["(detect llm)"],
         "parameters": {
             "gateway": "on",
             "device_inflight": 3,
             "qos": {"classes": {"batch": {"device_inflight": 1}},
                     "tenants": {
                         "alice": {"class": "interactive",
                                   "budget": 64},
                         "bulk": {"class": "batch", "budget": 4}},
                     "max_inflight": 24, "age_ms": 60000,
                     "session_window": 64}},
         "elements": [
             {**element("detect", "StageWork", ["x"], ["x"],
                        {"busy_ms": GATEWAY_BUSY_MS, "factor": 2.0}),
              "placement": {"devices": n // 2}},
             {**element("llm", "StageWork", ["x"], ["x"],
                        {"busy_ms": GATEWAY_BUSY_MS, "factor": 3.0}),
              "placement": {"devices": n - n // 2}},
         ]},
        runtime=runtime)
    port = pipeline.gateway.port
    payload = {"x": [1.0] * 64}

    def drive(specs, box):
        try:
            box["report"] = run_loadgen("127.0.0.1", port, specs)
        except Exception as error:
            box["error"] = f"{type(error).__name__}: {error}"

    def run_specs(specs, timeout=300.0):
        box: dict = {}
        thread = threading.Thread(target=drive, args=(specs, box),
                                  daemon=True)
        thread.start()
        runtime.run(until=lambda: not thread.is_alive(),
                    timeout=timeout)
        return box

    result: dict = {}
    try:
        # -- warmup: compile both stages' jits off the clock, or the
        # calibration reads compile time as steady-state latency and
        # the "2x overload" pass never actually overloads.
        box = run_specs([LoadSpec("alice", "interactive", rate=1000.0,
                                  frames=8, data=payload, window=4)])
        if "report" not in box:
            return {"pipeline_gateway_error":
                    box.get("error", "warmup hung")}
        # -- capacity calibration: one interactive tenant, effectively
        # closed by the session window, offered far above capacity.
        box = run_specs([LoadSpec("alice", "interactive", rate=1000.0,
                                  frames=GATEWAY_CAL_FRAMES,
                                  data=payload, window=8)])
        if "report" not in box:
            return {"pipeline_gateway_error":
                    box.get("error", "calibration hung")}
        calibration = box["report"]["classes"]["interactive"]
        capacity = max(1.0, calibration["goodput_fps"])
        result["gateway_capacity_fps"] = round(capacity, 2)
        result["gateway_uncontended_p99_ms"] = calibration["p99_ms"]
        # The interactive SLO for the overload pass: generous headroom
        # over the uncontended p99 (CPU-mesh jitter), recorded so the
        # "within SLO" bit below is honest and reproducible.
        slo_ms = max(50.0, 5.0 * calibration["p99_ms"])
        result["gateway_interactive_slo_ms"] = round(slo_ms, 2)

        # -- 2x overload: interactive offered at half capacity (inside
        # its budget), batch at 1.5x capacity -- 2x total.
        inter_rate = capacity * 0.5
        batch_rate = capacity * 1.5
        box = run_specs([
            LoadSpec("alice", "interactive", rate=inter_rate,
                     frames=int(inter_rate * GATEWAY_LOAD_SECONDS),
                     data=payload),
            LoadSpec("bulk", "batch", rate=batch_rate,
                     frames=int(batch_rate * GATEWAY_LOAD_SECONDS),
                     data=payload),
        ])
        if "report" not in box:
            return {**result,
                    "pipeline_gateway_error":
                        box.get("error", "overload pass hung")}
        report = box["report"]
        interactive = report["classes"]["interactive"]
        batch = report["classes"]["batch"]
        alice = report["tenants"]["alice"]
        bulk = report["tenants"]["bulk"]
        result.update({
            "gateway_overload_factor": 2.0,
            "gateway_interactive_p50_ms": interactive["p50_ms"],
            "gateway_interactive_p99_ms": interactive["p99_ms"],
            "gateway_interactive_goodput_fps":
                interactive["goodput_fps"],
            "gateway_interactive_sent": interactive["sent"],
            "gateway_interactive_ok": interactive["ok"],
            "gateway_interactive_within_slo":
                bool(interactive["p99_ms"] <= slo_ms),
            "gateway_batch_p99_ms": batch["p99_ms"],
            "gateway_batch_goodput_fps": batch["goodput_fps"],
            "gateway_batch_shed": batch["shed"] + batch["busy"],
            # The fairness contract: the over-budget tenant absorbed
            # every shed; interactive lost nothing.
            "gateway_shed_overbudget_first":
                bool(bulk["shed"] >= 1 and alice["shed"] == 0
                     and alice["ok"] == alice["sent"]),
            "gateway_qos_sheds": pipeline.share.get("qos_sheds", 0),
        })

        # -- promotion probe (ISSUE 18 satellite): `qos_promotions`
        # had never fired in any round because no bench frame carried
        # a deadline.  Batch frames with a deadline that lands inside
        # promote_ms while they queue behind interactive traffic MUST
        # promote at the stage-credit window; a counter still at zero
        # afterwards is a broken seam, reported as a loud error key
        # rather than a silently-zero metric.
        probe_rate = max(4.0, capacity * 0.8)
        probe_frames = int(probe_rate * 2.0)
        run_specs([
            LoadSpec("alice", "interactive", rate=probe_rate,
                     frames=probe_frames, data=payload),
            LoadSpec("bulk", "batch", rate=probe_rate,
                     frames=probe_frames, data=payload,
                     deadline_ms=150.0),
        ])
        promotions = pipeline.share.get("qos_promotions", 0)
        result["gateway_qos_promotions"] = promotions
        result["gateway_promotions_fired"] = bool(promotions > 0)
        if promotions == 0:
            result["pipeline_gateway_error"] = \
                "qos_promotions stayed 0 across the near-deadline " \
                "promotion probe (stage-credit promotion seam broken)"
    finally:
        runtime.terminate()

    previous = _previous_bench()
    for key in ("gateway_capacity_fps", "gateway_interactive_p50_ms",
                "gateway_interactive_p99_ms",
                "gateway_interactive_goodput_fps",
                "gateway_batch_p99_ms", "gateway_batch_goodput_fps",
                "gateway_qos_promotions"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior, 2)
    return result


# ---------------------------------------------------------------------------
# Process-level fault domain (ISSUE 13): journal overhead, kill ->
# first-frame-on-peer MTTR, and a rolling restart under the loadgen.

FAILOVER_BUSY_MS = 4.0
FAILOVER_JOURNAL_FRAMES = 120
FAILOVER_OVERHEAD_GATE_PCT = 2.0


def bench_pipeline_failover() -> dict:
    import queue
    import threading
    import time as time_module

    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        return {"pipeline_failover_skipped":
                f"needs >= 2 devices, have {len(jax.devices())}"}
    import tempfile

    from aiko_services_tpu.gateway.client import GatewayClient
    from aiko_services_tpu.gateway.loadgen import LoadSpec, run_loadgen
    from aiko_services_tpu.gateway.server import GatewayServer
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.services import Registrar
    from aiko_services_tpu.services.share import reset_services_cache
    from aiko_services_tpu.transport import reset_broker

    workdir = tempfile.mkdtemp(prefix="aiko_bench_failover_")
    payload = {"x": np.ones((64,), np.float32)}

    def make_pipeline(runtime, name, journal, busy_ms,
                      drain_timeout_ms=2000):
        parameters = {"drain_timeout_ms": drain_timeout_ms}
        if journal:
            parameters.update({"journal": "on",
                               "journal_dir": workdir})
        return Pipeline(
            {"version": 0, "name": name, "runtime": "jax",
             "graph": ["(work finish)"],
             "parameters": parameters,
             "elements": [
                 {**element("work", "StageWork", ["x"], ["x"],
                            {"busy_ms": busy_ms, "factor": 2.0}),
                  "placement": {"devices": 2}},
                 {**element("finish", "StageWork", ["x"], ["x"],
                            {"busy_ms": busy_ms, "factor": 3.0}),
                  "placement": {"devices": 2}},
             ]}, runtime=runtime)

    def fresh_runtime():
        reset_broker()
        reset_services_cache()
        reset_process()
        runtime = init_process(transport="loopback")
        runtime.initialize()
        return runtime

    result: dict = {}

    # -- journal overhead A/B: same workload, journal on vs off ----------
    def measure_fps(journal: bool) -> float:
        runtime = fresh_runtime()
        try:
            pipeline = make_pipeline(runtime, "jmeas", journal,
                                     FAILOVER_BUSY_MS)
            for stream_id, frames in (("warm", 16),
                                      ("meas", FAILOVER_JOURNAL_FRAMES)):
                responses = queue.Queue()
                pipeline.create_stream_local(
                    stream_id, queue_response=responses)
                start = time_module.perf_counter()
                for _ in range(frames):
                    pipeline.process_frame_local(dict(payload),
                                                 stream_id=stream_id)
                runtime.run(until=lambda: responses.qsize() == frames,
                            timeout=120.0)
                elapsed = time_module.perf_counter() - start
                if responses.qsize() != frames:
                    raise RuntimeError(
                        f"journal fps pass hung at "
                        f"{responses.qsize()}/{frames}")
            return frames / elapsed
        finally:
            runtime.terminate()

    # Scheduler jitter can exceed the 2% gate on a loaded CPU host:
    # re-measure up to 3x (the recorder-overhead discipline) -- a
    # genuine >2% journal cost fails all attempts.
    for _attempt in range(3):
        fps_off = measure_fps(journal=False)
        fps_on = measure_fps(journal=True)
        overhead_pct = (fps_off - fps_on) / fps_off * 100.0
        if overhead_pct <= FAILOVER_OVERHEAD_GATE_PCT:
            break
    result.update({
        "pipeline_nojournal_fps": round(fps_off, 2),
        "pipeline_journal_fps": round(fps_on, 2),
        "journal_overhead_pct": round(overhead_pct, 2),
        "journal_overhead_within_gate":
            bool(overhead_pct <= FAILOVER_OVERHEAD_GATE_PCT),
    })

    # -- kill -> first-frame-on-peer MTTR under load ---------------------
    runtime = fresh_runtime()
    try:
        Registrar(runtime=runtime, primary_search_timeout=0.05)
        p1 = make_pipeline(runtime, "fsrv1", True, 25.0)
        gateway = GatewayServer(runtime=runtime)
        runtime.run(until=lambda: len(gateway._peers) == 1,
                    timeout=10.0)
        p2 = make_pipeline(runtime, "fsrv2", True, 25.0)
        runtime.run(until=lambda: len(gateway._peers) == 2,
                    timeout=10.0)
        client = GatewayClient("127.0.0.1", gateway.port,
                               timeout=120.0)
        n_frames = 24
        arrivals: list = []
        errors: list = []

        def drive():
            try:
                client.open(session="mttr", tenant="t1")
                for index in range(n_frames):
                    client.send_frame(
                        {"x": [float(index + 1)] * 64})
                for _ in range(n_frames):
                    message = client.next_result(timeout=60.0)
                    arrivals.append(
                        (time_module.perf_counter(),
                         message["frame"], message["ok"]))
                client.close()
            except Exception as error:
                errors.append(f"{type(error).__name__}: {error}")

        thread = threading.Thread(target=drive, daemon=True)
        thread.start()
        runtime.run(until=lambda: len(arrivals) >= 4 or errors,
                    timeout=60.0)
        kill_at = time_module.perf_counter()
        delivered_before = len(arrivals)
        p1.kill()
        runtime.run(until=lambda: not thread.is_alive(),
                    timeout=120.0)
        if errors or thread.is_alive():
            result["pipeline_failover_error"] = \
                errors[0] if errors else "mttr pass hung"
        else:
            after = [stamp for stamp, _frame, _ok in
                     arrivals[delivered_before:]
                     if stamp > kill_at]
            frame_ids = [frame for _stamp, frame, _ok in arrivals]
            result.update({
                "pipeline_failover_mttr_ms": round(
                    (after[0] - kill_at) * 1000.0, 2) if after
                else None,
                "failover_frames_delivered": len(arrivals),
                "failover_in_order_no_dups":
                    frame_ids == list(range(n_frames)),
                "failover_all_ok": all(
                    ok for _stamp, _frame, ok in arrivals),
            })
    finally:
        try:
            gateway.stop()
        except Exception:
            pass
        runtime.terminate()

    # -- rolling restart of a 2-pipeline fleet under the loadgen ---------
    runtime = fresh_runtime()
    try:
        Registrar(runtime=runtime, primary_search_timeout=0.05)
        fleet = {"a": make_pipeline(runtime, "roll1", True, 8.0)}
        gateway = GatewayServer(runtime=runtime)
        runtime.run(until=lambda: len(gateway._peers) == 1,
                    timeout=10.0)
        fleet["b"] = make_pipeline(runtime, "roll2", True, 8.0)
        runtime.run(until=lambda: len(gateway._peers) == 2,
                    timeout=10.0)
        rate = 30.0
        seconds = 4.0
        spec = LoadSpec("t1", "standard", rate=rate,
                        frames=int(rate * seconds),
                        data={"x": [1.0] * 64}, window=16)
        box: dict = {}

        def drive_load():
            try:
                box["report"] = run_loadgen("127.0.0.1", gateway.port,
                                            [spec])
            except Exception as error:
                box["error"] = f"{type(error).__name__}: {error}"

        thread = threading.Thread(target=drive_load, daemon=True)
        thread.start()
        deadline = time_module.monotonic() + 1.0
        runtime.run(until=lambda: time_module.monotonic() > deadline,
                    timeout=5.0)
        fleet["a"].drain()              # rolling walk, pipeline 1
        runtime.run(
            until=lambda: fleet["a"].share.get("drained"),
            timeout=30.0)
        fleet["a2"] = make_pipeline(runtime, "roll1", True, 8.0)
        runtime.run(until=lambda: len(gateway._peers) == 2,
                    timeout=10.0)
        fleet["b"].drain()              # rolling walk, pipeline 2
        runtime.run(
            until=lambda: fleet["b"].share.get("drained"),
            timeout=30.0)
        runtime.run(until=lambda: not thread.is_alive(),
                    timeout=120.0)
        if "report" not in box:
            result["failover_rolling_error"] = \
                box.get("error", "loadgen hung")
        else:
            bucket = box["report"]["classes"]["standard"]
            dropped = bucket["sent"] - bucket["ok"] \
                - bucket["errors"] - bucket["rejected"] \
                - bucket["busy"]
            result.update({
                "failover_rolling_frames": bucket["sent"],
                "failover_rolling_ok": bucket["ok"],
                "failover_rolling_frames_dropped": dropped,
                "failover_rolling_p99_ms": bucket["p99_ms"],
                "failover_rolling_restarts": 2,
            })
    finally:
        try:
            gateway.stop()
        except Exception:
            pass
        runtime.terminate()

    previous = _previous_bench()
    for key in ("pipeline_journal_fps", "pipeline_nojournal_fps",
                "pipeline_failover_mttr_ms",
                "failover_rolling_p99_ms"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior,
                                                 2)
    return result


# ---------------------------------------------------------------------------
# 4g. Guarded elastic fleet controller (ISSUE 20): knob convergence
#     from a deliberately mis-tuned config (the controller must tune a
#     live pipeline to >= 90% of the hand-tuned fps), then the
#     multi-process 1->3->1 ramp -- scale-out under burning SLO, a
#     SIGKILL of a scaled-out peer absorbed by the supervised respawn
#     path, zero dropped frames, scale-in when the load releases.

CONTROLLER_STAGE_BUSY_MS = 6.0
CONTROLLER_WINDOW_S = 1.2
CONTROLLER_MAX_WINDOWS = 12
CONTROLLER_TARGET_FRAC = 0.9
CONTROLLER_RAMP_BUSY_MS = 30.0
CONTROLLER_RAMP_SLO_MS = 5000.0


def bench_pipeline_controller() -> dict:
    import queue as queue_module
    import threading
    import time as time_module

    import jax
    import numpy as np

    if len(jax.devices()) < 4:
        return {"pipeline_controller_skipped":
                f"needs >= 4 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    payload = {"x": np.ones((64,), np.float32)}
    result: dict = {}

    # -- part A: knob convergence on a live in-process pipeline ----------
    def build(runtime, extra):
        return Pipeline(
            {"version": 0, "name": "bench_ctl", "runtime": "jax",
             "graph": ["(work finish)"],
             "parameters": dict(extra),
             "elements": [
                 {**element("work", "StageWork", ["x"], ["x"],
                            {"busy_ms": CONTROLLER_STAGE_BUSY_MS,
                             "factor": 2.0}),
                  "placement": {"devices": 2}},
                 {**element("finish", "StageWork", ["x"], ["x"],
                            {"busy_ms": CONTROLLER_STAGE_BUSY_MS,
                             "factor": 3.0}),
                  "placement": {"devices": 2}},
             ]}, runtime=runtime)

    def run_windows(extra, windows, stop_at=None):
        """Open-loop pump (16 outstanding) measured in wall-clock
        windows; returns (per-window fps, final share, status)."""
        reset_broker()
        reset_process()
        runtime = init_process(transport="loopback")
        runtime.initialize()
        try:
            pipeline = build(runtime, extra)
            responses = queue_module.Queue()
            pipeline.create_stream_local("s",
                                         queue_response=responses)
            state = {"sent": 0, "done": 0}

            def pump(deadline):
                def step():
                    while not responses.empty():
                        responses.get()
                        state["done"] += 1
                    while state["sent"] - state["done"] < 16:
                        pipeline.process_frame_local(
                            dict(payload), stream_id="s")
                        state["sent"] += 1
                    return time_module.perf_counter() > deadline
                runtime.run(until=step, timeout=60.0)

            pump(time_module.perf_counter() + 1.0)     # compile warm
            rates = []
            for _ in range(windows):
                start = time_module.perf_counter()
                before = state["done"]
                pump(start + CONTROLLER_WINDOW_S)
                elapsed = time_module.perf_counter() - start
                rates.append((state["done"] - before) / elapsed)
                if stop_at is not None and rates[-1] >= stop_at:
                    break

            def drained():
                while not responses.empty():
                    responses.get()
                    state["done"] += 1
                return state["done"] >= state["sent"]
            runtime.run(until=drained, timeout=60.0)
            controller = pipeline.controller
            return (rates, dict(pipeline.share),
                    controller.status() if controller else {})
        finally:
            runtime.terminate()

    hand_rates, _, _ = run_windows(
        {"stage_inflight": 4, "device_inflight": 3}, 2)
    fps_hand = max(hand_rates)
    mis_rates, _, _ = run_windows(
        {"stage_inflight": 1, "device_inflight": 1}, 2)
    fps_mistuned = max(mis_rates)
    target = CONTROLLER_TARGET_FRAC * fps_hand
    ctl_rates, share, status = run_windows(
        {"stage_inflight": 1, "device_inflight": 1,
         "controller": {"mode": "act", "interval_ms": 100,
                        "hysteresis_ticks": 2, "cooldown_ms": 300,
                        "action_budget": 16, "budget_window_s": 30}},
        CONTROLLER_MAX_WINDOWS, stop_at=target)
    fps_converged = max(ctl_rates)
    result.update({
        "controller_fps_hand_tuned": round(fps_hand, 2),
        "controller_fps_mistuned": round(fps_mistuned, 2),
        "controller_fps_converged": round(fps_converged, 2),
        "controller_convergence_ratio": round(
            fps_converged / fps_hand, 3),
        "controller_converged": bool(fps_converged >= target),
        "controller_convergence_windows": len(ctl_rates),
        "controller_actions": share.get("controller_actions", 0),
        "controller_refusals": status.get("refusals", 0),
    })

    # -- part B: 1 -> 3 -> 1 process ramp with kill-while-scaled ---------
    import json as json_module
    import signal as signal_module
    import subprocess
    import tempfile

    from aiko_services_tpu.faults.chaos import (CHAOS_CHILD_DEVICE,
                                                _peer_pids,
                                                _pilot_definition)
    from aiko_services_tpu.orchestration.controller import device_env
    from aiko_services_tpu.gateway.client import GatewayClient
    from aiko_services_tpu.orchestration.controller import \
        FleetSupervisor
    from aiko_services_tpu.pipeline.pipeline import PROTOCOL_PIPELINE
    from aiko_services_tpu.services import ServiceFilter, do_discovery

    from aiko_services_tpu.transport.broker import BrokerProcess

    workdir = tempfile.mkdtemp(prefix="aiko_bench_ctl_")
    journal_dir = os.path.join(workdir, "journals")
    os.makedirs(journal_dir, exist_ok=True)
    pilot = "benchpilot"
    definitions = {pilot: _pilot_definition(
        pilot, journal_dir, busy_ms=CONTROLLER_RAMP_BUSY_MS,
        fleet_max=3, cooldown_ms=800.0)}
    broker = registrar = supervisor = runtime = discovery = None
    deadline = time.monotonic() + 300.0
    try:
        reset_broker()
        reset_process()
        broker = BrokerProcess(port=0, export_env=True).start()
        # Every child of this section is a synthetic StageWork pipeline
        # and is told the CPU (the parent process holds the chip); the
        # section's output says so (controller_child_device).
        env = {**os.environ, **device_env(CHAOS_CHILD_DEVICE)}
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
        registrar_log = open(os.path.join(workdir, "registrar.log"),
                             "w")
        registrar = subprocess.Popen(
            [sys.executable, "-m", "aiko_services_tpu", "registrar",
             "-t", "mqtt"], env=env, stdout=registrar_log,
            stderr=registrar_log, start_new_session=True)

        def spawner(name):
            path = os.path.join(workdir, f"{name}.json")
            with open(path, "w") as stream:
                json_module.dump(definitions[name], stream)
            log = open(os.path.join(workdir, f"{name}.log"), "a")
            return subprocess.Popen(
                [sys.executable, "-m", "aiko_services_tpu",
                 "pipeline", "create", path, "-t", "mqtt",
                 "--name", name],
                env=env, stdout=log, stderr=log,
                start_new_session=True)

        supervisor = FleetSupervisor(spawner, engine=None,
                                     backoff_s=0.5)
        runtime = init_process(transport="mqtt")
        runtime.initialize()

        peers: dict = {}                 # topic_path -> name
        tags: dict = {}                  # name -> host:port
        lock = threading.Lock()

        def on_found(record, proxy):
            with lock:
                peers[record.topic_path] = record.name
                for tag in record.tags:
                    if tag.startswith("gateway="):
                        tags[record.name] = tag.split("=", 1)[1]

        def on_lost(record, proxy):
            with lock:
                peers.pop(record.topic_path, None)

        discovery = do_discovery(
            runtime, ServiceFilter(protocol=PROTOCOL_PIPELINE),
            add_handler=on_found, remove_handler=on_lost)

        def wait_for(predicate, what):
            runtime.run(until=predicate,
                        timeout=max(1.0,
                                    deadline - time.monotonic()))
            if not predicate():
                raise RuntimeError(f"ramp: timed out waiting for "
                                   f"{what} (see {workdir})")

        def fleet_size():
            with lock:
                return len(set(peers.values()))

        supervisor.spawn(pilot)
        wait_for(lambda: pilot in tags, "pilot gateway tag")
        host, _, port = tags[pilot].partition(":")

        latencies: list = []
        errors: list = []
        release = threading.Event()
        sessions: list = []

        def drive(session_name, window):
            """Open-loop pressure until released; per-frame e2e
            latency from the in-order result stream."""
            try:
                client = GatewayClient(host, int(port),
                                       timeout=120.0)
                client.open(session=session_name)
                stamps: list = []
                delivered = []
                for index in range(window):
                    stamps.append(time_module.perf_counter())
                    client.send_frame({"x": [float(index + 1)] * 4})
                sent = window
                while not release.is_set():
                    entry = client.next_result(timeout=90.0)
                    latencies.append(
                        (time_module.perf_counter() - stamps.pop(0))
                        * 1000.0)
                    delivered.append(entry)
                    stamps.append(time_module.perf_counter())
                    client.send_frame({"x": [float(sent + 1)] * 4})
                    sent += 1
                while len(delivered) < sent:
                    entry = client.next_result(timeout=90.0)
                    latencies.append(
                        (time_module.perf_counter() - stamps.pop(0))
                        * 1000.0)
                    delivered.append(entry)
                client.close()
                sessions.append((session_name, sent, delivered))
            except Exception as error:
                errors.append(f"{session_name}: "
                              f"{type(error).__name__}: {error}")

        ramp_start = time_module.perf_counter()
        threads = [threading.Thread(target=drive,
                                    args=(f"press{i}", 4),
                                    daemon=True) for i in range(3)]
        for thread in threads:
            thread.start()

        # Scale-out #1: burning SLO + overload spawns the first peer.
        wait_for(lambda: fleet_size() >= 2 or errors,
                 "first controller scale-out")
        if errors:
            raise RuntimeError(errors[0])
        with lock:
            first_peer = next(name for name in peers.values()
                              if name != pilot)
        # A probe session now binds to the idle peer (least-loaded
        # balancing) -- the kill below lands under a live session.
        probe = threading.Thread(target=drive, args=("probe", 2),
                                 daemon=True)
        threads.append(probe)
        probe.start()

        # Scale-out #2: pressure sessions stay bound to the pilot, so
        # it keeps burning until the fleet hits fleet_max=3.
        wait_for(lambda: fleet_size() >= 3 or errors,
                 "fleet to reach 3")
        if errors:
            raise RuntimeError(errors[0])
        result["controller_scaleout_s"] = round(
            time_module.perf_counter() - ramp_start, 2)
        result["controller_fleet_peak"] = fleet_size()

        # Kill-while-scaled: SIGKILL the first peer (the probe's
        # host); the pilot's supervisor must respawn it.
        pids = _peer_pids(first_peer)
        if not pids:
            raise RuntimeError(f"no process found for {first_peer}")
        os.kill(pids[0], signal_module.SIGKILL)
        wait_for(lambda: any(name == first_peer
                             for name in list(peers.values()))
                 or errors, f"{first_peer} respawn")
        if errors:
            raise RuntimeError(errors[0])
        result["controller_kill_absorbed"] = True

        # Release: drain every session, then the controller must
        # retire the idle peers back down to fleet_min=1.
        hold = time_module.perf_counter() + 2.0
        wait_for(lambda: time_module.perf_counter() > hold, "hold")
        release.set()
        wait_for(lambda: not any(thread.is_alive()
                                 for thread in threads),
                 "session completion")
        if errors:
            raise RuntimeError(errors[0])
        scalein_start = time_module.perf_counter()
        wait_for(lambda: fleet_size() <= 1, "scale-in back to 1")
        result["controller_scalein_s"] = round(
            time_module.perf_counter() - scalein_start, 2)

        sent_total = sum(sent for _, sent, _ in sessions)
        delivered_total = sum(len(delivered)
                              for _, _, delivered in sessions)
        in_order = all(
            [entry["frame"] for entry in delivered]
            == list(range(sent))
            for _, sent, delivered in sessions)
        all_ok = all(entry["ok"] for _, _, delivered in sessions
                     for entry in delivered)
        ordered = sorted(latencies)
        p99 = ordered[int(len(ordered) * 0.99)] if ordered else None
        result.update({
            "controller_ramp_frames": sent_total,
            "controller_ramp_dropped": sent_total - delivered_total,
            "controller_ramp_in_order": bool(in_order),
            "controller_ramp_all_ok": bool(all_ok),
            "controller_ramp_p99_ms": round(p99, 2) if p99 else None,
            "controller_ramp_slo_ms": CONTROLLER_RAMP_SLO_MS,
            "controller_ramp_within_slo": bool(
                p99 is not None and p99 <= CONTROLLER_RAMP_SLO_MS),
            "controller_ramp_respawns": supervisor.respawns,
            "controller_ramp_ok": bool(
                in_order and all_ok
                and sent_total == delivered_total
                and result.get("controller_kill_absorbed")),
        })
    except Exception as error:
        result["pipeline_controller_error"] = \
            f"{type(error).__name__}: {error}"
    finally:
        if discovery is not None:
            discovery.terminate()
        if runtime is not None:
            try:
                runtime.terminate()
            except Exception:
                pass
            reset_process()
        if supervisor is not None:
            supervisor.stop_all(5.0)
        if registrar is not None:
            if registrar.poll() is None:
                registrar.terminate()
            try:
                registrar.wait(5.0)
            except subprocess.TimeoutExpired:
                registrar.kill()
        for pid in _peer_pids("benchpilot-peer"):
            try:
                os.kill(pid, signal_module.SIGKILL)
            except OSError:
                pass
        if broker is not None:
            broker.stop()

    result["controller_child_device"] = CHAOS_CHILD_DEVICE
    previous = _previous_bench()
    for key in ("controller_fps_converged",
                "controller_convergence_ratio",
                "controller_scaleout_s", "controller_scalein_s",
                "controller_ramp_p99_ms"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior,
                                                 2)
    return result


# ---------------------------------------------------------------------------
# Fleet observability plane (ISSUE 19): collector scrape overhead on a
# loaded pipeline (gated <= 1%), the door-to-decode trace a gateway
# request produces (span count + attribution coverage), and SLO
# error-budget burn firing under 2x overload.

FLEET_BUSY_MS = 4.0
FLEET_FRAMES = 120
FLEET_OVERHEAD_GATE_PCT = 1.0
FLEET_SCRAPE_FAST_MS = 50.0     # ~20 Hz: far above production cadence,
                                # so the gate bounds a WORST case


def bench_pipeline_fleet() -> dict:
    import json as json_module
    import queue
    import threading
    import time as time_module
    import urllib.request

    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        return {"pipeline_fleet_skipped":
                f"needs >= 2 devices, have {len(jax.devices())}"}
    from aiko_services_tpu.gateway.client import GatewayClient
    from aiko_services_tpu.gateway.loadgen import LoadSpec, run_loadgen
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.services import Registrar
    from aiko_services_tpu.services.share import reset_services_cache
    from aiko_services_tpu.transport import reset_broker

    payload = {"x": np.ones((64,), np.float32)}

    def fresh_runtime():
        reset_broker()
        reset_services_cache()
        reset_process()
        runtime = init_process(transport="loopback")
        runtime.initialize()
        return runtime

    def make_pipeline(runtime, name, fleet, extra=None):
        parameters: dict = dict(extra or {})
        if fleet:
            parameters.update({"fleet": "on",
                               "fleet_scrape_ms": FLEET_SCRAPE_FAST_MS})
        return Pipeline(
            {"version": 0, "name": name, "runtime": "jax",
             "graph": ["(work finish)"],
             "parameters": parameters,
             "elements": [
                 {**element("work", "StageWork", ["x"], ["x"],
                            {"busy_ms": FLEET_BUSY_MS, "factor": 2.0}),
                  "placement": {"devices": 2}},
                 {**element("finish", "StageWork", ["x"], ["x"],
                            {"busy_ms": FLEET_BUSY_MS, "factor": 3.0}),
                  "placement": {"devices": 2}},
             ]}, runtime=runtime)

    result: dict = {}

    # -- scrape overhead A/B: same workload, collector on vs off ---------
    # The collector scrapes the local pipeline's registry snapshot at
    # FLEET_SCRAPE_FAST_MS off-thread while the engine pushes frames.
    def measure_fps(fleet: bool) -> float:
        runtime = fresh_runtime()
        try:
            pipeline = make_pipeline(runtime, "fmeas", fleet)
            for stream_id, frames in (("warm", 16),
                                      ("meas", FLEET_FRAMES)):
                responses = queue.Queue()
                pipeline.create_stream_local(
                    stream_id, queue_response=responses)
                start = time_module.perf_counter()
                for _ in range(frames):
                    pipeline.process_frame_local(dict(payload),
                                                 stream_id=stream_id)
                runtime.run(until=lambda: responses.qsize() == frames,
                            timeout=120.0)
                elapsed = time_module.perf_counter() - start
                if responses.qsize() != frames:
                    raise RuntimeError(
                        f"fleet fps pass hung at "
                        f"{responses.qsize()}/{frames}")
            return frames / elapsed
        finally:
            runtime.terminate()

    # Scheduler jitter can exceed a 1% gate on a loaded CPU host:
    # re-measure up to 3x (the recorder-overhead discipline) -- a
    # genuine >1% scrape cost fails all attempts.
    for _attempt in range(3):
        fps_off = measure_fps(fleet=False)
        fps_on = measure_fps(fleet=True)
        overhead_pct = (fps_off - fps_on) / fps_off * 100.0
        if overhead_pct <= FLEET_OVERHEAD_GATE_PCT:
            break
    result.update({
        "pipeline_nofleet_fps": round(fps_off, 2),
        "pipeline_fleet_fps": round(fps_on, 2),
        "fleet_scrape_overhead_pct": round(overhead_pct, 2),
        "fleet_overhead_within_gate":
            bool(overhead_pct <= FLEET_OVERHEAD_GATE_PCT),
    })

    # -- door-to-decode trace + /fleet + SLO burn under overload ---------
    runtime = fresh_runtime()
    try:
        Registrar(runtime=runtime, primary_search_timeout=0.05)
        # A p99 objective of 1 ms against an ~8 ms two-stage workload:
        # every delivered frame violates it, so the latency burn is
        # ~100x the budget and the fast-burn path MUST fire once the
        # overload pass pushes samples through the window.
        pipeline = make_pipeline(
            runtime, "fgw", fleet=True,
            extra={"gateway": "on",
                   "qos": {"tenants": {"alice":
                                       {"class": "interactive",
                                        "budget": 64}},
                           "max_inflight": 24,
                           "session_window": 64},
                   "slo": {"interactive": {"p99_ms": 1.0,
                                           "availability": 0.999}}})
        port = pipeline.gateway.port

        # One traced request end to end via the real WebSocket door.
        box: dict = {}

        def probe():
            try:
                client = GatewayClient("127.0.0.1", port, timeout=60.0)
                client.open(session="trace-probe", tenant="alice",
                            qos_class="interactive")
                client.send_frame({"x": [1.0] * 64})
                box["message"] = client.next_result(timeout=60.0)
                client.close()
            except Exception as error:
                box["error"] = f"{type(error).__name__}: {error}"

        thread = threading.Thread(target=probe, daemon=True)
        thread.start()
        runtime.run(until=lambda: not thread.is_alive(), timeout=60.0)
        if "message" not in box:
            result["pipeline_fleet_error"] = \
                box.get("error", "trace probe hung")
            return result
        trace_id = box["message"].get("trace")
        trace = None if trace_id is None \
            else pipeline.telemetry.traces.get(str(trace_id))
        if trace is None:
            result["pipeline_fleet_error"] = \
                f"gateway result carried no resolvable trace " \
                f"(trace={trace_id!r})"
            return result
        spans = trace["spans"]
        gateway_spans = sum(1 for span in spans
                            if span.get("kind") == "gateway")
        result.update({
            "fleet_trace_spans": len(spans),
            "fleet_trace_gateway_spans": gateway_spans,
            "fleet_trace_one_id": all(
                span.get("trace_id") == str(trace_id)
                for span in spans),
        })
        explain = pipeline.explain_frame(str(trace_id))
        if explain is not None and explain.get("coverage") is not None:
            result["fleet_trace_attribution_coverage"] = \
                explain["coverage"]
        if gateway_spans < 3 or len(spans) <= gateway_spans:
            result["pipeline_fleet_error"] = \
                f"door-to-decode trace incomplete: {len(spans)} " \
                f"span(s), {gateway_spans} from the gateway"
            return result

        # 2x overload through the door; the 1 ms objective burns.
        rate = 120.0
        spec = LoadSpec("alice", "interactive", rate=rate,
                        frames=int(rate * 2.0),
                        data={"x": [1.0] * 64}, window=32)

        def drive_load():
            try:
                box["report"] = run_loadgen("127.0.0.1", port, [spec])
            except Exception as error:
                box["load_error"] = f"{type(error).__name__}: {error}"

        thread = threading.Thread(target=drive_load, daemon=True)
        thread.start()
        runtime.run(until=lambda: not thread.is_alive(), timeout=120.0)
        # One more engine beat so the posted note_slo_burn lands on the
        # share dict.
        deadline = time_module.monotonic() + 0.5
        runtime.run(until=lambda: time_module.monotonic() > deadline,
                    timeout=5.0)
        snapshot = pipeline.qos.slo.snapshot()
        burns = snapshot.get("tenants", {}).get("alice", {})
        burn = (burns.get("interactive") or {}).get("burn", 0.0)
        result.update({
            "fleet_slo_fast_burns": snapshot.get("fired", 0),
            "fleet_slo_burn": burn,
            "fleet_slo_burn_on_share":
                bool(pipeline.share.get("slo_burn")),
        })
        if not snapshot.get("fired"):
            result["pipeline_fleet_error"] = \
                "SLO fast burn never fired under 2x overload against " \
                "a 1 ms p99 objective (burn plumbing broken)"

        # The in-process collector has been scraping at 20 Hz through
        # all of the above: /fleet must answer with merged rows and
        # ZERO scrape errors.
        collector = pipeline.fleet_collector
        collector.scrape_once()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet",
                timeout=10.0) as reply:
            fleet_text = reply.read().decode()
        rows = collector.members_snapshot()
        result.update({
            "fleet_scrapes": int(sum(row["scrapes"] for row in rows)),
            "fleet_scrape_errors": int(sum(row["errors"]
                                           for row in rows)),
            "fleet_exposition_has_latency":
                "aiko_frame_latency_ms" in fleet_text,
        })
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet/slo",
                timeout=10.0) as reply:
            fleet_slo = json_module.loads(reply.read().decode())
        result["fleet_slo_endpoint_sees_burn"] = bool(
            (fleet_slo.get("tenants") or {}).get("alice"))
    finally:
        runtime.terminate()

    previous = _previous_bench()
    for key in ("pipeline_fleet_fps", "pipeline_nofleet_fps",
                "fleet_trace_spans", "fleet_slo_burn"):
        prior = previous.get(key)
        if prior and result.get(key):
            result[f"{key}_vs_baseline"] = round(result[key] / prior,
                                                 2)
    return result


# ---------------------------------------------------------------------------
# 5. ASR real-time factor (BASELINE config 5): seconds of audio
#    transcribed per wall-clock second, batch of chunks, one dispatch
#    (mel frontend + encoder + KV-cached 128-token greedy decode all
#    on-device; the decode scan always runs the full static budget, so
#    random weights time the same program fitted ones would).

def bench_asr(rtt: float) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from aiko_services_tpu.models import asr as asr_model

    from jax import lax

    config = asr_model.AsrConfig.base()
    params = asr_model.init_params(jax.random.PRNGKey(0), config)
    batch = 8
    iters = 8          # amortize the dispatch+fetch overhead
    chunk = int(config.sample_rate * config.chunk_seconds)
    audio = jax.random.normal(jax.random.PRNGKey(1),
                              (batch, chunk)) * 0.1

    @jax.jit
    def loop(params, audio):
        def body(i, acc):
            perturbed = audio + i.astype(audio.dtype) * 1e-6
            tokens = asr_model.transcribe.__wrapped__(params, config,
                                                      perturbed)
            return acc + tokens.sum()
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    int(loop(params, audio))                       # compile + warm
    elapsed = time_device_loop(lambda: int(loop(params, audio)), rtt,
                               samples=3)
    audio_seconds = batch * iters * config.chunk_seconds
    result = {
        "asr_model": "whisper-class-base",
        "asr_batch": batch,
        "asr_chunk_seconds": config.chunk_seconds,
        "asr_rtf": round(audio_seconds / elapsed, 1),
        "asr_batch_latency_ms": round(elapsed / iters * 1000, 1),
    }

    # -- streaming: the hop-bounded partial path.
    # A partial decode re-transcribes the zero-padded buffered window
    # (models/asr.py StreamingAsr) -- ONE batch-1 dispatch of the same
    # compiled shape.  First-word latency is therefore bounded by
    # hop_seconds (audio buffering) + one partial decode, vs the
    # chunk_seconds=10 wait of whole-chunk transcription.
    hop_s = 1.0
    stream_iters = 16
    audio1 = jax.random.normal(jax.random.PRNGKey(2), (1, chunk)) * 0.1

    @jax.jit
    def partial_loop(params, audio):
        def body(i, acc):
            perturbed = audio + i.astype(audio.dtype) * 1e-6
            tokens = asr_model.transcribe.__wrapped__(params, config,
                                                      perturbed)
            return acc + tokens.sum()
        return lax.fori_loop(0, stream_iters, body, jnp.int32(0))

    int(partial_loop(params, audio1))              # compile + warm
    elapsed = time_device_loop(
        lambda: int(partial_loop(params, audio1)), rtt, samples=3)
    partial_ms = elapsed / stream_iters * 1000
    result["asr_stream_hop_seconds"] = hop_s
    result["asr_stream_partial_decode_ms"] = round(partial_ms, 2)
    result["asr_stream_first_word_latency_ms"] = round(
        hop_s * 1000 + partial_ms, 1)
    result["asr_chunked_first_word_latency_ms"] = round(
        config.chunk_seconds * 1000 + partial_ms, 1)

    # Functional streaming through the REAL StreamingAsr: speech-energy
    # hops then silence; the endpoint push (0.5 s trailing silence)
    # finalizes the utterance without waiting for the 10 s chunk.  Host
    # wall times include the event loop; the device cost is
    # asr_stream_partial_decode_ms above.
    from aiko_services_tpu.models.asr import StreamingAsr
    rate = config.sample_rate
    hop_n = int(rate * hop_s)
    rng = np.random.default_rng(0)
    speech = (rng.standard_normal(hop_n) * 0.3).astype(np.float32)
    silence = np.zeros(hop_n, dtype=np.float32)
    asr_model.transcribe(params, config,
                         jnp.zeros((1, chunk)))    # warm batch-1 jit
    streamer = StreamingAsr(params, config, hop_seconds=hop_s,
                            endpoint_silence=0.5)
    push_times = []
    for _ in range(4):
        start = time.perf_counter()
        streamer.push(speech)
        push_times.append(time.perf_counter() - start)
    start = time.perf_counter()
    finalized = streamer.push(silence)             # endpoint fires here
    endpoint_elapsed = time.perf_counter() - start
    result["asr_stream_partial_push_host_ms"] = round(
        sorted(push_times)[len(push_times) // 2] * 1000, 1)
    result["asr_stream_endpoint_finalize_host_ms"] = round(
        endpoint_elapsed * 1000, 1)
    result["asr_stream_partial_decodes"] = streamer.partial_decodes
    # flush() ran via the endpoint (chunks_transcribed counts finalized
    # windows; the 10 s chunk never filled -- 5 s of audio).
    del finalized
    result["asr_stream_endpoint_finalized"] = \
        streamer.chunks_transcribed >= 1
    return result


# ---------------------------------------------------------------------------
# 6. Speech pipeline end-to-end (BASELINE config 5): live audio hops ->
#    streaming ASR -> utterance gate -> LLM response, through the REAL
#    engine -- the multimodal streaming composition, measured as the
#    per-hop transcription latency and the utterance-end -> LLM-response
#    latency.

SPEECH_UTTERANCES = 3


def bench_speech_e2e() -> dict:
    import numpy as np
    from aiko_services_tpu.pipeline import Pipeline
    from aiko_services_tpu.runtime import init_process, reset_process
    from aiko_services_tpu.transport import reset_broker

    reset_broker()
    reset_process()
    runtime = init_process(transport="loopback")
    runtime.initialize()
    rate = 16000
    hop = rate                                  # 1 s hops
    rng = np.random.default_rng(0)
    speech_hop = (rng.standard_normal(hop) * 0.3).astype(np.float32)
    silence_hop = np.zeros(hop, dtype=np.float32)

    asr_params = {"model_size": "base", "streaming": True,
                  "hop_seconds": 1.0, "endpoint_silence": 0.5}
    definition = {
        "version": 0, "name": "bench_speech", "runtime": "jax",
        "graph": ["(ASR (GATE (LLM)))"], "parameters": {},
        "elements": [
            element("ASR", "ASR", ["audio", "sample_rate"],
                    ["text", "partial_text", "utterance_end"],
                    asr_params,
                    module="aiko_services_tpu.elements.speech"),
            # Only utterance-END frames reach the LLM; per-hop partial
            # frames drop here (the reference's speech pipelines act on
            # whisper's completed segments the same way).
            element("GATE", "TextFilter", ["text", "utterance_end"],
                    ["text"], {"gate": "utterance_end"},
                    module="aiko_services_tpu.elements.text"),
            element("LLM", "LLM", ["text"], ["text"],
                    {"model": "llama3-1b", "max_seq": 512,
                     "quantize": "int8", "decode_block": 16,
                     "inflight": 3, "max_new_tokens": 32},
                    module="aiko_services_tpu.elements.llm"),
        ]}
    pipeline = Pipeline(definition, runtime=runtime)
    responses: "queue.Queue" = queue.Queue()

    def push(samples):
        pipeline.process_frame_local(
            {"audio": samples, "sample_rate": rate},
            stream_id="speech", queue_response=responses)

    def await_response(timeout):
        runtime.run(until=lambda: not responses.empty(), timeout=timeout)
        if responses.empty():
            return None
        *_, okay, diagnostic = responses.get()
        return okay, diagnostic

    # Warmup utterance: compiles the batch-1 ASR window and (unless the
    # e2e section already compiled them in-process) the LLM shapes.
    for _ in range(3):
        push(speech_hop)
    push(silence_hop)
    warm = await_response(1800.0)
    if warm is None or not warm[0]:
        runtime.terminate()
        return {"speech_e2e_error":
                f"warmup failed: {warm[1] if warm else 'timeout'}"}

    # Per-hop transcription latency: the streaming ASR decodes the
    # padded window every hop; gated frames DROP, so time each speech
    # hop through the engine on a second, gate-free stream.
    solo = Pipeline({
        "version": 0, "name": "bench_speech_solo", "runtime": "jax",
        "graph": ["(ASR)"], "parameters": {},
        "elements": [element(
            "ASR", "ASR", ["audio", "sample_rate"],
            ["text", "partial_text", "utterance_end"], asr_params,
            module="aiko_services_tpu.elements.speech")]},
        runtime=runtime)
    solo_responses: "queue.Queue" = queue.Queue()
    hop_times = []
    for index in range(6):
        start = time.perf_counter()
        solo.process_frame_local(
            {"audio": speech_hop, "sample_rate": rate},
            stream_id="solo", queue_response=solo_responses)
        runtime.run(until=lambda: not solo_responses.empty(),
                    timeout=120.0)
        if solo_responses.empty():
            break
        solo_responses.get()
        if index:                       # first hop pays residual warmup
            hop_times.append(time.perf_counter() - start)

    # Utterance -> response: 3 speech hops, then the silence hop whose
    # endpoint finalizes the utterance and wakes the LLM.  The pumps
    # are non-blocking posts, so the measured window covers the queued
    # hops' decodes + the endpoint flush + the 32-token generation.
    endpoint_times = []
    for _ in range(SPEECH_UTTERANCES):
        for _ in range(3):
            push(speech_hop)
        endpoint_start = time.perf_counter()
        push(silence_hop)
        reply = await_response(600.0)
        if reply is None or not reply[0]:
            runtime.terminate()
            return {"speech_e2e_error":
                    f"utterance failed: {reply[1] if reply else 'timeout'}"}
        endpoint_times.append(time.perf_counter() - endpoint_start)
    runtime.terminate()

    def p50(values):
        return sorted(values)[len(values) // 2] if values else None

    result = {"speech_e2e_utterances": SPEECH_UTTERANCES,
              "speech_e2e_hop_seconds": 1.0}
    if hop_times:
        result["speech_e2e_hop_p50_ms"] = round(p50(hop_times) * 1000, 1)
    result["speech_e2e_utterance_to_response_p50_ms"] = round(
        p50(endpoint_times) * 1000, 1)
    return result


# ---------------------------------------------------------------------------

def main() -> int:
    logging.disable(logging.WARNING)
    import jax

    peak = chip_peak_flops()
    record: dict = {
        "device_kind": jax.devices()[0].device_kind,
        "device_platform": jax.devices()[0].platform,
        "chip_peak_bf16_flops": peak,
    }
    try:
        rtt = measure_rtt()
        record["dispatch_rtt_ms"] = round(rtt * 1000.0, 2)
    except Exception as error:
        record["rtt_error"] = f"{type(error).__name__}: {error}"
        rtt = 0.0
    # AIKO_BENCH_SECTIONS=control,kernels,... runs a comma-named subset
    # (names with or without the bench_ prefix); unset runs everything.
    wanted = {part.strip().removeprefix("bench_")
              for part in os.environ.get("AIKO_BENCH_SECTIONS",
                                         "").split(",") if part.strip()}
    for name, section in (
            ("bench_control", bench_control),
            ("bench_detect", lambda: bench_detect(peak, rtt)),
            ("bench_llm", lambda: bench_llm(peak, rtt)),
            ("bench_kernels", lambda: bench_kernels(peak, rtt)),
            ("bench_pipeline_e2e", bench_pipeline_e2e),
            ("bench_pipeline_fusion", bench_pipeline_fusion),
            ("bench_pipeline_transport", bench_pipeline_transport),
            ("bench_pipeline_stages", bench_pipeline_stages),
            ("bench_pipeline_explain", bench_pipeline_explain),
            ("bench_pipeline_faults", bench_pipeline_faults),
            ("bench_pipeline_replicas", bench_pipeline_replicas),
            ("bench_pipeline_gateway", bench_pipeline_gateway),
            ("bench_pipeline_failover", bench_pipeline_failover),
            ("bench_pipeline_controller", bench_pipeline_controller),
            ("bench_pipeline_fleet", bench_pipeline_fleet),
            ("bench_asr", lambda: bench_asr(rtt)),
            ("bench_speech_e2e", bench_speech_e2e)):
        if wanted and name.removeprefix("bench_") not in wanted:
            continue
        try:
            record.update(section())
        except Exception as error:          # keep the other sections
            record[f"{name}_error"] = f"{type(error).__name__}: {error}"

    control_fps = record.get("control_fps", 0.0)
    record.update({
        "metric": "control_fps+detect_fps+llm_tokens_per_sec",
        "value": control_fps,
        "unit": "frames/sec (control); see detect_fps/llm_* keys",
        "vs_baseline": round(control_fps / BASELINE_FPS, 2),
    })
    print(json.dumps(record))
    # A section that failed is a failed round: its exception was kept as
    # a ``<name>_error`` key so the other sections could still run, but
    # the exit code must not hide it.
    failed = sorted(key for key in record if key.endswith("_error"))
    if failed:
        print(f"bench: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0 if "control_fps" in record \
        and "llm_tokens_per_sec" in record else 1


if __name__ == "__main__":
    sys.exit(main())
