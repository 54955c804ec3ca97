#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path ONCE through the entry
points a user calls, on one TPU chip, at the full width of a model the
repo supports (llama3-1b: 16 layers, dim 2048, 32 query / 8 KV heads,
hidden 8192, vocabulary 128,256; weights random from a seed):

1. ``serve``   -- a pipeline built from a JSON definition by
   ``create_pipeline`` (the ``pipeline create`` path) with ``gateway:
   on`` and ``transfer_guard: disallow``: image -> micro-batched resize
   -> fused (donating) resize chain -> Detector -> DetectionCaption ->
   LLM in the serving configuration (device loop, paged KV, int8
   weights, flash prefill; on one chip the ``auto`` probes resolve the
   Pallas kernels: a paged cache takes the paged kernel at any
   extent).  A ``GatewayClient``
   in this process streams two waves of 8 frames at 640x640 over
   WebSocket ``/v1/stream``.  Passes only if every frame comes back ok,
   in order, with non-empty text; nothing implicit crossed to the host;
   no fused segment broke and one donated; the batcher never
   "recovered"; the probes chose ``paged-kernel`` and ``pallas-int8``;
   every parameter and cache leaf is on a TPU device.
2. ``kernels`` -- every entry of README's kernel table compiled by
   Mosaic (``interpret=False``) at the shapes llama3-1b serving gives
   it, bf16 and int8 cache forms, and compared with the XLA reference it
   falls back to at its tier-1 equivalence test's tolerance.

``--placed`` is the same definition on a four-chip host with
``placement`` blocks: detector on two chips, LLM (tp=2) on the other
two; it additionally requires disjoint submeshes, every LLM leaf inside
the LLM submesh, all four chips holding memory and a counted stage hop.

``--rehearse`` -- never the default, never inferred -- runs the same
phases at ``model: tiny`` on whatever backend is present (the CPU), so
chip time is not spent on control-flow bugs; its result says
``"rehearsal": true``.  Without it the script fails at once, before
building anything, unless ``jax.devices()[0].platform == "tpu"``.

One process; it spawns nothing; it needs no network and no prebuilt
native artifact.  Each phase prints one line; a phase that fails raises
and the exit code is non-zero.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time

FRAMES_PER_WAVE = 8
WAVES = 2
# The first wave pays every cold compile (detector buckets, llama3-1b
# prefill and decode loop); the whole run must end inside 1200 s.
FIRST_WAVE_TIMEOUT_S = 900.0
WAVE_TIMEOUT_S = 300.0


def _settings(rehearse: bool) -> dict:
    """Sizes of the run: the chip run is the serving shape; the
    rehearsal is the same graph and phases at test scale."""
    if rehearse:
        return {"model": "tiny", "max_seq": 128, "page": 16, "slots": 4,
                "image": 64, "sizes": (48, 32, 64), "max_new": 8,
                "ring": 4, "long_context": 256}
    return {"model": "llama3-1b", "max_seq": 1024, "page": 128,
            "slots": FRAMES_PER_WAVE, "image": 640,
            "sizes": (576, 512, 640), "max_new": 16, "ring": 8,
            "long_context": 8192}


def _element(name, cls, module, inputs, outputs, parameters=None,
             placement=None, lint=None) -> dict:
    entry = {"name": name,
             "input": [{"name": n} for n in inputs],
             "output": [{"name": n} for n in outputs],
             "parameters": parameters or {},
             "deploy": {"local": {
                 "module": f"aiko_services_tpu.elements.{module}",
                 "class_name": cls}}}
    if placement:
        entry["placement"] = placement
    if lint:
        entry["lint"] = lint
    return entry


def definition(settings: dict, placed: bool) -> dict:
    """video -> detect -> caption -> LLM behind the gateway, with a
    micro-batched resize and a fusable two-resize chain ahead of the
    detector: R0 parks frames (a fusion
    boundary) and leaves a frame-produced device ``image``; R1+R2 fuse
    into ONE dispatch that consumes and overwrites it -- the donation
    case, which only a non-CPU backend takes."""
    first, second, final = settings["sizes"]
    llm = {"model": settings["model"], "max_seq": settings["max_seq"],
           "quantize": "int8",
           "decode_block_tokens": settings["ring"], "inflight": 2,
           "kv_page_tokens": settings["page"],
           "max_new_tokens": settings["max_new"],
           "max_slots": settings["slots"],
           # A tp-sharded cache resolves the reference decode path, and
           # the Pallas prefill kernel cannot be partitioned across
           # chips: the placed run serves dense.
           "attention": "dense" if placed else "flash"}
    resize = ["image"], ["image"]
    return {
        "version": 0, "name": "chip_smoke", "runtime": "jax",
        "graph": ["(R0 (R1 (R2 (DET (CAP (LLM))))))"],
        "parameters": {"gateway": "on", "transfer_guard": "disallow",
                       "device_inflight": 3},
        "elements": [
            _element("R0", "ImageResize", "image", *resize,
                     {"width": first, "height": first}),
            _element("R1", "ImageResize", "image", *resize,
                     {"width": second, "height": second,
                      "synchronous": True}),
            _element("R2", "ImageResize", "image", *resize,
                     {"width": final, "height": final,
                      "synchronous": True}),
            # image/overlay are response-swag deliverables, not graph
            # inputs -- dead-output is the point here.
            _element("DET", "Detector", "detect", ["image"],
                     ["image", "overlay", "detections"],
                     placement={"devices": 2} if placed else None,
                     lint=["dead-output"]),
            _element("CAP", "DetectionCaption", "llm", ["detections"],
                     ["text"]),
            _element("LLM", "LLM", "llm", ["text"], ["text"], llm,
                     placement={"mesh": {"tp": 2}} if placed else None),
        ]}


@contextlib.contextmanager
def phase(name: str):
    """One line per phase; a phase's exception ends the run (nothing
    here catches it)."""
    facts: dict = {}
    start = time.perf_counter()
    yield facts
    detail = " ".join(f"{key}={value}" for key, value in facts.items())
    print(f"phase {name}: ok {time.perf_counter() - start:.1f}s "
          f"{detail}".rstrip(), flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise AssertionError(message)


# ---------------------------------------------------------------------------
# Phase 1: serve


def _client(port: int, image_edge: int, box: dict, results_in, released):
    """The WebSocket client: two waves of frames, every protocol
    message kept.  Runs off the event loop thread."""
    import numpy as np
    from aiko_services_tpu.gateway.client import GatewayClient

    try:
        rng = np.random.default_rng(0)
        client = GatewayClient("127.0.0.1", port,
                               timeout=FIRST_WAVE_TIMEOUT_S)
        client.open(session="smoke", tenant="smoke")
        messages = box["messages"] = []
        waves = box["wave_s"] = []
        for wave in range(WAVES):
            start = time.perf_counter()
            for _ in range(FRAMES_PER_WAVE):
                image = rng.integers(0, 255,
                                     (image_edge, image_edge, 3),
                                     dtype=np.uint8)
                client.send_frame({"image": {
                    "__tensor__": image.tolist(), "dtype": "uint8"}})
                box["sent"] = box.get("sent", 0) + 1
            owed = FRAMES_PER_WAVE
            while owed:
                message = client.recv(
                    timeout=FIRST_WAVE_TIMEOUT_S if wave == 0
                    else WAVE_TIMEOUT_S)
                if message.get("op") in ("result", "busy", "rejected"):
                    # Keep what the checks read, not the 1.2M-number
                    # image echoed back in ``data``.
                    data = message.get("data") or {}
                    messages.append({
                        "op": message["op"], "ok": message.get("ok"),
                        "frame": message.get("frame"),
                        "text": data.get("text"),
                        "detections": len(data.get("detections") or ()),
                        "diagnostic": message.get("diagnostic")})
                    owed -= 1
            waves.append(round(time.perf_counter() - start, 1))
        results_in.set()
        released.wait(timeout=WAVE_TIMEOUT_S)
        client.close()
    except BaseException as error:          # surfaced by the main thread
        box["error"] = error
        results_in.set()


def serve(settings: dict, placed: bool, rehearse: bool, facts: dict):
    import jax
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.ops import matmul_backend
    from aiko_services_tpu.pipeline import create_pipeline
    from aiko_services_tpu.runtime import init_process

    runtime = init_process(transport="loopback")
    runtime.initialize()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        path = os.path.join(workdir, "chip_smoke.json")
        with open(path, "w") as stream:
            json.dump(definition(settings, placed), stream)
        pipeline = create_pipeline(path, runtime=runtime)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    box: dict = {}
    results_in, released = threading.Event(), threading.Event()
    client = threading.Thread(
        target=_client, name="chip-smoke-client", daemon=True,
        args=(pipeline.gateway.port, settings["image"], box, results_in,
              released))
    client.start()
    llm = pipeline.graph.get_node("LLM").element

    def settled():
        """Every result is back AND the decode worker is idle, so the
        batcher's cache is whatever the last retired block returned."""
        if not results_in.is_set():
            return False
        batcher = llm._batcher
        return "error" in box or batcher is None or not (
            batcher.active_count or batcher.queue_depth
            or batcher.blocks_in_flight)

    runtime.run(until=settled,
                timeout=FIRST_WAVE_TIMEOUT_S + WAVES * WAVE_TIMEOUT_S)
    if "error" in box:
        raise box["error"]
    check(settled(), f"client stalled after {box.get('sent', 0)} frames "
                     f"sent, {len(box.get('messages', ()))} answered")

    # -- what came back ----------------------------------------------------
    sent, messages = box["sent"], box["messages"]
    ok = [m for m in messages if m["op"] == "result" and m["ok"]]
    facts.update(sent=sent, ok=len(ok), wave_s=box["wave_s"])
    check(sent == WAVES * FRAMES_PER_WAVE == len(ok) == len(messages),
          f"sent {sent}, {len(ok)} ok of {len(messages)} answers: "
          f"{[m for m in messages if m not in ok][:3]}")
    check([m["frame"] for m in ok] == list(range(sent)),
          f"results out of order: {[m['frame'] for m in ok]}")
    check(all(isinstance(m["text"], str) and m["text"] for m in ok),
          "a frame came back without generated text")
    facts["text0"] = json.dumps(ok[0]["text"][:24])

    # -- how it got there (read while the session's stream, which owns
    # the fused segments, is still open) ------------------------------------
    transfers = pipeline.transfer_stats()
    fusion = pipeline.fusion_stats()
    batcher = llm._batcher
    facts.update(
        implicit=transfers["implicit"], explicit=transfers["explicit"],
        llm_block_fetches=transfers["explicit_by_label"].get(
            "llm_block", 0),
        fused_dispatches=fusion["dispatches"], donated=fusion["donated"],
        broken=fusion["broken"], recoveries=batcher.recoveries,
        evictions=batcher.evictions, tokens=batcher.tokens_emitted,
        blocks=batcher.blocks_retired)
    check(transfers["implicit"] == 0,
          f"{transfers['implicit']} implicit host transfer(s)")
    check(fusion["broken"] == 0 and fusion["dispatches"] > 0,
          f"fused segments: {fusion}")
    check(batcher.recoveries == 0,
          f"the batcher replayed {batcher.recoveries} time(s): a decode "
          f"tick raised (see the log above)")
    check(batcher.tokens_emitted >= sent,
          f"{batcher.tokens_emitted} tokens for {sent} requests")

    decode = llama.resolve_decode_backend(batcher.config, batcher.cache)
    matmul = matmul_backend(batcher.config.matmul_kernel)
    facts.update(decode_backend=decode, matmul_backend=matmul)
    where = llm.model_devices()
    det_devices = sorted(
        str(d) for d in
        pipeline.graph.get_node("DET").element.plan.mesh.devices.flat)
    llm_devices = sorted(str(d) for d in where["params"] | where["cache"])
    facts.update(det_on=",".join(det_devices),
                 llm_on=",".join(llm_devices),
                 resize_on=str(jax.devices()[0]))
    if not rehearse:
        config = batcher.config
        check((config.n_layers, config.dim, config.n_heads,
               config.n_kv_heads, config.hidden_dim, config.vocab_size)
              == (16, 2048, 32, 8, 8192, 128_256), f"served {config}")
        check(fusion["donated"] > 0, "no fused dispatch donated a buffer")
        check(all(d.platform == "tpu"
                  for d in where["params"] | where["cache"]),
              f"LLM leaves off the TPU: {llm_devices}")
        if not placed:
            check(decode == "paged-kernel" and matmul == "pallas-int8",
                  f"probes chose decode={decode} matmul={matmul}")
    if placed:
        _check_placed(pipeline, where, decode, matmul, rehearse, facts)

    released.set()
    runtime.run(until=lambda: not client.is_alive(),
                timeout=WAVE_TIMEOUT_S)
    pipeline.stop()
    runtime.terminate()


def _check_placed(pipeline, where: dict, decode: str, matmul: str,
                  rehearse: bool, facts: dict) -> None:
    import jax
    placement = pipeline.stage_placement
    det = set(placement.plan("DET").mesh.devices.flat)
    llm = set(placement.plan("LLM").mesh.devices.flat)
    check(len(det) == 2 and len(llm) == 2 and not det & llm,
          f"submeshes: DET {det} LLM {llm}")
    check(where["params"] <= llm and where["cache"] <= llm,
          f"LLM leaves outside its submesh {llm}: {where}")
    check(decode == "reference" and matmul == "reference",
          f"a tp-sharded model resolved decode={decode} matmul={matmul}")
    facts["transfer_puts"] = placement.transfer_puts
    check(placement.transfer_puts > 0, "no stage hop was counted")
    if not rehearse:            # the CPU backend reports no memory stats
        in_use = {str(d): d.memory_stats()["bytes_in_use"]
                  for d in jax.devices()}
        facts["bytes_in_use"] = json.dumps(in_use)
        check(all(in_use.values()), f"an idle chip: {in_use}")


# ---------------------------------------------------------------------------
# Phase 2: kernels


def compare(name: str, kernel, reference, atol: float, rtol: float) -> str:
    """Run one kernel entry and its XLA reference (thunks returning an
    array or a tuple of arrays) and require them to agree: same shape,
    finite, within ``atol``/``rtol`` (both 0 = exactly equal).  Returns
    the worst absolute difference."""
    import jax
    import numpy as np
    worst = 0.0
    for got, want in zip(jax.tree_util.tree_leaves(kernel()),
                         jax.tree_util.tree_leaves(reference()),
                         strict=True):
        got = np.asarray(got, dtype=np.float32)
        want = np.asarray(want, dtype=np.float32)
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{name}: shape {got.shape} vs {want.shape}, "
              f"finite={np.isfinite(got).all()}")
        np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                                   err_msg=name)
        worst = max(worst, float(np.abs(got - want).max()))
    return f"{worst:.1e}"


def kernels(settings: dict, rehearse: bool, facts: dict) -> None:
    """The kernel-table entries at the serving shapes, Mosaic
    compiled (``interpret=False``; the rehearsal interprets), against
    the XLA reference each falls back to.  Queries are scaled up so the
    softmax is peaked: with diffuse random attention every output is
    near zero and an absolute tolerance would pass anything."""
    import jax
    import jax.numpy as jnp
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.models.quant import (dequantize_kv,
                                                quantize_kv,
                                                quantize_weight)
    from aiko_services_tpu.ops.layers import (attention_decode_append,
                                              attention_prefill)
    from aiko_services_tpu.ops.pallas_attention import flash_attention
    from aiko_services_tpu.ops.pallas_decode import (
        _split_paged, _split_stacked, flash_decode_append,
        flash_decode_append_paged, flash_decode_append_stacked,
        flash_verify_append)
    from aiko_services_tpu.ops.pallas_matmul import int8_matmul
    from aiko_services_tpu.ops.pallas_topk import topk

    interpret = rehearse
    config = {"tiny": llama.LlamaConfig.tiny,
              "llama3-1b": llama.LlamaConfig.llama3_1b}[
                  settings["model"]]()
    heads, kv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    dim, vocab = config.dim, config.vocab_size
    extent, page, slots = (settings["max_seq"], settings["page"],
                           settings["slots"])
    layers, layer, width = 2, 1, kv * hd
    chunk = min(512, extent)
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(21), 256))

    def normal(shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * scale).astype(dtype)

    def close(name, kernel, reference, atol=6e-2, rtol=6e-2):
        facts[name] = compare(name, kernel, reference, atol, rtol)

    # 1. flash_attention: one admission chunk against the slot's row
    # (the last chunk of a full-extent prompt), then the tuned default
    # 512 x 2048 tile at long context.
    for name, total in (("flash_attention", extent),
                        ("flash_attention[long]",
                         settings["long_context"])):
        q = normal((1, chunk, heads, hd), scale=4.0)
        k, v = normal((1, total, kv, hd)), normal((1, total, kv, hd))
        offset = total - chunk
        positions = offset + jnp.arange(chunk)[None, :]
        close(name,
              lambda: flash_attention(q, k, v, q_offset=offset,
                                      interpret=interpret),
              lambda: jax.jit(attention_prefill)(q, k, v, positions))

    # 1b. the same kernel with a BLOCK-causal frontier (generation by
    # diffusion over blocks of four, models/sdar.py): a query sees its
    # whole block; the chunk starts mid-way through a kernel block.
    block = 4
    q = normal((1, chunk, heads, hd), scale=4.0)
    k, v = normal((1, extent, kv, hd)), normal((1, extent, kv, hd))
    offset = (extent - chunk) // 2 // block * block
    frontier = ((offset + jnp.arange(chunk)) // block + 1)[None, :] \
        * block - 1
    close("flash_attention[block=4]",
          lambda: flash_attention(q, k, v, q_offset=offset,
                                  interpret=interpret, block_length=block),
          lambda: jax.jit(attention_prefill)(q, k, v, frontier))

    # 2-4. split-K decode: flat, stacked and paged, bf16 and int8.
    lengths = jnp.asarray(
        [(extent - 1) * (i + 1) // slots for i in range(slots)],
        dtype=jnp.int32).at[0].set(0)
    q = normal((slots, 1, heads, hd), scale=4.0)
    k_new, v_new = normal((slots, 1, kv, hd)), normal((slots, 1, kv, hd))
    cache_k = normal((layers, slots, extent, kv, hd))
    cache_v = normal((layers, slots, extent, kv, hd))
    # 5. flash_verify_append: the speculative chunk (4 drafts + 1).
    span = 5
    starts = jnp.minimum(lengths, extent - 1)
    positions = jnp.minimum(
        starts[:, None] + jnp.arange(span)[None, :], extent - 1)
    vq = normal((slots, span, heads, hd), scale=4.0)
    vk, vv = normal((slots, span, kv, hd)), normal((slots, span, kv, hd))
    # The paged pool holds the same rows behind a scrambled table
    # (page 0 stays the trash page).
    pps = extent // page
    table = 1 + jax.random.permutation(
        next(keys), slots * pps).reshape(slots, pps).astype(jnp.int32)

    def paged(side):
        """[L, B, T, ...] dense rows -> [L, P, pt, ...] pool."""
        rows = side.reshape(layers, slots * pps, page, *side.shape[3:])
        pool = jnp.zeros((layers, slots * pps + 1) + rows.shape[2:],
                         dtype=side.dtype)
        return pool.at[:, table.reshape(-1)].set(rows)

    def flatten(side):
        return side.reshape(layers, slots, extent, width)

    for form in ("bf16", "int8"):
        if form == "int8":
            sides = [quantize_kv(cache_k), quantize_kv(cache_v)]
            ref_k, ref_v = [dequantize_kv(s, bf16)[layer] for s in sides]
            flat = [{"int8": s["int8"][layer], "scale": s["scale"][layer]}
                    for s in sides]
            stacked = [{"int8": flatten(s["int8"]), "scale": s["scale"]}
                       for s in sides]
            pools = [{"int8": paged(flatten(s["int8"])),
                      "scale": paged(s["scale"])} for s in sides]
        else:
            ref_k, ref_v = cache_k[layer], cache_v[layer]
            flat = [ref_k, ref_v]
            stacked = [flatten(cache_k), flatten(cache_v)]
            pools = [paged(s) for s in stacked]
        # Bind this form's operands: the thunks below run later.
        def decode_reference(ref_k=ref_k, ref_v=ref_v):
            return jax.jit(attention_decode_append)(
                q, ref_k, ref_v, k_new, v_new, lengths)

        def verify_reference(ref_k=ref_k, ref_v=ref_v):
            return jax.jit(_verify_reference)(
                ref_k, ref_v, vq, vk, vv, starts, positions)

        close(f"flash_decode_attention[{form}]",
              lambda: flash_decode_append(
                  q, *flat, k_new, v_new, lengths, interpret=interpret),
              decode_reference)
        close(f"flash_decode_attention_stacked[{form}]",
              lambda: flash_decode_append_stacked(
                  q, *map(_split_stacked, stacked), jnp.int32(layer),
                  k_new, v_new, lengths, interpret=interpret),
              decode_reference)
        close(f"flash_decode_attention_paged[{form}]",
              lambda: flash_decode_append_paged(
                  q, *map(_split_paged, pools), jnp.int32(layer), k_new,
                  v_new, table, lengths, interpret=interpret),
              decode_reference)
        close(f"flash_verify_append[stacked,{form}]",
              lambda: flash_verify_append(
                  vq, *map(_split_stacked, stacked), jnp.int32(layer),
                  vk, vv, starts, positions, interpret=interpret),
              verify_reference)
        close(f"flash_verify_append[paged,{form}]",
              lambda: flash_verify_append(
                  vq, *map(_split_paged, pools), jnp.int32(layer), vk,
                  vv, starts, positions, page_table=table,
                  interpret=interpret),
              verify_reference)

    # 5a. the verify chunk as ONE block of a block-causal mask (a pass
    # of models/sdar.py): block-aligned rows, the block's own part
    # visible to all of its queries.
    aligned = starts // block * block
    within = jnp.minimum(aligned[:, None] + jnp.arange(block)[None, :],
                         extent - 1)
    pools = [paged(flatten(cache_k)), paged(flatten(cache_v))]
    close("flash_verify_append[paged,block=4]",
          lambda: flash_verify_append(
              vq[:, :block], *map(_split_paged, pools), jnp.int32(layer),
              vk[:, :block], vv[:, :block], aligned, within,
              page_table=table, interpret=interpret, block_mask=True),
          lambda: jax.jit(_verify_reference, static_argnums=7)(
              cache_k[layer], cache_v[layer], vq[:, :block],
              vk[:, :block], vv[:, :block], aligned, within, True))

    # 5b. the paged form across the shapes its pages-a-group follows
    # (ops/pallas_decode.py:paged_pages_per_step): page sizes 8..256 at
    # pps 4, 16 and 64, bf16 and int8 pools, decode and the verify
    # chunk -- a ragged batch with an inactive row in its middle (the
    # rehearsal interprets the first and the last shape only).
    paged_shapes = ((8, 64), (16, 16), (32, 64), (64, 16), (128, 16),
                    (256, 4))
    for page_tokens, slot_pages in paged_shapes[::5 if rehearse else 1]:
        reach = page_tokens * slot_pages
        live = jnp.asarray([reach - 1, 0, reach // 3, page_tokens + 1],
                           dtype=jnp.int32)
        rows = live.shape[0]
        rows_k = normal((layers, rows, reach, kv, hd))
        rows_v = normal((layers, rows, reach, kv, hd))
        sweep_table = 1 + jax.random.permutation(
            next(keys), rows * slot_pages).reshape(
                rows, slot_pages).astype(jnp.int32)

        def pool_of(side):
            pages = side.reshape(layers, rows * slot_pages, page_tokens,
                                 *side.shape[3:])
            pool = jnp.zeros((layers, rows * slot_pages + 1)
                             + pages.shape[2:], dtype=side.dtype)
            return pool.at[:, sweep_table.reshape(-1)].set(pages)

        dq = normal((rows, 1, heads, hd), scale=4.0)
        dk, dv = normal((rows, 1, kv, hd)), normal((rows, 1, kv, hd))
        sq = normal((rows, span, heads, hd), scale=4.0)
        sk = normal((rows, span, kv, hd))
        sv = normal((rows, span, kv, hd))
        at = jnp.minimum(live[:, None] + jnp.arange(span)[None, :],
                         reach - 1)
        for form in ("bf16", "int8"):
            if form == "int8":
                sides = [quantize_kv(rows_k), quantize_kv(rows_v)]
                ref = [dequantize_kv(s, bf16)[layer] for s in sides]
                pools = [{"int8": pool_of(s["int8"].reshape(
                              layers, rows, reach, width)),
                          "scale": pool_of(s["scale"])} for s in sides]
            else:
                ref = [rows_k[layer], rows_v[layer]]
                pools = [pool_of(s.reshape(layers, rows, reach, width))
                         for s in (rows_k, rows_v)]
            tag = f"pt={page_tokens},pps={slot_pages},{form}"
            close(f"flash_decode_attention_paged[{tag}]",
                  lambda pools=pools: flash_decode_append_paged(
                      dq, *map(_split_paged, pools), jnp.int32(layer),
                      dk, dv, sweep_table, live, interpret=interpret),
                  lambda ref=ref: jax.jit(attention_decode_append)(
                      dq, *ref, dk, dv, live))
            close(f"flash_verify_append[paged,{tag}]",
                  lambda pools=pools: flash_verify_append(
                      sq, *map(_split_paged, pools), jnp.int32(layer),
                      sk, sv, live, at, page_table=sweep_table,
                      interpret=interpret),
                  lambda ref=ref: jax.jit(_verify_reference)(
                      *ref, sq, sk, sv, live, at))

    # 6. int8_matmul: the quantized unembed, decode rows and one
    # prefill chunk's rows.
    leaf = quantize_weight(normal((dim, vocab), jnp.float32, dim ** -0.5))
    for rows in (slots, chunk):
        x = normal((rows, dim))
        close(f"int8_matmul[M={rows}]",
              lambda: int8_matmul(x, leaf["int8"], leaf["scale"],
                                  interpret=interpret),
              lambda: jax.jit(
                  lambda x: (x @ leaf["int8"].astype(x.dtype))
                  * leaf["scale"].astype(x.dtype))(x),
              atol=1e-1, rtol=2e-2)
    if not interpret:
        # The head goes to the kernel as the array the tree holds: a
        # pad of it inside a loop is a copy of the whole weight in
        # every decode step (ISSUE 34), and only the compiled program
        # shows whether one is left.
        def two_steps(x, w, s):
            return jax.lax.fori_loop(
                0, 2, lambda _, x: x + int8_matmul(
                    x, w, s, interpret=False)[:, :dim], x)
        text = jax.jit(two_steps).lower(
            normal((slots, dim)), leaf["int8"],
            leaf["scale"]).compile().as_text()
        pads = re.findall(r"= s8\[[\d,]*\](?:\{[^}]*\})? pad\(.*", text)
        if pads:
            raise RuntimeError(
                f"int8_matmul pads its int8 weight: {pads[0][:160]}")
        facts["int8_matmul[loop]"] = "no s8 pad"

    # 7. topk: EXACTLY lax.top_k, values and indices, on the f32 logits
    # sampling hands it and on raw bf16 logits.
    for dtype, k in ((jnp.float32, 8), (bf16, 4)):
        logits = normal((slots, vocab), dtype)
        close(f"topk[{jnp.dtype(dtype).name},k={k}]",
              lambda: topk(logits, k, interpret=interpret),
              lambda: jax.lax.top_k(logits, k), atol=0, rtol=0)

    # 8-9. the gated delta rule (ops/pallas_gdn.py) at Olmo-Hybrid-7B's
    # widths -- 30 heads of 96 / 192 -- against the jax.numpy form each
    # falls back to: one 512-token admission chunk from a state that is
    # not zero, and one decode step over the stored pool with rows that
    # sit out (they keep their state bit for bit).  float32 both sides.
    from aiko_services_tpu.ops.pallas_gdn import (
        gated_delta_chunk_scan, gated_delta_decode_step, pack_state,
        state_pack)
    g_heads, g_dk, g_dv, g_chunk = (6, 24, 64, 128) if rehearse \
        else (30, 96, 192, 512)
    f32 = jnp.float32

    def unit(rows):
        return rows / jnp.linalg.norm(rows, axis=-1, keepdims=True)

    def recurrence_inputs(tokens):
        return (unit(normal((tokens, g_heads, g_dk), f32)) * g_dk ** -0.5,
                unit(normal((tokens, g_heads, g_dk), f32)),
                normal((tokens, g_heads, g_dv), f32),
                -jnp.exp(normal((tokens, g_heads), f32, 2.0) - 3.0),
                2.0 * jax.nn.sigmoid(normal((tokens, g_heads), f32, 2.0)))

    scan_inputs = recurrence_inputs(g_chunk)
    state = normal((g_heads, g_dk, g_dv), f32)
    close("gated_delta_chunk_scan",
          lambda: gated_delta_chunk_scan(*scan_inputs, state, kernel=True,
                                         interpret=interpret),
          lambda: jax.jit(gated_delta_chunk_scan)(*scan_inputs, state),
          atol=1e-4, rtol=1e-4)
    pack = state_pack(g_heads, g_dv)
    pool = pack_state(normal((3, slots, g_heads, g_dk, g_dv), f32), pack)
    step_inputs = recurrence_inputs(slots)
    decoding = jnp.arange(slots) % 3 != 1
    close("gated_delta_decode_step",
          lambda: gated_delta_decode_step(
              *step_inputs, pool, jnp.int32(1), decoding, pack=pack,
              kernel=True, interpret=interpret),
          lambda: jax.jit(lambda *inputs: gated_delta_decode_step(
              *inputs, pool, jnp.int32(1), decoding, pack=pack))(
                  *step_inputs),
          atol=1e-5, rtol=1e-5)

    # 10-11. the Mamba-2 SSD (ops/pallas_ssd.py) at Falcon-H1-34B's
    # widths -- 32 heads of 128, a state of 256, 2 groups, sub-chunks of
    # 128 -- against the jax.numpy form each falls back to: one 512-token
    # admission chunk from a state that is not zero, and one decode step
    # over the stored pool with rows that sit out.  float32 both sides.
    from aiko_services_tpu.ops.pallas_ssd import (ssd_chunk_scan,
                                                  ssd_decode_step)
    s_heads, s_dim, s_state, s_groups, s_sub = (4, 8, 16, 2, 8) \
        if rehearse else (32, 128, 256, 2, 128)

    def ssd_inputs(tokens):
        return (normal((tokens, s_heads, s_dim), f32),
                jnp.exp(normal((tokens, s_heads), f32, 1.5) - 4.0),
                -jnp.exp(normal((s_heads,), f32)),
                normal((tokens, s_groups, s_state), f32),
                normal((tokens, s_groups, s_state), f32),
                jnp.ones((s_heads,), f32))

    chunk_inputs = ssd_inputs(g_chunk)
    state = normal((s_heads, s_dim, s_state), f32)
    close("ssd_chunk_scan",
          lambda: ssd_chunk_scan(*chunk_inputs, state, chunk=s_sub,
                                 kernel=True, interpret=interpret),
          lambda: jax.jit(lambda *inputs: ssd_chunk_scan(
              *inputs, chunk=s_sub))(*chunk_inputs, state),
          atol=1e-3, rtol=1e-4)
    pool = normal((3, slots, s_heads, s_dim, s_state), f32)
    step_inputs = ssd_inputs(slots)
    close("ssd_decode_step",
          lambda: ssd_decode_step(*step_inputs, pool, jnp.int32(1),
                                  decoding, kernel=True,
                                  interpret=interpret),
          lambda: jax.jit(lambda *inputs: ssd_decode_step(
              *inputs, pool, jnp.int32(1), decoding))(*step_inputs),
          atol=1e-5, rtol=1e-5)


def _verify_reference(k_rows, v_rows, q, k_new, v_new, starts, positions,
                      block_mask=False):
    """The dense concat-attention ``llama._chunk_verify`` falls back
    to (tests/test_kernel_plane.py::_verify_reference, verbatim);
    ``block_mask``: the chunk's keys all take its first position, so
    every query of the chunk sees all of it (``sdar._pass_impl``)."""
    import jax.numpy as jnp
    from aiko_services_tpu.ops.layers import attention_prefill
    b, t = k_rows.shape[:2]
    s = q.shape[1]
    k_all = jnp.concatenate([k_rows, k_new], axis=1)
    v_all = jnp.concatenate([v_rows, v_new], axis=1)
    own = jnp.broadcast_to(positions[:, :1], (b, s)) if block_mask \
        else positions
    kv_positions = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(t)[None, :], (b, t)), own], axis=1)
    valid = jnp.concatenate(
        [jnp.arange(t)[None, :] < starts[:, None],
         jnp.ones((b, s), dtype=bool)], axis=1)
    return attention_prefill(q, k_all, v_all, positions,
                             kv_length_mask=valid,
                             kv_positions=kv_positions)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--placed", action="store_true",
                        help="the four-chip placed run (needs 4 chips)")
    parser.add_argument("--rehearse", action="store_true",
                        help="same phases at model: tiny on whatever "
                             "backend is present; never the default")
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; jax found platform="
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} device(s)).  --rehearse runs the "
              f"phases at model: tiny off the chip.", file=sys.stderr)
        return 2
    want = 4 if args.placed else 1
    if device["count"] < want:
        print(f"chip_smoke: --placed needs {want} devices, jax found "
              f"{device['count']}", file=sys.stderr)
        return 2

    from aiko_services_tpu.pipeline import setup_compilation_cache
    cache_dir = setup_compilation_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    def version(package):
        try:
            return importlib.metadata.version(package)
        except importlib.metadata.PackageNotFoundError:
            return "absent"
    print(f"chip_smoke: platform={device['platform']} "
          f"kind={device['kind']!r} devices={device['count']} "
          f"jax={version('jax')} jaxlib={version('jaxlib')} "
          f"libtpu={version('libtpu')} compile_cache={cache_dir} "
          f"cache_entries={cached} "
          f"({'warm' if cached else 'cold'}) "
          f"mode={'rehearse' if args.rehearse else 'chip'}"
          f"{'+placed' if args.placed else ''}", flush=True)

    settings = _settings(args.rehearse)
    start = time.perf_counter()
    with phase("serve") as facts:
        serve(settings, args.placed, args.rehearse, facts)
    if not args.placed:
        with phase("kernels") as facts:
            kernels(settings, args.rehearse, facts)
    print(f"chip_smoke: all phases ok in "
          f"{time.perf_counter() - start:.1f}s; compile cache now "
          f"{len(os.listdir(cache_dir))} entries", flush=True)
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
