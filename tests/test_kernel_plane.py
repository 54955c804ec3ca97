"""Kernel plane (ISSUE 11): paged flash-decode, batched chunk-verify,
fused int8 dequant-matmul and on-TPU top-k -- every kernel exercised
under ``interpret=True`` on the CPU mesh, so the equivalence tests gate
PRs without TPU hardware (the ``kernel-test`` selfcheck rule enforces
the kernel <-> test pairing repo-wide)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama
from aiko_services_tpu.models.paged import init_paged_cache
from aiko_services_tpu.models.quant import (dequantize_kv, quantize_kv,
                                            quantize_params,
                                            quantize_weight)
from aiko_services_tpu.ops import decode_backend, matmul_backend, topk
from aiko_services_tpu.ops.layers import (attention_decode_append,
                                          attention_prefill)
from aiko_services_tpu.ops.pallas_decode import (
    _combine_self, _prep_query, _split_paged, flash_decode_append_paged,
    flash_decode_attention, flash_decode_attention_paged,
    flash_verify_append, paged_grid_steps, paged_pages_per_step)
from aiko_services_tpu.ops.pallas_matmul import (
    VMEM_BUDGET_BYTES, int8_matmul, matmul_blocks, tile_bytes)
from aiko_services_tpu.ops.pallas_topk import topk as pallas_topk
from conftest import all_eqns, run_until


# -- paged flash-decode -----------------------------------------------------

def _paged_case(key, dtype=jnp.float32, quantized=False):
    """A small paged pool + table whose gathered view is the dense
    reference: L=2 layers, 3 slots x 4 logical pages of 32 tokens."""
    L, P, pt, B, K, G, hd = 2, 13, 32, 3, 2, 2, 16
    C = K * hd
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7], [8, 9, 0, 0]],
                        dtype=jnp.int32)
    lengths = jnp.asarray([70, 128, 33], dtype=jnp.int32)
    raw_k = jax.random.normal(key, (L, P, pt, K, hd), dtype=jnp.float32)
    raw_v = jax.random.normal(jax.random.fold_in(key, 1),
                              (L, P, pt, K, hd), dtype=jnp.float32)
    if quantized:
        qk, qv = quantize_kv(raw_k), quantize_kv(raw_v)
        pool_k = {"int8": qk["int8"].reshape(L, P, pt, C),
                  "scale": qk["scale"]}
        pool_v = {"int8": qv["int8"].reshape(L, P, pt, C),
                  "scale": qv["scale"]}
        dense_k = dequantize_kv(qk, jnp.float32)
        dense_v = dequantize_kv(qv, jnp.float32)
    else:
        pool_k = raw_k.reshape(L, P, pt, C).astype(dtype)
        pool_v = raw_v.reshape(L, P, pt, C).astype(dtype)
        dense_k = pool_k.reshape(L, P, pt, K, hd)
        dense_v = pool_v.reshape(L, P, pt, K, hd)
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, K * G, hd),
                          dtype=dtype)
    k_new = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, K, hd),
                              dtype=dtype)
    v_new = jax.random.normal(jax.random.fold_in(key, 4), (B, 1, K, hd),
                              dtype=dtype)
    return (pool_k, pool_v, dense_k, dense_v, table, lengths, q, k_new,
            v_new, dict(L=L, P=P, pt=pt, B=B, K=K, G=G, hd=hd, C=C))


@pytest.mark.parametrize("pages_per_step", [1, 2, 4, 8])
def test_paged_kernel_bitwise_matches_dense_kernel(pages_per_step):
    """f32 acceptance gate: the paged kernel walking the page table
    in-kernel is BITWISE identical to the dense split-K kernel run on
    the gathered contiguous view at ``block_t`` = one page, on every
    layer and at every count of pages a group (a group's pages are
    folded one by one, in order: the same op sequence) -- the strongest
    possible no-gather equivalence.  8 pages a group is more than the
    slot's 4: the pages past the table are dead."""
    (pool_k, pool_v, dense_k, dense_v, table, lengths, q, _, _,
     dims) = _paged_case(jax.random.PRNGKey(0))
    B, pt, K, hd, C = (dims["B"], dims["pt"], dims["K"], dims["hd"],
                       dims["C"])
    h = q.shape[2]
    q_pad, _, _, _ = _prep_query(q[:, 0], h, K, hd)
    for layer in range(dims["L"]):
        gathered = pool_k[layer][table].reshape(B, -1, C)
        gathered_v = pool_v[layer][table].reshape(B, -1, C)
        acc_d, m_d, l_d = flash_decode_attention(
            q_pad, gathered, gathered_v, None, None, lengths,
            block_t=pt, interpret=True)
        acc_p, m_p, l_p = flash_decode_attention_paged(
            q_pad, pool_k, pool_v, None, None, jnp.int32(layer), table,
            lengths, interpret=True, pages_per_step=pages_per_step)
        for dense, paged in ((acc_d, acc_p), (m_d, m_p), (l_d, l_p)):
            assert np.array_equal(np.asarray(dense), np.asarray(paged))


def _ragged_paged_case(key, pool: str):
    """Seven slots x 8 logical pages of 16 tokens whose lengths are 0,
    1, an exact page multiple, one past it, the full extent, 0 again
    (an inactive row between live ones) and a ragged middle; the last
    row's first two pages ARE the fourth row's (a shared prefix: one
    physical page under two rows).  Every table entry past a row's
    length names a page of NaNs (and so do the inactive rows'): a copy
    or a product of a dead page would show.  ``pool``: ``bf16`` (bf16
    queries) or ``int8`` (f32 queries: the in-kernel dequantisation is
    exact)."""
    L, P, pt, K, G, hd, pps = 2, 40, 16, 2, 2, 16, 8
    C = K * hd
    lengths = jnp.asarray([0, 1, 32, 33, 128, 0, 70], dtype=jnp.int32)
    B = lengths.shape[0]
    poison = P - 1
    table = np.full((B, pps), poison, dtype=np.int32)
    free = iter(np.random.default_rng(0).permutation(np.arange(1, poison)))
    for row, length in enumerate(np.asarray(lengths)):
        for page in range(-(-int(length) // pt)):
            table[row, page] = next(free)
    table[6, :2] = table[3, :2]
    table = jnp.asarray(table)
    raw_k = jax.random.normal(key, (L, P, pt, K, hd), dtype=jnp.float32)
    raw_v = jax.random.normal(jax.random.fold_in(key, 1),
                              (L, P, pt, K, hd), dtype=jnp.float32)
    if pool == "int8":
        dtype = jnp.float32
        qk, qv = quantize_kv(raw_k), quantize_kv(raw_v)
        pools = tuple(
            {"int8": side["int8"].reshape(L, P, pt, C),
             "scale": side["scale"].at[:, poison].set(jnp.nan)}
            for side in (qk, qv))
        dense = (dequantize_kv(qk, dtype), dequantize_kv(qv, dtype))
    else:
        dtype = jnp.bfloat16
        pools = tuple(
            side.reshape(L, P, pt, C).astype(dtype)
            .at[:, poison].set(jnp.nan) for side in (raw_k, raw_v))
        dense = tuple(side.reshape(L, P, pt, K, hd) for side in pools)
    # (the reference reads the dead pages as zeros: it masks them, and
    # a masked NaN is still a NaN)
    dense = tuple(jnp.nan_to_num(side) for side in dense)
    return pools, dense, table, lengths, dtype, \
        dict(B=B, K=K, H=K * G, hd=hd, pt=pt, pps=pps)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("pages_per_step", [1, 2, 4, 8])
def test_paged_pages_per_step_match_dense_reference(pages_per_step, pool):
    """The several-pages-a-group form against the dense reference over
    the gathered view: every count of pages a group x raw and int8
    pools, on lengths 0 / 1 / a page multiple / one past it / the full
    extent, with a physical page shared by two rows, an inactive row
    between live ones and every dead page full of NaNs."""
    key = jax.random.PRNGKey(20 + pages_per_step)
    pools, dense, table, lengths, dtype, dims = _ragged_paged_case(
        key, pool)
    B, K, H, hd = dims["B"], dims["K"], dims["H"], dims["hd"]
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, H, hd),
                          dtype=dtype)
    k_new = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, K, hd),
                              dtype=dtype)
    v_new = jax.random.normal(jax.random.fold_in(key, 4), (B, 1, K, hd),
                              dtype=dtype)
    q_pad, blocks, onehot, scale = _prep_query(q[:, 0], H, K, hd)
    tolerance = 6e-2 if pool == "bf16" else 1e-4
    for layer in range(2):
        (k_payload, k_scale), (v_payload, v_scale) = (
            _split_paged(pools[0]), _split_paged(pools[1]))
        acc, m, l = flash_decode_attention_paged(
            q_pad, k_payload, v_payload, k_scale, v_scale,
            jnp.int32(layer), table, lengths, interpret=True,
            pages_per_step=pages_per_step)
        out = _combine_self(acc, m, l, q[:, 0], k_new, v_new, blocks,
                            onehot, scale, K, hd).reshape(q.shape)
        reference = attention_decode_append(
            q, dense[0][layer][table].reshape(B, -1, K, hd).astype(dtype),
            dense[1][layer][table].reshape(B, -1, K, hd).astype(dtype),
            k_new, v_new, lengths)
        assert np.isfinite(np.asarray(out, dtype=np.float32)).all()
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32),
            np.asarray(reference, dtype=np.float32),
            atol=tolerance, rtol=tolerance)


def _copy_schedule(lengths, pt, pps, pages):
    """The paged kernel's copy schedule replayed on the host, statement
    for statement (``_paged_kernel``: prime, then per group 'start the
    next group -- the next live row's first at a row's end -- and fold
    this one'): the (row, logical page, slot) copies in the order they
    start, and the order they are waited for."""
    lengths = np.minimum(np.asarray(lengths), pps * pt)
    rows = len(lengths)
    started, waited = [], []
    slot, primed = 0, False

    def start_group(row, group, slot):
        for index in range(pages):
            logical = group * pages + index
            if logical * pt < lengths[row]:
                started.append((row, logical, slot, index))

    for b in range(rows):
        groups = -(-lengths[b] // (pt * pages))
        if groups > 0 and not primed:
            start_group(b, 0, slot)
            primed = True
        for group in range(groups):
            other = 1 - slot
            if group + 1 < groups:
                start_group(b, group + 1, other)
            else:
                following = b + 1
                while following < rows and lengths[following] <= 0:
                    following += 1
                if following < rows:
                    start_group(following, 0, other)
            for index in range(pages):
                logical = group * pages + index
                if logical * pt < lengths[b]:
                    waited.append((b, logical, slot, index))
            slot = other
    return started, waited


@pytest.mark.parametrize("pages_per_step", [1, 2, 3, 4, 8, 16])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_copy_schedule_copies_each_live_page_once(seed,
                                                        pages_per_step):
    """The kernel's copy schedule on the host: every live (row, page)
    is copied exactly once and waited for exactly once, in the order
    the rows and pages are folded; a dead page and an inactive row copy
    nothing; a buffer slot is never overwritten while its copy is
    awaited (at most one group in flight beside the one being folded);
    and the steps the schedule takes are what ``paged_grid_steps``
    counts."""
    rng = np.random.default_rng(seed)
    B, pps, pt = 12, 16, 32
    lengths = rng.integers(1, pps * pt + 1, size=B)
    lengths[rng.integers(0, B, size=3)] = 0          # inactive rows
    lengths[1], lengths[2] = pps * pt, 5 * pt        # full; a multiple
    if seed == 2:
        lengths[:4] = 0                      # the first rows inactive
    started, waited = _copy_schedule(lengths, pt, pps, pages_per_step)
    live = [(row, page) for row in range(B)
            for page in range(-(-lengths[row] // pt))]
    assert sorted((row, page) for row, page, _, _ in started) == live
    assert [(row, page) for row, page, _, _ in waited] == live
    assert sorted(started) == sorted(waited)   # the same slot and index
    # a (slot, index) buffer is free again before it is copied into
    position = {copy: n for n, copy in enumerate(waited)}
    busy = {}
    for copy in started:
        row, page, slot, index = copy
        earlier = busy.get((slot, index))
        if earlier is not None:
            # the copy that used this buffer before was waited for (and
            # folded) before the waits that follow this start
            assert position[earlier] < position[copy]
        busy[(slot, index)] = copy
    live_steps, steps = paged_grid_steps(lengths, pt, pps, pages_per_step)
    groups = {(row, page // pages_per_step) for row, page in live}
    assert live_steps == len(groups)
    assert steps == live_steps + int((lengths == 0).sum())


def test_paged_pages_per_step_follows_shapes():
    """Pages a group come from the static shapes alone: four
    [128, 1024] bf16 pages (K and V, two groups each: 4 MiB), a page a
    group where one page fills the budget or the slot holds one, int8
    pools nearly twice the pages, tiny pages capped at 16, and an even
    split where the budget does not divide the slot."""
    def view(page_tokens, c, dtype, n_kv=None):
        payload = jax.ShapeDtypeStruct((2, 9, page_tokens, c), dtype)
        scale = None if n_kv is None else jax.ShapeDtypeStruct(
            (2, 9, n_kv, page_tokens), jnp.float32)
        return payload, scale
    assert paged_pages_per_step(view(128, 1024, jnp.bfloat16), 16) == 4
    assert paged_pages_per_step(view(512, 1024, jnp.bfloat16), 8) == 1
    assert paged_pages_per_step(view(128, 1024, jnp.bfloat16), 1) == 1
    # (int8: 135,168 B a page with its scales: 7 fit, 64 = 10 x 7 - 6)
    assert paged_pages_per_step(view(128, 1024, jnp.int8, 8), 64) == 7
    assert paged_pages_per_step(view(8, 1024, jnp.bfloat16), 64) == 16
    assert paged_pages_per_step(view(128, 1024, jnp.bfloat16), 10) == 4
    assert paged_pages_per_step(view(128, 1024, jnp.bfloat16), 9) == 3


def test_paged_grid_steps_counts_steps_with_a_live_page():
    """The host's count behind ``llm_decode_live_grid_share``: a row of
    ``length`` tokens takes ``ceil(ceil(length / pt) / K)`` steps, each
    with a live page; an inactive row its grid step, with none."""
    lengths = np.array([0, 1, 128, 129, 631, 1024, 1025, 2048, 9999, 0])
    assert paged_grid_steps(lengths, 128, 16, 1) == (
        1 + 1 + 2 + 5 + 8 + 9 + 16 + 16, 58 + 2)
    assert paged_grid_steps(lengths, 128, 16, 8) == (
        1 + 1 + 1 + 1 + 1 + 2 + 2 + 2, 11 + 2)
    assert paged_grid_steps(lengths, 128, 16, 4) == (
        1 + 1 + 1 + 2 + 2 + 3 + 4 + 4, 18 + 2)


def test_paged_append_matches_dense_reference_f32():
    (pool_k, pool_v, dense_k, dense_v, table, lengths, q, k_new, v_new,
     dims) = _paged_case(jax.random.PRNGKey(1))
    B, K, hd = dims["B"], dims["K"], dims["hd"]
    layer = 1
    out = flash_decode_append_paged(
        q, _split_paged(pool_k), _split_paged(pool_v), jnp.int32(layer),
        k_new, v_new, table, lengths, interpret=True)
    gathered_k = dense_k[layer][table].reshape(B, -1, K, hd)
    gathered_v = dense_v[layer][table].reshape(B, -1, K, hd)
    reference = attention_decode_append(q, gathered_k, gathered_v,
                                        k_new, v_new, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-5, rtol=1e-5)


def test_paged_append_int8_pools_dequantized_in_kernel():
    """int8 scale pools ride their pages and dequantize in-kernel --
    exact relative to dequantize-then-dense (no weight quantization on
    this path, the flash-decode discipline)."""
    (pool_k, pool_v, dense_k, dense_v, table, lengths, q, k_new, v_new,
     dims) = _paged_case(jax.random.PRNGKey(2), quantized=True)
    B, K, hd = dims["B"], dims["K"], dims["hd"]
    layer = 0
    out = flash_decode_append_paged(
        q, _split_paged(pool_k), _split_paged(pool_v), jnp.int32(layer),
        k_new, v_new, table, lengths, interpret=True)
    reference = attention_decode_append(
        q, dense_k[layer][table].reshape(B, -1, K, hd),
        dense_v[layer][table].reshape(B, -1, K, hd), k_new, v_new,
        lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-4, rtol=1e-4)


def test_paged_append_bf16_tolerance():
    (pool_k, pool_v, dense_k, dense_v, table, lengths, q, k_new, v_new,
     dims) = _paged_case(jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    B, K, hd = dims["B"], dims["K"], dims["hd"]
    layer = 1
    out = flash_decode_append_paged(
        q, _split_paged(pool_k), _split_paged(pool_v), jnp.int32(layer),
        k_new, v_new, table, lengths, interpret=True)
    reference = attention_decode_append(
        q, dense_k[layer][table].reshape(B, -1, K, hd).astype(q.dtype),
        dense_v[layer][table].reshape(B, -1, K, hd).astype(q.dtype),
        k_new, v_new, lengths)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(reference, dtype=np.float32), atol=6e-2, rtol=6e-2)


def test_mixed_quantization_paged_views_rejected():
    (pool_k, pool_v, *_rest, table_lengths) = _paged_case(
        jax.random.PRNGKey(4))
    (pool_kq, pool_vq, _, _, table, lengths, q, k_new, v_new,
     _) = _paged_case(jax.random.PRNGKey(4), quantized=True)
    with pytest.raises(ValueError, match="quantization state"):
        flash_decode_append_paged(
            q, _split_paged(pool_kq), _split_paged(pool_v),
            jnp.int32(0), k_new, v_new, table, lengths, interpret=True)


# -- decode_step / decode_loop integration ----------------------------------

def _fully_mapped_paged_cache(config, batch, page_tokens):
    """Paged cache with every slot's logical pages mapped to distinct
    physical pages (full provisioning, deterministic layout)."""
    cache = init_paged_cache(config, batch, config.max_seq, page_tokens)
    pps = config.max_seq // page_tokens
    table = np.arange(1, batch * pps + 1, dtype=np.int32) \
        .reshape(batch, pps)
    cache["page_table"] = jnp.asarray(table)
    return cache


def _paged_decode_logits(config, steps=6):
    params = llama.init_params(jax.random.PRNGKey(0), config)
    cache = _fully_mapped_paged_cache(config, 2, 32)
    lengths = jnp.zeros(2, dtype=jnp.int32)
    outs = []
    for step in range(steps):
        tokens = jnp.asarray([10 + step, 20 + step], dtype=jnp.int32)
        logits, cache = llama.decode_step(params, config, tokens, cache,
                                          lengths)
        lengths = lengths + 1
        outs.append(logits)
    return jnp.stack(outs)


def test_decode_step_paged_kernel_matches_dense_gather():
    """decode_step on a paged cache with decode_attention='flash' (the
    request that used to RAISE) evolves the same cache and produces the
    same logits as the dense gather path over multiple steps."""
    base = llama.LlamaConfig.tiny(vocab_size=64, max_seq=128)
    dense = _paged_decode_logits(
        dataclasses.replace(base, decode_attention="dense"))
    flash = _paged_decode_logits(
        dataclasses.replace(base, decode_attention="flash"))
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=5e-2, rtol=2e-2)


def test_decode_loop_paged_kernel_token_identical():
    """The device-resident serving loop on a paged cache: paged-kernel
    vs reference backends emit IDENTICAL token streams at temperature 0
    (greedy ties broken the same way on this seed)."""
    base = llama.LlamaConfig.tiny(vocab_size=64, max_seq=128)
    streams = {}
    for name, attention in (("kernel", "flash"), ("reference", "dense")):
        config = dataclasses.replace(base, decode_attention=attention)
        params = llama.init_params(jax.random.PRNGKey(0), config)
        cache = _fully_mapped_paged_cache(config, 2, 32)
        out = llama.decode_loop(
            params, config,
            jnp.asarray([7, 11], dtype=jnp.int32), cache,
            jnp.asarray([1, 1], dtype=jnp.int32),
            jnp.ones(2, dtype=bool),
            jnp.full((2,), 12, dtype=jnp.int32),
            jnp.zeros(2, dtype=jnp.float32),
            jnp.full((2, 1), -1, dtype=jnp.int32),
            jnp.full((2, 1), -1, dtype=jnp.int32),
            jax.random.PRNGKey(5), ring=8)
        emitted, counts = out[0], out[1]
        streams[name] = (np.asarray(emitted), np.asarray(counts))
    assert np.array_equal(streams["kernel"][1], streams["reference"][1])
    assert np.array_equal(streams["kernel"][0], streams["reference"][0])


# -- a paged cache under the flash threshold (ISSUE 37) --------------------

def _random_pools(cache, key):
    """The cache with both pools drawn from ``key``: any length is then
    a history, without a prefill."""
    out = dict(cache)
    for index, side in enumerate(("k", "v")):
        out[side] = jax.random.normal(
            jax.random.fold_in(key, index), cache[side].shape,
            dtype=jnp.float32).astype(cache[side].dtype)
    return out


def test_paged_kernel_part_full_batch_matches_gather():
    """``camera-paced``'s geometry in small: 16 slots of 4 pages, 3
    rows live with ONE part-full page each, the other 13 at length 0
    with no page at all (their table rows hold the trash page).  The
    paged kernel -- four pages a group, so a live row is one group and
    a dead row a grid step that copies nothing -- gives the logits of
    the reference gather path in every row, and both leave the same
    pools behind: the token's K/V written into the live page."""
    rows, pps, page_tokens = 16, 4, 8
    base = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, max_seq=pps * page_tokens),
        dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), base)
    live = {2: (5, 3), 7: (9, 5), 13: (1, 7)}   # row: (page, length)
    table = np.zeros((rows, pps), dtype=np.int32)
    lengths = np.zeros(rows, dtype=np.int32)
    for row, (page, length) in live.items():
        table[row, 0], lengths[row] = page, length
    tokens = jnp.arange(3, 3 + rows, dtype=jnp.int32)
    assert paged_grid_steps(lengths, page_tokens, pps, pps) == (3, 16)
    results = {}
    for name, attention in (("kernel", "flash"), ("reference", "dense")):
        config = dataclasses.replace(base, decode_attention=attention)
        cache = _random_pools(
            init_paged_cache(config, rows, config.max_seq, page_tokens),
            jax.random.PRNGKey(7))
        cache["page_table"] = jnp.asarray(table)
        assert paged_pages_per_step(_split_paged(cache["k"]), pps) == pps
        before = np.asarray(cache["k"])      # (the same on both sides)
        logits, cache = llama.decode_step(params, config, tokens, cache,
                                          jnp.asarray(lengths))
        results[name] = (np.asarray(logits), np.asarray(cache["k"]),
                         np.asarray(cache["v"]))
    for kernel, reference in zip(results["kernel"],
                                 results["reference"]):
        np.testing.assert_allclose(kernel, reference, atol=2e-5,
                                   rtol=2e-5)
    after = results["kernel"][1]
    for row, (page, length) in live.items():
        # (the written page: one new position, the history untouched)
        assert np.array_equal(after[:, page, :length],
                              before[:, page, :length])
        assert not np.array_equal(after[:, page, length],
                                  before[:, page, length])
        assert np.array_equal(after[:, page, length + 1:],
                              before[:, page, length + 1:])


def _served_blocks(config, use_flash_expected):
    """Two blocks of the device loop on a paged cache of four slots,
    with row 1 retiring and row 2 joining between them, as the batcher
    folds them in: (emitted, counts) of each block."""
    from aiko_services_tpu.models.llama import _resolve_decode_flash
    params = llama.init_params(jax.random.PRNGKey(0), config)
    cache = _random_pools(_fully_mapped_paged_cache(config, 4, 16),
                          jax.random.PRNGKey(11))
    assert _resolve_decode_flash(config, cache) is use_flash_expected
    tokens = jnp.asarray([7, 11, 0, 0], dtype=jnp.int32)
    lengths = jnp.asarray([9, 21, 0, 0], dtype=jnp.int32)
    active = jnp.asarray([True, True, False, False])
    budget = jnp.asarray([12, 12, 0, 0], dtype=jnp.int32)
    key = jax.random.PRNGKey(5)
    blocks = []
    for block in range(2):
        (emitted, counts, tokens, lengths, active, budget, _, key, _, _,
         _, cache) = llama.decode_loop(
            params, config, tokens, cache, lengths, active, budget,
            jnp.zeros(4, dtype=jnp.float32),
            jnp.full((4, 1), -1, dtype=jnp.int32),
            jnp.full((4, 1), -1, dtype=jnp.int32), key, ring=4)
        blocks.append((np.asarray(emitted), np.asarray(counts)))
        if block == 0:
            # (row 1 retires, row 2 joins with a 13-token history)
            tokens = tokens.at[2].set(5)
            lengths = lengths.at[2].set(13)
            budget = budget.at[2].set(12)
            active = jnp.asarray([True, False, True, False])
    return blocks


def test_decode_loop_paged_under_threshold_takes_the_kernel(monkeypatch):
    """A paged cache whose extent (64) is under
    ``flash_decode_threshold`` (1024) decodes through the paged kernel
    under ``auto`` on the chip (ISSUE 37: ``ops.on_tpu`` answered "yes"
    here, the kernel interpreted), token for token what the reference
    path emits over two blocks with a row joining and a row retiring
    between them."""
    from aiko_services_tpu import ops
    base = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, max_seq=64),
        dtype="float32")
    assert base.decode_attention == "auto"
    assert base.max_seq < base.flash_decode_threshold
    reference = _served_blocks(
        dataclasses.replace(base, decode_attention="dense"), False)
    assert _served_blocks(base, False)[0][1].tolist() == [4, 4, 0, 0]
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # as on the chip
    kernel = _served_blocks(base, True)
    for (emitted, counts), (ref_emitted, ref_counts) in zip(kernel,
                                                             reference):
        assert np.array_equal(counts, ref_counts)
        assert np.array_equal(emitted, ref_emitted)
    assert kernel[1][1].tolist() == [4, 0, 4, 0]


def test_resolve_paged_cell_cache_answers_kernel(monkeypatch):
    """``camera-paced``'s cache -- ``init_paged_cache(..., max_seq=512,
    page_tokens=128)`` at InternLM2's 8 KV heads of 128 in bfloat16,
    16 slots -- resolves ``paged-kernel`` on the chip and 4 pages a
    grid step (one group a row), so the batcher counts
    ``llm_decode_live_grid_share`` there; off the chip, distributed or
    with ``decode_attention: dense`` it stays ``reference`` / None."""
    from aiko_services_tpu import ops
    config = llama.LlamaConfig(vocab_size=64, dim=2048, n_layers=1,
                               n_heads=16, n_kv_heads=8, hidden_dim=64,
                               max_seq=512)
    cache = init_paged_cache(config, 16, 512, page_tokens=128)
    assert cache["k"].shape == (1, 65, 128, 1024)
    assert llama.resolve_decode_backend(config, cache) == "reference"
    assert llama.paged_decode_pages(config, cache) is None
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # as on the chip
    assert llama.resolve_decode_backend(config, cache) == "paged-kernel"
    assert llama.paged_decode_pages(config, cache) == 4
    dense = dataclasses.replace(config, decode_attention="dense")
    assert llama.resolve_decode_backend(dense, cache) == "reference"
    assert llama.paged_decode_pages(dense, cache) is None
    flat = llama.init_cache(config, 16, 512)
    assert llama.resolve_decode_backend(config, flat) == "reference"
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    placed = jax.device_put(cache, jax.tree_util.tree_map(
        lambda spec: jax.sharding.NamedSharding(mesh, spec),
        llama.cache_specs(config, paged=True)))
    assert llama.resolve_decode_backend(config, placed) == "reference"
    assert llama.paged_decode_pages(config, placed) is None


def test_decode_backend_capability_probe(monkeypatch):
    """The probe replaces the old raise: paged + explicit flash is the
    paged kernel; auto follows platform and the cache's LAYOUT -- a
    paged cache takes the paged kernel at any extent (ISSUE 37: its
    dense path copies a layer's whole pools a step), a dense one takes
    flash from the threshold up -- and distributed and dense force the
    reference path.  Off the chip every ``auto`` probe resolves
    ``reference`` (ISSUE 21) -- only a kernel asked for by name runs
    there."""
    from aiko_services_tpu import ops
    assert decode_backend("flash", paged=True,
                          page_tokens=64) == "paged-kernel"
    assert decode_backend("flash") == "dense-flash"
    assert decode_backend("auto", paged=True, extent=2048,
                          threshold=1024, page_tokens=64) == "reference"
    assert decode_backend("auto", extent=2048,
                          threshold=1024) == "reference"
    assert decode_backend("auto", paged=True, extent=256,
                          threshold=1024, page_tokens=64) == "reference"
    assert matmul_backend("auto") == "reference"
    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # as on the chip
    assert matmul_backend("auto") == "pallas-int8"
    assert decode_backend("auto", paged=True, extent=2048,
                          threshold=1024,
                          page_tokens=64) == "paged-kernel"
    # (the threshold is the dense cache's: a paged cache under it ...)
    assert decode_backend("auto", paged=True, extent=256,
                          threshold=1024,
                          page_tokens=64) == "paged-kernel"
    assert decode_backend("auto", paged=True, extent=512,
                          threshold=1024,
                          page_tokens=128) == "paged-kernel"
    # (... and what did NOT change)
    assert decode_backend("auto", extent=256,
                          threshold=1024) == "reference"
    assert decode_backend("auto", paged=True, extent=256,
                          threshold=1024, distributed=True,
                          page_tokens=64) == "reference"
    assert decode_backend("auto", paged=True, extent=256,
                          threshold=1024, page_tokens=6) == "reference"
    assert decode_backend("dense", paged=True, extent=256,
                          threshold=1024, page_tokens=64) == "reference"
    assert decode_backend("reference", paged=True, extent=256,
                          page_tokens=64) == "reference"
    assert decode_backend("auto", paged=True, extent=2048,
                          threshold=1024, page_tokens=6) == "reference"
    assert decode_backend("flash") == "dense-flash"
    assert decode_backend("auto", extent=2048,
                          threshold=1024) == "dense-flash"
    assert decode_backend("auto", extent=2000,
                          threshold=1024) == "reference"   # % 128
    assert decode_backend("flash", paged=True, distributed=True,
                          page_tokens=64) == "reference"
    assert decode_backend("dense", extent=8192) == "reference"


# -- batched chunk-verify ---------------------------------------------------

def _verify_reference(k_rows, v_rows, q, k_new, v_new, starts,
                      positions):
    """The dense concat-attention _chunk_verify computes, verbatim."""
    b, t = k_rows.shape[:2]
    s = q.shape[1]
    k_all = jnp.concatenate([k_rows, k_new], axis=1)
    v_all = jnp.concatenate([v_rows, v_new], axis=1)
    kv_positions = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(t)[None, :], (b, t)), positions],
        axis=1)
    valid = jnp.concatenate(
        [jnp.arange(t)[None, :] < starts[:, None],
         jnp.ones((b, s), dtype=bool)], axis=1)
    return attention_prefill(q, k_all, v_all, positions,
                             kv_length_mask=valid,
                             kv_positions=kv_positions)


def test_chunk_verify_kernel_matches_dense():
    """flash_verify_append == the dense concat path at f32, across a
    zero-start row, a mid-cache row and a trash-clamped boundary row --
    stacked AND paged cache forms, raw and int8."""
    key = jax.random.PRNGKey(6)
    L, B, K, G, hd, S, T = 2, 3, 2, 2, 16, 5, 128
    C, H = K * hd, K * G
    starts = jnp.asarray([0, 17, T - 1], dtype=jnp.int32)
    positions = jnp.minimum(starts[:, None] + jnp.arange(S)[None, :],
                            T - 1)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd),
                          dtype=jnp.float32)
    k_new = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, hd),
                              dtype=jnp.float32)
    v_new = jax.random.normal(jax.random.fold_in(key, 3), (B, S, K, hd),
                              dtype=jnp.float32)

    # stacked raw
    k_cache = jax.random.normal(jax.random.fold_in(key, 4), (L, B, T, C),
                                dtype=jnp.float32)
    v_cache = jax.random.normal(jax.random.fold_in(key, 5), (L, B, T, C),
                                dtype=jnp.float32)
    layer = 1
    out = flash_verify_append(q, (k_cache, None), (v_cache, None),
                              jnp.int32(layer), k_new, v_new, starts,
                              positions, interpret=True)
    reference = _verify_reference(
        k_cache[layer].reshape(B, T, K, hd),
        v_cache[layer].reshape(B, T, K, hd), q, k_new, v_new, starts,
        positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-5, rtol=1e-5)

    # stacked int8: in-kernel dequant vs dequantize-then-dense
    raw_k = jax.random.normal(jax.random.fold_in(key, 6),
                              (L, B, T, K, hd), dtype=jnp.float32)
    raw_v = jax.random.normal(jax.random.fold_in(key, 7),
                              (L, B, T, K, hd), dtype=jnp.float32)
    qk, qv = quantize_kv(raw_k), quantize_kv(raw_v)
    k_view = (qk["int8"].reshape(L, B, T, C),
              qk["scale"][..., 0].transpose(0, 1, 3, 2)
              .astype(jnp.float32))
    v_view = (qv["int8"].reshape(L, B, T, C),
              qv["scale"][..., 0].transpose(0, 1, 3, 2)
              .astype(jnp.float32))
    out = flash_verify_append(q, k_view, v_view, jnp.int32(layer),
                              k_new, v_new, starts, positions,
                              interpret=True)
    reference = _verify_reference(
        dequantize_kv(qk, jnp.float32)[layer],
        dequantize_kv(qv, jnp.float32)[layer], q, k_new, v_new, starts,
        positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-4, rtol=1e-4)

    # paged: table walked in-kernel
    P, pt, pps = 13, 32, 4
    pool_k = jax.random.normal(jax.random.fold_in(key, 8),
                               (L, P, pt, C), dtype=jnp.float32)
    pool_v = jax.random.normal(jax.random.fold_in(key, 9),
                               (L, P, pt, C), dtype=jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7], [8, 9, 10, 11]],
                        dtype=jnp.int32)
    out = flash_verify_append(q, (pool_k, None), (pool_v, None),
                              jnp.int32(layer), k_new, v_new, starts,
                              positions, page_table=table,
                              interpret=True)
    reference = _verify_reference(
        pool_k[layer][table].reshape(B, pps * pt, K, hd),
        pool_v[layer][table].reshape(B, pps * pt, K, hd), q, k_new,
        v_new, starts, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-5, rtol=1e-5)


def test_chunk_verify_block_mask_matches_dense():
    """``block_mask``: the chunk is ONE block of a block-causal mask --
    its own keys visible to each of its queries in both directions
    (a pass of models/sdar.py) -- over a paged pool, at block-aligned
    rows; without it the chunk's part stays causal, bit for bit."""
    from aiko_services_tpu.ops.pallas_decode import _combine_chunk
    key = jax.random.PRNGKey(16)
    L, B, K, G, hd, S = 2, 3, 2, 2, 16, 4
    C, H = K * hd, K * G
    P, pt, pps = 13, 32, 4
    starts = jnp.asarray([0, 36, pps * pt - S], dtype=jnp.int32)
    positions = starts[:, None] + jnp.arange(S)[None, :]
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    k_new = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, hd))
    v_new = jax.random.normal(jax.random.fold_in(key, 3), (B, S, K, hd))
    pool_k = jax.random.normal(jax.random.fold_in(key, 4), (L, P, pt, C))
    pool_v = jax.random.normal(jax.random.fold_in(key, 5), (L, P, pt, C))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7], [8, 9, 10, 11]],
                        dtype=jnp.int32)
    layer = 1

    def rows(pool):
        return pool[layer][table].reshape(B, pps * pt, K, hd)
    out = flash_verify_append(q, (pool_k, None), (pool_v, None),
                              jnp.int32(layer), k_new, v_new, starts,
                              positions, page_table=table, interpret=True,
                              block_mask=True)
    # dense: the block's keys all stand at the block's first position
    t = pps * pt
    own = jnp.broadcast_to(starts[:, None], (B, S))
    reference = attention_prefill(
        q, jnp.concatenate([rows(pool_k), k_new], axis=1),
        jnp.concatenate([rows(pool_v), v_new], axis=1), positions,
        kv_length_mask=jnp.concatenate(
            [jnp.arange(t)[None, :] < starts[:, None],
             jnp.ones((B, S), dtype=bool)], axis=1),
        kv_positions=jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(t)[None, :], (B, t)), own],
            axis=1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(reference),
                               atol=1e-5, rtol=1e-5)
    causal = flash_verify_append(q, (pool_k, None), (pool_v, None),
                                 jnp.int32(layer), k_new, v_new, starts,
                                 positions, page_table=table,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(causal), np.asarray(_verify_reference(
            rows(pool_k), rows(pool_v), q, k_new, v_new, starts,
            positions)), atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(out - causal).max()) > 1e-2
    # every existing caller's program: the default is the causal chunk
    stats = (jnp.zeros((B, S * H, C)), jnp.full((B, S * H), -1e30),
             jnp.zeros((B, S * H)))

    def program(**mask):
        return str(jax.make_jaxpr(lambda *inputs: _combine_chunk(
            *inputs, positions, hd ** -0.5, K, hd, **mask))(
                *stats, q, k_new, v_new))
    assert program() == program(block_mask=False)
    assert program() != program(block_mask=True)


def test_chunk_verify_wired_into_speculative_loop():
    """_chunk_verify with use_flash routes through the kernel and
    produces the same logits and cache as the dense concat path."""
    from aiko_services_tpu.models.llama import _chunk_verify

    config = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, max_seq=128),
        dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), config)
    chunk = jnp.asarray([[5, 9, 2], [1, 3, 3]], dtype=jnp.int32)
    starts = jnp.asarray([4, 19], dtype=jnp.int32)
    trash = config.max_seq - 1

    outs = {}
    for use_flash in (False, True):
        cache = llama.init_cache(config, 2)
        logits, new_cache = jax.jit(
            lambda c: _chunk_verify(params, config, chunk, c, starts,
                                    trash, use_flash=use_flash))(cache)
        outs[use_flash] = (logits, new_cache)
    np.testing.assert_allclose(np.asarray(outs[True][0]),
                               np.asarray(outs[False][0]),
                               atol=1e-4, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(outs[True][1]),
                    jax.tree_util.tree_leaves(outs[False][1])):
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32),
            np.asarray(b, dtype=np.float32), atol=1e-5, rtol=1e-5)


# -- fused int8 dequant-matmul ----------------------------------------------

@pytest.mark.parametrize("pool", ["raw", "int8"])
@pytest.mark.parametrize("pages_per_step", [1, 2, 4, 8])
def test_paged_verify_chunk_pages_per_step(pages_per_step, pool):
    """The verify chunk's [S*H] query rows (``qrow_period``) through
    the several-pages-a-group paged form == the dense concat path's
    cache part: a zero-start row, a row starting on a page boundary,
    mid-page rows, an inactive row and a row whose first pages another
    row shares -- the kernel's (acc, m, l) against the same statistics
    of the dense scores over the gathered view."""
    key = jax.random.PRNGKey(40 + pages_per_step)
    L, K, G, hd, S = 2, 2, 2, 16, 5
    H = K * G
    pools, dense, table, _, _, dims = _ragged_paged_case(key, "int8")
    if pool == "raw":
        pools = tuple(jnp.where(
            (jnp.arange(side.shape[1]) == side.shape[1] - 1)
            [None, :, None, None], jnp.nan,
            side.reshape(L, -1, dims["pt"], K * hd)) for side in dense)
    B, pt, pps = dims["B"], dims["pt"], dims["pps"]
    # (starts within each row's mapped pages: _ragged_paged_case)
    starts = jnp.asarray([0, 1, 32, 29, 123, 0, 70], dtype=jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd),
                          dtype=jnp.float32)
    q_pad, _, _, scale = _prep_query(q.reshape(B, S * H, hd), S * H, K,
                                     hd, period=H)
    layer = 1
    (k_payload, k_scale), (v_payload, v_scale) = (
        _split_paged(pools[0]), _split_paged(pools[1]))
    acc, m, l = flash_decode_attention_paged(
        q_pad, k_payload, v_payload, k_scale, v_scale, jnp.int32(layer),
        table, starts, interpret=True, qrow_period=H,
        pages_per_step=pages_per_step)
    rows_k = dense[0][layer][table].reshape(B, pps * pt, K, hd)
    rows_v = dense[1][layer][table].reshape(B, pps * pt, K, hd)
    heads = jnp.arange(H) // G
    logits = jnp.einsum("bshd,bthd->bsht", q, rows_k[:, :, heads]) * scale
    valid = (jnp.arange(pps * pt)[None, :] < starts[:, None])[:, None,
                                                                None, :]
    logits = jnp.where(valid, logits, -1e30)
    m_ref = logits.max(-1)
    p = jnp.where(valid, jnp.exp(logits - m_ref[..., None]), 0.0)
    out_ref = jnp.einsum("bsht,bthd->bshd", p, rows_v[:, :, heads])
    acc = acc.reshape(B, S, H, K, hd)[:, :, jnp.arange(H), heads]
    seen = np.asarray(starts) > 0
    np.testing.assert_allclose(np.asarray(m.reshape(B, S, H))[seen],
                               np.asarray(m_ref)[seen], atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(l.reshape(B, S, H)),
                               np.asarray(p.sum(-1)), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(out_ref),
                               atol=1e-4, rtol=1e-4)


def test_int8_matmul_matches_xla():
    """Exact on exactly-representable inputs; f32 accumulation-order
    tolerance on gaussian bf16 -- vs the XLA reference
    ``(x @ w.astype) * scale`` (llama.matmul's non-kernel path)."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.integers(-7, 8, (96, 260)), jnp.float32)
    leaf = quantize_weight(w)
    x = jnp.asarray(rng.integers(-3, 4, (5, 96)), jnp.float32)
    reference = (x @ leaf["int8"].astype(x.dtype)) \
        * leaf["scale"].astype(x.dtype)
    out = int8_matmul(x, leaf["int8"], leaf["scale"], block_f=128,
                      block_d=32, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(reference))

    xb = jax.random.normal(jax.random.PRNGKey(0), (4, 96), jnp.bfloat16)
    reference = (xb @ leaf["int8"].astype(xb.dtype)) \
        * leaf["scale"].astype(xb.dtype)
    out = int8_matmul(xb, leaf["int8"], leaf["scale"], interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32),
        np.asarray(reference, dtype=np.float32), atol=1e-1, rtol=2e-2)


def _int8_case(m, d, f, seed=3):
    """Exactly-representable inputs: the kernel must EQUAL the XLA
    reference ``(x @ w.astype) * scale`` whatever its blocks."""
    rng = np.random.default_rng(seed)
    leaf = quantize_weight(
        jnp.asarray(rng.integers(-7, 8, (d, f)), jnp.float32))
    x = jnp.asarray(rng.integers(-3, 4, (m, d)), jnp.float32)
    reference = (x @ leaf["int8"].astype(x.dtype)) \
        * leaf["scale"].astype(x.dtype)
    return x, leaf, reference


def _pads_of_int8(jaxpr) -> list:
    """Every ``pad`` equation over an int8 operand, nested jaxprs
    included (the jitted call is a ``pjit`` equation)."""
    return [eqn for eqn in all_eqns(jaxpr)
            if eqn.primitive.name == "pad"
            and eqn.invars[0].aval.dtype == jnp.int8]


@pytest.mark.parametrize("m", [8, 32, 520])
@pytest.mark.parametrize("d,f", [(256, 1152), (256, 640), (128, 2176)])
def test_int8_matmul_reads_the_weight_where_it_lies(d, f, m):
    """Widths of 128 x an odd number (92,544 = 128 x 723 in small): the
    blocks follow the weight, so no ``pad`` of the int8 operand is
    traced -- on the chip that pad was a copy of the whole head in
    every decode step (ISSUE 34) -- and the answer is the reference's,
    ragged last column block included."""
    x, leaf, reference = _int8_case(m, d, f)
    assert matmul_blocks(m, d, f, x.dtype)[3] == 0
    jaxpr = jax.make_jaxpr(
        lambda x, w, s: int8_matmul(x, w, s, interpret=True))(
        x, leaf["int8"], leaf["scale"])
    assert "pallas_call" in str(jaxpr) and not _pads_of_int8(jaxpr.jaxpr)
    out = int8_matmul(x, leaf["int8"], leaf["scale"], interpret=True)
    assert out.shape == (m, f)
    assert np.array_equal(np.asarray(out), np.asarray(reference))


@pytest.mark.parametrize("m", [8, 300])
@pytest.mark.parametrize("d,f", [(96, 1000), (200, 384)])
def test_int8_matmul_pads_what_no_block_fits(d, f, m):
    """A width or a depth that is no multiple of 128 (tiny test
    vocabularies, GPT-2's 50,257) keeps the pad path, says so in
    ``padded_weight_bytes``, and still answers right."""
    x, leaf, reference = _int8_case(m, d, f)
    block_m, block_d, block_f, padded = matmul_blocks(
        m, d, f, x.dtype, block_d=128)
    assert padded >= d * f and padded % block_d == 0
    jaxpr = jax.make_jaxpr(
        lambda x, w, s: int8_matmul(x, w, s, block_d=128,
                                    interpret=True))(
        x, leaf["int8"], leaf["scale"])
    assert _pads_of_int8(jaxpr.jaxpr)
    out = int8_matmul(x, leaf["int8"], leaf["scale"], block_d=128,
                      interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(reference))


@pytest.mark.parametrize("m", [16, 32, 512, 4096])
@pytest.mark.parametrize("d,f", [(2048, 92544), (4096, 128256),
                                 (2048, 128256), (4096, 32000)])
def test_matmul_blocks_at_the_real_heads(d, f, m):
    """Arithmetic only, no array: InternLM2-1.8B's, llama3-8b's,
    llama3-1b's and Llama-2's heads are blocked as they lie (nothing
    padded), by tiles the chip's VMEM holds, and the grid covers every
    column and every row of the contraction."""
    for dtype in (jnp.bfloat16, jnp.float32):
        block_m, block_d, block_f, padded = matmul_blocks(m, d, f, dtype)
        assert padded == 0
        assert d % block_d == 0 and block_d % 128 == 0
        assert block_f % 128 == 0 and -(-f // block_f) * block_f >= f
        assert block_m % 8 == 0 and block_m <= max(m, 8)
        assert tile_bytes(block_m, block_d, block_f,
                          jnp.dtype(dtype).itemsize) <= VMEM_BUDGET_BYTES
    # a block asked for by name is kept
    assert matmul_blocks(m, d, f, jnp.bfloat16, block_f=128,
                         block_d=512)[1:3] == (512, 128)


def test_int8_matmul_serves_the_unembed():
    """decode_step logits with matmul_kernel='pallas' (the fused
    kernel on the quantized unembed, interpret mode here) match
    matmul_kernel='off' (XLA) on the same int8 tree."""
    base = llama.LlamaConfig.tiny(vocab_size=64, max_seq=64)
    params = quantize_params(
        llama.init_params(jax.random.PRNGKey(0), base))
    tokens = jnp.asarray([3, 5], dtype=jnp.int32)
    lengths = jnp.zeros(2, dtype=jnp.int32)
    logits = {}
    for mode in ("off", "pallas"):
        config = dataclasses.replace(base, matmul_kernel=mode)
        cache = llama.init_cache(config, 2)
        out, _ = llama.decode_step(params, config, tokens, cache,
                                   lengths)
        logits[mode] = np.asarray(out, dtype=np.float32)
    np.testing.assert_allclose(logits["pallas"], logits["off"],
                               atol=5e-2, rtol=5e-2)
    assert matmul_backend("off") == "reference"
    assert matmul_backend("pallas") == "pallas-int8"


def _build_stamps(runtime, event: str, **parameters) -> list:
    """Serve one request through an LLM element of ``model: tiny`` with
    ``parameters`` and return the info of every ``build:<event>`` the
    flight recorder holds afterwards."""
    import queue

    from aiko_services_tpu.pipeline import Pipeline

    responses = queue.Queue()
    pipeline = Pipeline({
        "version": 0, "name": f"{event}_stamp", "runtime": "jax",
        "parameters": {}, "graph": ["(llm)"],
        "elements": [{
            "name": "llm", "input": [{"name": "text"}],
            "output": [{"name": "text"}],
            "parameters": {"model": "tiny", "max_seq": 64,
                           "max_new_tokens": 2, "max_slots": 4,
                           **parameters},
            "deploy": {"local": {
                "module": "aiko_services_tpu.elements.llm",
                "class_name": "LLM"}}}]}, runtime=runtime)
    stream = pipeline.create_stream_local("1", queue_response=responses)
    pipeline.create_frame_local(stream, {"text": "hi"})
    assert run_until(runtime, lambda: responses.qsize() >= 1,
                     timeout=180.0)
    stamps = [entry[6] for entry in pipeline.recorder.snapshot()
              if entry[1] == "build" and entry[4] == event]
    pipeline.stop()
    return stamps


@pytest.mark.parametrize("vocab,padded", [(384, 0), (300, 64 * 384)])
def test_llm_element_stamps_the_unembed_blocks(runtime, vocab, padded):
    """A served int8 model says in the flight recorder how the fused
    unembed blocks its head at the decode width and what a call copies
    to get there (``build:llm_unembed``, beside ``build:llm_cache``):
    0 for a head of whole 128-lane tiles, the padded weight for one
    that is not."""
    assert _build_stamps(runtime, "llm_unembed", quantize="int8",
                         vocab_size=vocab) \
        == [{"block_m": 8, "block_d": 64, "block_f": 384,
             "padded_weight_bytes": padded}]


@pytest.mark.parametrize("page_tokens,on_chip,stamp", [
    (0, False, {"backend": "reference", "extent": 64,
                "page_tokens": None, "pages_per_step": None}),
    (16, False, {"backend": "reference", "extent": 64,
                 "page_tokens": 16, "pages_per_step": None}),
    (16, True, {"backend": "paged-kernel", "extent": 64,
                "page_tokens": 16, "pages_per_step": 4}),
])
def test_llm_element_stamps_the_decode_backend(runtime, monkeypatch,
                                               page_tokens, on_chip,
                                               stamp):
    """A served Llama-family model says once a build which decode
    attention the probe resolved for its cache and what the probe saw
    (``build:llm_decode_backend``, beside ``build:llm_unembed``): a
    paged cache of extent 64, far under the flash threshold, is the
    paged kernel's on the chip (``ops.on_tpu`` answered "yes" here:
    the request is then served through the interpreted kernel)."""
    from aiko_services_tpu import ops

    if on_chip:
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert _build_stamps(runtime, "llm_decode_backend",
                         kv_page_tokens=page_tokens) == [stamp]


# -- on-TPU top-k -----------------------------------------------------------

def test_topk_matches_lax():
    """Values AND indices equal lax.top_k across shapes, block sizes
    and dtypes -- including the ragged tail and a bf16 operand."""
    rng = np.random.default_rng(1)
    for (b, v, k, block_v) in ((5, 700, 8, 256), (1, 64, 3, 2048),
                               (17, 5000, 16, 1024), (8, 128, 128, 128)):
        x = jnp.asarray(rng.normal(size=(b, v)), jnp.float32)
        values, indices = pallas_topk(x, k, block_v=block_v,
                                      interpret=True)
        lax_values, lax_indices = jax.lax.top_k(x, k)
        assert np.array_equal(np.asarray(values), np.asarray(lax_values))
        assert np.array_equal(np.asarray(indices),
                              np.asarray(lax_indices))
    xb = jnp.asarray(rng.normal(size=(9, 333)), jnp.bfloat16)
    values, indices = pallas_topk(xb, 5, block_v=128, interpret=True)
    lax_values, lax_indices = jax.lax.top_k(xb, 5)
    assert values.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(values, dtype=np.float32),
                          np.asarray(lax_values, dtype=np.float32))
    assert np.array_equal(np.asarray(indices), np.asarray(lax_indices))


def test_int8_matmul_blocks_over_m():
    """Prefill-shaped M (B*S rows) exercises the M-blocking that keeps
    the kernel's VMEM tiles bounded on TPU -- with block_m smaller than
    M, partial tiles and the ragged M tail must still match XLA."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.integers(-7, 8, (64, 384)), jnp.float32)
    leaf = quantize_weight(w)
    x = jnp.asarray(rng.integers(-3, 4, (300, 64)), jnp.float32)
    reference = (x @ leaf["int8"].astype(x.dtype)) \
        * leaf["scale"].astype(x.dtype)
    out = int8_matmul(x, leaf["int8"], leaf["scale"], block_m=128,
                      block_f=128, block_d=32, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(reference))


def test_topk_masked_rows_match_lax():
    """Rows with fewer than k finite values (padded logits, masked ANN
    scores): the consumed-column mask keeps extracted (-inf, index)
    candidates DISTINCT, so indices stay unique and match lax.top_k's
    ascending order over the -inf tail (value-only masking re-extracted
    the same entry and emitted duplicates)."""
    x = jnp.full((3, 256), -jnp.inf)
    x = x.at[0, 3].set(1.0).at[0, 7].set(2.0)       # 2 finite < k=4
    x = x.at[1, 200].set(5.0)                       # 1 finite, tail block
    values, indices = pallas_topk(x, 4, block_v=128, interpret=True)
    lax_values, lax_indices = jax.lax.top_k(x, 4)
    assert np.array_equal(np.asarray(indices), np.asarray(lax_indices))
    assert np.array_equal(np.asarray(values), np.asarray(lax_values))
    for row in np.asarray(indices):
        assert len(set(row.tolist())) == 4          # no duplicates


def test_topk_tie_breaking_is_stable():
    """Equal values resolve to the LOWEST index first -- lax.top_k's
    stable contract, pinned explicitly (ties across block boundaries
    are exactly what the running-state merge could get wrong)."""
    x = jnp.zeros((3, 600)).at[:, 5].set(2.0).at[:, 300].set(2.0) \
        .at[:, 10].set(1.0).at[:, 599].set(1.0)
    values, indices = pallas_topk(x, 4, block_v=128, interpret=True)
    lax_values, lax_indices = jax.lax.top_k(x, 4)
    assert np.array_equal(np.asarray(indices), np.asarray(lax_indices))
    assert np.array_equal(np.asarray(values), np.asarray(lax_values))
    assert list(np.asarray(indices[0])) == [5, 300, 10, 599]


def test_paged_kernel_rejects_misaligned_page_size():
    """A forced paged-kernel request with a sublane-misaligned page
    size fails by name on every backend instead of surfacing an opaque
    Mosaic tiling error on TPU (the 'auto' probe never routes such a
    config here)."""
    L, P, pt, B, C = 1, 3, 12, 2, 32
    pool = jnp.zeros((L, P, pt, C), dtype=jnp.float32)
    table = jnp.zeros((B, 2), dtype=jnp.int32)
    q_pad = jnp.zeros((B, 4, C), dtype=jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        flash_decode_attention_paged(q_pad, pool, pool, None, None,
                                     jnp.int32(0), table,
                                     jnp.zeros(B, dtype=jnp.int32),
                                     interpret=True)


def test_sample_top_k_bounded_at_build_and_create():
    """sample_top_k above the kernel's 128-lane cap fails at batcher
    build AND at create-time domain validation -- not mid-serving on
    TPU (the CPU path would happily serve it via lax.top_k)."""
    from aiko_services_tpu.analysis.params import \
        validate_element_parameters
    from aiko_services_tpu.models.batching import ContinuousBatcher

    config = llama.LlamaConfig.tiny(vocab_size=64, max_seq=64)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    with pytest.raises(ValueError, match="128"):
        ContinuousBatcher(params, config, max_slots=2,
                          sample_top_k=200)
    findings = validate_element_parameters(
        "LLM", {"sample_top_k": 200}, "p: llm",
        module="aiko_services_tpu.elements.llm")
    assert [f.rule for f in findings] == ["bad-parameter"]
    assert "<= 128" in findings[0].message


def test_topk_rejects_bad_k():
    x = jnp.zeros((2, 64))
    with pytest.raises(ValueError, match="k="):
        pallas_topk(x, 0, interpret=True)
    with pytest.raises(ValueError, match="k="):
        pallas_topk(x, 129, interpret=True)


def test_select_tokens_top_k_restricts_sampling():
    """top_k=1 at temperature > 0 equals greedy (the candidate set is
    the argmax); top_k=0 keeps the full categorical; greedy rows are
    unaffected by top_k.  The dispatching ops.topk interface resolves
    to lax off-TPU, so this exercises the serving wiring."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.fold_in(key, 1), (4, 64))
    temps = jnp.asarray([0.0, 0.7, 1.0, 0.3])
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    top1 = np.asarray(llama.select_tokens(key, logits, temps, top_k=1))
    assert np.array_equal(top1, greedy)
    # top_k restricts every sampled row's token to the k candidates
    k = 4
    _, candidates = topk(jnp.asarray(logits, jnp.float32), k,
                         kernel=False)
    for draw in range(5):
        out = np.asarray(llama.select_tokens(
            jax.random.fold_in(key, draw), logits, temps, top_k=k))
        for row in range(4):
            assert out[row] in np.asarray(candidates[row])


def test_batcher_sample_top_k_round_trip():
    """ContinuousBatcher(sample_top_k=1) at temperature>0 emits the
    greedy stream (top-1 == argmax), through the real serving loop."""
    from aiko_services_tpu.models.batching import (ContinuousBatcher,
                                                   Request)

    config = llama.LlamaConfig.tiny(vocab_size=64, max_seq=64)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    streams = {}
    for label, kwargs in (
            ("greedy", dict()),
            ("top1", dict(sample_top_k=1))):
        batcher = ContinuousBatcher(params, config, max_slots=2,
                                    decode_block_tokens=8, **kwargs)
        collected = []
        temperature = 0.0 if label == "greedy" else 0.9
        batcher.submit(Request(
            "r", [5, 9, 2, 7], max_new_tokens=10,
            temperature=temperature,
            emit=lambda rid, tok, fin: collected.append(tok)))
        batcher.run_until_drained(max_steps=200)
        streams[label] = collected
    assert streams["top1"] == streams["greedy"]


@pytest.mark.parametrize("attention", ["flash", "dense"])
def test_batcher_counts_live_grid_share(attention):
    """Where the paged kernel serves decode, every retired device-loop
    block carries the kernel's (live, all) steps at its first step and
    the pages a step -- counted from the host's own lengths -- to
    ``take_block_stats`` and the ``demux`` phase's info; under another
    backend nothing is counted."""
    from aiko_services_tpu.models.batching import (ContinuousBatcher,
                                                   Request)

    config = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=64, max_seq=64),
        decode_attention=attention)
    params = llama.init_params(jax.random.PRNGKey(0), config)
    phases = []
    batcher = ContinuousBatcher(
        params, config, max_slots=3, decode_block_tokens=4,
        kv_page_tokens=8, prefill_chunk=16,
        trace=lambda name, ms, info: phases.append((name, info)))
    for index, prompt in enumerate(([5, 9, 2, 7], list(range(1, 20)))):
        batcher.submit(Request(f"r{index}", prompt, max_new_tokens=9,
                               emit=lambda rid, tok, fin: None))
    batcher.run_until_drained(max_steps=200)
    stats = batcher.take_block_stats()
    if attention == "dense":
        assert stats == [] and batcher._paged_pages is None
        return
    pages = batcher._paged_pages
    assert pages == 8 and len(stats) == batcher.blocks_retired > 0
    for observed in stats:
        assert observed["paged_pages_per_step"] == pages
        live = observed["paged_grid_steps_live"]
        assert 1 <= live <= 2       # one step a decoding row
        assert observed["paged_grid_steps"] == 3     # and an idle one
    assert [info for name, info in phases
            if name == "demux" and info] == stats
