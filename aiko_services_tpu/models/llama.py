"""Llama-3-family transformer, TPU-first (BASELINE config 3: the chat
element's model; reference equivalent: examples/llm/elements.py delegates
to an external Ollama server -- here the model IS the framework's, weights
resident in HBM).

Functional design: parameters are a pytree with layers stacked on a
leading axis and the layer loop is a ``lax.scan`` -- one trace, one
compile, regardless of depth.  ``partition_specs`` gives the
Megatron-style TP (+fsdp) layout; activations carry explicit sharding
constraints so XLA places collectives on the mesh axes
(dp=batch, sp=sequence, tp=heads/hidden).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import decode_backend, matmul_backend
from ..ops.layers import (rms_norm, rope_frequencies, apply_rope,
                          attention_prefill, attention_decode_append)
from ..parallel.mesh import P
from .families import config_fields
from .paged import (gather_layer, gather_rows, is_paged, paged_extent,
                    pool_page_tokens, scatter_pages)
from .quant import dequantize_kv, is_quantized, quantize_kv

__all__ = ["LlamaConfig", "init_params", "partition_specs",
           "cache_specs", "init_cache", "cache_array", "cache_extent",
           "prefill", "prefill_with_aux", "prefill_into_slot",
           "prefill_into_slots", "pack_admission", "admit_packed",
           "decode_step", "decode_loop", "greedy_sample", "select_tokens",
           "resolve_decode_backend", "paged_decode_pages"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14_336
    rope_theta: float = 500_000.0
    max_seq: int = 8192
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Prefill attention implementation: "dense" (einsum, materializes
    # the [S, T] logits) or "flash" (the Pallas kernel,
    # ops/pallas_attention.py -- O(block) memory, the long-context
    # serving path).  Applies to prefill_into_slot, the continuous
    # batcher's admission path; decode is O(1)-query and stays dense.
    attention: str = "dense"
    # Decode attention implementation: "dense" (ops/layers.py
    # attention_decode_append), "flash" (the split-K Pallas kernel,
    # ops/pallas_decode.py -- streams the cache once, softmax stats in
    # VMEM, int8 cache dequantized in-kernel), or "auto" (ON THE TPU
    # BACKEND: a PAGED cache takes the paged kernel whatever its
    # extent; a flat / stacked cache takes flash once its extent
    # reaches ``flash_decode_threshold``; dense everywhere else --
    # resolved at trace time, the cache's layout and length are static
    # under jit: ops.decode_backend).  ``flash_decode_threshold`` is
    # the FLAT cache's: measured on v5e with the flat cache, flash wins
    # from 1k up (0.88 vs 0.86 HBM util at 1k; 0.84 vs ~0.45 at 8k,
    # where dense's [B, H, T] HBM intermediates outweigh the cache);
    # sub-1k flat shapes keep dense (single fused dispatch, the row's
    # extent read in place).  A paged cache has no in-place dense
    # path -- inside the layer scan the gather takes a layer's whole K
    # and V pool out of the stack every step (PERF.md section 6, PR
    # 37) -- so the threshold does not apply to it.
    # NOTE: pallas_call has no GSPMD
    # partitioning rules, so under a tp-sharded cache keep "dense" (or
    # shard_map the layer); single-chip and dp-sharded serving
    # compose fine.
    decode_attention: str = "auto"
    flash_decode_threshold: int = 1024
    # Weight-only-int8 matmul implementation for UNSTACKED quantized
    # leaves (today: the unembed projection, serving's largest matmul):
    # "auto" (the fused Pallas dequant-matmul on TPU, XLA's
    # cast-into-the-dot elsewhere), "pallas" (force the kernel --
    # interpret mode off-TPU, the equivalence-test setting), "off"
    # (always XLA).  Resolved via ops.matmul_backend at trace time.
    matmul_kernel: str = "auto"
    # KV cache storage: "bfloat16" or "int8" (per-token-per-head scales,
    # models/quant.py:quantize_kv).  Decode streams the whole cache every
    # step, so at long context the cache -- not the weights -- dominates
    # the HBM bytes; int8 halves them.  Composes with weight-only int8
    # and with the TP/dp cache sharding (cache_specs).
    kv_dtype: str = "bfloat16"
    # Mixture-of-experts FFN (SURVEY §2.5: EP is a first-class axis of
    # the TPU build; the reference has no parallelism at all).  0 =
    # dense FFN; > 0 replaces every block's FFN with n_experts
    # independent SwiGLU experts, top-k routed per token, expert
    # weights sharded over the mesh's ``ep`` axis (partition_specs).
    n_experts: int = 0
    n_experts_per_token: int = 2
    # Static per-expert token buffer = capacity_factor x the perfectly
    # balanced share; overflow tokens fall back to their residual
    # stream (standard GShard semantics, keeps every shape static).
    capacity_factor: float = 2.0
    # Rematerialize each layer's activations in the backward pass
    # (jax.checkpoint around the scanned block): activation memory
    # drops from O(layers) to O(1) layers at ~1/3 extra forward FLOPs
    # -- the standard trade for long-sequence training.
    remat: bool = False

    def __post_init__(self):
        if self.attention not in ("dense", "flash"):
            raise ValueError(
                f"attention must be 'dense' or 'flash', "
                f"got {self.attention!r}")
        if self.decode_attention not in ("dense", "flash", "auto"):
            raise ValueError(
                f"decode_attention must be 'dense', 'flash' or 'auto', "
                f"got {self.decode_attention!r}")
        if self.kv_dtype not in ("bfloat16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bfloat16' or 'int8', "
                f"got {self.kv_dtype!r}")
        if self.matmul_kernel not in ("auto", "pallas", "off"):
            raise ValueError(
                f"matmul_kernel must be 'auto', 'pallas' or 'off', "
                f"got {self.matmul_kernel!r}")
        if self.n_experts and self.n_experts_per_token > self.n_experts:
            raise ValueError(
                f"n_experts_per_token ({self.n_experts_per_token}) "
                f"exceeds n_experts ({self.n_experts})")

    def moe_capacity(self, n_tokens: int) -> int:
        """Static per-expert buffer size for ``n_tokens`` routed
        tokens, rounded up to the 8-sublane TPU tile."""
        import math
        exact = math.ceil(self.capacity_factor * n_tokens
                          * self.n_experts_per_token / self.n_experts)
        return max(1, min(-(-exact // 8) * 8, n_tokens))

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def gqa_groups(self) -> int:
        return self.n_heads // self.n_kv_heads

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "LlamaConfig":
        """The config of published ``config.json`` keys
        (``families.FAMILY_WIDTHS["llama"]``; a key the family lacks is
        an error, a key left out keeps the dataclass default)."""
        return cls(**{**fields, **config_fields("llama", widths)})

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama3_1b(cls) -> "LlamaConfig":
        return cls(dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                   hidden_dim=8192)

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "LlamaConfig":
        """Test-size config: runs on CPU mesh in milliseconds."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, hidden_dim=128, max_seq=max_seq,
                   rope_theta=10_000.0)

    @classmethod
    def tiny_moe(cls, vocab_size: int = 512, max_seq: int = 256,
                 n_experts: int = 4) -> "LlamaConfig":
        """Test-size MoE config (4 experts, top-2 routing)."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
                   n_kv_heads=2, hidden_dim=128, max_seq=max_seq,
                   rope_theta=10_000.0, n_experts=n_experts)


def _dtype(config: LlamaConfig):
    return jnp.dtype(config.dtype)


def init_params(key: jax.Array, config: LlamaConfig) -> dict:
    c = config
    dtype = _dtype(c)
    keys = jax.random.split(key, 8)
    hd = c.head_dim

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    if c.n_experts:
        ffn = {
            "w_router": dense(jax.random.fold_in(keys[5], 1),
                              (c.n_layers, c.dim, c.n_experts), c.dim),
            "w_gate": dense(keys[5], (c.n_layers, c.n_experts, c.dim,
                                      c.hidden_dim), c.dim),
            "w_up": dense(keys[6], (c.n_layers, c.n_experts, c.dim,
                                    c.hidden_dim), c.dim),
            "w_down": dense(keys[7], (c.n_layers, c.n_experts,
                                      c.hidden_dim, c.dim),
                            c.hidden_dim),
        }
    else:
        ffn = {
            "w_gate": dense(keys[5], (c.n_layers, c.dim, c.hidden_dim),
                            c.dim),
            "w_up": dense(keys[6], (c.n_layers, c.dim, c.hidden_dim),
                          c.dim),
            "w_down": dense(keys[7], (c.n_layers, c.hidden_dim, c.dim),
                            c.hidden_dim),
        }
    return {
        "embed": dense(keys[0], (c.vocab_size, c.dim), c.dim),
        "layers": {
            "wq": dense(keys[1], (c.n_layers, c.dim, c.n_heads * hd),
                        c.dim),
            "wk": dense(keys[2], (c.n_layers, c.dim, c.n_kv_heads * hd),
                        c.dim),
            "wv": dense(keys[3], (c.n_layers, c.dim, c.n_kv_heads * hd),
                        c.dim),
            "wo": dense(keys[4], (c.n_layers, c.n_heads * hd, c.dim),
                        c.n_heads * hd),
            **ffn,
            "attn_norm": jnp.ones((c.n_layers, c.dim), dtype=dtype),
            "mlp_norm": jnp.ones((c.n_layers, c.dim), dtype=dtype),
        },
        "final_norm": jnp.ones((c.dim,), dtype=dtype),
        "unembed": dense(jax.random.fold_in(keys[0], 1),
                         (c.dim, c.vocab_size), c.dim),
    }


def partition_specs(config: LlamaConfig) -> dict:
    """Megatron TP + fsdp layout, layer axis unsharded (it is scanned).
    MoE expert weights add the ``ep`` axis on their expert dimension
    (each ep shard owns n_experts/ep experts; tokens reach them through
    the dispatch einsum, whose collective XLA derives from these
    shardings); the router is small and replicated over ep."""
    if config.n_experts:
        ffn = {
            "w_router": P(None, "fsdp", None),
            "w_gate": P(None, "ep", "fsdp", "tp"),
            "w_up": P(None, "ep", "fsdp", "tp"),
            "w_down": P(None, "ep", "tp", "fsdp"),
        }
    else:
        ffn = {
            "w_gate": P(None, "fsdp", "tp"),
            "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),
        }
    return {
        "embed": P("fsdp", None),
        "layers": {
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
            **ffn,
            "attn_norm": P(None, None),
            "mlp_norm": P(None, None),
        },
        "final_norm": P(None),
        "unembed": P("fsdp", "tp"),
    }


def cache_specs(config: LlamaConfig | None = None,
                paged: bool = False) -> dict:
    """KV cache: batch over dp, kv heads over tp.  The FLAT payload
    ([L, B, T, K*hd] -- see init_cache) shards its fused head axis over
    tp (tp divides K, so contiguous C blocks map to whole kv heads);
    an int8 cache's scale ([L, B, T, K, 1]) shards its kv-head axis on
    the same chips.  A ``paged`` cache (models/paged.py) keeps the tp
    split of its pools ([L, P, pt, K*hd]) but never shards the page
    axis -- every slot's table may point at any page -- and replicates
    the small page table."""
    batch = None if paged else "dp"
    spec = P(None, batch, None, "tp")
    if config is not None and config.kv_dtype == "int8":
        leaf = {"int8": spec, "scale": P(None, batch, None, "tp", None)}
        specs = {"k": leaf, "v": leaf}
    else:
        specs = {"k": spec, "v": spec}
    if paged:
        specs["page_table"] = P()
    return specs


def init_cache(config: LlamaConfig, batch: int,
               max_seq: int | None = None) -> dict:
    """Payloads are stored FLAT: [L, B, T, K*hd], the contiguous view
    every consumer wants -- the dense einsums flatten to it anyway
    (attention_decode_append's docstring) and the flash-decode Pallas
    kernel REQUIRES the default layout on it: a grouped 5-D buffer
    lets XLA pick a T-minor layout for the scatter writes and then
    pay two full-cache layout-conversion copies per decode step in
    front of the kernel (seen in compiled HLO on v5e).  Attention
    consumers regroup to [.., T, K, hd] with :func:`_grouped` -- a
    reshape of contiguous minor dims that fuses into the consuming
    einsum.  int8 scales keep the grouped [L, B, T, K, 1] shape."""
    c = config
    t = max_seq or c.max_seq
    shape = (c.n_layers, batch, t, c.n_kv_heads * c.head_dim)
    if c.kv_dtype == "int8":
        def layer():
            return {"int8": jnp.zeros(shape, dtype=jnp.int8),
                    "scale": jnp.zeros(
                        shape[:-1] + (c.n_kv_heads, 1),
                        dtype=jnp.float32)}
        return {"k": layer(), "v": layer()}
    return {"k": jnp.zeros(shape, dtype=_dtype(c)),
            "v": jnp.zeros(shape, dtype=_dtype(c))}


def _kv_store(layer, new, write):
    """Write raw k/v values ``new`` ([.., S, K, hd], grouped) into a
    cache layer via ``write(old_array, new_array) -> updated`` --
    payloads are written FLAT ([.., S, K*hd], matching the cache
    storage); int8 layers quantize first, the scale keeping its
    grouped shape.  ``write`` closures must therefore be rank-generic
    (payload and scale differ by one trailing dim)."""
    flat = new.reshape(*new.shape[:-2], -1)
    if is_quantized(layer):
        q = quantize_kv(new)
        return {"int8": write(layer["int8"],
                              q["int8"].reshape(flat.shape)),
                "scale": write(layer["scale"], q["scale"])}
    return write(layer, flat)


def _kv_rows(layer, slice_fn):
    """Apply a row-slicing fn to each stored array of a cache layer."""
    if is_quantized(layer):
        return {"int8": slice_fn(layer["int8"]),
                "scale": slice_fn(layer["scale"])}
    return slice_fn(layer)


def _grouped(layer, kv: int):
    """Flat cache layer [.., T, K*hd] -> grouped [.., T, K, hd] view
    for the attention einsums (contiguous-minor reshape: fuses into the
    consuming dot, no copy; int8 scales are already grouped)."""
    def regroup(arr):
        return arr.reshape(*arr.shape[:-1], kv, arr.shape[-1] // kv)
    if is_quantized(layer):
        return {"int8": regroup(layer["int8"]), "scale": layer["scale"]}
    return regroup(layer)


def cache_array(cache: dict):
    """The cache's key payload array (shape/sharding introspection that
    works for bf16, int8 and paged caches alike -- for a paged cache
    this is the PHYSICAL pool, so use :func:`cache_extent` for the
    logical per-slot extent)."""
    k = cache["k"]
    return k["int8"] if is_quantized(k) else k


def cache_extent(cache: dict) -> int:
    """Logical per-slot token extent T of a serving cache: the T axis
    of a dense cache, ``pages_per_slot * page_tokens`` of a paged one.
    Position T-1 is the trash position either way (the paged trash
    page sits behind the table's default entry 0)."""
    if is_paged(cache):
        return paged_extent(cache)
    return cache_array(cache).shape[2]


def matmul(x, w, kernel: bool = False):
    """``x @ w`` for raw arrays or weight-only-int8 leaves
    (``{"int8", "scale"}``, models/quant.py).  The int8->bf16 convert
    fuses into the dot's operand load on TPU, so int8 weights stream
    half the HBM bytes; the per-output-channel scale applies after the
    dot -- no dequantized weight tensor is ever materialized.

    ``kernel=True`` routes an UNSTACKED quantized leaf through the
    fused Pallas dequant-matmul (ops/pallas_matmul.py): cast, dot and
    scale in one kernel, no unscaled [M, F] intermediate.  Callers gate
    it on :func:`aiko_services_tpu.ops.matmul_backend` (the in-scan
    layer leaves stay on the XLA path -- a sliced operand in front of a
    pallas call would materialize; the scan-invariant unembed is the
    high-leverage site, see :func:`_finish`)."""
    if is_quantized(w):
        if kernel and w["int8"].ndim == 2:
            from ..ops.pallas_matmul import int8_matmul
            lead = x.shape[:-1]
            out = int8_matmul(x.reshape(-1, x.shape[-1]), w["int8"],
                              w["scale"])
            return out.reshape(*lead, out.shape[-1])
        return (x @ w["int8"].astype(x.dtype)) \
            * w["scale"].astype(x.dtype)
    return x @ w


def _expert_matmul(t, w, pattern):
    """Batched-over-experts einsum for raw or weight-only-int8 expert
    leaves; the [E, 1, F] per-channel scale applies after the dot
    (broadcasting over the capacity axis)."""
    if is_quantized(w):
        return jnp.einsum(pattern, t, w["int8"].astype(t.dtype)) \
            * w["scale"].astype(t.dtype)
    return jnp.einsum(pattern, t, w)


def _moe_ffn(config: LlamaConfig, x, layer):
    """Top-k routed mixture-of-experts SwiGLU FFN (GShard-style einsum
    dispatch -- the SPMD-native formulation: the dispatch/combine
    einsums carry the ``ep`` sharding from the expert weights
    (partition_specs), so XLA derives the expert collectives from the
    layout instead of hand-written all-to-alls.  No reference
    counterpart: /root/reference has no parallelism at all (SURVEY
    §2.5); EP is this build's own first-class axis.)

    x: [B, S, D] normed activations.  Returns (ffn_out [B, S, D],
    aux load-balance scalar).  Static shapes throughout: each expert
    processes a fixed ``moe_capacity`` token buffer; tokens routed past
    a full expert are dropped from that expert (their residual stream
    is unaffected -- standard capacity semantics).
    """
    c = config
    b, s, d = x.shape
    n = b * s
    e, k = c.n_experts, c.n_experts_per_token
    cap = c.moe_capacity(n)
    xf = x.reshape(n, d)

    router_logits = (xf.astype(jnp.float32)
                     @ layer["w_router"].astype(jnp.float32))   # [n,E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, choices = jax.lax.top_k(probs, k)                    # [n,k]
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    onehot = jax.nn.one_hot(choices, e, dtype=jnp.float32)      # [n,k,E]
    flat = onehot.reshape(n * k, e)
    # Each (token, choice)'s slot in its expert's buffer: how many
    # earlier rows picked the same expert (token-major order, so a
    # token's k distinct choices never collide).
    positions = ((jnp.cumsum(flat, axis=0) - flat) * flat).sum(-1)
    keep = positions < cap                                      # [n*k]
    pos_onehot = jax.nn.one_hot(positions.astype(jnp.int32), cap,
                                dtype=jnp.float32) * keep[:, None]
    # Dispatch/combine mask with the k choices PRE-SUMMED ([n, E, C];
    # the [n, k, E, C] tensor is never materialized -- the einsum
    # contracts k, and the sum is lossless because a token's k choices
    # hit distinct experts, so each (token, expert, slot) cell has at
    # most one contributor).  This [n, E, C] mask, in the compute
    # dtype, is the MoE memory ceiling (~cf*k*n^2/e * e elements per
    # layer); a sort/scatter router would remove the n^2 term if
    # profiles ever demand longer training batches.
    mask = jnp.einsum("nke,nkc->nec", onehot,
                      pos_onehot.reshape(n, k, cap)).astype(x.dtype)
    dispatch = jnp.einsum("nec,nd->ecd", mask, xf)
    gate_h = jax.nn.silu(_expert_matmul(dispatch, layer["w_gate"],
                                        "ecd,edf->ecf"))
    up_h = _expert_matmul(dispatch, layer["w_up"], "ecd,edf->ecf")
    out_e = _expert_matmul(gate_h * up_h, layer["w_down"],
                           "ecf,efd->ecd")                      # [E,C,D]
    gates_e = jnp.einsum("nke,nk->ne", onehot, gates)           # [n,E]
    combine = mask * gates_e.astype(x.dtype)[:, :, None]        # [n,E,C]
    out = jnp.einsum("nec,ecd->nd", combine, out_e)

    # GShard load-balance aux: E * sum_e(fraction routed * mean prob),
    # with fraction normalized over the n*k choices -- exactly 1.0 at
    # perfect balance for any k, grows as routing collapses.
    fraction = flat.reshape(n, k, e).sum(1).mean(0) / k
    aux = e * jnp.sum(fraction * probs.mean(0))
    return out.reshape(b, s, d), aux


def _block(config: LlamaConfig, hidden, layer, kv_write):
    """One transformer block.  ``kv_write(q, k, v) -> attn_out``
    abstracts prefill-vs-decode cache handling (RoPE + cache write +
    attention) and records the written cache on ``kv_write.updated``.
    Returns (hidden, moe aux-loss scalar -- 0 for dense FFN)."""
    c = config
    b, s, _ = hidden.shape
    hd = c.head_dim

    x = rms_norm(hidden, layer["attn_norm"], c.norm_eps)
    q = matmul(x, layer["wq"]).reshape(b, s, c.n_heads, hd)
    k = matmul(x, layer["wk"]).reshape(b, s, c.n_kv_heads, hd)
    v = matmul(x, layer["wv"]).reshape(b, s, c.n_kv_heads, hd)
    attn_out = kv_write(q, k, v)
    hidden = hidden + matmul(attn_out.reshape(b, s, c.n_heads * hd),
                             layer["wo"])

    x = rms_norm(hidden, layer["mlp_norm"], c.norm_eps)
    if c.n_experts:
        ffn_out, aux = _moe_ffn(c, x, layer)
        return hidden + ffn_out, aux
    gate = jax.nn.silu(matmul(x, layer["w_gate"]))
    hidden = hidden + matmul(gate * matmul(x, layer["w_up"]),
                             layer["w_down"])
    return hidden, jnp.float32(0.0)


def _forward_layers(params: dict, config: LlamaConfig, hidden,
                    cache: dict, kv_write_factory,
                    cache_from_updates=None):
    """Embed-to-logits scaffolding shared by the prefill/decode variants:
    scan the stacked layers, final-norm, unembed.

    ``kv_write_factory(k_layer, v_layer) -> kv_write`` builds the
    per-layer cache-write-and-attend closure (see :func:`_block`); each
    layer's ``kv_write.updated`` is stacked as the scan output.  The
    cache rides the scan as ``xs``, so every step slices its layer out
    (a copy wherever XLA cannot fuse the slice into its consumer).  By
    default the updates ARE the new cache layers, restacked into a
    FRESH cache that donation cannot alias -- only ``_prefill_core``
    (training / whole-batch dense prefill, whose cache is the size of
    its batch) is left on that branch.  ``cache_from_updates``
    post-processes them instead: the reference decode and verify paths
    read the cache as a read-only ``xs`` (their einsums fuse the
    slice), emit only each layer's new-token k/v and scatter once at
    the end.  Serving admission (:func:`_admit_chunks`) does not come
    through here at all: beside a multi-GB page pool the ``xs`` slices
    and the restacked ``ys`` were three quarters of a chunk's device
    time, so it scans the layer index and closes over the cache, as
    the flash decode scan does.
    Activation sharding follows from the param/cache input shardings via
    SPMD propagation; serving/training wrappers pin in_shardings
    explicitly (see models/train.py, tpu elements).

    Returns (logits, cache, aux) where aux is the summed MoE
    load-balance loss over layers (0 for dense configs).
    """
    def layer_step(carry, xs):
        hidden, aux = carry
        layer, k_layer, v_layer = xs
        kv_write = kv_write_factory(k_layer, v_layer)
        hidden2, aux2 = _block(config, hidden, layer, kv_write)
        return (hidden2, aux + aux2), kv_write.updated

    if config.remat:
        layer_step = jax.checkpoint(layer_step)

    (hidden, aux), updates = jax.lax.scan(
        layer_step, (hidden, jnp.float32(0.0)),
        (params["layers"], cache["k"], cache["v"]))
    logits = _finish(params, config, hidden)
    if cache_from_updates is not None:
        return logits, cache_from_updates(updates), aux
    k_new, v_new = updates
    return logits, {"k": k_new, "v": v_new}, aux


def _finish(params: dict, config: LlamaConfig, hidden) -> jax.Array:
    """Final norm + unembed, shared by _forward_layers and the flash
    decode scan (which carries a layer INDEX instead of cache slices --
    keep the two scaffolds in sync through this helper; decode never
    differentiates, so config.remat is irrelevant there).

    A quantized unembed dispatches through the fused Pallas
    dequant-matmul when ``config.matmul_kernel`` resolves to it
    (ops.matmul_backend): the unembed is the single largest serving
    matmul AND scan-invariant (closure-captured whole even inside the
    draft scan), so no per-layer slice materializes in front of the
    pallas call."""
    hidden = rms_norm(hidden, params["final_norm"], config.norm_eps)
    return matmul(hidden, params["unembed"],
                  kernel=(matmul_backend(config.matmul_kernel)
                          != "reference"))


def _prefill_core(params: dict, config: LlamaConfig, tokens: jax.Array,
                  cache: dict, start_positions: jax.Array):
    """Shared prefill body -> (logits, cache, moe aux)."""
    if is_paged(cache):
        raise ValueError(
            "prefill works on dense caches (training / whole-batch "
            "path); paged serving admission goes through "
            "prefill_into_slot(s)")
    c = config
    b, s = tokens.shape
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = start_positions[:, None] + jnp.arange(s)[None, :]

    def factory(k_layer, v_layer):
        def kv_write(q, k, v):
            q = apply_rope(q, rope_table, positions)
            k = apply_rope(k, rope_table, positions)
            # scatter chunk into the cache at [b, start+i]
            batch_index = jnp.arange(b)[:, None]

            def write(old, new):
                return old.at[batch_index, positions].set(new)
            k_layer2 = _kv_store(k_layer, k, write)
            v_layer2 = _kv_store(v_layer, v, write)
            kv_write.updated = (k_layer2, v_layer2)
            # Grouped view consumed directly (attention_prefill groups
            # the queries): no repeat_kv materialization.
            return attention_prefill(q, _grouped(k_layer2, c.n_kv_heads),
                                     _grouped(v_layer2, c.n_kv_heads),
                                     positions)
        return kv_write

    return _forward_layers(params, c, params["embed"][tokens], cache,
                           factory)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_jit(params: dict, config: LlamaConfig, tokens: jax.Array,
                 cache: dict, start_positions: jax.Array) \
        -> tuple[jax.Array, dict]:
    """Process a prompt chunk, writing the cache.

    tokens: [B, S] (right-padded chunks allowed -- positions beyond a
    sequence's true content are simply overwritten by later chunks);
    start_positions: [B] cache offset each row's chunk begins at.
    Returns (logits [B, S, vocab], cache).
    """
    logits, cache, _ = _prefill_core(params, config, tokens, cache,
                                     start_positions)
    return logits, cache


def prefill(params: dict, config: LlamaConfig, tokens: jax.Array,
            cache: dict, start_positions: jax.Array) \
        -> tuple[jax.Array, dict]:
    """Whole-batch prompt prefill (see _prefill_jit); a distributed
    quantized unembed resolves the matmul kernel off here, where the
    concrete tree's sharding is visible (_matmul_safe_config -- the
    decode wrappers' discipline)."""
    return _prefill_jit(params, _matmul_safe_config(config, params),
                        tokens, cache, start_positions)


prefill.__wrapped__ = _prefill_jit.__wrapped__


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_with_aux(params: dict, config: LlamaConfig,
                     tokens: jax.Array, cache: dict,
                     start_positions: jax.Array) \
        -> tuple[jax.Array, dict, jax.Array]:
    """:func:`prefill` that also returns the summed MoE load-balance
    aux loss over layers (the MoE training path; 0 for dense)."""
    return _prefill_core(params, config, tokens, cache,
                         start_positions)


def _scatter_chunks(cache: dict, k_chunks, v_chunks, slots,
                    starts) -> dict:
    """Write admission chunks (``[L, N, S, K, hd]``, row i at
    ``(slots[i], starts[i])``) into the donated cache AFTER the layer
    scan -- the whole-chunk twin of :func:`_scatter_positions`: one
    dynamic_update_slice per row spanning all layers for a dense cache,
    one per (row, covered page) through the page table for a paged one
    (paged.scatter_pages).  The cache is touched nowhere else."""
    def write(old, new):                         # new [L, N, S, *]
        if is_paged(cache):
            return scatter_pages(old, new, cache["page_table"], slots,
                                 starts, pool_page_tokens(cache))
        for i in range(new.shape[1]):
            old = jax.lax.dynamic_update_slice(
                old, new[:, i:i + 1],
                (0, slots[i], starts[i]) + (0,) * (old.ndim - 3))
        return old

    return {**cache, "k": _kv_store(cache["k"], k_chunks, write),
            "v": _kv_store(cache["v"], v_chunks, write)}


def _admit_chunks(params: dict, config: LlamaConfig, tokens: jax.Array,
                  cache: dict, slots, starts) -> tuple[jax.Array, dict]:
    """Admission body shared by prefill_into_slot (N=1, served or with
    every row's logits) and prefill_into_slots: forward chunks
    ``tokens`` [N, S], row i at cache offset ``starts[i]`` of batch row
    ``slots[i]``.  Returns the last layer's hidden states ``[N, S, D]``
    -- the callers choose which rows reach the head (:func:`_finish`)
    -- and the cache.

    The cache NEVER enters or leaves the layer scan as ``xs``/``ys``
    (the decode discipline, see _decode_step_impl): the scan carries
    the LAYER INDEX and closes over the stacked cache read-only.  Per
    layer it reads only the admitting slots' own rows (one gather: at
    ``(layer, slots)`` of a dense cache, of the slots' pages of a paged
    pool -- N unrolled dynamic_slices fuse into one consumer of the
    WHOLE cache, for which the v5e compiler then picks a T-minor layout
    and copies the cache ahead of the loop), lays the chunk's fresh k/v
    into that small row view, attends over it, and emits the chunk's
    k/v as the scan's only cache-related output;
    :func:`_scatter_chunks` writes them into the donated cache once,
    after the scan.  With the cache in ``xs``
    and the updated layers as ``ys`` each step sliced its layer out of
    the cache and the scan stacked a FRESH cache donation could not
    alias: on v5e every chunk moved each side of a 5.2 GB pool about
    four times (71 ms a chunk, of which ~16 ms matmuls and attention;
    PERF.md, PR 27)."""
    c = config
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    n, s = tokens.shape
    positions = starts[:, None] + jnp.arange(s)[None, :]     # [N, S]
    paged = is_paged(cache)
    if paged and s % pool_page_tokens(cache):
        raise ValueError(
            f"paged prefill chunk of {s} tokens is not a whole "
            f"number of {pool_page_tokens(cache)}-token pages")

    def rows(side, index):
        """One layer's rows of the admitting slots: [N, T, ...]."""
        if paged:
            return gather_rows(side, cache["page_table"][slots], index)
        return _kv_rows(side, lambda arr: arr[index, slots])

    def lay(old, new):                  # chunks into their row views
        for i in range(n):
            old = jax.lax.dynamic_update_slice(
                old, new[i:i + 1], (i, starts[i]) + (0,) * (old.ndim - 2))
        return old

    def layer_step(carry, xs):
        hidden, aux = carry
        layer, index = xs

        def kv_write(q, k, v):
            q = apply_rope(q, rope_table, positions)
            k = apply_rope(k, rope_table, positions)
            kv_write.updated = (k, v)
            k_rows = _grouped(_kv_store(rows(cache["k"], index), k, lay),
                              c.n_kv_heads)
            v_rows = _grouped(_kv_store(rows(cache["v"], index), v, lay),
                              c.n_kv_heads)
            if c.attention == "flash":
                # Causality from the traced chunk offset covers both
                # intra-chunk masking and the unwritten cache tail.
                # The kernel reads bf16; an int8 cache row is
                # dequantized here (admission is compute-bound -- the
                # byte saving matters in decode, which never does this).
                from ..ops.pallas_attention import flash_attention
                if is_quantized(k_rows):
                    k_rows = dequantize_kv(k_rows, q.dtype)
                    v_rows = dequantize_kv(v_rows, q.dtype)
                return flash_attention(q, k_rows, v_rows,
                                       q_offset=starts[0])
            return attention_prefill(q, k_rows, v_rows, positions)
        hidden2, aux2 = _block(c, hidden, layer, kv_write)
        return (hidden2, aux + aux2), kv_write.updated

    (hidden, _), (k_chunks, v_chunks) = jax.lax.scan(
        layer_step, (params["embed"][tokens], jnp.float32(0.0)),
        (params["layers"], jnp.arange(c.n_layers)))
    return hidden, _scatter_chunks(cache, k_chunks, v_chunks, slots, starts)


# A single-slot admission chunk reaches the device as ONE int32 row
# (:func:`pack_admission`): its tokens padded to the program's width,
# then this many columns -- the slot (also its index into the
# first-token vector), the chunk's cache offset, its last real
# position, whether it ends its prompt, and the request's temperature
# as its bits.
_ADMIT_COLUMNS = 5


def pack_admission(tokens, width: int, slot: int, start: int,
                   final: bool = False,
                   temperature: float = 0.0) -> np.ndarray:
    """One admission chunk as the host array :func:`admit_packed`
    takes: ``[1, width + 5]`` int32, the chunk's tokens padded to
    ``width`` and then the ``_ADMIT_COLUMNS`` scalars."""
    packed = np.zeros((1, width + _ADMIT_COLUMNS), dtype=np.int32)
    packed[0, :len(tokens)] = tokens
    packed[0, width:] = (slot, start, len(tokens) - 1, final,
                         np.float32(temperature).view(np.int32))
    return packed


def _first_token(logits, temperature, key, top_k: int):
    """The batcher's rule for a first token (``ContinuousBatcher.
    _sample``) on the device: the argmax of ``logits`` ``[1, vocab]``
    at temperature 0; otherwise a draw from a split of ``key`` --
    :func:`select_tokens` under ``top_k``, :func:`temperature_sample`
    without.  Returns (token ``[1]``, the key the batcher goes on
    with)."""
    def greedy(key):
        return greedy_sample(logits), key

    def sample(key):
        key, sub = jax.random.split(key)
        if top_k:
            return select_tokens(sub, logits, temperature[None],
                                 top_k=top_k), key
        return temperature_sample(sub, logits,
                                  temperature.astype(logits.dtype)), key

    return jax.lax.cond(temperature > 0, sample, greedy, key)


@partial(jax.jit, static_argnames=("config", "pick", "top_k"),
         donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: LlamaConfig,
                           packed: jax.Array, cache: dict,
                           firsts: jax.Array | None = None,
                           key: jax.Array | None = None, *,
                           pick: bool = False, top_k: int = 0):
    """Process one prompt chunk for ONE sequence, writing its KV directly
    into batch row ``slot`` of the BATCHED cache (no scratch cache; the
    donated cache is written once, after the layer scan, and only where
    the chunk lands -- see :func:`_admit_chunks`; the continuous
    batcher's admission path).

    packed: ``[1, S + 5]`` (:func:`pack_admission`): the chunk's tokens
    (right-padding allowed; pad positions are overwritten by decode
    before the length mask ever admits them), its slot, its cache
    offset ``start``, its last real position, whether it is its
    prompt's last and the request's temperature.  Queries attend the
    slot's whole cache row, so chunk N sees chunks 0..N-1 written by
    earlier calls.  The cache is donated for in-place update.

    Without ``pick``: (logits [1, S, vocab], cache), every row through
    the head.  With ``pick`` (the served form): the head reads the
    hidden row at the last real position alone, and only on a prompt's
    last chunk, which chooses the first token by :func:`_first_token`
    and writes it into ``firsts`` ``[max_slots]`` at the slot ->
    (cache, firsts, token [1], key).  A chunk that does not end its
    prompt runs no head and leaves ``firsts`` and ``key`` as they were.
    (``firsts`` is not donated: a decode block still in flight holds
    the vector it folded its joiners from, to fetch at its retire.)

    A PAGED cache (models/paged.py) is read and written through its
    page table: the chunk start must be page-aligned and S a whole
    number of pages (the ContinuousBatcher's chunk discipline
    guarantees both), so the write is one dynamic_update_slice per
    covered page and the attention row is the slot's gathered page
    view with the chunk laid into it.
    """
    width = packed.shape[1] - _ADMIT_COLUMNS
    slot, start, last, final, bits = (packed[0, width + column]
                                      for column in range(_ADMIT_COLUMNS))
    hidden, cache = _admit_chunks(params, config, packed[:, :width], cache,
                                  slot[None], start[None])
    if not pick:
        return _finish(params, config, hidden), cache
    row = jax.lax.dynamic_slice_in_dim(hidden, last, 1, axis=1)
    temperature = jax.lax.bitcast_convert_type(bits, jnp.float32)

    def choose(key):
        return _first_token(_finish(params, config, row)[:, 0, :],
                            temperature, key, top_k)

    def skip(key):
        return jnp.zeros((1,), jnp.int32), key

    token, key = jax.lax.cond(final != 0, choose, skip, key)
    firsts = firsts.at[slot].set(
        jnp.where(final != 0, token[0], firsts[slot]))
    return cache, firsts, token, key


def prefill_into_slot(params: dict, config: LlamaConfig,
                      tokens: jax.Array, cache: dict, slot: jax.Array,
                      start: jax.Array) -> tuple[jax.Array, dict]:
    """Single-slot admission with every row's logits: tokens [1, S],
    slot and start scalars -> (logits [1, S, vocab], cache); the
    served program's body with the whole chunk through the head (see
    _prefill_into_slot_jit).  The matmul kernel resolves eagerly on the
    concrete tree's sharding, as in :func:`prefill`."""
    width = tokens.shape[1]
    packed = jnp.concatenate(
        [jnp.asarray(tokens, jnp.int32),
         jnp.asarray([[slot, start, width - 1, 0, 0]], jnp.int32)], axis=1)
    return _prefill_into_slot_jit(
        params, _matmul_safe_config(config, params), packed, cache)


def admit_packed(params: dict, config: LlamaConfig, packed: jax.Array,
                 cache: dict, firsts: jax.Array, key: jax.Array, *,
                 top_k: int = 0):
    """The served single-slot admission (``ADMISSION_PICKS_FIRST``):
    one packed chunk (:func:`pack_admission`, uploaded once) -> (cache,
    firsts, token [1], key); see _prefill_into_slot_jit with ``pick``."""
    return _prefill_into_slot_jit(
        params, _matmul_safe_config(config, params), packed, cache,
        firsts, key, pick=True, top_k=top_k)



@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slots_jit(params: dict, config: LlamaConfig,
                            tokens: jax.Array, cache: dict,
                            slots: jax.Array,
                            starts: jax.Array) -> tuple[jax.Array, dict]:
    """Batched multi-slot admission: process one prompt chunk for N
    sequences in ONE dispatch, each row writing its KV into its own
    batch row of the cache (the batcher's burst-admission path -- N
    single-slot dispatches serialize ~N x 8 ms of device time at
    llama3-1b, and the [N*S, dim] matmuls feed the MXU far better than
    [1*S, dim]).

    tokens: [N, S] chunks (right-padding allowed); slots/starts: [N].
    Rows may DUPLICATE another row (same slot, same start, same tokens)
    -- the unrolled per-row cache writes are idempotent then, which is
    how the batcher pads N up to a compile-shape bucket.  Dense
    attention only (the flash path keeps per-slot calls: its q_offset
    is per-dispatch).  Returns (logits [N, S, vocab], cache).
    """
    if config.attention == "flash":
        raise ValueError("prefill_into_slots is dense-only; "
                         "flash admission uses prefill_into_slot")
    hidden, cache = _admit_chunks(params, config, tokens, cache, slots,
                                  starts)
    return _finish(params, config, hidden), cache


def prefill_into_slots(params: dict, config: LlamaConfig,
                       tokens: jax.Array, cache: dict, slots: jax.Array,
                       starts: jax.Array) -> tuple[jax.Array, dict]:
    """Batched multi-slot admission (see _prefill_into_slots_jit); the
    matmul kernel resolves eagerly on the concrete tree's sharding, as
    in :func:`prefill`."""
    return _prefill_into_slots_jit(
        params, _matmul_safe_config(config, params), tokens, cache,
        slots, starts)


# The batcher may hand admission a chunk only as wide as its real
# tokens need: ``_admit_chunks`` takes any whole number of pages (a
# dense cache any length), flash attention narrows its blocks to the
# chunk, and pad rows only write positions past the length mask.  (An
# MoE layer's capacity buffer follows the program's rows.)
ADMISSION_NARROW_CHUNKS = True

# The batcher hands a single-slot chunk over as ONE packed upload
# (:func:`pack_admission`) to ONE program that also chooses the first
# token and writes it into the first-token vector (:func:`admit_packed`).
ADMISSION_PICKS_FIRST = True



def _cache_distributed(cache) -> bool:
    """True when the cache payload lives sharded across more than one
    device.  The Pallas decode kernel (a custom call) has no GSPMD
    partitioning rules, so jit would wrap it in a full-cache all-gather
    every layer -- dense attention, whose einsums GSPMD partitions
    natively, is always faster there.  Tracers (calls from inside
    another jit) carry no sharding and resolve as resident."""
    return _distributed_array(cache_array(cache))


def resolve_decode_backend(c: LlamaConfig, cache: dict) -> str:
    """Pick the decode attention backend EAGERLY (outside jit), where
    the cache's sharding and structure are visible, through the ops
    capability probe (:func:`aiko_services_tpu.ops.decode_backend`):
    paged caches route to the page-table-walking Pallas kernel, dense
    flash-eligible caches to the flat/stacked split-K kernel, and
    everything else -- every ``auto`` resolution off the TPU backend
    included -- to the reference dense path.  No try/except, no paged
    dead-end raise (ISSUE 11).  'auto' keeps dense for a distributed
    cache; explicit 'flash' raises there rather than compiling a
    per-layer all-gather of the whole cache.  Returns one of
    ``ops.DECODE_BACKENDS`` (``chip_smoke.py`` prints it)."""
    distributed = _cache_distributed(cache)
    if c.decode_attention == "flash" and distributed:
        raise ValueError(
            "decode_attention='flash' needs the KV cache resident "
            "on one device (pallas_call has no GSPMD partitioning "
            "rules; a tp/dp-sharded cache would be all-gathered in "
            "full every layer).  Use 'dense' -- or 'auto', which "
            "falls back -- when serving with a sharded cache.")
    paged = is_paged(cache)
    return decode_backend(
        c.decode_attention, paged=paged, extent=cache_extent(cache),
        threshold=c.flash_decode_threshold, distributed=distributed,
        page_tokens=pool_page_tokens(cache) if paged else None)


def _resolve_decode_flash(c: LlamaConfig, cache: dict) -> bool:
    return resolve_decode_backend(c, cache) != "reference"


def paged_decode_pages(c: LlamaConfig, cache: dict) -> int | None:
    """Pages a grid step of the paged decode kernel where that kernel
    serves this cache's decode (:func:`resolve_decode_backend`), else
    None: what the batcher counts ``llm_decode_live_grid_share`` at."""
    if resolve_decode_backend(c, cache) != "paged-kernel":
        return None
    from ..ops.pallas_decode import _split_paged, paged_pages_per_step
    return paged_pages_per_step(jax.eval_shape(_split_paged, cache["k"]),
                                cache["page_table"].shape[1])


def _scatter_positions(config: LlamaConfig, cache: dict, k_tokens,
                       v_tokens, positions) -> dict:
    """Scatter per-token KV updates (``[L, B, S, K, hd]``) into the
    cache at ``positions`` [B, S] -- the post-scan write shared by
    decode_step (S=1) and the speculative verify chunk (S=k+1).  One
    unrolled dynamic_update_slice per (row, position): in place under
    donation for dense caches, and routed through the page table for
    paged ones.  Returns the cache dict (page table values untouched:
    paging changes WHERE bytes land, never the table itself)."""
    b, s = positions.shape
    paged = is_paged(cache)
    if paged:
        table = cache["page_table"]
        page_tokens = pool_page_tokens(cache)

    def scatter(layer, toks):
        def write(old, new):                     # new [L, B, S, *]
            for row in range(b):
                for col in range(s):
                    part = jax.lax.dynamic_slice(
                        new, (0, row, col) + (0,) * (new.ndim - 3),
                        (new.shape[0], 1, 1) + new.shape[3:])
                    pos = positions[row, col]
                    if paged:
                        start = (0, table[row, pos // page_tokens],
                                 pos % page_tokens)
                    else:
                        start = (0, row, pos)
                    old = jax.lax.dynamic_update_slice(
                        old, part, start + (0,) * (old.ndim - 3))
            return old
        return _kv_store(layer, toks, write)

    out = {"k": scatter(cache["k"], k_tokens),
           "v": scatter(cache["v"], v_tokens)}
    if paged:
        out["page_table"] = table
    return out


def _decode_step_impl(params: dict, config: LlamaConfig,
                      tokens: jax.Array, cache: dict,
                      lengths: jax.Array,
                      use_flash: bool | None = None,
                      attend: jax.Array | None = None) \
        -> tuple[jax.Array, dict]:
    """One token per active sequence.

    tokens: [B] current tokens; lengths: [B] positions to write (= current
    sequence length); attend: [B] cache positions each row reads
    (default ``lengths``; the device loop gives 0 to a row that does
    not decode -- its write lands at the trash position and it streams
    no page).  Returns (logits [B, vocab], cache).
    """
    c = config
    b = tokens.shape[0]
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = lengths[:, None]                       # [B, 1]
    if attend is None:
        attend = lengths
    paged = is_paged(cache)
    extent = cache_extent(cache)
    if use_flash is None:
        # In-jit callers have no sharding to inspect; resolve on
        # static structure alone through the same ops capability probe
        # the eager path uses.
        use_flash = decode_backend(
            c.decode_attention, paged=paged, extent=extent,
            threshold=c.flash_decode_threshold,
            page_tokens=pool_page_tokens(cache) if paged else None) \
            != "reference"

    def scatter_tokens(updates):
        # One dynamic_update_slice per batch row, unrolled.  A single
        # batched scatter (``.at[:, arange(b), lengths].set``) defeats
        # XLA's in-place buffer aliasing here -- the cache is also read
        # in full by the layer scan, and the scatter makes XLA copy the
        # whole cache every step (~1.25 ms at llama3-1b/1k on v5e); the
        # unrolled DUS chain updates in place.  b is a static trace-time
        # constant (the slot count), so the unroll is bounded.  Paged
        # caches route each row's write through its page table.
        k_tokens, v_tokens = updates               # [L, B, 1, K, hd]
        new_cache = _scatter_positions(c, cache, k_tokens, v_tokens,
                                       lengths[:, None])
        return new_cache

    if use_flash:
        # Split-K Pallas kernel path (ops/pallas_decode.py): the cache
        # streams once, no [B, H, T] HBM intermediates, int8 dequantized
        # in-kernel.  The layer scan carries the LAYER INDEX and the
        # kernel indexes the STACKED FLAT cache (or the paged page
        # POOLS, walking the [B, pps] table inside the grid -- no
        # host-side gather_layer materialization) in its BlockSpecs --
        # putting the cache in scan xs would materialize a per-layer
        # slice copy ahead of the pallas call (XLA fuses slices into
        # einsums but not into custom calls; measured ~0.3 ms/layer at
        # 8k on v5e).  The flat [L, B, T, K*hd] storage (init_cache) is
        # what keeps the kernel's operand at the default layout -- see
        # its docstring for the 2x full-cache copies a grouped buffer
        # cost.
        from ..ops.pallas_decode import (_split_paged, _split_stacked,
                                         flash_decode_append_paged,
                                         flash_decode_append_stacked)
        if paged:
            k_view = _split_paged(cache["k"])
            v_view = _split_paged(cache["v"])
        else:
            k_view = _split_stacked(cache["k"])
            v_view = _split_stacked(cache["v"])
        hidden0 = params["embed"][tokens][:, None, :]

        def layer_step(carry, xs):
            hidden, aux = carry
            layer, index = xs

            def kv_write(q, k, v):
                q = apply_rope(q, rope_table, positions)
                k = apply_rope(k, rope_table, positions)
                kv_write.updated = (k, v)
                if paged:
                    return flash_decode_append_paged(
                        q, k_view, v_view, index, k, v,
                        cache["page_table"], attend)
                return flash_decode_append_stacked(
                    q, k_view, v_view, index, k, v, attend)
            hidden2, aux2 = _block(c, hidden, layer, kv_write)
            return (hidden2, aux + aux2), kv_write.updated

        (hidden, _), updates = jax.lax.scan(
            layer_step, (hidden0, jnp.float32(0.0)),
            (params["layers"], jnp.arange(c.n_layers)))
        return _finish(params, c, hidden)[:, 0, :], \
            scatter_tokens(updates)

    def factory(k_layer, v_layer):
        def kv_write(q, k, v):
            q = apply_rope(q, rope_table, positions)
            k = apply_rope(k, rope_table, positions)
            # The cache stays a read-only scan input; only the token's
            # k/v leave the scan (see _forward_layers / the post-scan
            # scatter above).  A paged layer is gathered to the same
            # logical [B, T, ...] view first (the gather-reshape feeds
            # the attention einsums directly).
            kv_write.updated = (k, v)
            if paged:
                k_view = gather_layer(k_layer, cache["page_table"])
                v_view = gather_layer(v_layer, cache["page_table"])
            else:
                k_view, v_view = k_layer, v_layer
            return attention_decode_append(
                q, _grouped(k_view, c.n_kv_heads),
                _grouped(v_view, c.n_kv_heads), k, v, attend)
        return kv_write

    logits, new_cache, _ = _forward_layers(
        params, c, params["embed"][tokens][:, None, :], cache, factory,
        cache_from_updates=scatter_tokens)
    return logits[:, 0, :], new_cache


_decode_step_jit = partial(jax.jit, static_argnames=("config", "use_flash"),
                           donate_argnames=("cache",))(_decode_step_impl)


def _distributed_array(arr) -> bool:
    """Concrete array resident sharded across more than one device
    (tracers carry no sharding and resolve as resident)."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None:
        return False
    try:
        return (len(sharding.device_set) > 1
                and not sharding.is_fully_replicated)
    except (AttributeError, TypeError):
        return False


def _matmul_safe_config(c: LlamaConfig, params: dict) -> LlamaConfig:
    """The decode gate's pallas_call-has-no-GSPMD invariant applied to
    the matmul kernel: a DISTRIBUTED quantized unembed (TP/fsdp
    serving) must keep XLA's cast-into-dot path -- jit would otherwise
    all-gather the largest weight every step.  Resolved eagerly in the
    serving wrappers (and ContinuousBatcher), where the concrete
    tree's sharding is visible; inside jit the leaves are tracers and
    cannot be inspected."""
    if matmul_backend(c.matmul_kernel) == "reference":
        return c
    unembed = params.get("unembed") if isinstance(params, dict) else None
    if is_quantized(unembed) and _distributed_array(unembed["int8"]):
        return dataclasses.replace(c, matmul_kernel="off")
    return c


def check_serving(**_) -> None:
    """What this family cannot serve under a batcher's settings
    (``batching.model_family``): nothing is refused here -- the
    batcher's own checks cover the Llama family."""


def decode_step(params: dict, config: LlamaConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array) \
        -> tuple[jax.Array, dict]:
    """One decode token per active sequence (see _decode_step_impl).
    The flash-vs-dense choice resolves HERE, where the concrete cache's
    sharding is visible -- 'auto' never routes a tp/dp-sharded cache
    (or a tp/fsdp-sharded quantized unembed, via _matmul_safe_config)
    into the partitioning-rule-less Pallas kernels."""
    config = _matmul_safe_config(config, params)
    return _decode_step_jit(params, config, tokens, cache, lengths,
                            use_flash=_resolve_decode_flash(config, cache))



def greedy_sample(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1)


def temperature_sample(key: jax.Array, logits: jax.Array,
                       temperature: float = 0.7) -> jax.Array:
    return jax.random.categorical(key, logits / temperature, axis=-1)


def select_tokens(key: jax.Array, logits: jax.Array,
                  temperatures: jax.Array,
                  top_k: int = 0) -> jax.Array:
    """Per-row sampling in one draw: rows with temperature 0 take the
    argmax, rows with temperature > 0 a categorical sample at their own
    temperature.  ``top_k`` > 0 (static) restricts the categorical to
    the k highest logits via the ops top-k interface -- the Pallas
    kernel (ops/pallas_topk.py) on TPU, ``lax.top_k`` elsewhere; the
    candidate set is found in one cache-friendly pass instead of a
    full-vocab sort, and greedy rows are unaffected (argmax == top-1).
    """
    greedy = jnp.argmax(logits, axis=-1)
    safe = jnp.maximum(temperatures, 0.05)[:, None]
    if top_k:
        from ..ops import topk as ops_topk
        values, indices = ops_topk(logits.astype(jnp.float32),
                                   int(top_k))
        choice = jax.random.categorical(key, values / safe, axis=-1)
        sampled = jnp.take_along_axis(indices, choice[:, None],
                                      axis=1)[:, 0]
    else:
        sampled = jax.random.categorical(
            key, logits.astype(jnp.float32) / safe, axis=-1)
    return jnp.where(temperatures > 0, sampled, greedy)


# ---------------------------------------------------------------------------
# Device-resident generation loop (ISSUE 8 tentpole): a lax.while_loop
# that samples, detects stops and (optionally) speculates entirely
# on-device, so the host fetches a BLOCK of emitted tokens at a time
# instead of driving one round trip per token.


def _ngram_draft(history, tokens, k: int):
    """Self-drafting proposal from the recent-token window: find the
    most recent PRIOR occurrence of the current token in ``history``
    (the newest entry IS the current token) and propose the ``k``
    tokens that followed it; rows with no prior occurrence repeat the
    current token.  Unfilled window entries are -1 (never a real
    token id) and fall back to repetition too.

    history: [B, W] (old -> new); tokens: [B].  Returns [B, k] int32.
    """
    w = history.shape[1]
    prior = history[:, :-1]                          # continuation exists
    match = prior == tokens[:, None]
    latest = jnp.where(match, jnp.arange(w - 1)[None, :], -1).max(1)
    gather = jnp.clip(latest[:, None] + 1 + jnp.arange(k)[None, :],
                      0, w - 1)
    continuation = jnp.take_along_axis(history, gather, axis=1)
    drafts = jnp.where((latest >= 0)[:, None] & (continuation >= 0),
                       continuation, tokens[:, None])
    return drafts.astype(jnp.int32)


def _history_push(history, candidates, cut):
    """Append each row's first ``cut[b]`` candidate tokens to its
    recent-token window, dropping the oldest: one per-row gather over
    ``concat(history, candidates)`` shifted by ``cut`` -- rejected
    candidates (beyond the cut) sit past the gather's reach, so they
    never enter the window."""
    w = history.shape[1]
    combined = jnp.concatenate([history, candidates.astype(history.dtype)],
                               axis=1)
    index = jnp.arange(w)[None, :] + cut[:, None]
    return jnp.take_along_axis(combined, index, axis=1)


def _draft_window(draft, config: LlamaConfig, tokens, cache, lengths,
                  active, k: int, window: int, trash: int):
    """Amortized draft proposal (ISSUE 18): ``k`` greedy draft tokens
    per row from ONE cache read.  The old draft loop re-dispatched
    ``k`` full decode steps per iteration -- each streaming the whole
    KV cache (and gathering every page of a paged cache) for ONE
    cheap token, which is why r07/r08 measured draft speculation
    SLOWER than plain decode.  Here the last ``window`` cache
    positions of each row are gathered once ([B, W] per side, int8
    windows dequantized small), and the k autoregressive draft steps
    attend over window + the step's own scratch KV via
    :func:`attention_prefill` with explicit key positions -- the
    chunk-verify discipline.  Nothing is written back: verify's
    optimistic writes land target-weight KV at exactly these
    positions, so draft KV would be overwritten anyway.

    The window is an APPROXIMATION of the full prefix (draft quality,
    not correctness: the target verify accepts only matching tokens,
    so a clipped-context draft can only lower acceptance, never change
    output).  tokens/lengths/active: [B]; returns drafts [B, k]."""
    c = config
    b = tokens.shape[0]
    w = int(window)
    extent = cache_extent(cache)
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    # Window = the last w valid positions of each row (clamped; rows
    # shorter than w mask the underflow out).
    wpos_raw = lengths[:, None] - w + jnp.arange(w)[None, :]   # [B, W]
    wvalid = wpos_raw >= 0
    wpos = jnp.clip(wpos_raw, 0, extent - 1)

    def gather_window(side):
        """One cache side -> the dequantized grouped window
        [L, B, W, K, hd] -- the single full-cache read."""
        if is_paged(cache):
            pt = pool_page_tokens(cache)
            linear = cache["page_table"][
                jnp.arange(b)[:, None], wpos // pt] * pt + wpos % pt

            def flat_take(arr):        # [L, P, pt, ...] pool
                flat = arr.reshape(arr.shape[0], -1, *arr.shape[3:])
                return flat[:, linear]             # [L, B, W, ...]
            win = {"int8": flat_take(side["int8"]),
                   "scale": flat_take(side["scale"])} \
                if is_quantized(side) else flat_take(side)
        else:
            def row_take(arr, extra_dims):         # [L, B, T, ...]
                index = wpos[None, :, :].reshape(
                    1, b, w, *(1,) * extra_dims)
                return jnp.take_along_axis(arr, index, axis=2)
            win = {"int8": row_take(side["int8"], 1),
                   "scale": row_take(side["scale"], 2)} \
                if is_quantized(side) else row_take(side, 1)
        win = _grouped(win, c.n_kv_heads)
        if is_quantized(win):
            win = dequantize_kv(win, _dtype(c))
        return win.astype(_dtype(c))

    win_k = gather_window(cache["k"])              # [L, B, W, K, hd]
    win_v = gather_window(cache["v"])
    # Scratch KV for the up-to-k draft tokens of THIS iteration; column
    # j holds step j's keys/values at position lengths + j.
    scratch_shape = (c.n_layers, b, k, c.n_kv_heads, c.head_dim)
    spos = jnp.minimum(lengths[:, None] + jnp.arange(k)[None, :],
                       trash)                      # [B, k]

    def draft_step(carry, step):
        current, scratch_k, scratch_v = carry
        pos = jnp.where(active, jnp.minimum(lengths + step, trash),
                        trash)[:, None]            # [B, 1]
        svalid = jnp.broadcast_to(
            (jnp.arange(k) < step)[None, :], (b, k))

        def layer_step(carry2, xs):
            hidden, aux = carry2
            layer, wk_l, wv_l, sk_l, sv_l = xs

            def kv_write(q, kk, vv):
                q = apply_rope(q, rope_table, pos)
                kk = apply_rope(kk, rope_table, pos)
                kv_write.updated = (kk, vv)
                k_all = jnp.concatenate(
                    [wk_l, sk_l, kk.astype(wk_l.dtype)], axis=1)
                v_all = jnp.concatenate(
                    [wv_l, sv_l, vv.astype(wv_l.dtype)], axis=1)
                kv_positions = jnp.concatenate(
                    [wpos, spos, pos], axis=1)     # [B, W+k+1]
                valid = jnp.concatenate(
                    [wvalid, svalid, jnp.ones((b, 1), dtype=bool)],
                    axis=1)
                return attention_prefill(q, k_all, v_all, pos,
                                         kv_length_mask=valid,
                                         kv_positions=kv_positions)
            hidden2, aux2 = _block(c, hidden, layer, kv_write)
            return (hidden2, aux + aux2), kv_write.updated

        hidden = draft["embed"][current[:, None]]  # [B, 1, D]
        (hidden, _), updates = jax.lax.scan(
            layer_step, (hidden, jnp.float32(0.0)),
            (draft["layers"], win_k, win_v, scratch_k, scratch_v))
        new_k, new_v = updates                     # [L, B, 1, K, hd]
        scratch_k = jax.lax.dynamic_update_slice(
            scratch_k, new_k.astype(scratch_k.dtype),
            (0, 0, step, 0, 0))
        scratch_v = jax.lax.dynamic_update_slice(
            scratch_v, new_v.astype(scratch_v.dtype),
            (0, 0, step, 0, 0))
        logits = _finish(draft, c, hidden)         # [B, 1, V]
        current = jnp.argmax(logits[:, 0, :], -1).astype(jnp.int32)
        return (current, scratch_k, scratch_v), current

    carry = (tokens,
             jnp.zeros(scratch_shape, dtype=win_k.dtype),
             jnp.zeros(scratch_shape, dtype=win_v.dtype))
    _, drafts = jax.lax.scan(draft_step, carry,
                             jnp.arange(k, dtype=jnp.int32))
    return drafts.T                                # [B, k]


def _chunk_verify(params, config: LlamaConfig, chunk, cache, starts,
                  trash: int, use_flash: bool = False, attend=None):
    """One batched multi-token target step: forward ``chunk`` [B, S]
    (current token + S-1 draft tokens per row) at per-row positions
    ``starts + i``, writing every position's KV optimistically and
    returning logits for all S positions.  The cache stays a read-only
    scan input (chunk KV is concatenated onto the attention's key axis
    with explicit key positions) and the S writes scatter once after
    the scan -- the decode_step discipline, not the full-cache rewrite
    prefill pays.  Rejected drafts leave garbage KV beyond the
    advanced length, which the length masks never admit and later
    decode overwrites before exposing -- the same overshoot contract
    as a plain loop block's.  Positions clamp to the trash
    position at the cache boundary (rows there stop this iteration,
    and their clamped-position tokens are cut before emission).

    ``use_flash`` routes the concat-attention through the batched
    chunk-verify kernel (ops/pallas_decode.py:flash_verify_append,
    ISSUE 11): the cache streams ONCE for all S positions with no
    [B, H, S, T] HBM logits -- and paged caches walk the page table
    in-kernel instead of paying the per-layer gather.  int8 caches
    dequantize in-kernel (exact), so the dense path's gather-and-
    dequantize trick is no longer the only option.

    ``attend``: [B] cache positions each row reads, default ``starts``
    (0 for a row that does not decode, as in ``_decode_step_impl``)."""
    c = config
    b, s = chunk.shape
    if attend is None:
        attend = starts
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = jnp.minimum(starts[:, None] + jnp.arange(s)[None, :],
                            trash)                           # [B, S]
    paged = is_paged(cache)
    extent = cache_extent(cache)

    def scatter_chunk(updates):
        k_tokens, v_tokens = updates             # [L, B, S, K, hd]
        return _scatter_positions(c, cache, k_tokens, v_tokens,
                                  positions)

    if use_flash:
        from ..ops.pallas_decode import (_split_paged, _split_stacked,
                                         flash_verify_append)
        if paged:
            k_view = _split_paged(cache["k"])
            v_view = _split_paged(cache["v"])
        else:
            k_view = _split_stacked(cache["k"])
            v_view = _split_stacked(cache["v"])

        def layer_step(carry, xs):
            hidden, aux = carry
            layer, index = xs

            def kv_write(q, k, v):
                q = apply_rope(q, rope_table, positions)
                k = apply_rope(k, rope_table, positions)
                kv_write.updated = (k, v)
                return flash_verify_append(
                    q, k_view, v_view, index, k, v, attend, positions,
                    page_table=cache["page_table"] if paged else None)
            hidden2, aux2 = _block(c, hidden, layer, kv_write)
            return (hidden2, aux + aux2), kv_write.updated

        (hidden, _), updates = jax.lax.scan(
            layer_step, (params["embed"][chunk], jnp.float32(0.0)),
            (params["layers"], jnp.arange(c.n_layers)))
        return _finish(params, c, hidden), scatter_chunk(updates)

    def factory(k_layer, v_layer):
        def kv_write(q, k, v):
            q = apply_rope(q, rope_table, positions)
            k = apply_rope(k, rope_table, positions)
            kv_write.updated = (k, v)
            if paged:
                k_view = gather_layer(k_layer, cache["page_table"])
                v_view = gather_layer(v_layer, cache["page_table"])
            else:
                k_view, v_view = k_layer, v_layer
            k_rows = _grouped(k_view, c.n_kv_heads)
            v_rows = _grouped(v_view, c.n_kv_heads)
            if is_quantized(k_rows):
                # The verify chunk is compute-shaped (S queries), so
                # dequantizing the gathered rows -- the flash
                # admission path's trick -- beats teaching the
                # concat-attention the int8 split.
                k_rows = dequantize_kv(k_rows, q.dtype)
                v_rows = dequantize_kv(v_rows, q.dtype)
            k_all = jnp.concatenate([k_rows, k], axis=1)
            v_all = jnp.concatenate([v_rows, v], axis=1)
            kv_positions = jnp.concatenate(
                [jnp.broadcast_to(jnp.arange(extent)[None, :],
                                  (b, extent)), positions], axis=1)
            valid = jnp.concatenate(
                [jnp.arange(extent)[None, :] < attend[:, None],
                 jnp.ones((b, s), dtype=bool)], axis=1)
            return attention_prefill(q, k_all, v_all, positions,
                                     kv_length_mask=valid,
                                     kv_positions=kv_positions)
        return kv_write

    logits, new_cache, _ = _forward_layers(
        params, c, params["embed"][chunk], cache, factory,
        cache_from_updates=scatter_chunk)
    return logits, new_cache


@partial(jax.jit,
         static_argnames=("config", "ring", "speculative", "spec_tokens",
                          "spec_window", "use_flash", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, draft: dict, config: LlamaConfig,
                     tokens: jax.Array, cache: dict, lengths: jax.Array,
                     active: jax.Array, budget: jax.Array,
                     temperatures: jax.Array, eos: jax.Array,
                     history: jax.Array, key: jax.Array, *, ring: int,
                     speculative: str, spec_tokens: int,
                     spec_window: int, use_flash: bool, top_k: int = 0):
    """The device-resident serving loop: up to ``ring`` tokens per row
    generated inside ONE dispatch, with sampling, per-slot stop
    detection (EOS + budget + cache boundary) and speculative
    multi-token decoding all in the ``lax.while_loop`` carry.  The
    host's only per-block work is one counted fetch of the emitted
    ring; every carry comes back as a device array so block k+1 chains
    off block k without a round trip.

    tokens: [B] current (sampled, unprocessed) tokens; lengths: [B]
    valid cache positions (prompt + generated); active: [B] bool;
    budget: [B] tokens each row may still emit; eos: [B, E] per-row
    stop tokens (-1 pads); history: [B, W] recent-token window for the
    n-gram draft ([B, 1] dummy otherwise).  The loop exits when every
    row stopped, or when the ring cannot hold another iteration's
    worst-case emission (speculation emits up to spec_tokens+1 per row
    per iteration).

    Returns ``(emitted [B, ring], counts [B], tokens', lengths',
    active', budget', history', key', accepted [B], drafted [B],
    steps, cache)`` -- ``accepted``/``drafted`` count this block's
    draft tokens proposed and kept (the speculation acceptance
    telemetry), ``steps`` the target-model iterations the block ran.
    """
    b = tokens.shape[0]
    trash = cache_extent(cache) - 1
    extent = cache_extent(cache)
    spec = speculative != "off"
    k = spec_tokens if spec else 0
    per_iter = k + 1

    def stops(token, budget_left, total):
        """Stop verdict AFTER emitting ``token`` with ``budget_left``
        remaining and ``total`` cache length -- mirrors the host
        batcher's finish test exactly (the equivalence contract)."""
        return ((token[:, None] == eos).any(-1) | (budget_left <= 0)
                | (total >= extent))

    def cond(carry):
        (i, tokens, cache, lengths, active, budget, key, emitted,
         counts, history, accepted, drafted) = carry
        room = jnp.where(active, counts, 0).max() + per_iter <= ring
        return (i < ring) & active.any() & room

    def body_plain(carry):
        (i, tokens, cache, lengths, active, budget, key, emitted,
         counts, history, accepted, drafted) = carry
        positions = jnp.where(active, jnp.minimum(lengths, trash), trash)
        # (a row that does not decode writes at the trash position and
        # reads nothing: its pages -- a part-admitted prompt's, say --
        # cost the attention kernel no copy and no product)
        logits, cache = _decode_step_impl(
            params, config, tokens, cache, positions,
            use_flash=use_flash, attend=jnp.where(active, positions, 0))
        key, sub = jax.random.split(key)
        sampled = select_tokens(sub, logits, temperatures,
                                top_k=top_k).astype(jnp.int32)
        slot_index = jnp.where(active, counts, ring)     # ring = trash col
        emitted = emitted.at[jnp.arange(b), slot_index].set(sampled)
        counts = counts + active
        lengths = lengths + active
        budget = budget - active
        stop = stops(sampled, budget, lengths) & active
        tokens = jnp.where(active, sampled, tokens)
        return (i + 1, tokens, cache, lengths, active & ~stop, budget,
                key, emitted, counts, history, accepted, drafted)

    def body_spec(carry):
        (i, tokens, cache, lengths, active, budget, key, emitted,
         counts, history, accepted, drafted) = carry
        greedy_row = active & (temperatures <= 0)
        if speculative == "ngram":
            drafts = _ngram_draft(history, tokens, k)        # [B, k]
        else:
            # Self-drafting from the quantized tree, amortized (ISSUE
            # 18): one window gather, k tiny attention steps, zero
            # cache writes -- verify lands target-weight KV at the
            # same positions (see _draft_window).
            drafts = _draft_window(draft, config, tokens, cache,
                                   lengths, active, k, spec_window,
                                   trash)                    # [B, k]
        chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
        starts = jnp.where(active, jnp.minimum(lengths, trash), trash)
        logits, cache = _chunk_verify(
            params, config, chunk, cache, starts, trash,
            use_flash=use_flash, attend=jnp.where(active, starts, 0))
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)    # [B, k+1]
        first = select_tokens(sub, logits[:, 0, :], temperatures,
                              top_k=top_k).astype(jnp.int32)
        candidates = greedy.at[:, 0].set(first)
        # Longest matching draft prefix; sampled rows accept none (the
        # per-token distribution stays exactly the non-speculative one).
        match = (chunk[:, 1:] == candidates[:, :-1]) & greedy_row[:, None]
        accept = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(1)
        offsets = jnp.arange(per_iter)[None, :]              # [1, k+1]
        budget_after = budget[:, None] - (offsets + 1)
        total_after = lengths[:, None] + offsets + 1
        stop_at = ((candidates[:, :, None] == eos[:, None, :]).any(-1)
                   | (budget_after <= 0) | (total_after >= extent))
        clean_before = jnp.cumsum(
            jnp.pad(stop_at[:, :-1], ((0, 0), (1, 0))), axis=1) == 0
        emit_at = ((offsets <= accept[:, None]) & clean_before
                   & active[:, None])
        cut = emit_at.sum(1)                                 # [B]
        slot_index = jnp.where(emit_at, counts[:, None] + offsets, ring)
        emitted = emitted.at[jnp.arange(b)[:, None],
                             slot_index].set(candidates)
        counts = counts + cut
        lengths = lengths + cut
        budget = budget - cut
        stopped = (emit_at & stop_at).any(1)
        last = jnp.take_along_axis(
            candidates, jnp.maximum(cut - 1, 0)[:, None], axis=1)[:, 0]
        tokens = jnp.where(active & (cut > 0), last, tokens)
        accepted = accepted + jnp.where(active, jnp.maximum(cut - 1, 0),
                                        0)
        drafted = drafted + jnp.where(greedy_row, k, 0)
        if speculative == "ngram":
            history = _history_push(history, candidates, cut)
        return (i + 1, tokens, cache, lengths, active & ~stopped,
                budget, key, emitted, counts, history, accepted, drafted)

    carry = (jnp.int32(0), tokens, cache, lengths, active, budget, key,
             jnp.zeros((b, ring + 1), dtype=jnp.int32),
             jnp.zeros((b,), dtype=jnp.int32), history,
             jnp.zeros((b,), dtype=jnp.int32),
             jnp.zeros((b,), dtype=jnp.int32))
    (steps, tokens, cache, lengths, active, budget, key, emitted,
     counts, history, accepted, drafted) = jax.lax.while_loop(
        cond, body_spec if spec else body_plain, carry)
    return (emitted[:, :ring], counts, tokens, lengths, active, budget,
            history, key, accepted, drafted, steps, cache)


def decode_loop(params: dict, config: LlamaConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off",
                spec_tokens: int = 4, spec_window: int = 32,
                draft: dict | None = None, top_k: int = 0):
    """Device-resident generation block (see _decode_loop_jit); the
    flash-vs-dense choice resolves here on the concrete cache's
    sharding/structure, exactly as in :func:`decode_step`.
    ``speculative: auto`` resolves in the ContinuousBatcher's startup
    probe (models/batching.py), never here."""
    if speculative not in ("off", "ngram", "draft"):
        raise ValueError(
            f"speculative={speculative!r}: one of off|ngram|draft")
    config = _matmul_safe_config(config, params)
    return _decode_loop_jit(params, draft if draft is not None else params,
                            config, tokens, cache, lengths, active,
                            budget, temperatures, eos, history, key,
                            ring=int(ring), speculative=speculative,
                            spec_tokens=int(spec_tokens),
                            spec_window=max(1, int(spec_window)),
                            top_k=int(top_k),
                            use_flash=_resolve_decode_flash(config, cache))
