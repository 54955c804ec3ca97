"""The DeepSeek-V3 family (``model_type: deepseek_v3``: Moonlight,
DeepSeek-V2/V3 style decoders), the serving path: latent attention
(MLA) over a paged latent cache, leading dense layers, then sparse
layers of sigmoid-routed experts beside shared experts.  A second
family beside ``models/llama.py``; the ContinuousBatcher serves either,
choosing by the type of the config it is given (``models/batching.py``).

Per layer (pre-norm residual, no biases), ``h = norm(x)``:

- **latent attention**: ``q = W_q h`` per head ``[q_nope; q_rope]``;
  ``[c; k_r] = W_kva h``; ``c~ = RMSNorm(c)`` (its own weight and
  epsilon); ``k_rope = RoPE(k_r)`` shared by all heads; ``[k_nope,i;
  v_i] = W_kvb,i c~``; scores ``(q_nope.k_nope + q_rope.k_rope) /
  sqrt(nope + rope)``, causal, float32 softmax; output ``W_o [o_i]``.
  The CACHE holds ``[c~; k_rope]`` -- ``kv_lora_rank + qk_rope_head_dim``
  values a token a layer, no heads, no k/v pair (``models/paged.py``'s
  latent pool, a page's tokens along its last axis).  **Admission
  expands** (the chunk's queries against
  per-head keys and values rebuilt from the slot's latent rows:
  ``2 T R H (nope + v)`` operations to expand, then head width 192/128
  scores and values -- about half the operations of the absorbed form
  at a 512-query chunk, which multiplies every query by the 576-wide
  row); **decode absorbs** (``q^_i = W_kvb,i^K^T q_nope,i``, scores
  against the latent rows themselves, ``o_i = W_kvb,i^V (sum_j p_j
  c~_j)``): each live latent row is read once per layer and step and
  no per-head key or value of the context is ever materialised.
- **feed-forward**: the first ``first_dense_layers`` layers a SwiGLU of
  width ``hidden_dim``; the rest ``sum_chosen g_e E_e(h) + S(h)``:
  ``sigma = sigmoid(W_r h)`` in float32, the ``n_experts_per_token``
  largest ``sigma + b`` chosen (``b`` the selection bias, selection
  only), gates ``routed_scaling_factor * sigma_e / (sum_chosen sigma +
  1e-20)``, ``S`` one SwiGLU of width ``n_shared_experts *
  moe_hidden_dim``.  Routing is DROP-LESS: token-expert pairs are
  sorted by expert and multiplied by a grouped matmul
  (``jax.lax.ragged_dot``, a Mosaic kernel on the TPU) over the experts
  that have rows -- the same function for a 512-token chunk and a
  decode step.  No capacity, no token ever dropped.

Layout: the dense layers are a stacked tree of their own, applied
outside the scan; the sparse layers one stacked tree, scanned with the
LAYER INDEX in the carry and the page pool closed over (never a scan
input: PR 27's discipline).  The jitted programs carry the names the
benchmark's readers look for (``_prefill_into_slot_jit``,
``_decode_loop_jit``).

What this family does not serve raises at create time and names the
parameter (:func:`check_serving`): an int8 cache, speculation, a dense
(non-paged) cache, the prefix cache, a multi-chip placement
(``elements/llm.py``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.layers import apply_rope, rms_norm, rope_frequencies
from ..ops.tiles import on_tpu
from .families import FAMILY_WIDTHS, config_fields
from .llama import _finish, greedy_sample, select_tokens, \
    temperature_sample                                      # noqa: F401
from .moe import routed_experts
from .paged import (gather_latent_pages, is_paged, latent_pages,
                    paged_extent, pool_page_tokens, scatter_latent_pages,
                    scatter_latent_rows)

__all__ = ["DeepseekConfig", "init_params", "init_cache", "cache_array",
           "cache_extent", "check_serving", "prefill_into_slot",
           "decode_step", "decode_loop", "loop_stats", "routed_ffn",
           "greedy_sample", "temperature_sample", "select_tokens"]

# Published ``config.json`` key -> config field (``from_widths``).
WIDTH_FIELDS = FAMILY_WIDTHS["deepseek_v3"]


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """Defaults are Moonlight-16B-A3B's published ``config.json``."""
    vocab_size: int = 163_840
    dim: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    hidden_dim: int = 11_264            # the dense layers' feed-forward
    moe_hidden_dim: int = 1408          # one expert's
    n_experts: int = 64
    n_experts_per_token: int = 6
    n_shared_experts: int = 2
    first_dense_layers: int = 1
    routed_scaling_factor: float = 2.446
    rope_theta: float = 50_000.0
    max_seq: int = 8192
    norm_eps: float = 1e-5
    # The latent norm keeps the default epsilon of the source's norm
    # class (its modelling code hands ``rms_norm_eps`` to the block
    # norms and the final norm only).
    latent_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    # Admission's attention: "dense" (einsums over the slot's whole
    # extent, the [H, S, T] scores materialised in float32) or "flash"
    # (the Pallas kernel of ops/pallas_attention.py over the expanded
    # keys and values: online softmax, key blocks past the chunk never
    # fetched).  Decode has one path (the absorbed form).
    attention: str = "dense"
    # Decode's attention over the latent pages: "dense" (the pages
    # gathered to every slot's whole extent, einsums over them),
    # "flash" (the Pallas kernel of ops/pallas_latent.py: the page
    # table walked in-kernel, live pages read once) or "auto" (the
    # kernel on the TPU backend, dense elsewhere).
    decode_attention: str = "auto"
    # The routed experts' grouped matmul: "xla" (``jax.lax.ragged_dot``),
    # "megablox" (the Pallas kernel of jax.experimental.pallas.ops.tpu.
    # megablox, tiled over a whole expert matrix: on the v5e 0.69 ms
    # where XLA's own ragged dot takes 2.98 for a 512-token chunk's
    # rows against one layer's experts, 0.55 against 1.9 for a decode
    # step's; PERF.md, PR 29) or "auto" (megablox on the TPU backend).
    grouped_matmul: str = "auto"
    # ``llama._finish`` asks for it; this family serves unquantized.
    matmul_kernel: str = "off"

    def __post_init__(self):
        if self.attention not in ("dense", "flash"):
            raise ValueError(
                f"attention must be 'dense' or 'flash', "
                f"got {self.attention!r}")
        if self.decode_attention not in ("dense", "flash", "auto"):
            raise ValueError(
                f"decode_attention must be 'dense', 'flash' or 'auto', "
                f"got {self.decode_attention!r}")
        if self.grouped_matmul not in ("xla", "megablox", "auto"):
            raise ValueError(
                f"grouped_matmul must be 'xla', 'megablox' or 'auto', "
                f"got {self.grouped_matmul!r}")
        if self.kv_dtype != "bfloat16":
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}: the latent cache of the "
                f"deepseek_v3 family is bfloat16 only")
        if not 0 <= self.first_dense_layers < self.n_layers:
            raise ValueError(
                f"first_dense_layers={self.first_dense_layers}: the "
                f"deepseek_v3 family needs at least one sparse layer "
                f"of its {self.n_layers}")
        if self.n_experts_per_token > self.n_experts:
            raise ValueError(
                f"n_experts_per_token ({self.n_experts_per_token}) "
                f"exceeds n_experts ({self.n_experts})")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def latent_width(self) -> int:
        """Values a token holds in one layer of the cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_sparse_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "DeepseekConfig":
        """The config of published ``config.json`` keys (``WIDTH_FIELDS``;
        a key the family lacks is an error, a key left out keeps
        Moonlight's value)."""
        return cls(**{**fields, **config_fields("deepseek_v3", widths)})

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "DeepseekConfig":
        """The CPU tests' preset: every mechanism, toy widths."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=3, n_heads=4,
                   kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, hidden_dim=128,
                   moe_hidden_dim=32, n_experts=8, n_experts_per_token=3,
                   n_shared_experts=2, first_dense_layers=1,
                   max_seq=max_seq)


# -- parameters --------------------------------------------------------------

@partial(jax.jit, static_argnames=("shape", "fan_in", "dtype", "stack"))
def _normal(key, *, shape, fan_in, dtype, stack=0):
    """normal(0, 1 / fan_in) of ``shape`` in ``dtype``; ``stack`` > 0
    stacks that many on a leading axis, drawn ONE AT A TIME so that the
    float32 transient is one layer's (the whole model in float32 is 22
    GB at Moonlight's widths and must never exist)."""
    def one(k):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)
    if not stack:
        return one(key)
    return jax.lax.map(one, jax.random.split(key, stack))


def _residual_fan_in(c: DeepseekConfig, fan_in: int) -> int:
    """The fan-in that scales a projection WRITING the residual stream
    (``wo``, every ``w_down``) by a further ``(2 L) ** -0.5`` (the
    GPT-2 rule): with the embedding at unit variance the stream then
    keeps each token's identity through the layers.  Under plain
    fan-in scaling the blocks' outputs (elements ~1) drowned the
    embedding (elements ~0.02), every row of a batch looked alike to
    the router, and a decode step of 22 rows touched 30 of 64 experts
    with the fullest at 7.5 times the mean (PERF.md, PR 29) -- where a
    trained router, balanced by its selection bias, spreads them."""
    return fan_in * 2 * c.n_layers


def _attention_params(key, c: DeepseekConfig, stack: int) -> dict:
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 4)
    heads = c.n_heads

    def dense(k, shape, fan_in):
        return _normal(k, shape=shape, fan_in=fan_in, dtype=dtype,
                       stack=stack)

    return {
        "attn_norm": jnp.ones((stack, c.dim), dtype=dtype),
        "wq": dense(keys[0], (c.dim, heads * c.qk_head_dim), c.dim),
        "w_kva": dense(keys[1], (c.dim, c.latent_width), c.dim),
        "latent_norm": jnp.ones((stack, c.kv_lora_rank), dtype=dtype),
        # per head [k_nope; v], as the source's kv_b_proj is viewed
        "w_kvb": dense(keys[2], (c.kv_lora_rank, heads,
                                 c.qk_nope_head_dim + c.v_head_dim),
                       c.kv_lora_rank),
        "wo": dense(keys[3], (heads * c.v_head_dim, c.dim),
                    _residual_fan_in(c, heads * c.v_head_dim)),
        "mlp_norm": jnp.ones((stack, c.dim), dtype=dtype),
    }


def _swiglu_params(key, c: DeepseekConfig, stack: int, width: int,
                   lead: tuple = ()) -> dict:
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 3)
    return {
        "w_gate": _normal(keys[0], shape=lead + (c.dim, width),
                          fan_in=c.dim, dtype=dtype, stack=stack),
        "w_up": _normal(keys[1], shape=lead + (c.dim, width),
                        fan_in=c.dim, dtype=dtype, stack=stack),
        "w_down": _normal(keys[2], shape=lead + (width, c.dim),
                          fan_in=_residual_fan_in(c, width), dtype=dtype,
                          stack=stack)}


def init_params(key: jax.Array, config: DeepseekConfig) -> dict:
    """Random weights in ``config.dtype``, built leaf by leaf and layer
    by layer: normal, fan-in scaled, the embedding at unit variance and
    the projections that write the residual stream scaled down by the
    depth (:func:`_residual_fan_in`), so that rows route apart.  ``dense`` and ``sparse`` are stacked trees (leading axis:
    the layer); the router's selection bias ``router_bias`` is float32,
    small and non-zero, so that selection (``sigma + b``) and gate
    (``sigma``) really differ."""
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 10)
    sparse = c.n_sparse_layers
    params = {
        "embed": _normal(keys[0], shape=(c.vocab_size, c.dim),
                         fan_in=1, dtype=dtype),
        "sparse": {
            **_attention_params(keys[3], c, sparse),
            "w_router": _normal(keys[4], shape=(c.dim, c.n_experts),
                                fan_in=c.dim, dtype=dtype, stack=sparse),
            "router_bias": 0.05 * jax.random.normal(
                keys[5], (sparse, c.n_experts), dtype=jnp.float32),
            "experts": _swiglu_params(keys[6], c, sparse,
                                      c.moe_hidden_dim, (c.n_experts,)),
            "shared": _swiglu_params(
                keys[7], c, sparse,
                c.n_shared_experts * c.moe_hidden_dim)},
        "final_norm": jnp.ones((c.dim,), dtype=dtype),
        "unembed": _normal(keys[8], shape=(c.dim, c.vocab_size),
                           fan_in=c.dim, dtype=dtype),
    }
    if c.first_dense_layers:
        params["dense"] = {
            **_attention_params(keys[1], c, c.first_dense_layers),
            **_swiglu_params(keys[2], c, c.first_dense_layers,
                             c.hidden_dim)}
    return params


# -- the cache ----------------------------------------------------------------

def init_cache(config, batch, max_seq=None):
    raise ValueError(
        "kv_page_tokens=0: the deepseek_v3 family's latent cache is "
        "paged only; set kv_page_tokens > 0")


def cache_array(cache: dict):
    """The latent pool ``[L, P, latent_width, page_tokens]``."""
    return cache["latent"]


def cache_extent(cache: dict) -> int:
    return paged_extent(cache)


def check_serving(*, speculative: str, prefix_cache: bool,
                  kv_page_tokens: int) -> None:
    """What the latent family does not serve, refused when the batcher
    is created, each by its parameter's name."""
    if not kv_page_tokens:
        init_cache(None, 0)
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the deepseek_v3 family has "
            f"no draft or chunk-verify body over a latent cache; use "
            f"speculative: off")
    if prefix_cache:
        raise ValueError(
            "prefix_cache=on: a clamped admission chunk re-writes "
            "shared pages, and under grouped expert matmuls the "
            "re-written rows are not bit-equal; the deepseek_v3 family "
            "serves with prefix_cache: off")


def _matmul_safe_config(config, params):
    return config


# -- layers -------------------------------------------------------------------

def _swiglu(h, weights):
    return (jax.nn.silu(h @ weights["w_gate"])
            * (h @ weights["w_up"])) @ weights["w_down"]


def route(c: DeepseekConfig, h, w_router, router_bias):
    """Router over normed activations ``h [N, D]`` -> (chosen ``[N, k]``
    expert ids, gates ``[N, k]`` float32).  float32 throughout, as the
    source: scores ``sigmoid(W_r h)``; the ``k`` largest ``score +
    bias`` chosen; gates from the SCORES, normalised over the chosen
    and scaled by ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + router_bias, c.n_experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * c.routed_scaling_factor
    return chosen, gates


def routed_ffn(c: DeepseekConfig, h, layer, valid=None, stack=None):
    """The routed experts' part of a sparse layer over ``h [N, D]``:
    this family's :func:`route`, then every token-expert pair computed,
    none dropped (``moe.routed_experts``: the sort, the grouped matmuls
    over the experts' stacked weights, the unsort and the gate-weighted
    sum, shared with models/sdar.py).  ``valid [N]`` marks the rows
    that count (a decode step's live rows): the others touch no expert
    and give nought.  ``stack = (experts, index)`` hands over EVERY
    sparse layer's experts ``[Ls, E, ...]`` and this layer's (traced)
    index instead of ``layer["experts"]``, so that no layer's 1.1 GB
    of experts is sliced out of the stack in front of the kernel.

    Returns (out ``[N, D]``, chosen ``[N, k]``, rows per expert ``[E]``).
    """
    chosen, gates = route(c, h, layer["w_router"], layer["router_bias"])
    experts, index = (layer["experts"], None) if stack is None else stack
    out, sizes = routed_experts(h, chosen, gates, experts,
                                grouped_matmul=c.grouped_matmul,
                                valid=valid, index=index)
    return out, chosen, sizes


def _sparse_ffn(c: DeepseekConfig, h, layer, valid=None, stack=None):
    """``sum_chosen g_e E_e(h) + S(h)`` over ``h [..., D]``."""
    flat = h.reshape(-1, h.shape[-1])
    routed, chosen, sizes = routed_ffn(c, flat, layer, valid, stack)
    out = routed + _swiglu(flat, layer["shared"])
    return out.reshape(h.shape), chosen, sizes


def _latent_entry(c: DeepseekConfig, layer, h, rope_table, positions):
    """Queries and the cache rows of ``h [B, S, D]`` at ``positions
    [B, S]``: (q_nope ``[B, S, H, nope]``, q_rope ``[B, S, H, rope]``
    rotated, entry ``[B, S, latent_width]`` = ``[c~; k_rope]``)."""
    b, s, _ = h.shape
    q = (h @ layer["wq"]).reshape(b, s, c.n_heads, c.qk_head_dim)
    q_nope = q[..., :c.qk_nope_head_dim]
    q_rope = apply_rope(q[..., c.qk_nope_head_dim:], rope_table, positions)
    compressed = h @ layer["w_kva"]                     # [B, S, R + rope]
    latent = rms_norm(compressed[..., :c.kv_lora_rank],
                      layer["latent_norm"], c.latent_norm_eps)
    k_rope = apply_rope(compressed[..., None, c.kv_lora_rank:],
                        rope_table, positions)[:, :, 0, :]
    return q_nope, q_rope, jnp.concatenate([latent, k_rope], axis=-1)


def _attend_expanded(c: DeepseekConfig, layer, q_nope, q_rope, pages,
                     positions):
    """Admission's attention: ``pages [B, pps, latent_width, pt]`` (the
    slot's latent pages, the chunk laid in) expanded to per-head keys
    and values; queries at ``positions [B, S]`` see keys at or before
    them.  Returns ``[B, S, H * v_head_dim]``."""
    b, pps, _, pt = pages.shape
    latent, k_rope = pages[:, :, :c.kv_lora_rank], \
        pages[:, :, c.kv_lora_rank:]
    expanded = jnp.einsum("bprt,rhn->bpthn", latent, layer["w_kvb"]) \
        .reshape(b, pps * pt, c.n_heads, -1)
    k_nope = expanded[..., :c.qk_nope_head_dim]
    values = expanded[..., c.qk_nope_head_dim:]
    if c.attention == "flash":
        # One head width for the kernel: keys [k_nope; k_rope] (the one
        # rotated key repeated per head), values padded to it with
        # noughts, the output's padding dropped.  Its scale is the
        # query width ** -0.5 = (nope + rope) ** -0.5; causality from
        # the chunk's offset masks the chunk's own future and the
        # unwritten tail alike (one sequence: B = 1).
        from ..ops.pallas_attention import flash_attention
        shared = jnp.swapaxes(k_rope, 2, 3).reshape(b, pps * pt, 1, -1)
        keys = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                shared, k_nope.shape[:3] + shared.shape[3:])], axis=-1)
        pad = c.qk_head_dim - c.v_head_dim
        attended = flash_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), keys,
            jnp.pad(values, ((0, 0),) * 3 + ((0, pad),)),
            q_offset=positions[0, 0])[..., :c.v_head_dim]
        return attended.reshape(*attended.shape[:2], -1)
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshn,bpnt->bhspt", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
              .reshape(b, c.n_heads, -1, pps * pt)) \
        * (c.qk_head_dim ** -0.5)
    seen = jnp.arange(pps * pt)[None, None, :] \
        <= positions[:, :, None]                        # [B, S, T]
    weights = jax.nn.softmax(
        jnp.where(seen[:, None], scores, -1e30), axis=-1)
    attended = jnp.einsum("bhst,bthn->bshn", weights.astype(values.dtype),
                          values)
    return attended.reshape(*attended.shape[:2], -1)


def _absorbed_query(c: DeepseekConfig, layer, q_nope, q_rope):
    """``[W_kvb^K^T q_nope; q_rope]``: the query against a latent row,
    ``q_nope/q_rope [B, H, .]`` -> ``[B, H, latent_width]``."""
    w_k = layer["w_kvb"][..., :c.qk_nope_head_dim]          # [R, H, nope]
    return jnp.concatenate(
        [jnp.einsum("bhn,rhn->bhr", q_nope, w_k), q_rope], axis=-1)


def _cached_dense(query, pages, lengths, scale):
    """The reference of ``ops.pallas_latent``: partial softmax
    statistics of ``query [B, H, W]`` over gathered pages ``[B, pps, W,
    pt]`` of which the first ``lengths [B]`` tokens are live ->
    (acc ``[B, H, W]`` float32 unnormalised, m ``[B, H]``, l ``[B,
    H]``).  The page axis is a BATCH axis of both products: a page is
    then a plain [width, tokens] operand as it lies in the pool, where
    a product over rows [B, T, width] had the gathered pages transposed
    and their latent part copied out, every layer and step."""
    _, pps, _, pt = pages.shape
    paged_query = jnp.broadcast_to(query[:, None],
                                   (query.shape[0], pps) + query.shape[1:])
    scores = jnp.einsum("bphc,bpct->bpht", paged_query, pages,
                        preferred_element_type=jnp.float32) * scale
    live = (jnp.arange(pps * pt).reshape(pps, pt)[None, :, None]
            < lengths[:, None, None, None])
    scores = jnp.where(live, scores, -1e30)
    peak = scores.max((1, 3))                                # [B, H]
    weights = jnp.where(live, jnp.exp(scores - peak[:, None, :, None]),
                        0.0)
    acc = jnp.einsum("bpht,bpct->bphc", weights.astype(pages.dtype),
                     pages, preferred_element_type=jnp.float32).sum(1)
    return acc, peak, weights.sum((1, 3))


def _attend_absorbed(c: DeepseekConfig, layer, q_nope, q_rope, entry,
                     cached):
    """Decode's attention over latent pages: ``q_nope/q_rope [B, H, .]``
    of the current token and its own cache row ``entry [B,
    latent_width]`` (not yet written).  The key projection is absorbed
    into the query and the value projection applied after the weighted
    sum of latent rows, so the context is read as it lies in the cache:
    ``cached(query)`` -> the partial statistics (acc, m, l) over the
    live cached rows (the Pallas kernel walking the page table, or
    :func:`_cached_dense` over gathered pages); the token's own row is
    merged here.  Returns ``[B, H * v_head_dim]``."""
    query = _absorbed_query(c, layer, q_nope, q_rope)
    acc, m, l = cached(query)
    own = jnp.einsum("bhc,bc->bh", query, entry,
                     preferred_element_type=jnp.float32) \
        * (c.qk_head_dim ** -0.5)
    peak = jnp.maximum(m, own)
    kept = jnp.exp(m - peak)                 # (m = -1e30: nothing cached)
    own_e = jnp.exp(own - peak)
    rank = c.kv_lora_rank    # (the rope lanes of the sum are dropped)
    context = (acc[..., :rank] * kept[..., None]
               + own_e[..., None] * entry[:, None, :rank]
               .astype(jnp.float32)) / (l * kept + own_e)[..., None]
    w_v = layer["w_kvb"][..., c.qk_nope_head_dim:]          # [R, H, v]
    attended = jnp.einsum("bhr,rhv->bhv", context.astype(entry.dtype),
                          w_v)
    return attended.reshape(attended.shape[0], -1)


def decode_kernel_on(c: DeepseekConfig, page_tokens: int) -> bool:
    """Whether decode attention runs the Pallas kernel
    (``ops/pallas_latent.py``): asked for by name (``flash``:
    interpreted off the chip), or under ``auto`` on the TPU backend
    with lane-aligned pages."""
    if c.decode_attention == "flash":
        return True
    return c.decode_attention == "auto" and on_tpu() \
        and page_tokens % 128 == 0


def _layers(c: DeepseekConfig, params, hidden, attend, valid=None):
    """Every layer over ``hidden``: the dense layers one by one, then
    the sparse stack as one scan.  ``attend(layer, h, index)`` ->
    (attention output, this layer's new cache rows).  Returns (hidden,
    entries ``[L, ...]``, chosen experts ``[Ls, N, k]``, rows per
    expert ``[Ls, E]``)."""
    def block(hidden, layer, index, ffn):
        attended, entry = attend(
            layer, rms_norm(hidden, layer["attn_norm"], c.norm_eps), index)
        hidden = hidden + attended @ layer["wo"]
        return hidden, entry, ffn(
            rms_norm(hidden, layer["mlp_norm"], c.norm_eps), layer)

    entries = []
    for index in range(c.first_dense_layers):
        layer = jax.tree_util.tree_map(lambda leaf: leaf[index],
                                       params["dense"])
        hidden, entry, out = block(hidden, layer, index, _swiglu)
        hidden = hidden + out
        entries.append(entry)

    # The experts stay out of the scan's inputs (see routed_ffn).
    experts = params["sparse"]["experts"]
    scanned = {key: leaf for key, leaf in params["sparse"].items()
               if key != "experts"}

    def sparse_step(hidden, xs):
        layer, index = xs
        stack = (experts, index - c.first_dense_layers)
        hidden, entry, (out, chosen, sizes) = block(
            hidden, layer, index,
            lambda h, layer: _sparse_ffn(c, h, layer, valid, stack))
        return hidden + out, (entry, chosen, sizes)

    hidden, (sparse_entries, chosen, sizes) = jax.lax.scan(
        sparse_step, hidden,
        (scanned, jnp.arange(c.first_dense_layers, c.n_layers)))
    if entries:
        sparse_entries = jnp.concatenate(
            [jnp.stack(entries), sparse_entries])
    return hidden, sparse_entries, chosen, sizes


# -- admission ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: DeepseekConfig,
                           tokens: jax.Array, cache: dict,
                           slot: jax.Array, start: jax.Array,
                           last: jax.Array):
    """One prompt chunk ``tokens [1, S]`` of ONE sequence into the
    latent pages of ``slot`` at offset ``start`` (page-aligned, S whole
    pages).  Per layer the slot's own rows are gathered through its
    page table, the chunk's rows laid in, and the queries attend the
    expanded view; the chunk's rows leave the layer loop as its only
    cache-related output and are written in place once, after it
    (``paged.scatter_pages``).  Logits are computed for position
    ``last`` of the chunk ALONE (``[1, 1, vocab]``): the batcher samples
    one position, and the head is 163,840 wide.  Also returns the
    experts chosen, ``[Ls, S, k]`` (the reference check reads them)."""
    c = config
    if not is_paged(cache) or "latent" not in cache:
        raise ValueError("the deepseek_v3 family admits into a latent "
                         "paged cache (paged.init_paged_cache)")
    s = tokens.shape[1]
    page_tokens = pool_page_tokens(cache)
    if s % page_tokens:
        raise ValueError(
            f"paged prefill chunk of {s} tokens is not a whole number "
            f"of {page_tokens}-token pages")
    rope_table = rope_frequencies(c.qk_rope_head_dim, c.max_seq,
                                  c.rope_theta)
    slots, starts = jnp.reshape(slot, (1,)), jnp.reshape(start, (1,))
    positions = starts[:, None] + jnp.arange(s)[None, :]        # [1, S]
    table_row = cache["page_table"][slots]                      # [1, pps]

    def attend(layer, h, index):
        q_nope, q_rope, entry = _latent_entry(c, layer, h, rope_table,
                                              positions)
        entry = latent_pages(entry, page_tokens)        # [1, S/pt, W, pt]
        pages = jax.lax.dynamic_update_slice(
            gather_latent_pages(cache["latent"], table_row, index),
            entry, (0, starts[0] // page_tokens, 0, 0))
        return _attend_expanded(c, layer, q_nope, q_rope, pages,
                                positions), entry

    hidden, entries, chosen, _ = _layers(c, params,
                                         params["embed"][tokens], attend)
    sampled = jax.lax.dynamic_slice_in_dim(hidden, last, 1, axis=1)
    pool = scatter_latent_pages(cache["latent"], entries,
                                cache["page_table"], slots, starts)
    return _finish(params, c, sampled), {**cache, "latent": pool}, chosen


def prefill_into_slot(params: dict, config: DeepseekConfig,
                      tokens: jax.Array, cache: dict, slot: jax.Array,
                      start: jax.Array, last: jax.Array,
                      selections: bool = False):
    """Single-slot admission (see :func:`_prefill_into_slot_jit`):
    (logits ``[1, 1, vocab]`` at chunk position ``last``, cache), and
    with ``selections`` the chosen experts too -- the same program
    either way."""
    logits, cache, chosen = _prefill_into_slot_jit(
        params, config, tokens, cache, slot, start, last)
    return (logits, cache, chosen) if selections else (logits, cache)


# The batcher hands ``last`` (the chunk's last real position) to a
# family that computes the sampled position's logits alone.
ADMISSION_LOGITS_AT_LAST = True


# -- decode -------------------------------------------------------------------

def _decode_step_impl(params: dict, config: DeepseekConfig,
                      tokens: jax.Array, cache: dict, lengths: jax.Array,
                      active: jax.Array | None = None):
    """One token per sequence: ``tokens [B]``, written at ``lengths
    [B]`` (rows that are not live carry the trash position).  The
    absorbed form over the latent pages; each new cache row is written
    through the page table after the layer loop.  ``active [B]`` marks
    the rows whose expert routing counts.  Returns (logits ``[B,
    vocab]``, cache, chosen ``[Ls, B, k]``, rows per expert ``[Ls,
    E]``)."""
    c = config
    rope_table = rope_frequencies(c.qk_rope_head_dim, c.max_seq,
                                  c.rope_theta)
    positions = lengths[:, None]                                # [B, 1]
    table = cache["page_table"]
    kernel = decode_kernel_on(c, pool_page_tokens(cache))
    scale = c.qk_head_dim ** -0.5
    # A row that is not live reads nothing of the cache.
    live = lengths if active is None else jnp.where(active, lengths, 0)

    def attend(layer, h, index):
        q_nope, q_rope, entry = _latent_entry(c, layer, h, rope_table,
                                              positions)
        entry = entry[:, 0]

        def cached(query):
            if kernel:
                from ..ops.pallas_latent import \
                    latent_decode_attention_paged
                return latent_decode_attention_paged(
                    query, cache["latent"], index, table, live,
                    scale=scale)
            return _cached_dense(
                query, gather_latent_pages(cache["latent"], table, index),
                live, scale)
        attended = _attend_absorbed(c, layer, q_nope[:, 0], q_rope[:, 0],
                                    entry, cached)
        return attended[:, None, :], entry

    hidden, entries, chosen, sizes = _layers(
        c, params, params["embed"][tokens][:, None, :], attend, active)
    pool = scatter_latent_rows(cache["latent"], entries, table, lengths)
    return _finish(params, c, hidden)[:, 0, :], \
        {**cache, "latent": pool}, chosen, sizes


_decode_step_jit = partial(jax.jit, static_argnames=("config",),
                           donate_argnames=("cache",))(_decode_step_impl)


def decode_step(params: dict, config: DeepseekConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array,
                selections: bool = False):
    """(logits ``[B, vocab]``, cache) of one decode step; with
    ``selections`` the chosen experts ``[Ls, B, k]`` too."""
    logits, cache, chosen, _ = _decode_step_jit(params, config, tokens,
                                                cache, lengths)
    return (logits, cache, chosen) if selections else (logits, cache)


@partial(jax.jit, static_argnames=("config", "ring", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, config: DeepseekConfig,
                     tokens: jax.Array, cache: dict, lengths: jax.Array,
                     active: jax.Array, budget: jax.Array,
                     temperatures: jax.Array, eos: jax.Array,
                     history: jax.Array, key: jax.Array, *, ring: int,
                     top_k: int = 0):
    """The device-resident serving loop, this family's plain body (see
    ``llama._decode_loop_jit`` for the contract: carries, stop
    detection, the emitted ring).  Over its (sparse layer, step) pairs
    the block also sums the experts that got at least one live row and
    the fullest expert's rows over the mean: ``stats`` rides the
    block's one host fetch."""
    b = tokens.shape[0]
    extent = cache_extent(cache)
    trash = extent - 1
    per_row = config.n_experts_per_token

    def cond(carry):
        i, _, _, _, active, _, _, _, counts, _ = carry
        room = jnp.where(active, counts, 0).max() + 1 <= ring
        return (i < ring) & active.any() & room

    def body(carry):
        (i, tokens, cache, lengths, active, budget, key, emitted, counts,
         stats) = carry
        positions = jnp.where(active, jnp.minimum(lengths, trash), trash)
        logits, cache, _, sizes = _decode_step_impl(
            params, config, tokens, cache, positions, active)
        key, sub = jax.random.split(key)
        sampled = select_tokens(sub, logits, temperatures,
                                top_k=top_k).astype(jnp.int32)
        slot_index = jnp.where(active, counts, ring)     # ring = trash col
        emitted = emitted.at[jnp.arange(b), slot_index].set(sampled)
        counts = counts + active
        lengths = lengths + active
        budget = budget - active
        stop = ((sampled[:, None] == eos).any(-1) | (budget <= 0)
                | (lengths >= extent)) & active
        mean = jnp.maximum(active.sum() * per_row, 1) / sizes.shape[1]
        stats = {
            "touched": stats["touched"] + (sizes > 0).sum(),
            "imbalance": stats["imbalance"]
            + (sizes.max(-1) / mean).sum(),
            "pairs": stats["pairs"] + sizes.shape[0]}
        tokens = jnp.where(active, sampled, tokens)
        return (i + 1, tokens, cache, lengths, active & ~stop, budget,
                key, emitted, counts, stats)

    stats = {"touched": jnp.int32(0), "imbalance": jnp.float32(0.0),
             "pairs": jnp.int32(0)}
    carry = (jnp.int32(0), tokens, cache, lengths, active, budget, key,
             jnp.zeros((b, ring + 1), dtype=jnp.int32),
             jnp.zeros((b,), dtype=jnp.int32), stats)
    (steps, tokens, cache, lengths, active, budget, key, emitted, counts,
     stats) = jax.lax.while_loop(cond, body, carry)
    none = jnp.zeros((b,), dtype=jnp.int32)
    return (emitted[:, :ring], counts, tokens, lengths, active, budget,
            history, key, none, none, steps, cache, stats)


def decode_loop(params: dict, config: DeepseekConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off", top_k: int = 0,
                **_):
    """Device-resident generation block: ``llama.decode_loop``'s
    twelve results, then ``stats`` (see :func:`loop_stats`)."""
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the deepseek_v3 family "
            f"serves speculative: off")
    return _decode_loop_jit(params, config, tokens, cache, lengths,
                            active, budget, temperatures, eos, history,
                            key, ring=int(ring), top_k=int(top_k))


def loop_stats(stats: dict) -> dict:
    """A retired block's fetched ``stats`` as what the LLM element
    observes of it (``llm_moe_experts_touched``,
    ``llm_moe_load_imbalance``): the mean, over the block's (sparse
    layer, step) pairs, of the experts that got a live row and of the
    fullest expert's rows over the mean."""
    pairs = int(stats["pairs"])
    if not pairs:
        return {}
    return {"moe_experts_touched": float(stats["touched"]) / pairs,
            "moe_load_imbalance": float(stats["imbalance"]) / pairs}
