"""The Command A family (``model_type: cohere2_moe``: Command A+), the
serving path: layers of TWO kinds of attention in one model -- sliding-
window layers with rotary positions, and global layers with none (NoPE)
-- in the published pattern ``layer_types`` (whole periods: ``k`` window
layers, then one global one), each a PARALLEL block of attention and
sparse experts.  A fifth family beside ``models/llama.py``,
``models/deepseek.py``, ``models/olmo_hybrid.py`` and ``models/sdar.py``;
the ContinuousBatcher serves it through the same seam
(``batching.model_family``).

Per layer, ``h`` the residual stream and ``a = LN(h)`` (mean-centred
LayerNorm in float32, a weight, no bias, eps ``layer_norm_eps``)::

    h' = h + Attn(a) + F(a)

- **attention**: ``q = a W_q`` (``H`` heads of ``head_dim``, ``H *
  head_dim`` independent of the hidden size), ``k, v = a W_k, a W_v``
  (``K`` heads), ``H / K`` query heads a key/value head, scale
  ``head_dim ** -0.5``, ``W_o``, no bias, no query/key norm.  A WINDOW
  layer rotates q and k (GPT-J's interleaved rotary over all of
  ``head_dim``, base ``rope_theta``, absolute positions) and a query at
  ``i`` sees ``i - window < j <= i``; a GLOBAL layer has no positional
  encoding and a plain causal mask.  GPT-J's pairs ``(2i, 2i + 1)`` are
  served as rotate-half's ``(i, i + head_dim / 2)``: the served
  ``W_q`` / ``W_k`` columns are the published ones in rotate-half order
  within each head (:func:`rotate_half_columns`), which leaves every
  score as it is.
- **feed-forward**: every layer is sparse.  ``p = sigmoid(a W_r)`` in
  float32 over the router's ``num_experts * expert_shards`` outputs, the
  ``n_experts_per_token`` largest chosen, gates ``p_e / sum_chosen p``
  (``norm_topk_prob``), no selection bias; ``F(a) = sum_chosen g_e
  E_e(a) + mean_s S_s(a)`` over the ``num_shared_experts`` shared
  experts (``shared_expert_combination_strategy: average``), each a
  SwiGLU of ``intermediate_size``.  A chip holds ``num_experts`` of the
  router's experts (the first of ``expert_shards`` shares): the layer
  routes over all of them and computes its own experts' part
  (``moe.routed_experts`` with ``held``), what every chip computes alike
  -- attention, router, shared experts -- whole.
- **head**: a final LayerNorm, then the TIED embedding: ``logits = LN(h)
  E^T * logit_scale``.

The cache: the global layers keep every K/V page of a slot
(``cache["k"]``, ``["v"]``, ``["page_table"]``); the window layers keep
theirs in pools of their own behind a table of their own
(``cache["window_k"]``, ``["window_v"]``, ``["window_table"]``:
``paged.init_paged_cache``), from which the batcher gives back every
page that lies wholly behind the window (``paged.PageAllocator``) -- a
slot's window pages stay bounded however long its context.  Admission's
attention is the flash kernel banded by the window
(``ops/pallas_attention.py``); decode's the paged decode kernel, whose
rows start at the window's first page (``ops/pallas_decode.py``).

Layout: one stacked tree of every layer; the periods are scanned, a
period's layers unrolled in the body and sliced out of the stack where
they are used, the experts and the pools closed over (never scan
inputs).  The jitted programs carry the names the benchmark's readers
look for (``_prefill_into_slot_jit``, ``_decode_loop_jit``).

Refused by name (:func:`check_serving`, ``models/families.py``,
``elements/llm.py``): int8, speculation, the prefix cache, a dense
cache, a multi-chip placement; admission is one slot a program (no
``prefill_into_slots``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.layers import apply_rope, attention_prefill, rope_frequencies
from ..ops.tiles import on_tpu
from .deepseek import _normal
from .families import FAMILY_WIDTHS, config_fields
from .llama import (_grouped, greedy_sample, select_tokens,
                    temperature_sample)                     # noqa: F401
from .moe import routed_experts
from .paged import gather_rows, is_paged, paged_extent, pool_page_tokens

__all__ = ["CommandAConfig", "init_params", "init_cache", "cache_array",
           "cache_extent", "check_serving", "prefill_into_slot",
           "decode_step", "decode_loop", "loop_stats", "layer_norm",
           "rotate_half_columns", "greedy_sample", "temperature_sample",
           "select_tokens"]

WIDTH_FIELDS = FAMILY_WIDTHS["cohere2_moe"]
WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class CommandAConfig:
    """Defaults are Command A+'s published ``config.json``, every
    expert held (one share of one)."""
    vocab_size: int = 262_144
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    moe_hidden_dim: int = 4096
    n_experts: int = 128                # held here
    n_experts_per_token: int = 8
    n_shared_experts: int = 4
    window: int = 4096
    # () stands for the published pattern, (window x 3, full), repeated
    layer_types: tuple = ()
    rope_theta: float = 50_000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    # The router's outputs are ``n_experts * expert_shards``; this chip
    # holds the first ``n_experts`` of them.
    expert_shards: int = 1
    max_seq: int = 8192
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    # Admission's attention: "dense" (einsums over the slot's rows) or
    # "flash" (ops/pallas_attention.py, banded by the window).
    attention: str = "dense"
    # Decode's attention over the pages: "on" (the paged decode kernel
    # of ops/pallas_decode.py, interpreted off the chip), "off"
    # (gathered rows, einsums) or "auto" (on, on the TPU).
    kernels: str = "auto"
    # The routed experts' grouped matmul (``moe.routed_experts``).
    grouped_matmul: str = "auto"
    # The batcher's model-family seam asks for it; served unquantized.
    matmul_kernel: str = "off"

    def __post_init__(self):
        if not self.layer_types:
            object.__setattr__(self, "layer_types",
                               ((WINDOW,) * 3 + (FULL,))
                               * (self.n_layers // 4))
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention must be 'dense' or 'flash', "
                             f"got {self.attention!r}")
        if self.kernels not in ("on", "off", "auto"):
            raise ValueError(f"kernels must be 'on', 'off' or 'auto', "
                             f"got {self.kernels!r}")
        if self.grouped_matmul not in ("xla", "megablox", "auto"):
            raise ValueError(
                f"grouped_matmul must be 'xla', 'megablox' or 'auto', "
                f"got {self.grouped_matmul!r}")
        if self.kv_dtype != "bfloat16":
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}: the cohere2_moe family's "
                f"K/V pages are bfloat16 only")
        if len(types) != self.n_layers or FULL not in types:
            raise ValueError(
                f"layer_types: {len(types)} entries for "
                f"num_hidden_layers={self.n_layers}; it names every "
                f"layer and at least one {FULL}")
        period = types.index(FULL) + 1
        if period < 2 or self.n_layers % period \
                or types != types[:period] * (self.n_layers // period):
            raise ValueError(
                f"layer_types must be whole periods of {WINDOW} layers "
                f"then one {FULL} layer (local_attn_first), got {types}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                "num_attention_heads must be a multiple of "
                "num_key_value_heads, head_dim even")
        if self.window < 1:
            raise ValueError(f"sliding_window={self.window}: at least 1")
        if self.expert_shards < 1:
            raise ValueError(
                f"expert_shards={self.expert_shards}: at least 1")
        if self.n_experts_per_token > self.router_experts:
            raise ValueError(
                f"n_experts_per_token ({self.n_experts_per_token}) "
                f"exceeds the router's {self.router_experts} experts")

    @property
    def period(self) -> int:
        """Layers a period: its window layers and the one global."""
        return self.layer_types.index(FULL) + 1

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def n_window_layers(self) -> int:
        """The layers whose pages lie in the window pools
        (``paged.init_paged_cache``)."""
        return self.n_periods * (self.period - 1)

    @property
    def n_paged_layers(self) -> int:
        """The layers that keep every page (the global ones)."""
        return self.n_periods

    @property
    def router_experts(self) -> int:
        """The router's outputs: every share's experts."""
        return self.n_experts * self.expert_shards

    @property
    def held(self) -> tuple:
        """(first, count) of the router's experts this chip holds."""
        return 0, self.n_experts

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "CommandAConfig":
        """The config of published ``config.json`` keys (``WIDTH_FIELDS``;
        a key the family lacks is an error, a key left out keeps Command
        A+'s value; ``layer_types`` left out is the published pattern
        over ``num_hidden_layers``)."""
        return cls(**{**fields, **config_fields("cohere2_moe", widths)})

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "CommandAConfig":
        """The CPU tests' preset: one period (S, S, S, F), a window of
        16, every mechanism at toy widths."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=4, n_heads=8,
                   n_kv_heads=2, head_dim=16, moe_hidden_dim=32,
                   n_experts=8, n_experts_per_token=3, n_shared_experts=2,
                   window=16, max_seq=max_seq)


# -- parameters --------------------------------------------------------------

#: ``W_q``'s gain over its fan-in scale under the random init
#: (:func:`init_params`).
INIT_QUERY_GAIN = 4.0


def init_params(key: jax.Array, config: CommandAConfig) -> dict:
    """Random weights in ``config.dtype``, built leaf by leaf and layer
    by layer (``deepseek._normal``): normal, fan-in scaled, the embedding
    at unit variance and every expert's ``W_down``, routed and shared,
    scaled by a further ``(2 L) ** -0.5``, so that a token's identity
    survives the stream (PERF.md section 6) -- and ATTENTION
    GIVEN WEIGHT: ``W_q`` at ``INIT_QUERY_GAIN`` times its fan-in scale
    (scores of that spread: a query weighs a handful of keys, not the
    mean of a window of them, which is the same vector in every row)
    and ``W_o`` at its plain fan-in scale, so that what a row attends
    moves its stream and rows route apart (as models/sdar.py's
    init does).  ``layers`` is one stacked tree (leading axis: the
    layer); the shared experts lie side by side, ``[L, D, S * F]`` /
    ``[L, S * F, D]``, and the held routed experts ``[L, E, ...]``.
    The drawn ``W_q`` / ``W_k`` stand for published ones already in
    rotate-half order (:func:`rotate_half_columns`: a permutation of
    random columns is random columns)."""
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 12)
    stack, residual = c.n_layers, 2 * c.n_layers
    q_width, kv_width = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    shared = c.n_shared_experts * c.moe_hidden_dim

    def dense(k, shape, fan_in):
        return _normal(k, shape=shape, fan_in=fan_in, dtype=dtype,
                       stack=stack)

    experts = (c.n_experts,)
    layers = {
        "norm": jnp.ones((stack, c.dim), dtype=dtype),
        "wq": dense(keys[1], (c.dim, q_width),
                    c.dim / INIT_QUERY_GAIN ** 2),
        "wk": dense(keys[2], (c.dim, kv_width), c.dim),
        "wv": dense(keys[3], (c.dim, kv_width), c.dim),
        "wo": dense(keys[4], (q_width, c.dim), q_width),
        "w_router": dense(keys[5], (c.dim, c.router_experts), c.dim),
        "shared": {
            "w_gate": dense(keys[6], (c.dim, shared), c.dim),
            "w_up": dense(keys[7], (c.dim, shared), c.dim),
            "w_down": dense(keys[8], (shared, c.dim),
                            c.moe_hidden_dim * residual)},
        "experts": {
            "w_gate": dense(keys[9], experts + (c.dim, c.moe_hidden_dim),
                            c.dim),
            "w_up": dense(keys[10], experts + (c.dim, c.moe_hidden_dim),
                          c.dim),
            "w_down": dense(keys[11], experts + (c.moe_hidden_dim, c.dim),
                            c.moe_hidden_dim * residual)}}
    return {"embed": _normal(keys[0], shape=(c.vocab_size, c.dim),
                             fan_in=1, dtype=dtype),
            "layers": layers,
            "final_norm": jnp.ones((c.dim,), dtype=dtype)}


def rotate_half_columns(w, n_heads: int, head_dim: int):
    """Published ``W_q`` / ``W_k`` columns ``[..., heads * head_dim]``
    (GPT-J's interleaved rotary pairs ``(2i, 2i + 1)`` in every head) ->
    the served order, in which rotate-half's pairs ``(i, i + head_dim /
    2)`` are those same pairs: a head's even columns, then its odd
    ones.  Queries and keys permuted alike give every score unchanged."""
    order = jnp.concatenate([jnp.arange(0, head_dim, 2),
                             jnp.arange(1, head_dim, 2)])
    heads = w.reshape(*w.shape[:-1], n_heads, head_dim)
    return heads[..., order].reshape(w.shape)


# -- the cache ----------------------------------------------------------------

def init_cache(config, batch, max_seq=None):
    raise ValueError(
        "kv_page_tokens=0: the cohere2_moe family's window layers give "
        "their pages back behind the window, so its cache is paged "
        "only; set kv_page_tokens > 0")


def cache_array(cache: dict):
    """The global layers' key pool ``[L_full, P, page_tokens, K * hd]``
    (its pages are the ones under pool pressure)."""
    return cache["k"]


def cache_extent(cache: dict) -> int:
    return paged_extent(cache)


def check_serving(*, speculative: str, prefix_cache: bool,
                  kv_page_tokens: int) -> None:
    """What the family does not serve, refused when the batcher is
    created, each by its parameter's name."""
    if not kv_page_tokens:
        init_cache(None, 0)
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: a rejected draft would have "
            f"to give back window pages already released, and none is "
            f"kept; the cohere2_moe family serves speculative: off")
    if prefix_cache:
        raise ValueError(
            "prefix_cache=on: the window layers hold no prefix once "
            "their pages are given back, and under grouped expert "
            "matmuls re-written shared pages are not bit-equal; the "
            "cohere2_moe family serves with prefix_cache: off")


def _matmul_safe_config(config, params):
    return config


def kernels_on(c: CommandAConfig) -> bool:
    """Whether decode runs the paged decode kernel: asked for by name
    (interpreted off the chip), or under ``auto`` on the TPU backend."""
    return c.kernels == "on" or (c.kernels == "auto" and on_tpu())


# -- layers -------------------------------------------------------------------

def layer_norm(x, weight, eps: float):
    """Mean-centred LayerNorm over the last axis in float32, a weight,
    no bias."""
    x32 = x.astype(jnp.float32)
    centred = x32 - x32.mean(-1, keepdims=True)
    scale = jax.lax.rsqrt((centred * centred).mean(-1, keepdims=True) + eps)
    return (centred * scale).astype(x.dtype) * weight


def route(c: CommandAConfig, h, w_router):
    """Router over normed activations ``h [N, D]`` -> (chosen ``[N, k]``
    of the router's experts, gates ``[N, k]`` float32): sigmoid scores
    in float32, the ``k`` largest chosen, gates their scores normalised
    over the chosen."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    picked, chosen = jax.lax.top_k(scores, c.n_experts_per_token)
    return chosen, picked / picked.sum(-1, keepdims=True)


def _swiglu(h, weights):
    return (jax.nn.silu(h @ weights["w_gate"])
            * (h @ weights["w_up"])) @ weights["w_down"]


def ffn(c: CommandAConfig, a, layer, experts, valid=None, index=None):
    """``F(a)`` over ``a [N, D]``: this chip's routed part (the held
    experts, ``experts`` one layer's ``[E, ...]`` or every layer's with
    ``index``) plus the shared experts' mean.  Returns (out ``[N, D]``,
    chosen ``[N, k]``, rows per held expert ``[E]``)."""
    chosen, gates = route(c, a, layer["w_router"])
    routed, sizes = routed_experts(
        a, chosen, gates, experts, grouped_matmul=c.grouped_matmul,
        valid=valid, index=index, held=c.held)
    shared = _swiglu(a, layer["shared"]) * (1.0 / c.n_shared_experts)
    return routed + shared, chosen, sizes


def _qkv(c: CommandAConfig, layer, a, rope_table, positions, rotary):
    """``a [B, S, D]`` at ``positions [B, S]`` -> (q ``[B, S, H, hd]``,
    k, v ``[B, S, K, hd]``), q and k rotated in a window layer."""
    b, s, _ = a.shape
    q = jax.lax.optimization_barrier(a @ layer["wq"]) \
        .reshape(b, s, c.n_heads, c.head_dim)
    k = (a @ layer["wk"]).reshape(b, s, c.n_kv_heads, c.head_dim)
    v = (a @ layer["wv"]).reshape(b, s, c.n_kv_heads, c.head_dim)
    if rotary:
        q = apply_rope(q, rope_table, positions)
        k = apply_rope(k, rope_table, positions)
    return q, k, v


def _layers(c: CommandAConfig, params, hidden, attend, valid=None):
    """Every layer over ``hidden [B, S, D]``: the periods as one scan,
    a period's layers unrolled, each sliced out of the stack where it is
    used; the experts closed over (``moe.routed_experts``).
    ``attend(layer, a, pool_index, window)`` -> (attention output ``[B,
    S, H * hd]``, this layer's new K/V rows), ``pool_index`` the layer's
    place in its pools and ``window`` whether it is a window layer.
    Returns (hidden, window K/V rows ``[L_win, ...]``, global K/V rows
    ``[L_full, ...]``, chosen ``[L, N, k]``, rows per held expert ``[L,
    E]``)."""
    experts = params["layers"]["experts"]
    stacked = {key: leaf for key, leaf in params["layers"].items()
               if key != "experts"}
    per = c.period

    def period_step(hidden, period):
        window_rows, chosen, sizes = [], [], []
        for member in range(per):
            index = period * per + member
            layer = jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(
                    leaf, index, 0, False), stacked)
            a = layer_norm(hidden, layer["norm"], c.norm_eps)
            windowed = member < per - 1
            attended, rows = attend(
                layer, a, period * (per - 1) + member if windowed
                else period, windowed)
            out, picked, held = ffn(c, a.reshape(-1, a.shape[-1]), layer,
                                    experts, valid, index)
            hidden = hidden + attended @ layer["wo"] \
                + out.reshape(hidden.shape)
            chosen.append(picked)
            sizes.append(held)
            if windowed:
                window_rows.append(rows)
        stack = partial(jax.tree_util.tree_map, lambda *xs: jnp.stack(xs))
        return hidden, (stack(*window_rows), rows, jnp.stack(chosen),
                        jnp.stack(sizes))

    hidden, (window_rows, full_rows, chosen, sizes) = jax.lax.scan(
        period_step, hidden, jnp.arange(c.n_periods))

    def flat(tree):                     # [P, per, ...] -> [P * per, ...]
        return jax.tree_util.tree_map(
            lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), tree)
    return hidden, flat(window_rows), full_rows, flat(chosen), flat(sizes)


def _head(params: dict, c: CommandAConfig, hidden):
    """The final LayerNorm and the tied embedding, float32 logits: the
    embedding at unit variance gives logits of spread ``sqrt(hidden)``,
    64 at 4,096, where bfloat16's own spacing is up to a whole unit."""
    logits = jnp.einsum("...d,vd->...v",
                        layer_norm(hidden, params["final_norm"], c.norm_eps),
                        params["embed"], preferred_element_type=jnp.float32)
    return logits if c.logit_scale == 1.0 else logits * c.logit_scale


def _pools(windowed: bool) -> tuple:
    """(key pool, value pool, table) of a layer's kind."""
    return ("window_k", "window_v", "window_table") if windowed \
        else ("k", "v", "page_table")


# -- admission ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: CommandAConfig,
                           tokens: jax.Array, cache: dict,
                           slot: jax.Array, start: jax.Array,
                           last: jax.Array):
    """One prompt chunk ``tokens [1, S]`` of ONE sequence into the pages
    of ``slot`` at offset ``start`` (page-aligned, S whole pages): per
    layer the slot's own rows of its kind are gathered through its
    table, the chunk's rows laid in, and the queries attend them under
    the causal mask, banded by the window in a window layer (whose
    pages behind the window the table no longer names: the band never
    reaches them).  The chunk's rows leave the scan as its only
    cache-related outputs and are written in place once, after it.
    Logits for position ``last`` of the chunk ALONE (``[1, 1, vocab]``),
    and the experts chosen ``[L, S, k]``."""
    c = config
    if not is_paged(cache) or "window_k" not in cache:
        raise ValueError("the cohere2_moe family admits into a paged "
                         "cache with window pools "
                         "(paged.init_paged_cache)")
    s = tokens.shape[1]
    page_tokens = pool_page_tokens(cache)
    if s % page_tokens:
        raise ValueError(
            f"paged prefill chunk of {s} tokens is not a whole number "
            f"of {page_tokens}-token pages")
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = (start + jnp.arange(s))[None, :]                # [1, S]

    def attend(layer, a, pool_index, windowed):
        q, k, v = _qkv(c, layer, a, rope_table, positions, windowed)
        new = (k.reshape(1, s, -1), v.reshape(1, s, -1))
        k_pool, v_pool, table = _pools(windowed)
        row = cache[table][slot][None]

        def laid(pool, rows):           # the slot's rows, the chunk in
            held = gather_rows(cache[pool], row, pool_index)
            return _grouped(jax.lax.dynamic_update_slice(
                held, rows, (0, start, 0)), c.n_kv_heads)
        k_rows, v_rows = laid(k_pool, new[0]), laid(v_pool, new[1])
        window = c.window if windowed else 0
        if c.attention == "flash":
            from ..ops.pallas_attention import flash_attention
            attended = flash_attention(q, k_rows, v_rows, q_offset=start,
                                       window=window)
        else:
            attended = attention_prefill(q, k_rows, v_rows, positions,
                                         window=window)
        return attended.reshape(1, s, -1), (new[0][0], new[1][0])

    hidden, window_rows, full_rows, chosen, _ = _layers(
        c, params, params["embed"][tokens], attend)

    def paged(pool, table, new):        # [L, S, C], page by page
        row = cache[table][slot]
        for j in range(s // page_tokens):
            pool = jax.lax.dynamic_update_slice(
                pool, new[:, None, j * page_tokens:(j + 1) * page_tokens],
                (0, row[start // page_tokens + j], 0, 0))
        return pool

    cache = {**cache,
             "k": paged(cache["k"], "page_table", full_rows[0]),
             "v": paged(cache["v"], "page_table", full_rows[1]),
             "window_k": paged(cache["window_k"], "window_table",
                               window_rows[0]),
             "window_v": paged(cache["window_v"], "window_table",
                               window_rows[1])}
    sampled = jax.lax.dynamic_slice_in_dim(hidden, last, 1, axis=1)
    return _head(params, c, sampled), cache, chosen


def prefill_into_slot(params: dict, config: CommandAConfig,
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

def _scatter_rows(pool, table, rows, positions):
    """One token's row per batch row (``rows [L, B, C]``) into a pool
    ``[L, P, pt, C]`` (donated: updated in place) at ``positions [B]``
    through ``table [B, pps]``: one unrolled ``dynamic_update_slice`` a
    row (a batched scatter defeats the aliasing:
    ``llama._scatter_positions``)."""
    page_tokens = pool.shape[2]
    rows = rows.astype(pool.dtype)
    for row in range(rows.shape[1]):
        position = positions[row]
        pool = jax.lax.dynamic_update_slice(
            pool, rows[:, row][:, None, None],
            (0, table[row, position // page_tokens],
             position % page_tokens, 0))
    return pool


def _decode_step_impl(params: dict, config: CommandAConfig,
                      tokens: jax.Array, cache: dict, lengths: jax.Array,
                      active: jax.Array | None = None):
    """One token per sequence: ``tokens [B]``, written at ``lengths
    [B]`` (rows that are not live carry the trash position).  Each
    layer attends the row's cached keys of its kind -- a window layer
    the last ``window - 1`` of them -- and the token's own, merged
    outside the kernel; the new rows are written through the tables
    after the scan.  ``active [B]`` marks the rows whose routing counts
    (default: those not at the trash position) and that read the cache.
    Returns (logits ``[B, vocab]``, cache, chosen ``[L, B, k]``, rows
    per held expert ``[L, E]``)."""
    c = config
    b = tokens.shape[0]
    extent = cache_extent(cache)
    if active is None:
        active = lengths != extent - 1
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = lengths[:, None]                                # [B, 1]
    stored = jnp.where(active, lengths, 0)
    kernel = kernels_on(c)
    if kernel:
        from ..ops.pallas_decode import (_split_paged,
                                         flash_decode_append_paged)
        views = {pool: _split_paged(cache[pool]) for pool in
                 ("k", "v", "window_k", "window_v")}

    def attend(layer, a, pool_index, windowed):
        q, k, v = _qkv(c, layer, a, rope_table, positions, windowed)
        k_pool, v_pool, table = _pools(windowed)
        window = c.window if windowed else 0
        if kernel:
            attended = flash_decode_append_paged(
                q, views[k_pool], views[v_pool], pool_index, k, v,
                cache[table], stored, window=window)
        else:
            def rows(pool, new):        # the cached rows, then the token
                return jnp.concatenate([_grouped(gather_rows(
                    cache[pool], cache[table], pool_index), c.n_kv_heads),
                    new], axis=1)
            held = jnp.arange(extent)[None, :]
            attended = attention_prefill(
                q, rows(k_pool, k), rows(v_pool, v), positions,
                kv_length_mask=jnp.concatenate(
                    [held < stored[:, None], jnp.ones((b, 1), bool)],
                    axis=1),
                kv_positions=jnp.concatenate(
                    [jnp.broadcast_to(held, (b, extent)), positions],
                    axis=1), window=window)
        return attended.reshape(b, 1, -1), (k.reshape(b, -1),
                                            v.reshape(b, -1))

    hidden, window_rows, full_rows, chosen, sizes = _layers(
        c, params, params["embed"][tokens][:, None, :], attend, active)
    table, window_table = cache["page_table"], cache["window_table"]
    cache = {**cache,
             "k": _scatter_rows(cache["k"], table, full_rows[0], lengths),
             "v": _scatter_rows(cache["v"], table, full_rows[1], lengths),
             "window_k": _scatter_rows(cache["window_k"], window_table,
                                       window_rows[0], lengths),
             "window_v": _scatter_rows(cache["window_v"], window_table,
                                       window_rows[1], lengths)}
    return _head(params, c, hidden)[:, 0, :], cache, chosen, sizes


_decode_step_jit = partial(jax.jit, static_argnames=("config",),
                           donate_argnames=("cache",))(_decode_step_impl)


def decode_step(params: dict, config: CommandAConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array,
                active: jax.Array | None = None,
                selections: bool = False):
    """(logits ``[B, vocab]``, cache) of one decode step -- the device
    loop's own body -- and with ``selections`` the chosen experts ``[L,
    B, k]`` too."""
    logits, cache, chosen, _ = _decode_step_jit(params, config, tokens,
                                                cache, lengths, active)
    return (logits, cache, chosen) if selections else (logits, cache)


@partial(jax.jit, static_argnames=("config", "ring", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, config: CommandAConfig,
                     tokens: jax.Array, cache: dict, lengths: jax.Array,
                     active: jax.Array, budget: jax.Array,
                     temperatures: jax.Array, eos: jax.Array,
                     history: jax.Array, key: jax.Array, *, ring: int,
                     top_k: int = 0):
    """The device-resident serving loop, this family's plain body (see
    ``llama._decode_loop_jit`` for the contract: carries, stop
    detection, the emitted ring).  Over its (layer, step) pairs the
    block also sums the held experts that got at least one live row and
    the fullest held expert's rows over the held experts' mean:
    ``stats`` rides the block's one host fetch (:func:`loop_stats`)."""
    b = tokens.shape[0]
    extent = cache_extent(cache)
    trash = extent - 1

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
        mean = jnp.maximum(sizes.sum(-1), 1) / sizes.shape[1]
        stats = {
            "touched": stats["touched"] + (sizes > 0).sum(),
            "imbalance": stats["imbalance"]
            + (sizes.max(-1) / mean).sum(),
            "pairs": stats["pairs"] + sizes.shape[0]}
        counts = counts + active
        lengths = lengths + active
        budget = budget - active
        stop = ((sampled[:, None] == eos).any(-1) | (budget <= 0)
                | (lengths >= extent)) & active
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


def decode_loop(params: dict, config: CommandAConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off", top_k: int = 0,
                **_):
    """Device-resident generation block: ``llama.decode_loop``'s
    twelve results, then ``stats`` (see :func:`loop_stats`)."""
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the cohere2_moe family "
            f"serves speculative: off")
    return _decode_loop_jit(params, config, tokens, cache, lengths,
                            active, budget, temperatures, eos, history,
                            key, ring=int(ring), top_k=int(top_k))


def loop_stats(stats: dict) -> dict:
    """A retired block's fetched ``stats`` as what the LLM element
    observes of it (``llm_moe_experts_touched``,
    ``llm_moe_load_imbalance``, as for the other routed families): the
    mean, over the block's (layer, step) pairs, of the held experts
    that got a live row and of the fullest held expert's rows over the
    held experts' mean."""
    pairs = int(stats["pairs"])
    if not pairs:
        return {}
    return {"moe_experts_touched": float(stats["touched"]) / pairs,
            "moe_load_imbalance": float(stats["imbalance"]) / pairs}
