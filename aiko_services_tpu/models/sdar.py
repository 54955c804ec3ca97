"""The SDAR family (``model_type: sdar_moe``: SDAR-30B-A3B-Chat, a
Qwen3-MoE body that generates by DIFFUSION OVER BLOCKS), the serving
path.  A fourth family beside ``models/llama.py``, ``models/deepseek.py``
and ``models/olmo_hybrid.py``; the ContinuousBatcher serves it through
the same seam (``batching.model_family``), over the K/V page pools of
``models/paged.py`` as they are.

Per layer (pre-norm residual, no biases), ``a = RMSNorm(x)``:

- **attention**: ``q, k, v = a W_q, a W_k, a W_v`` (``H`` query heads
  over ``K`` key/value heads of ``head_dim``); RMSNorm over ``head_dim``
  of every query and key head (the Qwen3 body's query/key norm:
  assumed, no published key says it); rotate-half rotary at absolute
  positions; softmax at scale ``head_dim ** -0.5`` under a
  BLOCK-CAUSAL mask of block length ``B``: position ``i`` sees ``j``
  iff ``j // B <= i // B`` -- whole earlier blocks, and its own block
  in both directions; ``W_o``.
- **feed-forward**: every layer is sparse.  ``p = softmax(b W_r)`` in
  float32, the ``n_experts_per_token`` largest chosen, gates ``p_e /
  sum_chosen p`` (``norm_topk_prob``), no bias, no scaling, no shared
  expert; ``y = h + sum_chosen g_e E_e(b)``, each expert a SwiGLU.
  Drop-less: the sort, grouped matmuls, unsort and gate-weighted sum
  are ``moe.routed_experts``, shared with models/deepseek.py.

**Generation** (the SDAR repository's ``block_diffusion_generate``,
static low-confidence rule; the choices the published config leaves
open are the configuration's ``assumed``).  Logits at a position
predict THAT position's token (no shift).  The prompt's ``P // B``
whole blocks are prefilled under the mask and their K/V stored; the
``P mod B`` tokens left over open the first generated block as decided
positions.  A block starts ``[decided..., MASK...]``.  A **denoising
pass** runs the model over the block's ``B`` positions against the
stored K/V and the block's own keys, takes ``x0`` (argmax or a sample;
the mask token's logit is excluded, so a decided position is never a
mask) and its probability ``c`` at every still-masked position, and
decides the ``n_t`` masked positions of highest ``c`` (``n_t = B // T``
for ``T`` denoising steps, the remainder to the first passes; never
more than are masked); its K/V is NOT stored.  Once no mask is left a **commit pass** runs the model once more over
the decided block and stores its K/V; the block's tokens are emitted
and the next block starts all-masked.  A block costs up to ``T + 1``
passes and yields up to ``B`` tokens.  (The repository's dynamic rule
-- decide every position whose ``c`` passes a threshold -- is not
served: random weights never pass one, and nothing at the door could
set it.)

**The device loop** (:func:`decode_loop`): its body is ONE pass for
every live row whatever its phase, at fixed shapes ``[slots, B]``.  A
row that denoises writes its K/V to the trash block (the slot's last
``B`` positions, which no sequence reaches); a row that commits writes
it in place, emits into the ring and re-masks.  What a row carries
between passes and blocks -- its block, how many of its leading
positions are prompt and the passes done -- rides the
chained carry the Llama family calls ``history`` (:func:`carry_width`,
:func:`joiner_carry`).  Admission yields no token: a joiner enters with
a block, and its first token arrives when that block commits.

Refused by name (:func:`check_serving`, ``models/families.py``,
``elements/llm.py``): int8, speculation, the prefix cache, a dense
cache, the per-token tick, a multi-chip placement; admission is one
slot a program.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.layers import (apply_rope, attention_prefill, rms_norm,
                          rope_frequencies)
from ..ops.tiles import on_tpu
from .deepseek import _normal
from .families import FAMILY_WIDTHS, config_fields
from .llama import (_finish, _grouped, greedy_sample, select_tokens,
                    temperature_sample)                     # noqa: F401
from .moe import routed_experts
from .paged import gather_rows, is_paged, paged_extent, pool_page_tokens

__all__ = ["SdarConfig", "init_params", "init_cache", "cache_array",
           "cache_extent", "check_serving", "prefill_into_slot",
           "decode_step", "decode_loop", "decide", "loop_stats",
           "carry_width", "admitted_length", "joiner_carry",
           "greedy_sample",
           "temperature_sample", "select_tokens"]

WIDTH_FIELDS = FAMILY_WIDTHS["sdar_moe"]

#: ``<|MASK|>`` of the SDAR tokenizer (assumed: the repository's
#: generation default, no ``config.json`` key says it).
MASK_TOKEN = 151_669

#: The batcher reads this: admission yields no token, a joiner enters
#: with a block (:func:`joiner_carry`), a pass emits several tokens a
#: row or none, and K/V enters the cache a whole block at a time.
BLOCK_DIFFUSION = True


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """Defaults are SDAR-30B-A3B-Chat's published ``config.json``."""
    vocab_size: int = 151_936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_hidden_dim: int = 768
    n_experts: int = 128
    n_experts_per_token: int = 8
    rope_theta: float = 1_000_000.0
    max_seq: int = 32_768
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    # Generation by diffusion over blocks: the block length ``B``, the
    # denoising passes a block ``T`` (1..B; 0: ``B``, a position a
    # pass) and the mask token (-1: the family's, ``MASK_TOKEN``, or
    # the last id of a smaller vocabulary).
    block_length: int = 4
    denoising_steps: int = 0
    mask_token: int = -1
    # Admission's attention: "dense" (einsums over the slot's rows) or
    # "flash" (ops/pallas_attention.py, its frontier block-causal).
    attention: str = "dense"
    # The pass's attention over the K/V pages: "on" (the paged decode
    # kernel of ops/pallas_decode.py through ``flash_verify_append``,
    # the block's own part all-visible; interpreted off the chip),
    # "off" (gathered rows, einsums) or "auto" (on, on the TPU).
    kernels: str = "auto"
    # The routed experts' grouped matmul (``moe.routed_experts``).
    grouped_matmul: str = "auto"
    # ``llama._finish`` asks for it; this family serves unquantized.
    matmul_kernel: str = "off"

    def __post_init__(self):
        if self.mask_token < 0:
            object.__setattr__(
                self, "mask_token",
                MASK_TOKEN if self.vocab_size > MASK_TOKEN
                else self.vocab_size - 1)
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
                f"kv_dtype={self.kv_dtype!r}: the sdar_moe family's K/V "
                f"pages are bfloat16 only")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                "num_attention_heads must be a multiple of "
                "num_key_value_heads, head_dim even")
        if self.n_experts_per_token > self.n_experts:
            raise ValueError(
                f"n_experts_per_token ({self.n_experts_per_token}) "
                f"exceeds n_experts ({self.n_experts})")
        if self.block_length < 1:
            raise ValueError(
                f"block_length={self.block_length}: at least 1")
        if not self.denoising_steps:
            object.__setattr__(self, "denoising_steps", self.block_length)
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps={self.denoising_steps}: 1.."
                f"block_length ({self.block_length})")
        if not 0 <= self.mask_token < self.vocab_size:
            raise ValueError(
                f"mask_token={self.mask_token}: an id of the "
                f"{self.vocab_size}-row vocabulary")

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "SdarConfig":
        """The config of published ``config.json`` keys (``WIDTH_FIELDS``;
        a key the family lacks is an error, a key left out keeps
        SDAR-30B-A3B-Chat's value)."""
        return cls(**{**fields, **config_fields("sdar_moe", widths)})

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "SdarConfig":
        """The CPU tests' preset: every mechanism, toy widths."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=3, n_heads=4,
                   n_kv_heads=2, head_dim=16, moe_hidden_dim=32,
                   n_experts=8, n_experts_per_token=3, max_seq=max_seq)


# -- parameters --------------------------------------------------------------

#: The query norm's weight under the random init (:func:`init_params`).
INIT_QUERY_GAIN = 4.0


def init_params(key: jax.Array, config: SdarConfig) -> dict:
    """Random weights in ``config.dtype``, built leaf by leaf and layer
    by layer (``deepseek._normal``): normal, fan-in scaled, the
    embedding at unit variance and every expert's ``w_down`` scaled by
    a further ``(2 L) ** -0.5`` (PERF.md, PR 29) -- and ATTENTION GIVEN
    WEIGHT: the query norm's weight is ``INIT_QUERY_GAIN`` (scores of
    that spread: a query weighs a handful of keys, not the mean of all
    of them, which is the same vector in every row) and ``wo`` keeps
    its plain fan-in scale.  A block's masked positions all hold the
    SAME token: under an init where the token dominates the stream
    every one of them, in every row, looks alike to the router, a pass
    of ~112 positions touches ~30 of 128 experts and streams a quarter
    of what a trained model's would; at 4 it touches ~118 and the
    bfloat16 reading of the reference check stays an eighth of its fp8
    control's, at 8 it touches ~124 and the check can no longer tell
    the two apart (PERF.md section 6, PR 36: the sweep on the chip).
    ``layers`` is one stacked tree (leading axis: the layer)."""
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 9)
    stack, residual = c.n_layers, 2 * c.n_layers
    q_width, kv_width = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim

    def dense(k, shape, fan_in):
        return _normal(k, shape=shape, fan_in=fan_in, dtype=dtype,
                       stack=stack)

    experts = (c.n_experts,)
    layers = {
        "attn_norm": jnp.ones((stack, c.dim), dtype=dtype),
        "wq": dense(keys[1], (c.dim, q_width), c.dim),
        "wk": dense(keys[2], (c.dim, kv_width), c.dim),
        "wv": dense(keys[3], (c.dim, kv_width), c.dim),
        "q_norm": jnp.full((stack, c.head_dim), INIT_QUERY_GAIN,
                           dtype=dtype),
        "k_norm": jnp.ones((stack, c.head_dim), dtype=dtype),
        "wo": dense(keys[4], (q_width, c.dim), q_width),
        "mlp_norm": jnp.ones((stack, c.dim), dtype=dtype),
        "w_router": dense(keys[5], (c.dim, c.n_experts), c.dim),
        "experts": {
            "w_gate": dense(keys[6], experts + (c.dim, c.moe_hidden_dim),
                            c.dim),
            "w_up": dense(keys[7], experts + (c.dim, c.moe_hidden_dim),
                          c.dim),
            "w_down": dense(keys[8], experts + (c.moe_hidden_dim, c.dim),
                            c.moe_hidden_dim * residual)}}
    head = jax.random.split(keys[0])
    return {"embed": _normal(head[0], shape=(c.vocab_size, c.dim),
                             fan_in=1, dtype=dtype),
            "layers": layers,
            "final_norm": jnp.ones((c.dim,), dtype=dtype),
            "unembed": _normal(head[1], shape=(c.dim, c.vocab_size),
                               fan_in=c.dim, dtype=dtype)}


# -- the cache ----------------------------------------------------------------

def init_cache(config, batch, max_seq=None):
    raise ValueError(
        "kv_page_tokens=0: the sdar_moe family commits K/V a block at a "
        "time into pages only; set kv_page_tokens > 0")


def cache_array(cache: dict):
    """The key pool ``[L, P, page_tokens, K * hd]``."""
    return cache["k"]


def cache_extent(cache: dict) -> int:
    return paged_extent(cache)


def check_serving(*, speculative: str, prefix_cache: bool,
                  kv_page_tokens: int) -> None:
    """What the family does not serve, refused when the batcher is
    created, each by its parameter's name (the batcher itself holds
    ``decode_block_tokens``, the page and the chunk to the block
    length)."""
    if not kv_page_tokens:
        init_cache(None, 0)
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the sdar_moe family decides "
            f"several tokens a pass by diffusion over a block and has "
            f"no draft beside it; use speculative: off")
    if prefix_cache:
        raise ValueError(
            "prefix_cache=on: a shared prefix would have to end at a "
            "block boundary, and under grouped expert matmuls re-written "
            "shared pages are not bit-equal; the sdar_moe family serves "
            "with prefix_cache: off")


def _matmul_safe_config(config, params):
    return config


def kernels_on(c: SdarConfig) -> bool:
    """Whether a pass's attention runs the paged decode kernel: asked
    for by name (interpreted off the chip), or under ``auto`` on the
    TPU backend."""
    return c.kernels == "on" or (c.kernels == "auto" and on_tpu())


# -- what a row carries between passes ---------------------------------------

def carry_width(config: SdarConfig) -> int:
    """Columns of a row's chained carry: its block's ``B`` tokens
    (``mask_token`` where undecided), how many of the block's leading
    positions are prompt (never emitted), and the denoising passes done
    on the block."""
    return config.block_length + 2


def admitted_length(config: SdarConfig, prompt_tokens: int) -> int:
    """How much of a prompt admission prefills: its whole blocks."""
    return prompt_tokens // config.block_length * config.block_length


def joiner_carry(config: SdarConfig, prompt_tokens) -> list[int]:
    """The carry a joiner enters with (:func:`carry_width`): the
    ``P mod B`` prompt tokens left over open its first block as decided
    positions, the rest masked."""
    c = config
    left = len(prompt_tokens) % c.block_length
    decided = [int(token) for token in
               prompt_tokens[len(prompt_tokens) - left:]]
    return decided + [c.mask_token] * (c.block_length - left) + [left, 0]


# -- layers -------------------------------------------------------------------

def route(c: SdarConfig, h, w_router):
    """Router over normed activations ``h [N, D]`` -> (chosen ``[N, k]``
    expert ids, gates ``[N, k]`` float32): softmax scores in float32,
    the ``k`` largest chosen, gates their scores normalised over the
    chosen (``norm_topk_prob``)."""
    scores = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, chosen = jax.lax.top_k(scores, c.n_experts_per_token)
    return chosen, picked / picked.sum(-1, keepdims=True)


def _qkv(c: SdarConfig, layer, h, rope_table, positions):
    """``h [B, S, D]`` at ``positions [B, S]`` -> (q ``[B, S, H, hd]``,
    k, v ``[B, S, K, hd]``): the norm over ``head_dim`` of every query
    and key head, then the rotation."""
    b, s, _ = h.shape
    q = (h @ layer["wq"]).reshape(b, s, c.n_heads, c.head_dim)
    k = (h @ layer["wk"]).reshape(b, s, c.n_kv_heads, c.head_dim)
    v = (h @ layer["wv"]).reshape(b, s, c.n_kv_heads, c.head_dim)
    q = apply_rope(rms_norm(q, layer["q_norm"], c.norm_eps), rope_table,
                   positions)
    k = apply_rope(rms_norm(k, layer["k_norm"], c.norm_eps), rope_table,
                   positions)
    return q, k, v


def _layers(c: SdarConfig, params, hidden, attend, valid=None):
    """Every layer over ``hidden [B, S, D]`` as one scan, the layer
    index among its inputs and the experts closed over (never a scan
    input: ``moe.routed_experts``).  ``attend(layer, h, index)`` ->
    (attention output ``[B, S, H * hd]``, this layer's new K/V rows).
    Returns (hidden, rows ``[L, ...]``, chosen experts ``[L, N, k]``,
    rows per expert ``[L, E]``)."""
    experts = params["layers"]["experts"]
    scanned = {key: leaf for key, leaf in params["layers"].items()
               if key != "experts"}

    def step(hidden, xs):
        layer, index = xs
        attended, rows = attend(
            layer, rms_norm(hidden, layer["attn_norm"], c.norm_eps), index)
        hidden = hidden + attended @ layer["wo"]
        flat = rms_norm(hidden, layer["mlp_norm"], c.norm_eps) \
            .reshape(-1, hidden.shape[-1])
        chosen, gates = route(c, flat, layer["w_router"])
        out, sizes = routed_experts(
            flat, chosen, gates, experts,
            grouped_matmul=c.grouped_matmul, valid=valid, index=index)
        return hidden + out.reshape(hidden.shape), (rows, chosen, sizes)

    hidden, (rows, chosen, sizes) = jax.lax.scan(
        step, hidden, (scanned, jnp.arange(c.n_layers)))
    return hidden, rows, chosen, sizes


# -- admission ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: SdarConfig,
                           tokens: jax.Array, cache: dict,
                           slot: jax.Array, start: jax.Array):
    """One prompt chunk ``tokens [1, S]`` of ONE sequence -- whole
    blocks of it, the pad tail starting at a block boundary -- into the
    K/V pages of ``slot`` at offset ``start`` (page-aligned, S whole
    pages), under the block-causal mask: per layer the slot's own rows
    are gathered through its page table, the chunk's rows laid in, and
    a query sees every key up to the end of its own block.  The chunk's
    rows leave the layer loop as its only cache-related output and are
    written in place once, after it.  NO logits: nothing is sampled at
    admission (the final norm and the head are not in the program).
    Returns (cache, the experts chosen ``[L, S, k]``)."""
    c = config
    if not is_paged(cache) or "k" not in cache:
        raise ValueError("the sdar_moe family admits into a paged K/V "
                         "cache (paged.init_paged_cache)")
    s = tokens.shape[1]
    page_tokens = pool_page_tokens(cache)
    if s % page_tokens or page_tokens % c.block_length:
        raise ValueError(
            f"paged prefill chunk of {s} tokens: whole {page_tokens}-"
            f"token pages of whole {c.block_length}-token blocks")
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    positions = (start + jnp.arange(s))[None, :]                # [1, S]
    frontier = (positions // c.block_length + 1) * c.block_length - 1
    table_row = cache["page_table"][slot]                       # [pps]

    def attend(layer, h, index):
        q, k, v = _qkv(c, layer, h, rope_table, positions)
        new = (k.reshape(1, s, -1), v.reshape(1, s, -1))

        def laid(side, rows):           # the slot's rows, the chunk in
            held = gather_rows(cache[side], table_row[None], index)
            return _grouped(jax.lax.dynamic_update_slice(
                held, rows, (0, start, 0)), c.n_kv_heads)
        k_rows, v_rows = laid("k", new[0]), laid("v", new[1])
        if c.attention == "flash":
            from ..ops.pallas_attention import flash_attention
            attended = flash_attention(q, k_rows, v_rows, q_offset=start,
                                       block_length=c.block_length)
        else:
            attended = attention_prefill(q, k_rows, v_rows, frontier)
        return attended.reshape(1, s, -1), (new[0][0], new[1][0])

    _, (k_rows, v_rows), chosen, _ = _layers(
        c, params, params["embed"][tokens], attend)

    def paged(pool, new):               # [L, S, C], page by page
        for j in range(s // page_tokens):
            pool = jax.lax.dynamic_update_slice(
                pool, new[:, None, j * page_tokens:(j + 1) * page_tokens],
                (0, table_row[start // page_tokens + j], 0, 0))
        return pool

    return {**cache, "k": paged(cache["k"], k_rows),
            "v": paged(cache["v"], v_rows)}, chosen


def prefill_into_slot(params: dict, config: SdarConfig, tokens: jax.Array,
                      cache: dict, slot: jax.Array, start: jax.Array,
                      selections: bool = False):
    """Single-slot admission (see :func:`_prefill_into_slot_jit`):
    (None, cache) -- logits for no position -- and with ``selections``
    the chosen experts too; the same program either way."""
    cache, chosen = _prefill_into_slot_jit(params, config, tokens, cache,
                                           slot, start)
    return (None, cache, chosen) if selections else (None, cache)


# -- a pass -------------------------------------------------------------------

def _scatter_blocks(cache: dict, k_new, v_new, starts) -> dict:
    """Write one block a batch row (``[L, B, S, K, hd]``, S the block
    length) into the page pools at ``starts [B]`` (block-aligned, so a
    block lies inside one page): one unrolled ``dynamic_update_slice`` a
    row and side, in place under donation (a batched scatter defeats
    the aliasing: ``llama._scatter_positions``, whose block-wide twin
    this is)."""
    table = cache["page_table"]
    page_tokens = pool_page_tokens(cache)

    def write(pool, new):
        new = new.reshape(*new.shape[:3], -1).astype(pool.dtype)
        for row in range(new.shape[1]):
            position = starts[row]
            pool = jax.lax.dynamic_update_slice(
                pool, new[:, row][:, None],
                (0, table[row, position // page_tokens],
                 position % page_tokens, 0))
        return pool

    return {"k": write(cache["k"], k_new), "v": write(cache["v"], v_new)}


def _pass_impl(params: dict, config: SdarConfig, blocks: jax.Array,
               cache: dict, lengths: jax.Array, commit: jax.Array,
               active: jax.Array):
    """ONE pass of the model over every row's block, whatever the
    row's phase: ``blocks [B, S]`` (S the block length; mask tokens
    where undecided) at positions ``lengths + [0, S)``, against the
    row's ``lengths`` stored positions (through the page table) and the
    block's own keys in both directions.  A row that ``commit``s writes
    its block's K/V in place; any other writes it to the trash block
    (the slot's last S positions) and leaves every page it holds
    bit-equal; a row that is not ``active`` attends nothing and routes
    to no expert.  Returns (logits ``[B, S, vocab]``, cache, chosen
    experts ``[L, B * S, k]``, rows per expert ``[L, E]``)."""
    c = config
    b, s = blocks.shape
    extent = cache_extent(cache)
    trash = extent - s
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    base = jnp.where(active, jnp.minimum(lengths, trash), trash)
    positions = base[:, None] + jnp.arange(s)[None, :]          # [B, S]
    stored = jnp.where(active, lengths, 0)
    table = cache["page_table"]
    kernel = kernels_on(c)
    if kernel:
        from ..ops.pallas_decode import _split_paged, flash_verify_append
        k_view, v_view = _split_paged(cache["k"]), _split_paged(cache["v"])

    def attend(layer, h, index):
        q, k, v = _qkv(c, layer, h, rope_table, positions)
        if kernel:
            attended = flash_verify_append(
                q, k_view, v_view, index, k, v, stored, positions,
                page_table=table, block_mask=True)
        else:
            def rows(side, new):        # the stored rows, then the block
                return jnp.concatenate([_grouped(gather_rows(
                    cache[side], table, index), c.n_kv_heads), new],
                    axis=1)
            held = jnp.arange(extent)[None, :]
            # (the block's keys take its first position: every query of
            # the block is at or past it, so all of them see all of it)
            attended = attention_prefill(
                q, rows("k", k), rows("v", v), positions,
                kv_length_mask=jnp.concatenate(
                    [held < stored[:, None], jnp.ones((b, s), bool)],
                    axis=1),
                kv_positions=jnp.concatenate(
                    [jnp.broadcast_to(held, (b, extent)),
                     jnp.broadcast_to(base[:, None], (b, s))], axis=1))
        return attended.reshape(b, s, -1), (k, v)

    hidden, (k_rows, v_rows), chosen, sizes = _layers(
        c, params, params["embed"][blocks], attend, jnp.repeat(active, s))
    cache = {**cache, **_scatter_blocks(
        cache, k_rows, v_rows, jnp.where(commit & active, base, trash))}
    return _finish(params, c, hidden), cache, chosen, sizes


_pass_jit = partial(jax.jit, static_argnames=("config",),
                    donate_argnames=("cache",))(_pass_impl)


def decode_step(params: dict, config: SdarConfig, blocks: jax.Array,
                cache: dict, lengths: jax.Array, commit: jax.Array,
                active: jax.Array | None = None,
                selections: bool = False):
    """One pass outside the loop (:func:`_pass_impl`, the loop's own
    body): (logits ``[B, S, vocab]``, cache), and with ``selections``
    the chosen experts ``[L, B * S, k]`` too.  The reference check and
    the tests drive it; the batcher serves by the device loop alone."""
    if active is None:
        active = jnp.ones(blocks.shape[:1], dtype=bool)
    logits, cache, chosen, _ = _pass_jit(params, config, blocks, cache,
                                         lengths, commit, active)
    return (logits, cache, chosen) if selections else (logits, cache)


def _decide(c: SdarConfig, blocks, logits, temperatures, done, key,
            top_k: int = 0):
    """What a denoising pass decides: ``blocks [B, S]``, the pass's
    ``logits [B, S, vocab]``, per-row ``temperatures`` and denoising
    passes ``done`` -> (blocks with the decided positions filled,
    which positions those are ``[B, S]``).  ``x0`` is the argmax
    (temperature 0) or a sample, never the mask token; ``c`` its
    probability at the row's temperature; the ``n_t = S // T + (t < S
    mod T)`` masked positions of highest ``c`` are decided (ties to the
    lower index; never more than are masked)."""
    b, s = blocks.shape
    masked = blocks == c.mask_token
    flat = logits.reshape(b * s, -1).astype(jnp.float32)
    flat = jnp.where(jnp.arange(flat.shape[1])[None, :] == c.mask_token,
                     -1e30, flat)
    temps = jnp.repeat(temperatures, s)
    # (a draw a position over the whole vocabulary is a pass's largest
    # elementwise work: a batch that is all greedy makes none)
    x0 = jax.lax.cond(
        (temps > 0).any(),
        lambda: select_tokens(key, flat, temps, top_k=top_k)
        .astype(jnp.int32),
        lambda: jnp.argmax(flat, axis=-1).astype(jnp.int32))
    scaled = flat / jnp.where(temps > 0, jnp.maximum(temps, 0.05),
                              1.0)[:, None]
    confidence = jnp.exp(
        jnp.take_along_axis(scaled, x0[:, None], axis=1)[:, 0]
        - jax.nn.logsumexp(scaled, axis=-1)).reshape(b, s)
    confidence = jnp.where(masked, confidence, -1.0)
    steps = c.denoising_steps
    quota = s // steps + (done < s % steps)                     # [B]
    rank = jnp.argsort(jnp.argsort(-confidence, axis=-1, stable=True),
                       axis=-1)
    transfer = masked & (rank < quota[:, None])
    return jnp.where(transfer, x0.reshape(b, s), blocks), transfer


@partial(jax.jit, static_argnames=("config", "top_k"))
def decide(config: SdarConfig, blocks, logits, temperatures, done, key,
           top_k: int = 0):
    """:func:`_decide` as a program of its own (the reference check and
    the tests; the loop traces it into its body)."""
    return _decide(config, blocks, logits, temperatures, done, key, top_k)


@partial(jax.jit, static_argnames=("config", "ring", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, config: SdarConfig, tokens: jax.Array,
                     cache: dict, lengths: jax.Array, active: jax.Array,
                     budget: jax.Array, temperatures: jax.Array,
                     eos: jax.Array, carry: jax.Array, key: jax.Array, *,
                     ring: int, top_k: int = 0):
    """The device-resident serving loop (see ``llama._decode_loop_jit``
    for the contract: chained carries, stop detection, the emitted
    ring), its body ONE pass for every live row whatever its phase
    (module docstring).  ``carry [B, carry_width]`` takes the place of
    the Llama family's ``history``; ``lengths`` are the positions
    STORED, always whole blocks; ``budget`` the tokens still to emit.
    A row whose block holds no mask commits: its K/V is written in
    place, its tokens past the prompt's leftover go to the ring up to
    its budget and its first stop token, and it starts the next block
    all-masked -- or stops, at a stop token, a spent budget or the last
    block before the trash block.  Any other live row denoises.  A pass
    needs room in the ring for a whole block a row.  Over its passes
    the block also counts row-passes, those that committed, the
    positions decided, the tokens emitted and the routed experts'
    spread: ``stats`` rides the block's one host fetch
    (:func:`loop_stats`)."""
    c = config
    b, s = tokens.shape[0], c.block_length
    extent = cache_extent(cache)
    offsets = jnp.arange(s)[None, :]
    per_row = s * c.n_experts_per_token

    def cond(state):
        i, _, _, active, _, _, _, counts, _, _ = state
        room = jnp.where(active, counts, 0).max() + s <= ring
        return (i < (ring // s + 1) * (s + 1)) & active.any() & room

    def body(state):
        (i, cache, lengths, active, budget, key, emitted, counts, carry,
         stats) = state
        blocks = carry[:, :s]
        skip, done = carry[:, s], carry[:, s + 1]
        commit = active & ~(blocks == c.mask_token).any(-1)
        denoise = active & ~commit
        logits, cache, _, sizes = _pass_impl(params, c, blocks, cache,
                                             lengths, commit, active)
        key, sub = jax.random.split(key)
        decided, transfer = _decide(c, blocks, logits, temperatures, done,
                                    sub, top_k)
        # a committing row emits its block: past the prompt's leftover,
        # inside its budget, up to and with its first stop token
        index = offsets - skip[:, None]
        wanted = (index >= 0) & (index < budget[:, None]) \
            & commit[:, None]
        stops = (blocks[:, :, None] == eos[:, None, :]).any(-1) & wanted
        emit = wanted & (jnp.cumsum(stops, axis=-1) - stops == 0)
        column = jnp.where(emit, counts[:, None] + index, ring)
        emitted = emitted.at[jnp.arange(b)[:, None], column].set(blocks)
        sent = emit.sum(-1)
        counts = counts + sent
        budget = budget - sent
        lengths = lengths + jnp.where(commit, s, 0)
        stop = commit & ((stops & emit).any(-1) | (budget <= 0)
                         | (lengths >= extent - s))
        mean = jnp.maximum(active.sum() * per_row, 1) / sizes.shape[1]
        stats = {
            "passes": stats["passes"] + 1,
            "row_passes": stats["row_passes"] + active.sum(),
            "commits": stats["commits"] + commit.sum(),
            "decided": stats["decided"]
            + (transfer & denoise[:, None]).sum(),
            "tokens": stats["tokens"] + sent.sum(),
            "touched": stats["touched"] + (sizes > 0).sum(),
            "imbalance": stats["imbalance"]
            + (sizes.max(-1) / mean).sum(),
            "pairs": stats["pairs"] + sizes.shape[0]}
        blocks = jnp.where(commit[:, None], c.mask_token,
                           jnp.where(denoise[:, None], decided, blocks))
        carry = jnp.concatenate([
            blocks, jnp.where(commit, 0, skip)[:, None],
            jnp.where(commit, 0, done + denoise)[:, None]], axis=-1)
        return (i + 1, cache, lengths, active & ~stop, budget, key,
                emitted, counts, carry, stats)

    zero = jnp.int32(0)
    stats = {"passes": zero, "row_passes": zero, "commits": zero,
             "decided": zero, "tokens": zero, "touched": zero,
             "imbalance": jnp.float32(0.0), "pairs": zero}
    state = (zero, cache, lengths, active, budget, key,
             jnp.zeros((b, ring + 1), dtype=jnp.int32),
             jnp.zeros((b,), dtype=jnp.int32), carry, stats)
    (steps, cache, lengths, active, budget, key, emitted, counts, carry,
     stats) = jax.lax.while_loop(cond, body, state)
    none = jnp.zeros((b,), dtype=jnp.int32)
    return (emitted[:, :ring], counts, tokens, lengths, active, budget,
            carry, key, none, none, steps, cache, stats)


def decode_loop(params: dict, config: SdarConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off", top_k: int = 0,
                **_):
    """Device-resident generation block: ``llama.decode_loop``'s twelve
    results (``history`` is this family's carry, ``steps`` its passes),
    then ``stats`` (see :func:`loop_stats`)."""
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the sdar_moe family serves "
            f"speculative: off")
    if int(ring) % config.block_length:
        raise ValueError(
            f"decode_block_tokens={ring}: a multiple of block_length "
            f"({config.block_length}), so that a whole block fits the "
            f"ring")
    return _decode_loop_jit(params, config, tokens, cache, lengths,
                            active, budget, temperatures, eos, history,
                            key, ring=int(ring), top_k=int(top_k))


def loop_stats(stats: dict) -> dict:
    """A retired block's fetched ``stats`` as what the LLM element
    observes of it: the passes, the live rows summed over them, those
    of them that committed and the positions decided (the recorder's
    ``llm_tick:demux`` info), tokens emitted a row-pass and the share
    of row-passes that decided nothing and only stored K/V
    (``llm_diffusion_tokens_per_row_pass``,
    ``llm_diffusion_commit_pass_share``), and, over the block's
    (layer, pass) pairs, the experts that got a live position and the
    fullest expert's rows over the mean (``llm_moe_experts_touched``,
    ``llm_moe_load_imbalance``)."""
    pairs, row_passes = int(stats["pairs"]), int(stats["row_passes"])
    if not pairs or not row_passes:
        return {}
    commits = int(stats["commits"])
    return {"passes": int(stats["passes"]), "row_passes": row_passes,
            "commits": commits, "decided": int(stats["decided"]),
            "diffusion_tokens_per_row_pass":
                float(stats["tokens"]) / row_passes,
            "diffusion_commit_pass_share": 100.0 * commits / row_passes,
            "moe_experts_touched": float(stats["touched"]) / pairs,
            "moe_load_imbalance": float(stats["imbalance"]) / pairs}
