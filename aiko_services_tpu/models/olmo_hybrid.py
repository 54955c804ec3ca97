"""The Olmo-Hybrid family (``model_type: olmo_hybrid``), the serving
path: layers of TWO kinds in one model -- gated delta-rule (linear
attention) layers that carry a per-slot recurrent state, and full
softmax-attention layers over the paged K/V cache -- in the published
pattern ``layer_types`` (whole periods: ``k`` linear layers, then one
full).  A third family beside ``models/llama.py`` and
``models/deepseek.py``; the ContinuousBatcher serves it through the
same seam (``batching.model_family``).

Both kinds of layer, ``x`` the residual stream (:func:`_block`, the ONE
place that says where the norms go -- the Olmo 2 / Olmo 3 convention,
the norm on each sub-layer's OUTPUT; the source's ``config.json`` does
not place them)::

    h = x + RMSNorm(mix(x));   out = h + RMSNorm(SwiGLU(h))

*Full layer*: ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm over the whole
width of ``q`` and of ``k`` before the head split; causal softmax at
scale ``head_dim ** -0.5``; NO rotary (``rope_theta: null``: position
reaches these layers through the recurrent ones below them); ``W_o``.
Its K/V live in the page pools of ``models/paged.py`` -- ``[L_full, P,
page_tokens, K * hd]``, only the full layers own pages -- and go through
the Llama family's own kernels (``ops/pallas_attention.py`` for
admission, ``ops/pallas_decode.py:flash_decode_attention_paged`` for
decode).

*Linear layer* (Gated DeltaNet, arXiv:2412.06464), ``H`` heads of
``d_k`` / ``d_v``: ``[q~; k~; v~] = W_qkv x``, each channel through a
causal depthwise convolution of width 4 over time and SiLU; per head
``q = q~ / |q~| d_k^-1/2``, ``k = k~ / |k~|``; ``beta = 2 sigmoid(W_b
x)`` (the 2 is ``linear_allow_neg_eigval``), ``g = -exp(A_log)
softplus(W_a x + dt_bias)``; the state ``S [d_k, d_v]`` a head follows
``S_t = e^g S_{t-1} + beta k (v - (e^g S_{t-1})^T k)^T``, ``o = S_t^T
q`` (``ops/pallas_gdn.py``: a chunk-parallel scan in admission, a
read-modify-write step in decode); ``y = W_o [RMSNorm_dv(o) * silu(W_g
x)]``.  Its CACHE is per SLOT, not per token: the float32 state of
every head and the last ``kernel - 1`` pre-convolution rows
(``cache["state"]``, ``cache["conv"]``: ``[L_lin, slots, ...]``), which
no page table addresses.

What a state does not forgive and K/V pages do, and how each is met:

1. *a chunk's pad tail*: positions past ``last`` enter neither the
   state nor the convolution tail (``g = 0``, ``beta = 0`` there, the
   tail taken at ``last``);
2. *a clamped start*: the batcher moves a last chunk that would spill
   past ``max_seq`` back over rows already written -- re-written K/V is
   the same K/V, a state applied twice is not.  This family says
   ``ADMISSION_CARRIES_STATE`` and takes the chunk where it starts:
   the slot's rows are gathered a chunk longer than the slot (the
   excess from the trash page, past every query's causal frontier),
   and pages past the slot's last are written to the trash page;
3. *decode between two chunks of an admission*: a row that does not
   decode keeps its state and tail (``active``; the dense tick marks
   such a row by the trash position);
4. *reuse*: a chunk that starts at 0 reads a zero state and a zero
   tail whatever the slot held -- no reset launch.

Refused by name (:func:`check_serving`, ``models/families.py``,
``elements/llm.py``): int8, speculation, the prefix cache, a dense
cache, a multi-chip placement; admission is one slot a program.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.layers import (attention_decode_append, attention_prefill,
                          rms_norm)
from ..ops.pallas_gdn import (gated_delta_chunk_scan,
                              gated_delta_decode_step, pack_state,
                              state_pack, unpack_state)
from ..ops.tiles import on_tpu
from .deepseek import _normal
from .families import FAMILY_WIDTHS, config_fields
from .llama import (_finish, _grouped, _scatter_positions, greedy_sample,
                    select_tokens, temperature_sample)      # noqa: F401
from .paged import gather_rows, is_paged, paged_extent, pool_page_tokens

__all__ = ["OlmoHybridConfig", "init_params", "init_cache", "cache_array",
           "cache_extent", "check_serving", "prefill_into_slot",
           "decode_step", "decode_loop", "loop_stats",
           "paged_decode_pages", "greedy_sample", "temperature_sample",
           "select_tokens"]

WIDTH_FIELDS = FAMILY_WIDTHS["olmo_hybrid"]
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Defaults are Olmo-Hybrid-7B's published ``config.json``."""
    vocab_size: int = 100_352
    dim: int = 3840
    n_layers: int = 32
    n_heads: int = 30
    n_kv_heads: int = 30
    hidden_dim: int = 11_008
    # () stands for the published pattern, (linear x 3, full), repeated
    layer_types: tuple = ()
    linear_key_heads: int = 30
    linear_value_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = True
    max_seq: int = 65_536
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    # Admission's full-attention layers: "dense" (einsums over the
    # slot's rows) or "flash" (ops/pallas_attention.py).
    attention: str = "dense"
    # The decode-side kernels and the chunk scan -- the paged decode
    # kernel of ops/pallas_decode.py and both kernels of
    # ops/pallas_gdn.py: "on" (interpreted off the chip: the tests),
    # "off" (their jax.numpy forms) or "auto" (on, on the TPU backend).
    kernels: str = "auto"
    # ``llama._finish`` asks for it; this family serves unquantized.
    matmul_kernel: str = "off"

    def __post_init__(self):
        if not self.layer_types:
            object.__setattr__(self, "layer_types",
                               ((LINEAR,) * 3 + (FULL,))
                               * (self.n_layers // 4))
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention must be 'dense' or 'flash', "
                             f"got {self.attention!r}")
        if self.kernels not in ("on", "off", "auto"):
            raise ValueError(f"kernels must be 'on', 'off' or 'auto', "
                             f"got {self.kernels!r}")
        if self.kv_dtype != "bfloat16":
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}: the olmo_hybrid family's "
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
                f"layer_types must be whole periods of {LINEAR} layers "
                f"then one {FULL} layer, got {types}")
        if self.linear_key_heads != self.linear_value_heads:
            raise ValueError(
                "linear_num_key_heads != linear_num_value_heads: value "
                "heads that share a key head are not served")
        if self.n_heads % self.n_kv_heads or self.dim % self.n_heads:
            raise ValueError("num_attention_heads must divide "
                             "hidden_size and be a multiple of "
                             "num_key_value_heads")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def period(self) -> int:
        """Layers a period: its linear layers and the one full layer."""
        return self.layer_types.index(FULL) + 1

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def n_linear_layers(self) -> int:
        return self.n_periods * (self.period - 1)

    @property
    def n_paged_layers(self) -> int:
        """The layers that own K/V pages (``paged.init_paged_cache``)."""
        return self.n_periods

    @property
    def linear_heads(self) -> int:
        return self.linear_value_heads

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[q~; k~; v~]``."""
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    @property
    def state_pack(self) -> int:
        return state_pack(self.linear_heads, self.linear_value_dim)

    @property
    def slot_state(self) -> dict:
        """What every slot holds beside its pages, as ``name -> (shape
        a layer and slot, dtype)`` (``paged.init_paged_cache`` lays it
        out ``[L_lin, slots, ...]``): the float32 state, heads packed
        along the lanes (``ops/pallas_gdn.py:pack_state``), and the
        convolution's tail."""
        pack = self.state_pack
        return {
            "state": ((self.n_linear_layers,),
                      (self.linear_heads // pack, self.linear_key_dim,
                       pack * self.linear_value_dim), "float32"),
            "conv": ((self.n_linear_layers,),
                     (self.linear_conv_kernel - 1, self.conv_width),
                     self.dtype)}

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "OlmoHybridConfig":
        """The config of published ``config.json`` keys (``WIDTH_FIELDS``;
        a key the family lacks is an error, a key left out keeps
        Olmo-Hybrid-7B's value; ``layer_types`` left out is the
        published pattern over ``num_hidden_layers``)."""
        return cls(**{**fields, **config_fields("olmo_hybrid", widths)})

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "OlmoHybridConfig":
        """The CPU tests' preset: two periods of (2 linear, 1 full),
        d_k != d_v, a head count that is no power of two."""
        return cls(vocab_size=vocab_size, dim=96, n_layers=6, n_heads=6,
                   n_kv_heads=6, hidden_dim=160,
                   layer_types=(LINEAR, LINEAR, FULL) * 2,
                   linear_key_heads=6, linear_value_heads=6,
                   linear_key_dim=24, linear_value_dim=64,
                   max_seq=max_seq)


# -- parameters --------------------------------------------------------------

def _swiglu_params(keys, c: OlmoHybridConfig, dtype, stack: int) -> dict:
    return {
        "w_gate": _normal(keys[0], shape=(c.dim, c.hidden_dim),
                          fan_in=c.dim, dtype=dtype, stack=stack),
        "w_up": _normal(keys[1], shape=(c.dim, c.hidden_dim),
                        fan_in=c.dim, dtype=dtype, stack=stack),
        "w_down": _normal(keys[2], shape=(c.hidden_dim, c.dim),
                          fan_in=c.hidden_dim, dtype=dtype, stack=stack),
        "mix_norm": jnp.ones((stack, c.dim), dtype=dtype),
        "ffn_norm": jnp.ones((stack, c.dim), dtype=dtype)}


def init_params(key: jax.Array, config: OlmoHybridConfig) -> dict:
    """Random weights in ``config.dtype``, built leaf by leaf and layer
    by layer (normal, fan-in scaled; the embedding at unit variance, so
    that a token's identity survives the residual stream).  ``linear``
    is a stacked tree ``[linear layers, ...]``, ``full`` one
    ``[periods, ...]``.  The recurrence's own parameters
    as the flash-linear-attention layer initialises them: ``A_log =
    log U(0, 16)``, ``dt_bias`` the inverse softplus of a log-uniform
    ``dt`` in [1e-3, 1e-1], the convolution uniform in +-(1 /
    kernel)^1/2 -- under random weights a head's decay is then neither
    ~0 nor 1.  ``A_log``, ``dt_bias`` float32."""
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 20)
    heads = c.linear_heads
    lin, full = c.n_linear_layers, c.n_periods
    value_width = heads * c.linear_value_dim

    def dense(k, shape, stack, fan_in=None):
        return _normal(k, shape=shape, fan_in=fan_in or shape[0],
                       dtype=dtype, stack=stack)

    bound = c.linear_conv_kernel ** -0.5
    step = jnp.exp(jax.random.uniform(
        keys[5], (lin, heads), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    linear = {
        "w_qkv": dense(keys[0], (c.dim, c.conv_width), lin),
        "w_out_gate": dense(keys[1], (c.dim, value_width), lin),
        # [W_a; W_b]: the decay's and beta's projections
        "w_ab": dense(keys[2], (c.dim, 2 * heads), lin),
        "conv": jax.random.uniform(
            keys[3], (lin, c.linear_conv_kernel, c.conv_width),
            minval=-bound, maxval=bound).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(
            keys[4], (lin, heads), minval=1e-3, maxval=16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "out_norm": jnp.ones((lin, c.linear_value_dim), dtype=dtype),
        "wo": dense(keys[6], (value_width, c.dim), lin),
        **_swiglu_params(keys[7:10], c, dtype, lin)}
    kv_width = c.n_kv_heads * c.head_dim
    return {
        "embed": _normal(keys[10], shape=(c.vocab_size, c.dim), fan_in=1,
                         dtype=dtype),
        "linear": linear,
        "full": {
            "wq": dense(keys[11], (c.dim, c.dim), full),
            "wk": dense(keys[12], (c.dim, kv_width), full),
            "wv": dense(keys[13], (c.dim, kv_width), full),
            "q_norm": jnp.ones((full, c.dim), dtype=dtype),
            "k_norm": jnp.ones((full, kv_width), dtype=dtype),
            "wo": dense(keys[14], (c.dim, c.dim), full),
            **_swiglu_params(keys[15:18], c, dtype, full)},
        "final_norm": jnp.ones((c.dim,), dtype=dtype),
        "unembed": _normal(keys[18], shape=(c.dim, c.vocab_size),
                           fan_in=c.dim, dtype=dtype)}


# -- the cache ----------------------------------------------------------------

def init_cache(config, batch, max_seq=None):
    raise ValueError(
        "kv_page_tokens=0: the olmo_hybrid family's full-attention "
        "layers keep their K/V in pages only; set kv_page_tokens > 0")


def cache_array(cache: dict):
    """The key pool ``[L_paged, P, page_tokens, K * hd]``."""
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
            f"to roll the olmo_hybrid family's recurrent state back and "
            f"no snapshot of it is kept; use speculative: off")
    if prefix_cache:
        raise ValueError(
            "prefix_cache=on: a shared prefix of the olmo_hybrid family "
            "is a snapshot of the recurrent state, not pages, and none "
            "is kept; serve with prefix_cache: off")


def _matmul_safe_config(config, params):
    return config


def kernels_on(c: OlmoHybridConfig) -> bool:
    """Whether decode and the chunk scan run their Pallas kernels:
    asked for by name (interpreted off the chip), or under ``auto`` on
    the TPU backend."""
    return c.kernels == "on" or (c.kernels == "auto" and on_tpu())


def paged_decode_pages(c: OlmoHybridConfig, cache: dict) -> int | None:
    """Pages a grid step of the paged decode kernel where it serves
    the paged layers' decode, else None (``llama``'s twin)."""
    if not kernels_on(c):
        return None
    from ..ops.pallas_decode import _split_paged, paged_pages_per_step
    return paged_pages_per_step(jax.eval_shape(_split_paged, cache["k"]),
                                cache["page_table"].shape[1])


# -- layers -------------------------------------------------------------------

def _block(c: OlmoHybridConfig, x, layer, mix):
    """One layer of either kind around its mixer ``mix(x)``: WHERE THE
    NORMS GO, and nowhere else -- on each sub-layer's output (assumed:
    the Olmo 2 / Olmo 3 convention, not a reading of the source)."""
    h = x + rms_norm(mix(x), layer["mix_norm"], c.norm_eps)
    ffn = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) \
        @ layer["w_down"]
    return h + rms_norm(ffn, layer["ffn_norm"], c.norm_eps)


def _full_qkv(c: OlmoHybridConfig, layer, x):
    """``x [N, D]`` -> (q ``[N, H, hd]``, k, v ``[N, K, hd]``): the
    norm over the whole width of q and of k, then the head split."""
    n = x.shape[0]
    q = rms_norm(x @ layer["wq"], layer["q_norm"], c.norm_eps)
    k = rms_norm(x @ layer["wk"], layer["k_norm"], c.norm_eps)
    v = x @ layer["wv"]
    return (q.reshape(n, c.n_heads, c.head_dim),
            k.reshape(n, c.n_kv_heads, c.head_dim),
            v.reshape(n, c.n_kv_heads, c.head_dim))


def _linear_projections(c: OlmoHybridConfig, layer, x):
    """``x [N, D]`` -> (pre-convolution rows ``[N, conv_width]``, log
    decay ``g [N, H]`` and ``beta [N, H]`` float32, the output gate's
    input ``[N, H d_v]``)."""
    heads = c.linear_heads
    ab = jnp.dot(x, layer["w_ab"], preferred_element_type=jnp.float32)
    g = -jnp.exp(layer["a_log"]) * jax.nn.softplus(
        ab[:, :heads] + layer["dt_bias"])
    beta = jax.nn.sigmoid(ab[:, heads:]) \
        * (2.0 if c.linear_allow_neg_eigval else 1.0)
    return x @ layer["w_qkv"], g, beta, x @ layer["w_out_gate"]


def _convolve(c: OlmoHybridConfig, layer, window):
    """The causal depthwise convolution and SiLU over ``window [.., N +
    kernel - 1, C]`` (the tail, then the rows) -> ``[.., N, C]``
    float32: row ``t`` sees ``window[t : t + kernel]``."""
    width = c.linear_conv_kernel
    n = window.shape[-2] - width + 1
    weights = layer["conv"].astype(jnp.float32)
    window = window.astype(jnp.float32)
    mixed = sum(weights[j] * jax.lax.slice_in_dim(window, j, j + n,
                                                  axis=window.ndim - 2)
                for j in range(width))
    return jax.nn.silu(mixed)


def _split_heads(c: OlmoHybridConfig, mixed):
    """Convolved channels ``[N, C]`` -> (q ``[N, H, d_k]`` of length
    ``d_k^-1/2``, k of unit length, v ``[N, H, d_v]``), float32."""
    n, heads, dk = mixed.shape[0], c.linear_heads, c.linear_key_dim
    q = mixed[:, :heads * dk].reshape(n, heads, dk)
    k = mixed[:, heads * dk:2 * heads * dk].reshape(n, heads, dk)
    v = mixed[:, 2 * heads * dk:].reshape(n, heads, c.linear_value_dim)

    def unit(rows):
        return rows * jax.lax.rsqrt(
            jnp.sum(rows * rows, axis=-1, keepdims=True) + 1e-6)
    return unit(q) * dk ** -0.5, unit(k), v


def _linear_output(c: OlmoHybridConfig, layer, out, gate):
    """``W_o [RMSNorm_dv(o) * silu(W_g x)]``: ``out [N, H, d_v]``
    float32, ``gate [N, H d_v]``."""
    normed = rms_norm(out, layer["out_norm"].astype(jnp.float32),
                      c.norm_eps).astype(gate.dtype)
    return (normed.reshape(gate.shape) * jax.nn.silu(gate)) @ layer["wo"]


def _linear_layer(params: dict, index):
    """Linear layer ``index`` (traced) of the stacked tree, each leaf
    sliced where it is used: the period scan closes over the stack (as
    a scan input a period's three layers were copied out whole every
    step, 1.3 GB a chunk at the published widths: PERF.md, PR 33)."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_index_in_dim(leaf, index, 0, False),
        params["linear"])


# -- admission ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: OlmoHybridConfig,
                           tokens: jax.Array, cache: dict,
                           slot: jax.Array, start: jax.Array,
                           last: jax.Array):
    """One prompt chunk ``tokens [1, S]`` of ONE sequence into ``slot``
    at offset ``start`` (page-aligned, S whole pages; it may spill past
    the slot's extent: module docstring, 2).  Scans the PERIODS -- the
    period's linear layers and its full layer one body -- with the pools
    closed over; the chunk's K/V rows, the new states and the new tails
    leave the scan as its only cache-related outputs and are written in
    place once, after it.  The state a linear layer starts from is the
    slot's, or zero where ``start == 0``; positions past ``last`` change
    neither state nor tail.  Logits for position ``last`` of the chunk
    ALONE (``[1, 1, vocab]``)."""
    c = config
    if not is_paged(cache) or "state" not in cache:
        raise ValueError("the olmo_hybrid family admits into a paged "
                         "cache with a state pool "
                         "(paged.init_paged_cache)")
    s = tokens.shape[1]
    page_tokens = pool_page_tokens(cache)
    if s % page_tokens:
        raise ValueError(
            f"paged prefill chunk of {s} tokens is not a whole number "
            f"of {page_tokens}-token pages")
    per, pack = c.period - 1, c.state_pack
    width = c.linear_conv_kernel
    kernel = kernels_on(c)
    fresh = start == 0
    real = jnp.arange(s) <= last                               # [S]
    positions = (start + jnp.arange(s))[None, :]               # [1, S]
    # the slot's pages, then a chunk's worth of the trash page: a view
    # the chunk fits in wherever it starts
    table = jnp.concatenate([
        cache["page_table"][slot],
        jnp.zeros((s // page_tokens,), jnp.int32)])

    def linear_mix(layer, index):
        def mix(x):
            rows, g, beta, gate = _linear_projections(c, layer, x)
            tail = jnp.where(fresh, 0, cache["conv"][index, slot])
            window = jnp.concatenate([tail, rows], axis=0)
            q, k, v = _split_heads(c, _convolve(c, layer, window))
            state = jnp.where(fresh, 0.0, unpack_state(
                cache["state"][index, slot], pack))
            out, state = gated_delta_chunk_scan(
                q, k, v, jnp.where(real[:, None], g, 0.0),
                jnp.where(real[:, None], beta, 0.0), state, kernel=kernel)
            mix.carried = (pack_state(state, pack),
                           jax.lax.dynamic_slice_in_dim(
                               window, last + 1, width - 1, axis=0))
            return _linear_output(c, layer, out, gate)
        return mix

    def full_mix(layer, index):
        def mix(x):
            q, k, v = _full_qkv(c, layer, x)
            mix.rows = (k.reshape(s, -1), v.reshape(s, -1))

            def laid(side, new):        # the slot's rows, the chunk in
                rows = gather_rows(cache[side], table[None], index)
                return _grouped(jax.lax.dynamic_update_slice(
                    rows, new[None], (0, start, 0)), c.n_kv_heads)
            k_rows, v_rows = laid("k", mix.rows[0]), laid("v", mix.rows[1])
            if c.attention == "flash":
                from ..ops.pallas_attention import flash_attention
                attended = flash_attention(q[None], k_rows, v_rows,
                                           q_offset=start)
            else:
                attended = attention_prefill(q[None], k_rows, v_rows,
                                             positions)
            return attended.reshape(s, -1) @ layer["wo"]
        return mix

    def period_step(x, xs):
        full, index = xs
        states, tails = [], []
        for member in range(per):
            layer = _linear_layer(params, index * per + member)
            mix = linear_mix(layer, index * per + member)
            x = _block(c, x, layer, mix)
            states.append(mix.carried[0])
            tails.append(mix.carried[1])
        mix = full_mix(full, index)
        x = _block(c, x, full, mix)
        return x, (jnp.stack(states), jnp.stack(tails), *mix.rows)

    hidden, (states, tails, k_rows, v_rows) = jax.lax.scan(
        period_step, params["embed"][tokens[0]],
        (params["full"], jnp.arange(c.n_periods)))
    sampled = jax.lax.dynamic_slice_in_dim(hidden, last, 1, axis=0)

    def per_slot(pool, new):            # [P, per, ...] -> [L_lin, 1, ...]
        new = new.reshape(-1, 1, *new.shape[2:]).astype(pool.dtype)
        return jax.lax.dynamic_update_slice(
            pool, new, (0, slot) + (0,) * (pool.ndim - 2))

    def paged(pool, new):               # [L_full, S, C], page by page
        for j in range(s // page_tokens):
            pool = jax.lax.dynamic_update_slice(
                pool, new[:, None, j * page_tokens:(j + 1) * page_tokens],
                (0, table[start // page_tokens + j], 0, 0))
        return pool

    cache = {**cache, "k": paged(cache["k"], k_rows),
             "v": paged(cache["v"], v_rows),
             "state": per_slot(cache["state"], states),
             "conv": per_slot(cache["conv"], tails)}
    return _finish(params, c, sampled[None]), cache


def prefill_into_slot(params: dict, config: OlmoHybridConfig,
                      tokens: jax.Array, cache: dict, slot: jax.Array,
                      start: jax.Array, last: jax.Array):
    """Single-slot admission (see :func:`_prefill_into_slot_jit`):
    (logits ``[1, 1, vocab]`` at chunk position ``last``, cache)."""
    return _prefill_into_slot_jit(params, config, tokens, cache, slot,
                                  start, last)


# The batcher hands ``last`` to a family that computes the sampled
# position's logits alone, ...
ADMISSION_LOGITS_AT_LAST = True
# ... and to one whose chunks hand a state on it hands a last chunk
# over where it starts, not moved back to fit (this admission takes a
# chunk that spills), and notes which chunks were handed a state.
ADMISSION_CARRIES_STATE = True


# -- decode -------------------------------------------------------------------

def _decode_step_impl(params: dict, config: OlmoHybridConfig,
                      tokens: jax.Array, cache: dict, lengths: jax.Array,
                      active: jax.Array | None = None):
    """One token per sequence: ``tokens [B]``, written at ``lengths
    [B]``.  ``active [B]`` marks the rows that decode (default: those
    not at the trash position, as the batcher's dense tick marks them):
    any other row keeps its state and tail, attends nothing, and its
    K/V write lands wherever its position says (the trash position).
    The state pool rides the period scan as a carry and is advanced in
    place; the K/V pools are closed over and written once, after it."""
    c = config
    b = tokens.shape[0]
    per, pack = c.period - 1, c.state_pack
    width = c.linear_conv_kernel
    kernel = kernels_on(c)
    table = cache["page_table"]
    if active is None:
        active = lengths != cache_extent(cache) - 1
    attend = jnp.where(active, lengths, 0)
    if kernel:
        from ..ops.pallas_decode import (_split_paged,
                                         flash_decode_append_paged)
        k_view, v_view = _split_paged(cache["k"]), _split_paged(cache["v"])

    def linear_mix(layer, index, pools):
        def mix(x):
            state_pool, conv_pool = pools
            rows, g, beta, gate = _linear_projections(c, layer, x)
            window = jnp.concatenate(
                [conv_pool[index], rows[:, None, :]], axis=1)  # [B, w, C]
            q, k, v = _split_heads(c, _convolve(c, layer, window)[:, 0])
            out, state_pool = gated_delta_decode_step(
                q, k, v, g, beta, state_pool, index, active, pack=pack,
                kernel=kernel)
            tails = jnp.where(active[:, None, None], window[:, 1:],
                              conv_pool[index])
            mix.pools = (state_pool, jax.lax.dynamic_update_index_in_dim(
                conv_pool, tails, index, 0))
            return _linear_output(c, layer, out, gate)
        return mix

    def full_mix(layer, index):
        def mix(x):
            q, k, v = _full_qkv(c, layer, x)
            q, k, v = q[:, None], k[:, None], v[:, None]       # [B, 1, ..]
            mix.rows = (k, v)
            if kernel:
                attended = flash_decode_append_paged(
                    q, k_view, v_view, index, k, v, table, attend)
            else:
                attended = attention_decode_append(
                    q, _grouped(gather_rows(cache["k"], table, index),
                                c.n_kv_heads),
                    _grouped(gather_rows(cache["v"], table, index),
                             c.n_kv_heads), k, v, attend)
            return attended.reshape(b, -1) @ layer["wo"]
        return mix

    def period_step(carry, xs):
        x, pools = carry
        full, index = xs
        for member in range(per):
            layer = _linear_layer(params, index * per + member)
            mix = linear_mix(layer, index * per + member, pools)
            x = _block(c, x, layer, mix)
            pools = mix.pools
        mix = full_mix(full, index)
        x = _block(c, x, full, mix)
        return (x, pools), mix.rows

    (hidden, (state_pool, conv_pool)), (k_rows, v_rows) = jax.lax.scan(
        period_step,
        (params["embed"][tokens], (cache["state"], cache["conv"])),
        (params["full"], jnp.arange(c.n_periods)))
    cache = {**cache, "state": state_pool, "conv": conv_pool,
             **_scatter_positions(c, cache, k_rows, v_rows,
                                  lengths[:, None])}
    return _finish(params, c, hidden[:, None])[:, 0, :], cache


_decode_step_jit = partial(jax.jit, static_argnames=("config",),
                           donate_argnames=("cache",))(_decode_step_impl)


def decode_step(params: dict, config: OlmoHybridConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array,
                active: jax.Array | None = None):
    """(logits ``[B, vocab]``, cache) of one decode step."""
    return _decode_step_jit(params, config, tokens, cache, lengths, active)


def state_bytes_per_row(c: OlmoHybridConfig) -> int:
    """What a decoding row's recurrent layers move a step: the state
    once in and once out, the convolution's tail the same."""
    state = c.linear_heads * c.linear_key_dim * c.linear_value_dim * 4
    tail = (c.linear_conv_kernel - 1) * c.conv_width \
        * jnp.dtype(c.dtype).itemsize
    return c.n_linear_layers * 2 * (state + tail)


def kv_bytes_per_token(c: OlmoHybridConfig) -> int:
    """One cached token's K and V over the full-attention layers."""
    return c.n_paged_layers * 2 * c.n_kv_heads * c.head_dim \
        * jnp.dtype(c.kv_dtype).itemsize


def run_decode_loop(step_impl, row_bytes: float, token_bytes: float,
                    params: dict, config, tokens: jax.Array, cache: dict,
                    lengths: jax.Array, active: jax.Array,
                    budget: jax.Array, temperatures: jax.Array,
                    eos: jax.Array, history: jax.Array, key: jax.Array, *,
                    ring: int, top_k: int = 0):
    """The device-resident serving loop of a family with a recurrent
    state beside its K/V pages, traced inside that family's jitted
    ``_decode_loop_jit`` (see ``llama._decode_loop_jit`` for the
    contract: carries, stop detection, the emitted ring).  ``step_impl``
    is the family's decode step.  Over its steps the block also sums,
    from the loop's own lengths, the bytes of recurrent state its live
    rows moved (``row_bytes`` a row and step) and the bytes of live K/V
    rows they read (``token_bytes`` a cached token): ``stats`` rides the
    block's one host fetch (:func:`loop_stats`)."""
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
        logits, cache = step_impl(params, config, tokens, cache, positions,
                                  active)
        key, sub = jax.random.split(key)
        sampled = select_tokens(sub, logits, temperatures,
                                top_k=top_k).astype(jnp.int32)
        slot_index = jnp.where(active, counts, ring)     # ring = trash col
        emitted = emitted.at[jnp.arange(b), slot_index].set(sampled)
        stats = {
            "state_bytes": stats["state_bytes"]
            + active.sum().astype(jnp.float32) * row_bytes,
            "kv_bytes": stats["kv_bytes"]
            + jnp.where(active, lengths, 0).sum().astype(jnp.float32)
            * token_bytes}
        counts = counts + active
        lengths = lengths + active
        budget = budget - active
        stop = ((sampled[:, None] == eos).any(-1) | (budget <= 0)
                | (lengths >= extent)) & active
        tokens = jnp.where(active, sampled, tokens)
        return (i + 1, tokens, cache, lengths, active & ~stop, budget,
                key, emitted, counts, stats)

    stats = {"state_bytes": jnp.float32(0.0), "kv_bytes": jnp.float32(0.0)}
    carry = (jnp.int32(0), tokens, cache, lengths, active, budget, key,
             jnp.zeros((b, ring + 1), dtype=jnp.int32),
             jnp.zeros((b,), dtype=jnp.int32), stats)
    (steps, tokens, cache, lengths, active, budget, key, emitted, counts,
     stats) = jax.lax.while_loop(cond, body, carry)
    none = jnp.zeros((b,), dtype=jnp.int32)
    return (emitted[:, :ring], counts, tokens, lengths, active, budget,
            history, key, none, none, steps, cache, stats)


@partial(jax.jit, static_argnames=("config", "ring", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, config: OlmoHybridConfig,
                     tokens: jax.Array, cache: dict, lengths: jax.Array,
                     active: jax.Array, budget: jax.Array,
                     temperatures: jax.Array, eos: jax.Array,
                     history: jax.Array, key: jax.Array, *, ring: int,
                     top_k: int = 0):
    """This family's device loop (:func:`run_decode_loop`)."""
    return run_decode_loop(
        _decode_step_impl, float(state_bytes_per_row(config)),
        float(kv_bytes_per_token(config)), params, config, tokens, cache,
        lengths, active, budget, temperatures, eos, history, key,
        ring=ring, top_k=top_k)


def decode_loop(params: dict, config: OlmoHybridConfig, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off", top_k: int = 0,
                **_):
    """Device-resident generation block: ``llama.decode_loop``'s
    twelve results, then ``stats`` (see :func:`loop_stats`)."""
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the olmo_hybrid family "
            f"serves speculative: off")
    return _decode_loop_jit(params, config, tokens, cache, lengths,
                            active, budget, temperatures, eos, history,
                            key, ring=int(ring), top_k=int(top_k))


def loop_stats(stats: dict) -> dict:
    """A retired block's fetched ``stats`` as what the LLM element
    observes of it (``llm_state_traffic_share``): of the cache bytes
    its steps moved -- recurrent state in and out, live K/V rows read
    -- the share, in %, that was state."""
    state, kv = float(stats["state_bytes"]), float(stats["kv_bytes"])
    if state + kv <= 0:
        return {}
    return {"state_traffic_share": 100.0 * state / (state + kv)}
