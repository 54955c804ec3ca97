"""The Falcon-H1 family (``model_type: falcon_h1``), the serving path:
EVERY layer runs grouped-query attention over K/V pages and a Mamba-2
state-space mixer over a per-slot float32 state side by side, off one
norm, under the published muP multipliers.  A sixth family beside
``models/llama.py``, ``models/deepseek.py``, ``models/olmo_hybrid.py``,
``models/sdar.py`` and ``models/command_a.py``; the ContinuousBatcher
serves it through the same seam (``batching.model_family``).

Per layer, ``h`` the residual stream, ``a = RMSNorm(h)``::

    h'  = h + Attn(a) + Mix(a)
    h'' = h' + MLP(RMSNorm(h'))

- **attention**: ``q = a' W_q``, ``k = (a' W_k) key_multiplier``, ``v =
  a' W_v`` with ``a' = a attention_in_multiplier`` (``H`` heads of
  ``head_dim``, ``H * head_dim`` apart from the hidden size, ``K`` K/V
  heads); rotate-half rotary of base ``rope_theta`` on q and k; causal
  softmax at scale ``head_dim ** -0.5``; ``Attn = W_o(.)
  attention_out_multiplier``.  Its K/V live in the page pools of
  ``models/paged.py``, one pair of pools over EVERY layer, and go through
  the Llama family's kernels (``ops/pallas_attention.py`` for admission,
  ``ops/pallas_decode.py:flash_decode_append_paged`` for decode).
- **mixer** (Mamba-2): ``zxbcdt = (a ssm_in_multiplier) W_in * mup``,
  ``mup`` the five ``ssm_multipliers`` over the segments ``[z | x | B |
  C | dt]``; ``xBC`` through a causal depthwise convolution of
  ``mamba_d_conv`` with a bias, then SiLU, split into ``x [H_s, P]``,
  ``B, C [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the SSD recurrence of ``ops/pallas_ssd.py`` (a chunk scan in
  admission, a read-modify-write step in decode) gives ``y = S C + D
  x``; ``Mix = W_out(RMSNorm_G(y * silu(z))) ssm_out_multiplier``, the
  norm AFTER the gate over ``G`` groups of the channels.  Its CACHE is
  per SLOT: the float32 state ``[H_s, P, N]`` and the last ``d_conv -
  1`` pre-convolution rows of ``xBC`` (float32) of every layer
  (``cache["state"]``, ``cache["conv"]``: ``[L, slots, ...]``), which no
  page table addresses.
- **MLP**: ``W_down(silu(u W_gate m0) * u W_up) m1``, ``(m0, m1)`` the
  ``mlp_multipliers``.
- **ends**: the embedding times ``embedding_multiplier``; a final
  RMSNorm; the untied head times ``lm_head_multiplier``.

What a state does not forgive and K/V pages do is met as in
``models/olmo_hybrid.py``: a chunk's pad tail has ``dt = 0`` and the
convolution tail is taken at the last real position; a last chunk is
taken where it starts (``ADMISSION_CARRIES_STATE``), spilling into the
trash page; a row that does not decode keeps its state and tail; a chunk
that starts at 0 reads a zero state and a zero tail whatever the slot
held.  The state pool is updated in place: decode's kernel writes it
through ``input_output_aliases`` with the pool a carry of the layer
scan, admission writes the slot's new states once, after the scan.

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
import numpy as np

from ..ops.layers import (apply_rope, attention_decode_append,
                          attention_prefill, rms_norm, rope_frequencies)
from ..ops.pallas_ssd import ssd_chunk_scan, ssd_decode_step
from .deepseek import _normal
from .families import FAMILY_WIDTHS, config_fields
from .llama import (_grouped, _scatter_positions, greedy_sample,
                    select_tokens, temperature_sample)      # noqa: F401
from .olmo_hybrid import (_matmul_safe_config, cache_array,  # noqa: F401
                          cache_extent, kernels_on, loop_stats,
                          paged_decode_pages, run_decode_loop)
from .paged import gather_rows, is_paged, pool_page_tokens

__all__ = ["FalconH1Config", "init_params", "init_cache", "cache_array",
           "cache_extent", "check_serving", "prefill_into_slot",
           "decode_step", "decode_loop", "loop_stats",
           "paged_decode_pages", "greedy_sample", "temperature_sample",
           "select_tokens"]

WIDTH_FIELDS = FAMILY_WIDTHS["falcon_h1"]


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """Defaults are Falcon-H1-34B-Instruct's published ``config.json``."""
    vocab_size: int = 261_120
    dim: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    hidden_dim: int = 21_504
    ssm_dim: int = 4096
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    conv_kernel: int = 4
    ssm_chunk: int = 128
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    max_seq: int = 262_144
    dtype: str = "bfloat16"
    kv_dtype: str = "bfloat16"
    # Admission's attention: "dense" (einsums over the slot's rows) or
    # "flash" (ops/pallas_attention.py).
    attention: str = "dense"
    # The paged decode kernel and both kernels of ops/pallas_ssd.py:
    # "on" (interpreted off the chip: the tests), "off" (their
    # jax.numpy forms) or "auto" (on, on the TPU backend).
    kernels: str = "auto"
    # ``llama``'s helpers ask for it; this family serves unquantized.
    matmul_kernel: str = "off"

    def __post_init__(self):
        object.__setattr__(self, "mlp_multipliers",
                           tuple(float(m) for m in self.mlp_multipliers))
        object.__setattr__(self, "ssm_multipliers",
                           tuple(float(m) for m in self.ssm_multipliers))
        if self.attention not in ("dense", "flash"):
            raise ValueError(f"attention must be 'dense' or 'flash', "
                             f"got {self.attention!r}")
        if self.kernels not in ("on", "off", "auto"):
            raise ValueError(f"kernels must be 'on', 'off' or 'auto', "
                             f"got {self.kernels!r}")
        if self.kv_dtype != "bfloat16":
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}: the falcon_h1 family's K/V "
                f"pages are bfloat16 only")
        if len(self.mlp_multipliers) != 2 or len(self.ssm_multipliers) != 5:
            raise ValueError(
                "mlp_multipliers holds (gate, down), ssm_multipliers the "
                "five segments (z, x, B, C, dt)")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads, head_dim even")
        if self.ssm_heads * self.ssm_head_dim != self.ssm_dim \
                or self.ssm_heads % self.ssm_groups \
                or self.ssm_dim % self.ssm_groups:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must be mamba_d_ssm, and "
                "mamba_n_groups divide mamba_n_heads")

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[x; B; C]``."""
        return self.ssm_dim + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_width(self) -> int:
        """``W_in``'s outputs: ``[z | x | B | C | dt]``."""
        return self.ssm_dim + self.conv_width + self.ssm_heads

    @property
    def n_paged_layers(self) -> int:
        """Every layer owns K/V pages (``paged.init_paged_cache``)."""
        return self.n_layers

    @property
    def slot_state(self) -> dict:
        """What every slot holds beside its pages, as ``name -> (leading
        shape, shape a layer and slot, dtype)`` (``paged.init_paged_cache``
        lays it out ``[L, slots, ...]``): the float32 state and the
        convolution's float32 tail, in every layer."""
        return {
            "state": ((self.n_layers,),
                      (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                      "float32"),
            "conv": ((self.n_layers,),
                     (self.conv_kernel - 1, self.conv_width), "float32")}

    @property
    def mup(self) -> np.ndarray:
        """``ssm_in_multiplier`` times the five ``ssm_multipliers`` over
        ``W_in``'s segments ``[z | x | B | C | dt]`` (float32)."""
        widths = (self.ssm_dim, self.ssm_dim,
                  self.ssm_groups * self.ssm_state,
                  self.ssm_groups * self.ssm_state, self.ssm_heads)
        return self.ssm_in_multiplier * np.repeat(
            np.asarray(self.ssm_multipliers, np.float32), widths)

    @classmethod
    def from_widths(cls, widths: dict, **fields) -> "FalconH1Config":
        """The config of published ``config.json`` keys (``WIDTH_FIELDS``;
        a key the family lacks is an error, a key left out keeps
        Falcon-H1-34B-Instruct's value)."""
        return cls(**{**fields, **config_fields("falcon_h1", widths)})

    @classmethod
    def tiny(cls, vocab_size: int = 512, max_seq: int = 256) \
            -> "FalconH1Config":
        """The CPU tests' preset: two layers, a query group of 5, four
        mixer heads of 8 in two groups, sub-chunks of 8, and every
        multiplier away from 1."""
        return cls(vocab_size=vocab_size, dim=64, n_layers=2, n_heads=5,
                   n_kv_heads=1, head_dim=16, hidden_dim=96, ssm_dim=32,
                   ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
                   ssm_chunk=8, rope_theta=10_000.0,
                   attention_in_multiplier=0.7, max_seq=max_seq)


# -- parameters --------------------------------------------------------------

#: ``W_q``'s gain over its fan-in scale under the random init
#: (:func:`init_params`).
INIT_QUERY_GAIN = 2.0


def init_params(key: jax.Array, config: FalconH1Config) -> dict:
    """Random weights in ``config.dtype``, built leaf by leaf and layer
    by layer (``deepseek._normal``: normal, fan-in scaled), each matrix
    that a multiplier follows drawn at its fan-in scale OVER that
    multiplier -- the embedding, ``W_k``, ``W_o``, ``W_in`` column by
    segment, ``W_out``, the MLP's ``W_gate`` and ``W_down`` and the
    head -- so that under the multipliers every sub-layer adds a
    unit-scale term to a unit-scale stream and the logits spread about
    1; ``W_q`` at ``INIT_QUERY_GAIN`` times its scale, so that a query
    weighs a handful of keys.  The mixer's own parameters as the
    ``mamba_ssm`` layer initialises them: ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of a log-uniform ``dt`` in [1e-3,
    1e-1], ``D = 1`` (these three float32), the convolution and its
    bias at PyTorch's ``Conv1d`` default, uniform in +-(1 / d_conv)^1/2.
    ``layers`` is one stacked tree (leading axis: the layer)."""
    c = config
    dtype = jnp.dtype(c.dtype)
    keys = jax.random.split(key, 16)
    stack = c.n_layers
    q_width, kv_width = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    m_gate, m_down = c.mlp_multipliers

    def dense(k, shape, fan_in, multiplier=1.0):
        return _normal(k, shape=shape, fan_in=fan_in * multiplier ** 2,
                       dtype=dtype, stack=stack)

    bound = c.conv_kernel ** -0.5
    step = jnp.exp(jax.random.uniform(
        keys[10], (stack, c.ssm_heads), minval=jnp.log(1e-3),
        maxval=jnp.log(1e-1)))
    w_in = dense(keys[4], (c.dim, c.in_width), c.dim)
    layers = {
        "norm": jnp.ones((stack, c.dim), dtype=dtype),
        "wq": dense(keys[1], (c.dim, q_width), c.dim / INIT_QUERY_GAIN ** 2),
        "wk": dense(keys[2], (c.dim, kv_width), c.dim, c.key_multiplier),
        "wv": dense(keys[3], (c.dim, kv_width), c.dim),
        "wo": dense(keys[5], (q_width, c.dim), q_width,
                    c.attention_out_multiplier),
        "w_in": jax.lax.map(
            lambda w: (w.astype(jnp.float32) / c.mup).astype(dtype), w_in),
        "conv": jax.random.uniform(
            keys[6], (stack, c.conv_kernel, c.conv_width),
            minval=-bound, maxval=bound).astype(dtype),
        "conv_bias": jax.random.uniform(
            keys[7], (stack, c.conv_width), minval=-bound,
            maxval=bound).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(
            keys[8], (stack, c.ssm_heads), minval=1.0, maxval=16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d_skip": jnp.ones((stack, c.ssm_heads), jnp.float32),
        "ssm_norm": jnp.ones((stack, c.ssm_dim), dtype=dtype),
        "w_out": dense(keys[9], (c.ssm_dim, c.dim), c.ssm_dim,
                       c.ssm_out_multiplier),
        "ffn_norm": jnp.ones((stack, c.dim), dtype=dtype),
        "w_gate": dense(keys[11], (c.dim, c.hidden_dim), c.dim, m_gate),
        "w_up": dense(keys[12], (c.dim, c.hidden_dim), c.dim),
        "w_down": dense(keys[13], (c.hidden_dim, c.dim), c.hidden_dim,
                        m_down)}
    return {
        "embed": _normal(keys[0], shape=(c.vocab_size, c.dim),
                         fan_in=c.embedding_multiplier ** 2, dtype=dtype),
        "layers": layers,
        "final_norm": jnp.ones((c.dim,), dtype=dtype),
        "unembed": _normal(keys[14], shape=(c.dim, c.vocab_size),
                           fan_in=c.dim * c.lm_head_multiplier ** 2,
                           dtype=dtype)}


# -- the cache ----------------------------------------------------------------

def init_cache(config, batch, max_seq=None):
    raise ValueError(
        "kv_page_tokens=0: the falcon_h1 family keeps its K/V in pages "
        "beside its state pool only; set kv_page_tokens > 0")


def check_serving(*, speculative: str, prefix_cache: bool,
                  kv_page_tokens: int) -> None:
    """What the family does not serve, refused when the batcher is
    created, each by its parameter's name."""
    if not kv_page_tokens:
        init_cache(None, 0)
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: a rejected draft would have "
            f"to roll the falcon_h1 family's recurrent state back and no "
            f"snapshot of it is kept; use speculative: off")
    if prefix_cache:
        raise ValueError(
            "prefix_cache=on: a shared prefix of the falcon_h1 family is "
            "a snapshot of the recurrent state as well as pages, and "
            "none is kept; serve with prefix_cache: off")


# -- layers -------------------------------------------------------------------

def _layer(c: FalconH1Config, layer, x, attend, mix):
    """One layer over ``x [N, D]``: attention and mixer side by side
    off one norm, then the MLP.  ``attend(layer, a')`` -> the heads'
    output ``[N, H * hd]``; ``mix(layer, a)`` -> the mixer's output
    ``[N, D]``, its multiplier applied."""
    a = rms_norm(x, layer["norm"], c.norm_eps)
    attended = attend(layer, a * c.attention_in_multiplier)
    h = x + (attended @ layer["wo"]) * c.attention_out_multiplier \
        + mix(layer, a)
    u = rms_norm(h, layer["ffn_norm"], c.norm_eps)
    m_gate, m_down = c.mlp_multipliers
    ffn = (jax.nn.silu((u @ layer["w_gate"]) * m_gate)
           * (u @ layer["w_up"])) @ layer["w_down"]
    return h + ffn * m_down


def _qkv(c: FalconH1Config, layer, a, rope_table, positions):
    """``a [B, S, D]`` at ``positions [B, S]`` -> (q ``[B, S, H, hd]``,
    k, v ``[B, S, K, hd]``), q and k rotated, k times its multiplier."""
    b, s, _ = a.shape
    q = (a @ layer["wq"]).reshape(b, s, c.n_heads, c.head_dim)
    k = (a @ layer["wk"]) * c.key_multiplier
    k = k.reshape(b, s, c.n_kv_heads, c.head_dim)
    v = (a @ layer["wv"]).reshape(b, s, c.n_kv_heads, c.head_dim)
    return (apply_rope(q, rope_table, positions),
            apply_rope(k, rope_table, positions), v)


def _projections(c: FalconH1Config, layer, a):
    """``a [N, D]`` -> (z ``[N, d_ssm]``, pre-convolution ``xBC [N,
    conv_width]``, ``dt`` before its softplus ``[N, H_s]``), float32,
    the multipliers in."""
    zxbcdt = jnp.dot(a, layer["w_in"], preferred_element_type=jnp.float32) \
        * c.mup
    return (zxbcdt[:, :c.ssm_dim],
            zxbcdt[:, c.ssm_dim:c.ssm_dim + c.conv_width],
            zxbcdt[:, c.ssm_dim + c.conv_width:])


def _convolve(c: FalconH1Config, layer, window):
    """The causal depthwise convolution, its bias and SiLU over ``window
    [.., N + d_conv - 1, C]`` (the tail, then the rows) -> ``[.., N,
    C]`` float32: row ``t`` sees ``window[t : t + d_conv]``."""
    width = c.conv_kernel
    n = window.shape[-2] - width + 1
    mixed = sum(layer["conv"][j] * jax.lax.slice_in_dim(
        window, j, j + n, axis=window.ndim - 2) for j in range(width))
    return jax.nn.silu(mixed + layer["conv_bias"])


def _split_conv(c: FalconH1Config, mixed):
    """Convolved channels ``[N, C]`` -> (x ``[N, H_s, P]``, B, C ``[N,
    G, N_state]``)."""
    n, groups = mixed.shape[0], c.ssm_groups
    width = groups * c.ssm_state
    return (mixed[:, :c.ssm_dim].reshape(n, c.ssm_heads, c.ssm_head_dim),
            mixed[:, c.ssm_dim:c.ssm_dim + width]
            .reshape(n, groups, c.ssm_state),
            mixed[:, c.ssm_dim + width:].reshape(n, groups, c.ssm_state))


def _mix_output(c: FalconH1Config, layer, y, z, dtype):
    """``W_out(RMSNorm_G(y * silu(z))) ssm_out_multiplier``: ``y [N,
    H_s, P]`` and ``z [N, d_ssm]`` float32, the norm over each of ``G``
    groups of the channels, after the gate."""
    n = z.shape[0]
    gated = (y.reshape(n, c.ssm_dim) * jax.nn.silu(z)) \
        .reshape(n, c.ssm_groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + c.norm_eps)
    normed = normed.reshape(n, c.ssm_dim) \
        * layer["ssm_norm"].astype(jnp.float32)
    return (normed.astype(dtype) @ layer["w_out"]) * c.ssm_out_multiplier


def _layer_slice(params: dict, index):
    """Layer ``index`` (traced) of the stacked tree, each leaf sliced
    where it is used: the layer scan closes over the stack."""
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_index_in_dim(leaf, index, 0, False),
        params["layers"])


def _head(params: dict, c: FalconH1Config, hidden):
    """The final RMSNorm and the untied head, float32 logits times
    ``lm_head_multiplier``."""
    normed = rms_norm(hidden, params["final_norm"], c.norm_eps)
    return jnp.dot(normed, params["unembed"],
                   preferred_element_type=jnp.float32) \
        * c.lm_head_multiplier


def _embed(params: dict, c: FalconH1Config, tokens):
    return params["embed"][tokens] * c.embedding_multiplier


# -- admission ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def _prefill_into_slot_jit(params: dict, config: FalconH1Config,
                           tokens: jax.Array, cache: dict,
                           slot: jax.Array, start: jax.Array,
                           last: jax.Array):
    """One prompt chunk ``tokens [1, S]`` of ONE sequence into ``slot``
    at offset ``start`` (page-aligned, S whole pages; it may spill past
    the slot's extent into the trash page).  Scans the layers with the
    pools closed over; the chunk's K/V rows, the new states and the new
    tails leave the scan as its only cache-related outputs and are
    written in place once, after it.  The state a layer starts from is
    the slot's, or zero where ``start == 0``; positions past ``last``
    change neither state nor tail.  Logits for position ``last`` of the
    chunk ALONE (``[1, 1, vocab]``)."""
    c = config
    if not is_paged(cache) or "state" not in cache:
        raise ValueError("the falcon_h1 family admits into a paged cache "
                         "with a state pool (paged.init_paged_cache)")
    s = tokens.shape[1]
    page_tokens = pool_page_tokens(cache)
    if s % page_tokens:
        raise ValueError(
            f"paged prefill chunk of {s} tokens is not a whole number "
            f"of {page_tokens}-token pages")
    width = c.conv_kernel
    kernel = kernels_on(c)
    fresh = start == 0
    real = jnp.arange(s) <= last                               # [S]
    positions = (start + jnp.arange(s))[None, :]               # [1, S]
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    # the slot's pages, then a chunk's worth of the trash page: a view
    # the chunk fits in wherever it starts
    table = jnp.concatenate([
        cache["page_table"][slot],
        jnp.zeros((s // page_tokens,), jnp.int32)])

    def layer_step(x, index):
        layer = _layer_slice(params, index)
        carried = {}

        def attend(layer, a):
            q, k, v = _qkv(c, layer, a[None], rope_table, positions)
            carried["rows"] = (k.reshape(s, -1), v.reshape(s, -1))

            def laid(side, new):        # the slot's rows, the chunk in
                rows = gather_rows(cache[side], table[None], index)
                return _grouped(jax.lax.dynamic_update_slice(
                    rows, new[None], (0, start, 0)), c.n_kv_heads)
            k_rows = laid("k", carried["rows"][0])
            v_rows = laid("v", carried["rows"][1])
            if c.attention == "flash":
                from ..ops.pallas_attention import flash_attention
                attended = flash_attention(q, k_rows, v_rows,
                                           q_offset=start)
            else:
                attended = attention_prefill(q, k_rows, v_rows, positions)
            return attended.reshape(s, -1)

        def mix(layer, a):
            z, xbc, dt = _projections(c, layer, a)
            tail = jnp.where(fresh, 0.0, cache["conv"][index, slot])
            window = jnp.concatenate([tail, xbc], axis=0)
            x, b, cc = _split_conv(c, _convolve(c, layer, window))
            dt = jnp.where(real[:, None],
                           jax.nn.softplus(dt + layer["dt_bias"]), 0.0)
            state = jnp.where(fresh, 0.0, cache["state"][index, slot])
            y, state = ssd_chunk_scan(
                x, dt, -jnp.exp(layer["a_log"]), b, cc, layer["d_skip"],
                state, chunk=c.ssm_chunk, kernel=kernel)
            carried["state"] = (state, jax.lax.dynamic_slice_in_dim(
                window, last + 1, width - 1, axis=0))
            return _mix_output(c, layer, y, z, a.dtype)

        x = _layer(c, layer, x, attend, mix)
        return x, (*carried["rows"], *carried["state"])

    hidden, (k_rows, v_rows, states, tails) = jax.lax.scan(
        layer_step, _embed(params, c, tokens[0]), jnp.arange(c.n_layers))
    sampled = jax.lax.dynamic_slice_in_dim(hidden, last, 1, axis=0)

    def per_slot(pool, new):            # [L, ...] -> [L, 1, ...] at slot
        return jax.lax.dynamic_update_slice(
            pool, new[:, None].astype(pool.dtype),
            (0, slot) + (0,) * (pool.ndim - 2))

    def paged(pool, new):               # [L, S, C], page by page
        for j in range(s // page_tokens):
            pool = jax.lax.dynamic_update_slice(
                pool, new[:, None, j * page_tokens:(j + 1) * page_tokens],
                (0, table[start // page_tokens + j], 0, 0))
        return pool

    cache = {**cache, "k": paged(cache["k"], k_rows),
             "v": paged(cache["v"], v_rows),
             "state": per_slot(cache["state"], states),
             "conv": per_slot(cache["conv"], tails)}
    return _head(params, c, sampled[None]), cache


def prefill_into_slot(params: dict, config: FalconH1Config,
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

def _decode_step_impl(params: dict, config: FalconH1Config,
                      tokens: jax.Array, cache: dict, lengths: jax.Array,
                      active: jax.Array | None = None):
    """One token per sequence: ``tokens [B]``, written at ``lengths
    [B]``.  ``active [B]`` marks the rows that decode (default: those
    not at the trash position, as the batcher's dense tick marks them):
    any other row keeps its state and tail, attends nothing, and its
    K/V write lands wherever its position says (the trash position).
    The state and tail pools ride the layer scan as a carry and are
    advanced in place; the K/V pools are closed over and written once,
    after it."""
    c = config
    b = tokens.shape[0]
    kernel = kernels_on(c)
    table = cache["page_table"]
    if active is None:
        active = lengths != cache_extent(cache) - 1
    attend_to = jnp.where(active, lengths, 0)
    positions = lengths[:, None]                                # [B, 1]
    rope_table = rope_frequencies(c.head_dim, c.max_seq, c.rope_theta)
    if kernel:
        from ..ops.pallas_decode import (_split_paged,
                                         flash_decode_append_paged)
        k_view, v_view = _split_paged(cache["k"]), _split_paged(cache["v"])

    def layer_step(carry, index):
        x, (state_pool, conv_pool) = carry
        layer = _layer_slice(params, index)
        carried = {}

        def attend(layer, a):
            q, k, v = _qkv(c, layer, a[:, None], rope_table, positions)
            carried["rows"] = (k, v)
            if kernel:
                attended = flash_decode_append_paged(
                    q, k_view, v_view, index, k, v, table, attend_to)
            else:
                attended = attention_decode_append(
                    q, _grouped(gather_rows(cache["k"], table, index),
                                c.n_kv_heads),
                    _grouped(gather_rows(cache["v"], table, index),
                             c.n_kv_heads), k, v, attend_to)
            return attended.reshape(b, -1)

        def mix(layer, a):
            z, xbc, dt = _projections(c, layer, a)
            window = jnp.concatenate(
                [conv_pool[index], xbc[:, None, :]], axis=1)  # [B, w, C]
            x, bb, cc = _split_conv(c, _convolve(c, layer, window)[:, 0])
            y, pool = ssd_decode_step(
                x, jax.nn.softplus(dt + layer["dt_bias"]),
                -jnp.exp(layer["a_log"]), bb, cc, layer["d_skip"],
                state_pool, index, active, kernel=kernel)
            tails = jnp.where(active[:, None, None], window[:, 1:],
                              conv_pool[index])
            carried["pools"] = (pool, jax.lax.dynamic_update_index_in_dim(
                conv_pool, tails, index, 0))
            return _mix_output(c, layer, y, z, a.dtype)

        x = _layer(c, layer, x, attend, mix)
        return (x, carried["pools"]), carried["rows"]

    (hidden, (state_pool, conv_pool)), (k_rows, v_rows) = jax.lax.scan(
        layer_step,
        (_embed(params, c, tokens), (cache["state"], cache["conv"])),
        jnp.arange(c.n_layers))
    cache = {**cache, "state": state_pool, "conv": conv_pool,
             **_scatter_positions(c, cache, k_rows, v_rows,
                                  lengths[:, None])}
    return _head(params, c, hidden), cache


_decode_step_jit = partial(jax.jit, static_argnames=("config",),
                           donate_argnames=("cache",))(_decode_step_impl)


def decode_step(params: dict, config: FalconH1Config, tokens: jax.Array,
                cache: dict, lengths: jax.Array,
                active: jax.Array | None = None):
    """(logits ``[B, vocab]``, cache) of one decode step -- the device
    loop's own body."""
    return _decode_step_jit(params, config, tokens, cache, lengths, active)


def state_bytes_per_row(c: FalconH1Config) -> int:
    """What a decoding row's mixers move a step: the state once in and
    once out, the convolution's tail the same, in every layer."""
    state = c.ssm_heads * c.ssm_head_dim * c.ssm_state * 4
    tail = (c.conv_kernel - 1) * c.conv_width * 4
    return c.n_layers * 2 * (state + tail)


def kv_bytes_per_token(c: FalconH1Config) -> int:
    """One cached token's K and V over every layer."""
    return c.n_layers * 2 * c.n_kv_heads * c.head_dim \
        * jnp.dtype(c.kv_dtype).itemsize


@partial(jax.jit, static_argnames=("config", "ring", "top_k"),
         donate_argnames=("cache",))
def _decode_loop_jit(params: dict, config: FalconH1Config,
                     tokens: jax.Array, cache: dict, lengths: jax.Array,
                     active: jax.Array, budget: jax.Array,
                     temperatures: jax.Array, eos: jax.Array,
                     history: jax.Array, key: jax.Array, *, ring: int,
                     top_k: int = 0):
    """This family's device loop: ``olmo_hybrid``'s
    (:func:`~.olmo_hybrid.run_decode_loop`) over this family's step."""
    return run_decode_loop(
        _decode_step_impl, float(state_bytes_per_row(config)),
        float(kv_bytes_per_token(config)), params, config, tokens, cache,
        lengths, active, budget, temperatures, eos, history, key,
        ring=ring, top_k=top_k)


def decode_loop(params: dict, config: FalconH1Config, tokens: jax.Array,
                cache: dict, lengths: jax.Array, active: jax.Array,
                budget: jax.Array, temperatures: jax.Array,
                eos: jax.Array, history: jax.Array, key: jax.Array, *,
                ring: int, speculative: str = "off", top_k: int = 0,
                **_):
    """Device-resident generation block: ``llama.decode_loop``'s
    twelve results, then ``stats`` (see :func:`loop_stats`)."""
    if speculative != "off":
        raise ValueError(
            f"speculative={speculative!r}: the falcon_h1 family serves "
            f"speculative: off")
    return _decode_loop_jit(params, config, tokens, cache, lengths,
                            active, budget, temperatures, eos, history,
                            key, ring=int(ring), top_k=int(top_k))
