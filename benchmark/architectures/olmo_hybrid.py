"""The Olmo-Hybrid family (``model_type: olmo_hybrid``; Olmo-Hybrid-7B is
the configuration the benchmark runs), as the harness knows it: found by
the ``"architecture": "olmo_hybrid"`` of a configuration file.  The four
pieces a family brings (``benchmark/architectures/__init__.py``) --
``check_reference``, ``width_differences``, ``element_parameters``,
``decode_step`` -- and the counts of its two kernels
(``gated_delta_decode``, ``gated_delta_chunk``).

**The plain reference** (``forward``): the forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence, no cache, no
kernel, no batching, nothing of ``aiko_services_tpu.models``.  Layer
``i`` is of the kind ``layer_types[i]`` says; no biases, untied head,
final RMSNorm before the head, eps ``rms_norm_eps`` everywhere.

Block, both kinds (``_residual``; ASSUMED -- the source's keys do not
place the norms, this is the Olmo 2 / Olmo 3 convention of a norm on
each sub-layer's OUTPUT)::

    h = x + RMSNorm(mix(x));   out = h + RMSNorm(SwiGLU(h))

*Full layer* (``full_attention``): ``q, k, v = W_q x, W_k x, W_v x``
(``num_attention_heads`` x ``hidden / heads``; ``head_dim`` is not
given); RMSNorm over the WHOLE width of ``q`` and of ``k`` before the
head split; causal softmax at scale ``head_dim^-1/2``; no rotary
(``rope_theta: null``, ASSUMED to mean none: position reaches these
layers through the recurrent layers below them); ``W_o``.

*Linear layer* (``linear_attention``: Gated DeltaNet, arXiv:2412.06464,
as the flash-linear-attention library's ``GatedDeltaNet`` builds it),
``H = linear_num_value_heads``, ``d_k = linear_key_head_dim``, ``d_v =
linear_value_head_dim``, input ``x_t``:

- ``q~, k~, v~ = W_q x, W_k x, W_v x`` (H d_k, H d_k, H d_v wide); each
  channel through a causal depthwise convolution of width
  ``linear_conv_kernel_dim`` over time, then SiLU: ``c_t = silu(sum_j
  w_j * u_{t-3+j})``; per head ``q = q~ / (|q~|^2 + 1e-6)^1/2 d_k^-1/2``,
  ``k = k~ / (|k~|^2 + 1e-6)^1/2``;
- ``beta_t = 2 sigmoid(W_b x_t)`` (the 2 is ``linear_allow_neg_eigval``);
  ``g_t = -exp(A_log) softplus(W_a x_t + dt_bias)``, ``alpha_t =
  exp(g_t)``;
- the state ``S in R^{H x d_k x d_v}``, ``S_0 = 0``, TOKEN BY TOKEN (a
  ``lax.scan`` over time: it shares no algorithm with the served chunk
  scan): ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t
  S_{t-1})^T k_t)^T``; ``o_t = S_t^T q_t``;
- ``y_t = W_o [RMSNorm_dv(o_t) * silu(W_g x_t)]`` (one norm weight of
  ``d_v``, per head).

It is given the weights the system serves, cast to float32 IN BLOCKS: a
layer at a time, the head ``HEAD_BLOCK`` vocabulary rows at a time, so
that it fits beside 13 GB of served state.  Departures from the source:
the weights are random (the configuration's ``assumed``: ``A_log``,
``dt_bias`` and the convolution drawn as that library's layer draws
them), and the two assumptions above.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

HEAD_BLOCK = 16_384
UNIT_EPS = 1e-6                 # inside the q / k unit-length root
LINEAR, FULL = "linear_attention", "full_attention"

# Published config.json key -> the served config's field.
WIDTH_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "hidden_dim",
    "layer_types": "layer_types",
    "linear_num_key_heads": "linear_key_heads",
    "linear_num_value_heads": "linear_value_heads",
    "linear_key_head_dim": "linear_key_dim",
    "linear_value_head_dim": "linear_value_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel",
    "linear_allow_neg_eigval": "linear_allow_neg_eigval",
    "rms_norm_eps": "norm_eps"}

# Published keys with no field: what the program's family implements,
# and the only value of each it can serve.
IMPLEMENTED = {
    "model_type": "olmo_hybrid", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "rope_parameters": {"rope_theta": None}}


# -- the plain reference ------------------------------------------------------

def _float32(tree):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32),
                                  tree)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _fp8(x, on: bool):
    """The control's rounding: ``x`` to fp8 (e4m3), the nearest
    precision below the bfloat16 the configuration states.
    ``reduce_precision``, not a cast there and back: the chip's compiler
    drops such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) \
        if on else x


def _bfloat16(x, on: bool):
    """The second control's rounding: to bfloat16's 8 bits of mantissa."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) \
        if on else x


def _residual(x, weights, mixed, eps, fp8):
    """WHERE THE NORMS GO (assumed; module docstring), and nowhere
    else: ``h = x + RMSNorm(mix(x))``, ``out = h + RMSNorm(SwiGLU(h))``.
    ``fp8`` (the control) rounds what the feed-forward multiplies."""
    h = x + _rms_norm(mixed, weights["mix_norm"], eps)
    rounded = _fp8(h, fp8)
    ffn = (jax.nn.silu(rounded @ weights["w_gate"])
           * (rounded @ weights["w_up"])) @ weights["w_down"]
    return h + _rms_norm(ffn, weights["ffn_norm"], eps)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "fp8"))
def _full_layer(x, layer, *, heads, kv_heads, eps, fp8=False):
    """x [S, D] -> the full-attention layer's output."""
    weights = _float32(layer)
    length = x.shape[0]
    rounded = _fp8(x, fp8)
    q = _rms_norm(rounded @ weights["wq"], weights["q_norm"], eps)
    k = _rms_norm(rounded @ weights["wk"], weights["k_norm"], eps)
    v = rounded @ weights["wv"]
    q = q.reshape(length, heads, -1)
    k = jnp.repeat(k.reshape(length, kv_heads, -1), heads // kv_heads, 1)
    v = jnp.repeat(v.reshape(length, kv_heads, -1), heads // kv_heads, 1)
    scores = jnp.einsum("shd,thd->hst", q, k) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1),
                          v).reshape(length, -1)
    return _residual(x, weights, attended @ weights["wo"], eps, fp8)


@partial(jax.jit, static_argnames=("heads", "key_dim", "value_dim",
                                   "neg_eigval", "eps", "fp8",
                                   "bf16_state"))
def _linear_layer(x, layer, *, heads, key_dim, value_dim, neg_eigval, eps,
                  fp8=False, bf16_state=False):
    """x [S, D] -> the gated delta-rule layer's output, the state
    followed token by token from zero.  ``bf16_state`` (the second
    control) rounds the state to bfloat16 after every token."""
    weights = _float32(layer)
    length = x.shape[0]
    rounded = _fp8(x, fp8)
    rows = rounded @ weights["w_qkv"]                    # [S, C]
    width = weights["conv"].shape[0]
    window = jnp.concatenate(
        [jnp.zeros((width - 1, rows.shape[1]), jnp.float32), rows])
    mixed = jax.nn.silu(sum(weights["conv"][j] * window[j:j + length]
                            for j in range(width)))
    q = mixed[:, :heads * key_dim].reshape(length, heads, key_dim)
    k = mixed[:, heads * key_dim:2 * heads * key_dim] \
        .reshape(length, heads, key_dim)
    v = mixed[:, 2 * heads * key_dim:].reshape(length, heads, value_dim)
    q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + UNIT_EPS) \
        / jnp.sqrt(jnp.float32(key_dim))
    k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + UNIT_EPS)
    ab = rounded @ weights["w_ab"]
    alpha = jnp.exp(-jnp.exp(weights["a_log"]) * jax.nn.softplus(
        ab[:, :heads] + weights["dt_bias"]))
    beta = jax.nn.sigmoid(ab[:, heads:]) * (2.0 if neg_eigval else 1.0)

    def token(state, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs
        decayed = alpha_t[:, None, None] * state
        predicted = jnp.einsum("hkv,hk->hv", decayed, k_t)
        state = decayed + k_t[:, :, None] \
            * (beta_t[:, None] * (v_t - predicted))[:, None, :]
        state = _bfloat16(state, bf16_state)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(
        token, jnp.zeros((heads, key_dim, value_dim), jnp.float32),
        (q, k, v, alpha, beta))
    gated = _rms_norm(out, weights["out_norm"], eps).reshape(length, -1) \
        * jax.nn.silu(rounded @ weights["w_out_gate"])
    return _residual(x, weights, gated @ weights["wo"], eps, fp8)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, block):
    return x @ block.astype(jnp.float32)


def _take(tree, index):
    return jax.tree_util.tree_map(lambda leaf: leaf[index], tree)


def forward(params: dict, widths: dict, tokens, positions,
            control: str | None = None):
    """Logits ``[len(positions), vocab]`` of the float32 forward pass
    over ``tokens`` (one sequence) at ``positions``.  ``widths``:
    published keys.  ``control``: ``fp8_activations`` -- every block
    multiplies fp8-rounded inputs, the reference in the nearest
    precision below the stated one; ``bf16_state`` -- the recurrent
    state rounded to bfloat16 after every token."""
    if control not in (None, "fp8_activations", "bf16_state"):
        raise ValueError(
            f"control={control!r}: fp8_activations | bf16_state")
    types = list(widths["layer_types"])
    eps = float(widths["rms_norm_eps"])
    fp8 = control == "fp8_activations"
    linear = dict(
        heads=int(widths["linear_num_value_heads"]),
        key_dim=int(widths["linear_key_head_dim"]),
        value_dim=int(widths["linear_value_head_dim"]),
        neg_eigval=bool(widths["linear_allow_neg_eigval"]), eps=eps,
        fp8=fp8, bf16_state=control == "bf16_state")
    full = dict(heads=int(widths["num_attention_heads"]),
                kv_heads=int(widths["num_key_value_heads"]), eps=eps,
                fp8=fp8)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for index, kind in enumerate(types):
            if kind == LINEAR:
                x = _linear_layer(
                    x, _take(params["linear"], types[:index].count(LINEAR)),
                    **linear)
            else:
                x = _full_layer(
                    x, _take(params["full"], types[:index].count(FULL)),
                    **full)
        x = _final_norm(x[jnp.asarray(positions)], params["final_norm"],
                        eps=eps)
        vocab = params["unembed"].shape[1]
        logits = jnp.concatenate(
            [_head_block(x, params["unembed"][:, first:first + HEAD_BLOCK])
             for first in range(0, vocab, HEAD_BLOCK)], axis=-1)
        return np.asarray(jax.device_get(logits))


# -- the served side ----------------------------------------------------------

def served_logits(batcher, prompt, decode_steps: int):
    """The system's own path on one sequence, in the batcher's own pools
    (which must be idle): the prompt admitted chunk by chunk into slot 0
    through the batcher's admission program -- the state handed from
    chunk to chunk, the last chunk padded -- then greedy decode steps at
    the batcher's width, every other row sitting the step out.  Returns
    (logits ``[1 + decode_steps, vocab]``, the tokens decoded)."""
    from aiko_services_tpu.models import olmo_hybrid
    if batcher.active_count or batcher.blocks_in_flight:
        raise RuntimeError("the reference check needs an idle batcher")
    params, config = batcher.params, batcher.config
    chunk, slot = batcher.prefill_chunk, 0
    pages = batcher._pages
    if not pages.ensure(slot, pages.pages_for(
            len(prompt) + decode_steps + 1, batcher.kv_page_tokens)):
        raise RuntimeError("the reference check found no free pages")
    batcher._sync_page_table()
    try:
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            padded = np.zeros((1, chunk), dtype=np.int32)
            padded[0, :len(piece)] = piece
            logits, batcher.cache = olmo_hybrid.prefill_into_slot(
                params, config, jnp.asarray(padded), batcher.cache,
                jnp.int32(slot), jnp.int32(start),
                jnp.int32(len(piece) - 1))
        rows = [logits[0, 0].astype(jnp.float32)]
        decoded = []
        trash = batcher.max_seq - 1
        for step in range(decode_steps):
            token = jnp.argmax(rows[-1]).astype(jnp.int32)
            decoded.append(token)
            tokens = jnp.zeros((batcher.max_slots,), jnp.int32) \
                .at[slot].set(token)
            lengths = jnp.full((batcher.max_slots,), trash, jnp.int32) \
                .at[slot].set(len(prompt) + step)
            logits, batcher.cache = olmo_hybrid.decode_step(
                params, config, tokens, batcher.cache, lengths)
            rows.append(logits[slot].astype(jnp.float32))
        served = np.asarray(jax.device_get(jnp.stack(rows)))
        tokens = [int(token) for token in jax.device_get(decoded)]
    finally:
        pages.release(slot)
        batcher._sync_page_table()
    return served, tokens


def published_widths(served) -> dict:
    """The served config's fields under their published keys."""
    return {key: getattr(served, field)
            for key, field in WIDTH_FIELDS.items()}


def compare(batcher, seed: int, prompt_tokens: int, decode_steps: int,
            control: str | None = None) -> dict:
    """Served against reference on one seeded prompt (BOS then random
    lower-case bytes, as ByteTokenizer would give): worst and mean
    absolute logit difference over the last prompt position and every
    decode step, and how many greedy tokens agree."""
    rng = np.random.default_rng([seed31(seed), 33])
    prompt = [257] + rng.integers(97, 123, prompt_tokens - 1).tolist()
    served, decoded = served_logits(batcher, prompt, decode_steps)
    sequence = prompt + decoded
    positions = list(range(len(prompt) - 1, len(sequence)))
    reference = forward(batcher.params, published_widths(batcher.config),
                        sequence, positions, control)
    difference = np.abs(served - reference)
    return {"max_abs_diff": float(difference.max()),
            "mean_abs_diff": float(difference.mean()),
            "logit_std": float(reference.std()),
            "positions": len(positions),
            "argmax_agree": int((served.argmax(-1)
                                 == reference.argmax(-1)).sum())}


# -- the four pieces the harness asks an architecture for --------------------

def check_reference(batcher, seed: int, spec: dict,
                    control: str | None = None) -> dict:
    """Served (the batcher's own admission and decode programs, page
    pools and state pool) against the plain reference on one prompt
    made from ``seed``; ``spec`` is the configuration file's
    ``reference``.  ``control`` is the same check with the reference in
    a lower precision (:func:`forward`), which has to fail."""
    return compare(batcher, seed,
                   min(int(spec["prompt_tokens"]), batcher.max_seq // 2),
                   int(spec["decode_steps"]), control)


def width_differences(config: dict, batcher) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none): every published key with a
    field -- the per-layer list ``layer_types`` among them --, the
    served context, and the keys whose one implemented value the
    program's family is."""
    served = batcher.config

    def same(key, field):
        value = getattr(served, field)
        if key == "layer_types":
            return list(value) == list(config[key])
        return float(value) == float(config[key])

    wrong = [(key, config[key], getattr(served, field))
             for key, field in WIDTH_FIELDS.items() if not same(key, field)]
    if served.max_seq != config["max_position_embeddings"]:
        wrong.append(("max_position_embeddings",
                      config["max_position_embeddings"], served.max_seq))
    wrong.extend((key, config[key], value)
                 for key, value in IMPLEMENTED.items()
                 if config[key] != value)
    if served.dtype != "bfloat16" or served.kv_dtype != "bfloat16":
        wrong.append(("torch_dtype", "bfloat16",
                      (served.dtype, served.kv_dtype)))
    return wrong


def element_parameters(config: dict) -> dict:
    """What the file hands the LLM element: the family's name and its
    published widths (``elements/llm.py`` builds the served config from
    them; the definition's own ``max_seq`` is the served context)."""
    return {"family": "olmo_hybrid",
            "widths": {key: config[key] for key in WIDTH_FIELDS}}


# -- counts, from the published widths alone ---------------------------------

def _layers(widths: dict) -> tuple[int, int]:
    types = list(widths["layer_types"])
    return types.count(LINEAR), types.count(FULL)


def _linear_shape(widths: dict) -> tuple[int, int, int]:
    return (int(widths["linear_num_value_heads"]),
            int(widths["linear_key_head_dim"]),
            int(widths["linear_value_head_dim"]))


def state_bytes(widths: dict) -> int:
    """One slot's float32 state in ONE recurrent layer."""
    heads, key_dim, value_dim = _linear_shape(widths)
    return heads * key_dim * value_dim * 4


def tail_bytes(widths: dict, activation_bytes: int = 2) -> int:
    """One slot's convolution tail in one recurrent layer: the last
    ``kernel - 1`` rows of ``[q~; k~; v~]``."""
    heads, key_dim, value_dim = _linear_shape(widths)
    return (int(widths["linear_conv_kernel_dim"]) - 1) \
        * heads * (2 * key_dim + value_dim) * activation_bytes


def token_bytes(widths: dict, activation_bytes: int = 2) -> int:
    """What the recurrence of one layer takes in and gives out for one
    token: q, k, v in, o out (bfloat16), g and beta (float32)."""
    heads, key_dim, value_dim = _linear_shape(widths)
    return heads * (2 * key_dim + 2 * value_dim) * activation_bytes \
        + heads * 2 * 4


def recurrence_operations(widths: dict) -> float:
    """The recurrence's own operations for one token in one layer: a
    head decays its state, predicts, corrects and answers -- 7 d_k d_v
    (a multiply to decay; a multiply-add each to predict, to update and
    to answer)."""
    heads, key_dim, value_dim = _linear_shape(widths)
    return 7.0 * heads * key_dim * value_dim


def kv_bytes_per_token(widths: dict, cache_bytes: int = 2) -> int:
    """One token's K and V over the full-attention layers."""
    _, full = _layers(widths)
    heads = int(widths["num_attention_heads"])
    return full * 2 * int(widths["num_key_value_heads"]) \
        * (int(widths["hidden_size"]) // heads) * cache_bytes


def gated_delta_decode(widths: dict, rows: float,
                       context_tokens: float) -> dict:
    """What the decode step of the recurrence must do in one step, all
    recurrent layers: each live row's state once in and once out, its
    convolution tail the same, its token's q, k, v, g, beta in and o
    out.  The recurrence's own count, whatever implements it."""
    linear, _ = _layers(widths)
    return {
        "bytes": rows * linear * (2 * state_bytes(widths)
                                  + 2 * tail_bytes(widths)
                                  + token_bytes(widths)),
        "operations": rows * linear * recurrence_operations(widths)}


def prefill_chunk(config: dict) -> int:
    """The admission chunk of the configuration's LLM element."""
    for element in config.get("definition", {}).get("elements", ()):
        if element["name"] == config.get("llm_element"):
            return int(element["parameters"].get("prefill_chunk", 512))
    return 512


def gated_delta_chunk(widths: dict, rows: float = 0.0,
                      context_tokens: float = 0.0) -> dict:
    """What admission's scan must do for ONE prompt token, all
    recurrent layers (``readers/op_roofline.py`` multiplies by the
    slice's ``batcher.prefill_tokens``; rows and context do not enter):
    the token's q, k, v, g, beta in and o out, and its share of the
    slot's state handed in and out once a chunk."""
    linear, _ = _layers(widths)
    return {
        "bytes": linear * (token_bytes(widths)
                           + 2.0 * state_bytes(widths)
                           / prefill_chunk(widths)),
        "operations": linear * recurrence_operations(widths)}


def layer_weights(widths: dict) -> tuple[int, int]:
    """(a linear layer's, a full layer's) matrix weights: the mixer's
    projections and the SwiGLU."""
    hidden = int(widths["hidden_size"])
    heads, key_dim, value_dim = _linear_shape(widths)
    ffn = 3 * hidden * int(widths["intermediate_size"])
    kv_width = int(widths["num_key_value_heads"]) \
        * (hidden // int(widths["num_attention_heads"]))
    linear = hidden * heads * (2 * key_dim + 2 * value_dim) \
        + heads * value_dim * hidden + hidden * 2 * heads
    full = 2 * hidden * hidden + 2 * hidden * kv_width
    return linear + ffn, full + ffn


def decode_step(widths: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """What one decode step over ``rows`` live sequences of
    ``context_tokens`` mean context must do.  Bytes: every layer's
    bfloat16 weights once and the head (the embedding table is looked
    up, not streamed); each live row's recurrent state once in and once
    out with its tail; every live K/V row of the full layers once.
    Operations: two per weight a row multiplies by, the recurrence's
    own, and per cached token, head and full layer the score and the
    value sum over ``head_dim``."""
    linear, full = _layers(widths)
    hidden = int(widths["hidden_size"])
    linear_weights, full_weights = layer_weights(widths)
    weights = linear * linear_weights + full * full_weights \
        + hidden * int(widths["vocab_size"])
    recurrent = gated_delta_decode(widths, rows, context_tokens)
    return {
        "bytes": weights * weight_bytes + recurrent["bytes"]
        + rows * context_tokens * kv_bytes_per_token(widths, cache_bytes),
        "operations": 2.0 * weights * rows + recurrent["operations"]
        + rows * context_tokens * full * 4.0 * hidden}
