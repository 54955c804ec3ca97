"""The DeepSeek-V3 family (``model_type: deepseek_v3``; Moonlight-16B-A3B
is the configuration the benchmark runs), as the harness knows it:
found by the ``"architecture": "deepseek_v3"`` of a configuration file.
The four pieces a family brings (``benchmark/architectures/__init__.py``):
``check_reference``, ``width_differences``, ``element_parameters``,
``decode_step``.

**The plain reference** (``forward``): the family's forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` and the source's modelling code -- pre-norm residual
blocks (RMSNorm, eps ``rms_norm_eps``), no biases, untied head;

- latent attention: ``q = W_q h`` per head ``[q_nope; q_rope]``;
  ``[c; k_r] = W_kva h``; ``c~ = RMSNorm(c)`` (own weight, eps 1e-6);
  rotary embedding (rotate-half, base ``rope_theta`` over the
  ``qk_rope_head_dim`` dims) on ``q_rope`` and on the ONE ``k_r`` all
  heads share; ``[k_nope,i; v_i] = W_kvb,i c~``; scores ``(q_nope .
  k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal, softmax;
  EXPANDED attention over the whole sequence, ``W_o [o_1 .. o_H]``;
- feed-forward: SwiGLU of width ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``sigma = sigmoid(W_r
  h)``, the ``num_experts_per_tok`` largest ``sigma + b`` chosen (``b``
  = ``e_score_correction_bias``, selection only; ``n_group`` =
  ``topk_group`` = 1), gates ``routed_scaling_factor * sigma_e /
  (sum_chosen sigma + 1e-20)``, EVERY expert computed for EVERY token
  and weighted by its gate (nought where not chosen), plus one SwiGLU
  of width ``n_shared_experts * moe_intermediate_size``.

No cache, no kernel, no batching, no bfloat16, nothing of
``aiko_services_tpu.models`` (the served side, further down, imports
the program lazily).  It is given the weights the system serves, cast
to float32 IN BLOCKS: a layer at a time, the routed experts
``EXPERT_BLOCK`` at a time, the head in blocks of ``HEAD_BLOCK``
vocabulary rows -- so it fits beside 13 GB of served state.

Departures from the source: (1) the source de-interleaves the
``qk_rope_head_dim`` rotary dims before rotating; with random weights
that is a fixed permutation of ``W_q`` / ``W_kva`` columns and is left
out; (2) the weights are random (the configuration's ``assumed``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

EXPERT_BLOCK = 16
HEAD_BLOCK = 16_384
LATENT_NORM_EPS = 1e-6          # the source's norm class default

# Published config.json key -> the served config's field.
WIDTH_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "hidden_dim",
    "moe_intermediate_size": "moe_hidden_dim",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_dense_layers",
    "routed_scaling_factor": "routed_scaling_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}

# Published keys with no field: what the program's family implements,
# and the only value of each it can serve.
IMPLEMENTED = {
    "model_type": "deepseek_v3", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "q_lora_rank": None, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "ep_size": 1,
    "num_nextn_predict_layers": 0}


# -- the plain reference ------------------------------------------------------

def _float32(tree):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32),
                                  tree)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rotary(x, positions, theta):
    """x [S, ..., d]: rotate pairs (i, i + d/2) by position * theta **
    (-2i / d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inverse[None, :]
    angles = angles.reshape(angles.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _swiglu(h, weights):
    return (jax.nn.silu(h @ weights["w_gate"])
            * (h @ weights["w_up"])) @ weights["w_down"]


def _fp8(x, on: bool):
    """The control's rounding: ``x`` to fp8 (e4m3: 4 exponent bits, 3
    of mantissa), the nearest precision below the bfloat16 the
    configuration states.  ``reduce_precision``, not a cast there and
    back: the chip's compiler drops such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) \
        if on else x


@partial(jax.jit, static_argnames=("heads", "nope", "rope", "rank", "eps",
                                   "theta", "fp8"))
def _attention(x, layer, *, heads, nope, rope, rank, eps, theta,
               fp8=False):
    """x [S, D] -> (x + Attn(norm1(x)), norm2 of that).  ``fp8`` (the
    control) rounds the normed activations both blocks multiply by."""
    weights = _float32(layer)
    length = x.shape[0]
    positions = jnp.arange(length)
    h = _fp8(_rms_norm(x, weights["attn_norm"], eps), fp8)
    q = (h @ weights["wq"]).reshape(length, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], positions, theta)
    compressed = h @ weights["w_kva"]
    latent = _rms_norm(compressed[:, :rank], weights["latent_norm"],
                       LATENT_NORM_EPS)
    k_rope = _rotary(compressed[:, rank:], positions, theta)
    expanded = jnp.einsum("tr,rhn->thn", latent, weights["w_kvb"])
    k_nope, values = expanded[..., :nope], expanded[..., nope:]
    scores = (jnp.einsum("shn,thn->hst", q_nope, k_nope)
              + jnp.einsum("shn,tn->hst", q_rope, k_rope)) \
        / jnp.sqrt(jnp.float32(nope + rope))
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hst,thn->shn", jax.nn.softmax(scores, axis=-1),
                          values).reshape(length, -1)
    x = x + attended @ weights["wo"]
    return x, _fp8(_rms_norm(x, weights["mlp_norm"], eps), fp8)


NEAR_TIE = 0.02     # in units of the selection score, sigma + b


@partial(jax.jit, static_argnames=("top_k", "scale"))
def _route(h, w_router, bias, given=None, *, top_k, scale):
    """(each expert's share of each token ``[S, E]``, the experts it
    routed to ``[S, k]``, its own choice ``[S, k]``, and by how much
    ``given``'s worst expert falls short of its own k-th best selection
    score ``[S]``).  ``given [S, k]`` (the served side's selection)
    stands in for its own choice WHERE THE TWO DIFFER BY A NEAR-TIE: a
    token whose given experts all score within ``NEAR_TIE`` of its own
    k-th best.  Anywhere else it keeps its own, so a selection that is
    wrong by more than rounding still shows in the logits.  Gates are
    its own scores' either way."""
    scores = jax.nn.sigmoid(h @ w_router.astype(jnp.float32))
    selection = scores + bias
    best, own = jax.lax.top_k(selection, top_k)
    chosen, short = own, jnp.zeros(h.shape[:1], jnp.float32)
    if given is not None:
        short = best[:, -1] - jnp.take_along_axis(
            selection, given, axis=-1).min(-1)
        chosen = jnp.where((short <= NEAR_TIE)[:, None], given, own)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    share = (jax.nn.one_hot(chosen, scores.shape[-1])
             * gates[..., None]).sum(1)
    return share, chosen, own, short


@jax.jit
def _experts(h, share, block):
    """Every expert of ``block`` for every token, weighted by its share:
    h [S, D], share [S, e] -> [S, D]."""
    weights = _float32(block)
    hidden = jax.nn.silu(jnp.einsum("sd,edf->esf", h, weights["w_gate"])) \
        * jnp.einsum("sd,edf->esf", h, weights["w_up"])
    return jnp.einsum("se,esd->sd", share, jnp.einsum(
        "esf,efd->esd", hidden, weights["w_down"]))


@jax.jit
def _dense_ffn(h, weights):
    return _swiglu(h, _float32(weights))


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, block):
    return x @ block.astype(jnp.float32)


def _take(tree, index):
    return jax.tree_util.tree_map(lambda leaf: leaf[index], tree)


def forward(params: dict, widths: dict, tokens, positions, given=None,
            fp8: bool = False):
    """(logits ``[len(positions), vocab]`` of the float32 forward pass
    over ``tokens`` (one sequence) at ``positions``, its own choice of
    experts ``[sparse layers, len(tokens), k]``, and the worst
    shortfall of ``given`` ``[sparse layers, len(tokens)]``).
    ``widths``: published keys.  ``given`` (``[sparse layers,
    len(tokens), k]``, the served side's selections) is followed where
    it differs from the layer's own choice by a near-tie (``_route``).
    ``fp8`` is the CONTROL: the same pass with every block's normed
    input rounded to fp8 -- the reference computed in the nearest
    precision below the stated one."""
    heads = int(widths["num_attention_heads"])
    eps = float(widths["rms_norm_eps"])
    dense_layers = int(widths["first_k_dense_replace"])
    experts = int(widths["n_routed_experts"])
    attention = dict(
        heads=heads, nope=int(widths["qk_nope_head_dim"]),
        rope=int(widths["qk_rope_head_dim"]),
        rank=int(widths["kv_lora_rank"]), eps=eps,
        theta=float(widths["rope_theta"]), fp8=bool(fp8))
    small = ("attn_norm", "wq", "w_kva", "latent_norm", "w_kvb", "wo",
             "mlp_norm")
    selections, shortfalls = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for index in range(int(widths["num_hidden_layers"])):
            dense = index < dense_layers
            stack = params["dense"] if dense else params["sparse"]
            at = index if dense else index - dense_layers
            x, h = _attention(x, {key: stack[key][at] for key in small},
                              **attention)
            if dense:
                x = x + _dense_ffn(h, {key: stack[key][at] for key in
                                       ("w_gate", "w_up", "w_down")})
                continue
            share, _, chosen, short = _route(
                h, stack["w_router"][at], stack["router_bias"][at],
                None if given is None else jnp.asarray(given[at]),
                top_k=int(widths["num_experts_per_tok"]),
                scale=float(widths["routed_scaling_factor"]))
            selections.append(chosen)
            shortfalls.append(short)
            out = _dense_ffn(h, _take(stack["shared"], at))
            for first in range(0, experts, EXPERT_BLOCK):
                # (one block of one layer's experts is sliced out of
                # the stack at a time: a whole layer is 1.1 GB)
                last = min(first + EXPERT_BLOCK, experts)
                out = out + _experts(
                    h, share[:, first:last],
                    jax.tree_util.tree_map(
                        lambda leaf: leaf[at, first:last],
                        stack["experts"]))
            x = x + out
        x = _final_norm(x[jnp.asarray(positions)], params["final_norm"],
                        eps=eps)
        vocab = params["unembed"].shape[1]
        logits = jnp.concatenate(
            [_head_block(x, params["unembed"][:, first:first + HEAD_BLOCK])
             for first in range(0, vocab, HEAD_BLOCK)], axis=-1)
        return (np.asarray(jax.device_get(logits)),
                np.asarray(jax.device_get(jnp.stack(selections))),
                np.asarray(jax.device_get(jnp.stack(shortfalls))))


# -- the served side ----------------------------------------------------------

@partial(jax.jit, donate_argnums=0)
def _round_fp8(pool, pages):
    """The control: the latent rows of ``pages`` rounded to fp8 (e4m3:
    4 exponent bits, 3 of mantissa) -- a cache in the nearest precision
    below the one the configuration states.  ``reduce_precision``, not
    a cast there and back: the chip's compiler drops such a pair as
    excess precision and the control then rounds nothing."""
    return pool.at[:, pages].set(jax.lax.reduce_precision(
        pool[:, pages], exponent_bits=4, mantissa_bits=3))


def served_logits(batcher, prompt, decode_steps: int,
                  control: str | None = None):
    """The system's own path on one sequence, in the batcher's own
    cache and page pool (which must be idle): the prompt admitted chunk
    by chunk into slot 0 through the batcher's admission program, then
    greedy decode steps at the batcher's width through the latent
    pages.  Returns (logits ``[1 + decode_steps, vocab]``, the tokens
    decoded, the experts chosen ``[sparse layers, len(prompt) +
    decode_steps, k]``)."""
    from aiko_services_tpu.models import deepseek
    if batcher.active_count or batcher.blocks_in_flight:
        raise RuntimeError("the reference check needs an idle batcher")
    params, config = batcher.params, batcher.config
    chunk, slot = batcher.prefill_chunk, 0
    total = len(prompt) + decode_steps
    pages = batcher._pages
    if not pages.ensure(slot, pages.pages_for(total + 1,
                                              batcher.kv_page_tokens)):
        raise RuntimeError("the reference check found no free pages")
    batcher._sync_page_table()
    try:
        chosen = []
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            padded = np.zeros((1, chunk), dtype=np.int32)
            padded[0, :len(piece)] = piece
            logits, batcher.cache, selected = deepseek.prefill_into_slot(
                params, config, jnp.asarray(padded), batcher.cache,
                jnp.int32(slot), jnp.int32(start),
                jnp.int32(len(piece) - 1), selections=True)
            chosen.append(selected[:, :len(piece)])
        rows = [logits[0, 0].astype(jnp.float32)]
        if control == "fp8_cache":
            held = np.asarray(jax.device_get(
                batcher.cache["page_table"][slot]))
            batcher.cache["latent"] = _round_fp8(
                batcher.cache["latent"], jnp.asarray(held[held > 0]))
        elif control not in (None, "fp8_activations"):
            raise ValueError(
                f"control={control!r}: fp8_cache | fp8_activations")
        decoded = []
        trash = batcher.max_seq - 1
        for step in range(decode_steps):
            token = jnp.argmax(rows[-1]).astype(jnp.int32)
            decoded.append(token)
            tokens = jnp.zeros((batcher.max_slots,), jnp.int32) \
                .at[slot].set(token)
            lengths = jnp.full((batcher.max_slots,), trash, jnp.int32) \
                .at[slot].set(len(prompt) + step)
            logits, batcher.cache, selected = deepseek.decode_step(
                params, config, tokens, batcher.cache, lengths,
                selections=True)
            rows.append(logits[slot].astype(jnp.float32))
            chosen.append(selected[:, slot:slot + 1])
        served = np.asarray(jax.device_get(jnp.stack(rows)))
        tokens = [int(token) for token in jax.device_get(decoded)]
        selections = np.asarray(jax.device_get(
            jnp.concatenate(chosen, axis=1)))
    finally:
        pages.release(slot)
        batcher._sync_page_table()
    return served, tokens, selections


def published_widths(served) -> dict:
    """The served config's fields under their published keys."""
    return {key: getattr(served, field)
            for key, field in WIDTH_FIELDS.items()}


def compare(batcher, seed: int, prompt_tokens: int, decode_steps: int,
            control: str | None = None, free: bool = False) -> dict:
    """Served against reference on one seeded prompt (BOS then random
    lower-case bytes, as ByteTokenizer would give): worst and mean
    absolute logit difference over the last prompt position and every
    decode step, and whether the greedy tokens agree.

    The router: a near-tie at rank k / k + 1 flips under the bfloat16
    rounding of the router's input (on the chip in 15-20 % of the
    (token, sparse layer) pairs by the eighth layer: 64 selection
    scores lie ~0.02 apart at the cut, PERF.md section 6), and one flip
    moves a logit by up to 0.5 -- more than any precision the check is
    there to tell apart.  So the reference routes as the served side
    did where the two differ by a near-tie in ITS OWN scores
    (``NEAR_TIE``) and nowhere else, and the flips are counted, not
    hidden: ``router_flips`` of ``router_choices`` pairs differ,
    ``router_not_near_ties`` of them by more than a near-tie (none, on
    a sound router), ``router_worst_shortfall`` is the largest.
    ``free`` adds ``free_max_abs_diff``: the same comparison with the
    reference routing by its own scores throughout."""
    rng = np.random.default_rng([seed31(seed), 31])
    prompt = [257] + rng.integers(97, 123, prompt_tokens - 1).tolist()
    served, decoded, served_chosen = served_logits(
        batcher, prompt, decode_steps, control)
    sequence = prompt + decoded
    positions = list(range(len(prompt) - 1, len(sequence)))
    widths = published_widths(batcher.config)
    reference, own, short = forward(
        batcher.params, widths, sequence, positions, given=served_chosen,
        fp8=control == "fp8_activations")
    difference = np.abs(served - reference)
    flips = (np.sort(served_chosen, -1) != np.sort(own, -1)).any(-1)
    result = {"max_abs_diff": float(difference.max()),
              "mean_abs_diff": float(difference.mean()),
              "logit_std": float(reference.std()),
              "positions": len(positions),
              "argmax_agree": int((served.argmax(-1)
                                   == reference.argmax(-1)).sum()),
              "router_flips": int(flips.sum()),
              "router_choices": int(flips.size),
              "router_not_near_ties": int((short > NEAR_TIE).sum()),
              "router_worst_shortfall": float(short.max())}
    if free:
        result["free_max_abs_diff"] = float(np.abs(
            served - forward(batcher.params, widths, sequence,
                             positions)[0]).max())
    return result


# -- the four pieces the harness asks an architecture for --------------------

def check_reference(batcher, seed: int, spec: dict,
                    control: str | None = None) -> dict:
    """Served (the batcher's own admission program and latent pages)
    against the plain reference on one prompt made from ``seed``;
    ``spec`` is the configuration file's ``reference``.  ``control``
    is the same check in the nearest precision below the stated one,
    which has to fail: ``fp8_activations`` (the reference's own blocks
    multiply fp8-rounded inputs) or ``fp8_cache`` (the served side
    decodes over latent pages rounded to fp8)."""
    return compare(batcher, seed,
                   min(int(spec["prompt_tokens"]), batcher.max_seq // 2),
                   int(spec["decode_steps"]), control)


def width_differences(config: dict, batcher) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none): every published key with a
    field, the served context, and the keys whose one implemented
    value the program's family is."""
    served = batcher.config
    wrong = [(key, config[key], getattr(served, field))
             for key, field in WIDTH_FIELDS.items()
             if float(getattr(served, field)) != float(config[key])]
    if served.max_seq != config["max_position_embeddings"]:
        wrong.append(("max_position_embeddings",
                      config["max_position_embeddings"], served.max_seq))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        wrong.append(("num_key_value_heads", config["num_key_value_heads"],
                      served.n_heads))
    wrong.extend((key, config[key], value)
                 for key, value in IMPLEMENTED.items()
                 if config[key] != value)
    if served.dtype != "bfloat16" or served.kv_dtype != "bfloat16":
        wrong.append(("torch_dtype", "bfloat16",
                      (served.dtype, served.kv_dtype)))
    return wrong


def element_parameters(config: dict) -> dict:
    """What the file hands the LLM element: the family's name and its
    published widths (``elements/llm.py`` builds the served config
    from them; the definition's own ``max_seq`` is the served
    context)."""
    return {"family": "deepseek_v3",
            "widths": {key: config[key] for key in WIDTH_FIELDS}}


def attention_weights(widths: dict) -> int:
    """One layer's attention projections: W_q, W_kva, W_kvb, W_o."""
    hidden, heads = int(widths["hidden_size"]), \
        int(widths["num_attention_heads"])
    nope, rope = int(widths["qk_nope_head_dim"]), \
        int(widths["qk_rope_head_dim"])
    rank, value = int(widths["kv_lora_rank"]), int(widths["v_head_dim"])
    return (hidden * heads * (nope + rope) + hidden * (rank + rope)
            + rank * heads * (nope + value) + heads * value * hidden)


def expert_weights(widths: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * int(widths["hidden_size"]) \
        * int(widths["moe_intermediate_size"])


def experts_touched(widths: dict, rows: float) -> float:
    """Experts a step of ``rows`` live rows is expected to touch in one
    layer under uniform routing: ``E (1 - (1 - k / E) ** rows)``, never
    more than ``E``."""
    experts = int(widths["n_routed_experts"])
    top_k = int(widths["num_experts_per_tok"])
    return min(float(experts),
               experts * (1.0 - (1.0 - top_k / experts) ** rows))


def cache_bytes_per_token(widths: dict, cache_bytes: int = 2) -> int:
    """One token's latent rows over all layers."""
    return int(widths["num_hidden_layers"]) * cache_bytes \
        * (int(widths["kv_lora_rank"]) + int(widths["qk_rope_head_dim"]))


def latent_decode_attention(widths: dict, rows: float,
                            context_tokens: float,
                            cache_bytes: int = 2) -> dict:
    """What the decode attention kernel over latent pages
    (``ops/pallas_latent.py``) must do in one step, all layers: read
    every live latent row once; per cached token, head and layer the
    absorbed score over ``rank + rope`` and the value sum over
    ``rank``."""
    heads = int(widths["num_attention_heads"])
    rank, rope = int(widths["kv_lora_rank"]), \
        int(widths["qk_rope_head_dim"])
    return {
        "bytes": rows * context_tokens
        * cache_bytes_per_token(widths, cache_bytes),
        "operations": rows * context_tokens
        * int(widths["num_hidden_layers"]) * heads
        * (2.0 * (rank + rope) + 2.0 * rank)}


def decode_step(widths: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """What one decode step over ``rows`` live sequences of
    ``context_tokens`` mean context must do.  Bytes: every sparse
    layer's attention, router and shared experts once and the routed
    experts its rows are expected to touch; the dense layers; the head;
    every live latent row once (bfloat16 all: ``weight_bytes`` 2).
    Operations: two per weight a row multiplies by (its
    ``num_experts_per_tok`` experts, not the touched ones) and, per
    cached token, head and layer, the absorbed score over ``rank +
    rope`` and the value sum over ``rank``."""
    hidden = int(widths["hidden_size"])
    layers = int(widths["num_hidden_layers"])
    dense_layers = int(widths["first_k_dense_replace"])
    sparse_layers = layers - dense_layers
    attention = attention_weights(widths)
    router = hidden * int(widths["n_routed_experts"])
    shared = int(widths["n_shared_experts"]) * expert_weights(widths)
    expert = expert_weights(widths)
    dense = attention + 3 * hidden * int(widths["intermediate_size"])
    head = hidden * int(widths["vocab_size"])
    streamed = (sparse_layers * (attention + router + shared
                                 + experts_touched(widths, rows) * expert)
                + dense_layers * dense + head)
    multiplied = (sparse_layers * (
        attention + router + shared
        + int(widths["num_experts_per_tok"]) * expert)
        + dense_layers * dense + head)
    return {
        "bytes": streamed * weight_bytes
        + rows * context_tokens * cache_bytes_per_token(widths,
                                                        cache_bytes),
        "operations": 2.0 * multiplied * rows
        + latent_decode_attention(widths, rows,
                                  context_tokens)["operations"],
    }
