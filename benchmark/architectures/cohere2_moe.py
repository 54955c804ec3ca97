"""The Command A family (``model_type: cohere2_moe``; Command A+ is the
configuration the benchmark runs), as the harness knows it: found by the
``"architecture": "cohere2_moe"`` of a configuration file.  The four
pieces a family brings (``benchmark/architectures/__init__.py``) --
``check_reference``, ``width_differences``, ``element_parameters``,
``decode_step`` -- and ``window_decode_attention`` /
``window_prefill_attention``, the counts behind
``kernel.window_decode_roofline`` / ``kernel.window_prefill_roofline``.

**The plain reference** (``forward``): the family's forward pass over
ONE whole sequence in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` -- for a layer's input ``x``, ``a = LN(x)`` (mean-
centred LayerNorm, a weight, no bias, eps ``layer_norm_eps``) and

    x' = x + Attn(a) + F(a)        (``use_parallel_block``)

- ``Attn``: ``q = a W_q`` (128 heads of ``head_dim``), ``k = a W_k``,
  ``v = a W_v`` (8 heads), 16 query heads a key/value head, softmax of
  ``q k^T / sqrt(head_dim) + M``, ``W_o``.  In a ``sliding_attention``
  layer q and k take GPT-J's INTERLEAVED rotary (``position_embedding_
  type: rope_gptj``, ``rotary_pct`` 1: pairs ``(2i, 2i + 1)`` rotated by
  ``position * rope_theta ** (-2i / head_dim)``) and ``M`` lets ``i``
  see ``j`` iff ``i - sliding_window < j <= i``; a ``full_attention``
  layer has no positional encoding (NoPE) and a causal ``M``;
- ``F``: ``p = sigmoid(a W_r)`` over the router's 128 outputs, the 8
  largest chosen, gates ``p_e / sum_chosen p`` (``norm_topk_prob``);
  EVERY held expert computed for EVERY token and weighted by its gate
  (nought where not chosen), plus the MEAN of the
  ``num_shared_experts`` shared experts (``shared_expert_combination_
  strategy: average``), each a SwiGLU of ``intermediate_size``;

then a final LayerNorm and the tied head, ``logits = LN(x) E^T *
logit_scale``.  The chip's share: the reference is given the experts
this chip holds (``first`` .. ``first + count`` of the router's) and
adds what they give, as the program does -- what the other chips'
experts would add is left out of both (the ``model-configs`` guide,
section 4).

No cache, no kernel, no batching, no bfloat16, nothing of
``aiko_services_tpu.models`` (the served side, further down, imports the
program lazily).  It is given the weights the system serves, cast to
float32 IN BLOCKS -- a layer's attention at a time, one expert at a
time, the head in blocks of ``HEAD_BLOCK`` rows, attention a key/value
head and ``QUERY_BLOCK`` queries at a time -- so it fits beside 14 GB of
served state.  The served ``W_q`` / ``W_k`` hold each head's columns in
rotate-half order (the program serves GPT-J's pairs as rotate-half's);
the reference puts them back in the published order
(:func:`published_columns`) and rotates the published pairs.

Departures from the source, each under the configuration's ``assumed``:
"average" read as the mean of the shared experts; rotary in the window
layers and none in the global ones (Cohere2's rule, from ``described_as``
"SWA; global NoPE"); random weights.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

HEAD_BLOCK = 16_384
QUERY_BLOCK = 1024  # and the bucket a sequence is padded to
NEAR_TIE = 0.02     # in units of the router's sigmoid score
MISMATCH = 100.0    # what a slot whose stored positions are wrong reads

# Published config.json key -> the served config's field.
WIDTH_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "moe_hidden_dim", "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_token",
    "num_shared_experts": "n_shared_experts",
    "sliding_window": "window", "layer_types": "layer_types",
    "rope_theta": "rope_theta", "layer_norm_eps": "norm_eps",
    "logit_scale": "logit_scale"}

# Published keys with no field: what the program's family implements,
# and the only value of each it can serve (the ``prefix_dense_*`` keys
# name widths no layer has while ``first_k_dense_replace`` is 0).
IMPLEMENTED = {
    "model_type": "cohere2_moe", "hidden_act": "silu",
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "norm_topk_prob": True,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "shared_expert_combination_strategy": "average",
    "tie_word_embeddings": True, "use_embedding_sharing": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_parallel_embedding": False, "use_qk_norm": False,
    "rms_norm_eps": None}
WINDOW = "sliding_attention"


# -- the plain reference ------------------------------------------------------

def _float32(tree):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32),
                                  tree)


def _layer_norm(x, weight, eps):
    centred = x - x.mean(-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        (centred * centred).mean(-1, keepdims=True) + eps) * weight


def _fp8(x, on: bool):
    """The control's rounding: ``x`` to fp8 (e4m3: 4 exponent bits, 3
    of mantissa), the nearest precision below the bfloat16 the
    configuration states.  ``reduce_precision``, not a cast there and
    back: the chip's compiler drops such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) \
        if on else x


def published_columns(w, heads: int, head_dim: int):
    """Served ``W_q`` / ``W_k`` columns ``[..., heads * head_dim]`` (a
    head's published even columns, then its odd ones) back in the
    published order."""
    half = head_dim // 2
    order = np.empty(head_dim, dtype=np.int32)
    order[0::2] = np.arange(half)
    order[1::2] = half + np.arange(half)
    split = w.reshape(*w.shape[:-1], heads, head_dim)
    return split[..., order].reshape(w.shape)


def gptj_rotary(x, positions, theta):
    """x [S, heads, d]: GPT-J's interleaved rotary -- pairs (2i, 2i + 1)
    rotated by position * theta ** (-2i / d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "theta", "window", "fp8"))
def _attention(x, layer, *, heads, kv_heads, head_dim, eps, theta, window,
               fp8=False):
    """x [S, D] -> (a = LN(x), Attn(a)).  ``window`` > 0: a window
    layer (GPT-J rotary, banded mask); 0: a global one (no positional
    encoding, causal).  ``fp8`` (the control) rounds ``a``."""
    weights = _float32(layer)
    length = x.shape[0]
    positions = jnp.arange(length)
    a = _fp8(_layer_norm(x, weights["norm"], eps), fp8)
    q = (a @ published_columns(weights["wq"], heads, head_dim)) \
        .reshape(length, heads, head_dim)
    k = (a @ published_columns(weights["wk"], kv_heads, head_dim)) \
        .reshape(length, kv_heads, head_dim)
    v = (a @ weights["wv"]).reshape(length, kv_heads, head_dim)
    if window:
        q, k = gptj_rotary(q, positions, theta), \
            gptj_rotary(k, positions, theta)
    group = heads // kv_heads
    q = q.reshape(length, kv_heads, group, head_dim)

    def one_head(args):                 # one key/value head's group
        qh, kh, vh = args               # [S, G, d], [S, d], [S, d]

        def block(first):               # QUERY_BLOCK queries
            rows = first + jnp.arange(QUERY_BLOCK)
            scores = jnp.einsum(
                "sgd,td->gst",
                jax.lax.dynamic_slice_in_dim(qh, first, QUERY_BLOCK), kh) \
                / jnp.sqrt(jnp.float32(head_dim))
            seen = positions[None, :] <= rows[:, None]
            if window:
                seen = seen & (positions[None, :] > rows[:, None] - window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("gst,td->sgd",
                              jax.nn.softmax(scores, axis=-1), vh)
        return jax.lax.map(block, jnp.arange(0, length, QUERY_BLOCK)) \
            .reshape(length, group, head_dim)
    attended = jax.lax.map(one_head, (q.transpose(1, 0, 2, 3),
                                      k.transpose(1, 0, 2),
                                      v.transpose(1, 0, 2)))
    attended = attended.transpose(1, 0, 2, 3).reshape(length, -1)
    return a, attended @ weights["wo"]


@partial(jax.jit, static_argnames=("top_k", "first", "count"))
def _route(a, w_router, given=None, *, top_k, first, count):
    """(each held expert's share of each token ``[S, count]``, the
    experts it routed to ``[S, k]``, its own choice ``[S, k]``, and by
    how much ``given``'s worst expert falls short of its own k-th best
    score ``[S]``).  ``given [S, k]`` (the served side's choice; a row
    of -1: none) stands in for its own choice WHERE THE TWO DIFFER BY A
    NEAR-TIE and nowhere else, so a choice that is wrong by more than
    rounding still shows in the logits.  Gates are its own scores'
    either way."""
    scores = jax.nn.sigmoid(a @ w_router.astype(jnp.float32))
    best, own = jax.lax.top_k(scores, top_k)
    chosen, short = own, jnp.zeros(a.shape[:1], jnp.float32)
    if given is not None:
        known = given[:, 0] >= 0
        short = jnp.where(known, best[:, -1] - jnp.take_along_axis(
            scores, jnp.maximum(given, 0), axis=-1).min(-1), 0.0)
        chosen = jnp.where((known & (short <= NEAR_TIE))[:, None],
                           given, own)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / picked.sum(-1, keepdims=True)
    share = (jax.nn.one_hot(chosen - first, count) * gates[..., None]) \
        .sum(1)
    return share, chosen, own, short


@jax.jit
def _swiglu(a, weights, share):
    """``share [S] * SwiGLU(a)`` of one expert's weights."""
    weights = _float32(weights)
    return share[:, None] * ((jax.nn.silu(a @ weights["w_gate"])
                              * (a @ weights["w_up"])) @ weights["w_down"])


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, weight, *, eps):
    return _layer_norm(x, weight.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, block):
    return x @ block.astype(jnp.float32).T


def _take(tree, index):
    return jax.tree_util.tree_map(lambda leaf: leaf[index], tree)


def forward(params: dict, widths: dict, tokens, positions, held: tuple,
            given=None, fp8: bool = False, causal_windows: bool = False):
    """(logits ``[len(positions), vocab]`` of the float32 forward pass
    over ``tokens`` (one sequence) at ``positions``, its own choice of
    experts ``[layers, len(tokens), k]``, and the worst shortfall of
    ``given`` ``[layers, len(tokens)]``).  ``widths``: published keys;
    ``held``: (first, count) of the router's experts this chip holds.
    ``given`` (``[layers, len(tokens), k]``, the served side's choice;
    rows of -1 where there is none) is followed where it differs from
    the layer's own choice by a near-tie (``_route``).  The CONTROLS:
    ``fp8``, the same pass with every block's normed input rounded to
    fp8 -- the reference in the nearest precision below the stated one;
    ``causal_windows``, the window layers attending the whole causal
    past (no window).  The sequence is padded with token 0 to whole
    ``QUERY_BLOCK``s (one program a bucket; no real position sees a
    pad: every mask is causal)."""
    real = len(tokens)
    pad = -real % QUERY_BLOCK
    tokens = list(tokens) + [0] * pad
    if given is not None:
        given = np.pad(np.asarray(given), ((0, 0), (0, pad), (0, 0)),
                       constant_values=-1)
    heads = int(widths["num_attention_heads"])
    kv_heads = int(widths["num_key_value_heads"])
    head_dim = int(widths["head_dim"])
    eps = float(widths["layer_norm_eps"])
    width = int(widths["intermediate_size"])
    shared = int(widths["num_shared_experts"])
    first, count = held
    attention = dict(heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                     eps=eps, theta=float(widths["rope_theta"]),
                     fp8=bool(fp8))
    layers = params["layers"]
    small = ("norm", "wq", "wk", "wv", "wo")
    selections, shortfalls = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for index, kind in enumerate(widths["layer_types"]):
            window = int(widths["sliding_window"]) \
                if kind == WINDOW and not causal_windows else 0
            if kind == WINDOW and causal_windows:
                window = len(tokens) + 1    # a band wider than the past
            a, attended = _attention(
                x, {key: layers[key][index] for key in small},
                window=window, **attention)
            share, _, chosen, short = _route(
                a, layers["w_router"][index],
                None if given is None else jnp.asarray(given[index]),
                top_k=int(widths["num_experts_per_tok"]), first=first,
                count=count)
            selections.append(chosen)
            shortfalls.append(short)
            out = x + attended
            for expert in range(count):
                out = out + _swiglu(a, _take(layers["experts"],
                                             (index, expert)),
                                    share[:, expert])
            mean = jnp.full(a.shape[:1], 1.0 / shared, jnp.float32)
            for member in range(shared):
                columns = slice(member * width, (member + 1) * width)
                block = layers["shared"]
                out = out + _swiglu(
                    a, {"w_gate": block["w_gate"][index][:, columns],
                        "w_up": block["w_up"][index][:, columns],
                        "w_down": block["w_down"][index][columns]}, mean)
            x = out
        x = _final_norm(x[jnp.asarray(positions)], params["final_norm"],
                        eps=eps) * float(widths["logit_scale"])
        vocab = params["embed"].shape[0]
        logits = jnp.concatenate(
            [_head_block(x, params["embed"][first_row:first_row
                                            + HEAD_BLOCK])
             for first_row in range(0, vocab, HEAD_BLOCK)], axis=-1)
        return (np.asarray(jax.device_get(logits)),
                np.asarray(jax.device_get(jnp.stack(selections)))[:, :real],
                np.asarray(jax.device_get(jnp.stack(shortfalls)))[:, :real])


# -- the served side ----------------------------------------------------------

def published_widths(served) -> dict:
    """The served config's fields under their published keys."""
    widths = {key: getattr(served, field)
              for key, field in WIDTH_FIELDS.items()}
    widths["layer_types"] = list(served.layer_types)
    return widths


def _prompt(seed: int, index: int, length: int) -> list:
    """BOS, then random lower-case bytes, as ByteTokenizer would give."""
    rng = np.random.default_rng([seed31(seed), 40, index])
    return [257] + rng.integers(97, 123, length - 1).tolist()


def served_logits(batcher, prompt, decode_steps: int):
    """The system's own path on one sequence, in the batcher's own
    cache and page pools (which must be idle): the prompt admitted chunk
    by chunk into slot 0 through the batcher's admission program -- its
    pages ensured as the batcher ensures them, window pages given back
    behind each chunk's window -- then greedy decode steps through the
    device loop's own body at the batcher's width, window pages given
    back as the sequence crosses page boundaries.  Returns (logits at
    each chunk's last position and every decode step ``[chunks +
    decode_steps, vocab]``, those positions, the tokens decoded, the
    experts chosen ``[layers, len(prompt) + decode_steps, k]``)."""
    from aiko_services_tpu.models import command_a
    if batcher.active_count or batcher.blocks_in_flight or batcher.pending:
        raise RuntimeError("the reference check needs an idle batcher")
    params, config = batcher.params, batcher.config
    chunk, slot = batcher.prefill_chunk, 0
    rows, positions, chosen, decoded = [], [], [], []
    try:
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            if not batcher._ensure_pages(slot, start + chunk, start):
                raise RuntimeError("the reference check found no pages")
            batcher._sync_page_table()
            padded = np.zeros((1, chunk), dtype=np.int32)
            padded[0, :len(piece)] = piece
            logits, batcher.cache, selected = command_a.prefill_into_slot(
                params, config, jnp.asarray(padded), batcher.cache,
                jnp.int32(slot), jnp.int32(start),
                jnp.int32(len(piece) - 1), selections=True)
            rows.append(logits[0, 0].astype(jnp.float32))
            positions.append(start + len(piece) - 1)
            chosen.append(selected[:, :len(piece)])
        trash = batcher.max_seq - 1
        for step in range(decode_steps):
            length = len(prompt) + step
            token = jnp.argmax(rows[-1]).astype(jnp.int32)
            decoded.append(token)
            if not batcher._ensure_pages(slot, length + 2, length):
                raise RuntimeError("the reference check found no pages")
            batcher._sync_page_table()
            tokens = jnp.zeros((batcher.max_slots,), jnp.int32) \
                .at[slot].set(token)
            lengths = jnp.full((batcher.max_slots,), trash, jnp.int32) \
                .at[slot].set(length)
            logits, batcher.cache, selected = command_a.decode_step(
                params, config, tokens, batcher.cache, lengths,
                selections=True)
            rows.append(logits[slot].astype(jnp.float32))
            positions.append(length)
            chosen.append(selected[:, slot:slot + 1])
        served = np.asarray(jax.device_get(jnp.stack(rows)))
        tokens = [int(token) for token in jax.device_get(decoded)]
        selections = np.asarray(jax.device_get(
            jnp.concatenate(chosen, axis=1)))
        window_held = batcher._pages.window_holds(slot)
        released = batcher._pages.window_released
    finally:
        batcher._pages.release(slot)
        batcher._sync_page_table()
    return served, positions, tokens, selections, window_held, released


def compare(batcher, seed: int, prompt_tokens: int, decode_steps: int,
            control: str | None = None) -> dict:
    """Served against reference on one seeded prompt: worst and mean
    absolute logit difference over each admission chunk's last position
    and every decode step, and whether the greedy tokens agree.  The
    router: a near-tie at rank k / k + 1 flips under the bfloat16
    rounding of the router's input, and a flip between a held expert
    and one of another chip's moves a logit by a whole expert's part --
    more than any precision the check is there to tell apart.  So the
    reference routes as the served side did where the two differ by a
    near-tie in ITS OWN scores (``NEAR_TIE``) and nowhere else, and the
    flips are counted, not hidden (``router_flips`` of
    ``router_choices``; ``router_not_near_ties`` of them by more than a
    near-tie: none, on a sound router)."""
    prompt = _prompt(seed, 0, prompt_tokens)
    served, positions, decoded, served_chosen, window_held, released = \
        served_logits(batcher, prompt, decode_steps)
    reference, own, short = forward(
        batcher.params, published_widths(batcher.config),
        prompt + decoded, positions, batcher.config.held,
        given=served_chosen, fp8=control == "fp8_activations",
        causal_windows=control == "causal_windows")
    difference = np.abs(served - reference)
    flips = (np.sort(served_chosen, -1) != np.sort(own, -1)).any(-1)
    return {"max_abs_diff": float(difference.max()),
            "mean_abs_diff": float(difference.mean()),
            "logit_std": float(reference.std()),
            "positions": len(positions),
            "argmax_agree": int((served.argmax(-1)
                                 == reference.argmax(-1)).sum()),
            "router_flips": int(flips.sum()),
            "router_choices": int(flips.size),
            "router_not_near_ties": int((short > NEAR_TIE).sum()),
            "router_worst_shortfall": float(short.max()),
            "window_pages_held": window_held,
            "window_pages_released": released}


def served_loop(batcher, prompts, new_tokens: int, join: int):
    """The system's own serving path on MANY sequences at once, through
    the idle batcher's own ``submit`` and ``step``: admission chunk by
    chunk, the fold of joiners, the DEVICE LOOP at the batcher's ring,
    the retire -- ``join`` requests submitted a step, so rows are at
    mixed phases and the window pages one slot gives back are taken by
    another's admission.  Every request runs until it has emitted
    ``new_tokens``; then ONE decode step outside the loop (the loop's
    own body) reads every slot's pages back: each live row's logits at
    its next position.  Then the requests are cancelled.  Returns a
    record a request (its slot, what it emitted, the positions stored,
    its read-back logits), and the most window pages a slot held."""
    from aiko_services_tpu.models import command_a
    from aiko_services_tpu.models.batching import Request
    if batcher.active_count or batcher.blocks_in_flight or batcher.pending:
        raise RuntimeError("the reference check needs an idle batcher")
    if len(prompts) > batcher.max_slots:
        raise ValueError(f"{len(prompts)} sequences for "
                         f"{batcher.max_slots} slots")
    emitted = [[] for _ in prompts]
    requests = [Request(
        request_id=f"reference/{index}", prompt_tokens=list(prompt),
        max_new_tokens=batcher.max_seq,
        emit=lambda _id, token, _finished, out=emitted[index]:
            out.append(token))
        for index, prompt in enumerate(prompts)]
    waiting, steps, fullest = list(requests), 0, 0
    try:
        while waiting or any(len(out) < new_tokens for out in emitted):
            for request in waiting[:join]:
                batcher.submit(request)
            del waiting[:join]
            batcher.step()
            fullest = max([fullest] + [batcher._pages.window_holds(slot)
                                       for slot in range(batcher.max_slots)])
            steps += 1
            if steps > 64 * len(prompts) * (1 + new_tokens):
                raise RuntimeError("the served loop made no progress")
        while batcher.blocks_in_flight:
            batcher.step()
        chain = jax.device_get({key: batcher._loop_chain[key] for key in
                                ("tokens", "lengths", "active")})
        logits, batcher.cache = command_a.decode_step(
            batcher.params, batcher.config, jnp.asarray(chain["tokens"]),
            batcher.cache, jnp.asarray(np.where(
                chain["active"], chain["lengths"], batcher.max_seq - 1)),
            jnp.asarray(chain["active"]))
        records = [{"slot": request.slot, "emitted": list(out),
                    "live": bool(chain["active"][request.slot]),
                    "stored": int(chain["lengths"][request.slot]),
                    "logits": np.asarray(jax.device_get(
                        logits[request.slot].astype(jnp.float32)))}
                   for request, out in zip(requests, emitted)]
    finally:
        for request in requests:
            batcher.cancel(request.request_id)
        batcher.run_until_drained()
    return records, fullest


def compare_loop(batcher, seed: int, prompt_lengths, new_tokens: int,
                 join: int, control: str | None = None) -> dict:
    """The device loop against the reference on ``len(prompt_lengths)``
    seeded prompts served AT ONCE (:func:`served_loop`): every slot's
    pages, read back by one step, give the logits the reference gives
    over the same sequence -- the prompt and everything the slot emitted
    -- within the check's limit (``loop_readback_max_abs_diff``): a
    window page read after another slot took it, a neighbour's K/V in a
    slot's page or a ring read out of order shows here.  A slot whose
    stored positions are not its prompt and all it emitted but the last
    token is a mismatch (``loop_mismatches``)."""
    prompts = [_prompt(seed, 1 + index, length)
               for index, length in enumerate(prompt_lengths)]
    records, fullest = served_loop(batcher, prompts, new_tokens, join)
    widths = published_widths(batcher.config)
    worst, mismatches = 0.0, 0
    for prompt, record in zip(prompts, records):
        sequence = prompt + record["emitted"]
        if not record["live"] or record["stored"] != len(sequence) - 1:
            mismatches += 1
            continue
        reference = forward(
            batcher.params, widths, sequence, [len(sequence) - 1],
            batcher.config.held, fp8=control == "fp8_activations",
            causal_windows=control == "causal_windows")[0]
        worst = max(worst, float(np.abs(record["logits"]
                                        - reference[0]).max()))
    return {"loop_sequences": len(prompts), "loop_mismatches": mismatches,
            "loop_readback_max_abs_diff": worst,
            "loop_window_pages_held_max": fullest}


# -- the pieces the harness asks an architecture for --------------------------

def check_reference(batcher, seed: int, spec: dict,
                    control: str | None = None) -> dict:
    """Served against the plain reference, on prompts made from
    ``seed``; ``spec`` is the configuration file's ``reference``.  ONE
    SEQUENCE (:func:`compare`): a ``prompt_tokens`` prompt through the
    batcher's own admission, then ``decode_steps`` steps of the loop's
    own body, every served logit held to the reference's full forward.
    THE DEVICE LOOP (:func:`compare_loop`): ``loop_prompt_tokens``
    sequences at once through the batcher's own ``step``,
    ``loop_join`` joining a step, ``loop_new_tokens`` each, every
    slot's pages read back.  The worst reading is the check's
    ``max_abs_diff``; a mismatch of the loop's reads :data:`MISMATCH`.
    ``control``: ``fp8_activations`` or ``causal_windows``
    (:func:`forward`), each of which has to fail."""
    single = compare(batcher, seed,
                     min(int(spec["prompt_tokens"]),
                         batcher.max_seq - int(spec["decode_steps"]) - 1),
                     int(spec["decode_steps"]), control)
    # The file's sequences, as many as the batcher has slots (all of
    # them in the cell; the rehearsal's twelve slots serve the first 12).
    lengths = [int(length) for length in spec["loop_prompt_tokens"]]
    loop = compare_loop(batcher, seed, lengths[:batcher.max_slots],
                        int(spec["loop_new_tokens"]),
                        int(spec["loop_join"]), control)
    return {**single, **loop,
            "single_max_abs_diff": single["max_abs_diff"],
            "max_abs_diff": max(single["max_abs_diff"],
                                loop["loop_readback_max_abs_diff"],
                                MISMATCH * loop["loop_mismatches"])}


def width_differences(config: dict, batcher) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none): every published key with a
    field, the served context, the chip's share of the experts, the
    keys whose one implemented value the program's family is, and the
    precision."""
    served = batcher.config
    wrong = [(key, config[key], getattr(served, field))
             for key, field in WIDTH_FIELDS.items()
             if key != "layer_types"
             and float(getattr(served, field)) != float(config[key])]
    if list(served.layer_types) != list(config["layer_types"]):
        wrong.append(("layer_types", config["layer_types"],
                      served.layer_types))
    if served.max_seq != config["max_position_embeddings"]:
        wrong.append(("max_position_embeddings",
                      config["max_position_embeddings"], served.max_seq))
    share = config["expert_share"]
    if (served.expert_shards, served.held[0]) \
            != (share["shards"], share["shard"] * served.n_experts):
        wrong.append(("expert_share", share,
                      (served.expert_shards, served.held)))
    wrong.extend((key, config[key], value)
                 for key, value in IMPLEMENTED.items()
                 if config[key] != value)
    if served.dtype != "bfloat16" or served.kv_dtype != "bfloat16":
        wrong.append(("torch_dtype", "bfloat16",
                      (served.dtype, served.kv_dtype)))
    return wrong


def element_parameters(config: dict) -> dict:
    """What the file hands the LLM element: the family's name, its
    published widths and the chip's share of the experts
    (``elements/llm.py`` builds the served config from them; the
    definition's own ``max_seq`` is the served context)."""
    return {"family": "cohere2_moe",
            "widths": {key: config[key] for key in WIDTH_FIELDS},
            "expert_shards": config["expert_share"]["shards"]}


# -- what a step must stream and compute --------------------------------------

def attention_weights(widths: dict) -> int:
    """One layer's attention projections: W_q, W_k, W_v, W_o."""
    hidden, width = int(widths["hidden_size"]), int(widths["head_dim"])
    heads, kv_heads = int(widths["num_attention_heads"]), \
        int(widths["num_key_value_heads"])
    return 2 * hidden * heads * width + 2 * hidden * kv_heads * width


def expert_weights(widths: dict) -> int:
    """One expert's SwiGLU (routed or shared)."""
    return 3 * int(widths["hidden_size"]) * int(widths["intermediate_size"])


def router_experts(config: dict) -> int:
    """The router's outputs: every chip's share of the experts."""
    return int(config["num_experts"]) * int(config["expert_share"]["shards"])


def held_experts_touched(config: dict, rows: float) -> float:
    """Held experts a step of ``rows`` live rows is expected to touch in
    one layer under independent uniform routing: ``E (1 - (1 - k / R)
    ** rows)`` of the ``E`` held, ``R`` the router's outputs."""
    held = int(config["num_experts"])
    top_k = int(config["num_experts_per_tok"])
    return held * (1.0 - (1.0 - top_k / router_experts(config)) ** rows)


def streamed_held_experts(config: dict, rows: float) -> float:
    """Held experts a decode step of ``rows`` live rows streams in one
    layer: :func:`held_experts_touched`, but no more than the
    configuration's ``measured_routing`` says a step touched on the
    chip.  Under this family's random weights rows do not route apart
    (a tied head makes greedy decoding echo a prompt's last byte), so
    the independent expectation counts bytes no step streams and the
    roofline share would read past 100 % (PERF.md section 6)."""
    expected = held_experts_touched(config, rows)
    measured = config.get("measured_routing", {}).get(
        "held_experts_touched")
    return expected if measured is None else min(expected, float(measured))


def kv_bytes_per_token(config: dict, cache_bytes: int = 2) -> int:
    """One token's keys and values in ONE layer."""
    return 2 * cache_bytes * int(config["num_key_value_heads"]) \
        * int(config["head_dim"])


def _kinds(config: dict) -> tuple[int, int]:
    """(window layers, global layers)."""
    windows = sum(kind == WINDOW for kind in config["layer_types"])
    return windows, len(config["layer_types"]) - windows


def window_decode_attention(config: dict, rows: float,
                            context_tokens: float,
                            cache_bytes: int = 2) -> dict:
    """What the paged decode kernel must do in one step, all layers:
    over the rows, a global layer reads the context and a window layer
    its last ``min(context, sliding_window)`` positions; those keys and
    values read once, and per position read, layer and query head the
    score and the value product over ``head_dim``."""
    windows, full = _kinds(config)
    read = rows * (full * context_tokens + windows * min(
        context_tokens, float(config["sliding_window"])))
    return {"bytes": read * kv_bytes_per_token(config, cache_bytes),
            "operations": read * int(config["num_attention_heads"])
            * 4.0 * int(config["head_dim"])}


#: Rows of the flash kernel's query block: a block of queries reads its
#: keys and values once.
PREFILL_QUERY_BLOCK = 512


def window_prefill_attention(config: dict, rows: float,
                             context_tokens: float,
                             cache_bytes: int = 2) -> dict:
    """What admission's flash attention must do for ONE prompt token,
    all layers (``rows`` is not used: the harness multiplies by the
    prompt tokens admitted), ``context_tokens`` taken as the mean prompt
    (the harness's context is the prompt plus half the new tokens: 2 %
    high at 6,144 + 128).  A global layer's token attends on average
    half the prompt, a window layer's ``min(p + 1, window)`` averaged
    over the prompt's positions ``p``; per key and query head the score
    and the value product over ``head_dim``.  Bytes: the token's query
    and output, and the keys and values its query block reads, shared
    by the block's ``PREFILL_QUERY_BLOCK`` queries."""
    windows, full = _kinds(config)
    prompt, window = float(context_tokens), float(config["sliding_window"])
    if prompt <= window:
        banded = (prompt + 1.0) / 2.0
    else:
        banded = (window * (window + 1.0) / 2.0
                  + (prompt - window) * window) / prompt
    keys = full * (prompt + 1.0) / 2.0 + windows * banded
    heads, width = int(config["num_attention_heads"]), \
        int(config["head_dim"])
    layers = windows + full
    return {"bytes": layers * 2 * heads * width * cache_bytes
            + keys * kv_bytes_per_token(config, cache_bytes)
            / PREFILL_QUERY_BLOCK,
            "operations": keys * heads * 4.0 * width}


def decode_step(config: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """What one decode step over ``rows`` live sequences of
    ``context_tokens`` mean context must do.  Bytes: every layer's
    attention, router and shared experts once and the held experts its
    rows touch (:func:`streamed_held_experts`); the
    tied head (this chip's rows of the vocabulary); the K/V the layers
    read (:func:`window_decode_attention`); bfloat16 all.  Operations:
    two per weight a row multiplies by -- its expected picks among the
    held experts (``k`` times the held share), not the touched ones --
    and the attention products."""
    hidden = int(config["hidden_size"])
    layers = len(config["layer_types"])
    attention = attention_weights(config)
    router = hidden * router_experts(config)
    expert = expert_weights(config)
    shared = int(config["num_shared_experts"]) * expert
    head = hidden * int(config["vocab_size"])
    picks = int(config["num_experts_per_tok"]) \
        * int(config["num_experts"]) / router_experts(config)
    streamed = layers * (attention + router + shared
                         + streamed_held_experts(config, rows) * expert) \
        + head
    multiplied = layers * (attention + router + shared + picks * expert) \
        + head
    kernel = window_decode_attention(config, rows, context_tokens,
                                     cache_bytes)
    return {"bytes": streamed * weight_bytes + kernel["bytes"],
            "operations": 2.0 * multiplied * rows + kernel["operations"]}
