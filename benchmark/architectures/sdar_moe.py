"""The SDAR family (``model_type: sdar_moe``; SDAR-30B-A3B-Chat is the
configuration the benchmark runs), as the harness knows it: found by the
``"architecture": "sdar_moe"`` of a configuration file.  The four pieces
a family brings (``benchmark/architectures/__init__.py``) --
``check_reference`` (pass by pass on one slot, and the device loop on
many at once), ``width_differences``, ``element_parameters``,
``decode_step`` -- and ``block_decode_attention``, the count behind
``kernel.block_attention_roofline``.

**The plain reference**: ``forward``, the family's forward pass over ONE
whole sequence in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, written from the published
``config.json`` (a Qwen3-MoE body) -- pre-norm residual blocks (RMSNorm,
eps ``rms_norm_eps``), no biases, untied head; for a layer's input ``x``:

- ``a = RMSNorm(x)``; ``q = a W_q`` (32 heads of 128), ``k = a W_k``,
  ``v = a W_v`` (4 heads); RMSNorm over ``head_dim`` of every query and
  key head (ASSUMED: the Qwen3 body's, no published key says it);
  rotate-half rotary over all of ``head_dim``, base ``rope_theta``, at
  absolute positions; softmax of ``q k^T / sqrt(head_dim) + M``, 8
  query heads a key/value head, where **M is block-causal** with block
  length ``B``: position ``i`` sees ``j`` iff ``j // B <= i // B``;
  ``h = x + o W_o``;
- ``b = RMSNorm(h)``; ``p = softmax(b W_r)`` over 128 experts; the 8
  largest chosen; gates ``p_e / sum_chosen p`` (``norm_topk_prob``);
  EVERY expert computed for EVERY token and weighted by its gate
  (nought where not chosen); ``y = h + sum_e g_e W_down,e (silu(W_gate,e
  b) * W_up,e b)``; no shared expert, none dropped;

then the final RMSNorm and the head.  Logits at a position predict THAT
position's token (no shift).  And ``generate``, the SDAR repository's
``block_diffusion_generate`` (static low-confidence rule) in plain
Python over ``forward``: no cache, no
kernel, no batching, no bfloat16, nothing of ``aiko_services_tpu.models``
(the served side, further down, imports the program lazily).  It is
given the weights the system serves, cast to float32 IN BLOCKS: a layer
at a time, the experts ``EXPERT_BLOCK`` at a time, the head in blocks of
``HEAD_BLOCK`` vocabulary rows -- so it fits beside 12 GB of served
state.

Departures from the source, each under the configuration's ``assumed``:
the query/key norm; block length, mask token and decoding rule (the
repository's generation defaults, from memory of it); where fewer
positions are masked than a pass's quota, only the masked ones are
decided (the repository's ``topk`` would then also pick decided
positions); random weights.
"""

from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

EXPERT_BLOCK = 16
HEAD_BLOCK = 16_384

# Published config.json key -> the served config's field.
WIDTH_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "moe_intermediate_size": "moe_hidden_dim", "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_token",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}

# Published keys with no field: what the program's family implements,
# and the only value of each it can serve (``intermediate_size`` and
# ``max_window_layers`` name widths no layer has while
# ``mlp_only_layers`` is empty and no window slides).
IMPLEMENTED = {
    "model_type": "sdar_moe", "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True, "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False}


# -- the plain reference ------------------------------------------------------

def _float32(tree):
    return jax.tree_util.tree_map(lambda leaf: leaf.astype(jnp.float32),
                                  tree)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rotary(x, positions, theta):
    """x [S, heads, d]: rotate pairs (i, i + d/2) by position * theta **
    (-2i / d)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _fp8(x, on: bool):
    """The control's rounding: ``x`` to fp8 (e4m3: 4 exponent bits, 3
    of mantissa), the nearest precision below the bfloat16 the
    configuration states.  ``reduce_precision``, not a cast there and
    back: the chip's compiler drops such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) \
        if on else x


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "theta", "block", "fp8"))
def _attention(x, layer, *, heads, kv_heads, head_dim, eps, theta, block,
               fp8=False):
    """x [S, D] -> (x + Attn(norm1(x)), norm2 of that), the mask
    block-causal with block length ``block`` (1: causal -- the second
    control's mask).  ``fp8`` (the first control) rounds the normed
    activations both sub-layers multiply by."""
    weights = _float32(layer)
    length = x.shape[0]
    positions = jnp.arange(length)
    a = _fp8(_rms_norm(x, weights["attn_norm"], eps), fp8)
    q = (a @ weights["wq"]).reshape(length, heads, head_dim)
    k = (a @ weights["wk"]).reshape(length, kv_heads, head_dim)
    v = (a @ weights["wv"]).reshape(length, kv_heads, head_dim)
    q = _rotary(_rms_norm(q, weights["q_norm"], eps), positions, theta)
    k = _rotary(_rms_norm(k, weights["k_norm"], eps), positions, theta)
    group = heads // kv_heads
    scores = jnp.einsum("skgd,tkd->kgst",
                        q.reshape(length, kv_heads, group, head_dim), k) \
        / jnp.sqrt(jnp.float32(head_dim))
    seen = positions[None, :] // block <= positions[:, None] // block
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    attended = jnp.einsum("kgst,tkd->skgd",
                          jax.nn.softmax(scores, axis=-1), v) \
        .reshape(length, -1)
    x = x + attended @ weights["wo"]
    return x, _fp8(_rms_norm(x, weights["mlp_norm"], eps), fp8)


NEAR_TIE = 0.08     # in units of the router's logit (the log of a score)


@partial(jax.jit, static_argnames=("top_k",))
def _route(b, w_router, given=None, *, top_k):
    """(each expert's share of each token ``[S, E]``, the experts it
    routed to ``[S, k]``, its own choice ``[S, k]``, and by how much
    ``given``'s worst expert falls short of its own k-th best, in the
    log of the score, ``[S]``).  ``given [S, k]`` (the served side's
    selection) stands in for its own choice WHERE THE TWO DIFFER BY A
    NEAR-TIE: a token whose given experts all score within ``NEAR_TIE``
    of its own k-th best.  Anywhere else it keeps its own, so a
    selection that is wrong by more than rounding still shows in the
    logits.  Gates are its own scores' either way."""
    logits = b @ w_router.astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    best, own = jax.lax.top_k(logits, top_k)
    chosen, short = own, jnp.zeros(b.shape[:1], jnp.float32)
    if given is not None:
        short = best[:, -1] - jnp.take_along_axis(
            logits, given, axis=-1).min(-1)
        chosen = jnp.where((short <= NEAR_TIE)[:, None], given, own)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / picked.sum(-1, keepdims=True)
    share = (jax.nn.one_hot(chosen, scores.shape[-1])
             * gates[..., None]).sum(1)
    return share, chosen, own, short


@jax.jit
def _experts(b, share, block):
    """Every expert of ``block`` for every token, weighted by its share:
    b [S, D], share [S, e] -> [S, D]."""
    weights = _float32(block)
    hidden = jax.nn.silu(jnp.einsum("sd,edf->esf", b, weights["w_gate"])) \
        * jnp.einsum("sd,edf->esf", b, weights["w_up"])
    return jnp.einsum("se,esd->sd", share, jnp.einsum(
        "esf,efd->esd", hidden, weights["w_down"]))


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, block):
    return x @ block.astype(jnp.float32)


def forward(params: dict, widths: dict, tokens, positions,
            block_length: int, given=None, fp8: bool = False):
    """(logits ``[len(positions), vocab]`` of the float32 forward pass
    over ``tokens`` (one whole sequence, mask tokens and all) at
    ``positions``, its own choice of experts ``[layers, len(tokens),
    k]``, and the worst shortfall of ``given`` ``[layers,
    len(tokens)]``).  ``widths``: published keys.  ``block_length``:
    the mask's (1 is the causal mask: the second control).  ``given``
    (``[layers, len(tokens), k]``, the served side's selections) is
    followed where it differs from the layer's own choice by a near-tie
    (``_route``).  ``fp8`` is the first CONTROL: the same pass with
    every sub-layer's normed input rounded to fp8 -- the reference
    computed in the nearest precision below the stated one."""
    eps = float(widths["rms_norm_eps"])
    experts = int(widths["num_experts"])
    attention = dict(
        heads=int(widths["num_attention_heads"]),
        kv_heads=int(widths["num_key_value_heads"]),
        head_dim=int(widths["head_dim"]), eps=eps,
        theta=float(widths["rope_theta"]), block=int(block_length),
        fp8=bool(fp8))
    small = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
             "mlp_norm")
    stack = params["layers"]
    selections, shortfalls = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for at in range(int(widths["num_hidden_layers"])):
            x, b = _attention(x, {key: stack[key][at] for key in small},
                              **attention)
            share, _, chosen, short = _route(
                b, stack["w_router"][at],
                None if given is None else jnp.asarray(given[at]),
                top_k=int(widths["num_experts_per_tok"]))
            selections.append(chosen)
            shortfalls.append(short)
            for first in range(0, experts, EXPERT_BLOCK):
                # (one block of one layer's experts is sliced out of
                # the stack at a time: a whole layer is 2.4 GB in
                # float32)
                last = min(first + EXPERT_BLOCK, experts)
                x = x + _experts(
                    b, share[:, first:last],
                    jax.tree_util.tree_map(
                        lambda leaf: leaf[at, first:last],
                        stack["experts"]))
        x = _final_norm(x[jnp.asarray(positions)], params["final_norm"],
                        eps=eps)
        vocab = params["unembed"].shape[1]
        logits = jnp.concatenate(
            [_head_block(x, params["unembed"][:, first:first + HEAD_BLOCK])
             for first in range(0, vocab, HEAD_BLOCK)], axis=-1)
        return (np.asarray(jax.device_get(logits)),
                np.asarray(jax.device_get(jnp.stack(selections))),
                np.asarray(jax.device_get(jnp.stack(shortfalls))))


def quota(block_length: int, denoising_steps: int, done: int) -> int:
    """Positions denoising pass ``done`` (from 0) of ``denoising_steps``
    decides: ``B // T``, the remainder to the first passes."""
    return block_length // denoising_steps \
        + (done < block_length % denoising_steps)


def decide_positions(logits, state, mask_token: int, count: int):
    """What a denoising pass decides, on the host: ``logits [B,
    vocab]`` at the block's positions, ``state`` the block's tokens ->
    (``x0 [B]``, the argmax with the mask token's logit excluded; the
    positions decided: the ``count`` masked positions of highest
    probability of ``x0``, ties to the lower index, never more than are
    masked)."""
    scores = np.array(logits, dtype=np.float64)
    scores[:, mask_token] = -np.inf
    x0 = scores.argmax(-1)
    peak = scores.max(-1, keepdims=True)
    confidence = 1.0 / np.exp(scores - peak).sum(-1)
    masked = [index for index, token in enumerate(state)
              if token == mask_token]
    ranked = sorted(masked, key=lambda index: (-confidence[index], index))
    return x0, sorted(ranked[:count])


def generate(params: dict, widths: dict, prompt, new_tokens: int, *,
             block_length: int, denoising_steps: int, mask_token: int,
             stop_tokens=()) -> list[int]:
    """The block loop in plain Python over :func:`forward` (greedy):
    the prompt's whole blocks stand; the tokens left over open the
    first generated block as decided positions.  For each block: from
    ``[decided..., MASK...]``, denoising passes decide :func:`quota`
    masked positions each (:func:`decide_positions`) until no mask is
    left -- a commit pass changes no token, so the reference makes none
    -- and the block's generated tokens are emitted, up to
    ``new_tokens`` and up to and with the first of ``stop_tokens``."""
    sequence = [int(token) for token in prompt]
    start = len(sequence) // block_length * block_length
    emitted: list[int] = []
    while len(emitted) < new_tokens:
        state = sequence[start:] \
            + [mask_token] * (start + block_length - len(sequence))
        done = 0
        while mask_token in state:
            logits, _, _ = forward(
                params, widths, sequence[:start] + state,
                list(range(start, start + block_length)), block_length)
            x0, chosen = decide_positions(
                logits, state, mask_token,
                quota(block_length, denoising_steps, done))
            for index in chosen:
                state[index] = int(x0[index])
            done += 1
        for token in state[len(sequence) - start:]:
            emitted.append(token)
            if token in stop_tokens or len(emitted) >= new_tokens:
                return emitted
        sequence = sequence[:start] + state
        start += block_length
    return emitted


# -- the served side ----------------------------------------------------------

def served_passes(batcher, prompt, blocks: int):
    """The system's own path on one sequence, in the batcher's own
    cache and page pool (which must be idle): the prompt's whole blocks
    admitted chunk by chunk into slot 0 through the batcher's admission
    program, then ``blocks`` generated blocks pass by pass (greedy)
    through the loop's own pass at the batcher's width -- every
    denoising pass and every commit pass recorded: where the block
    starts, its state going in, the logits it produced at the block's
    positions, the experts chosen there, what it decided.  Returns (the
    passes, the experts chosen in admission ``[layers, whole blocks of
    the prompt, k]``)."""
    from aiko_services_tpu.models import sdar
    if batcher.active_count or batcher.blocks_in_flight:
        raise RuntimeError("the reference check needs an idle batcher")
    params, config = batcher.params, batcher.config
    chunk, slot, size = batcher.prefill_chunk, 0, config.block_length
    stored = sdar.admitted_length(config, len(prompt))
    pages = batcher._pages
    if not pages.ensure(slot, pages.pages_for(
            stored + (blocks + 1) * size, batcher.kv_page_tokens)):
        raise RuntimeError("the reference check found no free pages")
    batcher._sync_page_table()
    width = batcher.max_slots
    live = jnp.zeros((width,), bool).at[slot].set(True)
    zeros = jnp.zeros((width,), jnp.int32)
    passes, admitted = [], []
    try:
        for start in range(0, stored, chunk):
            piece = prompt[start:min(start + chunk, stored)]
            padded = np.zeros((1, chunk), dtype=np.int32)
            padded[0, :len(piece)] = piece
            _, batcher.cache, selected = sdar.prefill_into_slot(
                params, config, jnp.asarray(padded), batcher.cache,
                jnp.int32(slot), jnp.int32(start), selections=True)
            admitted.append(np.asarray(jax.device_get(
                selected[:, :len(piece)])))
        state = sdar.joiner_carry(config, prompt)[:size]
        for _ in range(blocks):
            done = 0
            while True:
                commit = config.mask_token not in state
                block = jnp.full((width, size), config.mask_token,
                                 jnp.int32).at[slot].set(
                                     jnp.asarray(state, jnp.int32))
                logits, batcher.cache, chosen = sdar.decode_step(
                    params, config, block, batcher.cache,
                    zeros.at[slot].set(stored),
                    jnp.zeros((width,), bool).at[slot].set(commit), live,
                    selections=True)
                record = {
                    "start": stored, "state": list(state),
                    "commit": commit,
                    "logits": np.asarray(jax.device_get(
                        logits[slot].astype(jnp.float32))),
                    "chosen": np.asarray(jax.device_get(
                        chosen[:, slot * size:(slot + 1) * size]))}
                passes.append(record)
                if commit:
                    break
                decided, transfer = sdar.decide(
                    config, block, logits, jnp.zeros((width,), jnp.float32),
                    zeros.at[slot].set(done), jax.random.PRNGKey(0))
                record["decided"] = np.flatnonzero(np.asarray(
                    jax.device_get(transfer[slot]))).tolist()
                state = np.asarray(jax.device_get(decided[slot])).tolist()
                done += 1
            stored += size
            state = [config.mask_token] * size
    finally:
        pages.release(slot)
        batcher._sync_page_table()
    selections = np.concatenate(admitted, axis=1) if admitted else None
    return passes, selections


def published_widths(served) -> dict:
    """The served config's fields under their published keys."""
    return {key: getattr(served, field)
            for key, field in WIDTH_FIELDS.items()}


def compare(batcher, seed: int, prompt_tokens: int, blocks: int,
            control: str | None = None, free: bool = False) -> dict:
    """Served against reference on one seeded prompt (BOS then random
    lower-case bytes, as ByteTokenizer would give), PASS BY PASS: the
    reference is driven along the served side's block states -- for
    every recorded pass, denoising and commit alike, its float32
    forward over the whole sequence so far with that state as its last
    block -- and the logits at the block's positions are compared:
    worst and mean absolute difference over every pass and position.

    The router: a near-tie at rank k / k + 1 flips under the bfloat16
    rounding of the router's input, and one flip moves a logit by more
    than any precision the check is there to tell apart; so the
    reference routes as the served side did where the two differ by a
    near-tie in ITS OWN scores (``NEAR_TIE``) and nowhere else, and the
    flips are counted, not hidden (``router_flips`` of
    ``router_choices``, ``router_not_near_ties``,
    ``router_worst_shortfall``: ``deepseek_v3.compare``'s treatment).
    Which position to decide is a near-tie of the same kind: the
    reference's own choice from its own logits is compared with the
    served side's and the differences counted (``decide_flips`` of
    ``decide_choices``), not failed on.

    ``control``: ``fp8_activations`` (the reference's sub-layers
    multiply fp8-rounded inputs) or ``causal_in_block`` (the reference's
    mask is causal inside a block too); both have to fail.  ``free``
    adds ``free_max_abs_diff``: the reference routing by its own scores
    throughout."""
    if control not in (None, "fp8_activations", "causal_in_block"):
        raise ValueError(f"control={control!r}: fp8_activations | "
                         f"causal_in_block")
    rng = np.random.default_rng([seed31(seed), 36])
    prompt = [257] + rng.integers(97, 123, prompt_tokens - 1).tolist()
    config = batcher.config
    size, mask_token = config.block_length, config.mask_token
    passes, admitted = served_passes(batcher, prompt, blocks)
    widths = published_widths(config)
    first = passes[0]["start"]
    committed = list(prompt[:first])
    history = [] if admitted is None else [admitted]   # [L, tokens, k]
    worst = total = count = flips = choices = far = agree = 0
    worst_short = free_worst = 0.0
    decide_flips = decide_choices = done = 0
    spread = []
    for record in passes:
        sequence = committed + record["state"]
        positions = list(range(record["start"], record["start"] + size))
        given = np.concatenate(history + [record["chosen"]], axis=1)
        reference, own, short = forward(
            params=batcher.params, widths=widths, tokens=sequence,
            positions=positions,
            block_length=1 if control == "causal_in_block" else size,
            given=given, fp8=control == "fp8_activations")
        difference = np.abs(record["logits"] - reference)
        worst = max(worst, float(difference.max()))
        total += float(difference.sum())
        count += difference.size
        spread.append(float(reference.std()))
        agree += int((record["logits"].argmax(-1)
                      == reference.argmax(-1)).sum())
        flipped = (np.sort(given, -1) != np.sort(own, -1)).any(-1)
        flips += int(flipped[:, -size:].sum())
        choices += int(flipped[:, -size:].size)
        far += int((short[:, -size:] > NEAR_TIE).sum())
        worst_short = max(worst_short, float(short.max()))
        if free:
            free_worst = max(free_worst, float(np.abs(
                record["logits"] - forward(
                    batcher.params, widths, sequence, positions,
                    size)[0]).max()))
        if record["commit"]:
            committed = sequence
            history.append(record["chosen"])
            done = 0
            continue
        _, decided = decide_positions(
            reference, record["state"], mask_token,
            quota(size, config.denoising_steps, done))
        decide_flips += decided != record["decided"]
        decide_choices += 1
        done += 1
    result = {"max_abs_diff": worst, "mean_abs_diff": total / count,
              "logit_std": float(np.mean(spread)),
              "positions": len(passes) * size, "passes": len(passes),
              "argmax_agree": agree,
              "router_flips": flips, "router_choices": choices,
              "router_not_near_ties": far,
              "router_worst_shortfall": worst_short,
              "decide_flips": int(decide_flips),
              "decide_choices": decide_choices}
    if free:
        result["free_max_abs_diff"] = free_worst
    return result


# -- the served side, many slots at once: the device loop ---------------------

#: What one mismatch of the loop's check -- a block the rule cannot
#: explain, a slot whose stored positions are not what it emitted --
#: reads as in ``max_abs_diff``: far over any limit.
MISMATCH = 100.0
BUCKET = 128        # the reference's sequences are padded to whole buckets


def served_loop(batcher, prompts, blocks: int, join: int):
    """The system's own serving path on MANY sequences at once, through
    the idle batcher's own ``submit`` and ``step``: admission chunk by
    chunk, the fold of joiners, the DEVICE LOOP (the program the window
    times, at the batcher's ring) and the retire's demultiplexing --
    ``join`` requests submitted a step, so the loop's rows are at mixed
    phases and a joiner enters while others are mid-block.  Every
    request runs until it has emitted ``blocks`` generated blocks and
    none finishes; then ONE pass outside the loop (``sdar.decode_step``,
    no row committing) reads every slot's stored pages back: the logits
    at the block each row carries, in the state it carries it.  Then
    the requests are cancelled.  Returns a record a request: its slot,
    what it emitted, the positions stored, the carried block and the
    read-back logits there."""
    from aiko_services_tpu.models import sdar
    from aiko_services_tpu.models.batching import Request
    if batcher.active_count or batcher.blocks_in_flight or batcher.pending:
        raise RuntimeError("the reference check needs an idle batcher")
    if len(prompts) > batcher.max_slots:
        raise ValueError(f"{len(prompts)} sequences for "
                         f"{batcher.max_slots} slots")
    config = batcher.config
    size = config.block_length
    emitted = [[] for _ in prompts]
    wanted = [blocks * size - len(prompt) % size for prompt in prompts]
    requests = [Request(
        request_id=f"reference/{index}", prompt_tokens=list(prompt),
        max_new_tokens=batcher.max_seq,
        emit=lambda _id, token, _finished, out=emitted[index]:
            out.append(token))
        for index, prompt in enumerate(prompts)]
    waiting, steps = list(requests), 0
    try:
        while waiting or any(len(out) < want
                             for out, want in zip(emitted, wanted)):
            for request in waiting[:join]:
                batcher.submit(request)
            del waiting[:join]
            batcher.step()
            steps += 1
            if steps > 64 * len(prompts):
                raise RuntimeError("the served loop made no progress")
        if batcher.blocks_in_flight or batcher.pending:
            raise RuntimeError("the served loop left work in flight")
        chain = jax.device_get({key: batcher._loop_chain[key] for key in
                                ("lengths", "active", "history")})
        logits, batcher.cache = sdar.decode_step(
            batcher.params, config,
            jnp.asarray(chain["history"][:, :size]), batcher.cache,
            jnp.asarray(chain["lengths"]),
            jnp.zeros((batcher.max_slots,), bool),
            jnp.asarray(chain["active"]))
        records = []
        for request, out in zip(requests, emitted):
            slot = request.slot
            records.append({
                "slot": slot, "emitted": list(out),
                "live": bool(chain["active"][slot]),
                "stored": int(chain["lengths"][slot]),
                "state": chain["history"][slot, :size].tolist(),
                "logits": np.asarray(jax.device_get(
                    logits[slot].astype(jnp.float32)))})
    finally:
        for request in requests:
            batcher.cancel(request.request_id)
        batcher.run_until_drained()
    return records, batcher.take_block_stats()


def explain_block(logits_of, state, final, steps: int, mask_token: int,
                  tie: float, notes: dict, done: int = 0):
    """Whether the static rule on the REFERENCE's logits explains how a
    served block came out: from ``state``, passes that each decide
    :func:`quota` masked positions, until ``final``.  The served side
    is followed where its choice differs from the reference's own by a
    near-tie and nowhere else: a token whose reference logit lies
    within ``tie`` of the position's best, positions whose confidence
    lies within ``2 * tie`` (in the log) of the best left undecided.
    ``logits_of(state)`` -> the reference's logits ``[B, vocab]`` with
    ``state`` as the block.  Returns the number of choices followed
    that were not the reference's own (``notes["margin"]``: the worst
    token near-tie followed), or None: no sequence of near-ties gives
    ``final``.  The candidates of a pass are tried nearest first: the
    set of positions whose served tokens lie closest to the
    reference's best there, its own choice first among equals (a
    position the served side decided LATER, in another state, tends to
    hold a token this pass's logits rank lower)."""
    size = len(state)
    masked = [at for at in range(size) if state[at] == mask_token]
    if not masked:
        return 0
    scores = np.array(logits_of(state), dtype=np.float64)
    scores[:, mask_token] = -np.inf
    peak = scores.max(-1)
    norm = peak + np.log(np.exp(scores - peak[:, None]).sum(-1))
    own = peak - norm               # the log of c at its own x0 ...
    took = scores[np.arange(size), final] - norm    # ... at the served
    count = min(quota(size, steps, done), len(masked))
    ranked = sorted(sorted(masked, key=lambda at: (-own[at], at))[:count])
    candidates = sorted(
        (max(own[at] - took[at] for at in chosen), list(chosen) != ranked,
         list(chosen))
        for chosen in itertools.combinations(masked, count))
    for margin, _, chosen in candidates:
        rest = [at for at in masked if at not in chosen]
        if margin > tie or (rest and min(took[at] for at in chosen)
                            < max(own[at] for at in rest) - 2 * tie):
            continue
        after = [final[at] if at in chosen else token
                 for at, token in enumerate(state)]
        deeper = explain_block(logits_of, after, final, steps, mask_token,
                               tie, notes, done + 1)
        if deeper is not None:
            notes["margin"] = max(notes.get("margin", 0.0), float(margin))
            return deeper + (chosen != ranked) + sum(
                bool(took[at] < own[at]) for at in chosen)
    return None


def compare_loop(batcher, seed: int, prompt_lengths, blocks: int,
                 join: int, tie: float, control: str | None = None) -> dict:
    """The device loop against the reference, on ``len(prompt_lengths)``
    seeded prompts served AT ONCE (:func:`served_loop`).  Two things
    are held to the reference.  **What was stored**: every slot's
    pages, read back by one pass, give the logits the reference gives
    over the same sequence -- the prompt and everything the slot
    emitted, then the block it carries -- within the check's limit
    (``loop_readback_max_abs_diff``): a block committed before it was
    whole, a neighbour's K/V in a slot's page or a ring read out of
    order shows here.  **What was decided**: each of a request's first
    ``blocks`` generated blocks is replayed by the static rule on the
    reference's own logits, teacher-forced on what the slot emitted
    before it (:func:`explain_block`, ``tie`` wide); near-ties are
    counted (``loop_flips`` of ``loop_blocks``,
    ``loop_worst_margin``), a block no near-tie explains is a
    mismatch, as is a slot whose stored positions are not its prompt
    and its emitted tokens (``loop_mismatches``)."""
    config = batcher.config
    size, mask_token = config.block_length, config.mask_token
    widths = published_widths(config)
    prompts = []
    for index, length in enumerate(prompt_lengths):
        rng = np.random.default_rng([seed31(seed), 36, index])
        prompts.append([257] + rng.integers(97, 123, length - 1).tolist())
    records, block_stats = served_loop(batcher, prompts, blocks, join)

    def reference(tokens, start):
        """Its logits at the block that starts at ``start``, the
        sequence padded with whole blocks that no real position sees."""
        pad = -len(tokens) % BUCKET
        return forward(
            batcher.params, widths, list(tokens) + [0] * pad,
            list(range(start, start + size)),
            1 if control == "causal_in_block" else size,
            fp8=control == "fp8_activations")[0]

    worst = 0.0
    mismatches = flips = explained = forwards = 0
    notes: dict = {}
    for prompt, record in zip(prompts, records):
        sequence = prompt + record["emitted"]
        stored = record["stored"]
        if not record["live"] or stored != len(sequence) or stored % size:
            mismatches += 1
            continue
        worst = max(worst, float(np.abs(
            record["logits"] - reference(sequence + record["state"],
                                         stored)).max()))
        forwards += 1
        first = len(prompt) // size * size
        for start in range(first, first + blocks * size, size):
            final = sequence[start:start + size]
            state = [token if at < len(prompt) else mask_token
                     for at, token in enumerate(final, start)]

            def logits_of(state, start=start):
                nonlocal forwards
                forwards += 1
                return reference(sequence[:start] + state, start)
            found = explain_block(logits_of, state, final,
                                  config.denoising_steps, mask_token, tie,
                                  notes)
            explained += 1
            if found is None:
                mismatches += 1
            else:
                flips += found
    return {"loop_sequences": len(prompts),
            "loop_blocks": explained, "loop_flips": flips,
            "loop_worst_margin": notes.get("margin", 0.0),
            "loop_mismatches": mismatches,
            "loop_readback_max_abs_diff": worst,
            "loop_reference_forwards": forwards,
            "loop_passes": sum(block["passes"] for block in block_stats),
            "loop_row_passes": sum(block["row_passes"]
                                   for block in block_stats),
            "loop_commits": sum(block["commits"] for block in block_stats)}


# -- the pieces the harness asks an architecture for --------------------------

def check_reference(batcher, seed: int, spec: dict,
                    control: str | None = None) -> dict:
    """Served against the plain reference, on prompts made from
    ``seed``; ``spec`` is the configuration file's ``reference``.

    PASS BY PASS (:func:`compare`: the batcher's own admission program,
    pages and pass, one slot live, the logits of every pass) on TWO
    prompts -- ``prompt_tokens`` (past an admission chunk's boundary)
    and ``short_prompt_tokens`` (a few blocks: there the keys of a
    block are a large share of what a query sees, which is what the
    second control needs), neither a whole number of blocks, ``blocks``
    generated blocks past each.

    THE DEVICE LOOP (:func:`compare_loop`: the batcher's own ``step``,
    ``loop_prompt_tokens`` sequences at once, ``loop_join`` joining a
    step): what every slot stored, read back within the same limit,
    and what it decided, replayed by the rule on the reference's
    logits with near-ties ``2 * tolerance`` wide (a served logit
    within ``tolerance`` of the reference's can put another token on
    top only where the reference holds the two that close).

    The worst reading of the three is the check's ``max_abs_diff``; a
    mismatch of the loop's reads :data:`MISMATCH`.  ``control``: see
    :func:`compare`."""
    blocks = int(spec["blocks"])
    results = [
        compare(batcher, seed, min(int(spec[key]), batcher.max_seq // 2),
                blocks, control)
        for key in ("prompt_tokens", "short_prompt_tokens")]
    long, short = results
    loop = compare_loop(
        batcher, seed, [int(length) for length in
                        spec["loop_prompt_tokens"]], blocks,
        int(spec["loop_join"]), 2.0 * float(spec["tolerance"]), control)
    merged = {key: long[key] + short[key] for key in long
              if key not in ("max_abs_diff", "mean_abs_diff", "logit_std",
                             "router_worst_shortfall")}
    merged.update(
        loop,
        max_abs_diff=max(long["max_abs_diff"], short["max_abs_diff"],
                         loop["loop_readback_max_abs_diff"],
                         MISMATCH * loop["loop_mismatches"]),
        mean_abs_diff=(long["mean_abs_diff"] + short["mean_abs_diff"]) / 2,
        logit_std=(long["logit_std"] + short["logit_std"]) / 2,
        router_worst_shortfall=max(long["router_worst_shortfall"],
                                   short["router_worst_shortfall"]),
        long_max_abs_diff=long["max_abs_diff"],
        short_max_abs_diff=short["max_abs_diff"])
    return merged


def width_differences(config: dict, batcher) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none): every published key with a
    field, the served context, the keys whose one implemented value the
    program's family is, the precision, and the generation's own sizes
    (the file's ``generation``)."""
    served = batcher.config
    wrong = [(key, config[key], getattr(served, field))
             for key, field in WIDTH_FIELDS.items()
             if float(getattr(served, field)) != float(config[key])]
    if served.max_seq != config["max_position_embeddings"]:
        wrong.append(("max_position_embeddings",
                      config["max_position_embeddings"], served.max_seq))
    wrong.extend((key, config[key], value)
                 for key, value in IMPLEMENTED.items()
                 if config[key] != value)
    if served.dtype != "bfloat16" or served.kv_dtype != "bfloat16":
        wrong.append(("torch_dtype", "bfloat16",
                      (served.dtype, served.kv_dtype)))
    wrong.extend((key, value, getattr(served, key))
                 for key, value in config["generation"].items()
                 if getattr(served, key) != value)
    return wrong


def element_parameters(config: dict) -> dict:
    """What the file hands the LLM element: the family's name and its
    published widths (``elements/llm.py`` builds the served config from
    them; the definition's own ``max_seq``, ``block_length`` and
    ``denoising_steps`` are the served context and generation)."""
    return {"family": "sdar_moe",
            "widths": {key: config[key] for key in WIDTH_FIELDS}}


# -- what a pass must stream and compute --------------------------------------

def attention_weights(widths: dict) -> int:
    """One layer's attention projections: W_q, W_k, W_v, W_o."""
    hidden, width = int(widths["hidden_size"]), int(widths["head_dim"])
    heads, kv_heads = int(widths["num_attention_heads"]), \
        int(widths["num_key_value_heads"])
    return 2 * hidden * heads * width + 2 * hidden * kv_heads * width


def expert_weights(widths: dict) -> int:
    """One expert's SwiGLU."""
    return 3 * int(widths["hidden_size"]) \
        * int(widths["moe_intermediate_size"])


def experts_touched(widths: dict, positions: float) -> float:
    """Experts a pass over ``positions`` live positions is expected to
    touch in one layer under uniform routing: ``E (1 - (1 - k / E) **
    positions)``, never more than ``E``."""
    experts = int(widths["num_experts"])
    top_k = int(widths["num_experts_per_tok"])
    return min(float(experts),
               experts * (1.0 - (1.0 - top_k / experts) ** positions))


def cache_bytes_per_token(widths: dict, cache_bytes: int = 2) -> int:
    """One token's keys and values over all layers."""
    return int(widths["num_hidden_layers"]) * 2 * cache_bytes \
        * int(widths["num_key_value_heads"]) * int(widths["head_dim"])


def live_rows(config: dict, tokens_per_pass: float) -> tuple[float, int]:
    """(live rows a pass, positions a row) from the tokens a pass emits
    -- what the harness hands a count as ``rows``
    (``batcher.rows_per_step`` = tokens emitted / passes): a live row
    emits ``block_length`` tokens every ``denoising_steps + 1`` passes,
    so ``rows x (T + 1) / B`` rows are live, ``B`` positions each."""
    generation = config["generation"]
    block, steps = int(generation["block_length"]), \
        int(generation["denoising_steps"])
    return tokens_per_pass * (steps + 1) / block, block


def block_decode_attention(config: dict, rows: float,
                           context_tokens: float,
                           cache_bytes: int = 2) -> dict:
    """What the paged decode kernel under a pass's attention
    (``ops/pallas_decode.py`` through ``flash_verify_append``) must do
    in one pass, all layers: read every live row's stored keys and
    values once; per stored token, layer and query row (``B x heads`` a
    live row) the score and the value product over ``head_dim``."""
    live, block = live_rows(config, rows)
    heads, width = int(config["num_attention_heads"]), \
        int(config["head_dim"])
    return {
        "bytes": live * context_tokens
        * cache_bytes_per_token(config, cache_bytes),
        "operations": live * context_tokens
        * int(config["num_hidden_layers"]) * block * heads * 4.0 * width}


def decode_step(config: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """What one PASS must do, ``rows`` being the tokens it emits
    (:func:`live_rows`).  Bytes: every layer's attention projections
    and router once and the experts its live positions are expected to
    touch under UNIFORM INDEPENDENT routing (``128 (1 - (120 / 128) **
    positions)`` a layer: an assumption about the data, not what the
    pass streamed -- the harness hands a count no measured experts;
    where routing is less spread the share of the roofline reads high
    by ``assumed / measured``: PERF.md section 5); the head
    once; every live row's stored keys and values once (bfloat16 all).
    Operations: two per weight a position multiplies by (its
    ``num_experts_per_tok`` experts, not the touched ones; the head at
    every position of the block), and the attention products of
    :func:`block_decode_attention`."""
    live, block = live_rows(config, rows)
    positions = live * block
    hidden = int(config["hidden_size"])
    layers = int(config["num_hidden_layers"])
    attention = attention_weights(config)
    router = hidden * int(config["num_experts"])
    expert = expert_weights(config)
    head = hidden * int(config["vocab_size"])
    streamed = layers * (attention + router
                         + experts_touched(config, positions) * expert) \
        + head
    multiplied = layers * (attention + router + int(
        config["num_experts_per_tok"]) * expert) + head
    kernel = block_decode_attention(config, rows, context_tokens,
                                    cache_bytes)
    return {"bytes": streamed * weight_bytes + kernel["bytes"],
            "operations": 2.0 * multiplied * positions
            + kernel["operations"]}
