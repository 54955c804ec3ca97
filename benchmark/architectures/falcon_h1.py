"""The Falcon-H1 family (``model_type: falcon_h1``; Falcon-H1-34B-Instruct is
the configuration the benchmark runs), as the harness knows it: found by
the ``"architecture": "falcon_h1"`` of a configuration file.  The four
pieces a family brings (``benchmark/architectures/__init__.py``) --
``check_reference``, ``width_differences``, ``element_parameters``,
``decode_step`` -- and the counts of its two kernels (``ssd_decode``,
``ssd_chunk``).

**The plain reference** (``forward``): the forward pass in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, one sequence, no cache, no
kernel, no batching, nothing of ``aiko_services_tpu.models``.  For a
layer's input ``h`` and ``a = RMSNorm(h)`` (eps ``rms_norm_eps``)::

    h'  = h + Attn(a attention_in_multiplier) attention_out_multiplier
            + Mix(a ssm_in_multiplier) ssm_out_multiplier
    h'' = h' + W_down(silu(u W_gate m0) * u W_up) m1,  u = RMSNorm(h')

- ``Attn``: ``q = a W_q`` (``num_attention_heads`` of ``head_dim``), ``k
  = (a W_k) key_multiplier``, ``v = a W_v`` (``num_key_value_heads``);
  rotate-half rotary of base ``rope_theta`` on q and k (position ``t``,
  pair ``(i, i + head_dim / 2)`` turned by ``t theta^(-2i / head_dim)``);
  causal softmax of ``q k^T / sqrt(head_dim)``; ``W_o``.
- ``Mix`` (Mamba-2): ``[z | x | B | C | dt] = (a W_in) * mup``, ``mup``
  the five ``ssm_multipliers`` over those segments; ``xBC`` through a
  causal depthwise convolution of ``mamba_d_conv`` taps with a bias from
  zero history, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head, TOKEN BY TOKEN (a ``lax.scan`` over time: it
  shares no algorithm with the served chunk scan), ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t B_t^T`` from ``S_0 = 0``, ``y_t = S_t C_t + D x_t``
  (head ``h`` reading group ``h // (heads / groups)``); ``W_out`` of
  ``RMSNorm`` over each of ``mamba_n_groups`` groups of ``y * silu(z)``
  (the gate first: ``mamba_norm_before_gate`` false).
- ends: the embedding times ``embedding_multiplier``, a final RMSNorm,
  the untied head times ``lm_head_multiplier``.

It is given the weights the system serves, cast to float32 IN BLOCKS --
a layer's attention, its mixer, its MLP ``MLP_BLOCK`` intermediate
columns at a time, the head ``HEAD_BLOCK`` vocabulary rows at a time --
so that it fits beside 13.8 GB of served state.  Departures from the
source, each under the configuration's ``assumed``: random weights; the
order of the ``ssm_multipliers`` segments and the gate before the
grouped norm are the published modelling code as this file reads it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

HEAD_BLOCK = 16_384
MLP_BLOCK = 5376
BUCKET = 256        # sequences are padded to this: causal, so harmless
MISMATCH = 100.0    # what a slot whose stored positions are wrong reads

# Published config.json key -> the served config's field.
WIDTH_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "hidden_dim", "mamba_d_ssm": "ssm_dim",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_state": "ssm_state", "mamba_n_groups": "ssm_groups",
    "mamba_d_conv": "conv_kernel", "mamba_chunk_size": "ssm_chunk",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_in_multiplier": "attention_in_multiplier",
    "attention_out_multiplier": "attention_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "mlp_multipliers": "mlp_multipliers",
    "ssm_multipliers": "ssm_multipliers"}

# Published keys with no field: what the program's family implements,
# and the only value of each it can serve.
IMPLEMENTED = {
    "model_type": "falcon_h1", "hidden_act": "silu",
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "mamba_proj_bias": False, "mamba_conv_bias": True,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "mamba_use_mlp": True, "attn_layer_indices": None,
    "rope_scaling": None, "tie_word_embeddings": False}

CONTROLS = ("fp8_activations", "chunk_reset")


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


def _rotary(x, theta: float):
    """Rotate-half rotary over ``x [S, heads, head_dim]`` at positions
    ``0..S-1``, the angles in float64."""
    length, _, dim = x.shape
    inverse = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.outer(np.arange(length, dtype=np.float64), inverse)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    first, second = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(jnp.float32), eps)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "key_multiplier", "scale_in",
                                   "scale_out", "fp8"))
def _attention(a, layer, *, heads, kv_heads, head_dim, theta,
               key_multiplier, scale_in, scale_out, fp8):
    """``a [S, D]`` (normed) -> the attention's term of the stream."""
    weights = _float32(layer)
    length = a.shape[0]
    a = _fp8(a * scale_in, fp8)
    q = _rotary((a @ weights["wq"]).reshape(length, heads, head_dim), theta)
    k = _rotary((a @ weights["wk"] * key_multiplier)
                .reshape(length, kv_heads, head_dim), theta)
    v = (a @ weights["wv"]).reshape(length, kv_heads, head_dim)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(head_dim)
    causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1),
                          v).reshape(length, -1)
    return _fp8(attended, fp8) @ weights["wo"] * scale_out


@partial(jax.jit, static_argnames=("heads", "head_dim", "state", "groups",
                                   "eps", "scale_in", "scale_out",
                                   "segments", "fp8", "reset"))
def _mixer(a, layer, *, heads, head_dim, state, groups, eps, scale_in,
           scale_out, segments, fp8, reset):
    """``a [S, D]`` (normed) -> the mixer's term of the stream, the
    state followed token by token from zero.  ``reset`` > 0 (the second
    control) zeroes the state and the convolution's history at every
    multiple of ``reset`` tokens."""
    weights = _float32(layer)
    length = a.shape[0]
    inner, width = heads * head_dim, groups * state
    mup = np.repeat(np.asarray(segments, np.float32),
                    (inner, inner, width, width, heads))
    projected = (_fp8(a * scale_in, fp8) @ weights["w_in"]) * mup
    z = projected[:, :inner]
    rows = projected[:, inner:2 * inner + 2 * width]
    dt = jax.nn.softplus(projected[:, 2 * inner + 2 * width:]
                         + weights["dt_bias"])
    taps = weights["conv"].shape[0]
    position = jnp.arange(length)
    mixed = weights["conv_bias"]
    for back in range(taps):            # tap taps - 1 - back reads t - back
        seen = jnp.roll(rows, back, axis=0)
        keep = position >= back
        if reset:
            keep &= position % reset >= back
        mixed = mixed + weights["conv"][taps - 1 - back] \
            * jnp.where(keep[:, None], seen, 0.0)
    mixed = jax.nn.silu(mixed)
    x = mixed[:, :inner].reshape(length, heads, head_dim)
    per_group = heads // groups
    b = jnp.repeat(mixed[:, inner:inner + width]
                   .reshape(length, groups, state), per_group, axis=1)
    c = jnp.repeat(mixed[:, inner + width:]
                   .reshape(length, groups, state), per_group, axis=1)
    decay = jnp.exp(dt * -jnp.exp(weights["a_log"]))
    fresh = (position % reset == 0) if reset \
        else jnp.zeros((length,), bool)

    def token(s, xs):
        x_t, dt_t, decay_t, b_t, c_t, fresh_t = xs
        s = jnp.where(fresh_t, 0.0, s) * decay_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, head_dim, state), jnp.float32),
        (x, dt, decay, b, c, fresh))
    y = y + weights["d_skip"][:, None] * x
    gated = (y.reshape(length, inner) * jax.nn.silu(z)) \
        .reshape(length, groups, -1)
    normed = _rms_norm(gated, 1.0, eps).reshape(length, inner) \
        * weights["ssm_norm"]
    return _fp8(normed, fp8) @ weights["w_out"] * scale_out


@partial(jax.jit, static_argnames=("gate_scale", "fp8"))
def _mlp_block(u, w_gate, w_up, w_down, *, gate_scale, fp8):
    """``u [S, D]`` (normed) through ``MLP_BLOCK`` of the intermediate
    columns, before the down multiplier."""
    u = _fp8(u, fp8)
    hidden = jax.nn.silu(u @ w_gate.astype(jnp.float32) * gate_scale) \
        * (u @ w_up.astype(jnp.float32))
    return _fp8(hidden, fp8) @ w_down.astype(jnp.float32)


@jax.jit
def _head_block(x, block):
    return x @ block.astype(jnp.float32)


def forward(params: dict, widths: dict, tokens, positions,
            control: str | None = None, chunk: int = 512):
    """Logits ``[len(positions), vocab]`` of the float32 forward pass
    over ``tokens`` (one sequence, padded at its end to a multiple of
    ``BUCKET``: causal, so the positions asked for see none of it) at
    ``positions``.  ``widths``: published keys.  ``control``:
    ``fp8_activations`` -- every block multiplies fp8-rounded inputs,
    the reference in the nearest precision below the stated one;
    ``chunk_reset`` -- the mixer's state and convolution history zeroed
    at every ``chunk`` tokens, as an admission that did not hand the
    state on would leave them."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"control={control!r}: {' | '.join(CONTROLS)}")
    fp8 = control == "fp8_activations"
    eps = float(widths["rms_norm_eps"])
    heads, kv_heads = (int(widths["num_attention_heads"]),
                       int(widths["num_key_value_heads"]))
    attention = dict(
        heads=heads, kv_heads=kv_heads, head_dim=int(widths["head_dim"]),
        theta=float(widths["rope_theta"]),
        key_multiplier=float(widths["key_multiplier"]),
        scale_in=float(widths["attention_in_multiplier"]),
        scale_out=float(widths["attention_out_multiplier"]), fp8=fp8)
    mixer = dict(
        heads=int(widths["mamba_n_heads"]),
        head_dim=int(widths["mamba_d_head"]),
        state=int(widths["mamba_d_state"]),
        groups=int(widths["mamba_n_groups"]), eps=eps,
        scale_in=float(widths["ssm_in_multiplier"]),
        scale_out=float(widths["ssm_out_multiplier"]),
        segments=tuple(float(m) for m in widths["ssm_multipliers"]),
        fp8=fp8, reset=int(chunk) if control == "chunk_reset" else 0)
    gate_scale, down_scale = (float(m) for m in widths["mlp_multipliers"])
    tokens = list(tokens) + [0] * (-len(tokens) % BUCKET)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32) \
            * float(widths["embedding_multiplier"])
        for index in range(int(widths["num_hidden_layers"])):
            def take(*keys):            # this layer's leaves, and no more
                return {key: layers[key][index] for key in keys}
            a = _norm(x, layers["norm"][index], eps=eps)
            x = x + _attention(a, take("wq", "wk", "wv", "wo"),
                               **attention) \
                + _mixer(a, take("w_in", "conv", "conv_bias", "a_log",
                                 "dt_bias", "d_skip", "ssm_norm", "w_out"),
                         **mixer)
            u = _norm(x, layers["ffn_norm"][index], eps=eps)
            width = layers["w_gate"].shape[2]
            ffn = sum(_mlp_block(
                u, layers["w_gate"][index, :, first:first + MLP_BLOCK],
                layers["w_up"][index, :, first:first + MLP_BLOCK],
                layers["w_down"][index, first:first + MLP_BLOCK],
                gate_scale=gate_scale, fp8=fp8)
                for first in range(0, width, MLP_BLOCK))
            x = x + ffn * down_scale
        x = _norm(x[jnp.asarray(positions)], params["final_norm"], eps=eps)
        vocab = params["unembed"].shape[1]
        logits = jnp.concatenate(
            [_head_block(x, params["unembed"][:, first:first + HEAD_BLOCK])
             for first in range(0, vocab, HEAD_BLOCK)], axis=-1)
        logits = logits * float(widths["lm_head_multiplier"])
        return np.asarray(jax.device_get(logits))


# -- the served side ----------------------------------------------------------

def published_widths(served) -> dict:
    """The served config's fields under their published keys."""
    widths = {key: getattr(served, field)
              for key, field in WIDTH_FIELDS.items()}
    widths["mlp_multipliers"] = list(served.mlp_multipliers)
    widths["ssm_multipliers"] = list(served.ssm_multipliers)
    return widths


def _prompt(seed: int, index: int, length: int) -> list:
    """BOS, then random lower-case bytes, as ByteTokenizer would give."""
    rng = np.random.default_rng([seed31(seed), 42, index])
    return [257] + rng.integers(97, 123, length - 1).tolist()


def served_logits(batcher, prompt, decode_steps: int):
    """The system's own path on one sequence, in the batcher's own
    pools (which must be idle): the prompt admitted chunk by chunk into
    slot 0 through the batcher's admission program -- the state handed
    from chunk to chunk, the last chunk padded -- then greedy decode
    steps through the device loop's own body at the batcher's width,
    every other row sitting the step out.  Returns (logits at each
    chunk's last position and every decode step, those positions, the
    tokens decoded)."""
    from aiko_services_tpu.models import falcon_h1
    if batcher.active_count or batcher.blocks_in_flight or batcher.pending:
        raise RuntimeError("the reference check needs an idle batcher")
    params, config = batcher.params, batcher.config
    chunk, slot = batcher.prefill_chunk, 0
    pages = batcher._pages
    if not pages.ensure(slot, pages.pages_for(
            len(prompt) + decode_steps + 1, batcher.kv_page_tokens)):
        raise RuntimeError("the reference check found no free pages")
    batcher._sync_page_table()
    rows, positions, decoded = [], [], []
    try:
        for start in range(0, len(prompt), chunk):
            piece = prompt[start:start + chunk]
            padded = np.zeros((1, chunk), dtype=np.int32)
            padded[0, :len(piece)] = piece
            logits, batcher.cache = falcon_h1.prefill_into_slot(
                params, config, jnp.asarray(padded), batcher.cache,
                jnp.int32(slot), jnp.int32(start),
                jnp.int32(len(piece) - 1))
            rows.append(logits[0, 0].astype(jnp.float32))
            positions.append(start + len(piece) - 1)
        trash = batcher.max_seq - 1
        for step in range(decode_steps):
            token = jnp.argmax(rows[-1]).astype(jnp.int32)
            decoded.append(token)
            tokens = jnp.zeros((batcher.max_slots,), jnp.int32) \
                .at[slot].set(token)
            lengths = jnp.full((batcher.max_slots,), trash, jnp.int32) \
                .at[slot].set(len(prompt) + step)
            logits, batcher.cache = falcon_h1.decode_step(
                params, config, tokens, batcher.cache, lengths)
            rows.append(logits[slot].astype(jnp.float32))
            positions.append(len(prompt) + step)
        served = np.asarray(jax.device_get(jnp.stack(rows)))
        tokens = [int(token) for token in jax.device_get(decoded)]
    finally:
        pages.release(slot)
        batcher._sync_page_table()
    return served, positions, tokens


def compare(batcher, seed: int, prompt_tokens: int, decode_steps: int,
            control: str | None = None) -> dict:
    """Served against reference on one seeded prompt: worst and mean
    absolute logit difference over each admission chunk's last position
    and every decode step, and how many greedy tokens agree."""
    prompt = _prompt(seed, 0, prompt_tokens)
    served, positions, decoded = served_logits(batcher, prompt,
                                               decode_steps)
    reference = forward(batcher.params, published_widths(batcher.config),
                        prompt + decoded, positions, control,
                        batcher.prefill_chunk)
    difference = np.abs(served - reference)
    return {"max_abs_diff": float(difference.max()),
            "mean_abs_diff": float(difference.mean()),
            "logit_std": float(reference.std()),
            "positions": len(positions),
            "argmax_agree": int((served.argmax(-1)
                                 == reference.argmax(-1)).sum())}


def served_loop(batcher, first, prompts, new_tokens: int, join: int):
    """The system's own serving path on many sequences, through the idle
    batcher's own ``submit`` and ``step`` (admission chunk by chunk, the
    fold of joiners, the DEVICE LOOP at the batcher's ring, the retire):
    the ``first`` prompts are served two tokens each and retired, so the
    lowest slots have held a state; then ``prompts`` are submitted
    ``join`` a step -- rows at mixed phases, the first of them
    re-admitted into those slots -- and each runs until it has emitted
    ``new_tokens``.  ONE decode step outside the loop (the loop's own
    body) then reads every slot back: each live row's logits at its
    next position.  Then the requests are cancelled.  Returns a record
    a request of ``prompts`` (its slot, what it emitted, the position
    stored, its read-back logits) and how many took a slot the first
    ones had held."""
    from aiko_services_tpu.models import falcon_h1
    from aiko_services_tpu.models.batching import Request
    if batcher.active_count or batcher.blocks_in_flight or batcher.pending:
        raise RuntimeError("the reference check needs an idle batcher")
    if len(prompts) > batcher.max_slots:
        raise ValueError(f"{len(prompts)} sequences for "
                         f"{batcher.max_slots} slots")
    budget = 64 * (len(first) + len(prompts)) * (1 + new_tokens)

    def request(name, prompt, tokens, out):
        return Request(request_id=f"reference/{name}",
                       prompt_tokens=list(prompt), max_new_tokens=tokens,
                       emit=lambda _id, token, _done: out.append(token))

    earlier = [request(f"first/{index}", prompt, 2, [])
               for index, prompt in enumerate(first)]
    emitted = [[] for _ in prompts]
    requests = [request(index, prompt, batcher.max_seq, emitted[index])
                for index, prompt in enumerate(prompts)]
    steps = 0
    try:
        for done in earlier:
            batcher.submit(done)
        held = set()
        while any(not done.done for done in earlier):
            batcher.step()
            held.update(done.slot for done in earlier if done.slot >= 0)
            steps += 1
            if steps > budget:
                raise RuntimeError("the served loop made no progress")
        waiting = list(requests)
        while waiting or any(len(out) < new_tokens for out in emitted):
            for pending in waiting[:join]:
                batcher.submit(pending)
            del waiting[:join]
            batcher.step()
            steps += 1
            if steps > budget:
                raise RuntimeError("the served loop made no progress")
        while batcher.blocks_in_flight:
            batcher.step()
        chain = jax.device_get({key: batcher._loop_chain[key] for key in
                                ("tokens", "lengths", "active")})
        logits, batcher.cache = falcon_h1.decode_step(
            batcher.params, batcher.config, jnp.asarray(chain["tokens"]),
            batcher.cache, jnp.asarray(np.where(
                chain["active"], chain["lengths"], batcher.max_seq - 1)),
            jnp.asarray(chain["active"]))
        records = [{"slot": each.slot, "emitted": list(out),
                    "live": bool(chain["active"][each.slot]),
                    "stored": int(chain["lengths"][each.slot]),
                    "logits": np.asarray(jax.device_get(
                        logits[each.slot].astype(jnp.float32)))}
                   for each, out in zip(requests, emitted)]
    finally:
        for each in earlier + requests:
            batcher.cancel(each.request_id)
        batcher.run_until_drained()
    return records, sum(record["slot"] in held for record in records)


def loop_lengths(seed: int, count: int, lowest: int, highest: int) -> list:
    """``count`` prompt lengths log-uniform in [lowest, highest], drawn
    from the seed as the cell's traffic draws them."""
    rng = np.random.default_rng([seed31(seed), 42, 1])
    return [int(round(np.exp(value))) for value in rng.uniform(
        np.log(lowest), np.log(highest), count)]


def compare_loop(batcher, seed: int, spec: dict,
                 control: str | None = None) -> dict:
    """The device loop against the reference on ``loop_sequences``
    seeded prompts of the cell's lengths (:func:`served_loop`): every
    slot, read back by one step, gives the logits the reference gives
    over the same sequence -- the prompt and everything the slot emitted
    -- within the check's limit (``loop_readback_max_abs_diff``): a
    state left from a slot's last request, a neighbour's state or K/V,
    or a ring read out of order shows here.  A slot whose stored
    position is not its prompt and all it emitted but the last token is
    a mismatch (``loop_mismatches``)."""
    lowest, highest = (int(v) for v in spec["loop_prompt_tokens"])
    count = min(int(spec["loop_sequences"]), batcher.max_slots)
    first = [_prompt(seed, 100 + index, length) for index, length in
             enumerate(loop_lengths(seed + 1, int(spec["loop_first"]),
                                    lowest, highest))]
    prompts = [_prompt(seed, 1 + index, length) for index, length in
               enumerate(loop_lengths(seed, count, lowest, highest))]
    records, reused = served_loop(batcher, first, prompts,
                                  int(spec["loop_new_tokens"]),
                                  int(spec["loop_join"]))
    widths = published_widths(batcher.config)
    worst, mismatches = 0.0, 0
    for prompt, record in zip(prompts, records):
        sequence = prompt + record["emitted"]
        if not record["live"] or record["stored"] != len(sequence) - 1:
            mismatches += 1
            continue
        reference = forward(batcher.params, widths, sequence,
                            [len(sequence) - 1], control,
                            batcher.prefill_chunk)[0]
        worst = max(worst, float(np.abs(record["logits"]
                                        - reference).max()))
    return {"loop_sequences": len(prompts), "loop_mismatches": mismatches,
            "loop_slots_reused": reused,
            "loop_readback_max_abs_diff": worst}


# -- the four pieces the harness asks an architecture for --------------------

def check_reference(batcher, seed: int, spec: dict,
                    control: str | None = None) -> dict:
    """Served against the plain reference, on prompts made from
    ``seed``; ``spec`` is the configuration file's ``reference``.  ONE
    SEQUENCE (:func:`compare`): a ``prompt_tokens`` prompt through the
    batcher's own admission (three chunks at the cell's sizes: the
    state handed on twice, the last chunk padded), then
    ``decode_steps`` steps of the loop's own body across a page
    boundary, every served logit held to the reference's full forward.
    THE DEVICE LOOP (:func:`compare_loop`): ``loop_sequences`` sequences
    through the batcher's own ``step``, after ``loop_first`` served and
    retired, ``loop_join`` joining a step, ``loop_new_tokens`` each,
    every slot read back.  The worst reading is the check's
    ``max_abs_diff``; a mismatch of the loop's reads :data:`MISMATCH`.
    ``control``: ``fp8_activations`` or ``chunk_reset``
    (:func:`forward`), each of which has to fail."""
    single = compare(batcher, seed,
                     min(int(spec["prompt_tokens"]),
                         batcher.max_seq - int(spec["decode_steps"]) - 1),
                     int(spec["decode_steps"]), control)
    loop = compare_loop(batcher, seed, spec, control)
    return {**single, **loop,
            "single_max_abs_diff": single["max_abs_diff"],
            "max_abs_diff": max(single["max_abs_diff"],
                                loop["loop_readback_max_abs_diff"],
                                MISMATCH * loop["loop_mismatches"])}


def width_differences(config: dict, batcher) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none): every published key with a
    field -- the two lists of multipliers among them --, the served
    context, the keys whose one implemented value the program's family
    is, and the precision."""
    served = batcher.config
    widths = published_widths(served)

    def same(key):
        if isinstance(config[key], list):
            return [float(v) for v in widths[key]] \
                == [float(v) for v in config[key]]
        return float(widths[key]) == float(config[key])

    wrong = [(key, config[key], widths[key]) for key in WIDTH_FIELDS
             if not same(key)]
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
    return {"family": "falcon_h1",
            "widths": {key: config[key] for key in WIDTH_FIELDS}}


# -- counts, from the published widths alone ---------------------------------

def _mixer_shape(config: dict) -> tuple[int, int, int, int]:
    """(heads, head_dim, state, groups) of the mixer."""
    return (int(config["mamba_n_heads"]), int(config["mamba_d_head"]),
            int(config["mamba_d_state"]), int(config["mamba_n_groups"]))


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE layer."""
    heads, head_dim, state, _ = _mixer_shape(config)
    return heads * head_dim * state * 4


def tail_bytes(config: dict) -> int:
    """One slot's float32 convolution tail in one layer: the last
    ``d_conv - 1`` rows of ``xBC``."""
    heads, head_dim, state, groups = _mixer_shape(config)
    return (int(config["mamba_d_conv"]) - 1) \
        * (heads * head_dim + 2 * groups * state) * 4


def token_bytes(config: dict) -> int:
    """What the recurrence of one layer takes in and gives out for one
    token, float32: x, B, C and dt in, y out."""
    heads, head_dim, state, groups = _mixer_shape(config)
    return 4 * (2 * heads * head_dim + 2 * groups * state + heads)


def kv_bytes_per_token(config: dict, cache_bytes: int = 2) -> int:
    """One token's K and V over every layer."""
    return int(config["num_hidden_layers"]) * 2 \
        * int(config["num_key_value_heads"]) * int(config["head_dim"]) \
        * cache_bytes


def ssd_decode(config: dict, rows: float,
               context_tokens: float = 0.0) -> dict:
    """What the decode step of the recurrence must do in one step, all
    layers: each live row's state once in and once out, its token's x,
    B, C and dt in and y out (the convolution's tail is read and written
    by the convolution, outside the recurrence); per state element a
    multiply to decay, a multiply-add to take the token in and a
    multiply-add to answer: 5 H P N operations."""
    heads, head_dim, state, _ = _mixer_shape(config)
    layers = int(config["num_hidden_layers"])
    return {"bytes": rows * layers * (2 * state_bytes(config)
                                      + token_bytes(config)),
            "operations": rows * layers * 5.0 * heads * head_dim * state}


def prefill_chunk(config: dict) -> int:
    """The admission chunk of the configuration's LLM element."""
    for element in config.get("definition", {}).get("elements", ()):
        if element["name"] == config.get("llm_element"):
            return int(element["parameters"].get("prefill_chunk", 512))
    return 512


def ssd_chunk(config: dict, rows: float = 0.0,
              context_tokens: float = 0.0) -> dict:
    """What admission's scan must do for ONE prompt token, all layers
    (``readers/op_roofline.py`` multiplies by the slice's
    ``batcher.prefill_tokens``; rows and context do not enter): the
    token's x, B, C and dt in and y out, its share of the slot's state
    handed in and out once an admission chunk, and the SSD's products
    in sub-chunks of ``c = mamba_chunk_size``: a group's ``C B^T``
    (``2 c N`` a token and group), each head's masked product with
    ``dt x`` (``2 c P``), its answer from the state handed in (``2 P
    N``) and its part of the state handed on (``2 P N``)."""
    heads, head_dim, state, groups = _mixer_shape(config)
    sub = int(config["mamba_chunk_size"])
    layers = int(config["num_hidden_layers"])
    operations = 2.0 * sub * state * groups \
        + heads * (2.0 * sub * head_dim + 4.0 * head_dim * state)
    return {"bytes": layers * (token_bytes(config) + 2.0 * state_bytes(config)
                               / prefill_chunk(config)),
            "operations": layers * operations}


def layer_weights(config: dict) -> int:
    """A layer's matrix weights: attention, the mixer's projections and
    the SwiGLU (its vectors -- norms, convolution, A_log, dt_bias, D --
    counted apart, :func:`layer_vectors`)."""
    hidden = int(config["hidden_size"])
    heads, head_dim, state, groups = _mixer_shape(config)
    inner = heads * head_dim
    q_width = int(config["num_attention_heads"]) * int(config["head_dim"])
    kv_width = int(config["num_key_value_heads"]) * int(config["head_dim"])
    attention = 2 * hidden * q_width + 2 * hidden * kv_width
    mixer = hidden * (2 * inner + 2 * groups * state + heads) \
        + inner * hidden
    return attention + mixer + 3 * hidden * int(config["intermediate_size"])


def layer_vectors(config: dict) -> int:
    """A layer's vectors: the convolution and its bias, A_log, dt_bias,
    D, the mixer's norm and the layer's two norms."""
    hidden = int(config["hidden_size"])
    heads, head_dim, state, groups = _mixer_shape(config)
    channels = heads * head_dim + 2 * groups * state
    return (int(config["mamba_d_conv"]) + 1) * channels + 3 * heads \
        + heads * head_dim + 2 * hidden


def decode_step(config: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """What one decode step over ``rows`` live sequences of
    ``context_tokens`` mean context must do.  Bytes: every layer's
    bfloat16 weights once and the head (the embedding table is looked
    up, not streamed); each live row's state and convolution tail once
    in and once out in every layer; every live K/V row once.
    Operations: two per weight a row multiplies by, the recurrence's
    own, and per cached token, query head and layer the score and the
    value sum over ``head_dim``."""
    layers = int(config["num_hidden_layers"])
    weights = layers * (layer_weights(config) + layer_vectors(config)) \
        + int(config["hidden_size"]) * int(config["vocab_size"])
    recurrent = ssd_decode(config, rows)
    tails = rows * layers * 2 * tail_bytes(config)
    return {
        "bytes": weights * weight_bytes + recurrent["bytes"] + tails
        + rows * context_tokens * kv_bytes_per_token(config, cache_bytes),
        "operations": 2.0 * weights * rows + recurrent["operations"]
        + rows * context_tokens * layers * 4.0
        * int(config["num_attention_heads"]) * int(config["head_dim"])}
