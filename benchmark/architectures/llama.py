"""The Llama family (InternLM2, Llama 2/3: one decoder layer shape,
repeated), as the harness knows it: everything ``benchmark/run.py`` and
the readers need of a model family, found by the ``"architecture":
"llama"`` of a configuration file.  The four pieces a family brings
(``benchmark/architectures/__init__.py`` names them):

- ``check_reference`` -- served against the plain reference;
- ``width_differences`` -- published keys against what is served;
- ``element_parameters`` -- what the file hands the LLM element;
- ``decode_step`` -- the bytes and operations of one decode step.

**The plain reference**: the forward pass of the InternLM2 / Llama-style
decoder in straightforward float32 ``jax.numpy``, written from the
published description -- RMSNorm before attention and before the
feed-forward, rotary position embedding on queries and keys (the
rotate-half form of the Hugging Face implementation, base
``rope_theta``), grouped-query causal attention, SwiGLU, untied output
head, no biases.  No kernel, no cache, no batching, no bfloat16.

It is given the weights the system serves, dequantized (int8 times
its per-channel scale), one layer at a time so it never holds the
whole model in float32.  ``compare`` runs the served path -- prefill
into a paged cache, then decode steps through it -- on one seeded
prompt and returns the worst logit difference.

Departures from the description: none in the mathematics; the weights
are random (the configuration file's ``assumed``).

A family whose layers differ only in the feed-forward passes its own
``ffn(h, weights)`` to ``forward`` / ``compare`` and keeps the rest.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.traffic import seed31

# Published config.json key -> LlamaConfig field.
WIDTH_FIELDS = {"vocab_size": "vocab_size", "hidden_size": "dim",
                "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads",
                "intermediate_size": "hidden_dim",
                "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def _float32(leaf):
    """A served weight in float32: int8 times its scale, or a cast."""
    if isinstance(leaf, dict):
        return leaf["int8"].astype(jnp.float32) \
            * leaf["scale"].astype(jnp.float32)
    return leaf.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rotary(x, positions, theta):
    """x [S, H, hd]: rotate pairs (i, i + hd/2) by position * theta **
    (-2i / hd)."""
    half = x.shape[-1] // 2
    inverse = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def swiglu(h, weights):
    """The dense feed-forward over normed activations h [S, D]."""
    return (jax.nn.silu(h @ weights["w_gate"])
            * (h @ weights["w_up"])) @ weights["w_down"]


@partial(jax.jit,
         static_argnames=("heads", "kv_heads", "eps", "theta", "ffn"))
def _layer(x, layer, *, heads, kv_heads, eps, theta, ffn=swiglu):
    """One decoder layer over a whole sequence x [S, D]."""
    weights = {key: _float32(value) for key, value in layer.items()}
    length, hidden = x.shape
    head = hidden // heads
    positions = jnp.arange(length)
    h = _rms_norm(x, weights["attn_norm"], eps)
    q = (h @ weights["wq"]).reshape(length, heads, head)
    k = (h @ weights["wk"]).reshape(length, kv_heads, head)
    v = (h @ weights["wv"]).reshape(length, kv_heads, head)
    q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(
        jnp.float32(head))
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1),
                          v).reshape(length, hidden)
    x = x + attended @ weights["wo"]
    return x + ffn(_rms_norm(x, weights["mlp_norm"], eps), weights)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    return _rms_norm(x, _float32(final_norm), eps) @ _float32(unembed)


def forward(params: dict, config, tokens, positions,
            ffn=swiglu) -> np.ndarray:
    """Logits [len(positions), vocab] of the float32 forward pass over
    ``tokens`` (one sequence), at ``positions``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for index in range(config.n_layers):
            layer = jax.tree_util.tree_map(lambda leaf: leaf[index],
                                           params["layers"])
            x = _layer(x, layer, heads=config.n_heads,
                       kv_heads=config.n_kv_heads,
                       eps=float(config.norm_eps),
                       theta=float(config.rope_theta), ffn=ffn)
        logits = _head(x[jnp.asarray(positions)], params["final_norm"],
                       params["unembed"], eps=float(config.norm_eps))
        return np.asarray(jax.device_get(logits))


def served_logits(params: dict, config, prompt, decode_steps: int,
                  page_tokens: int, chunk: int):
    """The system's own path on one sequence: chunked prefill into slot
    0 of a paged cache, then greedy decode steps through it.  Returns
    (logits [1 + decode_steps, vocab], the tokens it decoded)."""
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.models.paged import (init_paged_cache,
                                                pages_per_slot)
    cache = init_paged_cache(config, 1, config.max_seq, page_tokens)
    pages = pages_per_slot(config.max_seq, page_tokens)
    cache["page_table"] = jnp.arange(1, pages + 1,
                                     dtype=jnp.int32)[None, :]
    rows = []
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        padded = np.zeros((1, chunk), dtype=np.int32)
        padded[0, :len(piece)] = piece
        logits, cache = llama.prefill_into_slot(
            params, config, jnp.asarray(padded), cache, jnp.int32(0),
            jnp.int32(start))
    rows.append(logits[0, len(piece) - 1].astype(jnp.float32))
    decoded = []
    for step in range(decode_steps):
        token = jnp.argmax(rows[-1]).astype(jnp.int32)[None]
        decoded.append(token)
        logits, cache = llama.decode_step(
            params, config, token, cache,
            jnp.asarray([len(prompt) + step], dtype=jnp.int32))
        rows.append(logits[0].astype(jnp.float32))
    served = np.asarray(jax.device_get(jnp.stack(rows)))
    tokens = [int(t[0]) for t in jax.device_get(decoded)]
    del cache
    return served, tokens


def compare(params: dict, config, seed: int, prompt_tokens: int,
            decode_steps: int, page_tokens: int, chunk: int,
            ffn=swiglu) -> dict:
    """Served against reference on one seeded prompt (BOS then random
    lower-case bytes, as ByteTokenizer would give): worst absolute
    logit difference over the last prompt position and every decode
    step, and whether the greedy tokens agree."""
    rng = np.random.default_rng([seed31(seed), 31])
    prompt = [257] + rng.integers(97, 123, prompt_tokens - 1).tolist()
    served, decoded = served_logits(params, config, prompt, decode_steps,
                                    page_tokens, chunk)
    sequence = prompt + decoded
    positions = list(range(len(prompt) - 1, len(sequence)))
    reference = forward(params, config, sequence, positions, ffn)
    difference = np.abs(served - reference)
    return {"max_abs_diff": float(difference.max()),
            "mean_abs_diff": float(difference.mean()),
            "logit_std": float(reference.std()),
            "positions": len(positions),
            "argmax_agree": int((served.argmax(-1)
                                 == reference.argmax(-1)).sum())}


# -- the four pieces the harness asks an architecture for --------------------

def check_reference(batcher, seed: int, spec: dict, ffn=swiglu) -> dict:
    """Served (what ``batcher`` serves with: its ``params``, ``config``,
    ``kv_page_tokens``, ``prefill_chunk``) against the plain reference
    on one prompt made from ``seed``; ``spec`` is the configuration
    file's ``reference``."""
    return compare(batcher.params, batcher.config, seed,
                   min(int(spec["prompt_tokens"]), batcher.max_seq // 2),
                   int(spec["decode_steps"]), batcher.kv_page_tokens,
                   batcher.prefill_chunk, ffn)


def width_differences(config: dict, batcher,
                      fields=WIDTH_FIELDS) -> list:
    """``(key, published, served)`` wherever the served model differs
    from the configuration file (none)."""
    served = batcher.config
    wrong = [(key, config[key], getattr(served, field))
             for key, field in fields.items()
             if float(getattr(served, field)) != float(config[key])]
    if served.max_seq != config["max_position_embeddings"]:
        wrong.append(("max_position_embeddings",
                      config["max_position_embeddings"], served.max_seq))
    return wrong


def element_parameters(config: dict) -> dict:
    """What the file hands the LLM element: its numeric top-level keys
    as ``widths`` (``benchmark.elements.ConfiguredLLM`` builds the
    served ``LlamaConfig`` from them)."""
    return {"widths": {key: value for key, value in config.items()
                       if isinstance(value, (int, float))
                       and not isinstance(value, bool)}}


def llama_config(widths: dict):
    """The program's ``LlamaConfig`` of the published ``widths``."""
    from aiko_services_tpu.models import llama
    fields = {}
    for key, field in WIDTH_FIELDS.items():
        kind = float if field in ("rope_theta", "norm_eps") else int
        fields[field] = kind(widths[key])
    return llama.LlamaConfig(**fields)


def layer_matmul_weights(widths: dict) -> int:
    """Weights of one layer's seven projections (q, k, v, o, gate, up,
    down), biases none."""
    hidden = int(widths["hidden_size"])
    head = hidden // int(widths["num_attention_heads"])
    kv = int(widths["num_key_value_heads"]) * head
    ffn = int(widths["intermediate_size"])
    return 2 * hidden * hidden + 2 * hidden * kv + 3 * hidden * ffn


def matmul_weights(widths: dict) -> int:
    """Every weight a decode step multiplies by: the layers' and the
    output head (the embedding is a gather of one row per sequence)."""
    return (int(widths["num_hidden_layers"]) * layer_matmul_weights(widths)
            + int(widths["hidden_size"]) * int(widths["vocab_size"]))


def cache_bytes_per_token(widths: dict, cache_bytes: int = 2) -> int:
    """Keys and values of one token over all layers."""
    hidden = int(widths["hidden_size"])
    head = hidden // int(widths["num_attention_heads"])
    kv = int(widths["num_key_value_heads"]) * head
    return int(widths["num_hidden_layers"]) * 2 * kv * cache_bytes


def decode_step(widths: dict, rows: float, context_tokens: float,
                weight_bytes: int = 1, cache_bytes: int = 2) -> dict:
    """What one decode step over ``rows`` live sequences of
    ``context_tokens`` mean context must do: stream every matmul weight
    once (``weight_bytes`` each: 1 for weight-only int8) and every
    live cache row once; two operations per weight per row, and four
    per cached token per query head per head element."""
    weights = matmul_weights(widths)
    hidden = int(widths["hidden_size"])
    layers = int(widths["num_hidden_layers"])
    return {
        "bytes": weights * weight_bytes
        + rows * context_tokens * cache_bytes_per_token(widths,
                                                        cache_bytes),
        "operations": 2.0 * weights * rows
        + 4.0 * rows * context_tokens * hidden * layers,
    }
