"""A second model family, known to no file outside this directory: the
program's ``tiny-moe`` preset (the Llama layer with its feed-forward
replaced by 4 SwiGLU experts, top-2 routed per token; with
``capacity_factor`` 2.0 an expert's buffer holds every token, so
nothing is dropped).  It proves that the harness takes a family as
files: ``test_tiny_moe.py`` walks a cell of it with ``--rehearse``.

Attention, norms, rotary embedding and the served side are the Llama
family's (imported); the routed feed-forward, the width check over the
expert keys and a per-layer list, the element's parameters and the
step count are this family's own."""

from functools import lru_cache

import jax
import jax.numpy as jnp

from benchmark.architectures import llama

FIELDS = {**{key: field for key, field in llama.WIDTH_FIELDS.items()
             if key != "intermediate_size"},
          "moe_intermediate_size": "hidden_dim", "num_experts": "n_experts",
          "num_experts_per_tok": "n_experts_per_token"}


@lru_cache(maxsize=None)
def routed_ffn(top_k: int):
    """The plain routed feed-forward over normed activations h [S, D]:
    softmax over the router's logits, the ``top_k`` largest
    renormalised to sum to one, every expert computed for every token
    and weighted by its gate (nought for the experts not chosen)."""
    def ffn(h, weights):
        probabilities = jax.nn.softmax(h @ weights["w_router"], axis=-1)
        gates, chosen = jax.lax.top_k(probabilities, top_k)
        gates = gates / gates.sum(-1, keepdims=True)
        share = (jax.nn.one_hot(chosen, probabilities.shape[-1])
                 * gates[..., None]).sum(1)                  # [S, E]
        hidden = jax.nn.silu(jnp.einsum("sd,edf->esf", h,
                                        weights["w_gate"])) \
            * jnp.einsum("sd,edf->esf", h, weights["w_up"])
        return jnp.einsum("se,esd->sd", share, jnp.einsum(
            "esf,efd->esd", hidden, weights["w_down"]))
    return ffn


def check_reference(batcher, seed: int, spec: dict) -> dict:
    return llama.check_reference(
        batcher, seed, spec,
        ffn=routed_ffn(int(batcher.config.n_experts_per_token)))


def width_differences(config: dict, batcher) -> list:
    wrong = llama.width_differences(config, batcher, FIELDS)
    served = batcher.config
    # The per-layer list: every layer of this family is sparse.
    kinds = ["sparse" if served.n_experts else "dense"] * served.n_layers
    if config["mlp_layer_types"] != kinds:
        wrong.append(("mlp_layer_types", config["mlp_layer_types"], kinds))
    if served.head_dim != config["head_dim"]:
        wrong.append(("head_dim", config["head_dim"], served.head_dim))
    return wrong


def element_parameters(config: dict) -> dict:
    """The published keys whole, lists included: the family's element
    class (``elements.py`` here) picks the model from them."""
    return {"published": {key: config[key] for key in
                          (*FIELDS, "head_dim", "mlp_layer_types")}}


def decode_step(config: dict, rows: float, context_tokens: float,
                weight_bytes: int = 2, cache_bytes: int = 2) -> dict:
    """One decode step: a row multiplies by the attention projections,
    the router and its ``num_experts_per_tok`` experts; the step streams
    the attention and router weights once and as many experts as its
    rows can touch (all of them from ``num_experts / top_k`` rows up);
    the cache as the Llama family's.  bf16 weights (``weight_bytes``
    2): the cell serves unquantized."""
    hidden, layers = int(config["hidden_size"]), \
        int(config["num_hidden_layers"])
    heads, head = int(config["num_attention_heads"]), int(config["head_dim"])
    kv = int(config["num_key_value_heads"]) * head
    experts, top_k = int(config["num_experts"]), \
        int(config["num_experts_per_tok"])
    attention = 2 * hidden * heads * head + 2 * hidden * kv
    router = hidden * experts
    expert = 3 * hidden * int(config["moe_intermediate_size"])
    unembed = hidden * int(config["vocab_size"])
    touched = min(float(experts), rows * top_k)
    return {
        "bytes": (layers * (attention + router + touched * expert)
                  + unembed) * weight_bytes
        + rows * context_tokens * layers * 2 * kv * cache_bytes,
        "operations": 2.0 * rows * (
            layers * (attention + router + top_k * expert) + unembed)
        + 4.0 * rows * context_tokens * heads * head * layers,
    }
