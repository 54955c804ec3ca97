"""Model families the LLM element serves, as data: which published
``config.json`` keys (``widths``) each takes and what each refuses.
Imports nothing of jax, so the create-time parameter check
(``analysis/params.py``) and the runtime (``elements/llm.py``,
``models/llama.py``, ``models/deepseek.py``) read ONE table.

An LLM element names a family and hands it widths::

    "parameters": {"family": "deepseek_v3",
                   "widths": {"hidden_size": 2048, "kv_lora_rank": 512,
                              ...},
                   "max_seq": 8192, ...}

A key the family lacks is an error; a key left out keeps the family's
default (``LlamaConfig`` / ``DeepseekConfig``).
"""

from __future__ import annotations

__all__ = ["FAMILY_WIDTHS", "FAMILY_REFUSES", "config_fields",
           "family_spec_error"]

#: family -> {published config.json key: config dataclass field}
FAMILY_WIDTHS: dict[str, dict[str, str]] = {
    "llama": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads",
        "intermediate_size": "hidden_dim",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"},
    "deepseek_v3": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim",
        "intermediate_size": "hidden_dim",
        "moe_intermediate_size": "moe_hidden_dim",
        "n_routed_experts": "n_experts",
        "num_experts_per_tok": "n_experts_per_token",
        "n_shared_experts": "n_shared_experts",
        "first_k_dense_replace": "first_dense_layers",
        "routed_scaling_factor": "routed_scaling_factor",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"},
}

#: family -> {parameter: why it is refused} for every parameter whose
#: mere presence with a non-default value the family cannot serve
#: (``models/deepseek.py:check_serving`` refuses the same at run time).
FAMILY_REFUSES: dict[str, dict[str, str]] = {
    "llama": {
        "model": "a family is built from widths, not from a preset"},
    "deepseek_v3": {
        "quantize": "the family serves bfloat16 weights and a bfloat16 "
                    "latent cache (no int8)",
        "speculative": "no draft or chunk-verify body over a latent "
                       "cache",
        "spec_tokens": "speculation is not served",
        "spec_window": "speculation is not served",
        "prefix_cache": "re-written shared pages are not bit-equal "
                        "under grouped expert matmuls",
        "model": "a family is built from widths, not from a preset",
    },
}

_FLOAT_FIELDS = ("rope_theta", "norm_eps", "routed_scaling_factor")


def config_fields(family: str, widths: dict) -> dict:
    """``widths`` (published keys) as the family's config dataclass
    fields, each an int or a float as the field is; a key the family
    lacks is an error."""
    known = FAMILY_WIDTHS[family]
    unknown = sorted(set(widths) - set(known))
    if unknown:
        raise ValueError(
            f"widths: the {family} family has no {unknown} "
            f"(has: {sorted(known)})")
    return {known[key]: float(value) if known[key] in _FLOAT_FIELDS
            else int(value) for key, value in widths.items()}


_OFF = ("", "off", "false", "0", "no", "none", "auto")


def _is_default(name: str, value) -> bool:
    if name in ("spec_tokens", "spec_window", "model"):
        return False
    return str(value).strip().lower() in _OFF


def family_spec_error(parameters: dict) -> str | None:
    """What is wrong with an LLM element's ``family`` / ``widths`` pair
    and the parameters beside it, or None: an unknown family, widths
    without a family (or not a mapping of numbers), a width the family
    lacks, a parameter the family refuses -- and ``decode_block``,
    which no family serves any more (an unknown parameter is ignored,
    and this one would then decode by the per-token tick)."""
    if "decode_block" in parameters:
        return f"decode_block={parameters['decode_block']!r}: the " \
               f"fused-block driver is gone; set decode_block_tokens " \
               f"(the device loop)"
    family = parameters.get("family")
    widths = parameters.get("widths")
    if family is None:
        if widths is not None:
            return "widths: needs a family " \
                   f"({'|'.join(sorted(FAMILY_WIDTHS))})"
        return None
    family = str(family).strip().lower()
    if family not in FAMILY_WIDTHS:
        return f"family={parameters['family']!r}: one of " \
               f"{'|'.join(sorted(FAMILY_WIDTHS))}"
    if widths is not None:
        if not isinstance(widths, dict):
            return f"widths={widths!r}: a mapping of published " \
                   f"config.json keys to numbers"
        known = FAMILY_WIDTHS[family]
        unknown = sorted(set(widths) - set(known))
        if unknown:
            return f"widths: the {family} family has no {unknown} " \
                   f"(has: {sorted(known)})"
        bad = sorted(key for key, value in widths.items()
                     if isinstance(value, bool)
                     or not isinstance(value, (int, float)))
        if bad:
            return f"widths: {bad} must be numbers"
    for name, why in FAMILY_REFUSES[family].items():
        if name in parameters and not _is_default(name, parameters[name]):
            return f"{name}={parameters[name]!r}: not with family " \
                   f"{family} ({why})"
    return None
