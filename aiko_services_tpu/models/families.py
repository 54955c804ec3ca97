"""Model families the LLM element serves, as data: which published
``config.json`` keys (``widths``) each takes and what each refuses.
Imports nothing of jax, so the create-time parameter check
(``analysis/params.py``) and the runtime (``elements/llm.py``,
``models/llama.py``, ``models/deepseek.py``, ``models/olmo_hybrid.py``,
``models/sdar.py``, ``models/command_a.py``, ``models/falcon_h1.py``)
read ONE table.

An LLM element names a family and hands it widths::

    "parameters": {"family": "deepseek_v3",
                   "widths": {"hidden_size": 2048, "kv_lora_rank": 512,
                              ...},
                   "max_seq": 8192, ...}

A key the family lacks is an error; a key left out keeps the family's
default (``LlamaConfig`` / ``DeepseekConfig`` / ``OlmoHybridConfig`` /
``SdarConfig`` / ``CommandAConfig`` / ``FalconH1Config``).
Widths are numbers, but for the keys of ``LIST_WIDTHS`` (a list of
names from the family's set: the ``olmo_hybrid`` and ``cohere2_moe``
families' per-layer ``layer_types``), ``NUMBER_LIST_WIDTHS`` (a list of
so many numbers: the ``falcon_h1`` family's ``mlp_multipliers`` and
``ssm_multipliers``) and ``BOOL_WIDTHS`` (a boolean).
A family may also take parameters of its own beside its widths
(``FAMILY_PARAMETERS``: the ``sdar_moe`` family's ``block_length`` and
``denoising_steps``; the ``cohere2_moe`` family's ``expert_shards``,
the chips a layer's experts are divided over, of which this one holds
the first share).
"""

from __future__ import annotations

__all__ = ["FAMILY_WIDTHS", "FAMILY_REFUSES", "FAMILY_PARAMETERS",
           "config_fields", "family_spec_error"]

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
    "olmo_hybrid": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads",
        "intermediate_size": "hidden_dim",
        "layer_types": "layer_types",
        "linear_num_key_heads": "linear_key_heads",
        "linear_num_value_heads": "linear_value_heads",
        "linear_key_head_dim": "linear_key_dim",
        "linear_value_head_dim": "linear_value_dim",
        "linear_conv_kernel_dim": "linear_conv_kernel",
        "linear_allow_neg_eigval": "linear_allow_neg_eigval",
        "rms_norm_eps": "norm_eps"},
    "sdar_moe": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
        "moe_intermediate_size": "moe_hidden_dim",
        "num_experts": "n_experts",
        "num_experts_per_tok": "n_experts_per_token",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"},
    "cohere2_moe": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
        "intermediate_size": "moe_hidden_dim",
        "num_experts": "n_experts",
        "num_experts_per_tok": "n_experts_per_token",
        "num_shared_experts": "n_shared_experts",
        "sliding_window": "window", "layer_types": "layer_types",
        "rope_theta": "rope_theta", "layer_norm_eps": "norm_eps",
        "logit_scale": "logit_scale"},
    "falcon_h1": {
        "vocab_size": "vocab_size", "hidden_size": "dim",
        "num_hidden_layers": "n_layers",
        "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
        "intermediate_size": "hidden_dim",
        "mamba_d_ssm": "ssm_dim", "mamba_n_heads": "ssm_heads",
        "mamba_d_head": "ssm_head_dim", "mamba_d_state": "ssm_state",
        "mamba_n_groups": "ssm_groups", "mamba_d_conv": "conv_kernel",
        "mamba_chunk_size": "ssm_chunk",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
        "embedding_multiplier": "embedding_multiplier",
        "lm_head_multiplier": "lm_head_multiplier",
        "key_multiplier": "key_multiplier",
        "attention_in_multiplier": "attention_in_multiplier",
        "attention_out_multiplier": "attention_out_multiplier",
        "ssm_in_multiplier": "ssm_in_multiplier",
        "ssm_out_multiplier": "ssm_out_multiplier",
        "mlp_multipliers": "mlp_multipliers",
        "ssm_multipliers": "ssm_multipliers"},
}

#: family -> {element parameter: config dataclass field} for what a
#: family takes beside its widths (whole numbers; with another family
#: the parameter is refused)
FAMILY_PARAMETERS: dict[str, dict[str, str]] = {
    "sdar_moe": {"block_length": "block_length",
                 "denoising_steps": "denoising_steps"},
    "cohere2_moe": {"expert_shards": "expert_shards"},
}

#: family -> {width key: the names its list may hold} (every other
#: width is a number or, for ``BOOL_WIDTHS``, a boolean)
LIST_WIDTHS: dict[str, dict[str, tuple]] = {
    "olmo_hybrid": {"layer_types": ("linear_attention", "full_attention")},
    "cohere2_moe": {"layer_types": ("sliding_attention",
                                    "full_attention")}}
BOOL_WIDTHS = ("linear_allow_neg_eigval",)
#: family -> {width key: how many numbers its list holds} (the
#: ``falcon_h1`` family's muP multipliers: the MLP's gate and down
#: projections, the mixer's five input segments)
NUMBER_LIST_WIDTHS: dict[str, dict[str, int]] = {
    "falcon_h1": {"mlp_multipliers": 2, "ssm_multipliers": 5}}

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
    "olmo_hybrid": {
        "quantize": "the family serves bfloat16 weights, bfloat16 K/V "
                    "pages and a float32 recurrent state (no int8)",
        "speculative": "a rejected draft would have to roll the "
                       "recurrent state back; no snapshot of it is kept",
        "spec_tokens": "speculation is not served",
        "spec_window": "speculation is not served",
        "prefix_cache": "a shared prefix is a snapshot of the recurrent "
                        "state, not pages; none is kept",
        "model": "a family is built from widths, not from a preset",
    },
    "sdar_moe": {
        "quantize": "the family serves bfloat16 weights and bfloat16 "
                    "K/V pages (no int8)",
        "speculative": "several tokens a pass are decided by diffusion "
                       "over a block; there is no draft beside it",
        "spec_tokens": "speculation is not served",
        "spec_window": "speculation is not served",
        "prefix_cache": "a shared prefix would have to end at a block "
                        "boundary, and re-written shared pages are not "
                        "bit-equal under grouped expert matmuls",
        "model": "a family is built from widths, not from a preset",
    },
    "cohere2_moe": {
        "quantize": "the family serves bfloat16 weights and bfloat16 "
                    "K/V pages (no int8)",
        "speculative": "a draft's rejected tokens would have to give "
                       "back window pages already released; none is "
                       "kept",
        "spec_tokens": "speculation is not served",
        "spec_window": "speculation is not served",
        "prefix_cache": "window layers hold no prefix once their pages "
                        "are released, and re-written shared pages are "
                        "not bit-equal under grouped expert matmuls",
        "model": "a family is built from widths, not from a preset",
    },
    "falcon_h1": {
        "quantize": "the family serves bfloat16 weights, bfloat16 K/V "
                    "pages and a float32 recurrent state (no int8)",
        "speculative": "a rejected draft would have to roll the "
                       "recurrent state back; no snapshot of it is kept",
        "spec_tokens": "speculation is not served",
        "spec_window": "speculation is not served",
        "prefix_cache": "a shared prefix is a snapshot of the recurrent "
                        "state as well as pages; none is kept",
        "model": "a family is built from widths, not from a preset",
    },
}

_FLOAT_FIELDS = ("rope_theta", "norm_eps", "routed_scaling_factor",
                 "logit_scale", "embedding_multiplier",
                 "lm_head_multiplier", "key_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "ssm_in_multiplier", "ssm_out_multiplier")


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
    return {known[key]: _field_value(family, key, known[key], value)
            for key, value in widths.items()}


def _field_value(family: str, key: str, field: str, value):
    if key in LIST_WIDTHS.get(family, {}):
        return tuple(str(item) for item in value)
    if key in BOOL_WIDTHS:
        return bool(value)
    if key in NUMBER_LIST_WIDTHS.get(family, {}):
        return tuple(float(item) for item in value)
    return float(value) if field in _FLOAT_FIELDS else int(value)


def _width_error(family: str, key: str, value) -> str | None:
    """What is wrong with one width's value, or None."""
    if key in LIST_WIDTHS.get(family, {}):
        names = LIST_WIDTHS[family][key]
        if not isinstance(value, (list, tuple)) or not value \
                or any(item not in names for item in value):
            return f"widths: {key} must be a list of {'|'.join(names)}"
        return None
    if key in BOOL_WIDTHS:
        return None if isinstance(value, bool) \
            else f"widths: {key} must be true or false"
    length = NUMBER_LIST_WIDTHS.get(family, {}).get(key)
    if length is not None:
        if not isinstance(value, (list, tuple)) or len(value) != length \
                or any(isinstance(item, bool)
                       or not isinstance(item, (int, float))
                       for item in value):
            return f"widths: {key} must be a list of {length} numbers"
        return None
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return f"widths: ['{key}'] must be numbers"
    return None


_OFF = ("", "off", "false", "0", "no", "none", "auto")


def _is_default(name: str, value) -> bool:
    if name in ("spec_tokens", "spec_window", "model"):
        return False
    return str(value).strip().lower() in _OFF


def family_spec_error(parameters: dict) -> str | None:
    """What is wrong with an LLM element's ``family`` / ``widths`` pair
    and the parameters beside it, or None: an unknown family, widths
    without a family (or not a mapping of numbers -- a list of layer
    names, a list of so many numbers or a boolean where ``LIST_WIDTHS``
    / ``NUMBER_LIST_WIDTHS`` / ``BOOL_WIDTHS`` say so), a width the
    family lacks, a parameter the family refuses -- and ``decode_block``,
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
        for key in sorted(widths):
            problem = _width_error(family, key, widths[key])
            if problem is not None:
                return problem
    for name, why in FAMILY_REFUSES[family].items():
        if name in parameters and not _is_default(name, parameters[name]):
            return f"{name}={parameters[name]!r}: not with family " \
                   f"{family} ({why})"
    for other, names in FAMILY_PARAMETERS.items():
        for name in names:
            if name in parameters and other != family:
                return f"{name}={parameters[name]!r}: not with family " \
                       f"{family} (a parameter of the {other} family)"
    if family == "sdar_moe":
        return _block_diffusion_error(parameters)
    return None


def _whole(parameters: dict, name: str, default: int):
    """A whole-number parameter, its default where left out, None where
    it is no whole number."""
    value = parameters.get(name, default)
    if isinstance(value, bool):
        return None
    try:
        number = float(value)
    except (TypeError, ValueError):
        return None
    return int(number) if number == int(number) else None


def _block_diffusion_error(parameters: dict) -> str | None:
    """What is wrong with the ``sdar_moe`` family's own parameters and
    the sizes they must divide, or None: ``block_length`` >= 1 dividing
    the page (``kv_page_tokens``, which must be set: K/V is committed
    into pages), the admission chunk (512) and the emitted-token ring
    (``decode_block_tokens``, which must be set: generation is by the
    device loop), ``denoising_steps`` in 1..``block_length``."""
    block = _whole(parameters, "block_length", 4)
    if block is None or block < 1:
        return f"block_length={parameters.get('block_length')!r}: a " \
               f"whole number >= 1"
    steps = _whole(parameters, "denoising_steps", block)
    if steps is None or not 1 <= steps <= block:
        return f"denoising_steps={parameters.get('denoising_steps')!r}: " \
               f"a whole number in 1..block_length ({block})"
    page = _whole(parameters, "kv_page_tokens", 0)
    if not page:
        return "kv_page_tokens=0: the sdar_moe family commits K/V a " \
               "block at a time into pages only; set kv_page_tokens > 0"
    if page % block or 512 % block:
        return f"block_length={block}: must divide the page " \
               f"(kv_page_tokens={page}) and the admission chunk (512)"
    ring = _whole(parameters, "decode_block_tokens", 0)
    if not ring or ring % block:
        return f"decode_block_tokens=" \
               f"{parameters.get('decode_block_tokens', 0)!r}: the " \
               f"sdar_moe family generates by the device loop alone; a " \
               f"multiple of block_length ({block})"
    return None
