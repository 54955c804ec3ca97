"""The table of peaks and the functions that count what a kernel or a
step must do, from shapes alone.  They live with the benchmark so that
no PR that claims a gain can change the yardstick."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error, never
    a default."""
    with open(os.path.join(_HERE, "peaks.json")) as stream:
        table = json.load(stream)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in benchmark/peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


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


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of bytes over peak bandwidth and
    operations over peak rate (bf16: weight-only int8 multiplies in
    bf16), and which of the two binds."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["operations"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops \
        else (by_ops, "compute")
