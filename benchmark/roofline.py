"""The table of peaks and the roofline itself.  What a step must do
(its bytes and operations, from shapes alone) is counted by the
configuration's architecture (``benchmark/architectures/<name>.py``:
``decode_step``).  Both live with the benchmark so that no PR that
claims a gain can change the yardstick."""

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


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of bytes over peak bandwidth and
    operations over peak rate (bf16: weight-only int8 multiplies in
    bf16), and which of the two binds."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["operations"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops \
        else (by_ops, "compute")
