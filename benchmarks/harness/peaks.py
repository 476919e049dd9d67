"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not in the table is an error, never
a default: a roofline share against a guessed peak is not a measurement."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(_TABLE, "r", encoding="utf-8") as handle:
        table = json.load(handle)
    if device_kind.startswith("_") or device_kind not in table:
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no peaks for device kind {device_kind!r} (known: {known})")
    return table[device_kind]


def least_seconds(flops: float, bytes_moved: float, device_kind: str) -> Dict[str, Any]:
    """The least time the chip could take for `flops` and `bytes_moved`: the
    larger of operations over peak FLOP/s and bytes over peak bytes/s, and
    which of the two binds."""
    peaks = peaks_for(device_kind)
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    return {
        "seconds": max(by_compute, by_memory),
        "binds": "compute" if by_compute >= by_memory else "memory",
        "by_compute_s": by_compute,
        "by_memory_s": by_memory,
    }
