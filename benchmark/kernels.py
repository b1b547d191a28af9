"""What the match kernel has to do, from its shapes, and the chip's peaks.

The kernel (``cedar_tpu/ops/match.py``) expands a batch of B requests into
a {0,1} literal matrix [B, L] and multiplies it with the rule plane
W [L, R] (int8 inputs, int32 accumulation; bf16 on the other plane), then
reduces the [B, R] scores to verdict words and rule bitsets. Its least
work, whatever the implementation:

  operations  2 * B * L * R       the one contraction
  bytes       L * R * w           the rule plane, read once per batch
              + 2 * B * L * w     the literal matrix, written and read
              + B * R / 8         the per-rule satisfaction bits, written

At the served batch sizes (B of a few rows) the plane's L * R bytes
dominate and the kernel is bound by memory: reading 63 MB at 819 GB/s.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, never a default."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def match_cost(L: int, R: int, B: int, weight_bytes: int = 1) -> dict:
    """Operations and bytes one batch of the match kernel needs."""
    if min(L, R, B) < 1:
        raise ValueError("L, R and B are positive")
    return {
        "ops": 2 * B * L * R,
        "bytes": L * R * weight_bytes + 2 * B * L * weight_bytes + B * R // 8,
    }


def match_least_seconds(L: int, R: int, B: int, peak: dict, weight_bytes: int = 1) -> dict:
    """The least time the chip could take for one batch, and which of the
    two bounds it."""
    cost = match_cost(L, R, B, weight_bytes)
    ops_peak = peak["int8_ops_per_s"] if weight_bytes == 1 else peak["bf16_flops_per_s"]
    t_ops = cost["ops"] / ops_peak
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "seconds": max(t_ops, t_bytes),
        "bound": "memory" if t_bytes >= t_ops else "compute",
        **cost,
    }
