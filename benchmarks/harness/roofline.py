"""The table of peaks and the least time a device can take."""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``.  A device that is
    not in ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"{_PEAKS}: add the device with its source, do not guess")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, which bound): the larger of operations over peak FLOP/s
    and bytes over peak bytes/s."""
    by_compute = flops / peak["bf16_flops_per_s"]
    by_memory = nbytes / peak["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "memory"))


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int,
                peak: dict) -> float:
    return 100.0 * flops_per_token * tokens_per_s / (
        chips * peak["bf16_flops_per_s"])
