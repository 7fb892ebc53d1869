"""The chip: refuse anything but a TPU with enough chips, look up its
published peaks, and read its memory peak."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises NoChip otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"needs {n} TPU chips, JAX found {len(devs)}")
    return devs[:n]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (peaks.json); an unknown
    kind is an error, never a default."""
    with open(PEAKS) as fh:
        table = json.load(fh)["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS}")
    return table[kind]


def memory_peak(devs) -> int:
    """peak_bytes_in_use of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
