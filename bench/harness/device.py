"""The chip the run holds: found or refused, described, and its peak."""
from __future__ import annotations

from typing import Dict, List


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def require(chips: int) -> List:
    """The first ``chips`` TPU devices; ``NoChip`` on any other platform
    (there is no CPU fallback) or when too few are attached."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found platform {platform!r} "
                     f"({len(devices)} device(s))")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> Dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
