"""The chip gate and what the result line says about the device."""

from __future__ import annotations


class NoChip(Exception):
    """JAX found no TPU, or another number of chips than the cell asks."""


def require_tpu(chips: int) -> dict:
    """{"platform", "kind", "count"} as JAX reports it; raises NoChip unless
    that is `chips` TPU devices. A CPU number is never printed under a device
    metric's name, so there is no fallback."""
    import jax

    devices = jax.devices()
    doc = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if doc["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {doc['platform']!r} "
                     f"({doc['kind']}); the benchmark measures nothing else")
    if doc["count"] != chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX reports "
                     f"{doc['count']}")
    return doc


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    such statistic, as the CPU's)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
