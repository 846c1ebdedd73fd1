"""Device HBM gauges via ``device.memory_stats()``.

TPU/GPU runtimes expose allocator stats; the CPU backend returns
``None``. The telemetry schema keeps the keys with explicit nulls in
that case so consumers can rely on their presence.
"""

from __future__ import annotations

from typing import Dict, Optional

__all__ = ["device_memory_stats"]

_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def device_memory_stats(device=None) -> Dict[str, Optional[int]]:
    """HBM usage for ``device`` (default: first local device).

    Always returns the full key set; values are ``None`` when the
    backend has no allocator stats (CPU) or the query fails (a failed
    query must degrade telemetry, never training).
    """
    out: Dict[str, Optional[int]] = {k: None for k in _KEYS}
    try:
        if device is None:
            import jax
            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return out
    if not stats:
        return out
    for k in _KEYS:
        v = stats.get(k)
        if v is not None:
            out[k] = int(v)
    return out
