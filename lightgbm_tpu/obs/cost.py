"""XLA cost attribution: in-band roofline numbers for every compile.

docs/ROOFLINE.md justifies each perf decision against hand-curated
flops/bytes numbers from offline traces. This module makes that
accounting always-on: every jitted entry point registered through
:func:`~lightgbm_tpu.obs.jit_tracker.register_jit` is wrapped in a
:class:`CostTracked` proxy that notices each XLA cache miss (a miss IS
a compilation) and captures, once per new call signature:

- ``flops`` / ``bytes_accessed`` from the XLA HLO cost model
  (``fn.lower(...).cost_analysis()`` — the lowering is a re-trace,
  microseconds-to-milliseconds, NOT a second compile; set
  ``LIGHTGBM_TPU_COST_OPTIMIZED=1`` to pay one extra compile per
  signature for post-optimization numbers instead),
- ``wall_ms`` — the first call's host wall time (trace + compile +
  first dispatch),
- the device peaks (:func:`device_peaks`) and the resulting
  cost-model-optimal runtime ``optimal_ms = max(flops/peak_flops,
  bytes/peak_bw)`` — the live roofline denominator.

Each capture emits one ``{"event": "compile"}`` record (drained into
the telemetry JSONL stream by the recorder / serve daemon, summarized
by ``lightgbm_tpu stats``) and feeds the registry families
``xla_compiles{entry=}`` / ``xla_flops{entry=}`` /
``xla_bytes_accessed{entry=}`` / ``xla_compile_ms{entry=}``.

Hot-path cost: two C++ ``_cache_size()`` reads and one
``perf_counter`` pair per call — no host sync, no device work, no
lock. The capture itself (the only expensive part) runs exactly once
per compile, which already cost orders of magnitude more.

Threading contract (tpulint TPL008 over obs/): the pending-event list
is appended from whatever thread dispatched the compile (trainer loop,
serve batcher worker) and drained from recorder/daemon threads — every
touch goes through ``_events_lock``. The jax work (lowering) always
happens OUTSIDE that lock (TPL006).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .registry import registry as _global_registry

__all__ = ["CostTracked", "drain_compile_events",
           "compile_events_snapshot", "device_peaks",
           "roofline_optimal_ms", "cost_wrap_enabled",
           "DEVICE_PEAKS"]

#: dense peak compute (flops/s, bf16 systolic) and HBM bandwidth
#: (bytes/s) per device generation — the denominators of
#: docs/ROOFLINE.md, keyed by substrings of ``device_kind``. Override
#: with LIGHTGBM_TPU_PEAK_TFLOPS / LIGHTGBM_TPU_PEAK_GBPS for parts
#: not tabulated here.
DEVICE_PEAKS: Tuple[Tuple[str, float, float], ...] = (
    ("v5 lite", 197e12, 819e9),   # v5e ("TPU v5 lite")
    ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9),
    ("v6", 918e12, 1640e9),       # Trillium
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 45e12, 700e9),
)

#: pending {"event": "compile"} records awaiting a drain; bounded so a
#: process nobody scrapes (a bare serve replica without telemetry)
#: never grows without limit
_EVENTS_CAP = 1024
_events_lock = threading.Lock()
_events: List[Dict[str, Any]] = []


def cost_wrap_enabled() -> bool:
    """LIGHTGBM_TPU_COST_ATTRIBUTION=0 is the kill switch: entry
    points register un-wrapped (recompile counting still works; no
    per-call bookkeeping, no compile events)."""
    return os.environ.get("LIGHTGBM_TPU_COST_ATTRIBUTION",
                          "1") not in ("0", "off", "false")


# -- device peaks ------------------------------------------------------

# resolved once per process; (kind, peak_flops, peak_bytes_per_sec),
# entries None when unknown. Guarded by _peaks_lock.
_peaks_lock = threading.Lock()
_peaks: Optional[Tuple[Optional[str], Optional[float],
                       Optional[float]]] = None


def _resolve_peaks() -> Tuple[Optional[str], Optional[float],
                              Optional[float]]:
    kind: Optional[str] = None
    try:
        import jax
        kind = str(jax.devices()[0].device_kind)
    except Exception:
        pass
    flops = bw = None
    if kind:
        low = kind.lower()
        for sub, f, b in DEVICE_PEAKS:
            if sub in low:
                flops, bw = f, b
                break
    env_f = os.environ.get("LIGHTGBM_TPU_PEAK_TFLOPS")
    env_b = os.environ.get("LIGHTGBM_TPU_PEAK_GBPS")
    try:
        if env_f:
            flops = float(env_f) * 1e12
        if env_b:
            bw = float(env_b) * 1e9
    except ValueError:
        pass
    return kind, flops, bw


def device_peaks() -> Tuple[Optional[str], Optional[float],
                            Optional[float]]:
    """(device_kind, peak_flops_per_sec, peak_bytes_per_sec) of the
    first local device; Nones where unknown (CPU has no tabulated
    peaks — the roofline column renders n/a there)."""
    global _peaks
    with _peaks_lock:
        if _peaks is not None:
            return _peaks
    resolved = _resolve_peaks()        # may import jax: outside lock
    with _peaks_lock:
        if _peaks is None:
            _peaks = resolved
        return _peaks


def roofline_optimal_ms(flops: Optional[float],
                        bytes_accessed: Optional[float],
                        peak_flops: Optional[float],
                        peak_bytes_per_sec: Optional[float]) \
        -> Optional[float]:
    """Cost-model-optimal runtime in ms at the device peaks: the
    roofline max of the compute time and the memory time. None when
    either side of the division is unknown."""
    candidates = []
    if flops is not None and peak_flops:
        candidates.append(flops / peak_flops)
    if bytes_accessed is not None and peak_bytes_per_sec:
        candidates.append(bytes_accessed / peak_bytes_per_sec)
    if not candidates:
        return None
    return max(candidates) * 1e3


# -- signature description --------------------------------------------

def _describe_leaf(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    if isinstance(x, (bool, int, float, str)) or x is None:
        return repr(x)[:32]
    return type(x).__name__


def _describe_args(args: tuple, kwargs: dict) -> str:
    """Short human signature of a call: avals of the array leaves plus
    static scalars, capped — diagnostic text, never parsed."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    parts = [_describe_leaf(leaf) for leaf in leaves[:24]]
    if len(leaves) > 24:
        parts.append(f"+{len(leaves) - 24} more")
    return ",".join(parts)


# -- the capture -------------------------------------------------------

def _cost_analysis(fn: Callable, args: tuple, kwargs: dict) \
        -> Tuple[Optional[float], Optional[float]]:
    """(flops, bytes_accessed) from the XLA HLO cost model for this
    call signature. Default: ``lower().cost_analysis()`` — a re-trace,
    not a compile. LIGHTGBM_TPU_COST_OPTIMIZED=1 compiles the lowered
    program once more for post-optimization numbers (expensive:
    doubles compile time; measurement sessions only)."""
    lowered = fn.lower(*args, **kwargs)
    if os.environ.get("LIGHTGBM_TPU_COST_OPTIMIZED", "") \
            not in ("", "0"):
        ca = lowered.compile().cost_analysis()
    else:
        ca = lowered.cost_analysis()
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed")
    return (None if flops is None else float(flops),
            None if bytes_accessed is None else float(bytes_accessed))


def _capture(name: str, fn: Callable, args: tuple, kwargs: dict,
             wall_ms: float, compiles: int) -> None:
    """Build and enqueue one compile record. Runs once per cache miss,
    right after the compile that already cost seconds; every jax call
    here stays outside the events lock (TPL006)."""
    flops = bytes_accessed = None
    try:
        flops, bytes_accessed = _cost_analysis(fn, args, kwargs)
    except Exception:
        # donated buffers, lowering quirks: the event still records
        # the compile itself, just without the cost model numbers
        pass
    kind, peak_flops, peak_bw = device_peaks()
    event = {
        "event": "compile",
        "entry": name,
        "signature": _describe_args(args, kwargs),
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "wall_ms": round(wall_ms, 3),
        "compiles": int(compiles),
        "device_kind": kind,
        "peak_flops": peak_flops,
        "peak_bytes_per_sec": peak_bw,
        "optimal_ms": roofline_optimal_ms(flops, bytes_accessed,
                                          peak_flops, peak_bw),
        "time": time.time(),
    }
    with _events_lock:
        _events.append(event)
        if len(_events) > _EVENTS_CAP:
            del _events[:len(_events) - _EVENTS_CAP]
    reg = _global_registry
    reg.counter("xla_compiles", entry=name).inc(compiles)
    reg.histogram("xla_compile_ms", entry=name).observe(wall_ms)
    if flops is not None:
        reg.gauge("xla_flops", entry=name).set(flops)
    if bytes_accessed is not None:
        reg.gauge("xla_bytes_accessed", entry=name).set(bytes_accessed)


def drain_compile_events() -> List[Dict[str, Any]]:
    """Locked snapshot-and-clear of the pending compile records (the
    ``faults.drain_events`` contract: a concurrent append can never be
    lost between a copy and a clear)."""
    global _events
    with _events_lock:
        drained, _events = _events, []
    return drained


def compile_events_snapshot() -> List[Dict[str, Any]]:
    """Non-destructive copy of the pending records (tests, bench)."""
    with _events_lock:
        return list(_events)


class CostTracked:
    """Call-through proxy over one jitted entry point.

    ``__call__`` detects XLA cache misses by diffing the function's
    compile-cache size around the call — the same signal the
    recompile watcher polls — and runs the cost capture once per
    miss. Everything else (``_cache_size``, ``lower``, AOT attrs)
    proxies to the wrapped function, so the jit tracker and existing
    callers never branch on whether an entry point is wrapped.
    """

    __slots__ = ("_fn", "_name", "__weakref__")

    def __init__(self, name: str, fn: Callable):
        self._fn = fn
        self._name = name

    @property
    def unwrapped(self) -> Callable:
        return self._fn

    @property
    def entry_name(self) -> str:
        return self._name

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            before = int(fn._cache_size())
        except Exception:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        try:
            grew = int(fn._cache_size()) - before
        except Exception:
            grew = 0
        if grew > 0:
            _capture(self._name, fn, args, kwargs,
                     (time.perf_counter() - t0) * 1e3, grew)
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self) -> str:
        return f"CostTracked({self._name!r}, {self._fn!r})"
