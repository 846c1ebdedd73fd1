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

Hot-path cost: two C++ ``_cache_size()`` reads, one
``perf_counter`` pair and one thread-local store per call — no host
sync, no device work, no lock. The capture itself (the only expensive
part) runs exactly once per compile, which already cost orders of
magnitude more — and how much the capture adds to that is itself a
span, ``compile/cost_capture``.

Compile spans (docs/OBSERVABILITY.md "Tracing"): JAX reports each
stage of a compilation through ``jax.monitoring`` — jaxpr trace,
lowering to MLIR, backend compile (which holds the persistent cache's
lookup), cache retrieval, cache hit / miss. :func:`install_compile_
listeners` registers one listener pair per process; a duration event
that fires while a :class:`CostTracked` call is on the stack is put
down to that entry, and when the call turns out to have compiled, the
stages are recorded as job-level spans ``compile/<entry>`` ⊃
``compile/trace``, ``compile/lower``, ``compile/backend`` (attrs
``cache: hit|miss``, ``retrieval_s``), ``compile/cost_capture``.
Hits and misses of the persistent cache also feed the registry
counters ``compile_cache_hits`` / ``compile_cache_misses``, whoever
compiled.

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
           "install_compile_listeners", "DEVICE_PEAKS"]

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


# -- compile stages, from jax.monitoring -------------------------------

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: a stage shorter than this is summed into its ``compile/<entry>``
#: root's ``small_stages_s`` instead of being a span of its own
_STAGE_MIN_S = 1e-3

# .stages: the list the CostTracked call this thread is inside
# collects its compile stages into; None outside any (and inside the
# cost capture's own re-lowering, whose price is its own span)
_tls = threading.local()
_listeners_lock = threading.Lock()
_listeners_installed = False


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    stages = getattr(_tls, "stages", None)
    if stages is None:
        return
    stage = _STAGE_OF_EVENT.get(event)
    if stage is not None:
        # the event fires as the stage ends: its true interval
        end = time.perf_counter()
        stages.append((stage, end - float(duration_secs), end))
    elif event == _RETRIEVAL_EVENT:
        stages.append(("retrieval", float(duration_secs), 0.0))


def _on_event(event: str, **_kw) -> None:
    # the counter names stay literals: the contract lint (TPL016) reads
    # them off the call sites
    if event == _HIT_EVENT:
        _global_registry.counter("compile_cache_hits").inc()
        mark = "hit"
    elif event == _MISS_EVENT:
        _global_registry.counter("compile_cache_misses").inc()
        mark = "miss"
    else:
        return
    stages = getattr(_tls, "stages", None)
    if stages is not None:
        stages.append((mark, 0.0, 0.0))


def install_compile_listeners() -> None:
    """Register the two ``jax.monitoring`` listeners, once a process
    (``register_jit`` calls this: jax is imported by then; this module
    never imports it on its own)."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        _listeners_installed = True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        # a JAX without the hooks: no compile spans, nothing breaks
        pass


def _outermost(intervals: List[Tuple[float, float]]) \
        -> List[Tuple[float, float]]:
    """Drop intervals that lie inside another: a jit traced inside a
    jit reports its own trace duration, which the outer one holds."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if out and b <= out[-1][1] + 1e-9:
            continue
        out.append((a, b))
    return out


def _record_compile_spans(name: str, t0: float, t1: float,
                          stages: List[Tuple[str, float, float]],
                          capture: Optional[Tuple[float, float]]) -> None:
    """One compile of entry ``name`` as real spans: ``compile/<name>``
    over the whole first call [t0, t1] (trace, lower, backend compile
    or cache retrieval, first dispatch), its stages as children at the
    intervals JAX reported, and the cost capture after it."""
    from . import trace as _trace
    trace_id, parent_id = _trace.open_span_context()
    hit = any(s[0] == "hit" for s in stages)
    miss = any(s[0] == "miss" for s in stages)
    retrieval = sum(s[1] for s in stages if s[0] == "retrieval")
    children = []
    small_s = 0.0
    for stage in ("compile/trace", "compile/lower", "compile/backend"):
        for a, b in _outermost([(a, b) for s, a, b in stages
                                if s == stage]):
            if b - a < _STAGE_MIN_S:
                # the helper jits a lowering traces on its way: summed
                # on the root, not a span each
                small_s += b - a
                continue
            attrs: Dict[str, Any] = {"entry": name}
            if stage == "compile/backend":
                # neither event: the persistent cache is off, or the
                # compile was too quick or small to be written
                attrs["cache"] = "hit" if hit and not miss \
                    else "miss" if miss else "uncached"
                attrs["retrieval_s"] = round(retrieval, 6)
            children.append((stage, a, b, attrs))
    end = max([t1] + ([capture[1]] if capture else []))
    root = _trace.record_span(
        f"compile/{name}", t0, end, trace_id=trace_id,
        parent_id=parent_id,
        attrs={"entry": name, "small_stages_s": round(small_s, 6)})
    for stage, a, b, attrs in children:
        _trace.record_span(stage, a, b, trace_id=trace_id,
                           parent_id=root, attrs=attrs)
    if capture is not None:
        _trace.record_span("compile/cost_capture", capture[0],
                           capture[1], trace_id=trace_id,
                           parent_id=root, attrs={"entry": name})


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


def _avals_of(args: tuple, kwargs: dict):
    """The call's arguments with every array leaf replaced by its
    ``ShapeDtypeStruct`` (a donated, deleted array still knows its
    aval), everything else as passed. The sharding is kept only where
    the array was committed to it: an uncommitted array lowers with its
    placement unspecified, and the re-lowering has to be the module the
    call compiled — the persistent cache's key is made from it."""
    import jax

    def abstract(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return x
        if getattr(x, "_committed", False):
            try:
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=x.sharding)
            except Exception:
                pass
        return jax.ShapeDtypeStruct(shape, dtype)

    return jax.tree_util.tree_map(abstract, (args, kwargs))


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

    __slots__ = ("_fn", "_name", "_avals", "__weakref__")

    def __init__(self, name: str, fn: Callable):
        self._fn = fn
        self._name = name
        self._avals = None

    @property
    def unwrapped(self) -> Callable:
        return self._fn

    @property
    def entry_name(self) -> str:
        return self._name

    @property
    def last_avals(self):
        """``(args, kwargs)`` of the last signature this entry
        compiled, arrays as ``ShapeDtypeStruct``: what ``obs.op_scopes``
        re-lowers at. ``None`` before the first compile."""
        return self._avals

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            before = int(fn._cache_size())
        except Exception:
            return fn(*args, **kwargs)
        outer = getattr(_tls, "stages", None)
        _tls.stages = stages = []
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            _tls.stages = outer
        try:
            grew = int(fn._cache_size()) - before
        except Exception:
            grew = 0
        if grew > 0:
            t1 = time.perf_counter()
            self._avals = _avals_of(args, kwargs)
            # the capture's own re-lowering reports no stages: its
            # price is the compile/cost_capture span
            _tls.stages = None
            try:
                _capture(self._name, fn, args, kwargs,
                         (t1 - t0) * 1e3, grew)
            finally:
                _tls.stages = outer
            try:
                _record_compile_spans(self._name, t0, t1, stages,
                                      (t1, time.perf_counter()))
            except Exception:
                pass        # telemetry never breaks a dispatch
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self) -> str:
        return f"CostTracked({self._name!r}, {self._fn!r})"
