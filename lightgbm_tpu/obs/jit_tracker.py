"""Recompile tracking for jitted hot-path entry points.

A silent XLA recompile is the single most expensive event this codebase
can hit mid-training (the Higgs-width fused step takes ~154 s to
compile cold on a v5e — chip_smoke.py, PR 21), and it never announces
itself. Every jitted boosting-path entry point registers
here (``register_jit``); the per-function compile-cache size
(``PjitFunction._cache_size``) is then a direct compile counter — a
cache miss IS a compilation — and :class:`RecompileWatcher` turns the
sizes into per-interval deltas for the JSONL event stream.

Registration keys on ``(name, seq)`` with a monotonic sequence number:
rebuilding an entry point (the fused step is re-jitted after
``reset_parameter``; cv builds one per fold) registers a NEW key whose
whole cache size counts as fresh compiles, so replacement never hides
work behind a shrinking counter — and a recycled object address
(``id()`` reuse after GC) can never alias a new function onto a dead
entry. Entries hold their callables by WEAKREF and retire once the
callable is collected (the OOM ladder's jit rebuilds and the engine's
``_scan_fns`` resets would otherwise leave dead functions' last cache
sizes in ``jit_cache_sizes()``/``total_recompiles()`` forever —
tests/test_metrics_export.py pins the rebuild-then-count behavior).

Since the fleet-metrics PR, ``register_jit`` additionally wraps each
entry point in :class:`~lightgbm_tpu.obs.cost.CostTracked` (XLA cost
attribution: one ``{"event": "compile"}`` record with flops/bytes per
first compile per signature; LIGHTGBM_TPU_COST_ATTRIBUTION=0
disables). Definition sites therefore REBIND the registered name —
``fn = register_jit("x", fn)`` — so calls route through the wrapper;
the wrapper proxies ``_cache_size`` and the AOT surface, so this
module's polling is unchanged.
"""

from __future__ import annotations

import threading
import weakref
from typing import Callable, Dict, Tuple

__all__ = ["register_jit", "jit_cache_sizes", "total_recompiles",
           "jit_declarations", "live_entries", "RecompileWatcher"]

_lock = threading.Lock()
# (name, seq) -> weakref to the jitted callable; weak so per-booster
# fused functions don't outlive their engine
_tracked: Dict[Tuple[str, int], "weakref.ref"] = {}
_seq = 0
# name -> declared recompile surface: the number of distinct call
# signatures the entry point is ALLOWED to compile over a process
# lifetime (the pow2 serve buckets, the per-(W, bag_live) scan
# variants, ...). ``lint --ir`` (analysis/ircheck.py, TPL014) demands a
# declaration at every register_jit site and the telemetry consistency
# test cross-checks jit_cache_sizes() against it — an entry whose
# cache outgrows its declaration is a recompile storm by definition.
_declared: Dict[str, int] = {}


def register_jit(name: str, fn: Callable,
                 max_signatures: int = None) -> Callable:
    """Track a jitted callable's compile cache and wrap it for XLA
    cost attribution; returns the (wrapped) callable, so definition
    sites rebind: ``fn = register_jit("name", fn)``. Non-jitted
    callables (no ``_cache_size``) are accepted and returned
    unchanged — callers never need to branch. Re-registering the same
    live object (or its already-registered wrapper) under the same
    name returns the existing wrapper, never a duplicate entry.

    ``max_signatures`` declares the entry point's recompile surface:
    the maximum number of distinct trace signatures the function is
    expected to compile. The declaration is advisory at runtime (no
    enforcement here — a hot path must never raise over telemetry) but
    is enforced statically by ``lint --ir`` (TPL014) and dynamically by
    the telemetry consistency test."""
    global _seq
    if max_signatures is not None:
        with _lock:
            prev = _declared.get(name)
            _declared[name] = max(prev, max_signatures) \
                if prev is not None else max_signatures
    if not hasattr(fn, "_cache_size"):
        return fn
    from .cost import (CostTracked, cost_wrap_enabled,
                       install_compile_listeners)
    # compile-stage spans and the persistent cache's hit / miss
    # counters (obs/cost.py): one listener pair a process
    install_compile_listeners()
    with _lock:
        for (tracked_name, _), r in _tracked.items():
            if tracked_name != name:
                continue
            live = r()
            if live is fn or getattr(live, "unwrapped", None) is fn:
                return live
    if cost_wrap_enabled() and not isinstance(fn, CostTracked):
        fn = CostTracked(name, fn)
    try:
        ref = weakref.ref(fn)
    except TypeError:  # not weakref-able; keep a strong closure
        ref = (lambda f: (lambda: f))(fn)
    with _lock:
        _seq += 1
        _tracked[(name, _seq)] = ref
    return fn


def jit_cache_sizes() -> Dict[Tuple[str, int], int]:
    """Current compile-cache size per live tracked function."""
    out: Dict[Tuple[str, int], int] = {}
    dead = []
    with _lock:
        items = list(_tracked.items())
    for key, ref in items:
        fn = ref()
        if fn is None:
            dead.append(key)
            continue
        try:
            out[key] = int(fn._cache_size())
        except Exception:
            out[key] = 0
    if dead:
        with _lock:
            for key in dead:
                _tracked.pop(key, None)
    return out


def live_entries(name: str) -> list:
    """The live callables registered under ``name``, oldest first (a
    rebuilt fused step registers anew; ``obs.op_scopes`` reads the
    newest that has run)."""
    with _lock:
        items = sorted((seq, ref) for (n, seq), ref in _tracked.items()
                       if n == name)
    return [fn for fn in (ref() for _, ref in items) if fn is not None]


def total_recompiles() -> int:
    """Total compilations across all live tracked entry points."""
    return sum(jit_cache_sizes().values())


def jit_declarations() -> Dict[str, int]:
    """Declared recompile surface per entry name (``max_signatures``
    passed to :func:`register_jit`). Re-registrations keep the largest
    declaration seen (cv folds / rebuilt fused steps re-declare)."""
    with _lock:
        return dict(_declared)


class RecompileWatcher:
    """Delta view over the tracked cache sizes.

    ``delta()`` returns compilations since the previous ``delta()`` (or
    construction): new keys contribute their full size, grown keys the
    growth. A function garbage-collected between calls simply drops out;
    its past compiles were already reported.
    """

    def __init__(self):
        self._last = jit_cache_sizes()
        self.total = 0

    def delta(self) -> int:
        now = jit_cache_sizes()
        d = 0
        for key, size in now.items():
            d += max(0, size - self._last.get(key, 0))
        self._last = now
        self.total += d
        return d
