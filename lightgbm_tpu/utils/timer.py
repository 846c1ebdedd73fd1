"""Phase timing — the USE_TIMETAG subsystem re-imagined for JAX.

The reference compiles a global ``Common::Timer`` + RAII ``FunctionTimer``
into every hot-path phase and logs a sorted per-label wall-time table at
process exit (/root/reference/include/LightGBM/utils/common.h:973-1057,
instrumentation points listed in SURVEY.md §5). Here ``timed(label)`` is
the ONE way the program opens a host span, and an active section does
three things with one clock pair:

- records a REAL span into ``obs/trace.py``'s buffer: true start, true
  end, parent = the enclosing ``timed`` span, one trace id per
  ``lgb.train`` call (docs/OBSERVABILITY.md "Tracing");
- enters a ``jax.profiler.TraceAnnotation`` while a profiler capture is
  live (``trace_to``, the env captures, or a session started outside
  through the Python API), so the span is on the device trace's clock;
- adds to the per-label ``Timer`` table when ``LIGHTGBM_TPU_TIMETAG=1``
  or ``Timer.enable()`` (``Timer.log_summary()`` prints it sorted,
  ``Timer.snapshot()`` returns it; the telemetry recorder diffs
  consecutive snapshots into per-iteration phase times).

These are HOST spans: the device runs asynchronously, so a label's time
reflects device work only if the section itself synchronizes.
``boosting/fused_iter`` is the enqueue of a round's program (~2 ms),
not the round; the round's device time is the device trace's.

Gating. ``timed(label, job=True)`` marks a job-level span (construct's
parts, ``train/job``, ``train/init``: a few dozen a job, none a round):
always recorded, a clock pair and one locked append each. Every other
section is per-round: with no capture, no ``Timer.enable()`` and no
telemetry recorder live, ``timed`` returns a shared null context — one
flag check, no jax import, no clock read.
"""

from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from contextlib import contextmanager, nullcontext
from typing import Dict, Iterator

from .log import log_info

__all__ = ["Timer", "timed", "trace_to", "EnvCapture",
           "parse_xprof_spec"]

# number of live trace_to() captures; touched under Timer._lock
_tracing = 0


class Timer:
    """Process-global label -> accumulated wall seconds."""

    _acc: Dict[str, float] = defaultdict(float)
    _cnt: Dict[str, int] = defaultdict(int)
    _enabled = os.environ.get("LIGHTGBM_TPU_TIMETAG", "") not in ("", "0")
    # callbacks can fire from user threads and the recorder snapshots
    # concurrently with additions
    _lock = threading.Lock()

    @classmethod
    def enable(cls, on: bool = True) -> None:
        cls._enabled = on

    @classmethod
    def enabled(cls) -> bool:
        return cls._enabled

    @classmethod
    def add(cls, label: str, seconds: float) -> None:
        with cls._lock:
            cls._acc[label] += seconds
            cls._cnt[label] += 1

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._acc.clear()
            cls._cnt.clear()

    @classmethod
    def summary(cls) -> Dict[str, float]:
        with cls._lock:
            return dict(cls._acc)

    @classmethod
    def snapshot(cls) -> Dict[str, Dict[str, float]]:
        """Consistent ``{label: {"total": seconds, "count": n}}`` copy."""
        with cls._lock:
            return {label: {"total": sec, "count": cls._cnt[label]}
                    for label, sec in cls._acc.items()}

    @classmethod
    def log_summary(cls) -> None:
        snap = cls.snapshot()
        if not snap:
            return
        grand = sum(v["total"] for v in snap.values()) or 1.0
        log_info("lightgbm_tpu phase timings (host wall):")
        log_info(f"  {'label':32s} {'total s':>10s} {'count':>8s} "
                 f"{'mean ms':>10s} {'%':>6s}")
        for label, v in sorted(snap.items(), key=lambda kv: -kv[1]["total"]):
            sec, cnt = v["total"], int(v["count"])
            mean_ms = sec / cnt * 1e3 if cnt else 0.0
            log_info(f"  {label:32s} {sec:10.3f} {cnt:8d} "
                     f"{mean_ms:10.3f} {100.0 * sec / grand:6.1f}")


# shared no-op context: the disabled cost of a timed() section is one
# flag check + returning this singleton, against the seed's per-call
# jax import + TraceAnnotation + generator frame
_NULL = nullcontext()

# jax resolved once on first active use — not at module import (utils
# load before the backend is configured) and not per call
_jax = None


def _get_jax():
    global _jax
    if _jax is None:
        import jax
        _jax = jax
    return _jax


# obs/trace.py resolved once on first active use (obs imports nothing
# of utils.timer at import time; the reverse import stays lazy so this
# module still loads before the package's other layers)
_trace_mod = None


def _get_trace():
    global _trace_mod
    if _trace_mod is None:
        from ..obs import trace
        _trace_mod = trace
    return _trace_mod


class _Span:
    """One active ``timed`` section (see the module docstring)."""

    __slots__ = ("label", "attrs", "_trace_root", "_ann", "_ids", "_t0")

    def __init__(self, label: str, attrs, trace_root: bool,
                 annotate: bool):
        self.label = label
        self.attrs = attrs
        self._trace_root = trace_root
        self._ann = _get_jax().profiler.TraceAnnotation(label) \
            if annotate else None

    def __enter__(self):
        self._ids = _get_trace().begin_span(self._trace_root)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = _get_trace()
        tr.end_span()
        if Timer._enabled:
            Timer.add(self.label, t1 - self._t0)
        trace_id, span_id, parent_id = self._ids
        tr.record_span(self.label, self._t0, t1, trace_id=trace_id,
                       span_id=span_id, parent_id=parent_id,
                       attrs=self.attrs)
        return False


# resolved lazily: the jax profiler's session slot, so timed() also
# annotates traces started OUTSIDE trace_to() via the Python API
# (jax.profiler.start_trace / jax.profiler.trace). Captures triggered
# against jax.profiler.start_server happen in C++ and are NOT visible
# here — use trace_to() or LIGHTGBM_TPU_TIMETAG=1 for those. False-y
# sentinel until jax is imported; None forever if the private attr
# moved (degrade to library-only detection, never break).
_profile_state = False


def _external_trace_active() -> bool:
    global _profile_state
    if _profile_state is False:
        import sys
        if "jax" not in sys.modules:
            return False
        try:
            from jax._src.profiler import _profile_state as st
            _profile_state = st
        except Exception:
            _profile_state = None
    if _profile_state is None:
        return False
    try:
        return _profile_state.profile_session is not None
    except Exception:
        return False


def timed(label: str, job: bool = False, attrs=None,
          trace_root: bool = False):
    """Open the host span ``label`` (module docstring). Per-round
    sections (the default) are a strict no-op — the shared null
    context — unless host timing, a capture (ours or an externally
    started jax profiler session) or the telemetry recorder is live;
    ``job=True`` sections are always recorded. ``attrs`` (a dict) is
    stamped onto the span; ``trace_root`` marks the span that is a job
    of its own trace (``train/job``)."""
    live = Timer._enabled or _tracing or _external_trace_active()
    if not live and not job:
        return _NULL
    return _Span(label, attrs, trace_root, annotate=live)


@contextmanager
def trace_to(log_dir: str) -> Iterator[None]:
    """Capture a full device trace (jax.profiler.trace wrapper) — view
    with tensorboard's profile plugin, or any xplane.pb reader. While a
    capture is live, ``timed`` sections emit TraceAnnotation spans even
    with host timing off. When it ends, the op -> scope table of the
    programs that ran is written beside it (``op_scopes.json``)."""
    global _tracing
    jax = _get_jax()

    with Timer._lock:
        _tracing += 1
    try:
        with jax.profiler.trace(log_dir):
            yield
    finally:
        with Timer._lock:
            _tracing -= 1
        # the op -> scope table of the programs that ran, beside the
        # trace: `python -m lightgbm_tpu trace <dir> --xplane <log_dir>`
        # reads it (obs/scopes.py)
        try:
            from ..obs.scopes import write_op_scopes
            write_op_scopes(log_dir)
        except Exception as e:
            from .log import log_warning
            log_warning(f"trace_to: no op_scopes.json beside "
                        f"{log_dir!r} ({type(e).__name__}: {e})")


def parse_xprof_spec(spec: str):
    """Parse ``LIGHTGBM_TPU_XPROF=<dir>:iters=A-B`` (or ``:iters=A``
    for a one-iteration window) into ``(log_dir, first, last)``.
    Raises ValueError on a malformed spec — a silently ignored typo
    would cost an on-chip session its capture."""
    if ":iters=" not in spec:
        raise ValueError(
            f"LIGHTGBM_TPU_XPROF expects <dir>:iters=A-B, got "
            f"{spec!r}")
    log_dir, window = spec.rsplit(":iters=", 1)
    lo, _, hi = window.partition("-")
    try:
        first = int(lo)
        last = int(hi) if hi else first
    except ValueError:
        raise ValueError(
            f"LIGHTGBM_TPU_XPROF iteration window {window!r} is not "
            "A-B integers") from None
    if not log_dir or first < 0 or last < first:
        raise ValueError(
            f"LIGHTGBM_TPU_XPROF window {spec!r} needs a directory "
            "and 0 <= A <= B")
    return log_dir, first, last


class EnvCapture:
    """Env-driven device captures for the train loop (engine.py):

    - ``LIGHTGBM_TPU_TRACE_TO=<dir>`` wraps the WHOLE iteration loop
      in one :func:`trace_to` capture — device profiles reachable
      without any API calls,
    - ``LIGHTGBM_TPU_XPROF=<dir>:iters=A-B`` captures only iterations
      A..B (engine-absolute): the programmatic window that makes a
      steady-state fused-scan iteration inspectable without paying a
      whole-run xplane file.

    The engine calls ``before_iteration(i)`` / ``after_iteration(i)``
    per iteration and ``close()`` in its finally; every call is a
    no-op (two integer compares) outside the configured windows, and
    :meth:`from_env` returns None when neither knob is set, so an
    untraced run never even takes the per-iteration calls."""

    def __init__(self, trace_dir=None, xprof=None, _tracer=None):
        self._trace_dir = trace_dir
        self._xprof = xprof                     # (dir, first, last)
        self._tracer = _tracer or trace_to
        self._whole = None
        self._window = None

    @classmethod
    def from_env(cls, env=None):
        env = os.environ if env is None else env
        trace_dir = env.get("LIGHTGBM_TPU_TRACE_TO") or None
        spec = env.get("LIGHTGBM_TPU_XPROF") or None
        xprof = parse_xprof_spec(spec) if spec else None
        if trace_dir is None and xprof is None:
            return None
        return cls(trace_dir=trace_dir, xprof=xprof)

    def _enter(self, log_dir):
        cm = self._tracer(log_dir)
        cm.__enter__()
        return cm

    def before_iteration(self, i: int) -> None:
        if self._trace_dir is not None and self._whole is None:
            self._whole = self._enter(self._trace_dir)
        if self._xprof is not None and self._window is None \
                and i == self._xprof[1]:
            self._window = self._enter(self._xprof[0])

    def after_iteration(self, i: int) -> None:
        if self._window is not None and i >= self._xprof[2]:
            cm, self._window = self._window, None
            self._xprof = None     # one window per run, never re-armed
            cm.__exit__(None, None, None)

    def close(self) -> None:
        """Idempotent; runs on the engine's finally so an exception
        mid-window still finalizes the capture files."""
        for attr in ("_window", "_whole"):
            cm = getattr(self, attr)
            if cm is not None:
                setattr(self, attr, None)
                try:
                    cm.__exit__(None, None, None)
                except Exception:
                    pass
