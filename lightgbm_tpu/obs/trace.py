"""Distributed tracing plane: spans across train -> publish -> serve.

The fleet metrics plane (obs/export.py, obs/registry.py) says how
much; this module says WHERE THE TIME GOES. One span is one named,
timed section of the lifecycle — a boosting iteration, a model
publication, a watcher's validate->load->swap, one served request —
emitted as ``{"event": "span"}`` JSONL lines through the exact same
recorder/daemon drain machinery every other telemetry event rides
(docs/OBSERVABILITY.md "Tracing").

Span model
----------
- ``trace_id`` groups spans into one causal story (a retrain
  generation, a client request); ``span_id`` names the span;
  ``parent_id`` is the causing span (or null for roots).
- Every span carries a PAIRED wall clock (``wall``, ``time.time`` at
  span start) and monotonic clock (``mono``, ``time.perf_counter`` at
  span start) plus ``dur`` seconds. Monotonic clocks have arbitrary
  per-process origins; the wall/mono pair lets the ``trace`` CLI
  estimate each process's offset (median of ``wall - mono`` over its
  spans) and place all processes on ONE corrected timeline — wall
  clocks alone would inherit NTP skew jitter per event, monotonic
  clocks alone cannot be merged at all.
- Context propagates explicitly: the pipeline supervisor seeds a
  generation trace through the ``LIGHTGBM_TPU_TRACE_CTX`` env var
  (``trace_id:span_id``), the publisher stamps its context into the
  manifest (``manifest["trace"]``) so the serve watcher's swap spans
  correlate to the publishing generation, and the serve protocol
  carries an optional ``trace`` field end to end.

- The program's own sections are opened by ``utils.timer.timed``
  (the one span primitive): every active ``timed`` section records a
  REAL span here — true start, true end, parent = the enclosing
  ``timed`` span of the same thread (:func:`begin_span` /
  :func:`end_span` keep that stack), one ``trace_id`` per
  ``lgb.train`` call — and, while a profiler capture is live, enters a
  ``jax.profiler.TraceAnnotation``, which puts the same section on the
  device trace's clock. Job-level spans (``dataset/construct/*``,
  ``train/job``, ``train/init``, ``compile/*``: a few dozen a job, none
  a round) are always recorded; per-round spans only while a capture,
  ``Timer.enable()`` or the telemetry recorder is live.

Cost contract: recording a span is one clock pair + one locked list
append, sampled/aggregated per iteration or per request — NEVER per
row. With nothing live, a per-round ``timed`` section is one flag
check.

Threading contract (tpulint TPL008 over obs/): the span buffer is
appended from trainer/handler/watcher threads and drained from
recorder/daemon threads — every touch of ``_spans`` and the current
trace context goes through ``_spans_lock``, mirroring the
locked snapshot-and-clear drains of ``resilience/faults.py`` and
``obs/cost.py``.

This module never imports jax (not even lazily): the ``trace`` CLI,
the pipeline supervisor and the publisher all consume it on jax-free
paths.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys
import threading
import time
import uuid
from collections import defaultdict
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = ["SPAN_EVENT_KEYS", "FUSED_SCAN_PHASE", "BLOCKING_PHASES",
           "TRACE_CTX_ENV", "new_trace_id", "new_span_id",
           "make_span", "record_span", "span", "drain_span_events",
           "span_events_snapshot", "current_context",
           "set_current_trace", "format_context",
           "begin_span", "end_span", "open_span_context",
           "record_iteration_spans", "load_spans",
           "correct_clock_skew", "chrome_trace", "critical_paths",
           "render_critical_paths", "main"]

#: the JSONL schema contract of every ``{"event": "span"}`` line —
#: derived from the single-source registry (obs/schemas.py EVENTS,
#: the TPL015 contract) and re-exported here for the span emitters
#: and tests that historically import it from this module
from .schemas import required_keys as _required_keys  # noqa: E402

SPAN_EVENT_KEYS = _required_keys("span")

#: the Timer phase that blocks INSIDE a fused-scan window's
#: train_one_iter call (the window-boundary batched fetch,
#: models/gbdt.py _dispatch_scan_window). Defined here — the jax-free
#: layer every consumer can import — and used by gbdt.py itself, the
#: fused-iteration bench and the per-iteration host-gap derivation
#: below: in-call wall minus these phases is the host driver gap the
#: ``fused_scan_iters auto`` flip gate requires to be ~0.
FUSED_SCAN_PHASE = "boosting/fused_scan"
BLOCKING_PHASES = (FUSED_SCAN_PHASE,)

#: env var carrying the current trace context into spawned workers
#: (``trace_id:span_id``) — the pipeline supervisor exports it per
#: generation so the train worker's iteration spans and the
#: publisher's publish span join the generation's trace
TRACE_CTX_ENV = "LIGHTGBM_TPU_TRACE_CTX"

#: span-buffer cap, same shape as obs/cost.py's event cap: a consumer
#: that never drains must not grow memory forever. The OLDEST spans go
#: first (as obs/cost.py's events do): a process nobody drains still
#: holds its last job's spans whole
_SPANS_CAP = 4096

_spans_lock = threading.Lock()
# ---- guarded by _spans_lock ----
_spans: List[Dict[str, Any]] = []
_spans_dropped = 0
# (trace_id, span_id) of the process-current trace; False = env not
# parsed yet, None = parsed and absent
_current: Any = False
# True while the process-current trace is one a ``train/job`` root
# minted for itself (begin_span(trace_root=True)), not one it was given
# (the env var, set_current_trace): the next job replaces a minted
# trace and joins a given one
_current_minted = False

# the open ``timed`` spans of each thread, innermost last:
# [(trace_id, span_id)]. Thread-local, so no lock.
_open = threading.local()


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def _proc_label() -> str:
    # derived per span, not cached: spans land per iteration/request
    # (never per row), and a cache would be one more thread-shared
    # field to guard across the pipeline's fork tree
    rank = os.environ.get("LIGHTGBM_TPU_RANK") or ""
    return f"pid{os.getpid()}" + (f".rank{rank}" if rank else "")


def format_context(trace_id: str, span_id: str) -> str:
    """The ``LIGHTGBM_TPU_TRACE_CTX`` wire form."""
    return f"{trace_id}:{span_id}"


def _parse_context(raw: str) -> Optional[Tuple[str, str]]:
    parts = (raw or "").split(":")
    if len(parts) == 2 and all(parts):
        return (parts[0], parts[1])
    return None


def current_context() -> Optional[Dict[str, str]]:
    """The process-current trace context (``{"trace_id", "span_id"}``)
    — set explicitly via :func:`set_current_trace` or inherited from
    the ``LIGHTGBM_TPU_TRACE_CTX`` env var on first use; None when
    neither exists."""
    global _current
    with _spans_lock:
        if _current is False:
            _current = _parse_context(
                os.environ.get(TRACE_CTX_ENV, ""))
        ctx = _current
    if ctx is None:
        return None
    return {"trace_id": ctx[0], "span_id": ctx[1]}


def set_current_trace(trace_id: Optional[str],
                      span_id: Optional[str] = None) -> None:
    """Set (or with ``None`` clear) the process-current trace."""
    global _current, _current_minted
    with _spans_lock:
        _current = None if trace_id is None \
            else (trace_id, span_id or new_span_id())
        _current_minted = False


def begin_span(trace_root: bool = False
               ) -> Tuple[str, str, Optional[str]]:
    """Open a span on this thread's stack: ``(trace_id, span_id,
    parent_id)``. The parent is the innermost open span of the thread;
    a span with none joins the process-current trace (the pipeline's
    ``LIGHTGBM_TPU_TRACE_CTX``) or roots its own. ``trace_root`` marks
    the span that IS a job (``train/job``): with no trace given to the
    process it mints one and makes it process-current, so the job's
    iterations and a publication after it share the trace, and the next
    job mints its own."""
    global _current, _current_minted
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    span_id = new_span_id()
    if stack:
        trace_id, parent_id = stack[-1]
    else:
        ctx = current_context()
        with _spans_lock:
            minted = _current_minted
        if ctx is not None and not (trace_root and minted):
            trace_id, parent_id = ctx["trace_id"], ctx["span_id"]
        else:
            trace_id, parent_id = new_trace_id(), None
            if trace_root:
                with _spans_lock:
                    _current = (trace_id, span_id)
                    _current_minted = True
    stack.append((trace_id, span_id))
    return trace_id, span_id, parent_id


def end_span() -> None:
    stack = getattr(_open, "stack", None)
    if stack:
        stack.pop()


def open_span_context() -> Tuple[Optional[str], Optional[str]]:
    """``(trace_id, span_id)`` of this thread's innermost open span,
    else of the process-current trace, else ``(None, None)``: what a
    span recorded after the fact (obs/cost.py's compile stages) hangs
    itself under."""
    stack = getattr(_open, "stack", None)
    if stack:
        return stack[-1]
    ctx = current_context()
    if ctx is not None:
        return ctx["trace_id"], ctx["span_id"]
    return None, None


def make_span(name: str, t_start: float,
              t_end: Optional[float] = None, *,
              trace_id: Optional[str] = None,
              span_id: Optional[str] = None,
              parent_id: Optional[str] = None,
              attrs: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
    """Build one span event dict WITHOUT buffering it (the load
    generator writes its spans straight to its own event log).

    ``t_start``/``t_end`` are ``time.perf_counter()`` readings;
    ``t_end`` defaults to now. The paired wall timestamp is derived
    from the current clock pair so spans whose start lies in the past
    still carry a consistent (wall, mono) anchor."""
    now_m = time.perf_counter()
    if t_end is None:
        t_end = now_m
    return {
        "event": "span",
        "name": str(name),
        "trace_id": trace_id or new_trace_id(),
        "span_id": span_id or new_span_id(),
        "parent_id": parent_id,
        "wall": time.time() - (now_m - t_start),
        "mono": float(t_start),
        "dur": max(0.0, float(t_end) - float(t_start)),
        "proc": _proc_label(),
        "attrs": dict(attrs) if attrs else {},
    }


def record_span(name: str, t_start: float,
                t_end: Optional[float] = None, *,
                trace_id: Optional[str] = None,
                span_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                attrs: Optional[Dict[str, Any]] = None) -> str:
    """Record one finished span into the process buffer; returns its
    span id. The buffer is drained into the JSONL stream by the
    telemetry recorder / serve daemon (locked snapshot-and-clear)."""
    global _spans_dropped
    ev = make_span(name, t_start, t_end, trace_id=trace_id,
                   span_id=span_id, parent_id=parent_id, attrs=attrs)
    with _spans_lock:
        _spans.append(ev)
        if len(_spans) > _SPANS_CAP:
            _spans_dropped += len(_spans) - _SPANS_CAP
            del _spans[:len(_spans) - _SPANS_CAP]
    return ev["span_id"]


class _SpanHandle:
    """What :func:`span` yields: the ids children parent to, plus a
    mutable ``attrs`` dict stamped onto the span when it closes."""

    __slots__ = ("trace_id", "span_id", "parent_id", "attrs")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str],
                 attrs: Optional[Dict[str, Any]]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}


@contextmanager
def span(name: str, *, trace_id: Optional[str] = None,
         parent_id: Optional[str] = None,
         attrs: Optional[Dict[str, Any]] = None
         ) -> Iterator[_SpanHandle]:
    """Record the enclosed section as a span. Without an explicit
    ``trace_id`` the process-current context supplies trace and
    parent; with neither, the span roots a fresh trace."""
    if trace_id is None:
        ctx = current_context()
        if ctx is not None:
            trace_id = ctx["trace_id"]
            if parent_id is None:
                parent_id = ctx["span_id"]
    handle = _SpanHandle(trace_id or new_trace_id(), new_span_id(),
                         parent_id, attrs)
    t0 = time.perf_counter()
    try:
        yield handle
    finally:
        record_span(name, t0, trace_id=handle.trace_id,
                    span_id=handle.span_id,
                    parent_id=handle.parent_id, attrs=handle.attrs)


def drain_span_events() -> List[Dict[str, Any]]:
    """Locked snapshot-and-clear of the span buffer (the same drain
    contract as ``resilience.faults.drain_events`` — a span recorded
    from another thread between a bare copy and clear would be lost
    forever)."""
    global _spans, _spans_dropped
    with _spans_lock:
        if not _spans:
            return []
        out, _spans = _spans, []
        _spans_dropped = 0
    return out


def span_events_snapshot() -> List[Dict[str, Any]]:
    """Copy of the pending (undrained) spans, for tests/inspection."""
    with _spans_lock:
        return list(_spans)


#: the job's own frame: never adopted by an iteration
_JOB_FRAME_SPANS = ("train/job", "train/init", "train/round",
                    "train/iteration")


def record_iteration_spans(event: Dict[str, Any], t_start: float,
                           t_end: float) -> None:
    """The telemetry recorder's span of one iteration (obs/recorder.py):
    ``train/iteration`` over [t_start, t_end], which ADOPTS the real
    spans the iteration's ``timed`` sections recorded — the spans that
    started inside the interval and hang directly under a span still
    open on this thread (the engine loop's ``train/round``, not yet
    recorded) are re-parented to it. Nothing is laid out: every child
    keeps its true start and duration.

    On fused-scan iterations the parent also carries the dispatch-gap
    decomposition: ``host_gap_s`` = iteration wall minus the interval's
    real ``boosting/fused_scan`` spans — the host driver time the
    ``fused_scan_iters auto`` flip gate requires to be ~0 inside a
    window (an upper bound off-chip, where per-iteration programs
    execute synchronously inside the dispatch call).

    Costs one clock pair + one locked pass over the pending spans per
    ITERATION — nothing here runs inside the hot-marked drivers."""
    stack = list(getattr(_open, "stack", None) or ())
    if stack:
        # under the job (the outermost open span), beside its rounds
        trace_id, parent_id = stack[0]
        open_ids = {sid for _, sid in stack}
    else:
        ctx = current_context()
        if ctx is None:
            # a bare record_iteration() outside any train() still
            # groups its iterations in one trace
            set_current_trace(new_trace_id())
            ctx = current_context()
        trace_id, parent_id = ctx["trace_id"], ctx["span_id"]
        open_ids = {parent_id, None}
    attrs: Dict[str, Any] = {"iteration": event.get("iteration")}
    scan = event.get("scan")
    span_id = new_span_id()
    blocking = 0.0
    with _spans_lock:
        for s in _spans:
            if s["trace_id"] != trace_id \
                    or not t_start <= s["mono"] <= t_end:
                continue
            if s["name"] in BLOCKING_PHASES:    # at whatever depth
                blocking += s["dur"]
            if s["parent_id"] in open_ids \
                    and s["name"] not in _JOB_FRAME_SPANS:
                s["parent_id"] = span_id
    if scan:
        attrs["scan"] = scan
        attrs["host_gap_s"] = round(
            max((t_end - t_start) - blocking, 0.0), 6)
    record_span("train/iteration", t_start, t_end, trace_id=trace_id,
                span_id=span_id, parent_id=parent_id, attrs=attrs)


# ---------------------------------------------------------------------
# the `python -m lightgbm_tpu trace <dir>` CLI: merge per-process
# streams, correct clock skew, reconstruct critical paths, export
# Chrome trace-event JSON (Perfetto-loadable)
# ---------------------------------------------------------------------

#: matches the fleet's stream names (x.jsonl, x.jsonl.rankN,
#: x.jsonl.fleet) — kept identical to obs/recorder._STREAM_NAME_RE so
#: `trace` and `stats --fleet` always walk the same files
_STREAM_NAME_RE = re.compile(r"\.jsonl(\.rank\d+|\.fleet)?$")


def load_spans(directory: str) -> List[Dict[str, Any]]:
    """Every ``{"event": "span"}`` line under ``directory``
    (recursive), each stamped with its stream's relative path under
    ``"_stream"``. A truncated FINAL line per stream is tolerated (a
    SIGKILLed replica lands mid-write); garbage before the last line
    raises — that is corruption, not a crash artifact."""
    from .recorder import _stream_lines

    spans: List[Dict[str, Any]] = []
    for root, _dirs, names in sorted(os.walk(directory)):
        for name in sorted(names):
            if not _STREAM_NAME_RE.search(name):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, directory)

            def _parse(line: str, is_last: bool) -> Optional[dict]:
                try:
                    ev = json.loads(line)
                except ValueError:
                    if is_last:
                        return None        # mid-write crash artifact
                    raise ValueError(
                        f"{path}: malformed telemetry line "
                        f"{line[:80]!r}")
                return ev if isinstance(ev, dict) else None

            for ev in _stream_lines(path, _parse):
                if ev.get("event") != "span":
                    continue
                ev["_stream"] = rel
                spans.append(ev)
    return spans


def _proc_key(s: Dict[str, Any]) -> Tuple[str, str]:
    # (stream, proc): pids recycle across elastic restarts and hosts,
    # the stream they wrote into disambiguates the clock domain
    return (str(s.get("_stream", "")), str(s.get("proc", "?")))


def correct_clock_skew(spans: List[Dict[str, Any]]
                       ) -> Dict[Tuple[str, str], float]:
    """Place every span on one corrected timeline: per process, the
    offset between its monotonic clock and the shared wall clock is
    the median of ``wall - mono`` over its spans (the median rejects
    the occasional NTP step mid-run), and each span gains absolute
    ``t0``/``t1`` seconds = ``mono + offset``. Returns the per-process
    offsets (for the CLI's provenance print)."""
    groups: Dict[Tuple[str, str], List[Dict[str, Any]]] = \
        defaultdict(list)
    for s in spans:
        groups[_proc_key(s)].append(s)
    offsets: Dict[Tuple[str, str], float] = {}
    for key, group in groups.items():
        offsets[key] = statistics.median(
            float(s["wall"]) - float(s["mono"]) for s in group)
    for s in spans:
        t0 = float(s["mono"]) + offsets[_proc_key(s)]
        s["t0"] = t0
        s["t1"] = t0 + float(s.get("dur", 0.0))
    return offsets


def chrome_trace(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome trace-event JSON (the ``traceEvents`` array of complete
    ``ph: "X"`` events in microseconds, plus ``process_name``
    metadata) over skew-corrected spans — loadable in Perfetto /
    chrome://tracing. Timestamps are relative to the earliest span so
    the viewer opens at t=0."""
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    procs = sorted({_proc_key(s) for s in spans})
    pid_of = {key: i + 1 for i, key in enumerate(procs)}
    base = min(float(s["t0"]) for s in spans)
    events: List[Dict[str, Any]] = []
    for (stream, proc), pid in sorted(pid_of.items(),
                                      key=lambda kv: kv[1]):
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0,
                       "args": {"name": f"{proc} ({stream})"}})
    for s in spans:
        args = {"trace_id": s.get("trace_id"),
                "span_id": s.get("span_id"),
                "parent_id": s.get("parent_id"),
                **(s.get("attrs") or {})}
        events.append({
            "name": str(s.get("name", "?")),
            "ph": "X",
            "ts": round((float(s["t0"]) - base) * 1e6, 3),
            "dur": round(float(s.get("dur", 0.0)) * 1e6, 3),
            "pid": pid_of[_proc_key(s)],
            "tid": 0,
            "cat": str(s.get("name", "?")).split("/", 1)[0],
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


#: watcher swap phases in causal order (serve/daemon.py poll_once)
_SWAP_STEPS = ("swap/validate", "swap/load", "swap/stage",
               "swap/apply")


def critical_paths(spans: List[Dict[str, Any]]
                   ) -> List[Dict[str, Any]]:
    """Reconstruct the named lifecycle critical paths from
    skew-corrected spans: for each trace that published a model,

        last train/iteration -> publish/model -> swap/validate ->
        load -> stage -> apply -> first serve/request answered by
        the swapped model

    The final hop joins ACROSS traces: request spans ride the
    client's trace, so the first request served by the new model is
    found by model id + corrected time (earliest ``serve/request``
    whose ``attrs.model`` matches the applied forest and whose start
    is at/after the swap's end)."""
    by_trace: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    requests: List[Dict[str, Any]] = []
    for s in spans:
        by_trace[str(s.get("trace_id"))].append(s)
        if s.get("name") == "serve/request":
            requests.append(s)
    requests.sort(key=lambda s: s["t0"])
    paths: List[Dict[str, Any]] = []
    for tid, group in by_trace.items():
        pubs = [s for s in group if s.get("name") == "publish/model"]
        if not pubs:
            continue
        pub = max(pubs, key=lambda s: s["t1"])
        steps: List[Dict[str, Any]] = []

        def _push(s: Dict[str, Any], label: Optional[str] = None
                  ) -> None:
            if steps and s["t0"] > steps[-1]["t1"]:
                steps.append({"name": "(wait)",
                              "t0": steps[-1]["t1"], "t1": s["t0"],
                              "dur_s": s["t0"] - steps[-1]["t1"],
                              "gap": True})
            steps.append({"name": label or str(s["name"]),
                          "t0": s["t0"], "t1": s["t1"],
                          "dur_s": float(s.get("dur", 0.0)),
                          "gap": False})

        iters = [s for s in group
                 if s.get("name") == "train/iteration"]
        if iters:
            last = max(iters, key=lambda s: (
                (s.get("attrs") or {}).get("iteration") or 0,
                s["t1"]))
            it_no = (last.get("attrs") or {}).get("iteration")
            _push(last, f"train/iteration #{it_no}")
        _push(pub)
        model = None
        swap_end = None
        # several replicas may swap; follow the EARLIEST completed
        # apply (the first replica able to answer from the new model)
        applies = sorted(
            (s for s in group if s.get("name") == "swap/apply"),
            key=lambda s: s["t1"])
        if applies:
            apply_proc = _proc_key(applies[0])
            for name in _SWAP_STEPS:
                cands = [s for s in group if s.get("name") == name
                         and _proc_key(s) == apply_proc]
                if cands:
                    _push(min(cands, key=lambda s: s["t0"]))
            model = (applies[0].get("attrs") or {}).get("model")
            swap_end = applies[0]["t1"]
        served = None
        if model is not None and swap_end is not None:
            for req in requests:
                if (req.get("attrs") or {}).get("model") == model \
                        and req["t0"] >= swap_end:
                    served = req
                    break
            if served is not None:
                _push(served, f"serve/request (model {model})")
        paths.append({
            "trace_id": tid,
            "generation": (pub.get("attrs") or {}).get("generation"),
            "model": model,
            "complete": bool(iters and applies and served),
            "steps": steps,
            "total_s": (steps[-1]["t1"] - steps[0]["t0"])
            if steps else 0.0,
        })
    paths.sort(key=lambda p: (p["generation"] is None,
                              p["generation"], p["trace_id"]))
    return paths


def render_critical_paths(paths: List[Dict[str, Any]]) -> str:
    lines: List[str] = []
    for p in paths:
        gen = p["generation"]
        head = f"critical path · generation " \
               f"{'?' if gen is None else gen} · trace " \
               f"{p['trace_id']}" \
               f"{'' if p['complete'] else ' · INCOMPLETE'}"
        lines.append(head)
        t_base = p["steps"][0]["t0"] if p["steps"] else 0.0
        for st in p["steps"]:
            at = st["t0"] - t_base
            lines.append(f"  {st['name']:44s} +{at:9.3f}s  "
                         f"{st['dur_s'] * 1e3:10.2f} ms")
        lines.append(f"  {'TOTAL iteration -> first-served':44s} "
                     f"{'':10s} {p['total_s'] * 1e3:10.2f} ms")
        lines.append("")
    return "\n".join(lines).rstrip()


_TRACE_HELP = """\
usage: python -m lightgbm_tpu trace <telemetry-dir> [--out FILE]
                                   [--xplane TRACE_DIR [--scopes FILE]]

Merge every telemetry stream under the directory (x.jsonl plus the
fleet's .rankN / .fleet suffixes, recursively), collect the
{"event": "span"} lines, correct cross-process clock skew from each
span's paired wall/monotonic timestamps, and:

- write Chrome trace-event JSON (default <dir>/trace.json) — open it
  at https://ui.perfetto.dev or chrome://tracing,
- print the reconstructed lifecycle critical paths: last trained
  iteration -> publish -> manifest-validated swap -> first request
  served by the new model, with clock-corrected latencies.

With --xplane TRACE_DIR (a profiler capture: trace_to,
LIGHTGBM_TPU_TRACE_TO / LIGHTGBM_TPU_XPROF, or any jax.profiler session
the program ran under) it also reads the device trace by layer: device
self time by named scope (a `while` does not count its body twice),
the unscoped remainder, and each idle gap over 1 ms put down to the
innermost program span covering it. The op -> scope table is
TRACE_DIR/op_scopes.json, which a program-owned capture writes beside
its trace (--scopes FILE names another; obs.op_scopes(entry) builds
one). With --xplane a directory without span events is no error.

Span schema, propagation map and the Perfetto workflow:
docs/OBSERVABILITY.md "Tracing". Without --xplane this command never
imports jax.

exit codes:
  0  spans merged and exported (even if no complete critical path)
  1  no span events found, unreadable directory, corrupt stream, or no
     readable capture under --xplane
"""


def _take_option(argv: List[str], flag: str) -> Tuple[bool, Optional[str]]:
    """Pop ``flag VALUE`` from argv: ``(ok, value or None)``; not ok
    when the flag is there without its value."""
    if flag not in argv:
        return True, None
    i = argv.index(flag)
    if i + 1 >= len(argv):
        print(f"trace: {flag} needs an argument", file=sys.stderr)
        return False, None
    value = argv[i + 1]
    del argv[i:i + 2]
    return True, value


def _xplane_report(trace_dir: str, scopes_path: Optional[str]) -> int:
    from . import xplane
    try:
        capture = xplane.load(xplane.find_xplane(trace_dir))
    except (OSError, ValueError) as e:
        print(f"[LightGBM-TPU] [Fatal] cannot read a capture under "
              f"{trace_dir}: {e}", file=sys.stderr)
        return 1
    path = scopes_path or os.path.join(trace_dir, "op_scopes.json")
    table = None
    if os.path.exists(path):
        table = xplane.load_op_scopes(path)
    elif scopes_path:
        print(f"[LightGBM-TPU] [Fatal] no such table: {scopes_path}",
              file=sys.stderr)
        return 1
    print(xplane.render_report(xplane.report(capture, table)))
    return 0



def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(_TRACE_HELP)
        return 0
    ok_o, out_path = _take_option(argv, "--out")
    ok_x, xplane_dir = _take_option(argv, "--xplane")
    ok_s, scopes_path = _take_option(argv, "--scopes")
    if not (ok_o and ok_x and ok_s):
        return 1
    if len(argv) != 1:
        print("usage: python -m lightgbm_tpu trace <telemetry-dir> "
              "[--out FILE] [--xplane TRACE_DIR [--scopes FILE]]",
              file=sys.stderr)
        return 1
    directory = argv[0]
    if not os.path.isdir(directory):
        print(f"[LightGBM-TPU] [Fatal] not a directory: {directory}",
              file=sys.stderr)
        return 1
    try:
        spans = load_spans(directory)
    except OSError as e:
        print(f"[LightGBM-TPU] [Fatal] cannot read {directory}: {e}",
              file=sys.stderr)
        return 1
    except ValueError as e:
        print(f"[LightGBM-TPU] [Fatal] corrupt telemetry: {e}",
              file=sys.stderr)
        return 1
    if not spans:
        print(f"no span events in any *.jsonl under {directory}",
              file=sys.stderr)
        return _xplane_report(xplane_dir, scopes_path) \
            if xplane_dir else 1
    offsets = correct_clock_skew(spans)
    doc = chrome_trace(spans)
    out_path = out_path or os.path.join(directory, "trace.json")
    try:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
    except OSError as e:
        print(f"[LightGBM-TPU] [Fatal] cannot write {out_path}: {e}",
              file=sys.stderr)
        return 1
    print(f"{len(spans)} span(s) from {len(offsets)} process(es) -> "
          f"{out_path} (Perfetto/chrome://tracing)")
    if len(offsets) > 1:
        monos = sorted(offsets.values())
        print(f"clock-skew correction: per-process mono->wall "
              f"offsets spread over {monos[-1] - monos[0]:.3f} s")
    paths = critical_paths(spans)
    if paths:
        print()
        print(render_critical_paths(paths))
    else:
        print("no publish spans: critical paths need a traced "
              "publish -> swap -> serve lifecycle (run the pipeline "
              "with tracing on)")
    if xplane_dir:
        print()
        return _xplane_report(xplane_dir, scopes_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
