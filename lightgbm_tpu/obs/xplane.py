"""A device trace read by layer: ``trace <dir> --xplane <trace-dir>``.

The operator's side of the device scopes (obs/scopes.py). Given a
profiler capture (``trace_to``, ``LIGHTGBM_TPU_XPROF``, or any
``jax.profiler`` session the program ran under) and the op -> scope
table the program wrote beside it (``op_scopes.json``), it reports

- device SELF time by scope: an ``XLA Ops`` event covers the events
  nested in it (a ``while`` covers its body's ops), so each event
  counts its duration minus its children's — a loop never counts its
  body twice — and events are put down to the scope of their op; the
  time of ops the table does not hold is stated as ``(unscoped)``;
- device busy time as the union of op intervals, and every idle gap
  over a threshold put down to the innermost PROGRAM span covering its
  middle (the ``timed`` sections the capture holds as
  ``TraceAnnotation`` events), with the innermost host event of any
  kind beside it.

The nesting and union arithmetic is the benchmark's
(``perfbench/harness/trace_reduce.py``), kept apart on purpose: the
benchmark runs against commits that lack this module, and the program
never imports the benchmark.

:func:`load` reads the ``.xplane.pb`` with nothing but
``jax.profiler.ProfileData``; everything else works on plain lists, so
a hand-built trace tests it. Times are seconds.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = ["HOST_SPAN_ROOTS", "find_xplane", "load", "op_head",
           "self_times", "union_seconds", "by_scope", "idle_gaps",
           "report", "render_report", "load_op_scopes"]

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
UNSCOPED = "(unscoped)"

#: first path segments of the program's host spans (``timed`` labels):
#: what tells a program span from the runtime's own host events in a
#: capture
HOST_SPAN_ROOTS = ("dataset", "train", "callbacks", "boosting", "tree",
                   "tree_learner", "engine", "ingest")

Event = Tuple[str, float, float]        # (name, start_s, dur_s)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: [op events]}, "host": [events]}`` from an
    ``.xplane.pb`` (imports jax: the one place this module does)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][plane.name] = [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events)
    return out


def op_head(name: str) -> str:
    """``fusion.211`` from ``%fusion.211 = f32[17,64,64,2]... fusion(``:
    a TPU op event is named by its whole HLO line."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def self_times(events: Iterable[Event]) -> List[Tuple[str, float]]:
    """``[(name, self_s)]``: each event's duration minus the part its
    nested children cover (events of one line nest properly)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []         # (end, index into out)
    for name, start, dur in evs:
        while stack and start >= stack[-1][0] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append((start + dur, len(out) - 1))
    return [(n, max(s, 0.0)) for n, s in out]


def union_seconds(intervals: Iterable[Tuple[float, float]]
                  ) -> Tuple[float, List[List[float]]]:
    """Length of the union of ``(start, end)`` intervals, and the
    merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def by_scope(ops: Iterable[Event], table: Optional[Dict[str, str]]
             ) -> Dict[str, float]:
    """Device self time by scope (``UNSCOPED`` for ops the table does
    not hold; with no table, everything)."""
    out: Dict[str, float] = {}
    table = table or {}
    for name, self_s in self_times(ops):
        sc = table.get(op_head(name), UNSCOPED)
        out[sc] = out.get(sc, 0.0) + self_s
    return out


def _is_program_span(name: str) -> bool:
    return name.split("/", 1)[0] in HOST_SPAN_ROOTS and "/" in name


def idle_gaps(ops: Iterable[Event], host: Iterable[Event],
              min_s: float = 1e-3) -> List[Dict[str, Any]]:
    """Every gap of ``min_s`` or more between the device's busy
    intervals, longest first: ``{"gap_s", "at_s", "span", "host"}``
    with ``span`` the innermost program span covering the gap's middle
    (``None``: the host was in no ``timed`` section) and ``host`` the
    innermost host event of any kind there."""
    _, merged = union_seconds((s, s + d) for _, s, d in ops)
    host = list(host)
    out = []
    for (_, b0), (a1, _) in zip(merged, merged[1:]):
        if a1 - b0 < min_s:
            continue
        mid = 0.5 * (b0 + a1)
        covering = sorted((d, n) for n, s, d in host if s <= mid <= s + d)
        spans = [n for _, n in covering if _is_program_span(n)]
        out.append({"gap_s": a1 - b0, "at_s": b0 - merged[0][0],
                    "span": spans[0] if spans else None,
                    "host": covering[0][1] if covering else None})
    out.sort(key=lambda g: -g["gap_s"])
    return out


def load_op_scopes(path: str, entry: Optional[str] = None
                   ) -> Optional[Dict[str, str]]:
    """One flat op -> scope table from an ``op_scopes.json``
    (``{entry: {"ops": {op: scope}, "derived": [...]}}``, as
    ``obs.scopes.write_op_scopes`` writes it; entries merged, or only
    ``entry``)."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    table: Dict[str, str] = {}
    for name, tab in doc.items():
        if entry is None or name == entry:
            table.update(tab["ops"])
    return table or None


def report(trace: Dict[str, Any], table: Optional[Dict[str, str]],
           min_gap_s: float = 1e-3) -> Dict[str, Any]:
    """The numbers of one capture, per device plane."""
    devices = []
    for plane, ops in sorted(trace["devices"].items()):
        if not ops:
            continue
        busy, merged = union_seconds((s, s + d) for _, s, d in ops)
        scopes = by_scope(ops, table)
        total = sum(scopes.values())
        devices.append({
            "plane": plane,
            "window_s": merged[-1][1] - merged[0][0],
            "busy_s": busy,
            "self_s": total,
            "scoped_share": (1.0 - scopes.get(UNSCOPED, 0.0) / total)
            if total else 0.0,
            "by_scope": dict(sorted(scopes.items(),
                                    key=lambda kv: -kv[1])),
            "idle_gaps": idle_gaps(ops, trace["host"], min_gap_s),
        })
    return {"devices": devices, "has_table": bool(table)}


def render_report(rep: Dict[str, Any]) -> str:
    lines: List[str] = []
    if not rep["devices"]:
        return "no device plane with an 'XLA Ops' line in the capture"
    if not rep["has_table"]:
        lines.append("no op -> scope table (op_scopes.json): every op "
                     "is unscoped")
    for dev in rep["devices"]:
        lines.append(f"{dev['plane']}: window {dev['window_s']:.6f} s, "
                     f"busy {dev['busy_s']:.6f} s "
                     f"(idle {100 * (1 - dev['busy_s'] / dev['window_s']):.3f}%), "
                     f"{100 * dev['scoped_share']:.2f}% of self time "
                     f"under a named scope")
        total = dev["self_s"] or 1.0
        for sc, sec in dev["by_scope"].items():
            lines.append(f"  {sc:28s} {sec:12.6f} s  "
                         f"{100 * sec / total:6.2f}%")
        for g in dev["idle_gaps"]:
            where = g["span"] or "(no program span)"
            host = f" > {g['host']}" if g["host"] \
                and g["host"] != g["span"] else ""
            lines.append(f"  idle {g['gap_s'] * 1e3:9.3f} ms at "
                         f"+{g['at_s']:.6f} s in {where}{host}")
    return "\n".join(lines)
