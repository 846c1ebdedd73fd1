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
- the same by PROGRAM: the compiler numbers every program's ops from
  the same names (``fusion.202`` is one op of the grower and another of
  the gradient), so each registered entry's table is laid over the ops
  that ran inside THAT program's executions (the ``XLA Modules`` line);
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

import bisect
import glob
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = ["HOST_SPAN_ROOTS", "find_xplane", "load", "op_head",
           "union_seconds", "op_times", "idle_gaps", "report",
           "render_report", "load_op_scopes"]

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
UNSCOPED = "(unscoped)"

#: first path segments of the program's host spans (``timed`` labels):
#: what tells a program span from the runtime's own host events in a
#: capture
HOST_SPAN_ROOTS = ("dataset", "train", "callbacks", "boosting", "tree",
                   "tree_learner", "engine", "ingest", "valid", "metric",
                   "compile")

Event = Tuple[str, float, float]        # (name, start_s, dur_s)
#: ``{program (a module's name) or None: {op: scope}}``; ``None`` holds
#: a table that names no program (laid over any program without its own)
Tables = Dict[Optional[str], Dict[str, str]]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: [op events]}, "modules": {plane: [program
    executions]}, "host": [events]}`` from an ``.xplane.pb`` (imports
    jax: the one place this module does)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                key = {OPS_LINE: "devices",
                       MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key][plane.name] = [
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


def _self_events(events: Iterable[Event]) -> List[Event]:
    """``[(name, start_s, self_s)]`` by start: each event's duration
    minus the part its nested children cover (events of one line nest
    properly)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []         # (end, index into out)
    for name, start, dur in evs:
        while stack and start >= stack[-1][0] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= dur
        out.append([name, start, dur])
        stack.append((start + dur, len(out) - 1))
    return [(n, t, max(s, 0.0)) for n, t, s in out]


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


def program_of(module_event: str) -> str:
    """``jit_grow_tree_impl`` from ``jit_grow_tree_impl(1234567)``: a
    module event is named by the program and its fingerprint."""
    return module_event.split("(", 1)[0]


def op_times(ops: Iterable[Event], tables: Optional[Tables],
             modules: Iterable[Event] = ()
             ) -> List[Tuple[Optional[str], str, float, str]]:
    """``[(program, op, self_s, scope)]``, one row an op of a program:
    every op event's self time, summed by the program whose execution
    it started in (``None`` outside any) and its name, with the scope
    THAT program's table gives it (``tables``: ``{program: {op:
    scope}}``; the table under ``None``, a file's that names no program,
    serves every program without one of its own; ``UNSCOPED`` where
    neither holds the op)."""
    tables = tables or {}
    runs = sorted((s, s + d, program_of(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]
    acc: Dict[Tuple[Optional[str], str], float] = {}
    for name, start, self_s in _self_events(ops):
        i = bisect.bisect_right(starts, start) - 1
        prog = runs[i][2] if i >= 0 and start < runs[i][1] else None
        key = (prog, op_head(name))
        acc[key] = acc.get(key, 0.0) + self_s
    fallback = tables.get(None, {})
    return [(prog, op, s, tables.get(prog, fallback).get(op, UNSCOPED))
            for (prog, op), s in acc.items()]


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
                   ) -> Optional[Tables]:
    """The op -> scope tables of an ``op_scopes.json`` (``{entry:
    {"ops": {op: scope}, "derived": [...], "module": program}}``, as
    ``obs.scopes.write_op_scopes`` writes it; every entry, or only
    ``entry``), by program. An entry that names no program (a file from
    before the per-program overlay) is merged under ``None``."""
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    tables: Tables = {}
    for name, tab in doc.items():
        if entry is None or name == entry:
            tables.setdefault(tab.get("module"), {}).update(tab["ops"])
    return tables or None


def _by_time(acc: Dict[Any, float]) -> Dict[Any, float]:
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def report(trace: Dict[str, Any], tables: Optional[Tables],
           min_gap_s: float = 1e-3) -> Dict[str, Any]:
    """The numbers of one capture, per device plane. ``tables``:
    ``{program: {op: scope}}`` (:func:`load_op_scopes`). ``by_program``
    is there where the capture has a module line: each program's
    executions, device self time and that time by scope."""
    devices = []
    for plane, ops in sorted(trace["devices"].items()):
        if not ops:
            continue
        mods = trace.get("modules", {}).get(plane, [])
        busy, merged = union_seconds((s, s + d) for _, s, d in ops)
        rows = op_times(ops, tables, mods)
        scopes: Dict[str, float] = {}
        programs: Dict[str, Dict[str, Any]] = {}
        for n, _, _ in mods:
            got = programs.setdefault(program_of(n), {
                "runs": 0, "self_s": 0.0, "by_scope": {}})
            got["runs"] += 1
        for prog, _, self_s, sc in rows:
            scopes[sc] = scopes.get(sc, 0.0) + self_s
            if prog in programs:
                got = programs[prog]
                got["self_s"] += self_s
                got["by_scope"][sc] = got["by_scope"].get(sc, 0.0) + self_s
        for got in programs.values():
            got["by_scope"] = _by_time(got["by_scope"])
        total = sum(scopes.values())
        devices.append({
            "plane": plane,
            "window_s": merged[-1][1] - merged[0][0],
            "busy_s": busy,
            "self_s": total,
            "scoped_share": (1.0 - scopes.get(UNSCOPED, 0.0) / total)
            if total else 0.0,
            "by_scope": _by_time(scopes),
            "by_program": dict(sorted(programs.items(),
                                      key=lambda kv: -kv[1]["self_s"])),
            "idle_gaps": idle_gaps(ops, trace["host"], min_gap_s),
        })
    return {"devices": devices, "has_table": bool(tables)}


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
        for prog, got in dev.get("by_program", {}).items():
            if got["self_s"] < 1e-3 * total:
                continue
            lines.append(f"  program {prog}: {got['runs']} runs, "
                         f"{got['self_s']:.6f} s")
            for sc, sec in got["by_scope"].items():
                lines.append(f"    {sc:26s} {sec:12.6f} s  "
                             f"{100 * sec / (got['self_s'] or 1.0):6.2f}%")
        for g in dev["idle_gaps"]:
            where = g["span"] or "(no program span)"
            host = f" > {g['host']}" if g["host"] \
                and g["host"] != g["span"] else ""
            lines.append(f"  idle {g['gap_s'] * 1e3:9.3f} ms at "
                         f"+{g['at_s']:.6f} s in {where}{host}")
    return "\n".join(lines)
