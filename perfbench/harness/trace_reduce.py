"""From a profiler trace to device busy time, idle gaps and op classes.

``load(path)`` turns an ``.xplane.pb`` (read with nothing but
``jax.profiler.ProfileData``) into plain lists; ``reduce(...)`` works on
those lists alone, so a hand-built trace tests it. Times are seconds.

As this JAX writes a TPU trace: a device is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per executed
HLO op (nested: a ``while`` covers the ops of its body), its line
``XLA Modules`` one event per executed program. Host threads are lines
of the plane ``/host:CPU``; the driver's ``TraceAnnotation`` spans are
events there, named as the driver named them.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
OP_NAME_CHARS = 160     # of an op's HLO line kept in the breakdown

# op classes by the op's own name: an event of ``XLA Ops`` is named by
# its whole HLO line, ``%sort.12 = (...) sort(%x, %y), ...``, so the
# class is read from the part before `` = `` alone (operands named
# ``%sort.12`` would otherwise claim their consumers). A class is only
# listed where this JAX names the op for what it is: the histogram's
# matmul is an anonymous ``%fusion.N``, so there is no matmul class
# until the program names its scopes (PERF.md, Open questions).
CATEGORIES = (
    ("sort", ("sort",)),
)


def op_head(name):
    """``sort.12`` from ``%sort.12 = (u32[..]) sort(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """``{"devices": {plane: {line: [(name, start_s, dur_s)]}},
    "host": [(name, start_s, dur_s)]}``"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {}
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                       for ev in line.events]
                lines[line.name] = evs
            out["devices"][plane.name] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    out["host"].append((ev.name, ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
    return out


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def self_times(events):
    """``[(name, self_s)]``: each event's duration minus the part its
    nested children cover (events of one line nest properly)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []     # stack of [end, index into out]
    for name, start, dur in evs:
        end = start + dur
        while stack and start >= stack[-1][0] - 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append([end, len(out) - 1])
    return [(n, max(s, 0.0)) for n, s in out]


def classify(name):
    head = op_head(name).lower()
    for cls, prefixes in CATEGORIES:
        if head.startswith(prefixes):
            return cls
    return "other"


def reduce(trace, window=None, span_name=None, top=10):
    """The numbers the readers take. ``window`` ``(start, end)`` bounds
    the traced interval; default: from the first ``span_name`` host span's
    start to the last one's end, else the device events' own extent."""
    spans = sorted((s, s + d) for n, s, d in trace["host"]
                   if span_name and n == span_name)
    per_dev = []
    for plane, lines in sorted(trace["devices"].items()):
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        if window is not None:
            w0, w1 = window
        elif spans:
            w0, w1 = spans[0][0], spans[-1][1]
        else:
            w0 = min(s for _, s, _ in ops)
            w1 = max(s + d for _, s, d in ops)
        inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in ops if s + d > w0 and s < w1]
        busy, merged = union_seconds((s, s + d) for _, s, d in inside)
        cats, by_name = {}, {}
        for n, self_s in self_times(inside):
            cls = classify(n)
            cats[cls] = cats.get(cls, 0.0) + self_s
            by_name[n] = by_name.get(n, 0.0) + self_s
        mods = [m for m in lines.get(MODULES_LINE, [])
                if m[1] + m[2] > w0 and m[1] < w1]
        gaps, at = [], w0
        for a, b in merged:
            if a > at:
                gaps.append((at, a))
            at = max(at, b)
        if w1 > at:
            gaps.append((at, w1))
        per_dev.append({"plane": plane, "window": (w0, w1), "busy_s": busy,
                        "category_s": cats, "by_name": by_name,
                        "module_executions": len(mods),
                        "module_names": sorted({m[0] for m in mods}),
                        "gaps": gaps})
    if not per_dev:
        return None
    k = len(per_dev)
    w0, w1 = per_dev[0]["window"]
    cat_keys = sorted({c for d in per_dev for c in d["category_s"]})
    d0 = per_dev[0]
    top_ops = sorted(d0["by_name"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(d0["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {
        "devices": k,
        "window_s": w1 - w0,
        "busy_s": sum(d["busy_s"] for d in per_dev) / k,
        "category_s": {c: sum(d["category_s"].get(c, 0.0)
                              for d in per_dev) / k for c in cat_keys},
        "module_executions": sum(d["module_executions"]
                                 for d in per_dev) / k,
        "module_names": d0["module_names"],
        "spans": len(spans),
        "device_ops": [[n[:OP_NAME_CHARS], s] for n, s in top_ops],
        "idle_gaps": [[_host_label(trace["host"], a, b, span_name), b - a]
                      for a, b in gaps],
    }


def _host_label(host, a, b, span_name):
    """What the host was doing at the middle of an idle gap: the
    shortest host event covering it (the innermost), with the driver's
    own span named first when the gap lies inside one."""
    mid = 0.5 * (a + b)
    covering = [(d, n) for n, s, d in host if s <= mid <= s + d]
    if not covering:
        return "no_host_event"
    inner = min(covering)[1]
    in_span = any(n == span_name for _, n in covering)
    prefix = (span_name if in_span else "between_rounds") \
        if span_name else "host"
    return inner if inner == prefix else f"{prefix}/{inner}"
