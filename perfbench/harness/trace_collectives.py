"""The collectives of a traced run, and how evenly the chips worked.

``trace_reduce.reduce`` means its numbers over the device planes and
keeps no op class but ``sort``; a cell that spans chips also wants what
only differs between them. From ``trace_reduce.load``'s lists alone:

- per device plane, the self time of the all-reduce ops inside the
  traced window, their mean over the planes, and how many ran. An op
  event is named by its whole HLO line, and the compiler names an
  all-reduce after the JAX primitive as often as after the opcode
  (``%psum.84 = f32[67,256,2]{...} all-reduce(...)`` beside
  ``%all-reduce.26 = (s32[], s32[]) all-reduce(...)``), so the class is
  read from the opcode, with the head as the fallback for a name that
  was cut short. Every reduction this program issues, the histograms'
  and the scalar sums', lowers to one; an asynchronous pair
  (``all-reduce-start`` / ``-done``) counts its time twice over and its
  op once;
- per device plane, the busy time (union of op intervals) in the same
  window, and its spread ``(max - min) / mean``: a rank that waits in a
  collective is busy in it, so the spread shows launch skew and
  stragglers, not load imbalance alone.

The window is ``trace_reduce.reduce``'s: the first ``span_name`` host
span's start to the last one's end.
"""

import re

from . import trace_reduce as T

_ALLREDUCE = re.compile(r" all-reduce(-start|-done)?\(")


def allreduce_kind(name):
    """``""`` / ``"-start"`` / ``"-done"`` for an all-reduce op's event
    name, ``None`` for any other op."""
    m = _ALLREDUCE.search(name)
    if m:
        return m.group(1) or ""
    head = T.op_head(name)
    if head.startswith("all-reduce"):
        return "-done" if head.startswith("all-reduce-done") else ""
    return None


def reduce(trace, span_name):
    spans = sorted((s, s + d) for n, s, d in trace["host"]
                   if n == span_name)
    planes = []
    for plane, lines in sorted(trace["devices"].items()):
        ops = lines.get(T.OPS_LINE, [])
        if not ops:
            continue
        if spans:
            w0, w1 = spans[0][0], spans[-1][1]
        else:
            w0 = min(s for _, s, _ in ops)
            w1 = max(s + d for _, s, d in ops)
        inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in ops if s + d > w0 and s < w1]
        busy, _ = T.union_seconds((s, s + d) for _, s, d in inside)
        ar = [(allreduce_kind(n), self_s)
              for n, self_s in T.self_times(inside)]
        planes.append({"plane": plane, "busy_s": busy,
                       "allreduce_s": sum(s for k, s in ar if k is not None),
                       "allreduce_ops": sum(1 for k, _ in ar
                                            if k in ("", "-start"))})
    if not planes:
        return None
    k = len(planes)
    busy = [p["busy_s"] for p in planes]
    mean_busy = sum(busy) / k
    return {
        "planes": planes,
        "allreduce_s": sum(p["allreduce_s"] for p in planes) / k,
        "allreduce_ops": sum(p["allreduce_ops"] for p in planes) / k,
        "busy_skew_pct": 100.0 * (max(busy) - min(busy)) / mean_busy
        if mean_busy else None,
    }
