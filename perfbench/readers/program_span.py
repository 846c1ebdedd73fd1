"""Sums the program's own host spans (``lightgbm_tpu.obs.trace``).

Readers run in the driver's process after ``run(ctx)`` has returned, so
the program's span buffer is read directly. ``args``:

- ``spans``: the span names whose durations are summed;
- ``within``: ``"construct"``, under the last ``dataset/construct``
  span, or ``"job"``, in the last ``train/job`` span's trace;
- ``per_round`` (optional): divide by ``host.traced_rounds``; every
  name of ``spans`` must then appear exactly that many times, else the
  spans do not cover exactly the traced rounds (a run traced as a whole,
  a buffer that lost spans) and nothing is read;
- ``scale`` (optional): multiplies the result (1000 for ms).

``None``, never a guess: where the program has no span buffer or no
such span (the parent of the PR that added the spans), where a named
span is absent, or where ``per_round`` does not hold.
"""


def _spans():
    try:
        from lightgbm_tpu.obs.trace import span_events_snapshot
        return list(span_events_snapshot())
    except Exception:
        return None


def _last(spans, name):
    found = [s for s in spans if s.get("name") == name]
    return max(found, key=lambda s: s["mono"]) if found else None


def _under(spans, root):
    """The spans whose parent chain reaches ``root``."""
    kids = {}
    for s in spans:
        kids.setdefault(s.get("parent_id"), []).append(s)
    out, todo = [], [root["span_id"]]
    while todo:
        for s in kids.get(todo.pop(), []):
            out.append(s)
            todo.append(s["span_id"])
    return out


def read(obs, args):
    spans = _spans()
    if not spans:
        return None
    if args["within"] == "construct":
        root = _last(spans, "dataset/construct")
        pool = _under(spans, root) if root else []
    else:
        root = _last(spans, "train/job")
        pool = [s for s in spans
                if root and s.get("trace_id") == root["trace_id"]]
    total = 0.0
    rounds = obs.get("host", {}).get("traced_rounds")
    for name in args["spans"]:
        named = [s for s in pool if s.get("name") == name]
        if not named:
            return None
        if args.get("per_round") and len(named) != rounds:
            return None
        total += sum(float(s["dur"]) for s in named)
    if args.get("per_round"):
        total /= rounds
    return total * float(args.get("scale", 1.0))
