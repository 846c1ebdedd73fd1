"""Least time the chip could take for ONE part of the round
(``obs["work"][args["work"]]``: operations and bytes as the algorithm
defines them, ``harness/work_model*.py``; ``harness/peaks.py``) over the
device seconds that part took in the traced rounds (the observation at
``args["seconds"]``, a dotted path), in percent. ``roofline_pct`` is the
whole round's; this one is a kernel's or a program's."""

from harness import work_model


def read(obs, args):
    work = obs.get("work") or {}
    part, peaks = work.get(args["work"]), work.get("peaks")
    seconds = obs
    for key in args["seconds"].split("."):
        seconds = seconds.get(key) if isinstance(seconds, dict) else None
    if not part or not peaks or not seconds:
        return None
    least, _ = work_model.least_seconds(part, peaks)
    return 100.0 * least / seconds
