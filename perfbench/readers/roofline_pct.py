"""Least time the chip could take for the algorithm's whole round
(``harness/work_model.py``, ``harness/peaks.py``) over the traced
interval, in percent."""

from harness import work_model


def read(obs, args):
    tr, work = obs.get("trace"), obs.get("work")
    if not tr or not work or not work.get("peaks") or not tr["window_s"]:
        return None
    least, _ = work_model.least_seconds(work["round"], work["peaks"])
    return 100.0 * least / tr["window_s"]
