"""Reads one counter of the program's metrics registry
(``lightgbm_tpu.obs.registry``) in the driver's process, summed over
its label sets. ``args``: ``counter``, the family's name.

A counter the program declares (``obs.schemas.METRICS``) but never
bumped reads 0; one it does not declare (the parent of the PR that
added it) reads ``None``.
"""


def read(obs, args):
    name = args["counter"]
    try:
        from lightgbm_tpu.obs.registry import registry
        from lightgbm_tpu.obs.schemas import METRICS
    except Exception:
        return None
    if name not in METRICS:
        return None
    family = registry.snapshot().get(name)
    if not family:
        return 0
    return sum(row.get("value") or 0 for row in family["series"])
