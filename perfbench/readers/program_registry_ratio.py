"""The ratio of two counters of the program's metrics registry
(``lightgbm_tpu.obs.registry``), each summed over its label sets, as
``program_registry`` reads one. ``args``: ``numerator``,
``denominator``, ``scale`` (optional; 100 for a percentage).

``None`` where the program declares either counter not (the parent of
the PR that added them) or the denominator never moved.
"""


def read(obs, args):
    try:
        from lightgbm_tpu.obs.registry import registry
        from lightgbm_tpu.obs.schemas import METRICS
    except Exception:
        return None
    snap, sums = registry.snapshot(), []
    for name in (args["numerator"], args["denominator"]):
        if name not in METRICS:
            return None
        family = snap.get(name)
        sums.append(sum(row.get("value") or 0 for row in family["series"])
                    if family else 0)
    if not sums[1]:
        return None
    return float(args.get("scale", 1.0)) * sums[0] / sums[1]
