"""Reads one value of the run's observations by its dotted path
(``host.construct_s``, ``counters.compiles_in_window``)."""


def read(obs, args):
    at = obs
    for key in args["path"].split("."):
        if not isinstance(at, dict) or at.get(key) is None:
            return None
        at = at[key]
    return at
