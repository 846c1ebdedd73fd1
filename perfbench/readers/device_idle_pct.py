"""Share of the traced interval in which no op ran on the device."""


def read(obs, args):
    tr = obs.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
