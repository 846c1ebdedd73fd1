"""Programs executed on the device per traced round: events of the
trace's module line over the traced rounds (1 when a round is one fused
program, 6 on the eager path)."""


def read(obs, args):
    tr, rounds = obs.get("trace"), obs["host"].get("traced_rounds")
    if not tr or not rounds or not tr["module_executions"]:
        return None
    return tr["module_executions"] / rounds
