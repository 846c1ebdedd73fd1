"""Device self-time of one class of ops (``sort``, ``matmul``; see
``harness/trace_reduce.py CATEGORIES``) per traced round, in ms."""


def read(obs, args):
    tr, rounds = obs.get("trace"), obs["host"].get("traced_rounds")
    if not tr or not rounds:
        return None
    seconds = tr["category_s"].get(args["class"])
    if not seconds:
        return None
    return seconds * 1e3 / rounds
