"""Device time of ONE program's ops by the program's own scopes, a round.

The ``train_eval`` drivers lay the op -> scope table the program gives
for its grower (``lightgbm_tpu.obs.op_scopes``) over the ops inside that
program's executions in the traced rounds and put the self time of each
scope, in ms a round, into ``obs["programs"]["scope_ms_per_round"]
[<program>][<scope>]``. ``args``:

- ``program``: the program's name on the trace's module line
  (``jit_grow_tree_impl``);
- ``scopes``: the scopes whose time is summed. One that the program's
  table does not hold counts 0 (the compiler left no op of it) as long
  as the table holds another of the list; ``(unscoped)``, the driver's
  name for the ops no scope reaches, is there whenever the table is (0
  where every op has a scope);
- ``per_rows`` (optional) ``{"span": ..., "attr": ...}``: divide by the
  rows the program says that work streamed, the attribute ``attr`` summed
  over the job's host spans named ``span`` (found as ``program_span``
  finds a job's spans), which must number exactly
  ``host.traced_rounds``: the result is then ns a row over the traced
  rounds.

``None``, never a guess: where the observation or the program is absent
(an untraced run, a driver that keeps no scopes); where none of the named
scopes is in the program's table (the parent of the PR that added them);
where a span lacks the attribute (the parent of the PR that stamped it),
the spans do not number the traced rounds, or they count no row.
"""

import os

from harness.manifest import load_module

UNSCOPED = "(unscoped)"
program_span = load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "program_span.py"), "perfbench_reader_program_span")


def _rows(obs, span, attr):
    """The attribute summed over the last job's spans ``span``."""
    spans = program_span._spans()
    root = program_span._last(spans or [], "train/job")
    rounds = obs.get("host", {}).get("traced_rounds")
    if not root or not rounds:
        return None
    named = [s for s in spans if s.get("name") == span
             and s.get("trace_id") == root["trace_id"]]
    if len(named) != rounds:
        return None
    values = [(s.get("attrs") or {}).get(attr) for s in named]
    if any(v is None for v in values):
        return None
    return sum(values)


def read(obs, args):
    table = ((obs.get("programs") or {}).get("scope_ms_per_round")
             or {}).get(args["program"])
    if not table:
        return None
    known = [sc for sc in args["scopes"] if sc in table or sc == UNSCOPED]
    if not known:
        return None
    ms = sum(table.get(sc, 0.0) for sc in known)
    if "per_rows" not in args:
        return ms
    rows = _rows(obs, args["per_rows"]["span"], args["per_rows"]["attr"])
    if not rows:
        return None
    return ms * 1e6 * obs["host"]["traced_rounds"] / rows
