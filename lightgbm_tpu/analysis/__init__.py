"""tpulint: JAX/TPU-aware static analysis for the boosting hot path.

The regression classes that hurt this codebase most are invisible at
runtime until a profile is taken: eager ``lax`` loops dispatching
op-by-op (one device launch per loop-body op instead of one compiled
program), host-device syncs hiding inside per-iteration code, recompile storms
from unstable trace signatures, use-after-donation, and SPMD
collective-order divergence. This package proves the corresponding
invariants at review time, from the source alone:

- :mod:`~lightgbm_tpu.analysis.astscan` parses every module of the
  package (pure ``ast`` — importing this package never imports jax),
- :mod:`~lightgbm_tpu.analysis.callgraph` builds a cross-module call
  graph and computes **jit-reachability**: the set of functions that
  are only ever entered through a ``jax.jit`` / ``pjit`` / ``shard_map``
  wrapper. This replaces the hand-maintained ``KNOWN_JITTED`` allowlist
  the old ``tests/test_hot_path_lint.py`` carried,
- :mod:`~lightgbm_tpu.analysis.cfg` builds per-function control-flow
  graphs and solves guard-pin and lock-held dataflow over them;
  :mod:`~lightgbm_tpu.analysis.dataflow` adds rank taint, the
  thread-side closure, and float64-producer classification,
- :mod:`~lightgbm_tpu.analysis.rules` runs the pluggable rule set
  (statement-level TPL001-TPL006 plus the CFG-based TPL007-TPL010 from
  :mod:`~lightgbm_tpu.analysis.rules_flow`; see
  docs/STATIC_ANALYSIS.md),
- :mod:`~lightgbm_tpu.analysis.baseline` matches findings against the
  checked-in accepted-findings file (tools/tpulint_baseline.txt),
- :mod:`~lightgbm_tpu.analysis.ircheck` (``lint --ir`` only — the one
  lint mode that imports jax, CPU lowering only, never executing)
  lowers every ``register_jit`` entry point at its declared
  signatures and checks the IR contracts TPL011-TPL014: dtype
  contract, collective bytes vs the committed tools/ir_budgets.json,
  donation honored in the lowered program, recompile surface
  declared.

Entry points: ``python -m lightgbm_tpu lint`` (see
:mod:`~lightgbm_tpu.analysis.cli`), :func:`run_lint` for library use,
and ``tests/test_static_analysis.py`` which gates tier-1 on a clean
tree.
"""

from .callgraph import CallGraph, build_callgraph
from .engine import LintResult, default_scope, package_root, run_lint
from .rules import ALL_RULES, IR_RULES, Finding, rule_by_id

__all__ = [
    "run_lint", "LintResult", "build_callgraph", "CallGraph",
    "Finding", "ALL_RULES", "IR_RULES", "rule_by_id", "default_scope",
    "package_root",
]
