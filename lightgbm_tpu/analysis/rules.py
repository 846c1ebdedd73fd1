"""The tpulint rule set (TPL001-TPL009). Pure stdlib.

Each rule is a class with a stable ``id``, a one-line ``title``, and a
``run(ctx)`` generator yielding :class:`Finding`. Rules see the whole
:class:`~lightgbm_tpu.analysis.callgraph.CallGraph` (jit-reachability,
call records, hot markers) plus the raw ASTs, and are scoped to the
hot-path files by the engine. The statement-level rules TPL001-TPL006
live here; the CFG/dataflow rules TPL007-TPL009 live in
:mod:`~lightgbm_tpu.analysis.rules_flow` and are re-registered into
``ALL_RULES`` below. docs/STATIC_ANALYSIS.md documents each rule's
hazard, an example, the fix, and how to baseline.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .astscan import ModuleScan, dotted_of
from .callgraph import CallGraph, CallRecord, Key

__all__ = ["Finding", "Rule", "ALL_RULES", "IR_RULES", "rule_by_id",
           "LintContext"]

_LAX_LOOPS = {"fori_loop", "scan", "while_loop"}

#: host-synchronizing calls (dotted externals)
_SYNC_DOTTED = {"numpy.asarray", "numpy.array", "jax.device_get"}
#: host-synchronizing method calls
_SYNC_METHODS = {"item", "block_until_ready"}


@dataclass
class Finding:
    rule: str
    relpath: str
    lineno: int
    col: int
    func: str              # enclosing qualname or "<module>"
    symbol: str            # what was matched (feeds the stable id)
    message: str
    fid: str = ""          # assigned by the engine (stable id)

    def sort_key(self):
        return (self.relpath, self.lineno, self.col, self.rule)


@dataclass
class LintContext:
    graph: CallGraph
    scans: Dict[str, ModuleScan]
    scope: Set[str]                      # relpaths the rules run over
    root: str = ""                       # package dir (doc checks only)

    def scoped_scans(self) -> Iterator[ModuleScan]:
        for rel in sorted(self.scope):
            if rel in self.scans:
                yield self.scans[rel]

    def scope_of_node(self, scan: ModuleScan, lineno: int) -> str:
        """Innermost enclosing function qualname for a line."""
        best = "<module>"
        best_span = None
        for qual, info in scan.funcs.items():
            if info.lineno <= lineno <= info.end_lineno:
                span = info.end_lineno - info.lineno
                if best_span is None or span <= best_span:
                    best, best_span = qual, span
        return best

    def is_traced(self, key: Optional[Key]) -> bool:
        return key is not None and key in self.graph.jit_reachable

    def is_hot(self, key: Optional[Key]) -> bool:
        if key is None:
            return False
        info = self.graph.funcs.get(key)
        if info is None:
            return False
        while info is not None:
            if info.is_hot:
                return True
            info = self.graph.funcs.get(
                (info.relpath, info.parent_qual)) \
                if info.parent_qual else None
        return False


class Rule:
    id = "TPL000"
    title = "abstract rule"

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        raise NotImplementedError

    def _finding(self, ctx: LintContext, relpath: str, node,
                 symbol: str, message: str,
                 func: Optional[str] = None) -> Finding:
        scan = ctx.scans[relpath]
        qual = func if func is not None \
            else ctx.scope_of_node(scan, node.lineno)
        return Finding(rule=self.id, relpath=relpath,
                       lineno=node.lineno, col=node.col_offset,
                       func=qual, symbol=symbol, message=message)


# ---------------------------------------------------------------------
class EagerLaxLoop(Rule):
    """TPL001: a ``lax.fori_loop`` / ``lax.scan`` / ``lax.while_loop``
    whose enclosing function is not jit-reachable dispatches op-by-op:
    one device launch per loop-body op instead of one compiled
    program."""

    id = "TPL001"
    title = "eager lax loop outside a jit-reachable function"

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scope, facts in ctx.graph.facts.items():
            for rec in facts.records:
                if rec.relpath not in ctx.scope:
                    continue
                name = None
                if rec.kind == "ext" and rec.dotted:
                    base = rec.dotted.rsplit(".", 1)[-1]
                    root = rec.dotted.split(".", 1)[0]
                    if base in _LAX_LOOPS and root in ("jax", "lax"):
                        name = base
                elif rec.kind == "method" and rec.attr in _LAX_LOOPS:
                    name = rec.attr
                if name is None:
                    continue
                if ctx.is_traced(scope):
                    continue
                func = scope[1] if scope else "<module>"
                yield self._finding(
                    ctx, rec.relpath, rec.node, f"lax.{name}",
                    f"lax.{name} in {func}() which is not jit-reachable "
                    "(no proof every entry goes through a jax.jit/"
                    "pjit/shard_map wrapper): this dispatches eagerly, "
                    "op-by-op — one device launch per loop-body op. Put "
                    "it behind a jitted entry point (and register_jit "
                    "it) or delete dead code.", func=func)


# ---------------------------------------------------------------------
class HostSync(Rule):
    """TPL002: host-device synchronization inside jit-reachable or
    per-iteration hot code (``# tpulint: hot``-marked drivers)."""

    id = "TPL002"
    title = "host sync in jit-reachable or hot per-iteration code"

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scope, facts in ctx.graph.facts.items():
            if scope is None:
                continue
            traced = ctx.is_traced(scope)
            hot = ctx.is_hot(scope)
            if not (traced or hot):
                continue
            where = "jit-reachable (traced)" if traced else \
                "per-iteration hot"
            for rec in facts.records:
                if rec.relpath not in ctx.scope:
                    continue
                sym = self._sync_symbol(rec, facts, traced)
                if sym is None:
                    continue
                yield self._finding(
                    ctx, rec.relpath, rec.node, sym,
                    f"{sym} in {scope[1]}() which is {where} code: "
                    "this forces a host-device round trip "
                    "(or a trace-time concretization error) and "
                    "serializes the device pipeline. Keep data on "
                    "device, or move the fetch onto the async "
                    "one-iteration-late queue "
                    "(copy_to_host_async + deferred read).",
                    func=scope[1])

    def _sync_symbol(self, rec: CallRecord, facts,
                     traced: bool) -> Optional[str]:
        if rec.kind == "ext" and rec.dotted in _SYNC_DOTTED:
            short = {"numpy.asarray": "np.asarray",
                     "numpy.array": "np.array",
                     "jax.device_get": "jax.device_get"}[rec.dotted]
            if traced and not self._touches_param(rec, facts):
                return None     # trace-time constant table building
            return short
        if rec.kind == "method" and rec.attr in _SYNC_METHODS:
            return f".{rec.attr}()"
        if traced and rec.kind == "builtin" \
                and rec.dotted in ("float", "int"):
            if rec.node.args and not isinstance(rec.node.args[0],
                                                ast.Constant) \
                    and self._touches_param(rec, facts):
                return f"{rec.dotted}()"
        return None

    @staticmethod
    def _touches_param(rec: CallRecord, facts) -> bool:
        """Does the call's argument expression reference a function
        parameter (i.e. likely a tracer, not a trace-time constant)?"""
        for arg in list(rec.node.args) \
                + [kw.value for kw in rec.node.keywords]:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Name) \
                        and sub.id in facts.param_names:
                    return True
        return False


# ---------------------------------------------------------------------
class RecompileHazard(Rule):
    """TPL003: recompile storms — a ``jax.jit`` constructed inside a
    loop (a fresh wrapper = a fresh compile cache), or data-derived
    Python scalars/tuples flowing into ``static_argnums`` /
    ``static_argnames`` (every new value is a new trace signature)."""

    id = "TPL003"
    title = "recompile hazard (jit-in-loop / data-derived static arg)"

    _DERIVERS = {"int", "float", "bool", "tuple", "list"}
    _DERIVER_METHODS = {"item", "tolist"}

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scope, facts in ctx.graph.facts.items():
            for rec in facts.records:
                if rec.relpath not in ctx.scope:
                    continue
                yield from self._jit_in_loop(ctx, rec, scope)
                yield from self._static_args(ctx, rec)

    def _jit_in_loop(self, ctx, rec: CallRecord, scope):
        from .astscan import jit_wrap_kind
        if rec.kind != "ext" or not rec.in_loop:
            return
        if jit_wrap_kind(rec.dotted) is None:
            return
        yield self._finding(
            ctx, rec.relpath, rec.node, "jit-in-loop",
            f"{rec.dotted} constructed inside a loop: every "
            "iteration builds a NEW wrapper with an empty compile "
            "cache, so every call recompiles (the telemetry "
            "`recompiles` counter spikes — docs/OBSERVABILITY.md). "
            "Hoist the jit to module/init scope or memoize it.")

    def _static_args(self, ctx, rec: CallRecord):
        if rec.kind != "wrapper" or rec.wrap is None:
            return
        wrap = rec.wrap
        static_pos = set(wrap.static_argnums or ())
        names = ()
        if wrap.static_argnames and rec.target is not None:
            info = ctx.graph.funcs.get(rec.target)
            if info is not None:
                names = wrap.static_argnames
                for nm in names:
                    if nm in info.params:
                        static_pos.add(info.params.index(nm))
        for i, arg in enumerate(rec.node.args):
            if i in static_pos and self._data_derived(arg):
                yield self._static_finding(ctx, rec, arg, f"arg{i}")
        for kw in rec.node.keywords:
            if kw.arg in (wrap.static_argnames or ()) \
                    and self._data_derived(kw.value):
                yield self._static_finding(ctx, rec, kw.value, kw.arg)

    def _static_finding(self, ctx, rec, node, which):
        return self._finding(
            ctx, rec.relpath, node, f"static-arg:{which}",
            f"static argument {which} is derived from data "
            "(int()/float()/tuple()/.item()/.tolist() of a runtime "
            "value): every distinct value is a distinct trace "
            "signature, so this recompiles per value — the recompile "
            "storm class (docs/OBSERVABILITY.md). Pass it as a traced "
            "array argument, or derive statics from shapes/config "
            "only.")

    def _data_derived(self, node) -> bool:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            if isinstance(sub.func, ast.Name) \
                    and sub.func.id in self._DERIVERS:
                if sub.args and not all(
                        isinstance(a, ast.Constant) for a in sub.args):
                    return True
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in self._DERIVER_METHODS:
                return True
        return False


# ---------------------------------------------------------------------
class DonationViolation(Rule):
    """TPL004: a buffer passed at a ``donate_argnums`` position is
    dead after the call — XLA reuses its memory. Reading it again
    raises "Array has been deleted" (or silently reads garbage on
    backends that skip the check)."""

    id = "TPL004"
    title = "use of a buffer after donate_argnums donation"

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scan in ctx.scoped_scans():
            for qual, info in scan.funcs.items():
                yield from self._check_function(ctx, scan, qual, info)

    def _check_function(self, ctx, scan, qual, info):
        facts = ctx.graph.facts.get(info.key)
        if facts is None:
            return
        donations: List[Tuple[str, int, int]] = []  # (name, call line)
        for rec in facts.records:
            if rec.kind != "wrapper" or rec.wrap is None \
                    or not rec.wrap.donate_argnums:
                continue
            for pos in rec.wrap.donate_argnums:
                if pos < len(rec.node.args):
                    nm = self._name_of(rec.node.args[pos])
                    if nm:
                        donations.append((nm, rec.node.lineno,
                                          rec.node.end_lineno or
                                          rec.node.lineno))
        if not donations:
            return
        for nm, lineno, end in donations:
            # a Store on the call's own line is the idiomatic rebind
            # (`score = fused(score, ...)`) — it ends the liveness
            # window immediately. Take the EARLIEST such store by line
            # (ast.walk is breadth-first, so the first hit may be a
            # later but shallower statement).
            end_of_life = min(
                (sub.lineno for sub in ast.walk(info.node)
                 if self._name_of(sub) == nm
                 and isinstance(getattr(sub, "ctx", None), ast.Store)
                 and sub.lineno >= lineno),
                default=None)
            for sub in ast.walk(info.node):
                if self._name_of(sub) == nm \
                        and isinstance(getattr(sub, "ctx", None),
                                       ast.Load) \
                        and sub.lineno > end \
                        and (end_of_life is None
                             or sub.lineno < end_of_life):
                    yield self._finding(
                        ctx, scan.relpath, sub, f"donated:{nm}",
                        f"`{nm}` is read after being donated "
                        f"(donate_argnums call at line {lineno}): the "
                        "buffer was handed to XLA for reuse — this "
                        "read raises \"Array has been deleted\" on "
                        "TPU/GPU. Rebind the result before any "
                        "further use.", func=qual)
                    break

    @staticmethod
    def _name_of(node) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            return f"self.{node.attr}"
        if isinstance(node, ast.Attribute):
            return None
        return None


# ---------------------------------------------------------------------
class UnorderedIteration(Rule):
    """TPL005: iteration over a ``set`` (or hash-ordered view) where the
    order feeds trace order or collective order. Set order varies with
    PYTHONHASHSEED and across processes — under SPMD each rank would
    trace a different program / join collectives in a different order
    (silent divergence or deadlock)."""

    id = "TPL005"
    title = "order-unstable set/dict iteration feeding trace order"

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scan in ctx.scoped_scans():
            in_parallel = scan.relpath.startswith("parallel/")
            for qual, info in scan.funcs.items():
                key = info.key
                relevant = (ctx.is_traced(key) or ctx.is_hot(key)
                            or in_parallel
                            or ctx.graph.dispatches_jax(key))
                if not relevant:
                    continue
                yield from self._check_function(ctx, scan, qual, info)

    def _set_assigns(self, fn_node) -> Dict[str, List[Tuple[int, bool]]]:
        """Per-variable assignment history: (lineno, assigned-a-set).
        Lookups are by line so ``s = {...}; use(s); s = sorted(s)``
        stays precise in straight-line code."""
        out: Dict[str, List[Tuple[int, bool]]] = {}
        for sub in ast.walk(fn_node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name):
                out.setdefault(sub.targets[0].id, []).append(
                    (sub.lineno, self._is_set_expr(sub.value)))
        for hist in out.values():
            hist.sort()
        return out

    @staticmethod
    def _is_set_expr(node) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("set", "frozenset"):
            return True
        if isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.BitAnd, ast.BitOr, ast.Sub)):
            return UnorderedIteration._is_set_expr(node.left) \
                or UnorderedIteration._is_set_expr(node.right)
        return False

    def _check_function(self, ctx, scan, qual, info):
        assigns = self._set_assigns(info.node)

        def is_set(node):
            if self._is_set_expr(node):
                return True
            if not isinstance(node, ast.Name):
                return False
            last = None
            for lineno, was_set in assigns.get(node.id, ()):
                if lineno >= node.lineno:
                    break
                last = was_set
            return bool(last)

        for sub in ast.walk(info.node):
            it = None
            how = None
            if isinstance(sub, (ast.For, ast.AsyncFor)):
                it, how, node = sub.iter, "for-loop", sub.iter
            elif isinstance(sub, (ast.ListComp, ast.SetComp,
                                  ast.GeneratorExp, ast.DictComp)):
                it, how, node = sub.generators[0].iter, \
                    "comprehension", sub.generators[0].iter
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Name) \
                    and sub.func.id == "sorted" and sub.args \
                    and is_set(sub.args[0]) \
                    and any(kw.arg == "key" for kw in sub.keywords):
                nm = self._describe(sub.args[0])
                yield self._finding(
                    ctx, scan.relpath, sub, f"set-sorted-key:{nm}",
                    f"sorted({nm}, key=...) over a set: the sort is "
                    "stable, so elements with EQUAL keys keep the "
                    "set's hash order — which varies per process "
                    "(PYTHONHASHSEED) and can diverge across SPMD "
                    "ranks. Build a list (deterministic order) before "
                    "sorting, or sort without ties.", func=qual)
                continue
            if it is None or not is_set(it):
                continue
            yield self._finding(
                ctx, scan.relpath, node,
                f"set-iteration:{self._describe(it)}",
                f"{how} over a set ({self._describe(it)}): set order "
                "varies with PYTHONHASHSEED and across processes. If "
                "it feeds trace order or collective order, SPMD ranks "
                "diverge silently (parallel/spmd.py turns that into a "
                "deadlock-or-error). Iterate sorted(...) or a list "
                "instead.", func=qual)

    @staticmethod
    def _describe(node) -> str:
        d = dotted_of(node)
        if d:
            return d
        return node.__class__.__name__.lower()


# ---------------------------------------------------------------------
class LockAcrossDispatch(Rule):
    """TPL006: a ``threading`` lock held across a jax dispatch in the
    observability or resilience layer. Dispatch can block on the device
    (or on jax's own internal locks); holding a telemetry lock across
    it turns a metrics read on another thread into a pipeline stall —
    or a deadlock if jax re-enters the instrumented path. In
    ``resilience/`` the same shape is worse: the collective watchdog's
    bookkeeping lock held across a *collective* would hang the exact
    abort path that exists to break hangs (watchdog.py's contract is
    copy-under-lock, sync-outside). ``serve/`` inherits the same
    contract: the micro-batcher's lock held across the compiled
    predict dispatch would stall every submit()/stats() caller behind
    one slow device batch."""

    id = "TPL006"
    title = "lock held across jax dispatch in obs/, resilience/, " \
            "serve/ or pipeline.py"

    _SCOPE_PREFIXES = ("obs/", "resilience/", "serve/", "pipeline")
    _LOCK_CALLS = {"Lock", "RLock", "Condition", "Semaphore"}

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        for scan in ctx.scoped_scans():
            if not scan.relpath.startswith(self._SCOPE_PREFIXES):
                continue
            for qual, info in scan.funcs.items():
                yield from self._check_function(ctx, scan, qual, info)

    def _looks_like_lock(self, node) -> bool:
        d = dotted_of(node)
        if d is None:
            if isinstance(node, ast.Call):
                f = dotted_of(node.func) or ""
                return f.rsplit(".", 1)[-1] in self._LOCK_CALLS
            return False
        last = d.rsplit(".", 1)[-1].lower()
        return "lock" in last or "mutex" in last

    def _check_function(self, ctx, scan, qual, info):
        facts = ctx.graph.facts.get(info.key)
        if facts is None:
            return
        for sub in ast.walk(info.node):
            if not isinstance(sub, (ast.With, ast.AsyncWith)):
                continue
            if not any(self._looks_like_lock(item.context_expr)
                       for item in sub.items):
                continue
            lo = sub.lineno
            hi = getattr(sub, "end_lineno", lo)
            for rec in facts.records:
                if not (lo <= rec.node.lineno <= hi):
                    continue
                if ctx.graph.record_dispatches(rec):
                    what = rec.dotted or (
                        f".{rec.attr}()" if rec.attr else "call")
                    yield self._finding(
                        ctx, scan.relpath, rec.node,
                        f"lock-dispatch:{what}",
                        f"jax dispatch ({what}) while holding a lock "
                        f"(with-block at line {lo}): dispatch can "
                        "block on the device, so every other thread "
                        "touching this lock (telemetry snapshots, "
                        "callbacks) stalls with it — and a re-entrant "
                        "path deadlocks. Copy state under the lock, "
                        "dispatch outside it.", func=qual)
                    break


#: imported at the bottom on purpose: rules_flow/rules_contract
#: subclass Rule/use Finding, so they need this module's upper half to
#: exist first. Import THIS module (or the package) for the full rule
#: set.
from .rules_flow import FLOW_RULES  # noqa: E402
from .rules_contract import CONTRACT_RULES  # noqa: E402

ALL_RULES: List[Rule] = [EagerLaxLoop(), HostSync(), RecompileHazard(),
                         DonationViolation(), UnorderedIteration(),
                         LockAcrossDispatch(), *FLOW_RULES,
                         *CONTRACT_RULES]


# ---------------------------------------------------------------------
# IR-contract rules (TPL011-TPL014): descriptors only. The checks run
# in analysis/ircheck.py under ``lint --ir`` — the ONE path that
# imports jax — by lowering every registered entry point at its
# declared signatures and diffing the IR against committed budgets.
# They are deliberately NOT in ALL_RULES: the default AST pass stays
# jax-free and byte-identical, and the AST fixture-coverage test keeps
# its exact TPL001-TPL010 surface.
# ---------------------------------------------------------------------

class IRRule(Rule):
    """Base for lowered-IR rules. ``run`` never yields — findings come
    from :mod:`~lightgbm_tpu.analysis.ircheck`."""

    ir_only = True

    def run(self, ctx: LintContext) -> Iterator[Finding]:
        return iter(())


class DtypeContractIR(IRRule):
    id = "TPL011"
    title = ("f64 op or weak-type widening in lowered IR "
             "(traced under enable_x64; weak scalar plumbing exempt)")


class CollectiveBudgetIR(IRRule):
    id = "TPL012"
    title = ("collective payload exceeds the committed "
             "tools/ir_budgets.json budget (or has none)")


class DonationHonoredIR(IRRule):
    id = "TPL013"
    title = ("declared donate_argnums shows no input->output aliasing "
             "in the lowered program")


class RecompileSurfaceIR(IRRule):
    id = "TPL014"
    title = ("jit entry point without a declared max_signatures "
             "recompile surface (or declaration below the pow2 serve "
             "bucket ladder)")


IR_RULES: List[Rule] = [DtypeContractIR(), CollectiveBudgetIR(),
                        DonationHonoredIR(), RecompileSurfaceIR()]


def rule_by_id(rid: str) -> Optional[Rule]:
    for r in ALL_RULES + IR_RULES:
        if r.id == rid:
            return r
    return None
