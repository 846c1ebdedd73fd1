"""Device scopes: the boosting round's ops named by layer.

A TPU trace names an ``XLA Ops`` event by its HLO text without
metadata (``%fusion.211 = f32[17,64,64,2]... fusion(...)``): the
compiler's numbering, new with every compile and silent about the
layer. The layer is in the compiled module's ``op_name`` metadata,
which carries every :func:`jax.named_scope` the op was traced under —
and only the program can reach that. So:

- the program names its layers with :func:`scope` / :func:`scoped`
  (metadata only: the optimized HLO is the same program), from ONE
  list, :data:`DEVICE_SCOPES`;
- :func:`op_scopes` reads the table ``{"fusion.211": "grow/hist/build",
  "copy.1297": "grow/partition/payload", ...}`` back from the
  executable a registered entry point last ran; ``python -m
  lightgbm_tpu trace <dir> --xplane <trace-dir>`` (obs/xplane.py) lays
  it over a device trace.

The hazard the list guards against: JAX's persistent compile cache
strips debug info from its key, so a warm cache hands back the
executable of whoever wrote the entry, with the WRITER's metadata —
no scopes (an entry from before this module) or old ones (after a
rename). :func:`op_scopes` therefore refuses (``None`` and one log
line, never a wrong table) an executable that carries no scope or one
this list does not declare.
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Dict, Optional, Sequence, Tuple

__all__ = ["DEVICE_SCOPES", "SCOPED_ENTRIES", "ScopeTable", "scope",
           "scoped", "scope_of_op_name", "scopes_from_hlo_text",
           "op_scopes", "tables_of_run", "write_op_scopes"]

#: every device scope the program opens, innermost wins. ``boost/*``:
#: the phases of one fused iteration (models/gbdt.py
#: ``_fused_iter_step``). ``grow/*``: the grower's layers (ops/grow.py,
#: ops/histogram.py, ops/split.py) — the partition's parts (the
#: per-chunk go-left decision and counts, the (side, position) key sort
#: with the per-row columns it carries, the row gather and word writes
#: that apply it, the (g, h) payload's slices and writes), histogram
#: build and sibling
#: subtraction, the split scan, and ``grow/fixed``: what a split costs
#: whatever its rows (tree and leaf bookkeeping, masks, bounds). What a
#: tree costs ONCE, over every row and whatever its splits:
#: ``grow/setup`` (before the first split: the weighted (g, h) payload,
#: the bin words packed and padded into the ping-pong buffers, the
#: root's sums and stored best split; the root's histogram and scan
#: inside it keep their own scopes) and ``grow/row_leaf`` (after the
#: last: every row routed through the finished tree, the quantized
#: leaf outputs renewed from that). Under a
#: mesh, the two collective layers: ``grow/hist/allreduce`` (every
#: histogram reduction: parallel/comms.py) and ``grow/sums/allreduce``
#: (root and leaf sums, counts, SplitInfo combines: bytes, not KB).
#: What a ranking job with a validation set adds, each a program of the
#: eager path: ``boost/gradients/lambdarank`` (ranking.py
#: ``_lambdarank_grads``: the pairwise pass over padded query blocks),
#: ``valid/score_update`` (models/gbdt.py ``_tree_values_binned``: one
#: tree routed over a resident binned table) and ``metric/eval``
#: (``GBDT.eval_metrics``: ranking.py ``_ndcg_at``, one program for every
#: ``eval_at``, and the other metrics' own, op-by-op programs).
DEVICE_SCOPES: Tuple[str, ...] = (
    "boost/gradients",
    "boost/gradients/lambdarank",
    "boost/grow",
    "boost/score_update",
    "boost/tree_pack",
    "grow/partition/route",
    "grow/partition/key_sort",
    "grow/partition/gather",
    "grow/partition/payload",
    "grow/hist/build",
    "grow/hist/subtract",
    "grow/hist/allreduce",
    "grow/sums/allreduce",
    "grow/split_scan",
    "grow/fixed",
    "grow/setup",
    "grow/row_leaf",
    "valid/score_update",
    "metric/eval",
)

#: the registered entry points whose programs carry these scopes (a
#: program-owned capture writes a table for each that has run)
SCOPED_ENTRIES: Tuple[str, ...] = ("gbdt/fused_iter", "gbdt/fused_scan",
                                   "ops/grow_tree", "parallel/dp_grow",
                                   "ranking/lambdarank_grads",
                                   "gbdt/tree_values_binned",
                                   "ranking/ndcg")

_SCOPE_SEGS = tuple(tuple(s.split("/")) for s in DEVICE_SCOPES)
_ROOTS = frozenset(segs[0] for segs in _SCOPE_SEGS)


def scope(name: str):
    """``jax.named_scope(name)`` for a declared scope. Trace-time only
    (never in a round's dispatch path)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"device scope {name!r} is not declared in "
                         "obs/scopes.py DEVICE_SCOPES")
    import jax
    return jax.named_scope(name)


def scoped(name: str) -> Callable:
    """Decorator form of :func:`scope` for whole traced functions."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def _scopes_on(op_name: str) -> Tuple[list, bool]:
    """``(the declared scopes on the path, outermost first; every scope
    declared?)`` of one ``op_name`` path. A scope starts at a segment
    that is a declared root (``boost``, ``grow``); what follows must
    spell a declared scope, or the path carries an undeclared one."""
    segs = op_name.split("/")
    found, ok, i = [], True, 0
    while i < len(segs):
        if segs[i] not in _ROOTS:
            i += 1
            continue
        match = None
        for cand in _SCOPE_SEGS:
            if tuple(segs[i:i + len(cand)]) == cand \
                    and (match is None or len(cand) > len(match)):
                match = cand
        if match is None:
            ok = False
            i += 1
        else:
            found.append("/".join(match))
            i += len(match)
    return found, ok


def scope_of_op_name(op_name: str) -> Tuple[Optional[str], bool]:
    """``(innermost declared scope or None, every scope declared?)``
    of one ``op_name`` path (``jit(step)/boost/grow/while/body/grow/
    partition/key_sort/sort``)."""
    found, ok = _scopes_on(op_name)
    return (found[-1] if found else None), ok


def _own_name(op_name: str) -> str:
    """What the op was called where it was made. A ``shard_map`` body's
    ops are named relative to the body, and the compiler, inlining the
    body, writes the call's own name before each
    (``jit(fn)/shard_map/boost/grow/...``; before an op the body left
    nameless, its instruction's: ``jit(fn)/shard_map/compare.718``): what
    follows the last ``shard_map`` is the op's own."""
    segs = op_name.split("/")
    if "shard_map" in segs:
        at = len(segs) - 1 - segs[::-1].index("shard_map")
        return "/".join(segs[at + 1:])
    return op_name


class ScopeTable(dict):
    """``{op: scope}``; ``derived`` holds the ops whose scope is not
    their own ``op_name``'s but was derived (:func:`scopes_from_hlo_text`);
    ``missing`` the declared scopes the program's CURRENT lowering opens
    and no op of the executable carries: a compile cache answered with an
    executable written before those scopes were (or the compiler folded a
    small one away: a warning, not a refusal); ``module`` the
    executable's module name (``jit_grow_tree_impl``)."""
    derived: frozenset = frozenset()
    missing: Tuple[str, ...] = ()
    #: the HLO module's name: what a trace's module line calls the program
    module: Optional[str] = None


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
#: a name location of a lowering's debug info:
#: ``loc("jit(f)/grow/setup/pad"(#loc7))``
_LOC_NAME_RE = re.compile(r'loc\("([^"]*)"\(')
_MODULE_RE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALLED_RE = re.compile(
    r"\b(?:body|condition|calls|to_apply)=%([\w.\-]+)")
_INDEX_RE = re.compile(r"\bindex=(\d+)")
#: opcodes that only move or re-view a value: without metadata of their
#: own they belong to whatever produced the value
_MOVERS = frozenset((
    "copy", "copy-start", "copy-done", "bitcast", "reshape", "transpose",
    "slice", "slice-start", "slice-done", "pad", "get-tuple-element"))


def _balanced(text: str, at: int) -> int:
    """Index just past the parenthesis group opening at ``text[at]``."""
    depth = 0
    for i in range(at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse_hlo(text: str):
    """``(insts, roots, callers)`` of an HLO text dump:
    ``insts[name] = (computation, opcode, [operands], called, index,
    op_name)``, ``roots[computation] = name``, ``callers[computation] =
    name of the instruction that calls it``."""
    insts, roots, callers = {}, {}, {}
    comp = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if line[0] not in " \t":
            # a computation's header (``%name (...) -> ... {``,
            # ``ENTRY %name ...``) or its closing brace
            if stripped.endswith("{"):
                head = stripped.split()
                name = head[1] if head[0] == "ENTRY" else head[0]
                comp = name.lstrip("%")
            continue
        is_root = stripped.startswith("ROOT ")
        body = stripped[5:] if is_root else stripped
        name, sep, rest = body.partition(" = ")
        if not sep:
            continue
        name = name.strip().lstrip("%")
        # skip the result type: a tuple's is a parenthesis group
        at = _balanced(rest, 0) if rest.startswith("(") \
            else rest.find(" ")
        if at < 0:
            continue
        tail = rest[at:].lstrip()
        paren = tail.find("(")
        if paren < 0:
            continue
        opcode = tail[:paren]
        close = _balanced(tail, paren)
        operands = _OPERAND_RE.findall(tail[paren:close])
        attrs = tail[close:]
        om = _OP_NAME_RE.search(attrs)
        im = _INDEX_RE.search(attrs)
        called = _CALLED_RE.findall(attrs)
        insts[name] = (comp, opcode, operands, called,
                       int(im.group(1)) if im else None,
                       om.group(1) if om else None)
        if is_root:
            roots[comp] = name
        for c in called:
            callers[c] = name
    return insts, roots, callers


def scopes_from_hlo_text(text: str, derive: bool = True,
                         lowering: Optional[str] = None
                         ) -> Optional[ScopeTable]:
    """The op -> scope table of one optimized HLO module's text.
    ``lowering``: the same program's lowered module WITH debug info
    (``lowered.as_text(debug_info=True)``), whose name locations carry
    the scopes the source opens now; those of them that no ``op_name``
    of ``text`` carries are the table's ``missing``, with one log line.

    Direct: every instruction (of every computation, fused ones
    included) whose own ``op_name`` lies under a declared scope.
    ``None`` when no op carries a scope, or when one carries an
    undeclared scope.

    Derived (``derive``; listed in the table's ``derived``): the
    compiler leaves what it inserts itself without metadata — layout
    copies, async copy pairs, fusions whose root lost it — and on the
    chip that is where most of a round can be (the float32 payload's
    relayout copy). Such an op takes, in this order: a fusion, the scope
    of its fused computation's root, else the commonest scope inside it;
    an op that only moves a value (``copy``, ``copy-start/done``,
    ``bitcast``, ``slice``, ...), the scope of what produced the value,
    followed through tuples and out of ``while`` loops to the body's
    root operand; anything else the compiler made (no ``op_name``, or a
    path-less one: ``reduce_window_sum``), the commonest scope of what
    produced its operands (each looked up the same way: a scan the
    tracer named without its scope path belongs with the values it
    reads); else the scope of the instruction that calls its
    computation (a loop body's op -> the loop's scope); and what of the
    compiler's is still bare then (the entry computation's, which
    nothing calls: a parameter's prefetch, a constant's broadcast), the
    commonest scope of what consumes it. An op that carries the
    tracer's path (``jit(f)/neg``) and no declared scope was traced
    outside every ``with scope``: neither neighbour rule touches it, so
    a body of work nobody named stays ``(unscoped)`` in a report. (Inside
    a ``shard_map`` body the tracer's own bare op and the compiler's are
    both path-less, :func:`_own_name`: there the rules cannot tell them
    apart and take both.)"""
    insts, roots, callers = _parse_hlo(text)
    direct: Dict[str, str] = {}
    carried = set()
    for name, inst in insts.items():
        if inst[5] is None:
            continue
        on_path, ok = _scopes_on(inst[5])
        carried.update(on_path)
        found = on_path[-1] if on_path else None
        if not ok:
            from ..utils.log import log_warning
            log_warning(f"op_scopes: op {name} carries a scope "
                        f"DEVICE_SCOPES does not declare ({inst[5]!r}): "
                        "an executable from a compile cache another "
                        "version wrote; no table")
            return None
        if found is not None:
            direct[name] = found
    if not direct:
        from ..utils.log import log_warning
        log_warning("op_scopes: the executable carries no device scope "
                    "(a compile-cache entry from before the scopes, or "
                    "metadata stripped); no table")
        return None
    table = ScopeTable(direct)
    mm = _MODULE_RE.search(text)
    table.module = mm.group(1) if mm else None
    if lowering is not None:
        opened = set()
        for path in set(_LOC_NAME_RE.findall(lowering)):
            opened.update(_scopes_on(path)[0])
        table.missing = tuple(sc for sc in DEVICE_SCOPES
                              if sc in opened and sc not in carried)
        if table.missing:
            from ..utils.log import log_warning
            log_warning("op_scopes: the program opens "
                        f"{', '.join(table.missing)} and no op of the "
                        "executable carries them: a compile cache "
                        "answered with an executable written before "
                        "these scopes were (read it from a fresh "
                        "JAX_COMPILATION_CACHE_DIR once), or the "
                        "compiler folded them away")
    if not derive:
        return table

    inside: Dict[str, Dict[str, int]] = {}      # computation -> scope counts
    for name, sc in direct.items():
        counts = inside.setdefault(insts[name][0], {})
        counts[sc] = counts.get(sc, 0) + 1

    def produced_by(name: str, seen: set) -> Optional[str]:
        """Scope of what produced the value ``name`` holds."""
        for _ in range(32):
            if name in seen or name not in insts:
                return None
            seen.add(name)
            comp, opcode, operands, called, index, _ = insts[name]
            # a tuple element's own op_name is only its loop's: the
            # value is looked THROUGH it before that is taken
            if name in direct and opcode != "get-tuple-element":
                return direct[name]
            if opcode == "fusion" and called:
                return of_fusion(called[0])
            if opcode == "get-tuple-element" and operands \
                    and index is not None:
                src = insts.get(operands[0])
                if src is not None and src[1] == "while" and src[3]:
                    # out of a loop: the body's root operand
                    body = [c for c in src[3] if roots.get(c)
                            and insts[roots[c]][1] == "tuple"]
                    if body:
                        root_ops = insts[roots[body[0]]][2]
                        if index < len(root_ops):
                            name = root_ops[index]
                            continue
                if src is not None and src[1] == "tuple" \
                        and index < len(src[2]):
                    name = src[2][index]
                    continue
                if src is not None and src[1] == "parameter" \
                        and roots.get(comp) \
                        and insts[roots[comp]][1] == "tuple" \
                        and index < len(insts[roots[comp]][2]):
                    # loop-carried: the previous iteration's producer
                    name = insts[roots[comp]][2][index]
                    continue
                return direct.get(name)
            if opcode in _MOVERS and operands:
                name = operands[0]
                continue
            return None
        return None

    def of_fusion(comp: str) -> Optional[str]:
        root = roots.get(comp)
        if root in direct:
            return direct[root]
        counts = inside.get(comp)
        if counts:
            return max(sorted(counts), key=lambda sc: counts[sc])
        return None

    def of_caller(comp: Optional[str]) -> Optional[str]:
        for _ in range(16):
            caller = callers.get(comp)
            if caller is None:
                return None
            if caller in direct:
                return direct[caller]
            comp = insts[caller][0]
        return None

    def commonest(scopes_seen) -> Optional[str]:
        counts: Dict[str, int] = {}
        for sc in scopes_seen:
            if sc is not None:
                counts[sc] = counts.get(sc, 0) + 1
        return max(sorted(counts), key=lambda sc: counts[sc]) \
            if counts else None

    def of_operands(operands) -> Optional[str]:
        return commonest(table.get(op) or produced_by(op, set())
                         for op in operands)

    bare = []
    for name, (comp, opcode, operands, called, _, op_name) in insts.items():
        if name in direct or opcode in ("parameter", "constant"):
            continue
        # the tracer's own op, traced outside every ``with scope``
        # (``jit(f)/neg``): a layer nobody named, which no neighbour's
        # scope may claim
        traced = op_name is not None and "/" in _own_name(op_name)
        sc = None
        if opcode == "fusion" and called:
            sc = of_fusion(called[0])
        elif opcode in _MOVERS:
            sc = produced_by(name, set())
        if sc is None and not traced:
            # text order: an operand's own derivation is in the table
            sc = of_operands(operands)
        if sc is None:
            sc = of_caller(comp)
        if sc is not None:
            table[name] = sc
        elif not traced:
            bare.append(name)
    if bare:
        users: Dict[str, list] = {}
        for name, inst in insts.items():
            for op in inst[2]:
                users.setdefault(op, []).append(name)

        def of_users(name: str, depth: int = 0) -> Optional[str]:
            # a tuple only bundles: what consumes IT consumes the value
            return commonest(
                table.get(u) or (of_users(u, depth + 1) if depth < 4
                                 and insts[u][1] == "tuple" else None)
                for u in users.get(name, ()))

        for name in reversed(bare):     # a consumer's own comes first
            sc = of_users(name)
            if sc is not None:
                table[name] = sc
    table.derived = frozenset(set(table) - set(direct))
    return table


def op_scopes(entry: str) -> Optional[ScopeTable]:
    """``{op: scope}`` for the executable the registered entry point
    ``entry`` (``"gbdt/fused_iter"``) last compiled, from the compiled
    module's ``op_name`` metadata (and, for the ops the compiler left
    without any, derived: the table's ``derived``). Built on request —
    a re-lowering at the last call's avals and a compile the persistent
    cache answers where it is on — never in a round's path. ``None``
    where no live entry of that name has run, where the executable cannot
    be reached, or where it carries no scope or an undeclared one. A
    declared scope the re-lowering opens and the executable lacks is the
    table's ``missing`` (a stale cache entry: see :class:`ScopeTable`)."""
    from .jit_tracker import live_entries
    for fn in reversed(live_entries(entry)):
        avals = getattr(fn, "last_avals", None)
        if avals is None:
            continue
        args, kwargs = avals
        try:
            lowered = fn.unwrapped.lower(*args, **kwargs)
            text = lowered.compile().as_text()
            lowering = lowered.as_text(debug_info=True)
        except Exception as e:
            from ..utils.log import log_warning
            log_warning(f"op_scopes: cannot reach {entry!r}'s "
                        f"executable ({type(e).__name__}: {e})")
            return None
        return scopes_from_hlo_text(text, lowering=lowering)
    return None


def tables_of_run(entries: Sequence[str] = SCOPED_ENTRIES
                  ) -> Dict[str, Optional[ScopeTable]]:
    """``{entry: op_scopes(entry)}`` for each of ``entries`` that has
    run in this process (``None`` where its table is refused)."""
    from .jit_tracker import live_entries
    return {entry: op_scopes(entry) for entry in entries
            if any(getattr(fn, "last_avals", None) is not None
                   for fn in live_entries(entry))}


def write_op_scopes(directory: str,
                    entries: Sequence[str] = SCOPED_ENTRIES) -> Optional[str]:
    """Write ``op_scopes.json`` (``{entry: {"ops": {op: scope},
    "derived": [op, ...], "missing": [scope, ...], "module": name}}``)
    beside a program-owned capture, for each of ``entries`` that has run
    and has a table. Returns the path, or ``None`` when none had one."""
    import json
    import os
    doc = {}
    for entry, table in tables_of_run(entries).items():
        if table:
            doc[entry] = {"ops": dict(table),
                          "derived": sorted(table.derived),
                          "missing": list(table.missing),
                          "module": table.module}
    if not doc:
        return None
    path = os.path.join(directory, "op_scopes.json")
    os.makedirs(directory, exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=0, sort_keys=True)
    return path
