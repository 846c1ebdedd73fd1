"""IR-contract lint (``python -m lightgbm_tpu lint --ir``).

The AST rules (TPL001-TPL010) see source idioms; this pass sees what
XLA will actually be asked to run. It walks the ``register_jit``
registry, lowers every entry point at the representative abstract
signatures declared in :data:`build_specs`'s per-entry table (seeded
from ``obs/recorder.py``'s ``ENTRY_PHASES`` entries plus the shapes
the tests/benches drive), and enforces four IR rule families:

- **TPL011 dtype contract** — trace under ``jax.enable_x64`` and
  flag any *strong* float64 aval in the jaxpr (including nested
  jaxprs). Weak-typed rank-0 literal plumbing
  (``jnp.where(m, x, 0.0)`` routing a python float through a scalar
  ``convert_element_type``) is exempt: it lowers to f32 compute and
  pinning every literal would be noise. A ``np.float64`` constant or
  an ``arange``-promoted chain is strong f64 and fails.
- **TPL012 collective budget** — :func:`~lightgbm_tpu.parallel.comms
  .collective_summary` of each entry's jaxpr diffed against the
  committed ``tools/ir_budgets.json`` (justification-required, same
  discipline as ``tools/tpulint_baseline.txt``): the int8 hist wire
  and the reduce-scatter post-reduction cut become
  regressions-by-construction.
- **TPL013 donation honored** — entries whose budget file declares
  ``donate_argnums`` are lowered (``fn.lower``) and the StableHLO must
  carry one ``tf.aliasing_output`` input marker per donated leaf
  (guards the fused scan's score/bag carries).
  ``LIGHTGBM_TPU_FORCE_DONATE=1`` keeps the donation declaration on
  CPU so a CPU-only CI host lowers the same contract the TPU runs.
- **TPL014 recompile surface** — every ``register_jit`` site must
  declare ``max_signatures`` (AST-scanned, so an undeclared entry
  fails review before it ever runs), and the ``serve/predict``
  declaration must cover the pow2 bucket ladder.

Lowering only — nothing is ever executed, no TPU is required, and this
module is imported ONLY under ``--ir`` (the default ``lint`` path
stays jax-free; tests/test_static_analysis.py proves it in a
subprocess). Findings reuse the stable-fid/baseline/SARIF machinery.
"""

from __future__ import annotations

import ast
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .baseline import BaselineEntry
from .rules import Finding

__all__ = ["run_ircheck", "IRCheckResult", "IRSpec", "build_specs",
           "default_budgets_path", "load_budgets", "f64_findings",
           "donation_findings", "budget_findings",
           "register_jit_sites", "recompile_surface_findings",
           "IR_RULE_IDS"]

IR_RULE_IDS = ("TPL011", "TPL012", "TPL013", "TPL014")

#: budget keys TPL012 compares (measured <= committed); any other key
#: in a budget entry (besides justification/donate_argnums) is a typo
#: and reported as a finding rather than silently ignored
_BUDGET_METRICS = ("wire_bytes", "post_reduction_bytes",
                   "n_collectives")
_BUDGET_KEYS = _BUDGET_METRICS + ("justification", "donate_argnums")


def default_budgets_path(root: Optional[str] = None) -> str:
    from .engine import package_root
    root = root or package_root()
    return os.path.join(os.path.dirname(root), "tools",
                        "ir_budgets.json")


def load_budgets(path: str):
    """Parse ``tools/ir_budgets.json``.

    Returns ``(entries, unjustified)``: the committed budget dict and
    the :class:`BaselineEntry` list for entries missing a real
    justification (TODO placeholders count as missing — the same
    discipline ``tools/tpulint_baseline.txt`` enforces)."""
    if not os.path.exists(path):
        return {}, []
    with open(path, encoding="utf-8") as fh:
        raw = json.load(fh)
    entries = raw.get("entries", {})
    unjustified: List[BaselineEntry] = []
    for i, (key, val) in enumerate(sorted(entries.items()), start=1):
        just = str(val.get("justification", "")).strip()
        if not just or just.upper().startswith("TODO"):
            unjustified.append(BaselineEntry(
                fid=f"ir_budgets.json:{key}", justification="",
                lineno=i))
    return entries, unjustified


def ensure_cpu_jax():
    """Import jax pinned to CPU with an 8-way forced host platform
    (the sharded specs need a D=8 mesh) and the donation contract kept
    on CPU. Must run before anything imports jax in this process; the
    CLI routes ``--ir`` here before touching the package."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("LIGHTGBM_TPU_FORCE_DONATE", "1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax


# ---------------------------------------------------------------------
# the per-entry signature table
# ---------------------------------------------------------------------

@dataclass
class IRSpec:
    """One lowering of one registered entry point.

    ``entry`` is ``<register_jit name>@<variant>`` — the budget-file
    key. ``build`` returns ``(fn, args, static_argnums, jit_fn)``:
    ``fn`` is traced with ``jax.make_jaxpr`` (TPL011/TPL012), ``jit_fn``
    (when not None) is the registered jitted wrapper whose ``.lower``
    text TPL013 inspects for aliasing markers."""

    entry: str
    relpath: str         # anchor for entry-level findings
    func: str
    signature: str       # human-readable declared signature
    build: Callable[[dict], tuple]
    donate: Tuple[int, ...] = ()
    lineno: int = 1      # entry-level findings anchor here


def _mk_engine(ctx: dict):
    """Tiny binary engine shared by the fused-step/scan specs —
    constructed (host binning only), never trained."""
    if "engine" in ctx:
        return ctx["engine"]
    import numpy as np
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(0)
    X = rs.randn(256, 8)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.Booster(dict(objective="binary", num_leaves=15,
                           max_bin=63, verbosity=-1),
                      lgb.Dataset(X, label=y))
    ctx["booster"] = bst          # keep alive: engine holds weakrefs
    ctx["engine"] = bst._engine
    return ctx["engine"]


def _engine_scan_args(eng, jnp):
    return (eng.score, jnp.ones((eng.n,), jnp.float32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0.1, jnp.float32),
            jnp.ones((eng.F,), jnp.bool_), eng.bins_T,
            eng.feat_num_bins, eng.feat_nan_bin, eng.label, eng.weight,
            eng.monotone, eng.feat_is_cat, eng.interaction_groups,
            eng.forced, eng._bundle_dev)


def build_specs(jax) -> List[IRSpec]:
    """The signature table: every ``register_jit`` entry point at the
    shapes the tests/benches drive. ``parallel/dp_grow@wide-sharded``
    is the Allstate-wide acceptance shape (F=4228, B=255, D=8,
    ``split_search=sharded``) whose reduce-scatter payload bound
    ``tools/ir_budgets.json`` pins."""
    import jax.numpy as jnp

    def sds(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt)

    def grow_args(F, n):
        return (sds((F, n), jnp.uint8), sds((n,), jnp.float32),
                sds((n,), jnp.float32), sds((n,), jnp.float32),
                sds((F,), jnp.bool_), sds((F,), jnp.int32),
                sds((F,), jnp.int32))

    def b_grow(ctx):
        from ..ops.grow import GrowConfig, grow_tree
        from ..ops.split import SplitParams
        cfg = GrowConfig(num_leaves=31, num_bins=63,
                         split=SplitParams(min_data_in_leaf=5.0),
                         hist_method="scatter")
        fn = getattr(grow_tree, "unwrapped", grow_tree)
        return fn, (cfg,) + grow_args(8, 512), (0,), None

    def _mesh(ctx):
        if "mesh" not in ctx:
            from ..parallel.mesh import make_mesh
            ctx["mesh"] = make_mesh(8, devices=jax.devices("cpu"))
        return ctx["mesh"]

    def b_dp_wide(ctx):
        from ..ops.grow import GrowConfig
        from ..ops.split import SplitParams
        from ..parallel.data_parallel import make_dp_grow_fn
        cfg = GrowConfig(
            num_leaves=7, num_bins=255,
            split=SplitParams(min_data_in_leaf=1.0,
                              min_sum_hessian_in_leaf=1e-6),
            hist_method="scatter", grower="masked",
            split_search="sharded", parallel_mode="data")
        fn = make_dp_grow_fn(cfg, _mesh(ctx))
        return fn, grow_args(4228, 64 * 8), (), None

    def b_dp_narrow(ctx):
        from ..ops.grow import GrowConfig
        from ..ops.split import SplitParams
        from ..parallel.data_parallel import make_dp_grow_fn
        cfg = GrowConfig(
            num_leaves=31, num_bins=63,
            split=SplitParams(min_data_in_leaf=1.0,
                              min_sum_hessian_in_leaf=1e-6),
            hist_method="scatter", parallel_mode="data")
        fn = make_dp_grow_fn(cfg, _mesh(ctx))
        return fn, grow_args(8, 64 * 8), (), None

    def b_fused_scan(ctx):
        eng = _mk_engine(ctx)
        jit_fn = eng._get_scan_fn(4, False)
        fn = getattr(jit_fn, "unwrapped", jit_fn)
        return fn, _engine_scan_args(eng, jnp), (), jit_fn

    def b_fused_iter(ctx):
        eng = _mk_engine(ctx)
        jit_fn = eng._get_fused_fn()
        fn = getattr(jit_fn, "unwrapped", jit_fn)
        a = _engine_scan_args(eng, jnp)
        # step takes (score, it, shrink, row_w, ...) — no bag carry
        args = (a[0], a[2], a[3], jnp.ones((eng.n,), jnp.float32)) \
            + a[4:]
        return fn, args, (), jit_fn

    def _stacked(T, L, W):
        from ..ops.predict import StackedTrees
        return StackedTrees(
            split_feature=sds((T, L - 1), jnp.int32),
            threshold=sds((T, L - 1), jnp.float32),
            threshold_bin=sds((T, L - 1), jnp.int32),
            default_left=sds((T, L - 1), jnp.bool_),
            missing_type=sds((T, L - 1), jnp.int8),
            is_categorical=sds((T, L - 1), jnp.bool_),
            cat_bitset=sds((T, L - 1, W), jnp.uint32),
            left_child=sds((T, L - 1), jnp.int32),
            right_child=sds((T, L - 1), jnp.int32),
            leaf_value=sds((T, L), jnp.float32))

    def b_serve(ctx):
        from ..serve.compile import _predict_scores_padded, bucket_rows
        fn = getattr(_predict_scores_padded, "unwrapped",
                     _predict_scores_padded)
        return fn, (_stacked(8, 16, 1),
                    sds((bucket_rows(10), 8), jnp.float32), 1), (2,), \
            None

    def b_forest_leaves(ctx):
        from ..prediction import _forest_leaves
        fn = getattr(_forest_leaves, "unwrapped", _forest_leaves)
        return fn, (_stacked(8, 16, 1), sds((16, 8), jnp.float32)), \
            (), None

    def b_lambdarank(ctx):
        from ..ranking import _lambdarank_grads
        fn = getattr(_lambdarank_grads, "unwrapped", _lambdarank_grads)
        # two length classes (ranking._RankLayout): 6 queries at width
        # 8 in two blocks, 2 at width 16 in one
        def length_class(nb, blk, w):
            return (sds((nb, blk), jnp.int32), sds((nb, blk), jnp.int32),
                    sds((nb, blk, w), jnp.float32),
                    sds((nb, blk), jnp.float32))
        args = (sds((128,), jnp.float32),
                (length_class(2, 3, 8), length_class(1, 2, 16)),
                sds((128,), jnp.int32), sds((128,), jnp.float32),
                1.0, 30, True)
        return fn, args, (4, 5, 6), None

    def b_ndcg(ctx):
        from ..ranking import _ndcg_at
        fn = getattr(_ndcg_at, "unwrapped", _ndcg_at)
        args = (sds((1, 128), jnp.float32), sds((128,), jnp.int32),
                sds((128,), jnp.float32), sds((8, 10), jnp.int32),
                sds((8, 10), jnp.float32), sds((8, 4), jnp.float32),
                (1, 3, 5, 10))
        return fn, args, (6,), None

    def _tree_args(L):
        return (sds((L - 1,), jnp.int32), sds((L - 1,), jnp.int32),
                sds((L - 1,), jnp.bool_), sds((L - 1,), jnp.int32),
                sds((L - 1,), jnp.int32), sds((L,), jnp.float32),
                sds((8,), jnp.int32), sds((8, 256), jnp.uint8))

    def b_tree_values(ctx):
        from ..models.gbdt import _tree_values_binned
        fn = getattr(_tree_values_binned, "unwrapped",
                     _tree_values_binned)
        return fn, _tree_args(15), (), None

    def b_tree_leaves(ctx):
        from ..models.gbdt import _tree_leaves_binned
        fn = getattr(_tree_leaves_binned, "unwrapped",
                     _tree_leaves_binned)
        a = _tree_args(15)
        return fn, a[:5] + a[6:], (), None

    def b_linear_eval(ctx):
        from ..models.gbdt import _linear_eval
        fn = getattr(_linear_eval, "unwrapped", _linear_eval)
        L, km = 15, 4
        args = (sds((L,), jnp.float32), sds((L, km), jnp.float32),
                sds((L, km), jnp.int32), sds((L,), jnp.int32),
                sds((L,), jnp.float32), sds((16, 8), jnp.float32),
                sds((16,), jnp.int32))
        return fn, args, (), None

    return [
        IRSpec("ops/grow_tree@narrow", "ops/grow.py", "grow_tree_impl",
               "F=8 n=512 B=63 leaves=31 scatter", b_grow),
        IRSpec("parallel/dp_grow@wide-sharded",
               "parallel/data_parallel.py", "make_dp_grow_fn",
               "F=4228 n=512 B=255 D=8 masked sharded", b_dp_wide),
        IRSpec("parallel/dp_grow@narrow-psum",
               "parallel/data_parallel.py", "make_dp_grow_fn",
               "F=8 n=512 B=63 D=8 gathered psum", b_dp_narrow),
        IRSpec("gbdt/fused_scan@W4", "models/gbdt.py",
               "GBDTBooster._get_scan_fn",
               "binary n=256 F=8 window=4 no-bag", b_fused_scan,
               donate=(0, 1)),
        IRSpec("gbdt/fused_iter@default", "models/gbdt.py",
               "GBDTBooster._get_fused_fn",
               "binary n=256 F=8", b_fused_iter, donate=(0,)),
        IRSpec("serve/predict@bucket16", "serve/compile.py",
               "_predict_scores_padded", "T=8 L=16 rows=16 K=1",
               b_serve),
        IRSpec("prediction/forest_leaves@default", "prediction.py",
               "_forest_leaves", "T=8 L=16 rows=16", b_forest_leaves),
        IRSpec("ranking/lambdarank_grads@default", "ranking.py",
               "_lambdarank_grads", "n=128 nq=8 widths=8,16 trunc=30",
               b_lambdarank),
        IRSpec("ranking/ndcg@default", "ranking.py", "_ndcg_at",
               "n=128 nq=8 eval_at=1,3,5,10", b_ndcg),
        IRSpec("gbdt/tree_values_binned@default", "models/gbdt.py",
               "_tree_values_binned", "L=15 F=8 n=256", b_tree_values),
        IRSpec("gbdt/tree_leaves_binned@default", "models/gbdt.py",
               "_tree_leaves_binned", "L=15 F=8 n=256", b_tree_leaves),
        IRSpec("gbdt/linear_eval@default", "models/gbdt.py",
               "_linear_eval", "L=15 km=4 rows=16", b_linear_eval),
    ]


# ---------------------------------------------------------------------
# TPL011: dtype contract
# ---------------------------------------------------------------------

_JAXPR_WRAPPERS = frozenset({"pjit", "scan", "while", "cond",
                             "closed_call", "custom_jvp_call",
                             "custom_vjp_call", "remat", "checkpoint"})


def _strong_f64(aval) -> bool:
    return (getattr(aval, "dtype", None) is not None
            and str(aval.dtype) == "float64"
            and not getattr(aval, "weak_type", False))


def _walk_jaxprs(jaxpr):
    """Yield every eqn of ``jaxpr`` and its nested sub-jaxprs."""
    import jax.extend.core as jcore
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            stack = [val]
            while stack:
                v = stack.pop()
                if isinstance(v, jcore.ClosedJaxpr):
                    yield from _walk_jaxprs(v.jaxpr)
                elif isinstance(v, jcore.Jaxpr):
                    yield from _walk_jaxprs(v)
                elif isinstance(v, (tuple, list)):
                    stack.extend(v)


def _site_of(eqn, fallback, marker: str = "/lightgbm_tpu/"):
    """(relpath, lineno, func) of the user frame that traced ``eqn``
    — the first frame under ``marker`` (the analyzed tree)."""
    from jax._src import source_info_util
    for fr in source_info_util.user_frames(eqn.source_info.traceback):
        fname = fr.file_name.replace(os.sep, "/")
        if marker in fname:
            rel = fname.rsplit(marker, 1)[1]
            if rel.startswith("analysis/"):
                continue
            return rel, int(fr.start_line or 0), fr.function_name
    return fallback


def f64_findings(closed, spec_relpath: str, spec_func: str,
                 entry: str,
                 marker: str = "/lightgbm_tpu/") -> List[Finding]:
    """TPL011 findings for one traced program: one finding per
    (site, primitive-set) carrying strong float64."""
    sites: Dict[Tuple[str, int, str], set] = {}
    for eqn in _walk_jaxprs(closed.jaxpr):
        if eqn.primitive.name in _JAXPR_WRAPPERS:
            continue
        if any(_strong_f64(getattr(v, "aval", None))
               for v in list(eqn.invars) + list(eqn.outvars)):
            key = _site_of(eqn, (spec_relpath, 1, spec_func),
                           marker=marker)
            sites.setdefault(key, set()).add(eqn.primitive.name)
    out = []
    for (rel, line, func), prims in sorted(sites.items()):
        out.append(Finding(
            rule="TPL011", relpath=rel, lineno=line, col=0, func=func,
            symbol="ir-f64",
            message=(f"strong float64 in lowered IR of {entry} "
                     f"({', '.join(sorted(prims))}): pin the dtype — "
                     f"an np.float64 constant or a default-int/float "
                     f"promotion widens the traced program 2x on the "
                     f"wire and falls off the TPU fast path")))
    return out


# ---------------------------------------------------------------------
# TPL012: collective budget
# ---------------------------------------------------------------------

def budget_findings(summary: dict, budget: Optional[dict],
                    spec: "IRSpec") -> List[Finding]:
    """Diff one entry's measured collective summary against its
    committed budget entry (None = no entry committed)."""
    out = []

    def f(message):
        out.append(Finding(
            rule="TPL012", relpath=spec.relpath, lineno=spec.lineno,
            col=0,
            func=spec.func, symbol="ir-budget", message=message))

    if summary["n_collectives"] == 0 and budget is None:
        return out
    if budget is None:
        f(f"{spec.entry} lowers {summary['n_collectives']} "
          f"collective(s) ({', '.join(summary['prims'])}; "
          f"wire {summary['wire_bytes']} B, post-reduction "
          f"{summary['post_reduction_bytes']} B) but has no committed "
          f"budget in tools/ir_budgets.json — add a justified entry")
        return out
    for key in sorted(budget):
        if key not in _BUDGET_KEYS:
            f(f"{spec.entry}: unknown budget key {key!r} in "
              f"tools/ir_budgets.json (have: "
              f"{', '.join(_BUDGET_KEYS)})")
    for metric in _BUDGET_METRICS:
        if metric not in budget:
            continue
        allowed = int(budget[metric])
        measured = int(summary[metric])
        if measured > allowed:
            f(f"{spec.entry}: {metric} {measured} exceeds the "
              f"committed budget {allowed} "
              f"({', '.join(summary['prims']) or 'no collectives'}) — "
              f"either the regression is real (fix it) or re-lower "
              f"and re-justify the budget "
              f"(docs/STATIC_ANALYSIS.md#tpl012)")
    return out


# ---------------------------------------------------------------------
# TPL013: donation honored
# ---------------------------------------------------------------------

def donation_marker_count(lowered_text: str) -> int:
    """Input->output aliasing markers in a lowered module's StableHLO
    (one ``tf.aliasing_output`` input attribute per donated leaf)."""
    return lowered_text.count("tf.aliasing_output")


def donation_findings(jit_fn, args, expected: Sequence[int],
                      spec: "IRSpec") -> List[Finding]:
    lowered = jit_fn.lower(*args)
    n = donation_marker_count(lowered.as_text())
    if n >= len(expected):
        return []
    return [Finding(
        rule="TPL013", relpath=spec.relpath, lineno=spec.lineno, col=0,
        func=spec.func, symbol="ir-donation",
        message=(f"{spec.entry}: donate_argnums "
                 f"{tuple(expected)} declared but the lowered program "
                 f"carries {n}/{len(expected)} tf.aliasing_output "
                 f"markers — the carry buffers will be copied, not "
                 f"reused (doubles the score/bag HBM footprint per "
                 f"fused step)"))]


# ---------------------------------------------------------------------
# TPL014: recompile surface
# ---------------------------------------------------------------------

def register_jit_sites(pkg_root: str) -> List[dict]:
    """AST scan for ``register_jit(...)`` call sites in the package:
    ``{"relpath", "lineno", "func", "name", "declared"}`` per site."""
    sites = []
    for dirpath, dirnames, filenames in os.walk(pkg_root):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in ("__pycache__", "analysis")]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, pkg_root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as fh:
                    tree = ast.parse(fh.read(), filename=rel)
            except SyntaxError:
                continue
            funcs = []
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    funcs.append((node.lineno,
                                  getattr(node, "end_lineno",
                                          node.lineno), node.name))
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                callee = node.func
                name = callee.attr if isinstance(callee, ast.Attribute) \
                    else getattr(callee, "id", "")
                if name != "register_jit":
                    continue
                entry = ""
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    entry = node.args[0].value
                declared = any(k.arg == "max_signatures"
                               for k in node.keywords)
                enclosing = "<module>"
                best = None
                for lo, hi, fn_name in funcs:
                    if lo <= node.lineno <= hi and \
                            (best is None or hi - lo < best):
                        enclosing, best = fn_name, hi - lo
                sites.append({"relpath": rel, "lineno": node.lineno,
                              "func": enclosing, "name": entry,
                              "declared": declared})
    return sites


def recompile_surface_findings(pkg_root: str) -> List[Finding]:
    out = []
    for site in register_jit_sites(pkg_root):
        if site["declared"]:
            continue
        out.append(Finding(
            rule="TPL014", relpath=site["relpath"],
            lineno=site["lineno"], col=0, func=site["func"],
            symbol="ir-sigs",
            message=(f"register_jit({site['name']!r}) declares no "
                     f"max_signatures — every entry point must commit "
                     f"its recompile surface so telemetry "
                     f"(jit_cache_sizes) and lint can flag a "
                     f"recompile storm against it")))
    # the serve ladder: the declaration must cover every pow2 bucket
    try:
        from ..obs import jit_declarations
        from ..serve.compile import n_serve_buckets
        declared = jit_declarations().get("serve/predict")
        buckets = n_serve_buckets()
        if declared is not None and declared < buckets:
            out.append(Finding(
                rule="TPL014", relpath="serve/compile.py", lineno=1,
                col=0, func="_predict_scores_padded", symbol="ir-sigs",
                message=(f"serve/predict declares max_signatures="
                         f"{declared} but bucket_rows emits {buckets} "
                         f"pow2 buckets — warmup alone overruns the "
                         f"declared recompile surface")))
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

@dataclass
class IRCheckResult:
    findings: List[Finding]
    stale_budget: List[BaselineEntry] = field(default_factory=list)
    unjustified_budget: List[BaselineEntry] = field(default_factory=list)
    entries_run: List[str] = field(default_factory=list)
    elapsed: float = 0.0


def run_ircheck(rules: Optional[Sequence[str]] = None,
                entries: Optional[Sequence[str]] = None,
                budgets_path: Optional[str] = None) -> IRCheckResult:
    """Lower every entry in the signature table and run the IR rules.

    ``rules`` filters to a subset of :data:`IR_RULE_IDS`;
    ``entries`` filters specs by full ``name@variant`` or bare
    registry name. Returns raw findings (fids are assigned by the
    engine alongside the AST findings)."""
    t0 = time.perf_counter()
    want = set(rules) & set(IR_RULE_IDS) if rules else set(IR_RULE_IDS)
    jax = ensure_cpu_jax()
    from ..parallel.comms import collective_summary

    budgets_path = budgets_path or default_budgets_path()
    budgets, unjustified = load_budgets(budgets_path)

    specs = build_specs(jax)
    if entries:
        wanted = set(entries)
        specs = [s for s in specs
                 if s.entry in wanted
                 or s.entry.split("@", 1)[0] in wanted]
        if not specs:
            raise ValueError(
                f"--ir-entry matched nothing (have: "
                f"{', '.join(s.entry for s in build_specs(jax))})")

    ctx: dict = {}
    findings: List[Finding] = []
    seen_keys = set()
    for spec in specs:
        fn, args, static_argnums, jit_fn = spec.build(ctx)
        closed = jax.make_jaxpr(fn, static_argnums=static_argnums)(
            *args)
        if "TPL011" in want:
            with jax.enable_x64(True):
                closed64 = jax.make_jaxpr(
                    fn, static_argnums=static_argnums)(*args)
            findings.extend(f64_findings(closed64, spec.relpath,
                                         spec.func, spec.entry))
        budget = budgets.get(spec.entry)
        if budget is not None:
            seen_keys.add(spec.entry)
        if "TPL012" in want:
            findings.extend(budget_findings(
                collective_summary(closed), budget, spec))
        expected_donate = tuple(budget.get("donate_argnums",
                                           spec.donate)) \
            if budget else spec.donate
        if "TPL013" in want and expected_donate and jit_fn is not None:
            dyn_args = args[len(static_argnums):] \
                if static_argnums == (0,) else args
            findings.extend(donation_findings(
                jit_fn, dyn_args, expected_donate, spec))

    if "TPL014" in want and not entries:
        from .engine import package_root
        findings.extend(recompile_surface_findings(package_root()))

    # budget-file staleness mirrors the baseline discipline: a key no
    # spec lowers anymore must be deleted, not rot as false assurance
    all_entries = {s.entry for s in build_specs(jax)}
    stale = [BaselineEntry(fid=f"ir_budgets.json:{key}",
                           justification="", lineno=i)
             for i, key in enumerate(sorted(set(budgets) - all_entries),
                                     start=1)]
    return IRCheckResult(findings=findings, stale_budget=stale,
                         unjustified_budget=unjustified,
                         entries_run=[s.entry for s in specs],
                         elapsed=time.perf_counter() - t0)
