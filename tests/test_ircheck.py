"""IR-contract lint (``lint --ir``, analysis/ircheck.py): TPL011-014.

Four layers, mirroring tests/test_static_analysis.py's structure:

1. End-to-end: the shipped tree lowers clean — every entry in the
   ircheck signature table, zero findings, inside the wall-clock
   budget, with the committed tools/ir_budgets.json neither stale nor
   unjustified.
2. Per-rule IR fixtures (tests/analysis_fixtures/ir/): one positive
   and one negative per rule, pinned by ``# EXPECT: TPLNNN`` markers
   (the marker names the line that FOLLOWS it, same convention as the
   AST fixtures) and cross-checked by finding id + line.
3. Mutation regressions on the REAL tree: three hand-applied
   regressions (sharded search's psum_scatter replaced by a full
   psum, the fused scan's donation dropped, an np.float64 constant
   injected into a traced helper) each must fail ``lint --ir`` in a
   subprocess with the exact expected finding id.
4. Consistency: the static declaration surface (register_jit AST
   sites, TPL014's input) must cover what a real training run
   actually compiles — every runtime-tracked entry point appears in
   the static scan and stays within its declared max_signatures.
"""

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
IR_FIXTURES = os.path.join(HERE, "analysis_fixtures", "ir")
_MARKER = "/analysis_fixtures/ir/"

_EXPECT_RE = re.compile(r"#\s*EXPECT:\s*(TPL\d{3})\s*$")


def _expected_findings(rel):
    out = []
    with open(os.path.join(IR_FIXTURES, rel), encoding="utf-8") as fh:
        for i, line in enumerate(fh, start=1):
            m = _EXPECT_RE.search(line)
            if m:
                out.append((m.group(1), i + 1))
    return sorted(out)


def _anchor_line(rel, name):
    """Line of the top-level ``NAME = ...`` assignment in a fixture —
    where entry-level findings (budget/donation) anchor."""
    with open(os.path.join(IR_FIXTURES, rel), encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) \
                and any(isinstance(t, ast.Name) and t.id == name
                        for t in node.targets):
            return node.lineno
    raise AssertionError(f"{rel}: no top-level {name} assignment")


def _load_fixture(rel):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ir_fixture_" + rel.replace(".py", ""),
        os.path.join(IR_FIXTURES, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check(findings, rel):
    from lightgbm_tpu.analysis.baseline import assign_ids
    assign_ids(findings)
    got = sorted((f.rule, f.lineno) for f in findings)
    expected = _expected_findings(rel)
    assert got == expected, (
        f"{rel}: findings diverge from # EXPECT markers\n"
        f"  expected: {expected}\n  got:      {got}\n  "
        + "\n  ".join(f"{f.fid} @ {f.lineno}: {f.message[:100]}"
                      for f in findings))
    for f in findings:
        assert f.fid.startswith(f"{f.rule}:{f.relpath}:"), f.fid


# ---------------------------------------------------------------------
# 1. end-to-end on the shipped tree
# ---------------------------------------------------------------------

def test_ir_lint_clean_on_tree(monkeypatch):
    """The committed tree lowers clean at every declared signature,
    the budget file is fully justified and non-stale, and the whole
    pass stays inside the CI wall-clock budget."""
    from lightgbm_tpu.analysis.ircheck import run_ircheck
    # run_ircheck setdefaults this; pin it via monkeypatch so the
    # in-process run can't leak the forced donation into later tests
    monkeypatch.setenv("LIGHTGBM_TPU_FORCE_DONATE", "1")
    res = run_ircheck()
    assert not res.findings, "\n".join(
        f"{f.rule} {f.relpath}:{f.lineno} {f.message}"
        for f in res.findings)
    assert not res.stale_budget, [e.fid for e in res.stale_budget]
    assert not res.unjustified_budget, \
        [e.fid for e in res.unjustified_budget]
    assert len(res.entries_run) == 12, res.entries_run
    assert "parallel/dp_grow@wide-sharded" in res.entries_run
    assert "ranking/ndcg@default" in res.entries_run
    assert res.elapsed < 60.0, f"IR pass took {res.elapsed:.1f}s"


def test_budget_file_pins_acceptance_entries():
    """tools/ir_budgets.json commits the wide-sharded payload bound
    and the scan-carry donation contract the ISSUE acceptance names."""
    with open(os.path.join(REPO, "tools", "ir_budgets.json"),
              encoding="utf-8") as fh:
        entries = json.load(fh)["entries"]
    wide = entries["parallel/dp_grow@wide-sharded"]
    # post-reduction must stay well under wire: that gap IS the
    # sharded-search cut a full-psum regression would erase
    assert wide["post_reduction_bytes"] * 4 < wide["wire_bytes"]
    assert entries["gbdt/fused_scan@W4"]["donate_argnums"] == [0, 1]
    assert entries["gbdt/fused_iter@default"]["donate_argnums"] == [0]
    for key, val in entries.items():
        just = str(val.get("justification", "")).strip()
        assert just and not just.upper().startswith("TODO"), key


def test_load_budgets_rejects_todo_justification(tmp_path):
    from lightgbm_tpu.analysis.ircheck import load_budgets
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"entries": {
        "a@x": {"wire_bytes": 1, "justification": "TODO: later"},
        "b@y": {"wire_bytes": 1, "justification": "real reason"},
    }}))
    _, unjustified = load_budgets(str(p))
    assert [e.fid for e in unjustified] == ["ir_budgets.json:a@x"]


# ---------------------------------------------------------------------
# 2. per-rule fixtures
# ---------------------------------------------------------------------

@pytest.mark.parametrize("rel", ["tpl011_pos.py", "tpl011_neg.py"])
def test_tpl011_fixture(rel):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.ircheck import f64_findings
    fn, args = _load_fixture(rel).build(jax, jnp)
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(fn)(*args)
    _check(f64_findings(closed, rel, "build", f"fixture/{rel}",
                        marker=_MARKER), rel)


@pytest.mark.parametrize("rel", ["tpl012_pos.py", "tpl012_neg.py"])
def test_tpl012_fixture(rel):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.ircheck import IRSpec, budget_findings
    from lightgbm_tpu.parallel.comms import collective_summary
    mod = _load_fixture(rel)
    fn, args = mod.build(jax, jnp)
    spec = IRSpec(entry=f"fixture/{rel}", relpath=rel, func="build",
                  signature="", build=None,
                  lineno=_anchor_line(rel, "BUDGET"))
    closed = jax.make_jaxpr(fn)(*args)
    _check(budget_findings(collective_summary(closed), mod.BUDGET,
                           spec), rel)


@pytest.mark.parametrize("rel", ["tpl013_pos.py", "tpl013_neg.py"])
def test_tpl013_fixture(rel):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.ircheck import IRSpec, donation_findings
    mod = _load_fixture(rel)
    jit_fn, args = mod.build(jax, jnp)
    spec = IRSpec(entry=f"fixture/{rel}", relpath=rel, func="build",
                  signature="", build=None,
                  lineno=_anchor_line(rel, "DONATE"))
    _check(donation_findings(jit_fn, args, mod.DONATE, spec), rel)


@pytest.mark.parametrize("rel", ["tpl014_pos.py", "tpl014_neg.py"])
def test_tpl014_fixture(rel):
    from lightgbm_tpu.analysis.ircheck import recompile_surface_findings
    findings = [f for f in recompile_surface_findings(IR_FIXTURES)
                if f.relpath == rel]
    _check(findings, rel)


def test_every_ir_rule_has_fixture_coverage():
    from lightgbm_tpu.analysis import IR_RULES
    covered = set()
    for rel in sorted(os.listdir(IR_FIXTURES)):
        if rel.endswith(".py"):
            for rule, _ in _expected_findings(rel):
                covered.add(rule)
    missing = {r.id for r in IR_RULES} - covered
    assert not missing, f"IR rules without a positive fixture: {missing}"


# ---------------------------------------------------------------------
# 3. mutation regressions on the real tree
# ---------------------------------------------------------------------

def _mutated_lint(tmp_path, relpath, old, new, entry):
    """Copy lightgbm_tpu + tools into tmp, apply one source mutation,
    and run ``lint --ir`` there in a subprocess (ircheck lowers the
    IMPORTED package, so the mutated copy must be what resolves)."""
    pkg = tmp_path / "lightgbm_tpu"
    shutil.copytree(os.path.join(REPO, "lightgbm_tpu"), pkg,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "tools"), tmp_path / "tools")
    target = pkg / relpath
    src = target.read_text(encoding="utf-8")
    assert src.count(old) == 1, \
        f"{relpath}: mutation anchor not unique ({src.count(old)} hits)"
    target.write_text(src.replace(old, new), encoding="utf-8")
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "lint", "--ir",
         "--ir-entry", entry, "--format", "json"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 1, (
        f"mutated lint --ir rc={proc.returncode} (want 1)\n"
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}")
    return [f["id"] for f in json.loads(proc.stdout)["findings"]]


def test_mutation_full_psum_trips_collective_budget(tmp_path):
    """Regressing sharded search to a full psum (+ slice) multiplies
    the post-reduction payload ~D x past the committed budget."""
    fids = _mutated_lint(
        tmp_path, "parallel/comms.py",
        "        chunk = lax.psum_scatter(x, axis_name,\n"
        "                                 scatter_dimension=scatter_axis,\n"
        "                                 tiled=True)\n",
        "        full = lax.psum(x, axis_name)\n"
        "        per = x.shape[scatter_axis] // lax.axis_size(axis_name)\n"
        "        chunk = lax.dynamic_slice_in_dim(\n"
        "            full, lax.axis_index(axis_name) * per, per,\n"
        "            axis=scatter_axis)\n",
        "parallel/dp_grow@wide-sharded")
    assert ("TPL012:parallel/data_parallel.py:make_dp_grow_fn:"
            "ir-budget#1") in fids, fids


def test_mutation_dropped_donation_trips_tpl013(tmp_path):
    """Dropping donate_argnums from the fused scan wrapper leaves the
    budget-declared carry donation unhonored in the lowered program."""
    fids = _mutated_lint(
        tmp_path, "models/gbdt.py",
        "jax.jit(scan_fn, donate_argnums=_donate(0, 1)),",
        "jax.jit(scan_fn),",
        "gbdt/fused_scan@W4")
    assert ("TPL013:models/gbdt.py:GBDTBooster._get_scan_fn:"
            "ir-donation#1") in fids, fids


def test_mutation_float64_constant_trips_tpl011(tmp_path):
    """An np.float64 constant in a traced helper becomes a strong f64
    aval under the x64 trace — the dtype-contract leak TPL011 exists
    to catch (the AST rule TPL009 can only see syntactic producers)."""
    fids = _mutated_lint(
        tmp_path, "ops/split.py",
        "    return t * t / (sum_h + p.lambda_l2 + K_EPS)\n",
        "    import numpy as np\n"
        "    return t * t / (sum_h + p.lambda_l2 + K_EPS) "
        "* np.float64(1.0)\n",
        "ops/grow_tree@narrow")
    assert "TPL011:ops/split.py:leaf_gain:ir-f64#1" in fids, fids


# ---------------------------------------------------------------------
# 4. static declarations vs runtime recompile counters
# ---------------------------------------------------------------------

def test_static_declarations_cover_runtime_recompiles():
    """Train for a few rounds and predict, then cross-check the
    runtime jit tracker against the static surface TPL014 scans:
    every entry point the run actually compiled must be a
    register_jit site in the source, carry a max_signatures
    declaration, and stay within it."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.engine import package_root
    from lightgbm_tpu.analysis.ircheck import register_jit_sites
    from lightgbm_tpu.obs import jit_cache_sizes, jit_declarations

    # module-level entry points (ops/grow_tree) keep the signatures
    # earlier tests of the session compiled: count this run's only
    before = jit_cache_sizes()
    rs = np.random.RandomState(7)
    X = rs.randn(256, 8)
    y = (X[:, 0] + 0.3 * rs.randn(256) > 0).astype(np.float64)
    bst = lgb.train(dict(objective="binary", num_leaves=7, max_bin=63,
                         verbosity=-1),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    bst.predict(X)

    static_names = {s["name"]
                    for s in register_jit_sites(package_root())
                    if s["name"]}
    declared = jit_declarations()
    sizes = jit_cache_sizes()
    assert sizes, "training tracked no jitted entry points"
    for (name, seq), size in sizes.items():
        size -= before.get((name, seq), 0)
        assert name in static_names, (
            f"runtime entry {name!r} has no register_jit site the "
            f"static scan can find")
        assert name in declared, (
            f"runtime entry {name!r} compiled without a "
            f"max_signatures declaration")
        assert size <= declared[name], (
            f"{name}: {size} live signatures exceeds the declared "
            f"max_signatures={declared[name]}")
