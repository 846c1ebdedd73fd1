"""The program's missing-value plane against the plain reference
(``perfbench/harness/reference_missing.py``, ISSUE 35) on the CPU at
test size: a table with NaN in station blocks, through ``lgb.train`` on
the fused path (no validation set) and the eager one (with one), at a
width that takes the grower's ``sort`` partition and one that takes the
``wide`` one, under ``use_missing`` true / false and ``zero_as_missing``.

Each case is one job and one comparison (``check_missing.compare``): leaf
and node counts exact, no node with a better default direction, leaf
values, gains, scores and AUC within the tolerances below; then
``Booster.predict`` on the raw NaN matrix, the model's round trip through
``save_model``, and columns that are all NaN or have none.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

import lightgbm_tpu as lgb  # noqa: E402
from harness import check_missing, datagen_missing  # noqa: E402

ROWS, VALID, ROUNDS = 8000, 2000, 4
# 24 columns pack to 6 words: 6 + 2 payload operands <= _SORT_SINGLE_MAX;
# 48 columns to 12 words: 14 operands, the wide partition
WIDTHS = {"sort": 24, "wide": 48}
MODES = {"nan": {}, "none": {"use_missing": False},
         "zero": {"zero_as_missing": True}}
PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 15,
          "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3, "verbose": -1}
CHECK = {"rounds_followed": ROUNDS, "root_candidates": 64,
         "candidate_sample_rows": ROWS, "deep_nodes": 3,
         "deep_min_share": 0.02}
# float32 against float32 over a few thousand rows reads ~1e-3 at the
# widest leaf (its sums come from parent-less-sibling subtractions, as
# tests/perfbench/bench_copy.py notes) and ~1e-5 at the median one: 3x
# the largest of the twelve cases' readings. The split search is held to
# the reference's best on ITS candidates (64 quantiles of the values that
# are not missing) against the program's 62 bins.
TOL = {"leaf_weight_gap": 8e-3, "leaf_value_gap": 2e-2,
       "split_gain_gap": 1e-2, "leaf_weight_median_gap": 3e-4,
       "leaf_value_median_gap": 2e-4, "split_gain_median_gap": 3e-4,
       "root_split_shortfall": 0.08, "deep_split_shortfall": 0.12,
       "score_gap": 5e-3, "valid_score_gap": 5e-3,
       "missing_direction_shortfall": 1e-4}


def spec(width):
    return {
        "block_rows": 1024,
        "stations": {"count": 6, "stages": 3, "min_columns": 2,
                     "max_columns": 12, "layout_seed": 35,
                     "min_share": 0.15, "max_share": 0.6,
                     "present_share": 0.4},
        "values": {"coarse_share": 0.75, "levels_min": 3, "levels_max": 9,
                   "value_seed": 36},
        "label": {"coef_seed": 37, "linear_scale": 1.0, "noise_scale": 0.5,
                  "products": [[0, 0, 1]], "product_scale": 3.0,
                  "visited": [[1, 1.0], [4, -0.8]]},
    }, width


def tables(width, seed=5):
    sp, F = spec(width)
    X, lat = datagen_missing.make_table(sp, F, ROWS + VALID, seed, threads=1)
    y = (lat > np.quantile(lat, 0.7)).astype(np.float32)
    return {"train": (X[:ROWS], y[:ROWS], None),
            "valid": (X[ROWS:], y[ROWS:], None)}


def train(tabs, mode, eager, **over):
    (X, y, _), (Xv, yv, _) = tabs["train"], tabs["valid"]
    params = dict(PARAMS, **MODES[mode], **over)
    ds = lgb.Dataset(X, label=y, params=params)
    evals, kw = {}, {}
    if eager:
        kw = {"valid_sets": [lgb.Dataset(Xv, label=yv, reference=ds)],
              "valid_names": ["valid"],
              "callbacks": [lgb.record_evaluation(evals)]}
    return lgb.train(params, ds, num_boost_round=ROUNDS, **kw), evals


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("arm", list(WIDTHS))
@pytest.mark.parametrize("path", ["fused", "eager"])
def test_the_program_agrees_with_the_reference(path, arm, mode):
    tabs = tables(WIDTHS[arm])
    bst, evals = train(tabs, mode, path == "eager")
    eng = bst._engine
    assert eng._grow_plan["partition"] == arm
    if path == "eager":
        valid_score = np.asarray(eng.valid_sets[0].score)[0]
    else:
        assert not eng.valid_sets
        valid_score = bst.predict(tabs["valid"][0], raw_score=True)
    prog = {"score": np.asarray(eng.score)[0], "valid_score": valid_score,
            "evals": dict(evals.get("valid", {}))}
    ref_cfg = {"lambda_l2": 0.0, "min_data_in_leaf": 20,
               "min_sum_hessian_in_leaf": 1e-3,
               "use_missing": mode != "none",
               "zero_as_missing": mode == "zero"}
    got = check_missing.compare(bst.dump_model(), prog, tabs, ref_cfg, 0.1,
                                CHECK, seed=5, operand_dtype="float32")
    assert got["trees"] == ROUNDS and got["leaf_count_mismatch"] == 0
    for name, tol in TOL.items():
        if mode == "none" and name == "missing_direction_shortfall":
            assert got[name] is None        # no node has a direction
            continue
        assert got[name] is not None and got[name] <= tol, (name, got[name])
    if path == "eager":
        assert got["eval_metric_gap"] <= 3e-4
    on_missing = got["nodes"]["on_missing"]
    assert (on_missing > 0) == (mode != "none")
    if mode == "nan":
        # both directions are won somewhere, or the number reads nothing
        assert 0 < got["nodes"]["default_left"] < on_missing
        assert got["nodes"]["asked"] > 0


@pytest.mark.parametrize("mode", list(MODES))
def test_predict_on_the_raw_nan_matrix_is_the_training_score(mode):
    tabs = tables(WIDTHS["sort"])
    bst, _ = train(tabs, mode, eager=True)
    eng = bst._engine
    for which, score in (("train", eng.score),
                         ("valid", eng.valid_sets[0].score)):
        raw = bst.predict(tabs[which][0], raw_score=True)
        assert np.isnan(tabs[which][0]).mean() > 0.4
        np.testing.assert_allclose(raw, np.asarray(score)[0], rtol=0,
                                   atol=2e-6)


def _nodes(model):
    out, stack = [], [t["tree_structure"] for t in model["tree_info"]]
    while stack:
        node = stack.pop()
        if "split_index" in node:
            out.append((node["split_feature"], node["threshold"],
                        node["default_left"], node["missing_type"]))
            stack += [node["left_child"], node["right_child"]]
    return sorted(out)


@pytest.mark.parametrize("mode", list(MODES))
def test_save_and_load_keep_direction_and_missing_type(mode, tmp_path):
    tabs = tables(WIDTHS["sort"])
    bst, _ = train(tabs, mode, eager=False)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    back = lgb.Booster(model_file=path)
    was, now = _nodes(bst.dump_model()), _nodes(back.dump_model())
    assert [n[0] for n in was] == [n[0] for n in now]
    assert [n[2:] for n in was] == [n[2:] for n in now]
    np.testing.assert_allclose([n[1] for n in now], [n[1] for n in was],
                               rtol=1e-15)
    want = {"nan": {"NaN"}, "none": {"None"}, "zero": {"Zero"}}[mode]
    assert {n[3] for n in was} == want
    if mode != "none":
        assert {n[2] for n in was} == {True, False}
    for which in ("train", "valid"):
        np.testing.assert_array_equal(
            back.predict(tabs[which][0], raw_score=True),
            bst.predict(tabs[which][0], raw_score=True))


def test_an_all_nan_column_is_never_split_and_a_dense_one_has_no_direction():
    tabs = tables(WIDTHS["sort"])
    X, y, _ = tabs["train"]
    X = X.copy()
    rng = np.random.default_rng(3)
    X[:, 0] = np.nan                                    # nothing measured
    X[:, 1] = rng.standard_normal(ROWS) + 2.0 * y       # always measured
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
    nodes = _nodes(bst.dump_model())
    assert 0 not in {n[0] for n in nodes}
    dense = [n for n in nodes if n[0] == 1]
    assert dense and all(n[2:] == (False, "None") for n in dense)
    assert {n[3] for n in nodes if n[0] > 1} == {"NaN"}
    # a NaN where training saw none reads as 0.0 (tree.h NumericalDecision)
    probe, zero = X[:500].copy(), X[:500].copy()
    probe[:, 1], zero[:, 1] = np.nan, 0.0
    np.testing.assert_array_equal(bst.predict(probe, raw_score=True),
                                  bst.predict(zero, raw_score=True))
    # and whatever is put in the column nothing was measured in
    probe = X[:500].copy()
    probe[:, 0] = 7.0
    np.testing.assert_array_equal(bst.predict(probe, raw_score=True),
                                  bst.predict(X[:500], raw_score=True))


def test_the_missing_value_counters_count_the_table_and_the_trees():
    from lightgbm_tpu.obs.registry import registry
    names = ("bin_cells", "bin_cells_missing", "tree_leaf_count", "tree_splits", "tree_splits_on_missing",
             "tree_splits_default_left")

    def counters():
        return {k: registry.counter(k).snapshot() for k in names}

    tabs = tables(WIDTHS["sort"])
    before = counters()
    bst, _ = train(tabs, "nan", eager=True)
    got = {k: v - before[k] for k, v in counters().items()}
    both = np.concatenate([tabs["train"][0], tabs["valid"][0]])
    assert got["bin_cells"] == both.size
    assert got["bin_cells_missing"] == np.isnan(both).sum()
    nodes = _nodes(bst.dump_model())
    assert got["tree_splits"] == len(nodes)
    assert got["tree_leaf_count"] == len(nodes) + ROUNDS
    assert got["tree_splits_on_missing"] == sum(n[3] != "None" for n in nodes)
    assert got["tree_splits_default_left"] == sum(n[2] for n in nodes)
    # the fused path counts where the deferred trees reach the host:
    # after the last round, never inside one
    before, seen = counters(), []
    (X, y, _) = tabs["train"]
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=ROUNDS,
                    callbacks=[lambda env: seen.append(
                        registry.counter("tree_leaf_count").snapshot())])
    assert seen == [before["tree_leaf_count"]] * ROUNDS
    nodes = _nodes(bst.dump_model())
    got = {k: v - before[k] for k, v in counters().items()}
    assert got["tree_leaf_count"] == len(nodes) + ROUNDS
    assert got["tree_splits_default_left"] == sum(n[2] for n in nodes)


def test_the_engine_keeps_its_own_grower_s_plan_not_the_last_traced():
    """Found by ISSUE 35's readings tool: a job whose grower came from
    the process's jit cache (an earlier job of the same shapes compiled
    it) reported the plan of whatever job had traced LAST, so the
    benchmark's driver refused a sound run after its tiny probe."""
    wide, sort = tables(WIDTHS["wide"]), tables(WIDTHS["sort"])
    first, _ = train(wide, "nan", eager=True)
    assert first._engine._grow_plan["partition"] == "wide"
    other, _ = train(sort, "nan", eager=True)
    assert other._engine._grow_plan["partition"] == "sort"
    again, _ = train(wide, "nan", eager=True)       # nothing is traced
    assert again._engine._grow_plan == first._engine._grow_plan
    assert again._engine._grow_plan["partition"] == "wide"
