"""Fused-iteration fast path (gbdt.py _train_one_iter_fused).

One boosting iteration = ONE XLA program (gradients -> grow -> pack ->
contrib -> score update) instead of the eager path's 6 launches/iter.

Contract: for every eligible config the fused path must produce the
same model as the eager path (same split structure, leaf values to
float tolerance — host RNG streams are shared by construction, device
RNG keys by an identical fold_in schedule). Ineligible configs
(CEGB, GOSS, RenewTreeOutput objectives, DART/RF, linear trees, valid
sets, custom gradients, mesh) must fall back to the eager path and
keep working.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDTBooster


@pytest.fixture
def data():
    rs = np.random.RandomState(7)
    X = rs.randn(3000, 10)
    y = ((X[:, :4] @ rs.randn(4) + 0.3 * rs.randn(3000)) > 0).astype(float)
    return X, y


def _train(params, X, y, n=8, fused=True, valid=False):
    if not fused:
        orig = GBDTBooster._fused_ok
        GBDTBooster._fused_ok = lambda self: False
    try:
        ds = lgb.Dataset(X, label=y)
        kw = {}
        if valid:
            kw = {"valid_sets": [lgb.Dataset(X[:500], label=y[:500],
                                             reference=ds)]}
        return lgb.train(dict(params, verbosity=-1), ds,
                         num_boost_round=n, **kw)
    finally:
        if not fused:
            GBDTBooster._fused_ok = orig


def _assert_same_model(a, b, rtol=1e-5, atol=1e-6):
    assert len(a._models) == len(b._models)
    for ta, tb in zip(a._models, b._models):
        assert ta.num_leaves == tb.num_leaves
        nn = ta.num_nodes
        assert np.array_equal(ta.split_feature[:nn], tb.split_feature[:nn])
        # trees adopted through init_model / checkpoint restore carry
        # threshold_bin = -1 (re-mapped lazily against the current
        # mappers, basic.Booster._preload); where EITHER side is
        # unbinned, the real-valued thresholds are the identity
        ba, bb = ta.threshold_bin[:nn], tb.threshold_bin[:nn]
        both = (ba >= 0) & (bb >= 0)
        assert np.array_equal(ba[both], bb[both])
        np.testing.assert_allclose(ta.threshold[:nn], tb.threshold[:nn],
                                   rtol=0, atol=0)
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=rtol, atol=atol)


ELIGIBLE = [
    ("plain", {"objective": "binary", "num_leaves": 15}),
    ("bagging", {"objective": "binary", "num_leaves": 15,
                 "bagging_fraction": 0.7, "bagging_freq": 2,
                 "bagging_seed": 5}),
    ("pos_neg_bagging", {"objective": "binary", "num_leaves": 15,
                         "pos_bagging_fraction": 0.8,
                         "neg_bagging_fraction": 0.6, "bagging_freq": 1}),
    ("quantized", {"objective": "binary", "num_leaves": 15,
                   "use_quantized_grad": True}),
    ("colsample", {"objective": "binary", "num_leaves": 15,
                   "feature_fraction": 0.7,
                   "feature_fraction_bynode": 0.8}),
    ("regression", {"objective": "regression", "num_leaves": 15}),
    ("monotone", {"objective": "regression", "num_leaves": 15,
                  "monotone_constraints": [1, -1] + [0] * 8}),
    # Pallas histogram kernel inside the fused program (interpret mode
    # on CPU): growth rides the same sibling-subtraction pipeline, so
    # fused == eager proves the kernel composes with the one-program
    # iteration (tests/test_pallas_hist.py owns numeric parity)
    ("pallas_hist", {"objective": "binary", "num_leaves": 15,
                     "hist_method": "pallas"}),
    ("pallas_quantized", {"objective": "binary", "num_leaves": 15,
                          "hist_method": "pallas",
                          "use_quantized_grad": True}),
    # depth-wise level grower fused into the one-program iteration
    ("level_grower", {"objective": "binary", "num_leaves": 15,
                      "max_depth": 4, "grower": "level"}),
    ("level_pallas", {"objective": "binary", "num_leaves": 15,
                      "max_depth": 4, "grower": "level",
                      "hist_method": "pallas"}),
]


@pytest.mark.parametrize("name,params", ELIGIBLE, ids=[e[0] for e in ELIGIBLE])
def test_fused_matches_eager(name, params, data):
    X, y = data
    yy = X[:, 0] * 2 + X[:, 1] if params["objective"] == "regression" else y
    a = _train(params, X, yy, fused=True)
    b = _train(params, X, yy, fused=False)
    assert a._engine._fused_fn is not None, "fused path did not engage"
    assert b._engine._fused_fn is None
    _assert_same_model(a, b)
    np.testing.assert_allclose(a.predict(X[:400]), b.predict(X[:400]),
                               rtol=1e-5, atol=1e-6)


def test_fused_multiclass_matches_eager(data):
    X, y = data
    y3 = (y + (X[:, 5] > 0)).astype(float)  # 3 well-populated classes
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7}
    a = _train(params, X, y3, fused=True)
    b = _train(params, X, y3, fused=False)
    assert a._engine._fused_fn is not None
    _assert_same_model(a, b)


@pytest.mark.parametrize("params", [
    {"objective": "regression_l1", "num_leaves": 15},   # need_renew
    {"objective": "binary", "boosting": "dart", "num_leaves": 15},
    {"objective": "binary", "data_sample_strategy": "goss",
     "num_leaves": 15},
    {"objective": "binary", "num_leaves": 15, "linear_tree": True},
], ids=["renew-objective", "dart", "goss", "linear-tree"])
def test_ineligible_configs_fall_back_and_train(params, data):
    X, y = data
    yy = X[:, 0] * 2 if params["objective"] == "regression_l1" else y
    bst = _train(params, X, yy, n=5)
    assert bst._engine._fused_fn is None, "fused path must not engage"
    assert len(bst._models) == 5
    assert np.isfinite(bst.predict(X[:100])).all()


def test_ranking_falls_back(data):
    """Ranking objectives mutate host state per iteration (lambdarank
    position biases, xendcg's key counter); under a traced program
    those updates would freeze at trace time — they must stay eager."""
    X, y = data
    group = [300] * 10
    for obj in ("lambdarank", "rank_xendcg"):
        ds = lgb.Dataset(X, label=(y * 3).astype(int), group=group)
        bst = lgb.train({"objective": obj, "num_leaves": 15,
                         "verbosity": -1}, ds, num_boost_round=4)
        assert bst._engine._fused_fn is None, obj
        assert len(bst._models) == 4


def test_valid_sets_fall_back(data):
    X, y = data
    bst = _train({"objective": "binary", "num_leaves": 15}, X, y, n=5,
                 valid=True)
    assert bst._engine._fused_fn is None
    assert len(bst._models) == 5


def test_fused_rollback_and_continue(data):
    """rollback_one_iter after fused iterations, then continue: the
    deferred-tree queue, score, and iteration counter all stay
    consistent (the Booster.rollback API is what network training and
    early-stopping-with-refit use)."""
    X, y = data
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(4):
        bst._engine.train_one_iter()
    assert bst._engine._fused_fn is not None
    bst.rollback_one_iter()
    assert bst.current_iteration() == 3
    for _ in range(2):
        bst._engine.train_one_iter()
    assert bst.current_iteration() == 5
    # equivalent straight-through run of the SAME final tree sequence:
    # iterations 0,1,2 then 3,4 recompute on the rolled-back state
    assert np.isfinite(bst.predict(X[:100])).all()


def test_fused_bagging_toggle_mid_training(data):
    """reset_parameter can switch bagging on mid-training
    (LGBM_BoosterResetParameter); the fused path must evaluate the
    bagging gate live, matching the eager path's per-iteration cfg
    read — not an __init__-time snapshot."""
    X, y = data
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}

    def run(fused):
        if not fused:
            orig = GBDTBooster._fused_ok
            GBDTBooster._fused_ok = lambda self: False
        try:
            bst = lgb.Booster(params=dict(params),
                              train_set=lgb.Dataset(X, label=y))
            for _ in range(3):
                bst._engine.train_one_iter()
            bst.reset_parameter({"bagging_fraction": 0.6,
                                 "bagging_freq": 1})
            for _ in range(3):
                bst._engine.train_one_iter()
            return bst
        finally:
            if not fused:
                GBDTBooster._fused_ok = orig

    a, b = run(True), run(False)
    assert a._engine._fused_fn is not None
    _assert_same_model(a, b)
    # and the toggle actually changed the trees (bagging engaged)
    c = _train(params, X, y, n=6, fused=True)
    assert any(ta.num_leaves != tc.num_leaves
               or not np.allclose(ta.leaf_value, tc.leaf_value)
               for ta, tc in zip(a._models[3:], c._models[3:]))


def test_fused_bynode_reset_mid_training(data):
    """feature_fraction_bynode is baked into the traced grow program;
    reset_parameter must re-trace BOTH paths (refresh grow_cfg, drop
    the cached fused program) so they keep matching."""
    X, y = data

    def run(fused):
        if not fused:
            orig = GBDTBooster._fused_ok
            GBDTBooster._fused_ok = lambda self: False
        try:
            bst = lgb.Booster(
                params={"objective": "binary", "num_leaves": 15,
                        "feature_fraction_bynode": 0.7, "verbosity": -1},
                train_set=lgb.Dataset(X, label=y))
            for _ in range(3):
                bst._engine.train_one_iter()
            bst.reset_parameter({"feature_fraction_bynode": 1.0})
            for _ in range(3):
                bst._engine.train_one_iter()
            return bst
        finally:
            if not fused:
                GBDTBooster._fused_ok = orig

    a, b = run(True), run(False)
    assert a._engine._fused_fn is not None
    _assert_same_model(a, b)


def test_fused_init_model_continuation(data):
    """Training continued from a saved model (init_model) goes through
    preload_models; the fused path must keep producing the same trees
    as an uninterrupted run (keys are folded with the absolute
    iteration index, so the streams line up)."""
    X, y = data
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    full = _train(params, X, y, n=6)
    half = _train(params, X, y, n=3)
    cont = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3,
                     init_model=half)
    assert len(cont._models) == 6
    _assert_same_model(full, cont)


def test_bynode_reset_rebuilds_distributed_grow_fn(data):
    """reset_parameter('feature_fraction_bynode') under mesh training:
    the distributed grow fn bakes grow_cfg + a has_node_key flag at
    build time, so the reset must rebuild it (not just the fused/eager
    paths) — enabling bynode mid-training used to crash with an arity
    mismatch, disabling silently kept sampling."""
    import jax
    if len(jax.devices()) < 2:
        import pytest
        pytest.skip("needs the multi-device CPU mesh")
    X, y = data
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                              "tree_learner": "data", "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(2):
        bst._engine.train_one_iter()
    bst.reset_parameter({"feature_fraction_bynode": 0.6})
    for _ in range(2):
        bst._engine.train_one_iter()
    bst.reset_parameter({"feature_fraction_bynode": 1.0})
    for _ in range(2):
        bst._engine.train_one_iter()
    assert len(bst._models) == 6
    assert np.isfinite(bst.predict(X[:100])).all()
