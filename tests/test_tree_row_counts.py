"""The rows a tree's layers streamed, counted where the tree reaches the
host (``models/gbdt.py _rows_streamed``, ISSUE 37): the ``tree/fetch``
span's ``hist_rows`` / ``partition_rows`` on the eager path, against the
same sums made plainly from the dumped model's counts. CPU, small jobs."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import _rows_streamed
from lightgbm_tpu.obs import trace
from lightgbm_tpu.utils.timer import Timer

ROWS, ROUNDS = 3000, 4


def _table(seed=0, nan_share=0.0):
    rs = np.random.RandomState(seed)
    X = rs.randn(ROWS + 600, 10).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + X[:, 2] > 0).astype(float)
    if nan_share:
        X[rs.rand(*X.shape) < nan_share] = np.nan
    return (X[:ROWS], y[:ROWS]), (X[ROWS:], y[ROWS:])


def _plain(tree_json):
    """``(hist_rows, partition_rows)`` of one dumped tree: the root's
    rows and every split's smaller child; every split's parent."""
    root = tree_json["tree_structure"]

    def rows(node):
        return node["internal_count"] if "split_index" in node \
            else node["leaf_count"]

    hist, part, stack = rows(root), 0, [root]
    while stack:
        node = stack.pop()
        if "split_index" in node:
            kids = [node["left_child"], node["right_child"]]
            hist += min(rows(k) for k in kids)
            part += node["internal_count"]
            stack += kids
    return hist, part


PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "verbose": -1}


@pytest.mark.parametrize("case", ["dense", "missing"])
def test_every_fetched_tree_says_what_rows_it_streamed(case):
    """The eager path (a validation set) under ``Timer.enable()``: one
    ``tree/fetch`` span a round, each with the two attrs, equal to the
    plain sums of the dumped tree."""
    (X, y), (Xv, yv) = _table(1, nan_share=0.3 if case == "missing" else 0)
    params = dict(PARAMS, metric="auc")
    train = lgb.Dataset(X, label=y)
    Timer.enable()
    try:
        bst = lgb.train(params, train, ROUNDS,
                        valid_sets=[lgb.Dataset(Xv, label=yv,
                                                reference=train)])
    finally:
        Timer.enable(False)
    spans = [s for s in trace.span_events_snapshot()
             if s["name"] == "tree/fetch"]
    want = [_plain(t) for t in bst.dump_model()["tree_info"]]
    assert len(spans) == len(want) >= 1
    assert [s["attrs"] for s in spans] == [
        {"hist_rows": h, "partition_rows": p} for h, p in want]
    # a split tree: the root's rows, then the smaller child of each split
    assert all(ROWS < h <= ROWS + p // 2 for h, p in want)


@pytest.mark.parametrize("case", ["grown", "stump"])
def test_the_count_is_the_trees_own_and_costs_nothing_untraced(case):
    """No capture, no validation set: the trees stay on the device, no
    ``tree/fetch`` span opens and nothing is counted. Asked of the trees
    ``dump_model`` materialised, the count is the same plain sums; a tree
    that could not split streamed its root and partitioned nothing."""
    (X, y), _ = _table(2)
    params = dict(PARAMS)
    if case == "stump":         # no leaf may split: a tree of one leaf
        params["min_data_in_leaf"] = ROWS
    bst = lgb.train(params, lgb.Dataset(X, label=y), ROUNDS)
    assert not [s for s in trace.span_events_snapshot()
                if s["name"] == "tree/fetch"]
    want = [_plain(t) for t in bst.dump_model()["tree_info"]]
    if case == "stump":         # training stops at the first of them
        assert set(want) == {(ROWS, 0)}
    else:
        assert len(want) == ROUNDS
    got = [_rows_streamed(t) for t in bst._engine.models]
    assert [(r["hist_rows"], r["partition_rows"]) for r in got] == want
