"""The comparison's own arithmetic on trees small enough to do by hand:
which trees are followed, which deep nodes are looked at, the gaps at
the worst and at the median leaf, the best split of a node's rows, and
what a limit of ``null`` means."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from harness import check  # noqa: E402
from harness import reference as R  # noqa: E402

# internal nodes 0..2, leaves 3..6:   0 -> (1, 2); 1 -> (3, 4); 2 -> (5, 6)
TREE = {"num_leaves": 4,
        "left": np.array([1, 3, 5]), "right": np.array([2, 4, 6]),
        "order": np.array([0, 1, 2])}


@pytest.mark.parametrize("n_trees,warm,k,want", [
    (5, 2, 3, [2, 3, 4]),          # a window of three: all of them
    (12, 2, 3, [2, 6, 11]),        # first, middle, last
    (4, 2, 3, [2, 3]),             # fewer trees than asked for
    (3, 2, 3, [2]),
    (10, 0, 1, [0]),
])
def test_followed_trees_are_the_windows_first_last_and_between(
        n_trees, warm, k, want):
    got = check.followed_trees(n_trees, warm, k)
    assert got == want
    assert all(t >= warm for t in got)


def test_subtree_leaves_and_depths():
    assert R.subtree_leaves(TREE, 0).tolist() == [True] * 4
    assert R.subtree_leaves(TREE, 1).tolist() == [True, True, False, False]
    assert R.subtree_leaves(TREE, 2).tolist() == [False, False, True, True]
    assert R.node_depths(TREE).tolist() == [0, 1, 1]


def test_deep_nodes_are_drawn_from_the_seed_below_the_root():
    counts = np.array([1000, 600, 400])
    a = check.deep_nodes(TREE, counts, seed=5, ti=2, k=3, min_rows=500,
                         min_depth=1)
    assert a == [1]                         # node 2 holds too few rows
    both = check.deep_nodes(TREE, counts, 5, 2, 3, 100, min_depth=1)
    assert both == [1, 2]
    assert check.deep_nodes(TREE, counts, 5, 2, 3, 100, min_depth=2) == []
    one = [check.deep_nodes(TREE, counts, s, 2, 1, 100, min_depth=1)
           for s in range(40)]
    assert {tuple(o) for o in one} == {(1,), (2,)}
    assert one == [check.deep_nodes(TREE, counts, s, 2, 1, 100, min_depth=1)
                   for s in range(40)]


def test_worst_and_median_gap_share_one_denominator():
    want = np.array([1.0, 2.0, 4.0, 100.0, 200.0])
    got = want + np.array([0.4, 0.0, 0.04, 1.0, -4.0])
    # the median leaf is 4: smaller leaves are held against it
    gaps = check._gaps(got, want)
    assert gaps == pytest.approx([0.1, 0.0, 0.01, 0.01, 0.02])
    assert check._rel_gap(got, want) == pytest.approx(0.1)
    assert check._median_gap(got, want) == pytest.approx(0.01)
    # a leaf the program did not produce is the widest gap there is
    assert check._rel_gap(np.array([1.0, np.nan]), np.array([1.0, 1.0])) \
        == np.inf


def test_an_inherited_error_moves_the_worst_leaf_and_not_the_median():
    rng = np.random.default_rng(3)
    want = rng.uniform(1e3, 1e4, 255)
    one_off = want.copy()
    one_off[np.argmax(want)] *= 1.0 + 6e-4   # the end of a subtraction chain
    lowered = want * (1.0 + rng.uniform(3e-4, 1e-3, 255))   # every leaf
    assert check._rel_gap(one_off, want) > 3e-4
    assert check._median_gap(one_off, want) == 0.0
    assert check._median_gap(lowered, want) > 1e-4


def test_node_best_gain_against_a_loop():
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    n, F, K = 700, 3, 5
    X = rng.normal(size=(n, F)).astype(np.float32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 0.25, n).astype(np.float32)
    w = (rng.uniform(size=n) < 0.4).astype(np.float32)
    cands = np.sort(rng.normal(size=(F, K)).astype(np.float32), axis=1)
    cands[2, 3:] = np.inf               # unused slots
    lam, min_data, min_hess = 0.0, 20.0, 1e-3

    def by_hand(w):
        best = np.full(F, -np.inf)
        m = w > 0
        G, H = g[m].sum(), h[m].sum()
        for f in range(F):
            for c in cands[f]:
                left = m & (X[:, f] <= c)
                right = m & ~(X[:, f] <= c)
                if min(left.sum(), right.sum()) < min_data:
                    continue
                gain = g[left].sum() ** 2 / h[left].sum() \
                    + g[right].sum() ** 2 / h[right].sum() - G * G / H
                best[f] = max(best[f], gain)
        return best

    for rows in (np.ones(n, np.float32), w):
        got = np.asarray(R.node_best_gain(
            jnp.asarray(X.T), jnp.asarray(cands), jnp.asarray(g),
            jnp.asarray(h), jnp.asarray(rows), jnp.float32(min_data),
            jnp.float32(min_hess), jnp.float32(lam), block=256))
        assert got == pytest.approx(by_hand(rows), rel=2e-4, abs=1e-5)


def test_rows_under_a_node():
    import jax.numpy as jnp
    at = jnp.asarray([3, 4, 5, 6, 6, 3])          # leaf ids after the tree
    under = jnp.asarray(R.subtree_leaves(TREE, 2))
    assert np.asarray(R.rows_under(at, under, 3)).tolist() \
        == [0.0, 0.0, 1.0, 1.0, 1.0, 0.0]


def numbers(**over):
    out = {name: 0.0 for name in check.NUMBERS}
    out.update(over)
    return out


def test_a_null_limit_is_printed_and_not_compared():
    limits = {name: 0.5 for name in check.NUMBERS}
    limits["deep_split_shortfall"] = None
    ok, table = check.judge(numbers(deep_split_shortfall=9.0), limits)
    assert ok and table["deep_split_shortfall"] == {"value": 9.0,
                                                    "limit": None}
    ok, _ = check.judge(numbers(score_gap=0.6), limits)
    assert not ok


@pytest.mark.parametrize("value", [None, float("nan"), float("inf")])
def test_a_compared_number_that_is_missing_fails(value):
    limits = {name: 0.5 for name in check.NUMBERS}
    ok, _ = check.judge(numbers(root_split_shortfall=value), limits)
    assert not ok


def test_every_number_needs_an_entry_in_the_limits():
    limits = {name: 0.5 for name in check.NUMBERS[1:]}
    with pytest.raises(KeyError, match="no limit for"):
        check.judge(numbers(), limits)
