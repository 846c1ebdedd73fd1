"""Cross-checks between the two growers and the three histogram methods.

The masked grower + scatter histogram is the simple reference
implementation; the compact grower + MXU nibble histogram is the fast
TPU path. They must agree exactly on tree structure (the reference's
cpu-vs-gpu parity tests, tests/python_package_test/test_dual.py, are the
model for this).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.grow import GrowConfig, grow_tree
from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.split import SplitParams


def _mk(n, F, B, seed=0, with_nan_bin=False):
    rs = np.random.RandomState(seed)
    bins = rs.randint(0, B, size=(F, n)).astype(np.uint8)
    g = rs.randn(n).astype(np.float32)
    h = (np.abs(rs.randn(n)) + 0.1).astype(np.float32)
    w = np.ones(n, np.float32)
    fnb = np.full(F, B, np.int32)
    fnan = np.full(F, -1, np.int32)
    if with_nan_bin:
        fnan[::2] = B - 1
    return (jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(w), jnp.ones((F,), bool), jnp.asarray(fnb),
            jnp.asarray(fnan))


@pytest.mark.parametrize("precision", ["default", "high", "highest"])
def test_hist_mxu_matches_scatter(precision):
    rs = np.random.RandomState(3)
    F, n, B = 11, 5000, 67
    bins_T = jnp.asarray(rs.randint(0, B, size=(F, n)).astype(np.uint8))
    g = jnp.asarray(rs.randn(n).astype(np.float32))
    h = jnp.asarray(rs.rand(n).astype(np.float32))
    w = jnp.asarray((rs.rand(n) > 0.3).astype(np.float32) * 1.7)
    mask = jnp.asarray(rs.rand(n) > 0.5)
    a = build_histogram(bins_T, g, h, w, mask, B, "scatter")
    b = build_histogram(bins_T, g, h, w, mask, B, "mxu", precision)
    # single-pass runs bf16 inputs with f32 accumulation — looser bars
    tol = dict(atol=2e-3, rtol=1e-4) if precision != "default" \
        else dict(atol=0.35, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)


def test_hist_mxu_blocked_path():
    """Row counts above ROW_BLOCK exercise the scan accumulation."""
    rs = np.random.RandomState(4)
    F, n, B = 3, 20000, 256
    bins_T = jnp.asarray(rs.randint(0, B, size=(F, n)).astype(np.uint8))
    g = jnp.asarray(rs.randn(n).astype(np.float32))
    h = jnp.asarray(rs.rand(n).astype(np.float32))
    ones = jnp.ones((n,))
    a = build_histogram(bins_T, g, h, ones, ones.astype(bool), B, "scatter")
    b = build_histogram(bins_T, g, h, ones, ones.astype(bool), B, "mxu",
                        "highest")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=4e-3, rtol=1e-4)


@pytest.mark.parametrize("with_nan", [False, True])
def test_compact_grower_matches_masked(with_nan):
    args = _mk(3000, 6, 64, seed=1, with_nan_bin=with_nan)
    cfg_m = GrowConfig(num_leaves=15, num_bins=64,
                       split=SplitParams(min_data_in_leaf=5.0),
                       grower="masked", hist_method="scatter")
    cfg_c = cfg_m._replace(grower="compact")
    tm, rlm = grow_tree(cfg_m, *args)
    tc, rlc = grow_tree(cfg_c, *args)
    assert int(tm.num_leaves) == int(tc.num_leaves)
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "leaf_count", "leaf_parent"):
        np.testing.assert_array_equal(np.asarray(getattr(tm, name)),
                                      np.asarray(getattr(tc, name)),
                                      err_msg=name)
    for name in ("leaf_value", "split_gain", "leaf_weight"):
        np.testing.assert_allclose(np.asarray(getattr(tm, name)),
                                   np.asarray(getattr(tc, name)),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    np.testing.assert_array_equal(np.asarray(rlm), np.asarray(rlc))


def test_compact_grower_weighted_rows():
    """Bagging-style zero/amplified weights flow through the compact
    partition (weighted counts gate splits; raw rows stay in ranges)."""
    (bins, g, h, _, fm, fnb, fnan) = _mk(4000, 5, 32, seed=2)
    rs = np.random.RandomState(9)
    w = jnp.asarray((rs.rand(4000) > 0.4).astype(np.float32) * 1.5)
    cfg_m = GrowConfig(num_leaves=10, num_bins=32,
                       split=SplitParams(min_data_in_leaf=5.0),
                       grower="masked", hist_method="scatter")
    cfg_c = cfg_m._replace(grower="compact")
    tm, rlm = grow_tree(cfg_m, bins, g, h, w, fm, fnb, fnan)
    tc, rlc = grow_tree(cfg_c, bins, g, h, w, fm, fnb, fnan)
    np.testing.assert_array_equal(np.asarray(tm.split_feature),
                                  np.asarray(tc.split_feature))
    np.testing.assert_array_equal(np.asarray(rlm), np.asarray(rlc))
    np.testing.assert_allclose(np.asarray(tm.leaf_value),
                               np.asarray(tc.leaf_value),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("quantized", [False, True])
def test_compact_grower_multi_chunk_windows(quantized):
    """Pin a small streaming chunk so leaf windows span SEVERAL chunks:
    exercises the telescoping scratch appends, the rot alignment and the
    merge write-back of the chunked partition (single-chunk windows
    cannot catch regressions there)."""
    args = _mk(6000, 5, 32, seed=6)
    cfg_m = GrowConfig(num_leaves=12, num_bins=32,
                       split=SplitParams(min_data_in_leaf=5.0),
                       grower="masked", hist_method="scatter",
                       quantized=quantized, stochastic=False)
    cfg_c = cfg_m._replace(grower="compact", chunk=512)
    tm, rlm = grow_tree(cfg_m, *args)
    tc, rlc = grow_tree(cfg_c, *args)
    if quantized:
        # the masked grower has no quantized path; compare the chunked
        # compact grower against the single-chunk compact grower instead
        tc1, rlc1 = grow_tree(cfg_c._replace(chunk=16384), *args)
        tm, rlm = tc1, rlc1
    assert int(tm.num_leaves) == int(tc.num_leaves)
    for name in ("split_feature", "threshold_bin", "leaf_count",
                 "left_child", "right_child"):
        np.testing.assert_array_equal(np.asarray(getattr(tm, name)),
                                      np.asarray(getattr(tc, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(rlm), np.asarray(rlc))
    np.testing.assert_allclose(np.asarray(tm.leaf_value),
                               np.asarray(tc.leaf_value),
                               atol=1e-4, rtol=1e-4)


def test_compact_grower_builds_the_child_of_fewer_rows(monkeypatch):
    """The compact grower's second pass builds the histogram of the child
    the PARTITION counted fewer rows in (ROADMAP S13). The split search's
    counts are hessian-ratio estimates, and with uneven hessians (a rare
    class that fails often) they call the small child the larger one: on
    one chip the estimate no longer decides, and the trees still agree
    with the masked grower's row for row."""
    from lightgbm_tpu.ops import grow
    asked = []
    inner = grow._left_is_smaller

    def spy(n_left, cnt, est_left_small, rows_sharded):
        asked.append(rows_sharded)
        return inner(n_left, cnt, est_left_small, rows_sharded)

    monkeypatch.setattr(grow, "_left_is_smaller", spy)
    # the partition's count where every row is on this device, whatever
    # the search estimated; the estimate where rows are sharded
    assert bool(inner(jnp.int32(3), jnp.int32(10), jnp.bool_(False), False))
    assert not bool(inner(jnp.int32(7), jnp.int32(10), jnp.bool_(True),
                          False))
    assert not bool(inner(jnp.int32(3), jnp.int32(10), jnp.bool_(False),
                          True))
    (bins, g, h, w, fm, fnb, fnan) = _mk(5003, 7, 32, seed=13)
    # a tenth of the rows, those of one column's low bins, carry 40x the
    # hessian of the others: the child that holds them is small in rows
    # and large in hessian
    rare = np.asarray(bins[0]) < 3
    h = jnp.asarray(np.where(rare, 4.0, 0.1).astype(np.float32))
    g = g + jnp.asarray(np.where(rare, 6.0, 0.0).astype(np.float32))
    cfg_m = GrowConfig(num_leaves=14, num_bins=32,
                       split=SplitParams(min_data_in_leaf=5.0),
                       grower="masked", hist_method="scatter")
    cfg_c = cfg_m._replace(grower="compact", chunk=1024)
    tm, rlm = grow_tree(cfg_m, bins, g, h, w, fm, fnb, fnan)
    tc, rlc = grow_tree(cfg_c, bins, g, h, w, fm, fnb, fnan)
    assert asked and not any(asked)
    # the estimate would have chosen otherwise at some node of this tree
    n = int(tc.num_leaves) - 1
    lc, rc = np.asarray(tc.left_child)[:n], np.asarray(tc.right_child)[:n]

    def rows_and_hess(child):       # a child is ~leaf or an internal node
        if child < 0:
            return (float(np.asarray(tc.leaf_count)[~child]),
                    float(np.asarray(tc.leaf_weight)[~child]))
        (a, b), (c, d) = rows_and_hess(lc[child]), rows_and_hess(rc[child])
        return a + c, b + d

    sides = [(rows_and_hess(lc[i]), rows_and_hess(rc[i])) for i in range(n)]
    assert any((l[0] <= r[0]) != (l[1] <= r[1]) for l, r in sides)
    for name in ("split_feature", "threshold_bin", "leaf_count",
                 "left_child", "right_child"):
        np.testing.assert_array_equal(np.asarray(getattr(tm, name)),
                                      np.asarray(getattr(tc, name)),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(rlm), np.asarray(rlc))
    np.testing.assert_allclose(np.asarray(tm.leaf_value),
                               np.asarray(tc.leaf_value),
                               atol=1e-4, rtol=1e-4)


def test_hist_from_rows_int_exact():
    """int8 nibble histogram is exact integer arithmetic."""
    from lightgbm_tpu.ops.histogram import hist_from_rows_int
    rs = np.random.RandomState(5)
    S, F, B = 20000, 5, 130  # crosses ROW_BLOCK=16384, s_hi=9
    rows = rs.randint(0, B, size=(S, F)).astype(np.uint8)
    pay = rs.randint(-4, 5, size=(S, 3)).astype(np.int8)
    out = np.asarray(hist_from_rows_int(jnp.asarray(rows),
                                        jnp.asarray(pay), B))
    ref = np.zeros((F, B, 3), np.int64)
    for f in range(F):
        for c in range(3):
            np.add.at(ref[f, :, c], rows[:, f], pay[:, c])
    np.testing.assert_array_equal(out, ref)
