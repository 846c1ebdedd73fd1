"""Butterfly-route partition (ops/grow.py route_concentrate).

The compact grower's in-chunk stable partition ships as LSB-first
butterfly concentration routing (GrowConfig.partition="route"); the
variadic-sort path remains as "sort". These tests pin:
- the routing primitive against a host-side stable compaction, across
  exhaustive small chunks and randomized large ones (the
  congestion-freedom of order-preserving partial routes is a theorem,
  but the implementation's bit plumbing is what can rot);
- tree-for-tree equality of the two partition modes through the full
  grower, the same equivalence bar tests/test_grower_equivalence.py
  holds the masked/compact pair to.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.grow import GrowConfig, grow_tree, route_concentrate
from lightgbm_tpu.ops.split import SplitParams


def _host_route(mark, col, offset):
    out = np.full(col.shape, -1, col.dtype)
    out[offset:offset + mark.sum()] = col[mark]
    return out


@pytest.mark.parametrize("k", [2, 4, 8])
def test_route_concentrate_exhaustive_small(k):
    f = jax.jit(route_concentrate)
    for bits in range(2 ** k):
        mark = np.array([(bits >> i) & 1 for i in range(k)], bool)
        cnt = int(mark.sum())
        for offset in (0, (k - cnt) // 2, k - cnt):
            col = np.arange(k, dtype=np.int32)
            (out,) = f((jnp.asarray(col),), jnp.asarray(mark),
                       jnp.int32(offset))
            got = np.asarray(out)[offset:offset + cnt]
            want = col[mark]
            assert np.array_equal(got, want), (k, bits, offset)


def test_route_concentrate_randomized_large():
    rs = np.random.RandomState(7)
    for _ in range(40):
        k = 2 ** rs.randint(5, 13)
        mark = rs.rand(k) < rs.rand()
        cnt = int(mark.sum())
        offset = int(rs.randint(0, k - cnt + 1))
        cols = (np.arange(k, dtype=np.int32),
                rs.randint(0, 2 ** 31, size=k).astype(np.uint32),
                rs.randn(k).astype(np.float32))
        outs = route_concentrate(tuple(jnp.asarray(c) for c in cols),
                                 jnp.asarray(mark), jnp.int32(offset))
        sel = slice(offset, offset + cnt)
        for c, o in zip(cols, outs):
            assert np.array_equal(np.asarray(o)[sel], c[mark])


def test_route_pair_kernel_matches_xla_route():
    """The Pallas pair kernel (ops/partition_kernel.py route_pair) in
    interpret mode against the XLA route — the oracle relationship the
    module docstring promises. (On the v5e the kernel compiles and
    matches — chip_smoke.py --kernels, PR 21; no config reaches it.)"""
    from lightgbm_tpu.ops.partition_kernel import (route_pair,
                                                   stack_cols,
                                                   unstack_cols)
    rs = np.random.RandomState(11)
    for k in (256, 1024):
        cols = (jnp.asarray(rs.randint(0, 2 ** 31, size=k)
                            .astype(np.uint32)),
                jnp.asarray(rs.randn(k).astype(np.float32)))
        r = rs.rand(k)
        vl = jnp.asarray(r < 0.35)
        vr = jnp.asarray((r >= 0.35) & (r < 0.9))
        rc = int(np.sum((r >= 0.35) & (r < 0.9)))
        lc = int(np.sum(r < 0.35))
        A, spec = stack_cols(cols)
        L, R = route_pair(A, vl, vr, interpret=True)
        lops = unstack_cols(L, spec)
        rops = unstack_cols(R, spec)
        l_ref = route_concentrate(cols, vl, jnp.int32(0))
        r_ref = route_concentrate(cols, vr, jnp.int32(k - rc))
        for a, b in zip(lops, l_ref):
            assert np.array_equal(np.asarray(a)[:lc],
                                  np.asarray(b)[:lc])
        for a, b in zip(rops, r_ref):
            assert np.array_equal(np.asarray(a)[k - rc:],
                                  np.asarray(b)[k - rc:])


def _grow(partition, bins_T, grad, hess, num_leaves=31, chunk=512,
          quantized=False):
    F = bins_T.shape[0]
    cfg = GrowConfig(num_leaves=num_leaves, num_bins=64,
                     split=SplitParams(), hist_method="scatter",
                     grower="compact", chunk=chunk, partition=partition,
                     quantized=quantized)
    n = bins_T.shape[1]
    return grow_tree(cfg, bins_T, grad, hess,
                     jnp.ones((n,), jnp.float32),
                     jnp.ones((F,), bool),
                     jnp.full((F,), 64, jnp.int32),
                     jnp.full((F,), -1, jnp.int32),
                     quant_key=(jax.random.PRNGKey(3) if quantized
                                else None))


@pytest.mark.parametrize("n,chunk", [(1000, 512), (4096, 512),
                                     (777, 256), (513, 1024)])
def test_grower_route_equals_sort(n, chunk):
    rs = np.random.RandomState(0)
    F = 9
    bins_T = jnp.asarray(rs.randint(0, 64, size=(F, n), dtype=np.uint8))
    grad = jnp.asarray(rs.randn(n).astype(np.float32))
    hess = jnp.asarray((np.abs(rs.randn(n)) + 0.1).astype(np.float32))
    t_r, rl_r = _grow("route", bins_T, grad, hess, chunk=chunk)
    t_s, rl_s = _grow("sort", bins_T, grad, hess, chunk=chunk)
    assert np.array_equal(np.asarray(rl_r), np.asarray(rl_s))
    for a, b in zip(t_r, t_s):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_grower_route_equals_sort_quantized():
    rs = np.random.RandomState(1)
    F, n = 6, 2000
    bins_T = jnp.asarray(rs.randint(0, 64, size=(F, n), dtype=np.uint8))
    grad = jnp.asarray(rs.randn(n).astype(np.float32))
    hess = jnp.asarray((np.abs(rs.randn(n)) + 0.1).astype(np.float32))
    t_r, rl_r = _grow("route", bins_T, grad, hess, quantized=True)
    t_s, rl_s = _grow("sort", bins_T, grad, hess, quantized=True)
    assert np.array_equal(np.asarray(rl_r), np.asarray(rl_s))
    for a, b in zip(t_r, t_s):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_grower_nibble_packed_low_bin():
    """B <= 16 streams bins at 8 columns per u32 word (the 4-bit
    DenseBin analog); the packed path must match the scatter-method
    masked grower tree-for-tree."""
    import lightgbm_tpu as lgb
    rs = np.random.RandomState(5)
    n = 3000
    X = rs.randn(n, 7)
    y = ((X[:, 0] - 0.5 * X[:, 1]) > 0).astype(float)
    base = {"objective": "binary", "num_leaves": 31, "max_bin": 15,
            "min_data_in_leaf": 5, "verbosity": -1}
    compact = lgb.train({**base, "grower": "compact"},
                        lgb.Dataset(X, label=y), num_boost_round=4)
    masked = lgb.train({**base, "grower": "masked"},
                       lgb.Dataset(X, label=y), num_boost_round=4)
    np.testing.assert_allclose(compact.predict(X[:400]),
                               masked.predict(X[:400]), rtol=1e-5)


# The wide partition's cases (grow.py make_body, the ``wide_part`` arm).
# F=64 u8 columns -> NW=16 packed words: with the two payload operands
# that is past _SORT_SINGLE_MAX, so the gather path engages at the
# default threshold. Each case: GrowConfig fields, then (F, n).
_WIDE_CASES = {
    # float32 payload, no row tracking: the words and the two payload
    # words are all the gathered row holds
    "plain": (dict(track_rows=False), (64, 5000)),
    # + ord2 (bagging / GOSS / EFB): ord sits behind the payload words
    "tracked": (dict(track_rows=True), (64, 4096)),
    # the benchmark cell's histogram: the MXU kernel reads the [CK, 2]
    # block the two planar slices stack
    "mxu_high": (dict(track_rows=False, hist_method="mxu",
                      hist_precision="high"), (64, 5000)),
    "tracked_mxu_high": (dict(track_rows=True, hist_method="mxu",
                              hist_precision="high"), (64, 4096)),
    # the Criteo width (NW=17, three pad columns in the last word) and a
    # row count that is no multiple of the chunk
    "criteo_width_ragged": (dict(track_rows=False, hist_method="mxu",
                                 hist_precision="high"), (67, 5003)),
    # fewer histogram slots than leaves: the pool-miss window_hist
    # re-reads a leaf's window of the payload
    "pooled": (dict(track_rows=False, hist_pool_slots=4), (64, 5000)),
    # the one-word int8 pair shares the arm's concatenate and gather
    # (sort A/B only: the masked grower does not quantize as this does)
    "int8": (dict(track_rows=True, quantized=True, stochastic=False),
             (64, 4096)),
    # The shifted right-write (PR 30: ONE gather a chunk, the rights
    # placed by the write's offset E - r_off - r_c - l_c). chunk=256
    # over 5,003 rows: many chunks a window and a ragged last one, leaf
    # windows of 1..K+-1 rows beside live neighbours on both sides, so a
    # lane written outside [l_c, l_c + r_c) or a clamped offset shows as
    # a changed tree
    "small_chunk_ragged": (dict(track_rows=False, chunk=256),
                           (67, 5003)),
    # ... with ord2 a third folded column, shifted with the rest
    "small_chunk_ragged_tracked": (dict(track_rows=True, chunk=256),
                                   (67, 5003)),
    # the root window under one chunk: every write of the tree is one
    # partial block reaching into the halves' PAD
    "under_one_chunk": (dict(track_rows=True, chunk=1024), (67, 1000)),
}


@functools.lru_cache(maxsize=None)
def _wide_case(name, variant):
    """(tree, row_leaf, plan) of one case grown ``wide`` (as shipped),
    by the variadic ``sort`` (threshold raised) or by the ``masked``
    grower. Each under a FRESH jit: the module's ``grow_tree`` keys its
    trace on (cfg, shapes), so a call after patching _SORT_SINGLE_MAX
    would re-run the program traced before it."""
    import lightgbm_tpu.ops.grow as growmod
    fields, (F, n) = _WIDE_CASES[name]
    rs = np.random.RandomState(7)
    bins_T = jnp.asarray(rs.randint(0, 64, size=(F, n), dtype=np.uint8))
    grad = jnp.asarray(rs.randn(n).astype(np.float32))
    hess = jnp.asarray((np.abs(rs.randn(n)) + 0.1).astype(np.float32))
    cfg = GrowConfig(**{**dict(
        num_leaves=31, num_bins=64,
        split=SplitParams(min_data_in_leaf=20.0), hist_method="scatter",
        grower="masked" if variant == "masked" else "compact",
        chunk=512, partition="sort"), **fields})
    growmod.last_plan.clear()
    with pytest.MonkeyPatch.context() as mp:
        if variant == "sort":
            mp.setattr(growmod, "_SORT_SINGLE_MAX", 10_000)
        tree, row_leaf = jax.jit(
            functools.partial(growmod.grow_tree_impl, cfg))(
            bins_T, grad, hess, jnp.ones((n,), jnp.float32),
            jnp.ones((F,), bool), jnp.full((F,), 64, jnp.int32),
            jnp.full((F,), -1, jnp.int32))
    return (jax.tree_util.tree_map(np.asarray, tree),
            np.asarray(row_leaf), dict(growmod.last_plan))


@pytest.mark.parametrize("case", list(_WIDE_CASES))
def test_grower_wide_gather_equals_sort(case):
    """The wide partition (sort (key, iota) + ONE row gather a chunk of
    the packed words with the payload's words behind them) must be
    bit-identical to the payload-carrying sort it replaces past
    _SORT_SINGLE_MAX operands; forcing the threshold sky-high re-takes
    the sort path on the identical inputs. The float32 payload is held
    planar (1-D, all g then all h) on the wide side and as [rows, 2] on
    the sort side: data movement only, so not one bit may differ."""
    t_g, rl_g, plan_g = _wide_case(case, "wide")
    t_s, rl_s, plan_s = _wide_case(case, "sort")
    int8 = case == "int8"
    assert plan_g == {"partition": "wide",
                      "payload": "int8" if int8 else "f32-planar"}
    assert plan_s == {"partition": "sort",
                      "payload": "int8" if int8 else "f32"}
    assert np.array_equal(rl_g, rl_s)
    for name, a, b in zip(t_g._fields, t_g, t_s):
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("case", [c for c in _WIDE_CASES if c != "int8"])
def test_grower_wide_gather_equals_masked(case):
    """... and equal to the masked grower's tree (which partitions
    nothing) by tests/test_grower_equivalence.py's bar: structure and
    row assignment exact, sums to float32 rounding."""
    t_g, rl_g, _ = _wide_case(case, "wide")
    t_m, rl_m, plan_m = _wide_case(case, "masked")
    assert plan_m == {}
    assert int(t_m.num_leaves) == int(t_g.num_leaves) == 31
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "leaf_count", "leaf_parent"):
        np.testing.assert_array_equal(getattr(t_m, name),
                                      getattr(t_g, name), err_msg=name)
    for name in ("leaf_value", "split_gain", "leaf_weight"):
        np.testing.assert_allclose(getattr(t_m, name), getattr(t_g, name),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    np.testing.assert_array_equal(rl_m, rl_g)
