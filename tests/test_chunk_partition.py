"""The compact grower's chunk partition (ops/grow.py part_apply).

A leaf's window is partitioned K rows at a time by one of two arms the
grower picks from the job's width: ``sort`` (a variadic sort carries
every packed word, the payload and the row ids) up to _SORT_SINGLE_MAX
sort operands, ``wide`` (a (key, iota) sort + ONE row gather a chunk)
past it. These tests pin:
- each arm against the ``masked`` grower, which partitions nothing
  (tests/test_grower_equivalence.py's bar: structure and row
  assignment exact, sums to float32 rounding), at ragged and
  under-one-chunk shapes;
- the two arms against each other, bit for bit, on the same inputs
  (the threshold raised re-takes ``sort`` at the wide width);
- that the chunk size never changes a tree: exact under quantized
  gradients (int32 histograms), structural in float32, where only the
  summation order within a window may differ;
- tracked against untracked rows, and the 4-bit packing.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from conftest import make_synthetic_binary
from lightgbm_tpu.ops.grow import GrowConfig
from lightgbm_tpu.ops.split import SplitParams


def _grow_fresh(cfg, F, n, seed, sort_single_max=None):
    """(tree, row_leaf, plan) of ``cfg`` grown over a random ``[F, n]``
    bin matrix, under a FRESH jit: the module's ``grow_tree`` keys its
    trace on (cfg, shapes), so a call after patching _SORT_SINGLE_MAX
    would re-run the program traced before it, and ``last_plan`` is
    written only while tracing."""
    import lightgbm_tpu.ops.grow as growmod
    rs = np.random.RandomState(seed)
    bins_T = jnp.asarray(rs.randint(0, 64, size=(F, n), dtype=np.uint8))
    grad = jnp.asarray(rs.randn(n).astype(np.float32))
    hess = jnp.asarray((np.abs(rs.randn(n)) + 0.1).astype(np.float32))
    growmod.last_plan.clear()
    with pytest.MonkeyPatch.context() as mp:
        if sort_single_max is not None:
            mp.setattr(growmod, "_SORT_SINGLE_MAX", sort_single_max)
        tree, row_leaf = jax.jit(
            functools.partial(growmod.grow_tree_impl, cfg))(
            bins_T, grad, hess, jnp.ones((n,), jnp.float32),
            jnp.ones((F,), bool), jnp.full((F,), 64, jnp.int32),
            jnp.full((F,), -1, jnp.int32))
    return (jax.tree_util.tree_map(np.asarray, tree),
            np.asarray(row_leaf), dict(growmod.last_plan))


def _cfg(**fields):
    return GrowConfig(**{**dict(
        num_leaves=31, num_bins=64,
        split=SplitParams(min_data_in_leaf=20.0), hist_method="scatter",
        grower="compact", chunk=512), **fields})


def _assert_equals_masked(t_m, rl_m, t_g, rl_g):
    """tests/test_grower_equivalence.py's bar."""
    assert int(t_m.num_leaves) == int(t_g.num_leaves)
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "leaf_count", "leaf_parent"):
        np.testing.assert_array_equal(getattr(t_m, name),
                                      getattr(t_g, name), err_msg=name)
    for name in ("leaf_value", "split_gain", "leaf_weight"):
        np.testing.assert_allclose(getattr(t_m, name), getattr(t_g, name),
                                   atol=1e-4, rtol=1e-4, err_msg=name)
    np.testing.assert_array_equal(rl_m, rl_g)


@pytest.mark.parametrize("track", [False, True],
                         ids=["untracked", "tracked"])
@pytest.mark.parametrize("n,chunk", [(1000, 512), (4096, 512),
                                     (777, 256), (513, 1024)])
def test_grower_sort_equals_masked(n, chunk, track):
    """The ``sort`` arm at the narrow width it ships for (F=9: three
    packed words + the float32 pair [+ the row ids]): several chunks a
    window, a ragged last chunk, and a root window under one chunk
    (513 rows in a 1,024-row chunk: every write is one partial block
    reaching into the halves' PAD)."""
    t_s, rl_s, plan = _grow_fresh(_cfg(chunk=chunk, track_rows=track),
                                  9, n, seed=0)
    assert plan == {"partition": "sort", "payload": "f32"}
    t_m, rl_m, plan_m = _grow_fresh(_cfg(grower="masked"), 9, n, seed=0)
    assert plan_m == {}
    _assert_equals_masked(t_m, rl_m, t_s, rl_s)


@pytest.mark.parametrize("track", [False, True],
                         ids=["untracked", "tracked"])
@pytest.mark.parametrize("arm,F", [("sort", 8), ("wide", 64)])
def test_chunk_size_never_changes_a_quantized_tree_in_either_arm(
        arm, F, track):
    """Chunking decides only where a window's rows sit within it (the
    rights of a window are packed backward, chunk by chunk); with int32
    histograms no sum can see that, so 20 chunks of 256 rows and 5 of
    1,024 must grow the same tree bit for bit, and leave every row in
    the same leaf."""
    grown = [_grow_fresh(_cfg(chunk=chunk, track_rows=track,
                              quantized=True, stochastic=False),
                         F, 5003, seed=3) for chunk in (256, 1024)]
    (t_a, rl_a, plan_a), (t_b, rl_b, plan_b) = grown
    assert plan_a == plan_b == {"partition": arm, "payload": "int8"}
    assert np.array_equal(rl_a, rl_b)
    for name, a, b in zip(t_a._fields, t_a, t_b):
        assert np.array_equal(a, b), name


def _bagged_cat_table():
    rs = np.random.RandomState(9)
    Xn, y = make_synthetic_binary(n=5000, f=6, seed=9)
    cat = rs.randint(0, 12, size=(5000, 1)).astype(np.float64)
    return (np.hstack([Xn, cat]),
            np.where((cat[:, 0] > 6) ^ (y > 0), 1.0, 0.0))


_QUANT = {"use_quantized_grad": True, "stochastic_rounding": False}
# name: (table, params, categorical columns, exact?)
_TRAIN_CASES = {
    "quantized": (lambda: make_synthetic_binary(n=6000, f=8, seed=3),
                  dict(_QUANT, num_leaves=31, min_data_in_leaf=5),
                  "auto", True),
    # float32 histograms: the summation order within a window follows
    # the chunking, so trees agree structurally on well-separated data
    "float": (lambda: make_synthetic_binary(n=6000, f=8, seed=4),
              dict(num_leaves=31, min_data_in_leaf=5), "auto", False),
    # row ids and in-bag bits ride the partition (track_rows), and a
    # categorical split routes by its bin set
    "bagging_cat_quantized": (
        _bagged_cat_table,
        dict(_QUANT, num_leaves=15, bagging_fraction=0.7, bagging_freq=1,
             seed=5), [6], True),
}


@pytest.mark.parametrize("case", list(_TRAIN_CASES))
def test_chunk_rows_never_changes_a_tree(case):
    """The same through ``lgb.train``: ``chunk_rows`` 256 against 1,024,
    five rounds."""
    table, params, cats, exact = _TRAIN_CASES[case]
    X, y = table()
    b256, b1024 = (lgb.train(
        {"objective": "binary", "verbosity": -1, "chunk_rows": k,
         **params},
        lgb.Dataset(X, label=y, categorical_feature=cats),
        num_boost_round=5) for k in (256, 1024))
    for t0, t1 in zip(b256._models, b1024._models):
        np.testing.assert_array_equal(t0.split_feature, t1.split_feature)
        np.testing.assert_array_equal(t0.threshold, t1.threshold)
        if exact:
            np.testing.assert_array_equal(t0.leaf_value, t1.leaf_value)
    if exact:
        np.testing.assert_array_equal(b256.predict(X), b1024.predict(X))
    else:
        np.testing.assert_allclose(b256.predict(X), b1024.predict(X),
                                   rtol=2e-5, atol=1e-7)


def test_a_retired_chunk_parameter_is_kept_as_unknown_and_changes_nothing():
    """A job that still passes the bulk-batching knob PR 31 deleted is
    treated as one passing any unknown parameter: it lands in
    ``Config.extra``, the job trains, and the trees are those of the
    job without it. (Spelled in parts so that a search of the tree for
    the retired name finds no user of it.)"""
    from lightgbm_tpu.config import Config
    retired = "_".join(("big", "chunk", "rows"))
    X, y = make_synthetic_binary(n=3000, f=8, seed=6)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "chunk_rows": 256, **_QUANT}
    assert Config.from_params({**params, retired: 1024}).extra \
        == {retired: 1024}
    with_it, without = (lgb.train(p, lgb.Dataset(X, label=y),
                                  num_boost_round=3)
                        for p in ({**params, retired: 1024}, params))
    for t0, t1 in zip(with_it._models, without._models):
        np.testing.assert_array_equal(t0.split_feature, t1.split_feature)
        np.testing.assert_array_equal(t0.threshold, t1.threshold)
        np.testing.assert_array_equal(t0.leaf_value, t1.leaf_value)
    np.testing.assert_array_equal(with_it.predict(X), without.predict(X))


def test_grower_nibble_packed_low_bin():
    """B <= 16 streams bins at 8 columns per u32 word (the 4-bit
    DenseBin analog); the packed path must match the scatter-method
    masked grower tree-for-tree."""
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
    grower."""
    fields, (F, n) = _WIDE_CASES[name]
    return _grow_fresh(
        _cfg(**{"grower": "masked" if variant == "masked" else "compact",
                **fields}),
        F, n, seed=7,
        sort_single_max=10_000 if variant == "sort" else None)


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
    assert int(t_g.num_leaves) == 31
    _assert_equals_masked(t_m, rl_m, t_g, rl_g)


def test_untracked_rows_bit_identical_to_tracked():
    """GrowConfig.track_rows=False (plain full-data path, round 4)
    drops the ord2 sort column; under quantized gradients the grown
    tree AND row_leaf must be bit-identical to the tracked path."""
    from lightgbm_tpu.ops.grow import grow_tree

    rs = np.random.RandomState(2)
    n, f, B = 5000, 6, 64
    bins_T = jnp.asarray(rs.randint(0, B - 1, size=(f, n)), jnp.uint8)
    y = (np.asarray(bins_T)[0] > 30).astype(np.float32)
    grad = jnp.asarray(0.5 - y + 0.1 * rs.randn(n).astype(np.float32))
    hess = jnp.full((n,), 0.25, jnp.float32)
    ones = jnp.ones((n,), jnp.float32)
    fmask = jnp.ones((f,), bool)
    fnb = jnp.full((f,), B - 1, jnp.int32)
    fnan = jnp.full((f,), -1, jnp.int32)
    outs = {}
    for track in (True, False):
        cfg = GrowConfig(num_leaves=31, num_bins=B,
                         split=SplitParams(min_data_in_leaf=5),
                         hist_method="scatter", quantized=True,
                         stochastic=False, track_rows=track)
        tree, row_leaf = grow_tree(cfg, bins_T, grad, hess, ones,
                                   fmask, fnb, fnan)
        outs[track] = (tree, row_leaf)
    t1, rl1 = outs[True]
    t0, rl0 = outs[False]
    np.testing.assert_array_equal(np.asarray(rl1), np.asarray(rl0))
    for a, b in zip(t1, t0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
