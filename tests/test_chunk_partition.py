"""The compact grower's chunk partition (ops/grow.py part_apply).

A leaf's window is partitioned K rows at a time by one of two arms the
grower picks from the job's width: ``sort`` (a variadic sort carries
every packed word, the payload and the row ids) up to _SORT_SINGLE_MAX
sort operands, ``wide`` (a (key, iota) sort that also carries what is
one value a row: the payload's columns and the row ids; + ONE row gather
a chunk of the packed words) past it. These tests pin:
- each arm against the ``masked`` grower, which partitions nothing
  (tests/test_grower_equivalence.py's bar: structure and row
  assignment exact, sums to float32 rounding), at ragged and
  under-one-chunk shapes;
- the two arms against each other, bit for bit, on the same inputs
  (the threshold raised re-takes ``sort`` at the wide width);
- that the chunk size never changes a tree: exact under quantized
  gradients (int32 histograms), structural in float32, where only the
  summation order within a window may differ;
- tracked against untracked rows, and the 4-bit packing;
- what the wide arm's sort carries (``last_plan["sort_operands"]``, and
  the traced sort itself), and that it moves those columns bit for bit.
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
    assert plan == {"partition": "sort", "payload": "f32",
                    "sort_operands": 6 + track}   # key, 3 words, g, h
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
    # the key, the sort arm's 2 words or the wide arm's iota, the pair
    assert plan_a == plan_b == {
        "partition": arm, "payload": "int8",
        "sort_operands": (4 if arm == "sort" else 3) + track}
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


# The wide partition's cases (grow.py part_apply.body, the ``wide_part``
# arm). F=64 u8 columns -> NW=16 packed words: with the two payload
# operands that is past _SORT_SINGLE_MAX, so the gather path engages at
# the default threshold. Each case: GrowConfig fields, then (F, n).
_WIDE_CASES = {
    # float32 payload, no row tracking: the sort carries key, iota, g, h
    "plain": (dict(track_rows=False), (64, 5000)),
    # + ord2 (bagging / GOSS / EFB): a fifth sort operand
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
    # the one-word int8 pair rides the sort as ONE u16 operand
    # (sort A/B only: the masked grower does not quantize as this does)
    "int8": (dict(track_rows=True, quantized=True, stochastic=False),
             (64, 4096)),
    # ... and alone behind key and iota: three operands, the fewest
    "int8_untracked": (dict(track_rows=False, quantized=True,
                            stochastic=False), (64, 4096)),
    # The shifted right-write (PR 30: ONE gather a chunk, the rights
    # placed by the write's offset E - r_off - r_c - l_c). chunk=256
    # over 5,003 rows: many chunks a window and a ragged last one, leaf
    # windows of 1..K+-1 rows beside live neighbours on both sides, so a
    # lane written outside [l_c, l_c + r_c) or a clamped offset shows as
    # a changed tree
    "small_chunk_ragged": (dict(track_rows=False, chunk=256),
                           (67, 5003)),
    # ... with ord2 a sorted column too, shifted with the rest
    "small_chunk_ragged_tracked": (dict(track_rows=True, chunk=256),
                                   (67, 5003)),
    # the root window under one chunk: every write of the tree is one
    # partial block reaching into the halves' PAD
    "under_one_chunk": (dict(track_rows=True, chunk=1024), (67, 1000)),
}


def _wide_plans(case):
    """What ``last_plan`` must read for a case grown ``wide`` and by the
    ``sort`` arm: the wide sort carries key, iota, the payload's columns
    (two float32, or the int8 pair's one word) and ord when tracked;
    the sort arm carries the NW word columns in iota's place."""
    fields, (F, _) = _WIDE_CASES[case]
    int8 = fields.get("quantized", False)
    rest = (1 if int8 else 2) + fields["track_rows"]
    return ({"partition": "wide",
             "payload": "int8" if int8 else "f32-planar",
             "sort_operands": 2 + rest},
            {"partition": "sort", "payload": "int8" if int8 else "f32",
             "sort_operands": 1 + -(-F // 4) + rest})


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
    """The wide partition (a (key, iota) sort that carries the payload's
    columns and ord + ONE row gather a chunk of the packed words) must
    be bit-identical to the all-carrying sort it replaces past
    _SORT_SINGLE_MAX operands; forcing the threshold sky-high re-takes
    the sort path on the identical inputs. The float32 payload is held
    planar (1-D, all g then all h) on the wide side and as [rows, 2] on
    the sort side: data movement only, so not one bit may differ."""
    t_g, rl_g, plan_g = _wide_case(case, "wide")
    t_s, rl_s, plan_s = _wide_case(case, "sort")
    assert (plan_g, plan_s) == _wide_plans(case)
    assert np.array_equal(rl_g, rl_s)
    for name, a, b in zip(t_g._fields, t_g, t_s):
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("case", [c for c in _WIDE_CASES
                                  if not c.startswith("int8")])
def test_grower_wide_gather_equals_masked(case):
    """... and equal to the masked grower's tree (which partitions
    nothing) by tests/test_grower_equivalence.py's bar: structure and
    row assignment exact, sums to float32 rounding."""
    t_g, rl_g, _ = _wide_case(case, "wide")
    t_m, rl_m, plan_m = _wide_case(case, "masked")
    assert plan_m == {}
    assert int(t_g.num_leaves) == 31
    _assert_equals_masked(t_m, rl_m, t_g, rl_g)


@pytest.mark.parametrize("case", ["plain", "tracked", "int8",
                                  "int8_untracked"])
def test_wide_sort_carries_the_per_row_columns(case):
    """The mechanism engages on every chunk of the wide arm or on none:
    the traced grower holds ONE sort over a chunk's K rows, keyed on its
    first operand alone, of as many operands as ``last_plan`` says; the
    one gather of K rows moves the ``[K, NW]`` words and nothing behind
    them, and no concatenate builds a wider row."""
    import lightgbm_tpu.ops.grow as growmod
    from lightgbm_tpu.analysis.ircheck import _walk_jaxprs
    fields, (F, n) = _WIDE_CASES[case]
    cfg, NW = _cfg(**fields), -(-F // 4)
    sds = jax.ShapeDtypeStruct
    growmod.last_plan.clear()
    jaxpr = jax.make_jaxpr(functools.partial(growmod.grow_tree_impl, cfg))(
        sds((F, n), jnp.uint8), *[sds((n,), jnp.float32)] * 3,
        sds((F,), bool), *[sds((F,), jnp.int32)] * 2)
    plan = dict(growmod.last_plan)
    assert plan == _wide_plans(case)[0]
    eqns = list(_walk_jaxprs(jaxpr.jaxpr))
    sorts = [e for e in eqns if e.primitive.name == "sort"
             and e.invars[0].aval.shape == (cfg.chunk,)]
    assert [(len(e.invars), e.params["num_keys"]) for e in sorts] \
        == [(plan["sort_operands"], 1)]
    f32 = not fields.get("quantized", False)
    assert [str(v.aval.dtype) for v in sorts[0].invars[2:]] \
        == (["float32"] * 2 if f32 else ["uint16"]) \
        + ["uint32"] * fields["track_rows"]
    # the 2-D blocks of K rows that a gather or a concatenate makes
    blocks = {prim: [e.outvars[0].aval.shape for e in eqns
                     if e.primitive.name == prim
                     and e.outvars[0].aval.ndim == 2
                     and e.outvars[0].aval.shape[0] == cfg.chunk]
              for prim in ("gather", "concatenate")}
    assert blocks["gather"] == [(cfg.chunk, NW)]
    assert not [s for s in blocks["concatenate"] if s[1] > NW]


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("form", ["f32_pair", "f32_pair_ord", "one_word",
                                  "one_word_ord"])
def test_wide_sort_moves_the_payload_and_never_compares_it(form):
    """The payload's columns are sort OPERANDS, not keys: NaN of either
    sign and any payload bits (a float comparison orders none of them,
    and an arithmetic pass would quieten the signalling ones), +-inf and
    -0.0 come out of one chunk's ``_sort_gather`` bit for bit where the
    stable order of the key puts them, beside the words of their rows:
    lefts, then rights, then the rows past the window, as rows that
    stay out of the bag carry whatever the objective left them."""
    import lightgbm_tpu.ops.grow as growmod
    K, NW = 512, 17
    rs = np.random.RandomState(11)
    special = np.array([0x7FC00000, 0xFFC00000, 0x7F800001, 0xFFFFFFFF,
                        0x7F800000, 0xFF800000, 0x80000000, 0x00000001],
                       np.uint32)
    words = rs.randint(0, 2 ** 32, size=(K, NW), dtype=np.uint64) \
        .astype(np.uint32)
    g, h = (np.where(rs.rand(K) < 0.5, rs.choice(special, K),
                     rs.randn(K).astype(np.float32).view(np.uint32))
            .astype(np.uint32) for _ in range(2))
    cols = {"f32_pair": (g.view(np.float32), h.view(np.float32)),
            "one_word": ((g >> 16).astype(np.uint16),)}[
                form.removesuffix("_ord")]
    if form.endswith("_ord"):
        cols += (rs.permutation(K).astype(np.uint32) | (g & 0x80000000),)
    side = rs.randint(0, 3, size=K)
    key = (side * K + np.arange(K)).astype(np.int32)
    rows_s, cols_s = jax.jit(growmod._sort_gather)(
        jnp.asarray(key), jnp.asarray(words), tuple(map(jnp.asarray, cols)))
    order = np.argsort(key, kind="stable")
    assert np.array_equal(np.asarray(rows_s), words[order])
    assert len(cols_s) == len(cols)
    for got, col in zip(cols_s, cols):
        assert got.dtype == col.dtype
        assert np.array_equal(_bits(got), _bits(col)[order])


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
