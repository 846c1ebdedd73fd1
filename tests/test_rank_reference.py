"""The ranking path against the plain reference (ISSUE 33): the
program's ``LambdarankNDCG.grad_hess`` and ``NDCGMetric`` against
``perfbench/harness/reference_rank.py``, which is itself held to a
document-by-document loop written from the source's
``rank_objective.hpp`` / ``dcg_calculator.cpp``; and a three-round
``lgb.train`` with ``group=`` and a validation set whose trees,
validation score and recorded NDCG agree with the reference
(``harness/check_eval.py``, the benchmark's own comparison). Small,
seeded and ragged: queries of 1, 2, 7 and 130 documents and one at ten
times their mean, one query of all-equal grades, tied scores.

ISSUE 34: NDCG is one registered program a round (``ranking/ndcg``) over
a per-data-set state: executions and state builds are counted, two data
sets keep two states, the edge queries and tied scores are held to the
loop, and the program's jaxpr holds no ``queries x longest`` array.

ISSUE 36: the lambdarank gradient pads a query to its length class
(``ranking._length_classes``): length mixes that cross every class edge,
weighted or not, against the reference and the loop; the traced program's
shapes; the counters as sums over the classes.
"""

import functools
import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu import ranking
from lightgbm_tpu.obs.registry import registry
from lightgbm_tpu.ranking import LambdarankNDCG, NDCGMetric

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench"))

from harness import check_eval, reference_rank  # noqa: E402

SIZES = np.array([1, 2, 7, 130, 350, 9])
N = int(SIZES.sum())
EQUAL_QUERY = slice(3, 10)          # the query of 7: all grades equal


def _labels():
    lab = np.random.RandomState(7).randint(0, 5, N).astype(np.float32)
    lab[EQUAL_QUERY] = 2.0
    return lab


def _scores(kind):
    rs = np.random.RandomState(11)
    if kind == "zero":              # the first round's all-tied score
        return np.zeros(N, np.float32)
    s = rs.randn(N)
    return (np.round(s, 1) if kind == "tied" else s).astype(np.float32)


def loop_lambdarank(score, label, sizes, sigma, trunc, norm):
    """``GetGradientsForOneQuery``, document by document, in double."""
    g, h, a = np.zeros(len(score)), np.zeros(len(score)), 0
    for n in sizes:
        s, lab = score[a:a + n].astype(np.float64), label[a:a + n]
        idx = sorted(range(n), key=lambda i: -s[i])         # stable
        gains = 2.0 ** lab - 1
        best = np.sort(gains)[::-1]
        max_dcg = sum(best[i] / np.log2(2 + i) for i in range(min(trunc, n)))
        inv = 1 / max_dcg if max_dcg > 0 else 0.0
        lam, hes, total = np.zeros(n), np.zeros(n), 0.0
        for i in range(min(n - 1, trunc)):
            for j in range(i + 1, n):
                if lab[idx[i]] == lab[idx[j]]:
                    continue
                hr, lr = (i, j) if lab[idx[i]] > lab[idx[j]] else (j, i)
                hi, lo = idx[hr], idx[lr]
                ds = s[hi] - s[lo]
                d = (gains[hi] - gains[lo]) * inv \
                    * abs(1 / np.log2(2 + hr) - 1 / np.log2(2 + lr))
                if norm and s[idx[0]] != s[idx[-1]]:
                    d /= 0.01 + abs(ds)
                p = 1 / (1 + np.exp(sigma * ds))
                ph = p * (1 - p) * sigma * sigma * d
                p *= -sigma * d
                lam[lo] -= p
                lam[hi] += p
                hes[lo] += ph
                hes[hi] += ph
                total -= 2 * p
        if norm and total > 0:
            lam *= np.log2(1 + total) / total
            hes *= np.log2(1 + total) / total
        g[a:a + n], h[a:a + n] = lam, hes
        a += n
    return g, h


def loop_ndcg(score, label, sizes, k):
    out, a = [], 0
    for n in sizes:
        s, lab = score[a:a + n], label[a:a + n]
        a += n
        gains = 2.0 ** lab - 1
        best = np.sort(gains)[::-1]
        top = sum(best[i] / np.log2(2 + i) for i in range(min(k, n)))
        idx = sorted(range(n), key=lambda i: -s[i])
        out.append(1.0 if top <= 0 else sum(
            gains[idx[i]] / np.log2(2 + i) for i in range(min(k, n))) / top)
    return float(np.mean(out))


def _close(got, want, tol=2e-5):
    want = np.asarray(want, np.float64)
    return np.max(np.abs(np.asarray(got, np.float64) - want)) \
        <= tol * max(np.max(np.abs(want)), 1e-30)


@pytest.fixture(scope="module")
def dataset():
    ds = lgb.Dataset(np.random.RandomState(3).randn(N, 3), label=_labels(),
                     group=SIZES)
    return ds.construct()


# Length mixes for the gradient's length classes. A block of the real
# size (2**25 pair slots) would merge every class of a test-sized table
# into the widest, so all but ``ragged`` run with a block of 256 pair
# slots: 4 queries of width 8, 1 of any wider class. Each lists the
# class widths the rule has to give it.
SMALL_BLOCK = 256
MIXES = {
    # the file's own table under the real block: one class of 350
    "ragged": (SIZES, None, [350]),
    # 1, w - 1, w, w + 1 around every width in use; the longest is 65
    "edges": ([1, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63, 64, 65, 5],
              SMALL_BLOCK, [8, 16, 32, 64, 65]),
    "one_far_longer": ([3, 5, 8, 2, 6, 4, 7, 200], SMALL_BLOCK, [8, 200]),
    "one_length": ([12] * 6, SMALL_BLOCK, [12]),
    "single_query": ([40], SMALL_BLOCK, [40]),
    # two queries are less than a block of width 8: they join width 16
    "merged_up": ([3, 5, 20, 30], SMALL_BLOCK, [16, 30]),
    "all_under_the_narrowest": ([2, 5, 1, 7, 3], SMALL_BLOCK, [7]),
}


@functools.lru_cache(maxsize=None)
def _mix(name):
    """Sizes, grades, per-row weights and the constructed data set of a
    mix, built once."""
    sizes = np.asarray(MIXES[name][0])
    n = int(sizes.sum())
    if name == "ragged":
        label = _labels()
    else:
        label = np.random.RandomState(len(name)).randint(0, 5, n) \
            .astype(np.float32)
    weight = np.random.RandomState(n).uniform(0.5, 2.0, n).astype(np.float32)
    ds = lgb.Dataset(np.random.RandomState(3).randn(n, 3), label=label,
                     group=sizes).construct()
    return sizes, label, weight, ds


def _mix_scores(name, kind):
    if name == "ragged":
        return _scores(kind)
    n = int(np.sum(MIXES[name][0]))
    if kind == "zero":
        return np.zeros(n, np.float32)
    s = np.random.RandomState(n + 1).randn(n)
    return (np.round(s, 1) if kind == "tied" else s).astype(np.float32)


@pytest.fixture
def length_mix(request, monkeypatch):
    """A mix's tables, with its block size in force for the test."""
    name = request.param
    if MIXES[name][1] is not None:
        monkeypatch.setattr(ranking, "_BLOCK_PAIR_SLOTS", MIXES[name][1])
    return (name, *_mix(name))


def _lambdarank(norm=True, trunc=30):
    return LambdarankNDCG(Config.from_params({
        "objective": "lambdarank", "lambdarank_norm": norm,
        "lambdarank_truncation_level": trunc}))


@pytest.mark.parametrize("trunc", [1, 30, 500])
@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scores", ["zero", "random", "tied"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("length_mix", sorted(MIXES), indirect=True)
def test_lambdarank_gradients_agree_with_the_reference(length_mix, weighted,
                                                       scores, norm, trunc):
    name, sizes, label, weight, dataset = length_mix
    score = _mix_scores(name, scores)
    obj = _lambdarank(norm, trunc)
    obj.set_dataset(dataset)
    assert [c[2].shape[-1] for c in obj._layout.classes] == MIXES[name][2]
    got = obj.grad_hess(jnp.asarray(score), jnp.asarray(label),
                        jnp.asarray(weight) if weighted else None)
    ref = reference_rank.lambdarank_grad_hess(
        jnp.asarray(score), jnp.asarray(label),
        reference_rank.query_layout(sizes), 1.0, trunc, norm)
    loop = loop_lambdarank(score, label, sizes, 1.0, trunc, norm)
    # the source folds a row's weight into its lambda after the query's
    # normalisation (rank_objective.hpp:75-86)
    scale = weight if weighted else 1.0
    for g, r, want in zip(got, ref, loop):
        assert _close(r, want), "the reference departs from the source"
        assert _close(g, want * scale), "the program departs from the source"
    if name == "ragged":
        # a query of one document and one of equal grades weigh no pair
        for g in got:
            assert not np.any(np.asarray(g)[0:1]) \
                and not np.any(np.asarray(g)[EQUAL_QUERY])


@pytest.mark.parametrize("k", [1, 3, 5, 10])
@pytest.mark.parametrize("scores", ["zero", "random", "tied"])
def test_ndcg_agrees_with_the_reference(dataset, scores, k):
    label, score = _labels(), _scores(scores)
    metric = NDCGMetric(Config.from_params({"objective": "lambdarank"}), k)
    got = float(metric.eval_with_query(
        jnp.asarray(score)[None, :], jnp.asarray(label), None, dataset,
        lambda s: s))
    ref, = reference_rank.ndcg_at(jnp.asarray(score), jnp.asarray(label),
                                  reference_rank.query_layout(SIZES), (k,))
    want = loop_ndcg(score, label, SIZES, k)
    assert abs(ref - want) < 2e-6 and abs(got - want) < 2e-6


EVAL_AT = (1, 3, 5, 10)


def _ndcg_metrics():
    return ranking.create_ranking_metric("ndcg", Config.from_params(
        {"objective": "lambdarank", "eval_at": list(EVAL_AT)}))


def _eval_all(metrics, score, ds):
    """What ``GBDT.eval_metrics`` does with the metrics of one data set:
    one score OBJECT handed to each metric in turn."""
    score = jnp.asarray(score)[None, :]
    label = jnp.asarray(ds.get_label(), jnp.float32)
    return [float(m.eval_with_query(score, label, None, ds, lambda s: s))
            for m in metrics]


def _agrees(got, score, label, sizes):
    return all(abs(g - loop_ndcg(np.asarray(score), np.asarray(label),
                                 sizes, k)) < 2e-6
               for g, k in zip(got, EVAL_AT))


def _state_builds():
    return registry.counter("metric_state_builds").snapshot()


@pytest.fixture
def executions(monkeypatch):
    """Executions of the registered ``ranking/ndcg`` program, counted at
    the entry every caller goes through."""
    calls, entry = [], ranking._ndcg_at

    def counted(*args, **kwargs):
        calls.append(kwargs["ks"])
        return entry(*args, **kwargs)

    monkeypatch.setattr(ranking, "_ndcg_at", counted)
    return calls


def test_every_eval_at_comes_from_one_execution(dataset, executions):
    metrics, label = _ndcg_metrics(), _labels()
    assert [m.name for m in metrics] == [f"ndcg@{k}" for k in EVAL_AT]
    got = _eval_all(metrics, _scores("random"), dataset)
    # the first evaluation of a data set: the best DCG, then the round's
    assert executions == [EVAL_AT] * 2
    assert _agrees(got, _scores("random"), label, SIZES)
    got = _eval_all(metrics, _scores("tied"), dataset)
    assert executions == [EVAL_AT] * 3, "one execution a round, not four"
    assert _agrees(got, _scores("tied"), label, SIZES)


def test_three_scores_build_the_state_once(dataset, executions):
    metrics, before = _ndcg_metrics(), _state_builds()
    for kind in ("zero", "random", "tied"):
        assert _agrees(_eval_all(metrics, _scores(kind), dataset),
                       _scores(kind), _labels(), SIZES), kind
    assert _state_builds() - before == 1
    assert len(executions) == 4


def test_two_data_sets_keep_two_states_and_a_freed_one_frees_its_own():
    rs = np.random.RandomState(9)
    sizes = {"train": SIZES, "valid": np.array([4, 40, 1, 15])}
    labels = {k: rs.randint(0, 5, int(sz.sum())).astype(np.float32)
              for k, sz in sizes.items()}
    sets = {k: lgb.Dataset(rs.randn(len(labels[k]), 2), label=labels[k],
                           group=sizes[k]).construct() for k in sizes}
    metrics, before = _ndcg_metrics(), _state_builds()
    for _ in range(3):
        for k in ("train", "valid"):
            score = rs.randn(len(labels[k])).astype(np.float32)
            assert _agrees(_eval_all(metrics, score, sets[k]), score,
                           labels[k], sizes[k]), k
    states = metrics[0]._evaluator._states
    assert _state_builds() - before == 2 and len(states) == 2
    assert all(m._evaluator is metrics[0]._evaluator for m in metrics)
    del sets["valid"]
    gc.collect()
    assert list(states) == [sets["train"]]


def test_new_labels_rebuild_the_state():
    rs = np.random.RandomState(13)
    sizes = np.array([6, 11, 3])
    ds = lgb.Dataset(rs.randn(20, 2), label=rs.randint(0, 5, 20),
                     group=sizes).construct()
    metrics, before = _ndcg_metrics(), _state_builds()
    score = rs.randn(20).astype(np.float32)
    assert _agrees(_eval_all(metrics, score, ds), score, ds.get_label(),
                   sizes)
    ds.set_label(rs.randint(0, 5, 20))
    assert _agrees(_eval_all(metrics, score, ds), score, ds.get_label(),
                   sizes)
    assert _state_builds() - before == 2


EDGES = {
    # sizes, labels (None: drawn), scores (None: drawn)
    "one_query": ([12], None, None),
    "queries_of_one_document": ([1, 1, 1], [0, 3, 1], None),
    "queries_shorter_than_k": ([2, 4, 3], None, None),
    "a_query_of_all_zero_grades": ([5, 8], [0] * 5 + [1, 0, 2, 0, 0, 4, 0, 3],
                                   None),
    # ties keep the source's order, by document index: the worst order
    # of the grades stays the worst
    "all_zero_scores_keep_document_order": ([6, 4], [0, 1, 2, 3, 4, 4,
                                                     0, 0, 1, 2],
                                            [0.0] * 10),
    "signed_zeros_tie": ([4], [0, 1, 2, 3], [0.0, -0.0, 0.0, -0.0]),
    "tied_scores_keep_document_order": ([5, 5], [0, 4, 1, 3, 2, 2, 0, 4, 0,
                                                 1],
                                        [1, 1, 0, 0, 1, 2, 2, 2, 2, 3]),
}


@pytest.mark.parametrize("case", sorted(EDGES))
def test_ndcg_at_the_edges_agrees_with_the_loop(case):
    sizes, label, score = EDGES[case]
    rs = np.random.RandomState(len(case))
    sizes, n = np.array(sizes), int(np.sum(sizes))
    label = np.asarray(rs.randint(0, 5, n) if label is None else label,
                       np.float32)
    score = np.asarray(rs.randn(n) if score is None else score, np.float32)
    ds = lgb.Dataset(rs.randn(n, 2), label=label, group=sizes).construct()
    got = _eval_all(_ndcg_metrics(), score, ds)
    assert _agrees(got, score, label, sizes), (case, got)
    if case == "a_query_of_all_zero_grades":
        # the source's rule: a query whose best DCG is 0 counts 1
        second = [loop_ndcg(score[5:], label[5:], sizes[1:], k)
                  for k in EVAL_AT]
        assert np.allclose(got, [(1 + v) / 2 for v in second], atol=2e-6)
    if case == "all_zero_scores_keep_document_order":
        # both queries open on a document of grade 0
        assert abs(got[0]) < 2e-6 and got[3] < 0.8


def test_the_sort_key_orders_as_argsort_of_the_negated_score():
    rs = np.random.RandomState(17)
    s = np.round(rs.randn(4000) * 10.0 ** rs.randint(-20, 20, 4000), 1)
    s[:9] = [0.0, -0.0, np.inf, -np.inf, np.nan, 3.0, 3.0, -3.0, -0.0]
    s = jnp.asarray(rs.permutation(s), jnp.float32)
    key = np.asarray(ranking._descending_key(s))
    assert key.dtype == np.int32
    assert np.array_equal(np.argsort(key, kind="stable"),
                          np.asarray(jnp.argsort(-s)))


def _jaxprs(jaxpr):
    """A jaxpr and every jaxpr inside its equations."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _jaxprs(inner)


def _jaxpr_vars(jaxpr):
    """Every operand and intermediate of a jaxpr, inner jaxprs too."""
    for j in _jaxprs(jaxpr):
        yield from j.invars
        for eqn in j.eqns:
            yield from eqn.outvars


def test_the_ndcg_program_holds_no_queries_by_longest_array(dataset):
    """The guard of ``train.peak_hbm_gib``'s bound: nothing resident or
    transient has ``queries x longest`` elements (here 6 x 350)."""
    metrics = _ndcg_metrics()
    _eval_all(metrics, _scores("random"), dataset)
    st, = metrics[0]._evaluator._states.values()
    padded = len(SIZES) * int(SIZES.max())
    operands = (jnp.zeros((1, N), jnp.float32), *st.operands)
    assert all(a.size < padded for a in operands)
    fn = getattr(ranking._ndcg_at, "unwrapped", ranking._ndcg_at)
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, ks=EVAL_AT))(*operands)
    sizes = [int(np.prod(v.aval.shape)) for v in _jaxpr_vars(jaxpr.jaxpr)]
    assert len(sizes) > 10 and N in sizes, "the walk saw the program"
    assert max(sizes) < padded, sorted(sizes)[-3:]


@pytest.mark.parametrize("length_mix", ["edges", "one_far_longer"],
                         indirect=True)
def test_the_gradient_program_is_shaped_by_the_length_classes(length_mix):
    """What ``rank.grad_ms_per_round`` rests on: on mixed lengths the
    traced ``ranking/lambdarank_grads`` pads nothing to the longest query
    outside the widest class, is handed no per-row gains to gather and
    scatter-adds nothing."""
    name, sizes, label, weight, dataset = length_mix
    obj = _lambdarank()
    obj.set_dataset(dataset)
    lay, n, longest = obj._layout, len(label), int(sizes.max())
    assert obj.q_idx.shape == obj.q_mask.shape == (len(sizes), longest)
    operands = (jnp.zeros(n, jnp.float32), lay.classes, lay.slot_of_row)
    fn = getattr(ranking._lambdarank_grads, "unwrapped",
                 ranking._lambdarank_grads)
    jaxpr = jax.make_jaxpr(
        lambda *a: fn(*a, None, jnp.float32(1.0), trunc=30, norm=True)
    )(*operands).jaxpr
    shapes = [tuple(v.aval.shape) for v in _jaxpr_vars(jaxpr)]
    assert len(shapes) > 50 and (n,) in shapes, "the walk saw the program"
    # the score is the one float [rows] operand: no gains to gather
    assert [v.aval.dtype for v in jaxpr.invars
            if v.aval.shape == (n,)] == [np.float32, np.int32]
    blocks = {(c[2].shape[1], c[2].shape[2]) for c in lay.classes}
    assert len(blocks) == len(MIXES[name][2]) > 1
    # (the tie-break's [1, w, w] document order is a constant a class)
    blocks |= {(1, w) for _, w in blocks}
    for shape in shapes:
        # no [queries, longest] array, and the pair tensors are a
        # class's own [blk, w, w]: [*, longest, longest] is the widest's
        assert shape[-2:] != (len(sizes), longest), shape
        if len(shape) == 3 and shape[1] == shape[2] > 1:
            assert (shape[0], shape[1]) in blocks, shape
    pair = [s for s in shapes if len(s) == 3 and s[1] == s[2] == longest]
    assert pair and {s[0] for s in pair} == {1}, "the widest holds one query"
    prims = {e.primitive.name for j in _jaxprs(jaxpr) for e in j.eqns}
    assert "gather" in prims and not any("scatter" in p for p in prims), \
        sorted(p for p in prims if "scatter" in p)


@pytest.mark.parametrize("length_mix", sorted(MIXES), indirect=True)
def test_the_pass_counts_its_classes_slots(length_mix):
    """``rank_pairs`` is the source loop's whatever the layout;
    ``rank_pair_slots`` / ``rank_row_slots`` are the sums over the
    classes, ``rank_rows`` the documents they hold; the classes are a
    function of the lengths alone."""
    name, sizes, label, weight, dataset = length_mix
    obj, again = _lambdarank(), _lambdarank()
    obj.set_dataset(dataset)
    again.set_dataset(dataset)
    again.set_dataset(dataset)
    lay = obj._layout
    for a, b in zip(jax.tree_util.tree_leaves(lay.classes + (lay.slot_of_row,)),
                    jax.tree_util.tree_leaves(again._layout.classes
                                              + (again._layout.slot_of_row,))):
        assert a.shape == b.shape and np.array_equal(a, b)
    dims = [c[2].shape for c in lay.classes]
    assert lay.pair_slots == sum(nb * blk * w * w for nb, blk, w in dims)
    assert lay.row_slots == sum(nb * blk * w for nb, blk, w in dims)
    # every row has one slot of its own, inside its class's block
    slots = np.asarray(lay.slot_of_row)
    assert len(set(slots.tolist())) == len(label) and slots.max() < lay.row_slots
    # never more than one pad to the longest under the same block rule
    longest = int(np.max(sizes))
    old_blk = max(1, min(len(sizes), ranking._BLOCK_PAIR_SLOTS // longest ** 2))
    assert lay.pair_slots <= -(-len(sizes) // old_blk) * old_blk * longest ** 2
    if len(dims) == 1:
        assert dims[0][2] == longest
    names = ("rank_queries", "rank_pairs", "rank_pair_slots",
             "rank_row_slots", "rank_rows")
    before = [registry.counter(c).snapshot() for c in names]
    obj.grad_hess(jnp.zeros(len(label), jnp.float32), jnp.asarray(label), None)
    moved = [registry.counter(c).snapshot() - b for c, b in zip(names, before)]
    assert moved == [len(sizes), ranking.source_loop_pairs(
        sizes, np.split(label, np.cumsum(sizes)[:-1]), 30),
        lay.pair_slots, lay.row_slots, len(label)]
    assert len(label) <= lay.row_slots      # the row fill is a share


def test_auc_is_the_rank_sum_statistic_with_ties():
    rs = np.random.RandomState(5)
    s = np.round(rs.randn(500), 1).astype(np.float32)
    y = (rs.rand(500) < 0.3).astype(np.float32)
    pos, neg = s[y > 0], s[y == 0]
    want = np.mean((pos[:, None] > neg[None, :])
                   + 0.5 * (pos[:, None] == neg[None, :]))
    assert abs(float(reference_rank.auc(jnp.asarray(s), jnp.asarray(y)))
               - want) < 1e-6


def test_the_counted_pairs_are_the_source_loops():
    from lightgbm_tpu.ranking import source_loop_pairs
    # 4 documents, grades 2 1 1 0: 5 pairs differ, truncation 1 visits 3,
    # truncation 2 visits 5, any deeper all 6 of which one is of equal grade
    lab = [np.array([2., 1., 1., 0.])]
    assert [source_loop_pairs([4], lab, t) for t in (1, 2, 3, 30)] \
        == [3, 5, 5, 5]
    assert source_loop_pairs([1, 3], [np.zeros(1), np.ones(3)], 30) == 0


def test_three_rounds_with_a_validation_set_agree_with_the_reference():
    """Trees, train and validation score, and the NDCG the engine
    recorded each round, against the plain reference: the benchmark's own
    comparison (``check_eval.compare``) at test size."""
    rs = np.random.RandomState(21)
    sizes, vsizes = np.array([1, 2, 7, 130, 60, 25, 40]), np.array([9, 30, 5])

    def table(sz):
        n = int(sz.sum())
        X = rs.randn(n, 6).astype(np.float32)
        lat = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + rs.randn(n)
        return X, np.digitize(lat, [0.2, 1.0, 1.8, 2.4]).astype(np.float32), sz

    tables = {"train": table(sizes), "valid": table(vsizes)}
    (X, y, _), (Xv, yv, _) = tables["train"], tables["valid"]
    params = {"objective": "lambdarank", "metric": "ndcg",
              "eval_at": [1, 3, 5, 10], "num_leaves": 7, "learning_rate": 0.1,
              "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 0.05,
              "verbose": -1}
    train = lgb.Dataset(X, label=y, group=sizes)
    valid = lgb.Dataset(Xv, label=yv, group=vsizes, reference=train)
    evals = {}
    bst = lgb.train(params, train, 3, valid_sets=[valid],
                    valid_names=["valid"],
                    callbacks=[lgb.record_evaluation(evals)])
    eng = bst._engine
    g, h = eng._gradients(eng.score)
    prog = {"score": np.asarray(eng.score)[0],
            "valid_score": np.asarray(eng.valid_sets[0].score)[0],
            "evals": evals["valid"],
            "grad": (np.asarray(g)[0], np.asarray(h)[0])}
    ref_cfg = {"objective": "lambdarank", "lambda_l2": 0.0,
               "min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 0.05,
               "sigmoid": 1.0, "lambdarank_truncation_level": 30,
               "lambdarank_norm": True, "eval_at": [1, 3, 5, 10]}
    check = {"rounds_followed": 3, "root_candidates": 32,
             "candidate_sample_rows": 1000, "deep_nodes": 0,
             "deep_min_share": 0.5}
    out = check_eval.compare(bst.dump_model(), prog, tables, ref_cfg, 0.1,
                             check, 1, "float32")
    assert out["followed"] == [0, 1, 2] and out["leaf_count_mismatch"] == 0
    for name in ("leaf_weight_gap", "leaf_value_gap", "split_gain_gap",
                 "score_gap", "valid_score_gap"):
        assert out[name] < 2e-3, (name, out[name])
    assert out["eval_metric_gap"] < 1e-5 and out["grad_gap"] < 1e-4
    assert out["root_split_shortfall"] < 0.05
