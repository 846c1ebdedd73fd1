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
"""

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


@pytest.mark.parametrize("trunc", [1, 30, 500])
@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scores", ["zero", "random", "tied"])
def test_lambdarank_gradients_agree_with_the_reference(dataset, scores, norm,
                                                       trunc):
    label, score = _labels(), _scores(scores)
    obj = LambdarankNDCG(Config.from_params({
        "objective": "lambdarank", "lambdarank_norm": norm,
        "lambdarank_truncation_level": trunc}))
    obj.set_dataset(dataset)
    got = obj.grad_hess(jnp.asarray(score), jnp.asarray(label), None)
    ref = reference_rank.lambdarank_grad_hess(
        jnp.asarray(score), jnp.asarray(label),
        reference_rank.query_layout(SIZES), 1.0, trunc, norm)
    loop = loop_lambdarank(score, label, SIZES, 1.0, trunc, norm)
    for g, r, want in zip(got, ref, loop):
        assert _close(r, want), "the reference departs from the source"
        assert _close(g, want), "the program departs from the source"
        # a query of one document and one of equal grades weigh no pair
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


def _jaxpr_vars(jaxpr):
    """Every operand and intermediate of a jaxpr, inner jaxprs too."""
    yield from jaxpr.invars
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _jaxpr_vars(inner)


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
