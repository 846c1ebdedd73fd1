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
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
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
