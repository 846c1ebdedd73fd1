"""The work the lambdarank gradient pass *needs*, as the source defines
it: from the queries' lengths, the grades and the truncation level, and
never from the blocks, padding or batching of whatever computes it.

Pairs. Per query of ``n`` documents the source's loop
(``rank_objective.hpp GetGradientsForOneQuery``) takes ``i`` over the
``T = min(trunc, n - 1)`` best ranked and ``j`` over all ranked below
``i``: ``T (n - 1) - T (T - 1) / 2`` visits. It weighs a pair only where
the two grades differ, and a query holds ``(n^2 - sum_c n_c^2) / 2`` such
pairs in all. Which of the visits those are depends on the round's
scores; the smaller of the two counts bounds them whatever the scores,
and that is what is counted (the program's counter ``rank_pairs`` is the
same rule, written apart from this file).

Per weighed pair, ``OPS_PER_PAIR`` float operations: score distance 1,
gain gap 1, discount gap 2, delta 2, its division by the distance 3, the
sigmoid 4, ``p (1 - p)`` 2, the two scalings 4, four accumulations 4, the
query's lambda sum 2, the grades' compare 1. Per document of a query of
``n``: ``ceil(log2 n)`` compares of its sort, and 16 bytes: the score and
the grade read, the gradient and the hessian written. A query fits in
on-chip memory many times over, so nothing is counted per pair in bytes.
"""

import numpy as np

OPS_PER_PAIR = 26
BYTES_PER_ROW = 16


def loop_pairs(sizes, labels, trunc):
    """Pairs the source's loop has to weigh in one pass over queries of
    ``sizes`` documents whose grades, query after query, are
    ``labels``."""
    sizes = np.asarray(sizes, np.int64)
    labels = np.asarray(labels).astype(np.int64)
    if labels.shape[0] != int(sizes.sum()):
        raise ValueError("the grades do not cover the queries' rows")
    t = np.minimum(int(trunc), sizes - 1)
    visited = t * (sizes - 1) - t * (t - 1) // 2
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    same = np.zeros_like(sizes)
    live = sizes > 0
    for grade in np.unique(labels):
        per_query = np.zeros_like(sizes)
        per_query[live] = np.add.reduceat(
            (labels == grade).astype(np.int64), starts[live])
        same += per_query ** 2
    unequal = (sizes * sizes - same) // 2
    return int(np.sum(np.minimum(np.maximum(visited, 0), unequal)))


def gradient_work(sizes, labels, trunc):
    """``{"pairs", "ops", "bytes"}`` of one pass."""
    sizes = np.asarray(sizes, np.int64)
    pairs = loop_pairs(sizes, labels, trunc)
    sort_ops = int(np.sum(sizes * np.ceil(np.log2(np.maximum(sizes, 1)))))
    return {"pairs": pairs, "ops": pairs * OPS_PER_PAIR + sort_ops,
            "bytes": int(sizes.sum()) * BYTES_PER_ROW}
