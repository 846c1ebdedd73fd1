"""The plain reference of a ranking round: LambdaMART's gradients, NDCG
and AUC, as the source publishes them.

This file imports nothing of the program (one helper of the other
reference file, ``reference.py``, which does not either). It follows
LightGBM's ``src/objective/rank_objective.hpp`` (``LambdarankNDCG::
GetGradientsForOneQuery``), ``src/metric/rank_metric.hpp`` and
``src/metric/dcg_calculator.cpp`` in straightforward ``jax.numpy``
float32:

lambdarank, per query: the documents are ranked by score, descending,
ties by document index (the source sorts stably, and so does this);
``inverse_max_dcg`` is one over the best DCG at the truncation level
(0 where that DCG is 0); for ``i`` over the ``min(trunc, n - 1)`` best
ranked and every ``j`` ranked below ``i`` whose grade differs, ``high`` is
the one of the higher grade and::

    delta_score = s_high - s_low
    delta = (gain_high - gain_low) |disc_i - disc_j| inverse_max_dcg
    delta /= 0.01 + |delta_score|     # lambdarank_norm, where the query's
                                      # best and worst scores differ
    p = 1 / (1 + exp(sigma delta_score))
    lambda = -sigma p delta,  hessian = sigma^2 p (1 - p) delta
    lambdas[high] += lambda, lambdas[low] -= lambda, both hessians += hessian
    sum_lambdas -= 2 lambda

and under ``lambdarank_norm``, where ``sum_lambdas > 0``, the query's
lambdas and hessians are scaled by ``log2(1 + sum_lambdas) /
sum_lambdas``. Gains are ``2^grade - 1``, discounts ``1 / log2(2 +
rank)``.

NDCG@k, per query: DCG of the first ``k`` documents in that same order
over the best DCG at ``k``; a query whose best DCG is 0 counts as 1; the
mean over the queries.

Departures from the header, each by design: the sigmoid is computed and
not read from the source's table of 1,048,576 steps; sums are float32
where the source's are double; queries are laid side by side (padded to
a power of two, grouped by that width, in blocks of a fixed number of
slots) and only the ``[trunc, width]`` pairs the source's loop visits
are formed, where the source walks them one by one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference import _as_operand      # the control's rounding of g and h

BLOCK_SLOTS = 1 << 22       # pair slots of one block: 16 MB a float32 temporary
MIN_WIDTH = 8


# ---------------------------------------------------------------------
# queries side by side
# ---------------------------------------------------------------------

def query_layout(sizes):
    """``[(width, idx [nq_w, width] int32)]``: the queries grouped by
    the power of two that holds them; ``idx`` is the row of each slot,
    -1 in the padding. Every query is in exactly one group."""
    sizes = np.asarray(sizes, np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    widths = np.maximum(MIN_WIDTH,
                        2 ** np.ceil(np.log2(np.maximum(sizes, 1)))
                        .astype(np.int64))
    out = []
    for w in np.unique(widths):
        qs = np.flatnonzero(widths == w)
        slot = np.arange(w)[None, :]
        idx = np.where(slot < sizes[qs, None], starts[qs, None] + slot, -1)
        out.append((int(w), idx.astype(np.int32)))
    return out


def _blocks(idx, rows_per_query):
    """``idx`` padded with empty queries and cut into ``[nb, blk,
    width]`` so that a block holds ``BLOCK_SLOTS`` pair slots."""
    nq, w = idx.shape
    blk = max(1, min(nq, BLOCK_SLOTS // (rows_per_query * w)))
    pad = (-nq) % blk
    idx = np.concatenate([idx, np.full((pad, w), -1, np.int32)])
    return idx.reshape(-1, blk, w)


def _sorted_queries(score, label, idx):
    """Per query of the block, its documents in rank order: ``(order,
    s, grade, here)``; ``order`` is a stable argsort by score
    descending, the padding last."""
    here = idx >= 0
    safe = jnp.maximum(idx, 0)
    key = jnp.where(here, -score[safe], jnp.inf)
    order = jnp.argsort(key, axis=1, stable=True)
    take = functools.partial(jnp.take_along_axis, indices=order, axis=1)
    return order, take(score[safe]), take(label[safe]), take(here)


def _max_dcg(grade, here, k):
    """Best DCG at ``k`` of each query: its gains in descending order
    against the discounts."""
    gain = jnp.where(here, jnp.exp2(grade) - 1.0, -1.0)
    best = -jnp.sort(-gain, axis=1)
    pos = jnp.arange(grade.shape[1])
    disc = 1.0 / jnp.log2(2.0 + pos.astype(jnp.float32))
    use = (pos[None, :] < k) & (best >= 0.0)
    return jnp.sum(jnp.where(use, best * disc[None, :], 0.0), axis=1)


# ---------------------------------------------------------------------
# lambdarank
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("trunc", "norm", "rows"))
def _lambdarank_width(score, label, blocks, sigma, trunc, norm, rows):
    n = score.shape[0]

    def one_block(acc, idx):
        order, s, grade, here = _sorted_queries(score, label, idx)
        w = idx.shape[1]
        pos = jnp.arange(w)
        disc = 1.0 / jnp.log2(2.0 + pos.astype(jnp.float32))
        gain = jnp.exp2(grade) - 1.0
        dcg = _max_dcg(grade, here, trunc)
        inv = jnp.where(dcg > 0.0, 1.0 / dcg, 0.0)
        count = jnp.sum(here, axis=1)
        # i: the `rows` best ranked; j: every document ranked below i
        si, gi, li = s[:, :rows, None], gain[:, :rows, None], \
            grade[:, :rows, None]
        sj, gj, lj = s[:, None, :], gain[:, None, :], grade[:, None, :]
        i_pos, j_pos = pos[None, :rows, None], pos[None, None, :]
        pair = (i_pos < trunc) & (j_pos > i_pos) \
            & here[:, :rows, None] & here[:, None, :] & (li != lj)
        i_high = li > lj
        delta_score = jnp.where(i_high, si - sj, sj - si)
        delta = jnp.abs(gi - gj) \
            * jnp.abs(disc[None, :rows, None] - disc[None, None, :]) \
            * inv[:, None, None]
        if norm:
            last = jnp.take_along_axis(
                s, jnp.maximum(count - 1, 0)[:, None], axis=1)[:, 0]
            spread = (s[:, 0] != last)[:, None, None]
            delta = jnp.where(spread, delta / (0.01 + jnp.abs(delta_score)),
                              delta)
        p = 1.0 / (1.0 + jnp.exp(sigma * delta_score))
        lam = jnp.where(pair, -sigma * p * delta, 0.0)
        hes = jnp.where(pair, sigma * sigma * p * (1.0 - p) * delta, 0.0)
        to_i = jnp.where(i_high, lam, -lam)     # the high document gains it
        g = jnp.sum(-to_i, axis=1)              # what every j receives
        g = g.at[:, :rows].add(jnp.sum(to_i, axis=2))
        h = jnp.sum(hes, axis=1)
        h = h.at[:, :rows].add(jnp.sum(hes, axis=2))
        if norm:
            total = -2.0 * jnp.sum(lam, axis=(1, 2))
            factor = jnp.where(total > 0.0,
                               jnp.log2(1.0 + total) / total, 1.0)
            g, h = g * factor[:, None], h * factor[:, None]
        # back to the documents' own rows; the padding's writes are dropped
        row = jnp.where(here, jnp.take_along_axis(idx, order, axis=1), n)
        g_acc, h_acc = acc
        return (g_acc.at[row.reshape(-1)].add(g.reshape(-1), mode="drop"),
                h_acc.at[row.reshape(-1)].add(h.reshape(-1), mode="drop")), \
            None

    zero = jnp.zeros((n,), jnp.float32)
    (g, h), _ = lax.scan(one_block, (zero, zero), blocks)
    return g, h


def lambdarank_grad_hess(score, label, layout, sigma=1.0, trunc=30,
                         norm=True, operand_dtype="float32"):
    """``(g, h)`` float32 ``[n]`` of the lambdarank objective at
    ``score``; ``layout`` is ``query_layout(sizes)``. Both are rounded
    to ``operand_dtype`` (the check's control) and returned as
    float32."""
    g = jnp.zeros(score.shape, jnp.float32)
    h = jnp.zeros(score.shape, jnp.float32)
    for width, idx in layout:
        rows = min(int(trunc), width)
        gw, hw = _lambdarank_width(
            score, label, jnp.asarray(_blocks(idx, rows)),
            jnp.float32(sigma), trunc=int(trunc), norm=bool(norm), rows=rows)
        g, h = g + gw, h + hw
    return _as_operand(g, operand_dtype), _as_operand(h, operand_dtype)


# ---------------------------------------------------------------------
# the metrics
# ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="ks")
def _ndcg_width(score, label, blocks, ks):
    def one_block(acc, idx):
        _, _, grade, here = _sorted_queries(score, label, idx)
        pos = jnp.arange(idx.shape[1])
        disc = 1.0 / jnp.log2(2.0 + pos.astype(jnp.float32))
        gain = jnp.where(here, jnp.exp2(grade) - 1.0, 0.0)
        real = jnp.any(here, axis=1)
        out = []
        for k in ks:
            dcg = jnp.sum(jnp.where(pos[None, :] < k, gain * disc[None, :],
                                    0.0), axis=1)
            best = _max_dcg(grade, here, k)
            ndcg = jnp.where(best > 0.0, dcg / jnp.maximum(best, 1e-30), 1.0)
            out.append(jnp.sum(jnp.where(real, ndcg, 0.0)))
        return acc + jnp.stack(out), None

    total, _ = lax.scan(one_block, jnp.zeros((len(ks),), jnp.float32),
                        blocks)
    return total


def ndcg_at(score, label, layout, ks):
    """NDCG at each of ``ks``: ``[len(ks)]`` floats, the mean over all
    queries of ``layout``."""
    total = np.zeros(len(ks), np.float64)
    queries = 0
    for width, idx in layout:
        queries += idx.shape[0]
        total += np.asarray(_ndcg_width(score, label,
                                        jnp.asarray(_blocks(idx, 1)),
                                        ks=tuple(int(k) for k in ks)),
                            np.float64)
    return [float(v) for v in total / queries]


@jax.jit
def auc(score, label):
    """Area under the ROC curve of ``score`` for labels in {0, 1}: the
    rank-sum statistic, tied scores sharing their mean rank."""
    n = score.shape[0]
    order = jnp.argsort(score)
    s, y = score[order], label[order]
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    group = jnp.cumsum(first) - 1
    rank = jnp.arange(1, n + 1, dtype=jnp.float32)
    ones = jnp.ones((n,), jnp.float32)
    mean_rank = (jax.ops.segment_sum(rank, group, num_segments=n)
                 / jnp.maximum(jax.ops.segment_sum(ones, group,
                                                   num_segments=n), 1.0))
    pos = jnp.sum(y)
    neg = n - pos
    # in float32 the ranks' sum would lose the low digits at this size:
    # centre the ranks first
    centred = mean_rank[group] - (n + 1) / 2.0
    return 0.5 + jnp.sum(jnp.where(y > 0, centred, 0.0)) / (pos * neg)
