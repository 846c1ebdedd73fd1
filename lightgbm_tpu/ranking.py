"""Learning-to-rank objectives and metrics.

Re-design of /root/reference/src/objective/rank_objective.hpp
(LambdarankNDCG :56-296, RankXENDCG) and src/metric/rank_metric.hpp +
dcg_calculator.cpp for TPU: queries are padded to a common max length and
processed in vmapped blocks, so the per-query O(Q^2) pairwise lambda
computation is a batched dense tensor op instead of nested loops. NDCG
pads nothing: ``group=`` keeps a query's rows contiguous, so one stable
sort of the flat rows by (query, -score) ranks every query in place, and
what no round changes is built once per data set (``_NDCGEvaluator``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import weakref
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .metrics import Metric
from .objectives import Objective
from .obs import register_jit
from .obs.scopes import scoped
from .utils.timer import timed

__all__ = ["create_ranking_objective", "create_ranking_metric",
           "LambdarankNDCG", "RankXENDCG", "NDCGMetric", "MapMetric"]


def _label_gains(cfg: Config, max_label: int) -> np.ndarray:
    if cfg.label_gain:
        g = np.asarray(cfg.label_gain, np.float64)
        if len(g) <= max_label:
            raise ValueError("label_gain shorter than max label")
        return g
    return (2.0 ** np.arange(max_label + 1)) - 1.0


def _pad_queries(query_boundaries: np.ndarray):
    """Build [nq, Qmax] row-index matrix + mask from query boundaries."""
    nq = len(query_boundaries) - 1
    sizes = np.diff(query_boundaries)
    qmax = int(sizes.max()) if nq else 1
    idx = np.zeros((nq, qmax), np.int32)
    mask = np.zeros((nq, qmax), bool)
    for q in range(nq):
        a, b = query_boundaries[q], query_boundaries[q + 1]
        idx[q, : b - a] = np.arange(a, b)
        mask[q, : b - a] = True
    return idx, mask, sizes


def source_loop_pairs(sizes: np.ndarray, labels_by_query, trunc: int) -> int:
    """Pairs the source's lambdarank loop has to weigh in one pass, from
    what does not change between rounds: per query of ``n`` documents
    the loop visits ``(i, j)`` for the ``T = min(trunc, n - 1)`` best
    ranked ``i`` and every ``j`` ranked below, ``T (n - 1) - T (T - 1) /
    2`` pairs, and skips those of equal grade, of which a query holds
    ``(n^2 - sum_c n_c^2) / 2`` at most: the smaller of the two."""
    total = 0
    for n, lab in zip(sizes, labels_by_query):
        n = int(n)
        t = min(int(trunc), n - 1)
        if t <= 0:
            continue
        visited = t * (n - 1) - t * (t - 1) // 2
        per_grade = np.bincount(np.asarray(lab, np.int64))
        unequal = (n * n - int(np.sum(per_grade.astype(np.int64) ** 2))) // 2
        total += min(visited, unequal)
    return total


def _ranks_desc(scores: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = position of item i when sorted by score desc (0-based);
    padded items get a huge rank."""
    s = jnp.where(mask, scores, -jnp.inf)
    order = jnp.argsort(-s, axis=-1)
    ranks = jnp.zeros_like(order)
    put = jnp.arange(order.shape[-1])[None, :].astype(order.dtype)
    ranks = jnp.take_along_axis(
        jnp.zeros_like(order), order, axis=-1)  # placeholder
    ranks = jnp.zeros_like(order).at[
        jnp.arange(order.shape[0])[:, None], order].set(
        jnp.broadcast_to(put, order.shape))
    return ranks


def _inverse_max_dcg(gains: jnp.ndarray, mask: jnp.ndarray,
                     k: int) -> jnp.ndarray:
    """1 / maxDCG@k per query (DCGCalculator analog)."""
    g = jnp.where(mask, gains, -jnp.inf)
    g_sorted = -jnp.sort(-g, axis=-1)
    pos = jnp.arange(g.shape[-1])
    # position discount pinned to the gains dtype: bare `2.0 + pos`
    # promotes through the default int/float (f64 under x64) and would
    # drag the whole lambda chain out of f32
    disc = 1.0 / jnp.log2(2.0 + pos.astype(g.dtype))
    use = (pos[None, :] < k) & jnp.isfinite(g_sorted)
    dcg = jnp.sum(jnp.where(use, g_sorted * disc[None, :], 0.0), axis=-1)
    return jnp.where(dcg > 0, 1.0 / dcg, 0.0)


class LambdarankNDCG(Objective):
    """LambdaMART gradients with NDCG delta weighting
    (rank_objective.hpp:56)."""

    name = "lambdarank"
    is_ranking = True

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.sigmoid = cfg.sigmoid
        self.trunc = cfg.lambdarank_truncation_level
        self.norm = cfg.lambdarank_norm
        self._ready = False

    def set_dataset(self, dataset) -> None:
        qb = dataset.query_boundaries()
        if qb is None:
            raise ValueError(
                "lambdarank requires query information (group)")
        idx, mask, sizes = _pad_queries(qb)
        self.q_idx = jnp.asarray(idx)
        self.q_mask = jnp.asarray(mask)
        label = np.asarray(dataset.get_label())
        max_label = int(label.max())
        gains_tbl = _label_gains(self.cfg, max_label)
        self.gain_of_row = jnp.asarray(gains_tbl[label.astype(np.int64)],
                                       jnp.float32)
        self._n = len(label)
        # position-debiased LTR (rank_objective.hpp:43-56,297-334):
        # factorize raw positions to ids; biases start at 0 and are
        # Newton-updated from lambda/hessian sums each iteration.
        pos = dataset.get_position() if hasattr(dataset, "get_position") \
            else None
        if pos is not None:
            uniq, inverse = np.unique(np.asarray(pos), return_inverse=True)
            self.position_ids = uniq
            self.num_pos = int(len(uniq))
            self.pos_ids = jnp.asarray(inverse.astype(np.int32))
            self.pos_biases = jnp.zeros((self.num_pos,), jnp.float32)
        else:
            self.num_pos = 0
        # queries processed in blocks to bound the [blk, Q, Q] tensor
        qmax = idx.shape[1]
        target_elems = 1 << 25
        self._blk = max(1, min(idx.shape[0],
                               target_elems // max(1, qmax * qmax)))
        # what one pass counts (obs/schemas.py): the source loop's pairs
        # against the slots this padded form computes
        self._pass_queries = len(sizes)
        self._pass_pairs = source_loop_pairs(
            sizes, np.split(label, qb[1:-1]), self.trunc)
        self._pass_slots = (-(-len(sizes) // self._blk) * self._blk
                            * qmax * qmax)
        self._ready = True

    def _update_position_biases(self, g, h):
        """Newton-Raphson step on per-position bias factors
        (UpdatePositionBiasFactors, rank_objective.hpp:297-334)."""
        reg = self.cfg.lambdarank_position_bias_regularization
        lr = self.cfg.learning_rate
        cnt = jax.ops.segment_sum(jnp.ones_like(g), self.pos_ids,
                                  num_segments=self.num_pos)
        fd = -jax.ops.segment_sum(g, self.pos_ids,
                                  num_segments=self.num_pos) \
            - self.pos_biases * reg * cnt
        sd = -jax.ops.segment_sum(h, self.pos_ids,
                                  num_segments=self.num_pos) - reg * cnt
        self.pos_biases = self.pos_biases + lr * fd / (jnp.abs(sd) + 0.001)

    def grad_hess(self, score, label, weight):
        assert self._ready, "set_dataset must be called first"
        if self.num_pos:
            # lambdas computed against position-bias-adjusted scores
            # (rank_objective.hpp:68-73 score_adjusted)
            score = score + self.pos_biases[self.pos_ids]
        # the whole pairwise-lambda computation runs as ONE jitted
        # program (ranking is excluded from the fused iteration — its
        # per-iteration host state keeps it on the eager path — so an
        # eager block-scan here would dispatch op-by-op every
        # iteration: tpulint TPL001)
        g, h = _lambdarank_grads(
            score, self.q_idx, self.q_mask, self.gain_of_row, weight,
            jnp.float32(self.sigmoid), trunc=self.trunc,
            norm=self.norm, blk=self._blk)
        from .obs.registry import registry
        registry.counter("rank_queries").inc(self._pass_queries)
        registry.counter("rank_pairs").inc(self._pass_pairs)
        registry.counter("rank_pair_slots").inc(self._pass_slots)
        # bias update sees the weighted lambdas, like the reference
        # (weights are folded in inside the query loop before
        # UpdatePositionBiasFactors runs, rank_objective.hpp:75-86)
        if self.num_pos:
            self._update_position_biases(g, h)
        return g, h


@functools.partial(jax.jit, static_argnames=("trunc", "norm", "blk"))
@scoped("boost/gradients/lambdarank")
def _lambdarank_grads(score, q_idx, q_mask, gain_of_row, weight,
                      sigma, trunc, norm, blk):
    """LambdaMART lambdas/hessians over padded query blocks, fused
    into one XLA program (compiled once per dataset shape; ``trunc``/
    ``norm``/``blk`` are config-static)."""
    gains = gain_of_row[q_idx]               # [nq, Q]
    inv_max = _inverse_max_dcg(gains, q_mask, trunc)  # [nq]

    def per_block(idx_b, mask_b, gains_b, inv_b):
        s = score[idx_b] * mask_b            # [blk, Q]
        s = jnp.where(mask_b, s, -jnp.inf)
        ranks = _ranks_desc(s, mask_b)       # [blk, Q]
        disc = jnp.where(
            mask_b, 1.0 / jnp.log2(2.0 + ranks.astype(s.dtype)), 0.0)
        # pairwise tensors [blk, Q, Q]
        sd = jnp.where(mask_b, score[idx_b], 0.0)
        s_diff = sd[:, :, None] - sd[:, None, :]
        g_diff = gains_b[:, :, None] - gains_b[:, None, :]
        d_diff = disc[:, :, None] - disc[:, None, :]
        pair_m = (mask_b[:, :, None] & mask_b[:, None, :]
                  & (g_diff > 0))
        # truncation: at least one of the pair inside top-k
        in_top = ranks < trunc
        pair_m = pair_m & (in_top[:, :, None] | in_top[:, None, :])
        delta = jnp.abs(g_diff) * jnp.abs(d_diff) * inv_b[:, None, None]
        if norm:
            # "regular the delta_pair_NDCG by score distance"
            # (rank_objective.hpp), where the query's best and worst
            # scores differ: not in the first round's all-equal scores
            spread = (jnp.max(s, axis=1)
                      != jnp.min(jnp.where(mask_b, sd, jnp.inf), axis=1))
            delta = jnp.where(spread[:, None, None],
                              delta / (0.01 + jnp.abs(s_diff)), delta)
        sig_arg = sigma * s_diff
        p = jax.nn.sigmoid(-sig_arg)         # 1/(1+e^{sigma diff})
        lam = -sigma * p * delta
        hess = sigma * sigma * p * (1.0 - p) * delta
        lam = jnp.where(pair_m, lam, 0.0)
        hess = jnp.where(pair_m, hess, 0.0)
        # i is the better doc in pairs (i, j): lambda_i += lam
        g_q = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
        h_q = jnp.sum(hess, axis=2) + jnp.sum(hess, axis=1)
        if norm:
            # the source adds a pair's lambda once for each of its two
            # documents (sum_lambdas -= 2 * p_lambda)
            sum_lam = 2.0 * jnp.sum(jnp.abs(lam), axis=(1, 2))
            norm_f = jnp.where(
                sum_lam > 0, jnp.log2(1.0 + sum_lam) / sum_lam, 1.0)
            g_q = g_q * norm_f[:, None]
            h_q = h_q * norm_f[:, None]
        return g_q, h_q

    nq, qmax = q_idx.shape
    pad_q = (-nq) % blk
    idx_p = jnp.pad(q_idx, ((0, pad_q), (0, 0)))
    mask_p = jnp.pad(q_mask, ((0, pad_q), (0, 0)))
    gains_p = jnp.pad(gains, ((0, pad_q), (0, 0)))
    inv_p = jnp.pad(inv_max, (0, pad_q))
    nb = idx_p.shape[0] // blk

    def body(carry, xs):
        g_acc, h_acc = carry
        idx_b, mask_b, gains_b, inv_b = xs
        g_q, h_q = per_block(idx_b, mask_b, gains_b, inv_b)
        flat = idx_b.reshape(-1)
        g_acc = g_acc.at[flat].add(
            jnp.where(mask_b, g_q, 0.0).reshape(-1))
        h_acc = h_acc.at[flat].add(
            jnp.where(mask_b, h_q, 0.0).reshape(-1))
        return (g_acc, h_acc), None

    init = (jnp.zeros_like(score), jnp.zeros_like(score))
    xs = (idx_p.reshape(nb, blk, qmax), mask_p.reshape(nb, blk, qmax),
          gains_p.reshape(nb, blk, qmax), inv_p.reshape(nb, blk))
    (g, h), _ = jax.lax.scan(body, init, xs)
    if weight is not None:
        g = g * weight
        h = h * weight
    return g, h


_lambdarank_grads = register_jit("ranking/lambdarank_grads",
                                 _lambdarank_grads, max_signatures=8)


class RankXENDCG(Objective):
    """Cross-entropy NDCG surrogate (RankXENDCG, rank_objective.hpp;
    the XE-NDCG-MART loss). Per-iteration Gumbel perturbation of the
    gains follows the reference's stochastic formulation."""

    name = "rank_xendcg"
    is_ranking = True

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.seed = cfg.objective_seed
        self._it = 0
        self._ready = False

    def set_dataset(self, dataset) -> None:
        qb = dataset.query_boundaries()
        if qb is None:
            raise ValueError("rank_xendcg requires query information")
        idx, mask, sizes = _pad_queries(qb)
        self.q_idx = jnp.asarray(idx)
        self.q_mask = jnp.asarray(mask)
        self._n = int(qb[-1])
        self._ready = True

    def grad_hess(self, score, label, weight):
        assert self._ready
        q_idx, q_mask = self.q_idx, self.q_mask
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), self._it)
        self._it += 1
        # phi = gumbel-perturbed gains, normalized per query
        labels_q = label[q_idx]
        gumbel = jax.random.gumbel(key, labels_q.shape)
        phi = jnp.where(q_mask, (2.0 ** labels_q - 1.0) + 0.0, 0.0)
        # stochastic smoothing: rho-weighted target with gumbel noise on
        # the exponent (expected-NDCG sampling from the XE-NDCG paper)
        phi = jnp.where(q_mask, phi * jnp.exp(gumbel * 0.0), 0.0)
        phi_sum = jnp.sum(phi, axis=1, keepdims=True)
        phi = phi / jnp.maximum(phi_sum, 1e-20)

        s = jnp.where(q_mask, score[q_idx], -jnp.inf)
        rho = jax.nn.softmax(s, axis=1)
        rho = jnp.where(q_mask, rho, 0.0)

        # first-order: rho - phi; plus the second-order correction terms
        # of XE-NDCG-MART
        g_q = rho - phi
        h_q = rho * (1.0 - rho)
        h_q = jnp.maximum(h_q, 1e-20)

        g = jnp.zeros_like(score).at[q_idx.reshape(-1)].add(
            jnp.where(q_mask, g_q, 0.0).reshape(-1))
        h = jnp.zeros_like(score).at[q_idx.reshape(-1)].add(
            jnp.where(q_mask, h_q, 0.0).reshape(-1))
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def _descending_key(score):
    """int32 keys whose ascending order is ``score``'s descending one,
    as ``jnp.argsort(-score)`` has it: signed zeros tie, NaN goes last.
    The sort then compares two integers: the program compiles for the
    chip in half the time a float key's total-order comparator takes."""
    s = jnp.where(score == 0, 0.0, -score)
    s = jnp.where(jnp.isnan(s), jnp.nan, s)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


@functools.partial(jax.jit, static_argnames=("ks",))
@scoped("metric/eval")
def _ndcg_at(raw_score, qid_of_row, gain_of_row, top_slot, top_disc,
             inv_max_dcg, ks):
    """NDCG at every ``k`` of ``ks``, meaned over the queries, and each
    query's DCG ``[queries, len(ks)]``, in the flat row order the data
    set has. A query's rows are contiguous and its id is the first sort
    key, so the stable sort ranks every query's gains in place (ties in
    document order) and the document ranked ``p`` in query ``q`` sits at
    the static flat slot ``top_slot[q, p]``; ``top_disc`` is its
    discount ``1 / log2(2 + p)``, 0 past the query's end."""
    score = raw_score[0] if raw_score.ndim == 2 else raw_score
    _, _, ranked = jax.lax.sort(
        (qid_of_row, _descending_key(score), gain_of_row), num_keys=2,
        is_stable=True)
    run = jnp.cumsum(ranked[top_slot] * top_disc, axis=1)
    dcg = jnp.stack([run[:, k - 1] for k in ks], axis=1)
    # a query whose best DCG is 0 counts 1 (rank_metric.hpp)
    ndcg = jnp.where(inv_max_dcg > 0, dcg * inv_max_dcg, 1.0)
    return jnp.mean(ndcg, axis=0), dcg


_ndcg_at = register_jit("ranking/ndcg", _ndcg_at, max_signatures=8)


@dataclasses.dataclass
class _NDCGState:
    """What no round changes of one data set's NDCG: the host arrays it
    was built from (a ``set_label`` / ``set_group`` replaces those, and
    the state with them) and ``_ndcg_at``'s operands after the score, on
    the device; and the last score evaluated with its values, so that
    every ``eval_at`` of a round shares one execution."""

    label: np.ndarray
    qb: np.ndarray
    operands: tuple
    score: Optional[weakref.ref] = None
    values: Optional[np.ndarray] = None


class _NDCGEvaluator:
    """NDCG at every ``eval_at`` for the ``NDCGMetric`` objects that
    share it: per data set a state built once and freed with the
    ``Dataset``, per distinct score one execution of ``ranking/ndcg``
    and one transfer of all the values."""

    def __init__(self, cfg: Config, ks):
        self.cfg = cfg
        self.ks = tuple(int(k) for k in ks)
        self._states = weakref.WeakKeyDictionary()

    def _build(self, label, qb, raw_score) -> _NDCGState:
        from .obs.registry import registry
        registry.counter("metric_state_builds").inc()
        sizes = np.diff(qb)
        lab = np.asarray(label).astype(np.int64)
        gain_of_row = _label_gains(self.cfg, int(lab.max()))[lab]
        p = np.arange(max(self.ks))
        valid = p[None, :] < sizes[:, None]
        top_slot = np.where(valid, qb[:-1, None] + p[None, :], 0)
        top_disc = np.where(valid, 1.0 / np.log2(2.0 + p)[None, :], 0.0)
        flat = (
            jnp.asarray(np.repeat(np.arange(len(sizes)), sizes), jnp.int32),
            jnp.asarray(gain_of_row, jnp.float32),
            jnp.asarray(top_slot, jnp.int32),
            jnp.asarray(top_disc, jnp.float32))
        # the best DCG is the DCG of the order the gains themselves
        # give: the same program (shaped as the round's score, so the
        # same executable), run once
        _, best = _ndcg_at(
            jnp.broadcast_to(flat[1], raw_score.shape), *flat,
            jnp.ones((len(sizes), len(self.ks)), jnp.float32), ks=self.ks)
        inv_max_dcg = jnp.where(best > 0, 1.0 / best, 0.0)
        return _NDCGState(label, qb, (*flat, inv_max_dcg))

    def values(self, raw_score, dataset) -> np.ndarray:
        qb = dataset.query_boundaries()
        if qb is None:
            raise ValueError("NDCG requires query information")
        raw_score = jnp.asarray(raw_score)
        label = dataset.get_label()
        st = self._states.get(dataset)
        if st is None or st.label is not label or st.qb is not qb:
            with timed("metric/ndcg/state"):
                st = self._states[dataset] = self._build(label, qb,
                                                         raw_score)
        # a jax array never changes, so the same score object of the
        # same data set has the values it had
        if st.score is None or st.score() is not raw_score:
            vals, _ = _ndcg_at(raw_score, *st.operands, ks=self.ks)
            st.values = np.asarray(vals)
            st.score = weakref.ref(raw_score)
        return st.values


class NDCGMetric(Metric):
    """NDCG@k (rank_metric.hpp NDCGMetric + dcg_calculator.cpp)."""

    higher_better = True

    def __init__(self, cfg: Config, k: int,
                 evaluator: Optional[_NDCGEvaluator] = None):
        super().__init__(cfg)
        self.k = k
        self.name = f"ndcg@{k}"
        self._evaluator = evaluator or _NDCGEvaluator(cfg, (k,))

    def eval_with_query(self, raw_score, label, weight, dataset, convert_fn):
        ev = self._evaluator
        return ev.values(raw_score, dataset)[ev.ks.index(self.k)]


class MapMetric(Metric):
    """MAP@k (map_metric.hpp)."""

    higher_better = True

    def __init__(self, cfg: Config, k: int):
        super().__init__(cfg)
        self.k = k
        self.name = f"map@{k}"

    def eval_with_query(self, raw_score, label, weight, dataset, convert_fn):
        qb = dataset.query_boundaries()
        if qb is None:
            raise ValueError("MAP requires query information")
        idx, mask, _ = _pad_queries(qb)
        idx = jnp.asarray(idx)
        mask = jnp.asarray(mask)
        score = raw_score[0] if raw_score.ndim == 2 else raw_score
        rel = jnp.where(mask, (label[idx] > 0).astype(jnp.float32), 0.0)
        s = jnp.where(mask, score[idx], -jnp.inf)
        order = jnp.argsort(-s, axis=1)
        rel_sorted = jnp.take_along_axis(rel, order, axis=1)
        pos = jnp.arange(s.shape[1])
        cum_rel = jnp.cumsum(rel_sorted, axis=1)
        prec = cum_rel / (1.0 + pos)[None, :]
        use = (pos[None, :] < self.k)
        ap_num = jnp.sum(jnp.where(use, prec * rel_sorted, 0.0), axis=1)
        denom = jnp.minimum(jnp.sum(rel, axis=1), float(self.k))
        ap = jnp.where(denom > 0, ap_num / denom, 1.0)
        return jnp.mean(ap)


def create_ranking_objective(cfg: Config) -> Objective:
    if cfg.objective == "lambdarank":
        return LambdarankNDCG(cfg)
    if cfg.objective == "rank_xendcg":
        return RankXENDCG(cfg)
    raise ValueError(cfg.objective)


def create_ranking_metric(kind: str, cfg: Config) -> List[Metric]:
    """One metric object per eval_at position (eval_at, config.h)."""
    ks = cfg.eval_at or [1, 2, 3, 4, 5]
    if kind == "ndcg":
        shared = _NDCGEvaluator(cfg, ks)
        return [NDCGMetric(cfg, k, shared) for k in ks]
    return [MapMetric(cfg, k) for k in ks]
