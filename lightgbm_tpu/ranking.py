"""Learning-to-rank objectives and metrics.

Re-design of /root/reference/src/objective/rank_objective.hpp
(LambdarankNDCG :56-296, RankXENDCG) and src/metric/rank_metric.hpp +
dcg_calculator.cpp for TPU: the per-query O(Q^2) pairwise lambda
computation is a batched dense tensor op over padded query blocks instead
of nested loops. The lambdarank gradient pads a query to the width of its
LENGTH CLASS, not to the data set's longest query: the classes (widths
that double, the widest the longest query) come from the query lengths
alone and are built once per data set with everything no round changes
(``_length_classes``, ``_RankLayout``); a round reads the score into the
classes' slots, runs every class's blocks inside one program and reads
each row's gradient back from its one slot. ``RankXENDCG`` and
``MapMetric`` still pad to the longest (``_pad_queries``). NDCG pads
nothing: ``group=`` keeps a query's rows contiguous, so one stable
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


def _ranks_desc(s: jnp.ndarray) -> jnp.ndarray:
    """rank[i] = position of item i of ``s[blk, w]`` when each query is
    sorted by score descending (0-based), ties in document order, as a
    stable ``jnp.argsort(-s)`` places them: the documents ahead of ``i``
    are counted over the pairwise compare, so no sort and no scatter.
    Padded items (``-inf``) rank after every document."""
    before = jnp.arange(s.shape[-1])
    ahead = (s[:, None, :] > s[:, :, None]) | (
        (s[:, None, :] == s[:, :, None])
        & (before[None, None, :] < before[None, :, None]))
    return jnp.sum(ahead, axis=2, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _inverse_max_dcg(gains: jnp.ndarray, mask: jnp.ndarray,
                     k: int) -> jnp.ndarray:
    """1 / maxDCG@k per query (DCGCalculator analog); no round changes
    it, so it runs once per length class, from ``set_dataset``."""
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


# pair slots of one block's [blk, w, w] temporaries, and the narrowest
# length class (one sublane tile)
_BLOCK_PAIR_SLOTS = 1 << 25
_NARROWEST_CLASS = 8


def _block_queries(width: int) -> int:
    """Queries of one block at padded width ``width``."""
    return max(1, _BLOCK_PAIR_SLOTS // (width * width))


def _length_classes(sizes: np.ndarray):
    """The padded widths a data set's queries are computed at, from
    their lengths alone: ``[(width, ids of its queries in data set
    order)]``, narrowest first. Widths double from ``_NARROWEST_CLASS``
    and the widest is the longest query itself; a query goes to the
    narrowest class that holds it; a class that would hold less than one
    block of its width joins the next one up, and an empty one does not
    exist. Queries all of one length, or all under the narrowest width,
    make ONE class of the longest's width, which is the padding every
    data set had before the classes."""
    sizes = np.asarray(sizes, np.int64)
    longest = max(1, int(sizes.max())) if len(sizes) else 1
    widths = []
    w = _NARROWEST_CLASS
    while w < longest:
        widths.append(w)
        w *= 2
    widths.append(longest)
    home = np.searchsorted(widths, sizes)
    classes, first = [], 0
    for k, w in enumerate(widths):
        members = np.flatnonzero((home >= first) & (home <= k))
        if k + 1 < len(widths) and len(members) < _block_queries(w):
            continue
        first = k + 1
        if len(members):
            classes.append((w, members))
    return classes


@dataclasses.dataclass
class _RankLayout:
    """What no round changes of one data set's lambdarank pass, built
    once in ``set_dataset``. ``classes``: per length class, on the
    device, ``(start [nb, blk], length [nb, blk], gains [nb, blk, w],
    1 / maxDCG [nb, blk])``: its queries' first rows and lengths (row
    index and mask are an ``iota`` compare away), their documents' gains
    padded to the class's width, and the truncated best DCG's inverse;
    the last block is filled with queries of no document. ``slot_of_row
    [rows]``: every row sits in exactly one slot of exactly one class,
    at this offset of the classes' slots laid end to end. ``pair_slots``
    / ``row_slots``: the sums over the classes of ``nb * blk * w^2`` and
    ``nb * blk * w``."""

    classes: tuple
    slot_of_row: jnp.ndarray
    pair_slots: int
    row_slots: int


def _rank_layout(qb: np.ndarray, gain_of_row: np.ndarray,
                 trunc: int) -> _RankLayout:
    qb = np.asarray(qb, np.int64)
    sizes = np.diff(qb)
    first_slot = np.zeros(len(sizes), np.int64)
    classes, row_slots, pair_slots = [], 0, 0
    for w, members in _length_classes(sizes):
        # blocks as even as the block size allows: never larger than
        # ``_block_queries(w)``, and fewer queries of no document
        nb = -(-len(members) // _block_queries(w))
        blk = -(-len(members) // nb)
        fill = (0, nb * blk - len(members))
        start = np.pad(qb[:-1][members], fill)
        length = np.pad(sizes[members], fill)
        pos = np.arange(w)
        mask = pos[None, :] < length[:, None]
        rows = np.where(mask, start[:, None] + pos[None, :], 0)
        gains = jnp.asarray(np.where(mask, gain_of_row[rows], 0.0),
                            jnp.float32)
        inv_max = _inverse_max_dcg(gains, jnp.asarray(mask), k=trunc)
        classes.append((
            jnp.asarray(start.reshape(nb, blk), jnp.int32),
            jnp.asarray(length.reshape(nb, blk), jnp.int32),
            gains.reshape(nb, blk, w), inv_max.reshape(nb, blk)))
        first_slot[members] = row_slots + np.arange(len(members)) * w
        row_slots += nb * blk * w
        pair_slots += nb * blk * w * w
    slot_of_row = (np.repeat(first_slot - qb[:-1], sizes)
                   + np.arange(int(qb[-1])))
    return _RankLayout(tuple(classes), jnp.asarray(slot_of_row, jnp.int32),
                       pair_slots, row_slots)


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
        self._qb = np.asarray(qb)
        label = np.asarray(dataset.get_label())
        max_label = int(label.max())
        gains_tbl = _label_gains(self.cfg, max_label)
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
        # the padded layout of the pass, shaped by the query lengths
        self._layout = _rank_layout(
            self._qb, gains_tbl[label.astype(np.int64)], self.trunc)
        # what one pass counts (obs/schemas.py): the source loop's pairs
        # against the slots this padded form computes and moves
        sizes = np.diff(self._qb)
        self._pass_queries = len(sizes)
        self._pass_rows = int(self._qb[-1])
        self._pass_pairs = source_loop_pairs(
            sizes, np.split(label, self._qb[1:-1]), self.trunc)
        self._ready = True

    @property
    def q_idx(self) -> np.ndarray:
        """``[queries, longest]`` row index of every query, on the host
        and built on demand: the pass itself holds no such array."""
        return _pad_queries(self._qb)[0]

    @property
    def q_mask(self) -> np.ndarray:
        """``[queries, longest]``: whether ``q_idx`` names a document."""
        return _pad_queries(self._qb)[1]

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
        layout = self._layout
        g, h = _lambdarank_grads(
            score, layout.classes, layout.slot_of_row, weight,
            jnp.float32(self.sigmoid), trunc=self.trunc, norm=self.norm)
        from .obs.registry import registry
        registry.counter("rank_queries").inc(self._pass_queries)
        registry.counter("rank_rows").inc(self._pass_rows)
        registry.counter("rank_pairs").inc(self._pass_pairs)
        registry.counter("rank_pair_slots").inc(layout.pair_slots)
        registry.counter("rank_row_slots").inc(layout.row_slots)
        # bias update sees the weighted lambdas, like the reference
        # (weights are folded in inside the query loop before
        # UpdatePositionBiasFactors runs, rank_objective.hpp:75-86)
        if self.num_pos:
            self._update_position_biases(g, h)
        return g, h


def _query_slices(padded_score, start, width):
    """``[queries, width]``: ``width`` consecutive elements from each of
    ``start``, one contiguous slice a query (a query's rows are
    consecutive), from a score padded at its end so no slice clamps."""
    return jax.lax.gather(
        padded_score, start[:, None],
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(),
            start_index_map=(0,)),
        slice_sizes=(width,),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


@functools.partial(jax.jit, static_argnames=("trunc", "norm"))
@scoped("boost/gradients/lambdarank")
def _lambdarank_grads(score, classes, slot_of_row, weight, sigma, trunc,
                      norm):
    """LambdaMART lambdas/hessians over padded query blocks, every
    length class of ``_RankLayout`` inside this one XLA program (compiled
    once per dataset shape; ``trunc``/``norm`` are config-static)."""

    def per_block(sd, mask_b, gains_b, inv_b):
        s = jnp.where(mask_b, sd, -jnp.inf)  # [blk, w]
        ranks = _ranks_desc(s)               # [blk, w]
        disc = jnp.where(
            mask_b, 1.0 / jnp.log2(2.0 + ranks.astype(s.dtype)), 0.0)
        # pairwise tensors [blk, w, w]
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

    # a slice of the widest class from any query's first row stays inside
    padded_score = jnp.pad(score, (0, classes[-1][2].shape[-1]))

    def block(_, xs):
        start_b, length_b, gains_b, inv_b = xs
        width = gains_b.shape[-1]
        mask_b = jnp.arange(width)[None, :] < length_b[:, None]
        sd = jnp.where(
            mask_b, _query_slices(padded_score, start_b, width), 0.0)
        return None, per_block(sd, mask_b, gains_b, inv_b)

    slots = [jax.lax.scan(block, None, c)[1] for c in classes]
    # every row sits in one slot of one class: a gather, not a scatter-add
    g, h = (jnp.concatenate([x.reshape(-1) for x in of_classes])
            .at[slot_of_row].get(mode="promise_in_bounds")
            for of_classes in zip(*slots))
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
