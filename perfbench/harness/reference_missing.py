"""The plain reference of a boosting round on a table with missing
values: ``reference.py``'s round, taught what a NaN is.

This file imports nothing of the program. From ``reference.py`` it takes
what a NaN does not touch (the table's upload, the binary objective, the
tree's shape helpers) and from ``reference_rank.py`` the AUC; it edits
neither. There is no upstream checkout here, so the semantics are written
from the reference's documentation as SURVEY.md cites it:

- routing a row through a node (``Tree::NumericalDecision``, tree.h:338-360;
  SURVEY.md lines 65, 282 and 503; ``docs/Advanced-Topics.rst`` "Missing
  Value Handle": "LightGBM uses NA (NaN) to represent missing values by
  default", ``zero_as_missing=true`` makes zeros missing too,
  ``use_missing=false`` turns the handling off)::

      if isnan(x) and missing_type != NaN:  x = 0.0
      if (missing_type == Zero and |x| <= 1e-35)
              or (missing_type == NaN and isnan(x)):
          go left if default_left else right
      else:
          go left if x <= threshold else right

- the split search (``FeatureHistogram::FindBestThresholdSequentially``,
  feature_histogram.hpp; SURVEY.md line 55 for the NaN bin): a column
  that has missing values is scanned twice, once with the missing rows
  on the right of every threshold and once with them on the left, and
  the last threshold of the first scan is "all finite values left, the
  missing rows right". Here: on the reference's own candidate thresholds
  (quantiles of the column's FINITE sample values, plus +inf for "all
  finite values one side") the finite rows with ``x <= c`` go left and
  the NaN rows are tried on either side.

From the raw table with its NaN, in ``jax.numpy`` float32, it recomputes
what ``reference.py`` does (gradients at its own running score, every
row routed on raw values, each node's rows and sums, the best gain at
the root and at deep nodes, the score after all trees) and beside it, per
internal node, the rows, gradient sum and hessian sum of the rows that
reach the node with a NaN in its column: what moving them to the other
side would change. Nothing is multiplied by a matrix, so no matmul
precision is at stake.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import reference as R
from .reference_rank import auc  # noqa: F401  (the check's metric)

MISSING = {"None": 0, "Zero": 1, "NaN": 2}
K_ZERO = 1e-35


def parse_tree(tree_json):
    """``reference.parse_tree`` with each node's ``default_left`` (bool)
    and ``missing_type`` (0 None, 1 Zero, 2 NaN)."""
    L = int(tree_json["num_leaves"])
    I = L - 1
    t = {
        "num_leaves": L,
        "feature": np.zeros(I, np.int32), "threshold": np.zeros(I, np.float64),
        "default_left": np.zeros(I, bool),
        "missing_type": np.zeros(I, np.int32),
        "left": np.zeros(I, np.int32), "right": np.zeros(I, np.int32),
        "gain": np.zeros(I, np.float64),
        "internal_count": np.zeros(I, np.int64),
        "leaf_value": np.zeros(L, np.float64),
        "leaf_weight": np.zeros(L, np.float64),
        "leaf_count": np.zeros(L, np.int64),
        "order": [],
    }
    if L == 1:
        t["leaf_value"][0] = tree_json["tree_structure"]["leaf_value"]
        t["order"] = np.zeros(0, np.int32)
        return t

    def node_id(n):
        return int(n["split_index"]) if "split_index" in n \
            else I + int(n["leaf_index"])

    stack = [tree_json["tree_structure"]]
    while stack:
        n = stack.pop()
        if "split_index" in n:
            k = int(n["split_index"])
            if n["decision_type"] != "<=":
                raise ValueError(
                    "this reference routes numerical '<=' splits only; node "
                    f"{k} has decision_type {n['decision_type']!r}")
            t["order"].append(k)
            t["feature"][k] = n["split_feature"]
            t["threshold"][k] = n["threshold"]
            t["default_left"][k] = bool(n["default_left"])
            t["missing_type"][k] = MISSING[str(n["missing_type"])]
            t["gain"][k] = n["split_gain"]
            t["internal_count"][k] = n["internal_count"]
            t["left"][k] = node_id(n["left_child"])
            t["right"][k] = node_id(n["right_child"])
            stack.append(n["right_child"])
            stack.append(n["left_child"])
        else:
            j = int(n["leaf_index"])
            t["leaf_value"][j] = n["leaf_value"]
            t["leaf_weight"][j] = n["leaf_weight"]
            t["leaf_count"][j] = n["leaf_count"]
    t["order"] = np.asarray(t["order"], np.int32)
    return t


def tree_args(tree):
    with np.errstate(over="ignore"):    # 1e300 -> the largest float32
        thr32 = R.threshold_f32(tree["threshold"])
    return (jnp.asarray(tree["feature"]), jnp.asarray(thr32),
            jnp.asarray(tree["default_left"]),
            jnp.asarray(tree["missing_type"]),
            jnp.asarray(tree["left"]), jnp.asarray(tree["right"]),
            jnp.asarray(tree["order"]))


def is_missing(x, missing_type):
    """The rows a node of ``missing_type`` sends by its default
    direction: NaN under ``NaN``; zero, and NaN (which reads as zero),
    under ``Zero``; none under ``None``."""
    nan = jnp.isnan(x)
    zero = jnp.abs(jnp.where(nan, jnp.float32(0.0), x)) <= K_ZERO
    return jnp.where(missing_type == 2, nan, (missing_type == 1) & zero)


def goes_left(x, thr32, default_left, missing_type):
    """``NumericalDecision`` for a vector of raw values at one node."""
    x0 = jnp.where(jnp.isnan(x), jnp.float32(0.0), x)
    return jnp.where(is_missing(x, missing_type), default_left, x0 <= thr32)


@functools.partial(jax.jit, static_argnames="with_stats")
def route_tree(X_T, feature, thr32, default_left, missing_type, left, right,
               order, g, h, with_stats=True):
    """Row -> node id after the tree and, per internal node, the rows
    ``[cntL, cntR, cntMissing]`` (int32, exact) and the sums ``[GL, HL,
    GR, HR, GMissing, HMissing]`` over the rows that reach it; the
    ``Missing`` entries are over the rows the node sent by its default
    direction (``is_missing``), whichever side that was."""
    n = X_T.shape[1]
    I = feature.shape[0]

    def body(i, carry):
        at, counts, stats = carry
        k = order[i]
        x = lax.dynamic_index_in_dim(X_T, feature[k], 0, keepdims=False)
        here = at == k
        to_l = goes_left(x, thr32[k], default_left[k], missing_type[k])
        go_l, go_r = here & to_l, here & ~to_l
        if with_stats:
            nan = here & is_missing(x, missing_type[k])

            def total(mask, v):
                return jnp.sum(jnp.where(mask, v, 0.0))

            counts = counts.at[k].set(jnp.stack([
                jnp.sum(m, dtype=jnp.int32) for m in (go_l, go_r, nan)]))
            stats = stats.at[k].set(jnp.stack([
                total(go_l, g), total(go_l, h), total(go_r, g),
                total(go_r, h), total(nan, g), total(nan, h)]))
        at = jnp.where(go_l, left[k], jnp.where(go_r, right[k], at))
        return at, counts, stats

    at0 = jnp.zeros((n,), jnp.int32)
    counts0 = jnp.zeros((I, 3), jnp.int32)
    stats0 = jnp.zeros((I, 6), jnp.float32)
    return lax.fori_loop(0, I, body, (at0, counts0, stats0))


def split_gain(gl, hl, gr, hr, lam):
    g, h = gl + gr, hl + hr
    return gl * gl / (hl + lam) + gr * gr / (hr + lam) - g * g / (h + lam)


def direction_shortfall(tree, counts, stats, min_data, min_hess, lam):
    """Per internal node: the gain with the node's missing rows (NaN;
    zeros too under ``Zero``) on the OTHER side less the gain with them
    where the tree's ``default_left`` put them, relative to the latter (or, as ``check._gaps`` floors a gap,
    to the tree's median gain or a thousandth of its largest where that
    is larger: a node of rows that all but share one gradient wins
    rounding noise whichever way its NaN rows go); ``-inf`` where the
    question does not arise (the node's ``missing_type`` is None, no
    missing row reaches it, or the other side would break
    ``min_data_in_leaf`` / ``min_sum_hessian_in_leaf`` or leave a child
    empty). Positive: the other direction was the better split."""
    counts = np.asarray(counts, np.float64)
    s = np.asarray(stats, np.float64)
    sign = np.where(tree["default_left"], -1.0, 1.0)    # left's change
    cl = counts[:, 0] + sign * counts[:, 2]
    cr = counts[:, 1] - sign * counts[:, 2]
    gl, hl = s[:, 0] + sign * s[:, 4], s[:, 1] + sign * s[:, 5]
    gr, hr = s[:, 2] - sign * s[:, 4], s[:, 3] - sign * s[:, 5]
    asked = (tree["missing_type"] != 0) & (counts[:, 2] > 0) \
        & (cl >= max(min_data, 1)) & (cr >= max(min_data, 1)) \
        & (hl >= min_hess) & (hr >= min_hess)
    with np.errstate(divide="ignore", invalid="ignore"):
        here = split_gain(s[:, 0], s[:, 1], s[:, 2], s[:, 3], lam)
        other = split_gain(gl, hl, gr, hr, lam)
        floor = max(np.median(np.abs(here)), 1e-3 * np.max(np.abs(here)),
                    1e-300) if here.size else 1.0
        short = (other - here) / np.maximum(np.abs(here), floor)
    return np.where(asked & np.isfinite(short), short, -np.inf)


# ---------------------------------------------------------------------
# the best split at a node, the NaN rows tried on either side
# ---------------------------------------------------------------------

def missing_mode(ref_cfg):
    """How the configuration treats a missing value: ``nan`` (the
    default: a NaN is missing), ``zero`` (``zero_as_missing``: zeros and
    NaN are), ``none`` (``use_missing=false``: a NaN reads as 0.0)."""
    if not ref_cfg.get("use_missing", True):
        return "none"
    return "zero" if ref_cfg.get("zero_as_missing", False) else "nan"


def candidate_thresholds(X, seed, k, sample_rows, mode="nan"):
    """``[F, k + 1]`` float32: per feature up to ``k`` distinct values of
    a row sample drawn from the seed, at evenly spaced ranks of the
    column's sample values that are NOT missing under ``mode`` (``x <= c``
    is the split), then ``+inf``: every value that is not missing on one
    side. Unused slots hold ``+inf`` too."""
    n, F = X.shape
    rng = np.random.default_rng([int(seed), 0x5EED])
    rows = np.sort(rng.choice(n, size=min(sample_rows, n), replace=False))
    S = X[rows]
    out = np.full((F, k + 1), np.inf, np.float32)
    q = (np.arange(1, k + 1) / (k + 1.0))
    for f in range(F):
        col = S[:, f]
        if mode == "none":
            col = np.nan_to_num(col, nan=0.0)
        col = col[~np.isnan(col) & ((mode != "zero")
                                    | (np.abs(col) > K_ZERO))]
        if col.size:
            c = np.unique(np.quantile(col, q, method="lower"))
            out[f, :len(c)] = c
    return out


@functools.partial(jax.jit, static_argnames=("block", "mode"))
def node_best_gain(X_T, cands, g, h, w, min_data, min_hess, lam,
                   block=1 << 16, mode="nan"):
    """Best gain over every feature, candidate and side of the missing
    rows, over the rows with ``w`` = 1 (all ones at the root); per
    feature ``[F]``. The sums under ``x <= c`` are over the rows that are
    not missing (a NaN compares false with every candidate); the missing
    rows' sums join the left or the right. Under ``mode`` ``none`` a NaN
    is the value 0.0 and no row is missing."""
    F, n = X_T.shape
    pad = (-n) % block
    g, h = g * w, h * w
    wp = jnp.pad(w, (0, pad)).reshape(-1, block)
    gp = jnp.pad(g, (0, pad)).reshape(-1, block)
    hp = jnp.pad(h, (0, pad)).reshape(-1, block)
    N, G, H = jnp.sum(w), jnp.sum(g), jnp.sum(h)
    floor = jnp.maximum(min_data, 1.0)

    def one_feature(args):
        x, c = args
        xp = jnp.pad(x, (0, pad), constant_values=jnp.inf) \
            .reshape(-1, block)

        def blk(acc, xs):
            xb, wb, gb, hb = xs
            if mode == "none":
                xb = jnp.where(jnp.isnan(xb), jnp.float32(0.0), xb)
            gone = is_missing(xb, {"nan": 2, "zero": 1, "none": 0}[mode])
            m = jnp.concatenate([(xb[None, :] <= c[:, None]) & ~gone[None, :],
                                 gone[None, :]])
            add = jnp.stack([jnp.sum(jnp.where(m, wb[None, :], 0.0), axis=1),
                             jnp.sum(jnp.where(m, gb[None, :], 0.0), axis=1),
                             jnp.sum(jnp.where(m, hb[None, :], 0.0), axis=1)],
                            axis=1)
            return acc + add, None

        acc, _ = lax.scan(blk, jnp.zeros((c.shape[0] + 1, 3), jnp.float32),
                          (xp, wp, gp, hp))
        nan, fin = acc[-1], acc[:-1]
        best = -jnp.inf
        for left in (fin, fin + nan[None, :]):  # missing right, then left
            cl, gl, hl = left[:, 0], left[:, 1], left[:, 2]
            cr, gr, hr = N - cl, G - gl, H - hl
            ok = (cl >= floor) & (cr >= floor) \
                & (hl >= min_hess) & (hr >= min_hess)
            gain = split_gain(gl, hl, gr, hr, lam)
            best = jnp.maximum(best, jnp.max(jnp.where(ok, gain, -jnp.inf)))
        return best

    return lax.map(one_feature, (X_T, cands))
