"""Batched tree traversal (prediction) as an in-order node sweep.

Re-design of Tree::Predict / the branchy per-row traversal
(/root/reference/include/LightGBM/tree.h:134,338-410 and
src/boosting/gbdt_prediction.cpp): one ``fori_loop`` over nodes in
creation order (parents always precede children) decides each node for
ALL rows at once from the node's scalar attributes, so no [n]-sized
gathers from node tables ever occur — XLA:TPU serializes those per
element.

Missing-value routing matches the reference's NumericalDecision
(tree.h:338-360): missing_type none -> NaN treated as 0; zero -> |v| <=
kZeroThreshold or NaN follows the default arm; nan -> NaN follows the
default arm (encoded in decision_type bits, see models/tree.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["predict_leaf_binned", "predict_leaf_raw", "StackedTrees"]

K_ZERO_THRESHOLD = 1e-35

# missing_type codes (match decision_type bits 2-3 in the model format)
MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class StackedTrees(NamedTuple):
    """A whole forest as stacked tensors: leading axis = tree index.

    Leaves are referenced as ``~leaf`` in child arrays (tree.h convention).
    """
    split_feature: jnp.ndarray   # [T, L-1] i32
    threshold: jnp.ndarray       # [T, L-1] f64/f32 real-valued thresholds
    threshold_bin: jnp.ndarray   # [T, L-1] i32
    default_left: jnp.ndarray    # [T, L-1] bool
    missing_type: jnp.ndarray    # [T, L-1] i8
    is_categorical: jnp.ndarray  # [T, L-1] bool
    cat_bitset: jnp.ndarray      # [T, L-1, W] u32 category membership bitsets
    left_child: jnp.ndarray      # [T, L-1] i32
    right_child: jnp.ndarray     # [T, L-1] i32
    leaf_value: jnp.ndarray      # [T, L] f32
    # linear leaves (None for constant-leaf forests)
    lin_const: jnp.ndarray = None   # [T, L] f32
    lin_nfeat: jnp.ndarray = None   # [T, L] i32
    lin_feats: jnp.ndarray = None   # [T, L, km] i32 (real feature ids)
    lin_coef: jnp.ndarray = None    # [T, L, km] f32


def _traverse(n: int, decide_node_fn, left_child, right_child):
    """Route every row to its leaf by ONE in-order sweep over nodes.

    Internal node k is created by split k, so a node's index is always
    greater than its parent's (models/tree.py follows the reference's
    Tree::Split numbering) — processing nodes 0..nn-1 in order
    therefore visits each row's path nodes in path order, and a single
    ``fori_loop`` replaces the per-level pointer chase. Crucially,
    each step uses SCALAR node attributes (``decide_node_fn(i)``
    evaluates node i's decision for all rows at once), so there are no
    [n]-sized gathers from node tables — XLA:TPU executes those one
    element at a time; this sweep is pure vector selects.
    """
    nn = left_child.shape[0]
    node0 = jnp.zeros((n,), jnp.int32)

    def body(i, node):
        go_left = decide_node_fn(i)
        nxt = jnp.where(go_left, left_child[i], right_child[i])
        return jnp.where(node == i, nxt, node)

    node = lax.fori_loop(0, nn, body, node0)
    return ~node  # leaf indices


def predict_leaf_binned(split_feature, threshold_bin, default_left,
                        left_child, right_child, feat_nan_bin,
                        bins_T, is_cat=None, cat_masks=None) -> jnp.ndarray:
    """Leaf index per row for one tree over the *binned* matrix [F, n].

    Used for train/valid score updates during boosting, where data is
    already binned (the ScoreUpdater::AddScore analog, score_updater.hpp).
    ``is_cat``/``cat_masks`` ([nn] bool, [nn, B] bool) route categorical
    nodes by bin membership instead of the bin threshold.
    """
    n = bins_T.shape[1]

    def decide(i):
        sf = split_feature[i]
        v = lax.dynamic_index_in_dim(bins_T, sf, keepdims=False) \
            .astype(jnp.int32)                                # [n]
        nb = feat_nan_bin[sf]
        num_left = jnp.where((nb >= 0) & (v == nb), default_left[i],
                             v <= threshold_bin[i])
        if is_cat is None:
            return num_left

        def cat_branch():
            # bin membership via the node's [B] mask: one-hot compare
            # (a cat_masks[i, v] gather would serialize per element).
            # This caller is never vmapped, so lax.cond genuinely
            # skips the [n, B] pass on numeric nodes
            B = cat_masks.shape[1]
            return jnp.any((v[:, None] == jnp.arange(B)[None, :])
                           & cat_masks[i][None, :], axis=1)

        return lax.cond(is_cat[i], cat_branch, lambda: num_left)

    return _traverse(n, decide, left_child, right_child)


def predict_leaf_raw(tree: StackedTrees, ti: int | jnp.ndarray,
                     X: jnp.ndarray) -> jnp.ndarray:
    """Leaf index per row for tree ``ti`` over raw features ``[n, F]``."""
    n = X.shape[0]
    X_T = X.T  # [F, n]: node sweeps slice whole contiguous columns
    sf = tree.split_feature[ti]
    thr = tree.threshold[ti]
    dl = tree.default_left[ti]
    mt = tree.missing_type[ti]
    is_cat = tree.is_categorical[ti]
    bitset = tree.cat_bitset[ti]

    def decide(i):
        v = lax.dynamic_index_in_dim(X_T, sf[i], keepdims=False)  # [n]
        m = mt[i]
        is_nan = jnp.isnan(v)
        v0 = jnp.where(is_nan, 0.0, v)
        # numerical decision with missing routing (tree.h:338-360)
        is_zero = jnp.abs(v0) <= K_ZERO_THRESHOLD
        missing = jnp.where(m == MISSING_NAN, is_nan,
                            jnp.where(m == MISSING_ZERO, is_zero | is_nan,
                                      jnp.zeros_like(is_nan)))
        num_left = jnp.where(missing, dl[i], v0 <= thr[i])

        def cat_branch():
            # membership in the node's u32 bitset (tree.h:402): the
            # word lookup unrolls over the W (small) bitset words —
            # a per-row bitset[word] gather would serialize. NOTE:
            # under _forest_leaves' vmap the cond lowers to a select
            # and this branch runs for numeric nodes too; at W words
            # it is a handful of [n] selects, which is still far
            # cheaper than any gather formulation
            iv = jnp.where(is_nan | (v < 0), -1, v).astype(jnp.int32)
            word = iv // 32
            bit = (iv % 32).astype(jnp.uint32)
            bits = bitset[i]                          # [W] u32
            W = bits.shape[0]
            w = jnp.zeros((n,), jnp.uint32)
            for k in range(W):
                w = jnp.where(word == k, bits[k], w)
            return (iv >= 0) & (word < W) \
                & (((w >> bit) & 1) != 0)

        return lax.cond(is_cat[i], cat_branch, lambda: num_left)

    return _traverse(n, decide, tree.left_child[ti], tree.right_child[ti])

# NOTE: the old `predict_forest_raw` (a fori_loop-of-trees scorer) was
# removed by tpulint TPL001: prediction.py's vmapped `_forest_leaves`
# replaced every caller long ago, leaving it dead — and a dead eager
# loop is one import away from dispatching op-by-op. Its KNOWN_JITTED
# allowlist entry was stale (nothing jitted it), and its eager-scope
# references also demoted `predict_leaf_raw`/`_traverse` out of the
# derived jit-reachable set. `python -m lightgbm_tpu lint` guards the
# replacement path.
