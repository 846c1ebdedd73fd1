"""Linear-leaf fitting (linear_tree).

Re-design of LinearTreeLearner::CalculateLinear
(/root/reference/src/treelearner/linear_tree_learner.cpp:180-375) for TPU:
per-leaf coefficients  beta = -(X^T H X + lambda I)^-1 X^T g  where X is
[leaf branch numerical features | 1].  Instead of per-thread accumulation
into triangular buffers, the normal equations for ALL leaves are built in
one batched segment-reduction over rows and solved with one batched
jnp.linalg.solve — the whole fit is three fused device passes.

Reference semantics kept:
- rows with NaN in any of the leaf's features are excluded from the fit
  and fall back to the piecewise-constant leaf value at prediction
  (tree.cpp:134-148);
- leaves with fewer valid rows than coefficients keep the constant model
  (linear_tree_learner.cpp:330-341);
- lambda is added to feature diagonals only, not the bias.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gather import gather_small

__all__ = ["branch_features_per_leaf", "fit_leaf_linear",
           "linear_leaf_values"]


def linear_leaf_values(const: jnp.ndarray, coef: jnp.ndarray,
                       feats: jnp.ndarray, nfeat: jnp.ndarray,
                       fallback: jnp.ndarray, X: jnp.ndarray,
                       leaves: jnp.ndarray) -> jnp.ndarray:
    """Per-row output of linear leaves with NaN fallback to the constant
    leaf value (tree.cpp:120-150 PredictionFunLinear). Shared by training
    score updates, binned valid scoring and raw batch prediction.

    Args:
      const: ``[L]`` fitted constants. coef: ``[L, km]``. feats: ``[L,
        km]`` feature column ids into X. nfeat: ``[L]`` active counts.
      fallback: ``[L]`` piecewise-constant leaf values.
      X: ``[n, F]`` feature values (NaN preserved). leaves: ``[n]`` i32.
    """
    km = feats.shape[1]
    if km == 0:
        return gather_small(const, leaves)
    # gather_small for every [n]-sized leaf lookup: XLA:TPU runs
    # small-table gathers one element at a time (ops/gather.py)
    fr = gather_small(feats, leaves)                       # [n, km]
    act = jnp.arange(km)[None, :] < gather_small(nfeat, leaves)[:, None]
    x = jnp.take_along_axis(X, fr, axis=1)
    nanrow = jnp.any(jnp.isnan(x) & act, axis=1)
    lin = gather_small(const, leaves) + jnp.sum(
        jnp.where(act, jnp.nan_to_num(x) * gather_small(coef, leaves),
                  0.0), axis=1)
    return jnp.where(nanrow, gather_small(fallback, leaves), lin)


def branch_features_per_leaf(split_feature: np.ndarray,
                             left_child: np.ndarray,
                             right_child: np.ndarray,
                             leaf_parent: np.ndarray,
                             num_leaves: int,
                             is_numerical) -> list:
    """Per-leaf sorted unique numerical features on the root->leaf path
    (Tree::branch_features analog; host-side, trees are tiny)."""
    nn = max(num_leaves - 1, 0)
    parent_of_node = np.full(nn, -1, np.int64)
    for i in range(nn):
        for c in (left_child[i], right_child[i]):
            if c >= 0:
                parent_of_node[c] = i
    out = []
    for leaf in range(num_leaves):
        feats = set()
        node = int(leaf_parent[leaf])
        while node >= 0:
            f = int(split_feature[node])
            if is_numerical(f):
                feats.add(f)
            node = int(parent_of_node[node])
        out.append(sorted(feats))
    return out


def fit_leaf_linear(raw: jnp.ndarray,
                    row_leaf: jnp.ndarray,
                    grad: jnp.ndarray,
                    hess: jnp.ndarray,
                    row_weight: jnp.ndarray,
                    leaf_feats: jnp.ndarray,
                    leaf_nfeat: jnp.ndarray,
                    leaf_value: jnp.ndarray,
                    linear_lambda: float
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fit every leaf's linear model in one batched pass.

    Args:
      raw: ``[n, F]`` float32 raw feature values (NaN preserved).
      row_leaf: ``[n]`` i32 leaf assignment.
      grad, hess: ``[n]`` float gradients/hessians.
      row_weight: ``[n]`` bagging/GOSS weight (0 = out of bag — excluded
        from the fit, like the reference's leaf_map_[i] == -1 skip).
      leaf_feats: ``[L, kmax]`` i32 per-leaf feature ids (0-padded).
      leaf_nfeat: ``[L]`` i32 number of active features per leaf.
      leaf_value: ``[L]`` float piecewise-constant outputs (fallback).
      linear_lambda: L2 regularization on coefficients.

    Returns:
      (leaf_const [L], leaf_coeff [L, kmax], train_pred [n]).
    """
    n, F = raw.shape
    L, kmax = leaf_feats.shape
    dtype = grad.dtype
    k1 = kmax + 1
    w = row_weight.astype(dtype)

    feats_row = leaf_feats[row_leaf]                       # [n, kmax]
    active_row = jnp.arange(kmax)[None, :] < leaf_nfeat[row_leaf][:, None]
    x = jnp.take_along_axis(raw, feats_row, axis=1)        # [n, kmax]
    row_ok = ~jnp.any(jnp.isnan(x) & active_row, axis=1)
    x = jnp.where(active_row & row_ok[:, None],
                  jnp.nan_to_num(x.astype(dtype)), 0.0)
    xa = jnp.concatenate([x, jnp.ones((n, 1), dtype)], axis=1)
    in_fit = row_ok & (w > 0)
    xa = xa * in_fit[:, None].astype(dtype)                # [n, k1]
    grad = grad * w
    hess = hess * w

    outer = xa[:, :, None] * (xa * hess[:, None])[:, None, :]
    XtHX = jax.ops.segment_sum(outer.reshape(n, k1 * k1), row_leaf,
                               num_segments=L).reshape(L, k1, k1)
    Xtg = jax.ops.segment_sum(xa * grad[:, None], row_leaf, num_segments=L)
    cnt_ok = jax.ops.segment_sum(in_fit.astype(dtype), row_leaf,
                                 num_segments=L)

    active_col = jnp.arange(kmax)[None, :] < leaf_nfeat[:, None]  # [L,kmax]
    act1 = jnp.concatenate([active_col, jnp.ones((L, 1), bool)], axis=1)
    pair_act = act1[:, :, None] & act1[:, None, :]
    eye = jnp.eye(k1, dtype=dtype)
    # diagonal additions: lambda on active feature entries, 0 on the bias,
    # and 1 on inactive (padded) entries so the batched solve stays
    # non-singular
    lam_vec = jnp.concatenate(
        [jnp.full((kmax,), linear_lambda, dtype), jnp.zeros((1,), dtype)])
    diag_add = jnp.where(act1, lam_vec[None, :], 1.0)     # [L, k1]
    A = jnp.where(pair_act, XtHX, 0.0) + eye[None] * diag_add[:, None, :]
    b = jnp.where(act1, Xtg, 0.0)
    coef = -jnp.linalg.solve(A, b[..., None])[..., 0]      # [L, k1]

    finite = jnp.all(jnp.isfinite(coef), axis=1)
    ok_leaf = (cnt_ok >= (leaf_nfeat + 1).astype(dtype)) & finite
    const = jnp.where(ok_leaf, coef[:, -1], leaf_value)
    coeffs = jnp.where(ok_leaf[:, None] & active_col, coef[:, :kmax], 0.0)

    pred_lin = gather_small(const, row_leaf) + jnp.sum(
        gather_small(coeffs, row_leaf) * x, axis=1)
    pred = jnp.where(row_ok, pred_lin, gather_small(leaf_value, row_leaf))
    return const, coeffs, pred
