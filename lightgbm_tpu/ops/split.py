"""Best-split search over histograms.

Re-design of FeatureHistogram::FindBestThreshold
(/root/reference/src/treelearner/feature_histogram.hpp:165 and the
numerical scan ``FindBestThresholdSequentially``) as a fully vectorized
two-direction prefix-scan over all features at once — no per-feature loop,
no template zoo; XLA fuses the whole search into a handful of kernels.

Missing handling matches the reference's dual scan: the left->right scan
sends the NaN bin right (default_left = False); the right->left scan is
realized as "NaN bin joined to the left side" (default_left = True).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.scopes import scoped

__all__ = ["SplitParams", "SplitResult", "find_best_split"]

K_EPS = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitParams(NamedTuple):
    """Static split-search hyperparameters (baked into the jitted fn)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    # categorical-split knobs (feature_histogram.hpp categorical path)
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0
    # leaf-output smoothing toward the parent's output
    # (CalculateSplittedLeafOutput USE_SMOOTHING, feature_histogram.hpp:732)
    path_smooth: float = 0.0
    # depth-based gain penalty on monotone-feature splits
    # (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:357)
    monotone_penalty: float = 0.0


class SplitResult(NamedTuple):
    """Best split for one leaf (SplitInfo analog, split_info.hpp)."""
    gain: jnp.ndarray          # f32 scalar; <= 0 means "no valid split"
    feature: jnp.ndarray       # i32
    threshold_bin: jnp.ndarray  # i32
    default_left: jnp.ndarray  # bool
    is_cat: jnp.ndarray        # bool — categorical membership split
    cat_mask: jnp.ndarray      # [B] bool — bins routed left (cat splits)
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray


def _threshold_l1(s, l1):
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(sum_g, sum_h, p: SplitParams):
    """Optimal leaf value -T_l1(g) / (h + l2), clipped by max_delta_step
    (CalculateSplittedLeafOutput, feature_histogram.hpp)."""
    w = -_threshold_l1(sum_g, p.lambda_l1) / (sum_h + p.lambda_l2 + K_EPS)
    if p.max_delta_step > 0.0:
        w = jnp.clip(w, -p.max_delta_step, p.max_delta_step)
    return w


def leaf_gain(sum_g, sum_h, p: SplitParams):
    """Gain of a leaf at its optimal (possibly clipped) output."""
    if p.max_delta_step > 0.0:
        w = leaf_output(sum_g, sum_h, p)
        t = _threshold_l1(sum_g, p.lambda_l1)
        return -(2.0 * t * w + (sum_h + p.lambda_l2) * w * w)
    t = _threshold_l1(sum_g, p.lambda_l1)
    return t * t / (sum_h + p.lambda_l2 + K_EPS)


def gain_at_output(sum_g, sum_h, w, p: SplitParams):
    """Leaf gain evaluated at a fixed (smoothed/clamped) output
    (GetLeafGainGivenOutput, feature_histogram.hpp)."""
    t = _threshold_l1(sum_g, p.lambda_l1)
    return -(2.0 * t * w + (sum_h + p.lambda_l2) * w * w)


def smooth_output(w, cnt, parent_output, p: SplitParams):
    """Shrink a leaf output toward its parent's:
    ``w*(n/s)/(n/s+1) + parent/(n/s+1)`` with s = path_smooth
    (CalculateSplittedLeafOutput USE_SMOOTHING, feature_histogram.hpp:734)."""
    if p.path_smooth <= 0.0:
        return w
    a = cnt / p.path_smooth
    return w * a / (a + 1.0) + parent_output / (a + 1.0)


def split_bounds_lrc(bounds):
    """Resolve a bounds spec into (left, right, cat) bound pairs.

    2-tuple (min, max): one bound for both children (basic/intermediate
    modes — scalars). 6-tuple (lmin_l, lmax_l, lmin_r, lmax_r, smin,
    smax): per-(feature, threshold) [F, B] arrays for the left/right
    children plus scalar fallbacks for categorical candidates — the
    monotone precise mode (AdvancedLeafConstraints,
    monotone_constraints.hpp:858)."""
    if bounds is None:
        return None, None, None
    if len(bounds) == 6:
        return ((bounds[0], bounds[1]), (bounds[2], bounds[3]),
                (bounds[4], bounds[5]))
    return bounds, bounds, bounds


def _parent_gain_shifted(total, p: SplitParams, p_out):
    """Parent gain at its (path-smoothed) output + min_gain_to_split —
    the per-candidate shift both searches subtract before the argmax
    (ComputeBestSplitForFeature's gain_shift)."""
    if p.path_smooth > 0.0:
        w_parent = smooth_output(leaf_output(total[0], total[1], p),
                                 total[2], p_out, p)
        parent_gain = gain_at_output(total[0], total[1], w_parent, p)
    else:
        parent_gain = leaf_gain(total[0], total[1], p)
    return parent_gain + p.min_gain_to_split


def _winner_outputs(lgs, lhs, lcs, rgs, rhs, rcs, is_sorted_cat,
                    exact, p: SplitParams, p_out, b_lw, b_rw):
    """The winning split's child outputs: sorted-categorical winners
    use l2 + cat_l2 (feature_histogram.cpp:144); the exact path
    smooths and clamps (CalculateSplittedLeafOutput composition)."""
    p_cat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)
    if exact:
        lo = jnp.where(
            is_sorted_cat,
            constrained_output(lgs, lhs, lcs, p_out, b_lw, p_cat),
            constrained_output(lgs, lhs, lcs, p_out, b_lw, p))
        ro = jnp.where(
            is_sorted_cat,
            constrained_output(rgs, rhs, rcs, p_out, b_rw, p_cat),
            constrained_output(rgs, rhs, rcs, p_out, b_rw, p))
    else:
        lo = jnp.where(is_sorted_cat, leaf_output(lgs, lhs, p_cat),
                       leaf_output(lgs, lhs, p))
        ro = jnp.where(is_sorted_cat, leaf_output(rgs, rhs, p_cat),
                       leaf_output(rgs, rhs, p))
    return lo, ro


def constrained_output(sum_g, sum_h, cnt, parent_output, bounds,
                       p: SplitParams):
    """Optimal output, then smoothing, then monotone min/max clamp — the
    composition order of CalculateSplittedLeafOutput<USE_MC,...>."""
    w = leaf_output(sum_g, sum_h, p)
    w = smooth_output(w, cnt, parent_output, p)
    if bounds is not None:
        w = jnp.clip(w, bounds[0], bounds[1])
    return w


def monotone_penalty_mult(leaf_depth, p: SplitParams):
    """Gain multiplier for monotone-feature splits at a given depth
    (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:357-366)."""
    pen = p.monotone_penalty
    d = leaf_depth.astype(jnp.float32)
    if pen <= 0.0:
        return jnp.asarray(1.0, jnp.float32)
    if pen <= 1.0:
        base = 1.0 - pen / jnp.exp2(d) + K_EPS
    else:
        base = 1.0 - jnp.exp2(pen - 1.0 - d) + K_EPS
    return jnp.where(pen >= d + 1.0, K_EPS, base)


def _cat_split_eval(hist, parent_g, parent_h, parent_cnt,
                    feat_num_bins, p: SplitParams,
                    parent_output=None, bounds=None):
    """Categorical split candidates, vectorized over all features.

    Mirrors FindBestThresholdCategoricalInner
    (src/treelearner/feature_histogram.cpp:144):
    - features with <= max_cat_to_onehot bins: one-hot scan — each bin as
      a left-singleton, plain lambda_l2;
    - otherwise: bins with enough data sorted ascending by
      g / (h + cat_smooth); prefix scans from both ends, left-set size
      capped at min(max_cat_threshold, (used+1)//2), l2 += cat_l2.
    Deviation from the reference: the sequential ``cnt_cur_group``
    min_data_per_group regrouping is relaxed to the (necessary) condition
    ``left_count >= min_data_per_group`` — the reference's rule is a
    path-dependent scan that would serialize the TPU program; the
    relaxation admits a superset of candidate prefixes.

    Returns (gains_oh, gains_fwd, gains_bwd, csum_f, csum_b, aux) where
    gains_* are [F, B] (position-indexed for fwd/bwd) and aux carries the
    sort order data needed to reconstruct the winning bin set.
    """
    F, B, _ = hist.shape
    dtype = hist.dtype
    bins = jnp.arange(B)
    in_range = bins[None, :] < feat_num_bins[:, None]
    h3 = jnp.where(in_range[:, :, None], hist, jnp.zeros_like(hist))
    g, h, c = h3[..., 0], h3[..., 1], h3[..., 2]
    p_cat = p._replace(lambda_l2=p.lambda_l2 + p.cat_l2)
    exact = p.path_smooth > 0.0 or bounds is not None

    def pair_gain(lg_, lh_, lc_, rg_, rh_, rc_, pp):
        if not exact:
            return leaf_gain(lg_, lh_, pp) + leaf_gain(rg_, rh_, pp)
        wl = constrained_output(lg_, lh_, lc_, parent_output, bounds, pp)
        wr = constrained_output(rg_, rh_, rc_, parent_output, bounds, pp)
        return gain_at_output(lg_, lh_, wl, pp) \
            + gain_at_output(rg_, rh_, wr, pp)

    # ---- one-hot path (left = one category bin) ----
    rg, rh, rc = parent_g - g, parent_h - h, parent_cnt - c
    valid_oh = (
        in_range
        & (c >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
        & (h >= p.min_sum_hessian_in_leaf)
        & (rh >= p.min_sum_hessian_in_leaf)
        & (c > 0) & (rc > 0)
    )
    gain_oh = pair_gain(g, h, c, rg, rh, rc, p)
    use_onehot = feat_num_bins <= p.max_cat_to_onehot  # [F]
    gains_oh = jnp.where(use_onehot[:, None] & valid_oh, gain_oh,
                         K_MIN_SCORE)

    # ---- sorted-subset path ----
    participate = in_range & (c >= p.cat_smooth)
    ratio = jnp.where(participate, g / (h + p.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1, stable=True)          # [F, B]
    inv = jnp.argsort(order, axis=1, stable=True)            # bin -> rank
    used = jnp.sum(participate, axis=1).astype(jnp.int32)    # [F]
    part_sorted = jnp.take_along_axis(participate, order, axis=1)
    stats_sorted = jnp.take_along_axis(h3, order[:, :, None], axis=1) \
        * part_sorted[:, :, None].astype(dtype)
    csum_f = jnp.cumsum(stats_sorted, axis=1)                # [F, B, 3]
    rev_pos = jnp.clip(used[:, None] - 1 - bins[None, :], 0, B - 1)
    stats_rev = jnp.take_along_axis(stats_sorted, rev_pos[:, :, None],
                                    axis=1)
    csum_b = jnp.cumsum(stats_rev, axis=1)

    max_num_cat = jnp.minimum(p.max_cat_threshold, (used + 1) // 2)
    pos_ok = (bins[None, :] < max_num_cat[:, None]) \
        & (bins[None, :] < used[:, None])
    right_min = max(p.min_data_in_leaf, p.min_data_per_group)

    def prefix_gains(csum):
        lg, lh, lc = csum[..., 0], csum[..., 1], csum[..., 2]
        rg_, rh_, rc_ = parent_g - lg, parent_h - lh, parent_cnt - lc
        valid = (
            pos_ok
            & (lc >= p.min_data_in_leaf) & (lc >= p.min_data_per_group)
            & (lh >= p.min_sum_hessian_in_leaf)
            & (rc_ >= right_min) & (rh_ >= p.min_sum_hessian_in_leaf)
            & (lc > 0) & (rc_ > 0)
        )
        gain = pair_gain(lg, lh, lc, rg_, rh_, rc_, p_cat)
        return jnp.where(valid & ~use_onehot[:, None], gain, K_MIN_SCORE)

    gains_fwd = prefix_gains(csum_f)
    gains_bwd = prefix_gains(csum_b)
    aux = (inv, used, participate)
    return gains_oh, gains_fwd, gains_bwd, csum_f, csum_b, aux


@scoped("grow/split_scan")
def find_best_split(hist: jnp.ndarray,
                    parent_g: jnp.ndarray,
                    parent_h: jnp.ndarray,
                    parent_cnt: jnp.ndarray,
                    feat_num_bins: jnp.ndarray,
                    feat_nan_bin: jnp.ndarray,
                    feature_mask: jnp.ndarray,
                    p: SplitParams,
                    monotone_constraints: jnp.ndarray | None = None,
                    feat_is_cat: jnp.ndarray | None = None,
                    gain_penalty: jnp.ndarray | None = None,
                    parent_output: jnp.ndarray | None = None,
                    leaf_depth: jnp.ndarray | None = None,
                    bounds: tuple | None = None,
                    return_feature_gains: bool = False):
    """Find the best (feature, threshold) over a leaf's histograms.

    Args:
      hist: ``[F, B, 2]`` (sum_g, sum_h) per feature/bin — histogram
        entries carry no counts, exactly like the reference
        (``kHistEntrySize = 2 * sizeof(hist_t)``, bin.h:39).
      parent_g/h/cnt: scalars — the leaf's total stats (``parent_cnt``
        is the exact partition count).
      feat_num_bins: ``[F]`` i32 — #bins actually used per feature.
      feat_nan_bin: ``[F]`` i32 — index of the NaN bin, or -1.
      feature_mask: ``[F]`` bool — column-sampling / trivial-feature mask.
      monotone_constraints: optional ``[F]`` i8 in {-1, 0, +1}.
      gain_penalty: optional ``[F]`` — per-feature gain penalty (CEGB
        DeltaGain) subtracted from every candidate of that feature.
      parent_output: scalar — the leaf's current output value, used by
        path smoothing (GetParentOutput, serial_tree_learner.cpp:1005).
      leaf_depth: scalar i32 — depth of the leaf, drives the
        monotone_penalty gain multiplier.
      bounds: optional (min, max) scalars — the leaf's monotone output
        constraint entry (BasicConstraint); candidate outputs are
        clamped into this interval before gains are evaluated.

    Returns a scalar SplitResult; ``gain`` is already shifted by the parent
    gain and min_gain_to_split (so "> 0" means worth splitting). The
    returned left/right counts are hessian-ratio estimates
    ``cnt = round(hess * num_data / sum_hessian)``
    (feature_histogram.hpp:528,543) — callers holding real partition
    counts overwrite them (SplitInner, serial_tree_learner.cpp:789).
    """
    F, B, _ = hist.shape
    dtype = hist.dtype
    # synthesize the per-bin count channel from the hessian ratio, rounded
    # per bin exactly like the reference's scan accumulates RoundInt(...)
    cnt_factor = parent_cnt / jnp.maximum(parent_h, K_EPS)
    hist = jnp.concatenate(
        [hist, jnp.round(hist[..., 1:2] * cnt_factor)], axis=-1)
    total = jnp.stack([parent_g, parent_h, parent_cnt]).astype(dtype)

    has_nan = feat_nan_bin >= 0
    nan_stats = jnp.where(
        has_nan[:, None],
        jnp.take_along_axis(
            hist, jnp.maximum(feat_nan_bin, 0)[:, None, None].repeat(3, -1),
            axis=1)[:, 0, :],
        jnp.zeros((F, 3), dtype=dtype))  # [F, 3]

    bins = jnp.arange(B)
    # exclude the missing bin (NaN bin, or the zero bin for zero_as_missing
    # features — it may sit mid-range) from the prefix scan: missing rows
    # join a side via the learned default direction, never the threshold.
    miss_onehot = (bins[None, :] == jnp.maximum(feat_nan_bin, 0)[:, None]) \
        & has_nan[:, None]
    cum = jnp.cumsum(
        hist - miss_onehot[:, :, None] * nan_stats[:, None, :], axis=1)

    exact = p.path_smooth > 0.0 or bounds is not None
    p_out = jnp.asarray(0.0, dtype) if parent_output is None \
        else parent_output
    bounds_l, bounds_r, bounds_c = split_bounds_lrc(bounds)

    def eval_dir(left: jnp.ndarray, t_valid: jnp.ndarray):
        right = total[None, None, :] - left
        lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
        rg, rh, rc = right[..., 0], right[..., 1], right[..., 2]
        valid = (
            t_valid
            & (lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
            & (lh >= p.min_sum_hessian_in_leaf)
            & (rh >= p.min_sum_hessian_in_leaf)
            & (lc > 0) & (rc > 0)
        )
        if exact:
            lo = constrained_output(lg, lh, lc, p_out, bounds_l, p)
            ro = constrained_output(rg, rh, rc, p_out, bounds_r, p)
            gain = gain_at_output(lg, lh, lo, p) \
                + gain_at_output(rg, rh, ro, p)
        else:
            lo = ro = None
            gain = leaf_gain(lg, lh, p) + leaf_gain(rg, rh, p)
        if monotone_constraints is not None:
            if lo is None:
                lo = leaf_output(lg, lh, p)
                ro = leaf_output(rg, rh, p)
            mc = monotone_constraints[:, None]
            valid = valid & ~((mc > 0) & (lo > ro)) & ~((mc < 0) & (lo < ro))
        return jnp.where(valid, gain, K_MIN_SCORE)

    # direction 1: missing goes right — thresholds t in [0, nb-1]; the
    # lc>0/rc>0 validity checks prune degenerate all-left/all-right cuts.
    t_valid_r = bins[None, :] < feat_num_bins[:, None]
    gains_r = eval_dir(cum, t_valid_r)

    # direction 2: missing goes left — only exists for missing-typed
    # features; t = nb-1 would put everything left (rc=0, pruned anyway).
    left_l = cum + nan_stats[:, None, :]
    t_valid_l = has_nan[:, None] & (bins[None, :] < (feat_num_bins - 1)[:, None])
    gains_l = eval_dir(left_l, t_valid_l)

    fmask = feature_mask[:, None]
    gains_r = jnp.where(fmask, gains_r, K_MIN_SCORE)
    gains_l = jnp.where(fmask, gains_l, K_MIN_SCORE)

    if feat_is_cat is not None:
        num_ok = ~feat_is_cat[:, None]
        gains_r = jnp.where(num_ok, gains_r, K_MIN_SCORE)
        gains_l = jnp.where(num_ok, gains_l, K_MIN_SCORE)
        g_oh, g_fwd, g_bwd, csum_f, csum_b, (inv, used, participate) = \
            _cat_split_eval(hist, total[0], total[1], total[2],
                            feat_num_bins, p, p_out, bounds_c)
        cmask = fmask & feat_is_cat[:, None]
        g_oh = jnp.where(cmask, g_oh, K_MIN_SCORE)
        g_fwd = jnp.where(cmask, g_fwd, K_MIN_SCORE)
        g_bwd = jnp.where(cmask, g_bwd, K_MIN_SCORE)
        stacks = [gains_r, gains_l, g_oh, g_fwd, g_bwd]
    else:
        stacks = [gains_r, gains_l]

    # shift every candidate to its NET gain before the argmax: the
    # reference compares per-feature SplitInfo.gain values that are
    # already ``raw - gain_shift - DeltaGain``, optionally scaled by the
    # monotone depth penalty (ComputeBestSplitForFeature,
    # serial_tree_learner.cpp:988-997) — the scaling changes the
    # cross-feature ranking, so it must precede the argmax.
    shift = _parent_gain_shifted(total, p, p_out)
    if gain_penalty is not None:
        nets = [g - shift - gain_penalty[:, None] for g in stacks]
    else:
        nets = [g - shift for g in stacks]
    if monotone_constraints is not None and p.monotone_penalty > 0.0:
        depth = jnp.asarray(0, jnp.int32) if leaf_depth is None \
            else leaf_depth
        mult = monotone_penalty_mult(depth, p).astype(dtype)
        is_mono = (monotone_constraints != 0)[:, None]
        nets = [jnp.where(is_mono, g * mult, g) for g in nets]
    # argmax with deterministic tie-breaking: lower (dir, feature, bin) wins
    all_gains = jnp.stack(nets)  # [D, F, B]
    flat_idx = jnp.argmax(all_gains)
    best_gain_net = all_gains.reshape(-1)[flat_idx]
    d = flat_idx // (F * B)
    f = (flat_idx // B) % F
    t = flat_idx % B

    if feat_is_cat is not None:
        is_cat = d >= 2
        is_sorted_cat = d >= 3
        bins_b = jnp.arange(B)
        onehot_mask = bins_b == t
        fwd_mask = participate[f] & (inv[f] <= t)
        bwd_mask = participate[f] & (inv[f] >= used[f] - 1 - t)
        cat_mask = jnp.where(
            is_cat,
            jnp.where(d == 2, onehot_mask,
                      jnp.where(d == 3, fwd_mask, bwd_mask)),
            jnp.zeros((B,), jnp.bool_))
        num_left = jnp.where(d == 0, cum[f, t, :],
                             cum[f, t, :] + nan_stats[f, :])
        cat_left = jnp.where(d == 2, hist[f, t, :],
                             jnp.where(d == 3, csum_f[f, t, :],
                                       csum_b[f, t, :]))
        sel_left = jnp.where(is_cat, cat_left, num_left)
    else:
        is_cat = jnp.asarray(False)
        is_sorted_cat = jnp.asarray(False)
        cat_mask = jnp.zeros((B,), jnp.bool_)
        sel_left = jnp.where(
            d == 0,
            cum[f, t, :],
            cum[f, t, :] + nan_stats[f, :],
        )
    lg, lh, lc = sel_left[0], sel_left[1], sel_left[2]
    rg, rh, rc = total[0] - lg, total[1] - lh, total[2] - lc

    gain = jnp.where(jnp.isfinite(best_gain_net), best_gain_net,
                     K_MIN_SCORE)

    # the winner's bounds: scalar pair as-is, or — for the advanced
    # per-(feature, threshold) arrays — the values at (f, t) for
    # the numeric winner / the scalar fallbacks for a cat winner
    b_lw = b_rw = bounds
    if bounds is not None and len(bounds) == 6:
        b_lw = (jnp.where(is_cat, bounds[4], bounds[0][f, t]),
                jnp.where(is_cat, bounds[5], bounds[1][f, t]))
        b_rw = (jnp.where(is_cat, bounds[4], bounds[2][f, t]),
                jnp.where(is_cat, bounds[5], bounds[3][f, t]))
    lo, ro = _winner_outputs(lg, lh, lc, rg, rh, rc, is_sorted_cat,
                             exact, p, p_out, b_lw, b_rw)

    result = SplitResult(
        gain=gain.astype(dtype),
        feature=f.astype(jnp.int32),
        threshold_bin=t.astype(jnp.int32),
        default_left=(d == 1),
        is_cat=is_cat,
        cat_mask=cat_mask,
        left_sum_g=lg, left_sum_h=lh, left_count=lc,
        right_sum_g=rg, right_sum_h=rh, right_count=rc,
        left_output=lo,
        right_output=ro,
    )
    if return_feature_gains:
        # best net gain per feature — the voting-parallel learner's
        # local ballot (VotingParallelTreeLearner top-k proposals)
        return result, jnp.max(all_gains, axis=(0, 2))
    return result


@scoped("grow/split_scan")
def find_best_split_bundled(hist: jnp.ndarray,
                            parent_g: jnp.ndarray,
                            parent_h: jnp.ndarray,
                            parent_cnt: jnp.ndarray,
                            member_at: jnp.ndarray,
                            tloc_at: jnp.ndarray,
                            end_at: jnp.ndarray,
                            is_direct_f: jnp.ndarray,
                            nanpos_at: jnp.ndarray,
                            nan_at: jnp.ndarray,
                            feature_mask: jnp.ndarray,
                            p: SplitParams,
                            feat_is_cat: jnp.ndarray | None = None,
                            feat_num_bins: jnp.ndarray | None = None,
                            gain_penalty: jnp.ndarray | None = None,
                            col_mask: jnp.ndarray | None = None,
                            return_col_gains: bool = False,
                            monotone_constraints: jnp.ndarray | None = None,
                            parent_output: jnp.ndarray | None = None,
                            leaf_depth: jnp.ndarray | None = None,
                            bounds: tuple | None = None):
    """Best split over an EFB-bundled histogram (ops/bundling.py layout).

    Every candidate is one (bundle, position) cell:
    - direct (singleton) bundles behave exactly like the plain scan:
      ``left = cum[position]`` with threshold = position;
    - multi-member bundles host member thresholds at their mapped
      positions, with ``left = leaf_total - (range_end_cum - cum)`` -
      the member's bin-0 mass reconstructed from the leaf totals (the
      FixHistogram / most_freq_bin trick, dataset.h:760).
    Members with a NaN bin (direct OR multi) get the plain search's
    dual missing-direction scan: the NaN position (``nan_at``) is
    excluded from prefix sums and thresholds, and its mass
    (``nanpos_at``) joins whichever side the scanned direction sends
    missing rows to.

    Categorical members (round 5; FindGroups is type-blind,
    dataset.cpp): a bundled cat member is always in the one-hot regime
    (bundling caps membership at max_cat_to_onehot), so its candidates
    are one-hot per position — the position's own mass for tail
    categories, and the reconstructed default (bin-0 = most-frequent
    category) mass for t=0 — exactly the plain one-hot scan. Direct
    singleton cat columns carry their histogram verbatim, so the full
    plain machinery (_cat_split_eval: one-hot AND sorted-subset)
    runs on them unchanged.
    """
    G, B, _ = hist.shape
    dtype = hist.dtype
    cnt_factor = parent_cnt / jnp.maximum(parent_h, K_EPS)
    h3 = jnp.concatenate([hist, jnp.round(hist[..., 1:2] * cnt_factor)],
                         axis=-1)
    total = jnp.stack([parent_g, parent_h, parent_cnt]).astype(dtype)

    has_member = member_at >= 0
    member_ix = jnp.maximum(member_at, 0)
    direct_pos = is_direct_f[member_ix] & has_member
    # NaN-bin positions are excluded from the prefix scan exactly like
    # the plain search (missing rows join a side via the learned
    # default direction, never the threshold)
    has_nan = nanpos_at >= 0                               # [G, B]
    cum = jnp.cumsum(
        h3 * (~nan_at)[:, :, None].astype(dtype), axis=1)
    cum_flat = cum.reshape(G * B, 3)
    e = cum_flat[jnp.clip(end_at, 0, G * B - 1).reshape(-1)] \
        .reshape(G, B, 3)
    h3_flat = h3.reshape(G * B, 3)
    nan_stats = h3_flat[jnp.clip(nanpos_at, 0, G * B - 1).reshape(-1)] \
        .reshape(G, B, 3)
    nan_stats = nan_stats * has_nan[:, :, None].astype(dtype)

    if feat_is_cat is not None:
        is_cat_pos = feat_is_cat[member_ix] & has_member   # [G, B]
    else:
        is_cat_pos = jnp.zeros((G, B), jnp.bool_)
    if col_mask is not None:
        # feature-parallel: only this device's OWNED bundle columns
        # may propose candidates (window overlap on tail devices is
        # resolved by ownership, exactly like the plain fp search)
        has_member = has_member & col_mask[:, None]

    # monotone / path-smoothing support mirrors the plain search's
    # eval_dir: gains via (smoothed, clamped) outputs when exact,
    # directional validity per member's constraint sign — NEVER
    # applied to categorical candidates (plain cat gains bypass
    # direction checks too). Bounds are scalar pairs
    # (basic/intermediate) or — advanced mode — per-(feature,
    # threshold) [F_orig, B] arrays, gathered into candidate space
    # through the position->member map.
    exact = p.path_smooth > 0.0 or bounds is not None
    p_out = jnp.asarray(0.0, dtype) if parent_output is None \
        else parent_output
    bounds_l, bounds_r, bounds_c = split_bounds_lrc(bounds)
    adv = bounds is not None and len(bounds) == 6
    if adv:
        def _gpos(arr):
            # [F_orig, Bf] -> per-candidate [G, B]: the member's bound
            # at its local threshold bin (invalid cells are masked by
            # has_member before they can win)
            return arr[member_ix,
                       jnp.clip(tloc_at, 0, arr.shape[1] - 1)]

        bounds_l = (_gpos(bounds_l[0]), _gpos(bounds_l[1]))
        bounds_r = (_gpos(bounds_r[0]), _gpos(bounds_r[1]))
    if monotone_constraints is not None:
        # direction validity never applies to categorical candidates
        # (the plain cat families bypass it too)...
        mc_pos = jnp.where(is_cat_pos, 0,
                           monotone_constraints[member_ix])  # [G, B]
        # ...but the depth PENALTY rescales every candidate of a
        # constrained feature, cat or not (the plain search scales all
        # five stacks via is_mono per feature)
        mono_pos = (monotone_constraints[member_ix] != 0) & has_member
    else:
        mc_pos = None
        mono_pos = None

    def eval_left(left, extra_valid, bl=None, br=None):
        if bl is None:
            bl, br = bounds_l, bounds_r
        right = total[None, None, :] - left
        lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
        rg, rh, rc = right[..., 0], right[..., 1], right[..., 2]
        valid = (
            extra_valid & has_member & feature_mask[member_ix]
            & (lc >= p.min_data_in_leaf) & (rc >= p.min_data_in_leaf)
            & (lh >= p.min_sum_hessian_in_leaf)
            & (rh >= p.min_sum_hessian_in_leaf)
            & (lc > 0) & (rc > 0)
        )
        if exact:
            lo_ = constrained_output(lg, lh, lc, p_out, bl, p)
            ro_ = constrained_output(rg, rh, rc, p_out, br, p)
            gain = gain_at_output(lg, lh, lo_, p) \
                + gain_at_output(rg, rh, ro_, p)
        else:
            lo_ = ro_ = None
            gain = leaf_gain(lg, lh, p) + leaf_gain(rg, rh, p)
        if mc_pos is not None:
            if lo_ is None:
                lo_ = leaf_output(lg, lh, p)
                ro_ = leaf_output(rg, rh, p)
            valid = valid & ~((mc_pos > 0) & (lo_ > ro_)) \
                & ~((mc_pos < 0) & (lo_ < ro_))
        return jnp.where(valid, gain, K_MIN_SCORE)

    # direction 1: missing goes right. For multi members the member's
    # right side is its positions in (t, range_end] (NaN excluded by
    # cum) plus its NaN mass; left = total - right. Like the plain
    # scan, every member threshold is a candidate — the cut at the NaN
    # position duplicates its neighbor and is tolerated (degenerate
    # cuts are pruned by the lc/rc validity checks).
    left1 = jnp.where(direct_pos[:, :, None], cum,
                      total[None, None, :] - (e - cum) - nan_stats)
    g1 = eval_left(left1, ~is_cat_pos)
    # direction 2: missing joins the left side (NaN members only)
    left2 = jnp.where(direct_pos[:, :, None], cum + nan_stats,
                      total[None, None, :] - (e - cum))
    g2 = eval_left(left2, has_nan & ~is_cat_pos)

    shift = _parent_gain_shifted(total, p, p_out)
    if gain_penalty is not None:
        # CEGB DeltaGain per ORIGINAL feature, looked up through the
        # position->member map (cost_effective_gradient_boosting.hpp)
        shift = shift + jnp.where(has_member,
                                  gain_penalty[member_ix], 0.0)
    stacks = [g1 - shift, g2 - shift]

    if feat_is_cat is not None:
        # member num_bins at each position (nb = end - pos + tloc + 1
        # holds for both layouts: direct tloc == pos, end == nb - 1;
        # multi pos == off + tloc - 1, end == off + nb - 2)
        end_pos = end_at - (jnp.arange(G) * B)[:, None]
        nb_at = end_pos - jnp.arange(B)[None, :] + tloc_at + 1
        use_oh = nb_at <= p.max_cat_to_onehot
        # one-hot family: tail category = the position's own mass;
        # the default category (t=0) = the member's reconstructed
        # bin-0 mass (for direct columns bin 0 is stored, h3 works)
        left_oh = jnp.where(
            ((tloc_at == 0) & ~direct_pos)[:, :, None],
            total[None, None, :] - (e - cum), h3)
        # cat candidates take the CAT bounds (scalar fallbacks in
        # advanced mode), like the plain _cat_split_eval path
        g_oh = eval_left(left_oh, is_cat_pos & use_oh,
                         bounds_c, bounds_c)
        # sorted-subset family for direct wide-cat columns: their rows
        # of the bundle histogram ARE the feature histograms, so the
        # plain machinery runs verbatim
        direct_member = member_ix[:, 0]
        col_cat = is_direct_f[direct_member] \
            & feat_is_cat[direct_member] & (member_at[:, 0] >= 0)
        if col_mask is not None:
            col_cat = col_cat & col_mask
        col_nb = jnp.where(
            col_cat,
            feat_num_bins[direct_member] if feat_num_bins is not None
            else 0, 0)
        _, g_fwd, g_bwd, csum_f, csum_b, (inv, used, participate) = \
            _cat_split_eval(h3, total[0], total[1], total[2],
                            col_nb, p, p_out, bounds_c)
        cmask2 = (col_cat & feature_mask[direct_member])[:, None]
        g_fwd = jnp.where(cmask2, g_fwd, K_MIN_SCORE)
        g_bwd = jnp.where(cmask2, g_bwd, K_MIN_SCORE)
        stacks += [g_oh - shift, g_fwd - shift, g_bwd - shift]

    net = jnp.stack(stacks)                       # [D, G, B]
    if mono_pos is not None and p.monotone_penalty > 0.0:
        # the penalty rescales constrained features' NET gains before
        # the argmax (ComputeBestSplitForFeature ordering)
        depth_ = jnp.asarray(0, jnp.int32) if leaf_depth is None \
            else leaf_depth
        mult = monotone_penalty_mult(depth_, p).astype(dtype)
        net = jnp.where(mono_pos[None], net * mult, net)
    net = jnp.where(jnp.isfinite(net), net, K_MIN_SCORE)

    flat = jnp.argmax(net)
    d = flat // (G * B)
    g = (flat // B) % G
    pos = flat % B
    best = net.reshape(-1)[flat]
    if feat_is_cat is not None:
        sel = jnp.stack([left1[g, pos], left2[g, pos], left_oh[g, pos],
                         csum_f[g, pos], csum_b[g, pos]])[d]
        is_cat_win = d >= 2
        is_sorted_cat = d >= 3
        bpos = jnp.arange(B)
        oh_mask = bpos == tloc_at[g, pos]
        fwd_mask = participate[g] & (inv[g] <= pos)
        bwd_mask = participate[g] & (inv[g] >= used[g] - 1 - pos)
        cat_mask = jnp.where(
            is_cat_win,
            jnp.where(d == 2, oh_mask,
                      jnp.where(d == 3, fwd_mask, bwd_mask)),
            jnp.zeros((B,), jnp.bool_))
    else:
        sel = jnp.where(d == 0, left1[g, pos], left2[g, pos])
        is_cat_win = jnp.asarray(False)
        is_sorted_cat = jnp.asarray(False)
        cat_mask = jnp.zeros((B,), jnp.bool_)
    lgs, lhs, lcs = sel[0], sel[1], sel[2]
    rgs, rhs, rcs = total[0] - lgs, total[1] - lhs, total[2] - lcs
    if adv:
        # the winner's bounds: the gathered value at (g, pos) for a
        # numeric winner, the scalar cat fallbacks otherwise
        b_lw = (jnp.where(is_cat_win, bounds[4], bounds_l[0][g, pos]),
                jnp.where(is_cat_win, bounds[5], bounds_l[1][g, pos]))
        b_rw = (jnp.where(is_cat_win, bounds[4], bounds_r[0][g, pos]),
                jnp.where(is_cat_win, bounds[5], bounds_r[1][g, pos]))
    else:
        b_lw, b_rw = bounds_l, bounds_r
    lo, ro = _winner_outputs(lgs, lhs, lcs, rgs, rhs, rcs,
                             is_sorted_cat, exact, p, p_out,
                             b_lw, b_rw)
    result = SplitResult(
        gain=jnp.where(jnp.isfinite(best), best, K_MIN_SCORE)
        .astype(dtype),
        feature=member_at[g, pos].astype(jnp.int32),
        threshold_bin=tloc_at[g, pos].astype(jnp.int32),
        default_left=(d == 1),
        is_cat=is_cat_win,
        cat_mask=cat_mask,
        left_sum_g=lgs, left_sum_h=lhs, left_count=lcs,
        right_sum_g=rgs, right_sum_h=rhs, right_count=rcs,
        left_output=lo,
        right_output=ro)
    if return_col_gains:
        # best net gain per bundle COLUMN — the voting-parallel local
        # ballot in bundle space (VotingParallelTreeLearner top-k)
        return result, jnp.max(net, axis=(0, 2))
    return result
