"""Leaf-wise tree growth as one jitted XLA program.

Re-design of SerialTreeLearner::Train
(/root/reference/src/treelearner/serial_tree_learner.cpp:179-245) and the
device-resident CUDA learner
(src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp) for TPU:

- The growth loop runs ``num_leaves - 1`` *static* split steps inside a
  ``lax.fori_loop`` (XLA needs static trip counts); a step whose best gain
  is <= 0 is a no-op, and since nothing changes afterwards all remaining
  steps stay no-ops — equivalent to the reference's early ``break``
  (serial_tree_learner.cpp:225).
- Rows are never compacted per leaf: a ``row_leaf`` vector (the
  DataPartition analog, data_partition.hpp) assigns each row to a leaf
  slot, and leaf histograms are built by masking the per-row payload.
- Leaf slots follow the reference Tree convention (tree.h: ``Split``):
  the left child keeps the parent's leaf slot, the right child takes slot
  ``num_leaves_so_far``; internal node k is created by split k; child
  pointers store ``~leaf`` for leaves.
- Histogram subtraction: only the smaller child is scatter-accumulated,
  the sibling = parent - smaller (serial_tree_learner.cpp:473-520).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel import comms
from .histogram import (build_histogram, hist_from_rows,
                        hist_from_rows_int, subtract_histogram)
from .predict import predict_leaf_binned
from .split import (SplitParams, SplitResult, constrained_output,
                    find_best_split, find_best_split_bundled,
                    gain_at_output, leaf_gain, leaf_output)

from ..obs.scopes import scope, scoped

__all__ = ["GrowConfig", "TreeArrays", "grow_tree"]


NEG_INF = -jnp.inf


def _sums_psum(x, axis_name):
    """Every reduction that is not a histogram's (those are
    parallel/comms.py's, scope ``grow/hist/allreduce``): root and leaf
    sums, row counts, votes, one histogram row broadcast from its
    owner. Small, so a trace shows their latency, not their bytes."""
    with scope("grow/sums/allreduce"):
        return lax.psum(x, axis_name)


@scoped("grow/sums/allreduce")
def _left_is_smaller(n_left, cnt, est_left_small, rows_sharded: bool):
    """Which child of a window the compact grower builds a histogram for
    (``part_apply``): the one the partition counted fewer rows in, or,
    where this device holds a shard of the rows and the count would be a
    collective, the split search's estimate."""
    return est_left_small if rows_sharded else n_left <= cnt - n_left


def _combine_split_infos(r: SplitResult, axis_name) -> SplitResult:
    """SyncUpGlobalBestSplit (parallel_tree_learner.h:209-232):
    allreduce the max-gain SplitInfo across devices searching disjoint
    feature subsets; ties resolve to the lower feature id (SplitInfo
    total order, split_info.hpp). Shared by the feature-parallel mode
    and the sharded data-parallel split search — with disjoint
    ownership exactly one device wins, so the psum-broadcast of each
    field is the winner's exact bit pattern."""
    gmax = lax.pmax(r.gain, axis_name)
    at_max = r.gain >= gmax
    packed = jnp.where(at_max, r.feature, jnp.int32(2 ** 30))
    fwin = lax.pmin(packed, axis_name)
    win = at_max & (r.feature == fwin)
    cnt = lax.psum(win.astype(jnp.float32), axis_name)

    def bc(x):
        xf = x.astype(jnp.float32)
        mean = lax.psum(jnp.where(win, xf, 0.0), axis_name) / cnt
        if x.dtype == jnp.bool_:
            return mean > 0.5
        if jnp.issubdtype(x.dtype, jnp.integer):
            return jnp.round(mean).astype(x.dtype)
        return mean.astype(x.dtype)

    return SplitResult(*(bc(field) for field in r))


class GrowConfig(NamedTuple):
    """Static (trace-time) growth configuration.

    ``axis_name``: when set, the grower runs inside shard_map/pjit with
    rows sharded over that mesh axis; histograms and root sums are
    psum-reduced — the TPU analog of the reference's data-parallel
    ReduceScatter+Allreduce (data_parallel_tree_learner.cpp:284-294,
    SURVEY.md §2.6). Split finding then happens identically on every
    device (deterministic), replacing SyncUpGlobalBestSplit.

    ``grower``: "compact" keeps rows grouped by leaf (DataPartition
    analog) so per-split work is proportional to the leaf size;
    "masked" builds every histogram with a full-row masked pass.
    """
    num_leaves: int
    num_bins: int
    max_depth: int = -1
    split: SplitParams = SplitParams()
    hist_method: str = "scatter"
    hist_precision: str = "default"  # mxu matmul passes: default|high|highest
    # rows per streaming chunk (compact grower): the ONE size every
    # window loop steps by (partition, child histogram, pool-miss
    # recompute). Both benchmark cells run the default, 16,384.
    chunk: int = 16384
    axis_name: Optional[str] = None
    grower: str = "compact"
    # quantized-gradient training (use_quantized_grad; the reference's
    # GradientDiscretizer, gradient_discretizer.hpp): g/h discretized to
    # int8, histograms accumulate in exact int32 on the int MXU.
    quantized: bool = False
    quant_bins: int = 4          # num_grad_quant_bins
    renew_leaf: bool = False     # quant_train_renew_leaf
    stochastic: bool = True      # stochastic_rounding
    # CEGB (cost_effective_gradient_boosting.hpp): gain penalties for
    # splits / first feature use / per-row feature acquisition
    cegb: bool = False
    cegb_lazy: bool = False
    cegb_coupled: bool = False   # any cegb_penalty_feature_coupled > 0
    cegb_tradeoff: float = 1.0
    cegb_split: float = 0.0
    # monotone constraint strategy (LeafConstraintsBase::Create,
    # monotone_constraints.hpp:1176): "basic" tracks per-leaf output
    # bounds set to the split midpoint; "intermediate" uses the sibling
    # subtree's extreme CURRENT outputs, refreshed (and every leaf's
    # best split re-searched) after each split — the batch fixed-point
    # of the reference's leaves_to_update propagation
    # (IntermediateLeafConstraints::Update), without the per-threshold
    # range refinement.
    monotone_method: str = "basic"
    # feature_fraction_bynode (ColSampler::GetByNode, col_sampler.hpp):
    # a fresh feature subset sampled per node from the per-tree set
    bynode: float = 1.0
    # distributed strategy under ``axis_name`` (SURVEY §2.6):
    # "data"    — rows sharded; histograms psum-reduced
    #             (DataParallelTreeLearner)
    # "feature" — rows replicated; devices search disjoint feature
    #             subsets and the winning SplitInfo is allreduced
    #             (FeatureParallelTreeLearner; on TPU the fused MXU
    #             histogram still covers all features — the sharing is
    #             in the split search, see best_for)
    # "voting"  — rows sharded; each device proposes its local top-k
    #             features, a global vote elects 2k, and only elected
    #             features' histograms are globally reduced
    #             (VotingParallelTreeLearner / PV-Tree)
    parallel_mode: str = "data"
    voting_top_k: int = 20
    # Exclusive Feature Bundling (ops/bundling.py): bins_T holds bundle
    # columns and the split search runs in bundle-position space
    bundled: bool = False
    # histogram cache budget (HistogramPool, the reference's
    # histogram_pool_size: src/treelearner/serial_tree_learner.cpp
    # GetShareStates + feature_histogram.hpp HistogramPool): 0 keeps
    # the full [L, F, B, 2] per-leaf cache HBM-resident; a positive
    # value caps the cache at that many leaf slots — evicted leaves'
    # histograms are recomputed from their (physically contiguous)
    # row window on demand, including inside the stored-candidate
    # re-search paths (CEGB / intermediate monotone / forced splits),
    # which walk leaves serving each hist from slot or recompute.
    hist_pool_slots: int = 0
    # carry per-row ids + in-bag bits (ord2) through the partition.
    # Only needed when something consumes them: exact in-bag child
    # counts under bagging/GOSS (weight-0 rows), CEGB's lazy per-row
    # feature sets, or the bundled final merge. Plain full-data
    # training (the benchmark path) drops the column: one less sort
    # operand in every chunk body and no in-bag bookkeeping.
    track_rows: bool = True
    # histogram allreduce wire format under data-parallel sharding
    # (parallel/comms.py, EQuARX-style block quantization):
    # "f32" exact psum | "int16"/"int8" blockwise-quantized exchange
    # with an error-feedback residual threaded through the growth
    # loop carry. Scalar/count psums stay f32; quantized-gradient
    # training (cfg.quantized: exact int32 histograms) and the
    # feature-parallel mode (no histogram reduction) ignore it.
    hist_comm: str = "f32"
    # data-parallel split search (parallel/comms.py, docs/SHARDING.md):
    # "gathered" — the reduced [F, B, 2] histogram is allreduced and
    #              every device searches all features (the legacy psum
    #              path; XLA's ring allreduce broadcasts the full
    #              payload back to every device);
    # "sharded"  — the reference DataParallelTreeLearner's
    #              ReduceScatter + per-worker feature-subset search
    #              (data_parallel_tree_learner.cpp:223-300): histograms
    #              are reduce-scattered so each device owns and
    #              searches only its ceil(F/D) feature chunk, then the
    #              per-device best SplitInfo records are allreduced
    #              (SyncUpGlobalBestSplit). Post-reduction traffic
    #              drops from the full histogram broadcast to a 1/D
    #              chunk + O(D) split records; split decisions are
    #              byte-identical to the gathered path (psum_scatter
    #              chunks are bit-identical to psum slices; the
    #              SplitInfo combine broadcasts the single winner's
    #              exact field bits).
    # Only meaningful under axis_name + parallel_mode="data"; feature/
    # voting parallelism have their own search sharding already.
    split_search: str = "gathered"


class TreeArrays(NamedTuple):
    """Flat-tensor tree (the Tree class re-imagined as arrays;
    include/LightGBM/tree.h:63-252). Sizes: L leaves, L-1 internal nodes."""
    split_feature: jnp.ndarray   # [L-1] i32
    threshold_bin: jnp.ndarray   # [L-1] i32
    default_left: jnp.ndarray    # [L-1] bool
    left_child: jnp.ndarray      # [L-1] i32 (~leaf for leaves)
    right_child: jnp.ndarray     # [L-1] i32
    split_gain: jnp.ndarray      # [L-1] f32
    internal_value: jnp.ndarray  # [L-1] f32
    internal_weight: jnp.ndarray  # [L-1] f32
    internal_count: jnp.ndarray  # [L-1] i32 (exact: see _count)
    leaf_value: jnp.ndarray      # [L] f32
    leaf_weight: jnp.ndarray     # [L] f32 (sum of hessians)
    leaf_count: jnp.ndarray      # [L] i32 (in-bag rows, exact)
    leaf_parent: jnp.ndarray     # [L] i32
    leaf_depth: jnp.ndarray      # [L] i32
    num_leaves: jnp.ndarray      # scalar i32 (actual leaves grown)
    split_is_cat: jnp.ndarray    # [L-1] bool — categorical membership split
    split_cat_mask: jnp.ndarray  # [L-1, B] bool — bins routed left


class _BestSplits(NamedTuple):
    """Per-leaf-slot best candidate split (the SplitInfo-per-leaf arrays)."""
    gain: jnp.ndarray
    feature: jnp.ndarray
    threshold_bin: jnp.ndarray
    default_left: jnp.ndarray
    is_cat: jnp.ndarray        # [L] bool
    cat_mask: jnp.ndarray      # [L, B] bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray

    @staticmethod
    def init(L: int, B: int, dtype) -> "_BestSplits":
        zf = jnp.zeros((L,), dtype=dtype)
        return _BestSplits(
            gain=jnp.full((L,), NEG_INF, dtype=dtype),
            feature=jnp.zeros((L,), jnp.int32),
            threshold_bin=jnp.zeros((L,), jnp.int32),
            default_left=jnp.zeros((L,), jnp.bool_),
            is_cat=jnp.zeros((L,), jnp.bool_),
            cat_mask=jnp.zeros((L, B), jnp.bool_),
            left_sum_g=zf, left_sum_h=zf, left_count=zf,
            right_sum_g=zf, right_sum_h=zf, right_count=zf,
            left_output=zf, right_output=zf,
        )

    def store(self, i, r: SplitResult, allowed) -> "_BestSplits":
        gain = jnp.where(allowed, r.gain, NEG_INF)
        return _BestSplits(
            gain=self.gain.at[i].set(gain),
            feature=self.feature.at[i].set(r.feature),
            threshold_bin=self.threshold_bin.at[i].set(r.threshold_bin),
            default_left=self.default_left.at[i].set(r.default_left),
            is_cat=self.is_cat.at[i].set(r.is_cat),
            cat_mask=self.cat_mask.at[i].set(r.cat_mask),
            left_sum_g=self.left_sum_g.at[i].set(r.left_sum_g),
            left_sum_h=self.left_sum_h.at[i].set(r.left_sum_h),
            left_count=self.left_count.at[i].set(r.left_count),
            right_sum_g=self.right_sum_g.at[i].set(r.right_sum_g),
            right_sum_h=self.right_sum_h.at[i].set(r.right_sum_h),
            right_count=self.right_count.at[i].set(r.right_count),
            left_output=self.left_output.at[i].set(r.left_output),
            right_output=self.right_output.at[i].set(r.right_output),
        )


class _GrowState(NamedTuple):
    tree: TreeArrays
    best: _BestSplits
    hists: jnp.ndarray      # [L, F, B, 2]
    row_leaf: jnp.ndarray   # [n] i32
    num_splits: jnp.ndarray  # scalar i32
    comm_ef: jnp.ndarray = ()  # quantized-allreduce error feedback
                               # (hist_comm int8/int16; comms.py)


def _count(x):
    """A row count as the tree keeps it: int32. The growers reckon with
    counts in float32 (the split search's estimates, the minimum-rows
    checks), which holds a count exactly only up to 2**24 = 16,777,216
    rows; a mesh's ranks together hold more (26,562,500 on one v5e host
    of the Criteo job, where a root's larger child read one row off).
    So the counts the tree RECORDS come from the integer sums and stay
    integers to the model file; float estimates round in."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x.astype(jnp.int32)
    return jnp.round(x).astype(jnp.int32)


def _init_tree(L: int, B: int, dtype) -> TreeArrays:
    return TreeArrays(
        split_is_cat=jnp.zeros((L - 1,), jnp.bool_),
        split_cat_mask=jnp.zeros((L - 1, B), jnp.bool_),
        split_feature=jnp.zeros((L - 1,), jnp.int32),
        threshold_bin=jnp.zeros((L - 1,), jnp.int32),
        default_left=jnp.zeros((L - 1,), jnp.bool_),
        left_child=jnp.zeros((L - 1,), jnp.int32),
        right_child=jnp.zeros((L - 1,), jnp.int32),
        split_gain=jnp.zeros((L - 1,), dtype),
        internal_value=jnp.zeros((L - 1,), dtype),
        internal_weight=jnp.zeros((L - 1,), dtype),
        internal_count=jnp.zeros((L - 1,), jnp.int32),
        leaf_value=jnp.zeros((L,), dtype),
        leaf_weight=jnp.zeros((L,), dtype),
        leaf_count=jnp.zeros((L,), jnp.int32),
        leaf_parent=jnp.full((L,), -1, jnp.int32),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32),
    )


def _apply_split_to_tree(tree: TreeArrays, best: _BestSplits, leaf, R, ns,
                         p: SplitParams, left_cnt=None,
                         right_cnt=None) -> TreeArrays:
    """Record split ``ns`` of leaf slot ``leaf`` (Tree::Split, tree.h:63).

    The left child keeps the parent's leaf slot; the right child takes
    slot ``R``; internal node ``ns`` is created by this split.
    ``left_cnt``/``right_cnt`` are the exact partition counts when the
    caller has them (SplitInner overwrites the search-time estimates the
    same way, serial_tree_learner.cpp:789-791); the stored candidate
    counts are hessian-ratio estimates otherwise."""
    f = best.feature[leaf]
    t = best.threshold_bin[leaf]
    dl = best.default_left[leaf]
    cm = best.cat_mask[leaf]
    parent = tree.leaf_parent[leaf]
    pidx = jnp.maximum(parent, 0)
    lc = tree.left_child
    rc = tree.right_child
    lc = lc.at[pidx].set(jnp.where((parent >= 0) & (lc[pidx] == ~leaf),
                                   ns, lc[pidx]))
    rc = rc.at[pidx].set(jnp.where((parent >= 0) & (rc[pidx] == ~leaf),
                                   ns, rc[pidx]))
    lc = lc.at[ns].set(~leaf)
    rc = rc.at[ns].set(~R)
    lcnt = _count(best.left_count[leaf] if left_cnt is None else left_cnt)
    rcnt = _count(best.right_count[leaf] if right_cnt is None
                  else right_cnt)
    parent_g = best.left_sum_g[leaf] + best.right_sum_g[leaf]
    parent_h = best.left_sum_h[leaf] + best.right_sum_h[leaf]
    parent_c = lcnt + rcnt
    new_depth = tree.leaf_depth[leaf] + 1
    return tree._replace(
        split_feature=tree.split_feature.at[ns].set(f),
        threshold_bin=tree.threshold_bin.at[ns].set(t),
        default_left=tree.default_left.at[ns].set(dl),
        split_is_cat=tree.split_is_cat.at[ns].set(best.is_cat[leaf]),
        split_cat_mask=tree.split_cat_mask.at[ns].set(cm),
        left_child=lc,
        right_child=rc,
        split_gain=tree.split_gain.at[ns].set(best.gain[leaf]),
        internal_value=tree.internal_value.at[ns].set(
            leaf_output(parent_g, parent_h, p)),
        internal_weight=tree.internal_weight.at[ns].set(parent_h),
        internal_count=tree.internal_count.at[ns].set(parent_c),
        leaf_value=tree.leaf_value.at[leaf].set(best.left_output[leaf])
        .at[R].set(best.right_output[leaf]),
        leaf_weight=tree.leaf_weight.at[leaf].set(best.left_sum_h[leaf])
        .at[R].set(best.right_sum_h[leaf]),
        leaf_count=tree.leaf_count.at[leaf].set(lcnt).at[R].set(rcnt),
        leaf_parent=tree.leaf_parent.at[leaf].set(ns).at[R].set(ns),
        leaf_depth=tree.leaf_depth.at[leaf].set(new_depth)
        .at[R].set(new_depth),
        num_leaves=tree.num_leaves + 1,
    )


def grow_tree_impl(cfg: GrowConfig,
                   bins_T: jnp.ndarray,
                   grad: jnp.ndarray,
                   hess: jnp.ndarray,
                   row_weight: jnp.ndarray,
                   feature_mask: jnp.ndarray,
                   feat_num_bins: jnp.ndarray,
                   feat_nan_bin: jnp.ndarray,
                   monotone_constraints: Optional[jnp.ndarray] = None,
                   feat_is_cat: Optional[jnp.ndarray] = None,
                   quant_key: Optional[jnp.ndarray] = None,
                   interaction_groups: Optional[jnp.ndarray] = None,
                   forced: Optional[tuple] = None,
                   cegb_arrays: Optional[tuple] = None,
                   node_key: Optional[jnp.ndarray] = None,
                   bundle_arrays: Optional[tuple] = None):
    """Grow one leaf-wise tree. Returns (TreeArrays, row_leaf)
    (+ (coupled_used, lazy_used) when cfg.cegb).

    Args:
      bins_T: [F, n] uint8/uint16 bin matrix.
      grad/hess: [n] float.
      row_weight: [n] float sampling weight (bagging/GOSS; 1.0 = use row).
      feature_mask: [F] bool usable-feature mask (feature_fraction etc).
      feat_num_bins / feat_nan_bin: [F] i32 per-feature bin metadata.
      quant_key: PRNG key for stochastic gradient rounding (quantized
        mode only).
      interaction_groups: optional [G, F] bool — allowed feature groups
        (interaction_constraints); compact grower only.
      forced: optional (leaf [M], feature [M], bin [M]) i32 arrays — the
        pre-planned forced splits (forcedsplits_filename, BFS order);
        compact grower only.
      node_key: PRNG key for per-node column sampling
        (feature_fraction_bynode; cfg.bynode < 1).
    """
    if cfg.split_search == "sharded" and cfg.bundled:
        raise NotImplementedError(
            "split_search='sharded' does not cover EFB bundling yet — "
            "the engine keeps bundled runs on the gathered search "
            "(models/gbdt.py)")
    if cfg.grower == "compact":
        return _grow_compact_impl(cfg, bins_T, grad, hess, row_weight,
                                  feature_mask, feat_num_bins, feat_nan_bin,
                                  monotone_constraints, feat_is_cat,
                                  quant_key, interaction_groups, forced,
                                  cegb_arrays, node_key, bundle_arrays)
    if cfg.grower == "level":
        if cfg.bundled or interaction_groups is not None \
                or forced is not None or cegb_arrays is not None \
                or cfg.quantized or cfg.bynode < 1.0 \
                or cfg.split.path_smooth > 0.0 \
                or cfg.hist_pool_slots > 0 \
                or (cfg.axis_name is not None
                    and cfg.parallel_mode != "data"):
            raise NotImplementedError(
                "grower='level' covers the core feature set only (no "
                "EFB/interaction/forced/CEGB/quantized/bynode/"
                "path-smooth/histogram-pool; data-parallel sharding "
                "only) — use grower='compact'")
        return _grow_level_impl(cfg, bins_T, grad, hess, row_weight,
                                feature_mask, feat_num_bins,
                                feat_nan_bin, monotone_constraints,
                                feat_is_cat)
    if cfg.bundled:
        raise NotImplementedError(
            "EFB bundling requires the compact grower")
    if interaction_groups is not None or forced is not None \
            or cegb_arrays is not None:
        raise NotImplementedError(
            "interaction_constraints/forced splits/CEGB require the "
            "compact grower")
    if cfg.bynode < 1.0 or cfg.split.path_smooth > 0.0:
        # path smoothing and per-node column sampling live on the
        # flagship compact grower only (gbdt.py routes those configs
        # there); the masked grower keeps monotone as a validity check
        # without output-bound entries (legacy behavior).
        raise NotImplementedError(
            "path_smooth/feature_fraction_bynode require the compact "
            "grower")
    return _grow_masked_impl(cfg, bins_T, grad, hess, row_weight,
                             feature_mask, feat_num_bins, feat_nan_bin,
                             monotone_constraints, feat_is_cat)


def _make_sharded_search(cfg: GrowConfig, F: int, qm: str,
                         use_ef: bool):
    """Reduce-scatter sharded-search context shared by every grower
    (docs/SHARDING.md): each device owns ``Fl = ceil(F/D)`` features
    of the reduced histogram (feature axis padded to ``Fsp = D * Fl``
    so psum_scatter chunks align), searches only its chunk, and the
    winning SplitInfo is allreduced — the reference
    DataParallelTreeLearner shape. Returns ``(Fl, Fsp, f_start,
    dev_idx, rs_pad, hist_psum_ef, owned_slice)``; the feature axis is
    third-from-last in every histogram shape the growers reduce
    ([F, B, 2] root / [L, F, B, 2] level batch), so the scatter axis
    is positional. Must be called inside the traced program (it takes
    ``lax.axis_index``)."""
    D_sh = lax.axis_size(cfg.axis_name)
    dev_idx = lax.axis_index(cfg.axis_name)
    Fl = -(-F // D_sh)
    Fsp = Fl * D_sh
    f_start = dev_idx * Fl

    def rs_pad(x):
        """Pad the feature axis (third-from-last) to Fsp."""
        if Fsp == F:
            return x
        pw = [(0, 0)] * x.ndim
        pw[x.ndim - 3] = (0, Fsp - F)
        return jnp.pad(x, pw)

    def hist_psum_ef(x, ef):
        x = rs_pad(x)
        # ``ef`` is () at the exact f32 wire: passed through untouched
        return comms.hist_reduce_scatter(x, cfg.axis_name, qm, ef,
                                         x.ndim - 3)

    def owned_slice(v, fill):
        """This device's Fl-slice of a per-feature vector."""
        if v is None:
            return None
        if Fsp > F:
            padv = jnp.full((Fsp - F,), fill, v.dtype)
            v = jnp.concatenate([v, padv])
        return lax.dynamic_slice(v, (f_start,), (Fl,))

    return Fl, Fsp, f_start, dev_idx, rs_pad, hist_psum_ef, owned_slice


def _grow_masked_impl(cfg: GrowConfig,
                      bins_T: jnp.ndarray,
                      grad: jnp.ndarray,
                      hess: jnp.ndarray,
                      row_weight: jnp.ndarray,
                      feature_mask: jnp.ndarray,
                      feat_num_bins: jnp.ndarray,
                      feat_nan_bin: jnp.ndarray,
                      monotone_constraints: Optional[jnp.ndarray] = None,
                      feat_is_cat: Optional[jnp.ndarray] = None):
    """Masked-pass grower: every histogram is a full-row masked pass."""
    L = cfg.num_leaves
    B = cfg.num_bins
    F = bins_T.shape[0]
    n = bins_T.shape[1]
    dtype = grad.dtype
    p = cfg.split
    sharded = (cfg.axis_name is not None and cfg.parallel_mode == "data"
               and cfg.split_search == "sharded")

    def psum(x):
        return _sums_psum(x, cfg.axis_name) if cfg.axis_name else x

    qm, use_ef, _gath_ef = comms.make_hist_psum_ef(
        cfg.axis_name, cfg.hist_comm)

    if sharded:
        Fl, Fsp, f_start, dev_idx, _rs_pad, hist_psum_ef, _ssl = \
            _make_sharded_search(cfg, F, qm, use_ef)
        FH = Fl

        def best_for(hist, sg, sh, sc):
            owned = (f_start + jnp.arange(Fl)) < F
            r = find_best_split(hist, sg, sh, sc,
                                _ssl(feat_num_bins, 1),
                                _ssl(feat_nan_bin, -1),
                                _ssl(feature_mask, False) & owned, p,
                                _ssl(monotone_constraints, 0),
                                _ssl(feat_is_cat, False))
            r = r._replace(feature=r.feature + f_start)
            return _combine_split_infos(r, cfg.axis_name)
    else:
        FH = F
        hist_psum_ef = _gath_ef

        def best_for(hist, sg, sh, sc):
            return find_best_split(hist, sg, sh, sc, feat_num_bins,
                                   feat_nan_bin, feature_mask, p,
                                   monotone_constraints, feat_is_cat)

    # ---- root (GlobalSyncUpBySum analog for the root tuple) ----
    w = row_weight.astype(dtype)
    inbag = row_weight > 0
    total_g = psum(jnp.sum(grad * w))
    total_h = psum(jnp.sum(hess * w))
    total_ci = psum(jnp.sum(inbag, dtype=jnp.int32))    # exact
    total_c = total_ci.astype(dtype)
    all_rows = jnp.ones((n,), jnp.bool_)
    comm_ef0 = jnp.zeros((Fsp if sharded else F, B, 2), dtype) \
        if use_ef else ()
    with comms.reduction_site("tree"):
        root_hist, comm_ef0 = hist_psum_ef(
            build_histogram(bins_T, grad, hess, row_weight, all_rows, B,
                            cfg.hist_method, cfg.hist_precision),
            comm_ef0)

    tree = _init_tree(L, B, dtype)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(leaf_output(total_g, total_h, p)),
        leaf_weight=tree.leaf_weight.at[0].set(total_h),
        leaf_count=tree.leaf_count.at[0].set(total_ci),
    )
    best = _BestSplits.init(L, B, dtype)
    best = best.store(0, best_for(root_hist, total_g, total_h, total_c),
                      jnp.asarray(True))
    hists = jnp.zeros((L, FH, B, 2), dtype).at[0].set(root_hist)
    state = _GrowState(tree=tree, best=best, hists=hists,
                       row_leaf=jnp.zeros((n,), jnp.int32),
                       num_splits=jnp.asarray(0, jnp.int32),
                       comm_ef=comm_ef0)

    def depth_ok(d):
        if cfg.max_depth <= 0:
            return jnp.asarray(True)
        return d < cfg.max_depth

    def do_split(state: _GrowState) -> _GrowState:
        tree, best, hists, row_leaf, ns, comm_ef = state
        leaf = jnp.argmax(best.gain).astype(jnp.int32)
        R = ns + 1  # new (right-child) leaf slot
        f = best.feature[leaf]
        t = best.threshold_bin[leaf]
        dl = best.default_left[leaf]

        # -- partition rows of `leaf` (DataPartition::Split analog) --
        col = lax.dynamic_index_in_dim(bins_T, f, axis=0,
                                       keepdims=False).astype(jnp.int32)
        nan_bin = feat_nan_bin[f]
        go_left_num = jnp.where((nan_bin >= 0) & (col == nan_bin), dl,
                                col <= t)
        cm = best.cat_mask[leaf]
        go_left = jnp.where(best.is_cat[leaf], cm[col], go_left_num)
        on_leaf = row_leaf == leaf
        # exact partition counts replace the search-time hessian-ratio
        # estimates (SplitInner update_cnt, serial_tree_learner.cpp:789)
        nl_i = psum(jnp.sum(on_leaf & go_left & inbag, dtype=jnp.int32))
        nr_i = tree.leaf_count[leaf] - nl_i
        nl_ex, nr_ex = nl_i.astype(dtype), nr_i.astype(dtype)
        row_leaf = jnp.where(on_leaf & ~go_left, R, row_leaf)

        # -- tree arrays update (Tree::Split, tree.h:63) --
        new_depth = tree.leaf_depth[leaf] + 1
        tree = _apply_split_to_tree(tree, best, leaf, R, ns, p,
                                    nl_i, nr_i)

        # -- histograms: scatter the smaller child, subtract for sibling --
        left_smaller = nl_ex <= nr_ex
        small_slot = jnp.where(left_smaller, leaf, R)
        small_mask = row_leaf == small_slot
        small_hist, comm_ef = hist_psum_ef(
            build_histogram(bins_T, grad, hess, row_weight, small_mask,
                            B, cfg.hist_method, cfg.hist_precision),
            comm_ef)
        parent_hist = hists[leaf]
        big_hist = subtract_histogram(parent_hist, small_hist)
        left_hist = jnp.where(left_smaller, small_hist, big_hist)
        right_hist = jnp.where(left_smaller, big_hist, small_hist)
        hists = hists.at[leaf].set(left_hist).at[R].set(right_hist)

        # -- child best splits --
        can_go_deeper = depth_ok(new_depth)
        rl = best_for(left_hist, best.left_sum_g[leaf],
                      best.left_sum_h[leaf], nl_ex)
        rr = best_for(right_hist, best.right_sum_g[leaf],
                      best.right_sum_h[leaf], nr_ex)
        best = best.store(leaf, rl, can_go_deeper)
        best = best.store(R, rr, can_go_deeper)

        return _GrowState(tree=tree, best=best, hists=hists,
                          row_leaf=row_leaf, num_splits=ns + 1,
                          comm_ef=comm_ef)

    def step(_, state: _GrowState) -> _GrowState:
        can = jnp.max(state.best.gain) > 0.0
        # tpulint: replicated-cond best.gain comes from psum-reduced histograms, so `can` is bit-identical on every device
        return lax.cond(can, do_split, lambda s: s, state)

    state = lax.fori_loop(0, L - 1, step, state)
    return state.tree, state.row_leaf


# ---------------------------------------------------------------------------
# Level grower: depth-wise growth, one fused step per frontier level
# ---------------------------------------------------------------------------

class _LevelState(NamedTuple):
    tree: TreeArrays
    best: _BestSplits
    hists: jnp.ndarray       # [L, F, B, 2]
    row_leaf: jnp.ndarray    # [n] i32
    num_splits: jnp.ndarray  # scalar i32
    level: jnp.ndarray       # scalar i32 — depth of the current frontier
    comm_ef: jnp.ndarray = ()  # error-feedback residual of the
                               # quantized histogram allreduce
                               # (hist_comm int8/int16): the scatter
                               # path reduces the whole [L, F, B, 2]
                               # level batch in one call, so its EF
                               # matches that shape; the kernel paths
                               # reduce one [F, B, 2] child at a time
                               # and carry a rolling [F, B, 2] buffer
                               # (the telescope bounds accumulated
                               # error regardless of leaf attribution
                               # — see _CompactState.comm_ef — at 1/L
                               # the HBM of a per-leaf buffer)


def _grow_level_impl(cfg: GrowConfig,
                     bins_T: jnp.ndarray,
                     grad: jnp.ndarray,
                     hess: jnp.ndarray,
                     row_weight: jnp.ndarray,
                     feature_mask: jnp.ndarray,
                     feat_num_bins: jnp.ndarray,
                     feat_nan_bin: jnp.ndarray,
                     monotone_constraints: Optional[jnp.ndarray] = None,
                     feat_is_cat: Optional[jnp.ndarray] = None):
    """Depth-wise (level-order) growth with the whole frontier fused
    into ONE loop iteration per level — the GPU tree-boosting pipeline
    shape (arXiv:1706.08359 §4, arXiv:2011.02022 "Booster") on the
    masked-state layout.

    Where the leaf-wise growers alternate argmax -> split -> re-score
    once per SPLIT (each hop round-tripping an ``[F, B, 2]`` histogram
    and an ``[n]`` leaf mask through HBM between separately-fused op
    islands), one level step here:

    1. elects every frontier leaf whose stored best gain is positive
       (gain-ranked when the remaining ``num_leaves`` budget can't take
       the whole frontier — the depth-wise analog of leaf-wise's
       global argmax),
    2. partitions the rows of ALL elected leaves,
    3. builds the level's child histograms in one batched pass over
       the rows of the (estimated-smaller) children only — one
       leaf-segmented scatter pass for ``hist_method="scatter"``, one
       masked kernel pass per small child for the MXU/Pallas methods —
       with every sibling recovered by subtraction, and
    4. scores best splits for the whole new frontier in ONE vmapped
       ``find_best_split`` batch over the ``[L, F, B, 2]`` cache.

    The whole tree is a single traced program (a ``lax.while_loop``
    with one iteration per level), so histogram -> best-split ->
    partition never crosses a dispatch boundary. With
    ``hist_method="scatter"`` the leaf-segmented pass makes total
    histogram work O(rows) per LEVEL instead of O(rows) per split;
    the mxu/pallas paths keep per-splitting-child masked passes (no
    segment axis in those kernels yet — see the note in step 3), so
    there the win is the fusion, sibling subtraction, and per-level
    batched scoring, not asymptotic histogram work. Depth-wise
    trees differ from leaf-wise trees whenever the leaf budget binds
    before the frontier is exhausted — that is the point of the mode
    (the reference's ``growing policy``), not a numerical gap; with a
    non-binding budget both policies split the identical leaf set.

    Supports the core feature set (numeric + categorical splits,
    bagging weights, max_depth, data-parallel ``axis_name`` psums);
    the flagship compact grower keeps everything else.
    """
    L = cfg.num_leaves
    B = cfg.num_bins
    F = bins_T.shape[0]
    n = bins_T.shape[1]
    dtype = grad.dtype
    p = cfg.split
    has_cat = feat_is_cat is not None
    hmethod = cfg.hist_method \
        if cfg.hist_method in ("scatter", "pallas") else "mxu"
    sharded = (cfg.axis_name is not None and cfg.parallel_mode == "data"
               and cfg.split_search == "sharded")

    def psum(x):
        return _sums_psum(x, cfg.axis_name) if cfg.axis_name else x

    qm, use_ef, _gath_ef = comms.make_hist_psum_ef(
        cfg.axis_name, cfg.hist_comm)

    if sharded:
        Fl, Fsp, f_start, dev_idx, _rs_pad, hist_psum_ef, _ssl = \
            _make_sharded_search(cfg, F, qm, use_ef)
        FH = Fl

        def best_for(hist, sg, sh, sc):
            owned = (f_start + jnp.arange(Fl)) < F
            r = find_best_split(hist, sg, sh, sc,
                                _ssl(feat_num_bins, 1),
                                _ssl(feat_nan_bin, -1),
                                _ssl(feature_mask, False) & owned, p,
                                _ssl(monotone_constraints, 0),
                                _ssl(feat_is_cat, False))
            r = r._replace(feature=r.feature + f_start)
            return _combine_split_infos(r, cfg.axis_name)
    else:
        FH = F
        hist_psum_ef = _gath_ef

        def best_for(hist, sg, sh, sc):
            return find_best_split(hist, sg, sh, sc, feat_num_bins,
                                   feat_nan_bin, feature_mask, p,
                                   monotone_constraints, feat_is_cat)

    def depth_ok(d):
        if cfg.max_depth <= 0:
            return jnp.asarray(True)
        return d < cfg.max_depth

    # ---- root ----
    w = row_weight.astype(dtype)
    inbag = row_weight > 0
    gh = jnp.stack([grad * w, hess * w], axis=-1)          # [n, 2]
    total_g = psum(jnp.sum(gh[:, 0]))
    total_h = psum(jnp.sum(gh[:, 1]))
    total_ci = psum(jnp.sum(inbag, dtype=jnp.int32))    # exact
    total_c = total_ci.astype(dtype)
    all_rows = jnp.ones((n,), jnp.bool_)
    FE = Fsp if sharded else F        # EF feature width (scatter-padded)
    root_local = build_histogram(bins_T, grad, hess, row_weight, all_rows,
                                 B, hmethod, cfg.hist_precision)
    with comms.reduction_site("tree"):
        if use_ef and hmethod == "scatter":
            # EF shape follows the reduction the path issues
            # (_LevelState): one slot a leaf
            comm_ef0 = jnp.zeros((L, FE, B, 2), dtype)
            root_hist, ef_slot0 = hist_psum_ef(root_local, comm_ef0[0])
            comm_ef0 = comm_ef0.at[0].set(ef_slot0)
        else:
            root_hist, comm_ef0 = hist_psum_ef(
                root_local,
                jnp.zeros((FE, B, 2), dtype) if use_ef else ())
    tree = _init_tree(L, B, dtype)
    tree = tree._replace(
        leaf_value=tree.leaf_value.at[0].set(
            leaf_output(total_g, total_h, p)),
        leaf_weight=tree.leaf_weight.at[0].set(total_h),
        leaf_count=tree.leaf_count.at[0].set(total_ci),
    )
    best = _BestSplits.init(L, B, dtype)
    best = best.store(0, best_for(root_hist, total_g, total_h, total_c),
                      jnp.asarray(True))
    hists = jnp.zeros((L, FH, B, 2), dtype).at[0].set(root_hist)
    state = _LevelState(tree=tree, best=best, hists=hists,
                        row_leaf=jnp.zeros((n,), jnp.int32),
                        num_splits=jnp.asarray(0, jnp.int32),
                        level=jnp.asarray(0, jnp.int32),
                        comm_ef=comm_ef0)
    slots = jnp.arange(L, dtype=jnp.int32)

    def level_step(state: _LevelState) -> _LevelState:
        tree, best, hists, row_leaf, ns, level, comm_ef = state

        # -- 1. elect the level's splits, gain-ranked under the budget --
        active = slots < tree.num_leaves
        frontier = active & (tree.leaf_depth == level)
        cand = frontier & (best.gain > 0.0)
        capacity = jnp.asarray(L - 1, jnp.int32) - ns
        order = jnp.argsort(jnp.where(cand, -best.gain, jnp.inf))
        rank = jnp.argsort(order).astype(jnp.int32)
        splitting = cand & (rank < capacity)
        # node ids / right-child slots in slot order (creation order is
        # a labeling choice; the Tree convention only needs left child
        # = parent slot, right child = next free slot)
        ordn = jnp.cumsum(splitting.astype(jnp.int32)) - 1
        node_ids = ns + ordn
        r_slots = jnp.clip(ns + 1 + ordn, 0, L - 1)

        # -- 2. partition every elected leaf's rows (the level's single
        # DataPartition::Split sweep) + record the split in the tree --
        def split_one(l, carry):
            def do(carry):
                tree, best, row_leaf = carry
                R = r_slots[l]
                f = best.feature[l]
                t = best.threshold_bin[l]
                dl = best.default_left[l]
                col = lax.dynamic_index_in_dim(
                    bins_T, f, axis=0, keepdims=False).astype(jnp.int32)
                nanb = feat_nan_bin[f]
                gl = jnp.where((nanb >= 0) & (col == nanb), dl, col <= t)
                if has_cat:
                    gl = jnp.where(best.is_cat[l], best.cat_mask[l][col],
                                   gl)
                on_leaf = row_leaf == l
                nl_i = psum(jnp.sum(on_leaf & gl & inbag,
                                    dtype=jnp.int32))
                row_leaf = jnp.where(on_leaf & ~gl, R, row_leaf)
                tree = _apply_split_to_tree(
                    tree, best, l, R, node_ids[l], p, nl_i,
                    tree.leaf_count[l] - nl_i)
                return tree, best, row_leaf

            # COLLECTIVE-IN-COND INVARIANT (data-parallel): the taken
            # branch psums the exact left count; `splitting` derives
            # only from globally-reduced histograms and the
            # deterministic election, so every device takes the same
            # branch sequence.
            # tpulint: replicated-cond splitting is a pure function of replicated state
            return lax.cond(splitting[l], do, lambda c: c, carry)

        tree, best, row_leaf = lax.fori_loop(
            0, L, split_one, (tree, best, row_leaf))

        # -- 3. the level's child histograms: one batched pass over the
        # (estimated-smaller) children's rows; siblings by subtraction --
        left_cnt = tree.leaf_count                       # [L] post-split
        right_cnt = tree.leaf_count[r_slots]
        left_small = left_cnt <= right_cnt
        small_slot = jnp.where(left_small, slots, r_slots)
        drop = jnp.asarray(L, jnp.int32)
        is_small = jnp.zeros((L,), jnp.bool_).at[
            jnp.where(splitting, small_slot, drop)].set(True, mode="drop")

        if hmethod == "scatter":
            # leaf-segmented scatter: ONE pass over all rows builds
            # every small child's histogram at once (segment id =
            # row_leaf, payload masked to small-child rows)
            seg = row_leaf
            m = is_small[seg].astype(dtype)[:, None]     # [n, 1]
            pay = gh * m

            def seg_body(carry, bins_f):
                idx = seg * B + bins_f.astype(jnp.int32)
                h = jnp.zeros((L * B, 2), dtype).at[idx].add(
                    pay, mode="drop")
                return carry, h

            _, h_f = lax.scan(seg_body, None, bins_T)    # [F, L*B, 2]
            with comms.reduction_site("level"):
                small_hists, comm_ef = hist_psum_ef(
                    h_f.reshape(F, L, B, 2).transpose(1, 0, 2, 3),
                    comm_ef)
        else:
            # MXU / Pallas kernels have no segment axis: one masked
            # kernel pass per small child, cond-skipped for idle
            # slots. NB: each taken pass streams the FULL bin matrix
            # with the other leaves' payload zeroed, so per-level hist
            # cost on these paths is (#splitting children) x O(n*F) —
            # the fusion/sibling-subtraction/batched-scoring wins
            # apply, but the O(rows)-per-level property belongs to the
            # scatter segment pass above. A segment-aware kernel pass
            # (gather the small child's rows first) is the open
            # follow-up for the TPU paths.
            def hist_one(l, carry):
                def do(carry):
                    acc, ef = carry
                    mask = row_leaf == small_slot[l]
                    h = build_histogram(bins_T, grad, hess, row_weight,
                                        mask, B, hmethod,
                                        cfg.hist_precision)
                    # rolling EF: each child reduction consumes +
                    # refills the one [F, B, 2] buffer in sequence
                    # (ef passes through untouched at exact f32 wire)
                    h, ef = hist_psum_ef(h, ef)
                    acc = lax.dynamic_update_index_in_dim(
                        acc, h, small_slot[l], axis=0)
                    return acc, ef

                # tpulint: replicated-cond splitting is replicated (see the partition sweep)
                return lax.cond(splitting[l], do, lambda c: c, carry)

            small_hists, comm_ef = lax.fori_loop(
                0, L, hist_one,
                (jnp.zeros((L, FH, B, 2), dtype), comm_ef))

        def sib_one(l, hists):
            def do(hists):
                R = r_slots[l]
                parent = hists[l]
                small = lax.dynamic_index_in_dim(
                    small_hists, small_slot[l], keepdims=False)
                other = subtract_histogram(parent, small)
                lh = jnp.where(left_small[l], small, other)
                rh = jnp.where(left_small[l], other, small)
                return hists.at[l].set(lh).at[R].set(rh)

            return lax.cond(splitting[l], do, lambda h: h, hists)

        hists = lax.fori_loop(0, L, sib_one, hists)

        # -- 4. score the whole new frontier in one vmapped batch;
        # every other slot (including just-retired frontier leaves that
        # didn't make the election) drops to -inf and never splits --
        if sharded:
            # leaf (g, h) totals from the GLOBAL feature-0 histogram
            # row — owned by device 0 (f_start == 0), broadcast with
            # one tiny [L, B, 2] psum so every device sums the exact
            # bin sequence the gathered path sums (hists[:, 0] on a
            # chunk is a different feature per device: same total,
            # different addition order, hence different last-ulp bits)
            row0 = _sums_psum(
                jnp.where(dev_idx == 0, hists[:, 0],
                          jnp.zeros_like(hists[:, 0])), cfg.axis_name)
            sums = row0.sum(axis=1)                      # [L, 2]
        else:
            sums = hists[:, 0].sum(axis=1)               # [L, 2]
        r = jax.vmap(best_for)(hists, sums[:, 0], sums[:, 1],
                               tree.leaf_count.astype(dtype))
        is_child = (slots < tree.num_leaves) \
            & (tree.leaf_depth == level + 1)
        allowed = is_child & depth_ok(level + 1)
        best = _BestSplits(jnp.where(allowed, r.gain, NEG_INF),
                           *tuple(r)[1:])
        return _LevelState(tree=tree, best=best, hists=hists,
                           row_leaf=row_leaf,
                           num_splits=ns + jnp.sum(
                               splitting.astype(jnp.int32)),
                           level=level + 1, comm_ef=comm_ef)

    def can_grow(state: _LevelState):
        return (state.num_splits < L - 1) \
            & jnp.any(state.best.gain > 0.0)

    state = lax.while_loop(can_grow, level_step, state)
    return state.tree, state.row_leaf


# ---------------------------------------------------------------------------
# Compact grower: rows grouped by leaf (DataPartition re-imagined)
# ---------------------------------------------------------------------------

class _CompactState(NamedTuple):
    tree: TreeArrays
    best: _BestSplits
    hists: jnp.ndarray       # [L, F, B, 2] (sum_grad, sum_hess); when
                             # the histogram pool is active, [P, F, B,
                             # 2] slot storage instead (see pool)
    bins2: jnp.ndarray       # [2*(n+2K), NW] u32 — bin columns packed
                             # 4 (u8) / 2 (u16) per word; two ping-pong
                             # halves laid out flat; half b's window
                             # positions start at b*(n+2K) + K (K rows
                             # of pad on both sides of each half absorb
                             # full-chunk write tails)
    pay2: jnp.ndarray        # [2*(n+2K), 2] i8/bf16/f32 — (g, h) payload
                             # rows; in the wide partition the f32
                             # payload is PLANAR instead: one 1-D
                             # f32[2 * 2*(n+2K)], all g then all h (g of
                             # position p at [p], h at [2*(n+2K) + p])
    ord2: jnp.ndarray        # [2*(n+2K)] u32 — original row id, top
                             # bit = in-bag flag
    leaf_buf: jnp.ndarray    # [L] i32 — which half (0/1) holds each
                             # leaf's window; the left child stays in
                             # the parent's half, the right child moves
                             # to the other
    leaf_begin: jnp.ndarray  # [L] i32 (local raw offsets)
    leaf_count: jnp.ndarray  # [L] i32 (local raw counts)
    branch: jnp.ndarray      # [L, F] bool — features used on leaf's path
    num_splits: jnp.ndarray  # scalar i32
    cegb: tuple = ()         # (coupled_used [F], lazy_used [n,F],
                             #  lazy_nu [L,F]) when cfg.cegb
    mono: tuple = ()         # (leaf_min [L], leaf_max [L]) output-bound
                             # entries (BasicConstraint analogs) when
                             # monotone constraints are active; plus
                             # (anc [L, L-1] i8: 0=not under node,
                             # 1=left subtree, 2=right) for intermediate
    node_masks: tuple = ()   # ([L, F] bool,) — per-node sampled feature
                             # sets when cfg.bynode < 1
    pool: tuple = ()         # histogram pool bookkeeping when
                             # cfg.hist_pool_slots > 0:
                             # (leaf2slot [L] i32, -1 = evicted;
                             #  slot2leaf [P] i32, -1 = free;
                             #  lru [P] i32 last-use split tick)
    comm_ef: jnp.ndarray = ()  # [F, B, 2] error-feedback residual of
                             # the quantized histogram allreduce
                             # (hist_comm int8/int16; parallel/
                             # comms.py). One rolling buffer, not
                             # per-leaf slots: the EF telescope bounds
                             # accumulated error across the SEQUENCE
                             # of reductions regardless of leaf
                             # attribution, at 1/L the memory of the
                             # histogram cache it rides beside.
    pcache: jnp.ndarray = () # [F, B, 2] prefetched parent histogram of
                             # the NEXT split's leaf (non-pooled only).
                             # Reading the parent from the carry instead
                             # of `hists[leaf]` removes the only
                             # pre-update use of `hists` in the loop
                             # body, so XLA aliases the two child
                             # dynamic-update-slices in place instead of
                             # copying the whole [L, F, B, 2] buffer
                             # twice per split (measured: 2x 14.6 MB at
                             # Higgs, 2x 167 MB at Allstate width)


_IB_BIT = jnp.uint32(1 << 31)


def _leaf_values_at_positions(leaf_begin, leaf_count, values, n):
    """Spread per-leaf int ``values`` onto the [n] grouped positions
    (ranges partition [0, n)).

    At each active range start, scatter the DELTA between consecutive
    begin-sorted leaves' values (an L-sized scatter — cheap), then one
    [n] cumsum materializes the value per position. No [n]-sized
    gather: XLA:TPU serializes gathers per element, while scatter-of-L
    + cumsum is pure vector work."""
    active = leaf_count > 0
    keys = jnp.where(active, leaf_begin, n + 1)
    ls = jnp.argsort(keys)  # leaves ordered by begin, inactive last
    flag = active[ls].astype(jnp.int32)
    v = values[ls].astype(jnp.int32)
    prev = jnp.concatenate([jnp.zeros((1,), v.dtype), v[:-1]])
    delta = (v - prev) * flag
    marks = jnp.zeros((n,), jnp.int32).at[
        jnp.clip(leaf_begin[ls], 0, n - 1)].add(delta)
    return jnp.cumsum(marks)


def _leaf_of_positions(leaf_begin, leaf_count, n, L):
    """[n] leaf id per grouped position (see _leaf_values_at_positions)."""
    return _leaf_values_at_positions(leaf_begin, leaf_count,
                                     jnp.arange(L, dtype=jnp.int32), n)


def _row_leaf_from_order(order, leaf_of_pos):
    """Positional->row-id inversion as a variadic sort (a vectorized
    sorting network) rather than a scatter, which XLA:TPU serializes
    per element."""
    _, row_leaf = lax.sort((order, leaf_of_pos), num_keys=1)
    return row_leaf


# What the compact grower resolved the last time it was traced: how a
# chunk is partitioned (``partition``: wide | sort) and the
# form of the (g, h) payload (``payload``: int8 | bf16 | f32-planar |
# f32), and how many columns the chunk's key sort carries
# (``sort_operands``: 4 for the wide arm's key, iota, g, h). Written
# at trace time, so it describes a compile, not a call;
# the engine stamps it onto its ``train/build_step`` span
# (models/gbdt.py, docs/OBSERVABILITY.md).
last_plan: dict = {}


# Variadic-sort width management for the chunk partition. The TPU
# backend's codegen for one variadic sort degrades SUPER-LINEARLY in
# operand count — measured on v5e (16K rows, compile seconds):
#   5-operand 7.0 | 9-op 15.0 | 13-op 25.0 | 17-op: minutes each |
#   168-op (Allstate EFB width, 667 bundle columns packed 4/word):
#   never returned within 2.5 h.
# (CPU-backend compile stays seconds at every width, so it is the TPU
# sort codegen, not XLA frontend passes.) Splitting into small-group
# sorts that each re-sort the SAME key is result-identical — the key
# (side*K + lane) is unique per row, so every group sort computes the
# same permutation — at the cost of one extra key column of VMEM
# traffic per group. Narrow datasets (the Higgs shape: 8-9 payload
# operands) keep the proven single sort; wide ones pay ~12% more sort
# traffic to make compile linear in width (~15 s per 9-operand group,
# one-time with the persistent compilation cache).
_SORT_SINGLE_MAX = 12
_SORT_GROUP = 8


def _chunk_rows(cfg: "GrowConfig", n: int) -> int:
    """Rows a streamed chunk of the compact grower: ``cfg.chunk``, halved
    while it is twice the table, never under 256."""
    K = cfg.chunk
    while K >= 2 * n:
        K //= 2
    return max(K, 256)


def _pack_width(bin_dtype, num_bins: int) -> int:
    """Bin columns per u32 word of the streamed copy: 8 when every
    feature fits 4 bits (the reference's 4-bit DenseBin,
    src/io/dense_bin.hpp is_4bit path), else 4 (u8) / 2 (u16)."""
    if bin_dtype == jnp.uint8:
        return 8 if num_bins <= 16 else 4
    return 2


def _payload_form(cfg: "GrowConfig") -> str:
    """The streamed (g, h) pair: ``int8`` under quantized gradients,
    ``bf16`` where the histogram matmul truncates to bfloat16 anyway
    (``_grow_compact_impl`` says why), else ``f32``."""
    if cfg.quantized:
        return "int8"
    if jax.default_backend() == "tpu" and cfg.hist_method != "scatter" \
            and cfg.hist_precision == "default":
        return "bf16"
    return "f32"


def compact_plan(cfg: "GrowConfig", n: int, F: int, bin_dtype,
                 bundled: bool = False) -> dict:
    """What the compact grower resolves for a ``[F, n]`` bin matrix of
    ``bin_dtype`` (``n``: the rows this device holds): ``last_plan``'s
    three keys, as a pure function of what a caller knows before any
    trace. The grower takes its partition arm from here, so the two
    cannot disagree; a caller whose grower came out of the process's jit
    cache (nothing was traced for it) asks here and not ``last_plan``,
    which is whatever job traced last."""
    K = _chunk_rows(cfg, n)
    NW = -(-F // _pack_width(bin_dtype, cfg.num_bins))
    form = _payload_form(cfg)
    NPAY = 2 if form == "f32" else 1
    track = bool(cfg.track_rows or cfg.cegb or (cfg.bundled and bundled))
    # the wide arm's flat offsets are int32 products pos * NW
    wide = NW + NPAY + int(track) > _SORT_SINGLE_MAX \
        and 2 * (n + 2 * K) * NW < 2 ** 31
    return {"partition": "wide" if wide else "sort",
            "payload": "f32-planar" if wide and form == "f32" else form,
            # the key, the wide arm's iota or the sort arm's NW word
            # columns, the payload's columns, ord
            "sort_operands": 1 + (1 if wide else NW) + NPAY + int(track)}


def _sort_gather(key, rows, cols):
    """One chunk of the WIDE partition: the ``[K, NW]`` packed-word
    ``rows`` and the 1-D per-row ``cols`` brought into the order of
    the unique ``key``. Whatever is ONE value a row (the float32 g and
    h columns, the one-word int8 / bf16 pair, ``ord``) rides the
    (key, iota) sort as a further operand: moved, never compared
    (``num_keys=1``). On the v5e at K = 16,384 the sort goes from 7.5
    to 10.9 us a chunk with g and h aboard, where folding them into
    the gathered row and slicing them out of its 128-lane padding again
    cost 21 us (PR 32's chip runs; one word: 37 us the whole step for
    53 folded). The rows follow by ONE gather, which costs per row
    whatever its width. The iota is made HERE, inside the chunk loop's
    body: the TPU's stable-sort expansion breaks ties on an iota
    operand, and adds one of its own where it does not recognise ours.
    Returns ``(rows, cols)``, sorted."""
    iota = lax.iota(jnp.int32, key.shape[0])
    with scope("grow/partition/key_sort"):
        perm, *cols = lax.sort((key, iota) + tuple(cols), num_keys=1)[1:]
    with scope("grow/partition/gather"):
        # perm sorts a unique key over iota(K): a permutation of
        # [0, K), so the promise holds and no bounds-fill select
        # follows the gather
        return rows.at[perm].get(mode="promise_in_bounds"), tuple(cols)


def _sort_by_key(key, cols):
    """Multi-operand sort by a UNIQUE key, group-split past
    _SORT_SINGLE_MAX payload operands (see note above). Returns
    (sorted_key, *sorted_cols) like lax.sort((key,) + cols).

    The wide path VMAPS one _SORT_GROUP-operand sort over the groups
    (same-dtype columns stacked [G, group, n], key broadcast) so the
    whole partition lowers to ONE batched sort HLO per dtype — compile
    cost is then CONSTANT in width, where even a Python loop of small
    sorts still compiled super-additively (F=256: 9 loop sorts ≈
    520 s; the batched form is the narrow program's ~15 s)."""
    cols = tuple(cols)
    if len(cols) <= _SORT_SINGLE_MAX:
        return lax.sort((key,) + cols, num_keys=1)
    by_dtype: dict = {}
    for i, c in enumerate(cols):
        by_dtype.setdefault(jnp.dtype(c.dtype), []).append(i)
    out = [None] * len(cols)
    key_sorted = None
    for dt, idxs in by_dtype.items():
        arrs = [cols[i] for i in idxs]
        if len(arrs) <= _SORT_GROUP:
            res = lax.sort((key,) + tuple(arrs), num_keys=1)
            if key_sorted is None:
                key_sorted = res[0]
            for j, i in enumerate(idxs):
                out[i] = res[1 + j]
            continue
        G = -(-len(arrs) // _SORT_GROUP)
        pad = G * _SORT_GROUP - len(arrs)
        stack = jnp.stack(arrs + [arrs[-1]] * pad)
        stack = stack.reshape(G, _SORT_GROUP, key.shape[0])
        keyb = jnp.broadcast_to(key, (G,) + key.shape)

        def _one(k, ws):
            r = lax.sort((k,) + tuple(ws[i] for i in
                                      range(_SORT_GROUP)), num_keys=1)
            return r[0], jnp.stack(r[1:])

        ks, ws = jax.vmap(_one)(keyb, stack)
        if key_sorted is None:
            key_sorted = ks[0]
        flat = ws.reshape(G * _SORT_GROUP, key.shape[0])
        for j, i in enumerate(idxs):
            out[i] = flat[j]
    return (key_sorted,) + tuple(out)


def _grow_compact_impl(cfg: GrowConfig,
                       bins_T: jnp.ndarray,
                       grad: jnp.ndarray,
                       hess: jnp.ndarray,
                       row_weight: jnp.ndarray,
                       feature_mask: jnp.ndarray,
                       feat_num_bins: jnp.ndarray,
                       feat_nan_bin: jnp.ndarray,
                       monotone_constraints: Optional[jnp.ndarray] = None,
                       feat_is_cat: Optional[jnp.ndarray] = None,
                       quant_key: Optional[jnp.ndarray] = None,
                       interaction_groups: Optional[jnp.ndarray] = None,
                       forced: Optional[tuple] = None,
                       cegb_arrays: Optional[tuple] = None,
                       node_key: Optional[jnp.ndarray] = None,
                       bundle_arrays: Optional[tuple] = None):
    """Leaf-wise growth with rows kept PHYSICALLY grouped by leaf.

    The reference's DataPartition (data_partition.hpp) + CUDA partition
    (cuda_data_partition.cu) analog, re-shaped for the TPU memory
    system: the bin rows, payload, in-bag flags and row ids are
    physically re-ordered on every split so each leaf occupies a
    contiguous range. All per-split work then streams CONTIGUOUS
    fixed-size chunks through ``lax.fori_loop`` bodies — no random
    gathers (TPU gathers serialize per element) and no ``lax.switch``
    over window sizes (XLA copies big conditional operands; while-loop
    carries alias in place). Histograms ride the MXU via the nibble
    decomposition (histogram.py). The partition is a SINGLE streaming
    pass per split: each chunk is sort-partitioned in registers and its
    left/right runs are appended (masked RMW) into the opposite buffer
    of a leading-axis ping-pong pair, with the child histogram
    accumulated from the same resident chunk — the CUDA bit-vector +
    prefix-sum + histogram kernels (cuda_data_partition.cu,
    cuda_histogram_constructor.cu) fused into one data movement."""
    L = cfg.num_leaves
    B = cfg.num_bins
    F = bins_T.shape[0]
    # ORIGINAL feature count: equals F except in bundled mode, where
    # bins_T holds bundle columns but SplitResult.feature, the
    # per-node masks (bynode / interaction) and branch sets all live
    # in original-feature space
    F_orig = feature_mask.shape[0]
    n = bins_T.shape[1]
    dtype = grad.dtype
    p = cfg.split
    K = _chunk_rows(cfg, n)
    PAD = K                      # write-tail padding absorbs one chunk

    fp = cfg.axis_name is not None and cfg.parallel_mode == "feature"
    vp = cfg.axis_name is not None and cfg.parallel_mode == "voting"
    # reduce-scatter sharded split search (docs/SHARDING.md): data-
    # parallel rows + feature-parallel search. Histograms built over
    # local rows are reduce-scattered so each device owns (and
    # searches) only its ceil(F/D) feature chunk of the globally
    # reduced histogram; the winning SplitInfo records are allreduced
    # (_fp_combine) — the reference DataParallelTreeLearner's
    # ReduceScatter + per-worker subset search.
    sharded = (cfg.axis_name is not None and cfg.parallel_mode == "data"
               and cfg.split_search == "sharded")

    rows_sharded = cfg.axis_name is not None and not fp

    def psum(x):
        """Row-sharded reduction; identity in feature-parallel mode
        (rows are replicated there)."""
        return _sums_psum(x, cfg.axis_name) if rows_sharded else x

    # histogram wire format (parallel/comms.py): quantized exchange
    # only where a histogram reduction actually happens — data-parallel
    # float histograms. Quantized-gradient training reduces EXACT int32
    # histograms (psum stays exact and is already 4x-dense payload-
    # wise), so it keeps the plain path.
    qm, use_ef, _psum_ef = comms.make_hist_psum_ef(
        cfg.axis_name, cfg.hist_comm,
        quantize=not (fp or vp or cfg.quantized))

    def hist_psum(x):
        """Histogram reduction: identity for feature-parallel (every
        device holds all rows, so a local histogram is already global)
        AND for voting (the cache stays local; the reduction happens
        per-search over elected features only); a reduce-scatter to
        this device's owned chunk under the sharded split search.
        (``_rs_pad``/``Fsp`` are assigned below, before any call —
        closures bind late.)"""
        if cfg.axis_name is None or fp or vp:
            return x
        if sharded:
            return comms.hist_reduce_scatter(_rs_pad(x), cfg.axis_name,
                                             qm)
        return comms.hist_allreduce(x, cfg.axis_name, qm)

    def hist_psum_ef(x, ef):
        """EF-threaded histogram reduction: the hot per-split child
        reduction (and the root) consume + refill the error-feedback
        residual carried in _CompactState.comm_ef so accumulated
        quantization error telescopes instead of compounding
        (comms.hist_allreduce docstring). ``ef`` passes through
        untouched when the wire is exact f32 — and no reduction at all
        happens under feature/voting parallelism (a local histogram is
        already the one the search consumes). Sharded search: the
        reduction is the EF-threaded reduce-scatter, and the result is
        this device's chunk."""
        if fp or vp:
            return x, ef
        if sharded:
            return _sh_psum_ef(x, ef)
        return _psum_ef(x, ef)

    has_mono = monotone_constraints is not None
    # "advanced" (monotone precise mode) keeps intermediate's every-split
    # re-search machinery and replaces the scalar output bounds with
    # per-(feature, threshold) bounds computed from leaf boxes
    advanced = has_mono and cfg.monotone_method == "advanced"
    intermediate = has_mono and cfg.monotone_method in ("intermediate",
                                                        "advanced")
    use_bynode = cfg.bynode < 1.0 and node_key is not None
    smoothing = p.path_smooth > 0.0

    bundled = cfg.bundled and bundle_arrays is not None
    if bundled:
        # Bundling sits BELOW the learner layer exactly like the
        # reference's FeatureGroup (feature_group.h:26 is a dataset
        # property every learner consumes), and composes with the FULL
        # feature matrix (round 5) — nothing is gated:
        # - all three parallel modes: data (rows shard, bundle hists
        #   psum), feature (bundle columns window/own per device),
        #   voting (ballot/election/exchange in bundle-column space);
        # - interaction/bynode/CEGB: [F_orig]-space inputs (masks,
        #   branch sets, penalties) consumed per member
        #   (feature_mask[member_ix] / gain_penalty[member_ix]);
        # - every monotone method: basic/intermediate use scalar
        #   per-leaf bounds; advanced's [F_orig, B] per-threshold
        #   bound arrays gather into candidate space through the
        #   position->member map;
        # - path smoothing, forced splits (member-range reconstruction
        #   in forced_result), categorical members.
        (bundle_of, offset_of, bundle_is_direct, member_at, tloc_at,
         end_at, bundle_nanpos, bundle_nan_at) = bundle_arrays

    def _fp_combine(r: SplitResult) -> SplitResult:
        """SyncUpGlobalBestSplit over disjoint per-device feature
        subsets (module-level :func:`_combine_split_infos`)."""
        return _combine_split_infos(r, cfg.axis_name)

    def best_for(hist, sg, sh, sc, extra_mask=None, gain_penalty=None,
                 parent_output=None, depth=None, bounds=None):
        fmask = feature_mask if extra_mask is None \
            else feature_mask & extra_mask
        if sharded:
            # sharded split search: the reduce-scattered chunk covers
            # features [f_start, f_start + Fl); slice every per-feature
            # input to the window, search locally, globalize the
            # winner's feature id and allreduce the SplitInfo
            # (SyncUpGlobalBestSplit) — the same search sharding the
            # feature-parallel mode uses, fed by scattered rows;
            # ``ssl`` is _make_sharded_search's owned_slice
            owned = (f_start + jnp.arange(Fl)) < F
            if bounds is not None and len(bounds) == 6:
                # advanced monotone: slice the per-[F, B] bound arrays
                # to this device's feature window
                def bsl(b):
                    if Fsp > F:
                        b = jnp.concatenate(
                            [b, jnp.zeros((Fsp - F, B), b.dtype)])
                    return lax.dynamic_slice(b, (f_start, 0), (Fl, B))

                bounds = tuple(bsl(b) for b in bounds[:4]) + bounds[4:]
            r = find_best_split(hist, sg, sh, sc,
                                ssl(feat_num_bins, 1),
                                ssl(feat_nan_bin, -1),
                                ssl(fmask, False) & owned, p,
                                ssl(monotone_constraints, 0),
                                ssl(feat_is_cat, False),
                                ssl(gain_penalty, 0.0),
                                parent_output, depth, bounds)
            r = r._replace(feature=r.feature + f_start)
            return _fp_combine(r)
        if bundled and not vp:
            b_member, b_tloc = member_at, tloc_at
            b_end, b_nanpos, b_nan = end_at, bundle_nanpos, bundle_nan_at
            col_mask = None
            if fp:
                # feature-parallel over BUNDLE columns: slice the
                # [G, B] metadata to this device's word-aligned column
                # window, rebase the flat (g*B + p) indices into
                # window space, and mask candidates to OWNED columns.
                # fmask / feat_is_cat / feat_num_bins / gain_penalty
                # stay GLOBAL — the search indexes them by ORIGINAL
                # member feature id, which needs no rebasing (so the
                # winning SplitInfo's feature is already global too).
                def gsl(v, fill):
                    if Fp > F:
                        pad = jnp.full((Fp - F, v.shape[1]), fill,
                                       v.dtype)
                        v = jnp.concatenate([v, pad])
                    return lax.dynamic_slice(
                        v, (f_start, 0), (Fl, v.shape[1]))

                b_member = gsl(member_at, -1)
                b_tloc = gsl(tloc_at, 0)
                b_end = jnp.where(b_member >= 0,
                                  gsl(end_at, 0) - f_start * B, 0)
                np_s = gsl(bundle_nanpos, -1)
                b_nanpos = jnp.where(np_s >= 0, np_s - f_start * B, -1)
                b_nan = gsl(bundle_nan_at, False)
                col_mask = _fp_owner(f_start + jnp.arange(Fl)) == dev_idx
            r = find_best_split_bundled(hist, sg, sh, sc, b_member,
                                        b_tloc, b_end,
                                        bundle_is_direct,
                                        b_nanpos, b_nan,
                                        fmask, p, feat_is_cat,
                                        feat_num_bins, gain_penalty,
                                        col_mask,
                                        monotone_constraints=
                                        monotone_constraints,
                                        parent_output=parent_output,
                                        leaf_depth=depth, bounds=bounds)
            return _fp_combine(r) if fp else r
        if fp:
            # disjoint feature ownership over word-aligned windows: the
            # device's histogram covers ONLY its own Fl columns (built
            # that way, _local_hist_rows), its search runs on the
            # matching slice of the per-feature metadata masked to the
            # features it OWNS (windows of tail devices overlap when D
            # does not divide NW; _fp_owner keeps the cover exact), and
            # the winning SplitInfo is allreduced with the feature id
            # globalized (FeatureParallelTreeLearner,
            # feature_parallel_tree_learner.cpp:71 +
            # SyncUpGlobalBestSplit)
            def lsl(v, fill):
                """Device's Fl-slice of a per-feature vector (padded to
                the packed width so the window stays in range)."""
                if v is None:
                    return None
                if Fp > F:
                    pad = jnp.full((Fp - F,), fill, v.dtype)
                    v = jnp.concatenate([v, pad])
                return lax.dynamic_slice(v, (f_start,), (Fl,))

            owned = _fp_owner(f_start + jnp.arange(Fl)) == dev_idx
            if bounds is not None and len(bounds) == 6:
                # advanced monotone: slice the per-[F, B] bound arrays
                # to this device's feature window (pad rows are masked
                # off by `owned` anyway)
                def bsl(b):
                    if Fp > F:
                        b = jnp.concatenate(
                            [b, jnp.zeros((Fp - F, B), b.dtype)])
                    return lax.dynamic_slice(b, (f_start, 0), (Fl, B))

                bounds = tuple(bsl(b) for b in bounds[:4]) + bounds[4:]
            r = find_best_split(hist, sg, sh, sc,
                                lsl(feat_num_bins, 1),
                                lsl(feat_nan_bin, -1),
                                lsl(fmask, False) & owned, p,
                                lsl(monotone_constraints, 0),
                                lsl(feat_is_cat, False),
                                lsl(gain_penalty, 0.0),
                                parent_output, depth, bounds)
            r = r._replace(feature=r.feature + f_start)
            return _fp_combine(r)
        if vp:
            # PV-Tree (VotingParallelTreeLearner, voting_parallel_tree_
            # learner.cpp:364): local top-k ballot over per-feature best
            # gains -> global election of 2k features -> reduce ONLY the
            # elected features' histograms -> one global search over
            # them. The exchanged payload is the static-shape [k2, B, C]
            # selection (k2 = min(2k, F)) — O(2k*B) bytes on the wire
            # per search like the reference's CopyLocalHistogram buffer
            # (parallel_tree_learner.h:153-161), not the full
            # O(F*B) a data-parallel reduction pays.
            ax = cfg.axis_name
            # the ballot judges LOCAL histograms, so it must use local
            # leaf sums and shard-scaled data constraints (the
            # reference's local_config_, voting_parallel_tree_learner
            # .cpp:61-63)
            ndev = lax.axis_size(ax)
            lh_tot = jnp.sum(hist[0], axis=0)   # feature 0 sees all rows
            sg_loc, sh_loc = lh_tot[0], lh_tot[1]
            sc_loc = jnp.round(sc * sh_loc / jnp.maximum(sh, 1e-15))
            p_loc = p._replace(
                min_data_in_leaf=p.min_data_in_leaf / ndev,
                min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf / ndev)
            if bundled:
                # ballots/election/exchange run in bundle-COLUMN space
                # (F here is the bundle-column count); the bundled
                # search supplies per-column gains and the final
                # search masks to elected columns
                _, fgains = find_best_split_bundled(
                    hist, sg_loc, sh_loc, sc_loc, member_at, tloc_at,
                    end_at, bundle_is_direct, bundle_nanpos,
                    bundle_nan_at, fmask, p_loc, feat_is_cat,
                    feat_num_bins, gain_penalty,
                    return_col_gains=True,
                    monotone_constraints=monotone_constraints,
                    parent_output=parent_output,
                    leaf_depth=depth, bounds=bounds)
            else:
                _, fgains = find_best_split(
                    hist, sg_loc, sh_loc, sc_loc, feat_num_bins,
                    feat_nan_bin, fmask, p_loc,
                    monotone_constraints, feat_is_cat, gain_penalty,
                    parent_output, depth, bounds,
                    return_feature_gains=True)
            k = min(cfg.voting_top_k, F)
            kth = jnp.sort(fgains)[F - k]
            ballot = jnp.isfinite(fgains) & (fgains >= kth)
            votes = _sums_psum(ballot.astype(jnp.int32), ax)
            k2 = min(2 * cfg.voting_top_k, F)
            # deterministic election, identical on every device: vote
            # count, ties to the lower feature id (GlobalVoting,
            # voting_parallel_tree_learner.cpp:205)
            score = votes * F + (F - 1 - jnp.arange(F))
            idx = lax.top_k(score, k2)[1]                 # [k2]
            E = idx[:, None] == jnp.arange(F)[None, :]    # [k2, F] bool
            elected = jnp.any(E, axis=0)                  # [F]
            # select elected rows (masked reduce, exact for int32 too),
            # psum the SMALL [k2, B, C] buffer, scatter back
            sel = jnp.sum(jnp.where(E[:, :, None, None], hist[None], 0),
                          axis=1)                         # [k2, B, C]
            # the elected-buffer exchange is the voting mode's one
            # histogram reduction — quantize it under hist_comm too.
            # Stateless + the vmap-safe shared-scale strategy: this
            # site runs under jax.vmap (both children's searches fuse
            # into one batched collective), where all_to_all has no
            # batching story; int32 hists (quantized grads) fall back
            # to the exact psum inside.
            gsel = comms.hist_allreduce(sel, ax, cfg.hist_comm,
                                        strategy="psum")
            ghist = jnp.sum(jnp.where(E[:, :, None, None], gsel[:, None],
                                      0), axis=0)         # [F, B, C]
            if bundled:
                return find_best_split_bundled(
                    ghist, sg, sh, sc, member_at, tloc_at, end_at,
                    bundle_is_direct, bundle_nanpos, bundle_nan_at,
                    fmask, p, feat_is_cat, feat_num_bins,
                    gain_penalty, col_mask=elected,
                    monotone_constraints=monotone_constraints,
                    parent_output=parent_output,
                    leaf_depth=depth, bounds=bounds)
            return find_best_split(ghist, sg, sh, sc, feat_num_bins,
                                   feat_nan_bin, fmask & elected, p,
                                   monotone_constraints, feat_is_cat,
                                   gain_penalty, parent_output, depth,
                                   bounds)
        return find_best_split(hist, sg, sh, sc, feat_num_bins,
                               feat_nan_bin, fmask, p,
                               monotone_constraints, feat_is_cat,
                               gain_penalty, parent_output, depth,
                               bounds)

    def node_feature_mask(idx):
        """Per-node feature subset (ColSampler::GetByNode): rank a fresh
        uniform draw over the tree's usable features, keep
        max(1, round(bynode * |usable|)). The reference samples with its
        sequential Random stream; this keyed-fold stream is an equally
        deterministic redesign."""
        u = jax.random.uniform(jax.random.fold_in(node_key, idx),
                               (F_orig,))
        u = jnp.where(feature_mask, u, jnp.inf)
        rank = jnp.argsort(jnp.argsort(u))
        total = jnp.sum(feature_mask.astype(jnp.int32))
        k = jnp.maximum(jnp.round(total * cfg.bynode).astype(jnp.int32),
                        jnp.minimum(1, total))
        return (rank < k) & feature_mask

    def allowed_features(branch_set):
        """Features usable at a node whose path used ``branch_set``
        (ColSampler::GetByNode, col_sampler.hpp:205): union of the
        constraint groups that contain the whole branch set."""
        contains = ~jnp.any(branch_set[None, :] & ~interaction_groups,
                            axis=1)                       # [G]
        return jnp.any(interaction_groups & contains[:, None], axis=0)

    def advanced_bounds(box_lo, box_hi, values, num_leaves_, bl, bh):
        """Per-(feature, threshold) monotone output bounds for the
        children of a split of the leaf whose bin-space box is
        [bl, bh) — AdvancedLeafConstraints ("monotone precise mode",
        monotone_constraints.hpp:858) re-expressed as box algebra.

        The reference walks up the leaf's path and recursively down
        each monotone ancestor's opposing subtree, collecting leaf
        outputs into per-threshold segment lists
        (GoDownToFindConstrainingLeaves / UpdateConstraints). The
        constraining set it visits is exactly: leaves whose boxes
        OVERLAP the searched leaf's box in every feature except one
        monotone feature m, where they are disjoint-ordered (the LCA
        split on m is the monotone ancestor; categorical splits leave
        both children's boxes equal to the parent's, reproducing the
        reference's keep-going-both-ways treatment of categorical
        nodes). So, tensorized over the CURRENT leaves:
        - route m != j (t-refined only through the child's j-interval
          overlap): ordered-in-m leaves bound the child wherever their
          j-interval overlaps the child's;
        - route m == j: leaves ordered in j against the CHILD interval
          ([lo_j, t+1) left / [t+1, hi_j) right) bound it directly.
        Upper bounds come from increasing-feature-above or
        decreasing-feature-below leaves (min of their outputs); lower
        bounds are symmetric (max).

        Returns the 6-tuple consumed by split_bounds_lrc: per-[F, B]
        (lmin_l, lmax_l, lmin_r, lmax_r) plus scalar fallbacks
        (smin, smax) for categorical candidates (a categorical split
        leaves both children's boxes equal to the parent's, so only the
        t-independent route applies)."""
        inf_ = jnp.asarray(jnp.inf, dtype)
        act = jnp.arange(L) < num_leaves_                  # [L]
        ov = (box_lo < bh[None, :]) & (box_hi > bl[None, :])   # [L, F]
        nonov = (~ov).astype(jnp.int32)
        cnt_no = jnp.sum(nonov, axis=1)                    # [L]
        only_m = (cnt_no[:, None] - nonov) == 0            # [L, F]
        above = box_lo >= bh[None, :]                      # [L, F]
        below = box_hi <= bl[None, :]
        mc_i = monotone_constraints.astype(jnp.int32)
        inc = (mc_i > 0)[None, :]
        dec = (mc_i < 0)[None, :]
        up_any = jnp.any(only_m & ((inc & above) | (dec & below)),
                         axis=1) & act                     # [L]
        dn_any = jnp.any(only_m & ((inc & below) | (dec & above)),
                         axis=1) & act
        t = jnp.arange(B)[None, None, :]                   # thresholds
        # overlap of each leaf's j-interval with the child's:
        # left child [bl_j, t+1), right child [t+1, bh_j)
        ovl_l = (box_lo[:, :, None] <= t) \
            & (box_hi[:, :, None] > bl[None, :, None])     # [L, F, B]
        ovl_r = (box_lo[:, :, None] < bh[None, :, None]) \
            & (box_hi[:, :, None] > t + 1)
        # route m == j: ordering against the child's own j-interval
        oj = (only_m & act[:, None])[:, :, None]           # [L, F, 1]
        above_l = box_lo[:, :, None] >= t + 1              # [L, F, B]
        below_r = box_hi[:, :, None] <= t + 1
        up_l2 = oj & ((inc[:, :, None] & above_l)
                      | (dec & below)[:, :, None])
        dn_l2 = oj & ((inc & below)[:, :, None]
                      | (dec[:, :, None] & above_l))
        up_r2 = oj & ((inc & above)[:, :, None]
                      | (dec[:, :, None] & below_r))
        dn_r2 = oj & ((inc[:, :, None] & below_r)
                      | (dec & above)[:, :, None])
        v = values[:, None, None]

        def vmin(mask):
            return jnp.min(jnp.where(mask, v, inf_), axis=0)

        def vmax(mask):
            return jnp.max(jnp.where(mask, v, -inf_), axis=0)

        u_any = up_any[:, None, None]
        d_any = dn_any[:, None, None]
        lmax_l = vmin((u_any & ovl_l) | up_l2)             # [F, B]
        lmin_l = vmax((d_any & ovl_l) | dn_l2)
        lmax_r = vmin((u_any & ovl_r) | up_r2)
        lmin_r = vmax((d_any & ovl_r) | dn_r2)
        smax = jnp.min(jnp.where(up_any, values, inf_))
        smin = jnp.max(jnp.where(dn_any, values, -inf_))
        return (lmin_l, lmax_l, lmin_r, lmax_r, smin, smax)

    cegb = cfg.cegb
    cegb_lazy = cfg.cegb_lazy and cegb
    cegb_coupled = cfg.cegb_coupled and cegb
    if cegb:
        pen_coupled, pen_lazy, coupled_used0, lazy_used0 = cegb_arrays
        if cegb_lazy and lazy_used0 is None:
            raise ValueError("cegb_lazy requires a lazy_used matrix")

        # Penalties count in-bag rows only: the reference's
        # num_data_in_leaf / GetIndexOnLeaf walk the bagged partition
        # (cost_effective_gradient_boosting.hpp:81,128-137), which holds
        # no out-of-bag rows.
        def cegb_penalty(cnt, coupled_used, lazy_nu_leaf):
            """DeltaGain (cost_effective_gradient_boosting.hpp:81-97):
            tradeoff * (penalty_split*n + coupled-first-use + lazy)."""
            pen = jnp.full((F_orig,), cfg.cegb_tradeoff
                           * cfg.cegb_split * 1.0, dtype) \
                * cnt.astype(dtype)
            pen = pen + jnp.where(coupled_used, 0.0,
                                  cfg.cegb_tradeoff * pen_coupled)
            if cegb_lazy:
                pen = pen + cfg.cegb_tradeoff * pen_lazy * lazy_nu_leaf
            return pen

    # row-id / in-bag tracking (see GrowConfig.track_rows); consumers
    # force it on regardless of the flag
    track = cfg.track_rows or cegb or bundled
    # once a tree, over every row, whatever the splits: the weighted
    # payload here, the packed words, ping-pong buffers and root below
    with scope("grow/setup"):
        bins_rm = bins_T.T                      # [n, F] row-major for gathers
        w = row_weight.astype(dtype)
        inbag = row_weight > 0
        gw2 = jnp.stack([grad * w, hess * w], axis=-1)  # [n, 2]
        # scatter and pallas pass through; anything else ("onehot" legacy
        # spelling included) maps to the MXU nibble kernel
        hmethod = cfg.hist_method \
            if cfg.hist_method in ("scatter", "pallas") else "mxu"

        quant = cfg.quantized
        if quant:
            # GradientDiscretizer analog (gradient_discretizer.hpp:35):
            # per-tree scales, stochastic rounding, int8 payload.
            def pmax(x):
                if not cfg.axis_name:
                    return x
                with scope("grow/sums/allreduce"):
                    return lax.pmax(x, cfg.axis_name)

            half = max(1, cfg.quant_bins // 2)
            gs = jnp.maximum(pmax(jnp.max(jnp.abs(gw2[:, 0]))), 1e-30) / half
            hs = jnp.maximum(pmax(jnp.max(gw2[:, 1])), 1e-30) \
                / max(1, cfg.quant_bins)
            if cfg.stochastic and quant_key is not None:
                k = quant_key
                if cfg.axis_name and not fp:
                    # feature-parallel replicates rows: every device must
                    # round identically
                    k = jax.random.fold_in(k, lax.axis_index(cfg.axis_name))
                u = jax.random.uniform(k, (n, 2), dtype)
            else:
                u = jnp.full((n, 2), 0.5, dtype)
            gq = jnp.clip(jnp.floor(gw2[:, 0] / gs + u[:, 0]), -127, 127)
            hq = jnp.clip(jnp.floor(gw2[:, 1] / hs + u[:, 1]), 0, 127)
            gw2_q = jnp.stack([gq, hq], axis=-1).astype(jnp.int8)
            scale2 = jnp.stack([gs, hs])

    def hist_f(h):
        """int32 histogram -> float stats for split search."""
        if quant:
            return h.astype(dtype) * scale2[None, None, :]
        return h

    # The bin matrix and payload are PHYSICALLY re-ordered on every split
    # so that each leaf's rows are contiguous. All ordered arrays carry K
    # rows of padding so chunk slices/updates never clamp at the end;
    # garbage lands in (and is read from) the pad region and is masked.
    C = 2

    def window_chunks(cnt):
        return lax.div(cnt + (K - 1), jnp.asarray(K, cnt.dtype))

    has_cat = feat_is_cat is not None
    bin_dt = bins_T.dtype
    pack_w = _pack_width(bin_dt, B)    # bin columns per u32 word
    nibble_bins = pack_w == 8
    Fp = -(-F // pack_w) * pack_w
    NW = Fp // pack_w                             # u32 words per row

    # feature-parallel work sharding: each device owns a word-aligned
    # block of NWl packed words (Fl = NWl*pack_w feature columns) and
    # builds histograms ONLY for that block — F/D of the MXU hist work,
    # the TPU analog of each rank's ConstructHistograms over its own
    # subset (feature_parallel_tree_learner.cpp:71). Rows stay
    # replicated (like the reference: full data on every worker, so the
    # partition needs no collective); only the winning SplitInfo is
    # allreduced (_fp_combine). When D does not divide NW the tail
    # devices' windows CLAMP to the last NWl words (so the hist slice
    # never reads out of range) and ownership inside the overlapping
    # windows is made exact by ``_fp_owned``: feature f belongs to
    # device min(f // Fl, D-1) only — each device's search mask keeps
    # just its owned columns, so hist rows and metadata stay aligned.
    if fp:
        D_fp = lax.axis_size(cfg.axis_name)          # static under shard_map
        dev_idx = lax.axis_index(cfg.axis_name)   # traced
        NWl = -(-NW // D_fp)
        Fl = NWl * pack_w
        # this device's window start, in words / in feature columns
        w_start = jnp.minimum(dev_idx * NWl, NW - NWl)
        f_start = w_start * pack_w

        def _fp_owner(f):
            return jnp.minimum(f // Fl, D_fp - 1)
    elif sharded:
        # sharded-search ownership windows: DISJOINT equal chunks over
        # a D*ceil(F/D)-padded feature axis (psum_scatter needs equal
        # chunks; unlike fp's word-aligned clamped windows there is no
        # packing constraint — the hist is built at full width and
        # scattered, so a plain ceil split keeps ownership exact)
        Fl, Fsp, f_start, dev_idx, _rs_pad, _sh_psum_ef, ssl = \
            _make_sharded_search(cfg, F, qm, use_ef)
    else:
        Fl = F
    FB = Fl if fp else F       # hist BUILD feature count (local pass)
    FH = Fl if (fp or sharded) else F   # hist CACHE/search feature count

    def chunk_goleft(col, f, t, dl, isc, cm):
        """go-left decision for one chunk given the SPLIT column's bins
        ``col`` [K] (extracted from the packed words by _extract_col)
        — all vector ops (a cm[col] table gather would serialize per
        element on TPU)."""
        if bundled:
            # the split references an ORIGINAL feature; resolve its
            # bundle member range (ops/bundling.py layout)
            off = offset_of[f]
            nb = feat_num_bins[f]
            nanb = feat_nan_bin[f]
            left_direct = jnp.where((nanb >= 0) & (col == nanb), dl,
                                    col <= t)
            # member bins > t occupy positions [off + t, off + nb - 2];
            # a NaN member's NaN bin maps to its LAST position, which
            # routes by the learned default direction instead
            is_nanrow = (nanb >= 0) & (col == off + nanb - 1)
            right_multi = (col >= off + t) & (col <= off + nb - 2) \
                & ~is_nanrow
            left_multi = jnp.where(is_nanrow, dl, ~right_multi)
            gl_b = jnp.where(bundle_is_direct[f], left_direct,
                             left_multi)
            if has_cat:
                # categorical membership split: recover the member's
                # LOCAL bin (direct columns store it verbatim; multi
                # members map bins 1..nb-1 to [off, off+nb-2], rows
                # outside the range sit at the member's bin 0), then
                # route by the [B] membership mask like the plain path
                local = jnp.where(
                    bundle_is_direct[f], col,
                    jnp.where((col >= off) & (col <= off + nb - 2),
                              col - off + 1, 0))
                cm_col = jnp.any(
                    (local[:, None] == jnp.arange(B)[None, :])
                    & cm[None, :], axis=1)
                gl_b = jnp.where(isc, cm_col, gl_b)
            return gl_b
        nanb = feat_nan_bin[f]
        gl = jnp.where((nanb >= 0) & (col == nanb), dl, col <= t)
        if has_cat:
            cm_col = jnp.any((col[:, None] == jnp.arange(B)[None, :])
                             & cm[None, :], axis=1)
            gl = jnp.where(isc, cm_col, gl)
        return gl

    def _unpack_words(w32):
        """[S, nw] u32 words -> [S, nw*pack_w] native-width bins."""
        S, nw = w32.shape
        if nibble_bins:
            nibs = [((w32 >> (4 * k)) & 0xF).astype(bin_dt)
                    for k in range(8)]                    # 8 x [S, nw]
            u = jnp.stack(nibs, axis=2)                   # [S, nw, 8]
        else:
            u = lax.bitcast_convert_type(w32, bin_dt)     # [S, nw, pack_w]
        return u.reshape(S, nw * pack_w)

    def _extract_col(blk_w, c):
        """ONE bin column [K] from the packed [K, NW] words.

        The partition body needs only the SPLIT column to route rows;
        unpacking the whole [K, F] block for it cost O(F) VPU work
        per chunk — invisible at Higgs width (F=28) but ~6% of a wide
        EFB iteration (1044 bundle columns). c is traced (the split's
        column index)."""
        w = c // pack_w
        wordcol = lax.dynamic_slice(blk_w, (jnp.int32(0), w),
                                    (blk_w.shape[0], 1))[:, 0]
        bits = 32 // pack_w
        shift = (c % pack_w) * bits
        return ((wordcol >> shift.astype(jnp.uint32))
                & jnp.uint32((1 << bits) - 1)).astype(jnp.int32)

    def _local_hist_rows(w32, pos0, CK):
        """The rows fed to the MXU histogram: all F features, or — in
        feature-parallel — ONLY this device's NWl-word block (F/D of
        the one-hot/matmul work)."""
        if fp:
            if wide_part:
                blk = lax.dynamic_slice(_bins_slice(w32, pos0, CK),
                                        (jnp.int32(0), w_start),
                                        (CK, NWl))
            else:
                blk = lax.dynamic_slice(
                    w32, (pos0, jnp.asarray(w_start, pos0.dtype)),
                    (CK, NWl))
            return _unpack_words(blk)                     # [CK, Fl]
        blk = _bins_slice(w32, pos0, CK)
        return _unpack_words(blk)[:, :F]

    def rot(a, s):
        """a shifted so that out[j] = a[j - (K - s)] — dynamic roll via
        self-concatenation (vectorized; no per-element gather)."""
        if a.ndim == 2:
            return lax.dynamic_slice(jnp.concatenate([a, a], axis=0),
                                     (s, jnp.zeros((), s.dtype)),
                                     (a.shape[0], a.shape[1]))
        return lax.dynamic_slice(jnp.concatenate([a, a]), (s,),
                                 (a.shape[0],))

    # bf16 payload storage on TPU: the streamed (g, h) pairs only ever
    # feed the MXU histogram, whose single-pass default truncates f32
    # inputs to bf16 anyway — so storing them as bf16 is numerically
    # IDENTICAL on TPU while halving payload bytes in every chunk
    # slice/sort/write (and packing the pair into one u32 sort column).
    # Exact float sums (root totals, leaf renewal) read the original
    # f32 gw2, never pay2. Everything else keeps f32: the CPU (its
    # matmuls don't truncate), the scatter method, and on the TPU
    # hist_precision=high|highest. The f32 pair is two sort columns on
    # either path (held planar on the wide one: pay_planar below).
    bf16_pay = _payload_form(cfg) == "bf16"
    if quant:
        # int8 (g, h) pairs ride the sort as ONE u16 column
        def _pack_pay(blk_p):
            return (lax.bitcast_convert_type(
                blk_p.reshape(blk_p.shape[0], 1, 2), jnp.uint16)[:, 0],)

        def _unpack_pay(cols):
            return lax.bitcast_convert_type(
                cols[0][:, None], jnp.int8).reshape(cols[0].shape[0], 2)
        NPAY = 1
    elif bf16_pay:
        # bf16 (g, h) pairs ride the sort as ONE u32 column
        def _pack_pay(blk_p):
            return (lax.bitcast_convert_type(
                blk_p.reshape(blk_p.shape[0], 1, 2), jnp.uint32)[:, 0],)

        def _unpack_pay(cols):
            return lax.bitcast_convert_type(
                cols[0][:, None],
                jnp.bfloat16).reshape(cols[0].shape[0], 2)
        NPAY = 1
    else:
        def _pack_pay(blk_p):
            return (blk_p[:, 0], blk_p[:, 1])

        def _unpack_pay(cols):
            return jnp.stack(cols, axis=1)
        NPAY = 2

    SEG = n + 2 * PAD  # rows per ping-pong half (PAD rows both sides)

    # WIDE partition mode (round 5): at EFB width the per-chunk
    # partition permutes rows with a (key, iota) sort + ONE row GATHER a
    # chunk of the packed words (_sort_gather; the payload columns and
    # ord, one value a row each, ride the sort)
    # instead of carrying all NW word columns through the variadic sort
    # (which costs O(NW) traffic per bitonic stage — 0.77 ms/chunk at
    # NW=167 vs 35 us at Higgs width). Both children are written from
    # that one gathered block, the rights placed by the write's offset
    # (make_body). The gather and its DUS writeback want the ROW-MAJOR
    # layout, while the histogram one-hot wants rows minor; storing
    # bins2 FLAT (1-D) pins the row-major linearization globally, so XLA
    # relayouts only chunk-sized hist inputs instead of transposing the
    # whole multi-hundred-MB ping-pong buffer twice per chunk (measured
    # in-situ: the whole-buffer copies were 1.7 s/tree at 131K x 665).
    # (the 2**31 guard: flat offsets are int32 products pos*NW — past
    # ~2^31 elements they would wrap and silently corrupt the
    # partition, so such shapes — which exceed v5e HBM anyway — keep
    # the group-sort path)
    # Which of the two a job gets is read off its width here, never
    # asked of the user. The wide arm's (key, iota, g, h) sort is
    # ``grow.sort_ms_per_round`` in both benchmark cells (47.5, PR
    # 32's chip run; 32.8 as (key, iota, iota): ledger, PR 30); the
    # narrow arm's variadic sort has no cell that times it.
    plan = compact_plan(cfg, n, F, bin_dt, bundle_arrays is not None)
    wide_part = plan["partition"] == "wide"
    # The f32 (g, h) payload of the wide partition is resident PLANAR:
    # one 1-D f32[2 * 2*SEG], all g then all h. A 1-D buffer has one
    # possible layout, so the partition loop and the histogram loop
    # cannot disagree on it: as a 2-D [2*SEG, 2] carry the first kept
    # it row-major (minor dimension 2 padded to 128 lanes, 64x) and the
    # second rows-minor, and XLA:TPU copied the whole buffer between
    # them once a split (58% of a 6.6M x 67 round on the v5e). Flat
    # INTERLEAVED (as bins2 is) also drops the copy but the per-chunk
    # de-interleave of a 2-wide row compiled 8x slower at twice the
    # code. The int8 and bf16 pairs are one word and stay 2-D.
    pay_planar = wide_part and NPAY == 2
    last_plan.update(plan)

    def _bins_slice(w32, pos0, CK):
        """[CK, NW] chunk of the packed words at row offset pos0
        (the ndim check keeps the root-hist pass, which reads the
        pre-pad 2-D [n, NW] block, on the plain slice)."""
        if wide_part and w32.ndim == 1:
            return lax.dynamic_slice(
                w32, (pos0 * NW,), (CK * NW,)).reshape(CK, NW)
        return lax.dynamic_slice(
            w32, (pos0, jnp.zeros((), pos0.dtype)), (CK, NW))

    def _bins_write(arr, off, block, lo, hi):
        """Masked RMW of rows [lo, hi) of a K-row block of packed words
        at row offset ``off``. The wide mode takes the block FLAT
        (``u32[K*NW]``, flattened once by the caller for both of its
        writes) and selects in the flat domain: the flat buffer's slice
        is never re-tiled to ``[K, NW]``, whose 17-word minor dimension
        is padded to 128 lanes, and back."""
        i = jnp.arange(block.shape[0])
        if not wide_part:
            z = jnp.zeros((), off.dtype)
            cur = lax.dynamic_slice(arr, (off, z), block.shape)
            out = jnp.where(((i >= lo) & (i < hi))[:, None], block, cur)
            return lax.dynamic_update_slice(arr, out, (off, z))
        cur = lax.dynamic_slice(arr, (off * NW,), block.shape)
        out = jnp.where((i >= lo * NW) & (i < hi * NW), block, cur)
        return lax.dynamic_update_slice(arr, out, (off * NW,))

    def write(arr, off, block, m):
        """Masked RMW block write at a dynamic row offset."""
        if arr.ndim == 2:
            z = jnp.zeros((), off.dtype)
            cur = lax.dynamic_slice(arr, (off, z),
                                    (block.shape[0], arr.shape[1]))
            out = jnp.where(m[:, None], block, cur)
            return lax.dynamic_update_slice(arr, out, (off, z))
        cur = lax.dynamic_slice(arr, (off,), (block.shape[0],))
        out = jnp.where(m, block, cur)
        return lax.dynamic_update_slice(arr, out, (off,))

    def _pay_slice(pay2, pos0):
        """[K, 2] (g, h) chunk of the payload at row offset pos0."""
        if pay_planar:
            return jnp.stack(_pay_cols(pay2, pos0), axis=1)
        return lax.dynamic_slice(
            pay2, (pos0, jnp.zeros((), pos0.dtype)), (K, C))

    def _pay_cols(pay2, pos0):
        """The same chunk as the NPAY 1-D columns a sort carries: the
        planar form's two slices as they lie, else the pair in its one
        word."""
        if pay_planar:
            return tuple(
                lax.dynamic_slice(pay2, (pos0 + c * 2 * SEG,), (K,))
                for c in range(C))
        return _pack_pay(_pay_slice(pay2, pos0))

    def _pay_write(pay2, off, block, m):
        """Masked RMW of a (g, h) chunk at row offset ``off``: a [K, 2]
        block, or for the planar form its two sorted columns, each
        written as ord2 is written."""
        if pay_planar:
            for c in range(C):
                pay2 = write(pay2, off + c * 2 * SEG, block[c], m)
            return pay2
        return write(pay2, off, block, m)

    def chunk_hist(bins2, pay2, pos0, limit):
        """Histogram of one K-row chunk at dynamic row offset ``pos0``:
        slice the packed bin words + payload, mask the window tail
        (rows past ``limit`` relative to the chunk start), accumulate
        on the MXU. Shared by the post-partition child pass and the
        pool-miss window recompute."""
        with scope("grow/hist/build"):
            blk_b = _local_hist_rows(bins2, pos0, K)
            blk_p = _pay_slice(pay2, pos0)
            valid = jnp.arange(K) < jnp.clip(limit, 0, K)
            hp = blk_p * valid[:, None].astype(blk_p.dtype)
            if quant:
                return hist_from_rows_int(blk_b, hp, B, hmethod), valid
            return hist_from_rows(blk_b, hp, B, hmethod,
                                  cfg.hist_precision), valid

    def part_apply(bins2, pay2, ord2, lazy_used, src, start, cnt,
                   f, t, dl, isc, cm, est_left_small, comm_ef):
        """Stable two-way window compaction + child histogram in ONE
        streaming pass over the leaf's window.

        The two ping-pong halves live in one flat array; the half
        choice is plain row-offset arithmetic (``b*SEG + PAD``), so every
        access is the dynamic-row-slice pattern XLA:TPU aliases well —
        no conditional branches, no dynamic major-axis indexing.

        Each K-row chunk is read from the source half, partitioned
        in-registers by a variadic sort on a (side, position) key — the
        TPU's one fast data-movement primitive (gathers/scatters
        serialize per element; the wide mode sorts the key alone and
        applies the permutation with one gather of whole rows a chunk,
        rights placed by offset) — then:
        - LEFT runs append forward IN PLACE in the source half (safely
          behind the read frontier: l_off + K <= (c+1)K);
        - RIGHT runs pack backward from ``start + cnt`` in the OTHER
          half (dead space: window ranges partition [0, n) and only one
          half per range is live).
        Both writes are masked read-modify-writes: a full-chunk block's
        garbage lanes would otherwise spill across the window edge into
        a NEIGHBORING leaf's live rows whenever cnt is not K-aligned.
        The left child therefore stays in the parent's half and the
        right child lands in the opposite half (leaf_buf tracks this).
        The histogram of the (estimated-)smaller child is then built
        in a SECOND streaming pass over that child's now-contiguous
        rows only — the sibling follows by subtraction — so histogram
        work scales with Sum(min-child) instead of Sum(parent) rows.
        The CUDA analog is GenDataToLeftBitVector + prefix-sum
        compaction (cuda_data_partition.cu) followed by
        ConstructHistogramForLeaf on the smaller leaf
        (cuda_histogram_constructor.cu).

        The histogrammed side is the child of FEWER rows. On one chip
        (and under feature parallelism, where rows are replicated) that
        is read off the partition pass that has just run: ``n_left``
        against ``cnt - n_left``, exact, as the reference re-checks its
        smaller leaf with the partition's counts. Where rows are sharded
        the choice has to be the same on every shard before the
        histogram loop starts, and the exact global count is a
        collective, so there ``est_left_small`` decides: the stored
        SplitInfo's hessian-ratio estimates, deterministic and
        replicated, and wrong where hessians are uneven (a rare class:
        the child of few rows that fail often carries more hessian than
        its sibling; ROADMAP S13). Returns the side it built as
        ``left_small``.
        """
        src_base = src * SEG + PAD + start
        dst_base = (1 - src) * SEG + PAD + start
        zero = jnp.asarray(0, jnp.int32)
        acc0 = jnp.zeros((FB, B, C), jnp.int32 if quant else dtype)

        iota_c = jnp.arange(K)

        def body(c, carry):
            """Partition of the window's K-row chunk ``c``."""
            (bins2, pay2, ord2, lazy_used,
             l_off, r_off, nlib, nib) = carry
            off = c * K
            pos0 = src_base + off
            with scope("grow/partition/gather"):
                blk_w = _bins_slice(bins2, pos0, K)
            with scope("grow/partition/payload"):
                cols = _pay_cols(pay2, pos0)
            split_col = _extract_col(blk_w,
                                     bundle_of[f] if bundled else f)
            gl = chunk_goleft(split_col, f, t, dl, isc, cm)
            valid = iota_c < jnp.clip(cnt - off, 0, K)
            vl = valid & gl
            l_c = jnp.sum(vl, dtype=jnp.int32)
            r_c = jnp.sum(valid & ~gl, dtype=jnp.int32)
            if track:
                blk_o = lax.dynamic_slice(ord2, (pos0,), (K,))
                blk_i = (blk_o & _IB_BIT) != 0
                nlib += jnp.sum(vl & blk_i, dtype=jnp.int32)
                nib += jnp.sum(valid & blk_i, dtype=jnp.int32)
            else:
                # every row is in-bag: the partition counts ARE the
                # in-bag counts
                nlib += l_c
                nib += l_c + r_c
            if cegb_lazy:
                rows = (blk_o & ~_IB_BIT).astype(jnp.int32)
                # the split acquires feature f for every in-bag row
                # in the leaf (UpdateLeafBestSplits' InsertBitset
                # loop over the bagged partition)
                lazy_used = lazy_used.at[rows, f].max(valid & blk_i)
            # either arm moves the PACKED u32 word columns;
            # children are written back packed too — bins only ever
            # unpack transiently for goleft/histogram (bins2 stays
            # u32-tiled, avoiding the u8 (4,1) sub-byte layout tax
            # on every slice/RMW write). Each arm names ``r_lo``, the
            # rights' first lane in the block written for them.
            ml = iota_c < l_c
            side = jnp.where(vl, 0, jnp.where(valid, 1, 2))
            key = side * K + iota_c
            if track:
                cols += (blk_o,)
            if wide_part:
                # WIDE partition (round 5): a variadic sort moves
                # every operand through every bitonic stage (Allstate,
                # NW=167 word columns: 0.77 ms/chunk vs 35 us at Higgs
                # width), so the sort carries only what is ONE value a
                # row and the words follow by ONE row gather
                # (_sort_gather). Rows here are NW*4-byte contiguous
                # runs, wide enough to gather at vector width (at
                # Higgs width rows are ~28 B and the all-carrying sort
                # wins — hence the gate).
                la, cols = _sort_gather(key, blk_w, cols)
                with scope("grow/partition/gather"):
                    # flattened ONCE, for both writes (_bins_write)
                    lb = rb = la.reshape(-1)
                lp = rp = cols[:NPAY] if pay_planar \
                    else _unpack_pay(cols[:NPAY])
                if track:
                    lo = ro = cols[NPAY]
                # the sorted order IS the right block's: its rights
                # are lanes [l_c, l_c + r_c), placed by the write's
                # offset below instead of a second, rotated gather
                r_lo = l_c
            else:
                # stable in-chunk partition: variadic sort moving
                # all row data by a (side, position) key
                with scope("grow/partition/key_sort"):
                    ops = _sort_by_key(
                        key, tuple(blk_w[:, i] for i in range(NW)) + cols)
                lb = jnp.stack(ops[1:1 + NW], axis=1)
                lp = _unpack_pay(ops[1 + NW:1 + NW + NPAY])
                # rights [l_c, l_c+r_c) rotated to the block END
                r_lo = K - r_c
                s_r = lax.rem(l_c + r_c, jnp.asarray(K, jnp.int32))
                rb, rp = rot(lb, s_r), rot(lp, s_r)
                if track:
                    lo = ops[1 + NW + NPAY]
                    ro = rot(lo, s_r)
            # lefts [0, l_c) forward in place; rights packed
            # backward from the window end E = dst_base + cnt in the
            # other half: lane j of the right block lands at o_r + j,
            # so lanes [r_lo, r_lo + r_c) fill [E - r_off - r_c,
            # E - r_off). dynamic_slice CLAMPS an out-of-range offset
            # silently, so both writes lean on the PAD == K rows either
            # side of a half's n live rows (E <= the live rows' end):
            # o_r + K <= E + K with the rights at the wide block's
            # middle (and o_r >= dst_base + l_off), the same trailing
            # bound the LEFT write's src_base + l_off + K has;
            # o_r = E - r_off - K >= dst_base - K with the rights at
            # the sorted block's end (r_off <= cnt)
            o_r = dst_base + cnt - r_off - r_lo - r_c
            mr = (iota_c >= r_lo) & (iota_c < r_lo + r_c)
            with scope("grow/partition/gather"):
                bins2 = _bins_write(bins2, src_base + l_off, lb,
                                    0, l_c)
            with scope("grow/partition/payload"):
                pay2 = _pay_write(pay2, src_base + l_off, lp, ml)
            with scope("grow/partition/gather"):
                bins2 = _bins_write(bins2, o_r, rb, r_lo, r_lo + r_c)
            with scope("grow/partition/payload"):
                pay2 = _pay_write(pay2, o_r, rp, mr)
            if track:
                with scope("grow/partition/gather"):
                    ord2 = write(ord2, src_base + l_off, lo, ml)
                    ord2 = write(ord2, o_r, ro, mr)
            return (bins2, pay2, ord2, lazy_used,
                    l_off + l_c, r_off + r_c, nlib, nib)

        carry = (bins2, pay2, ord2, lazy_used, zero, zero, zero, zero)
        # what of a chunk no inner scope names: the go-left decision,
        # the counts, the loop
        with scope("grow/partition/route"):
            carry = lax.fori_loop(0, window_chunks(cnt), body, carry)
        (bins2, pay2, ord2, lazy_used, n_left, _,
         n_left_ib, n_ib) = carry

        # -- second streaming pass: histogram of the smaller child over
        # its NOW-CONTIGUOUS rows only. Histogram work drops from
        # Sum(parent) to Sum(min-child) rows per tree (~0.42x
        # empirically), which the one extra read of the small side's
        # rows does not come close to cancelling. The side is the
        # partition's own count where every device sees every row (the
        # reference's smaller-leaf choice, serial_tree_learner.cpp:
        # 473-520), the search-time ESTIMATE where rows are sharded
        # (docstring); the sibling follows by subtraction. --
        left_small = _left_is_smaller(n_left, cnt, est_left_small,
                                      rows_sharded)
        est_start = jnp.where(left_small, start, start + n_left)
        est_cnt = jnp.where(left_small, n_left, cnt - n_left)
        est_half = jnp.where(left_small, src, 1 - src)
        est_base = est_half * SEG + PAD + est_start

        def hist_body(c, carry):
            hist, nu = carry
            off = c * K
            h, valid = chunk_hist(bins2, pay2, est_base + off,
                                  est_cnt - off)
            hist = hist + h
            if cegb_lazy:
                blk_o = lax.dynamic_slice(ord2, (est_base + off,), (K,))
                blk_i = (blk_o & _IB_BIT) != 0
                rows = (blk_o & ~_IB_BIT).astype(jnp.int32)
                used_rows = jnp.take(lazy_used, rows,
                                     axis=0)          # [K, F]
                # lazy_used already acquired feature f during the
                # partition pass, so column f over-counts as "used"
                # — harmless: the caller zeroes est_nu[f] regardless
                # (do_split's est_nu_z)
                nu = nu + jnp.sum(
                    (valid & blk_i)[:, None] & ~used_rows,
                    axis=0).astype(dtype)
            return hist, nu

        with scope("grow/hist/build"):
            est_hist, est_nu = lax.fori_loop(
                0, window_chunks(est_cnt), hist_body,
                (acc0, jnp.zeros((F_orig,), dtype)))

        # exact global in-bag child counts replace the search-time
        # hessian-ratio estimates (SplitInner update_cnt,
        # serial_tree_learner.cpp:789-791)
        nl_ex = psum(n_left_ib)              # int32: the tree's record
        nr_ex = psum(n_ib - n_left_ib)
        est_hist, comm_ef = hist_psum_ef(est_hist, comm_ef)
        return (bins2, pay2, ord2, lazy_used, n_left, nl_ex, nr_ex,
                left_small, est_hist, est_nu, comm_ef)

    def window_hist(bins2, pay2, src, start, cnt):
        """Recompute one leaf's full histogram from its contiguous row
        window — the pool-miss path (the reference recomputes evicted
        histograms the same way, HistogramPool::Get on a miss).
        Out-of-bag rows carry zero payload (w folded into pay2), so no
        extra masking beyond the window tail is needed."""
        src_base = src * SEG + PAD + start
        acc0 = jnp.zeros((FB, B, C), jnp.int32 if quant else dtype)

        def body(c, acc):
            off = c * K
            return acc + chunk_hist(bins2, pay2, src_base + off,
                                    cnt - off)[0]

        with scope("grow/hist/build"):
            # runs on a histogram-pool miss only: listed, not counted
            with comms.reduction_site("pool_miss"):
                return hist_psum(lax.fori_loop(
                    0, window_chunks(cnt), body, acc0))

    # (the root's histogram and split search keep their own scopes:
    # innermost wins)
    with scope("grow/setup"):
        # the streamed copy of the bin matrix lives PACKED: u32 words of
        # pack_w bin columns each (u8 arrays carry a (4,1) sub-byte tiling
        # that taxes every dynamic slice / masked RMW ~2-4x)
        bins_pk = bins_rm if Fp == F \
            else jnp.pad(bins_rm, ((0, 0), (0, Fp - F)))
        if nibble_bins:
            nib = bins_pk.reshape(n, NW, 8).astype(jnp.uint32)
            bins_pk = sum(nib[:, :, k] << (4 * k) for k in range(8))
        else:
            bins_pk = lax.bitcast_convert_type(
                bins_pk.reshape(n, NW, pack_w), jnp.uint32)    # [n, NW]

        # ---- root ----
        # feature-parallel devices histogram only their own feature block
        root_rows = _local_hist_rows(bins_pk, jnp.asarray(0, jnp.int32),
                                     n) if fp else bins_rm
        total_ci = psum(jnp.sum(inbag, dtype=jnp.int32))    # exact
        total_c = total_ci.astype(dtype)
        comm_ef0 = jnp.zeros((Fsp if sharded else FB, B, C),
                             dtype) if use_ef else ()
        if quant:
            with comms.reduction_site("tree"):
                root_hist = hist_psum(hist_from_rows_int(root_rows, gw2_q, B,
                                                         hmethod))
            if sharded:
                # the GLOBAL feature-0 row lives on device 0's chunk only;
                # broadcast it (exact int32 psum of one contributor) and
                # sum the same bin sequence the gathered path sums
                row0 = _sums_psum(
                    jnp.where(dev_idx == 0, root_hist[0],
                              jnp.zeros_like(root_hist[0])), cfg.axis_name)
                sums = (row0.astype(dtype) * scale2[None, :]).sum(axis=0)
            else:
                sums = hist_f(root_hist)[0].sum(axis=0)  # row hits feature 0
            if vp:
                # voting keeps the cache local; the root tuple is global
                sums = _sums_psum(sums, cfg.axis_name)
            total_g, total_h = sums[0], sums[1]
        else:
            total_g = psum(jnp.sum(gw2[:, 0]))
            total_h = psum(jnp.sum(gw2[:, 1]))
            with comms.reduction_site("tree"):
                root_hist, comm_ef0 = hist_psum_ef(
                    hist_from_rows(root_rows, gw2, B, hmethod,
                                   cfg.hist_precision), comm_ef0)

        tree = _init_tree(L, B, dtype)
        tree = tree._replace(
            leaf_value=tree.leaf_value.at[0].set(
                leaf_output(total_g, total_h, p)),
            leaf_weight=tree.leaf_weight.at[0].set(total_h),
            leaf_count=tree.leaf_count.at[0].set(total_ci),
        )
        best = _BestSplits.init(L, B, dtype)
        root_mask = None if interaction_groups is None \
            else allowed_features(jnp.zeros((F_orig,), jnp.bool_))
        cegb_state = ()
        root_pen = None
        if cegb:
            coupled_used = coupled_used0
            if cegb_lazy:
                lazy_used = lazy_used0
                root_nu = jnp.sum(~lazy_used & inbag[:, None],
                                  axis=0).astype(dtype)               # [F]
            else:
                lazy_used = jnp.zeros((1, 1), jnp.bool_)
                root_nu = jnp.zeros((F_orig,), dtype)
            lazy_nu = jnp.zeros((L, F_orig), dtype).at[0].set(root_nu)
            cegb_state = (coupled_used, lazy_used, lazy_nu)
            root_pen = cegb_penalty(total_c, coupled_used, root_nu)
        mono_state = ()
        root_bounds = None
        if has_mono:
            leaf_min0 = jnp.full((L,), -jnp.inf, dtype)
            leaf_max0 = jnp.full((L,), jnp.inf, dtype)
            mono_state = (leaf_min0, leaf_max0)
            if intermediate:
                mono_state = mono_state + (jnp.zeros((L, L - 1), jnp.int8),)
            root_bounds = (leaf_min0[0], leaf_max0[0])
            if advanced:
                # per-leaf bin-space boxes [lo, hi) per feature; the root
                # covers everything
                box_lo0 = jnp.zeros((L, F_orig), jnp.int32)
                box_hi0 = jnp.full((L, F_orig), B, jnp.int32)
                mono_state = mono_state + (box_lo0, box_hi0)
                root_bounds = advanced_bounds(box_lo0, box_hi0,
                                              tree.leaf_value,
                                              tree.num_leaves,
                                              box_lo0[0], box_hi0[0])
        nmask_state = ()
        root_node_mask = None
        if use_bynode:
            root_node_mask = node_feature_mask(0)
            nmask_state = (jnp.zeros((L, F_orig), jnp.bool_)
                           .at[0].set(root_node_mask),)
            root_mask = root_node_mask if root_mask is None \
                else root_mask & root_node_mask
        # the root's "parent output" is its own unsmoothed output
        # (GetParentOutput, serial_tree_learner.cpp:1005-1012)
        root_out = tree.leaf_value[0]
        best = best.store(0, best_for(hist_f(root_hist), total_g, total_h,
                                      total_c, root_mask, root_pen,
                                      root_out, jnp.asarray(0, jnp.int32),
                                      root_bounds),
                          jnp.asarray(True))
        # histogram cache: full per-leaf [L, F, B, 2], or a bounded slot
        # pool [PS, F, B, 2] with recompute-on-miss (HistogramPool analog,
        # feature_histogram.hpp; budget from histogram_pool_size)
        pooled = 0 < cfg.hist_pool_slots < L
        PS = cfg.hist_pool_slots if pooled else L
        hists = jnp.zeros((PS, FH, B, 2),
                          jnp.int32 if quant else dtype).at[0].set(root_hist)
        pool_state = ()
        if pooled:
            pool_state = (
                jnp.full((L,), -1, jnp.int32).at[0].set(0),   # leaf2slot
                jnp.full((PS,), -1, jnp.int32).at[0].set(0),  # slot2leaf
                jnp.zeros((PS,), jnp.int32),                  # lru tick
            )
        pay0 = gw2_q if quant \
            else (gw2.astype(jnp.bfloat16) if bf16_pay else gw2)
        ord0 = (jnp.arange(n, dtype=jnp.uint32)
                | jnp.where(inbag, _IB_BIT, jnp.uint32(0))) if track \
            else jnp.zeros((2,), jnp.uint32)
        bins2_0 = jnp.pad(bins_pk, ((PAD, PAD + SEG), (0, 0)))
        state = _CompactState(
            tree=tree, best=best, hists=hists,
            # the wide partition stores the words FLAT (see wide_part)
            bins2=bins2_0.reshape(-1) if wide_part else bins2_0,
            # ... and the f32 payload PLANAR (see pay_planar)
            pay2=jnp.concatenate(
                [jnp.pad(pay0[:, c], (PAD, PAD + SEG)) for c in range(C)])
            if pay_planar else jnp.pad(pay0, ((PAD, PAD + SEG), (0, 0))),
            ord2=jnp.pad(ord0, (PAD, PAD + SEG)) if track else ord0,
            leaf_buf=jnp.zeros((L,), jnp.int32),
            leaf_begin=jnp.zeros((L,), jnp.int32),
            leaf_count=jnp.zeros((L,), jnp.int32).at[0].set(n),
            branch=jnp.zeros((L, F_orig), jnp.bool_),
            num_splits=jnp.asarray(0, jnp.int32),
            cegb=cegb_state, mono=mono_state, node_masks=nmask_state,
            pool=pool_state, comm_ef=comm_ef0,
            # the first split's leaf is 0 (only the root has a stored
            # candidate), so the prefetched parent is the root histogram
            pcache=(jnp.zeros((1,), hists.dtype) if pooled else root_hist))

    def depth_ok(d):
        if cfg.max_depth <= 0:
            return jnp.asarray(True)
        return d < cfg.max_depth

    def _leaf_mask_pen_bounds(tree, branch, cegb_st, mono_st, nmask_st,
                              l):
        """One leaf's (mask, penalty, bounds) under the CURRENT state —
        the per-leaf body shared by the pooled re-search."""
        mask_l = None
        if interaction_groups is not None:
            mask_l = allowed_features(branch[l])
        if use_bynode:
            nm = nmask_st[0][l]
            mask_l = nm if mask_l is None else mask_l & nm
        pen_l = None
        if cegb:
            coupled_used, _, lazy_nu = cegb_st
            pen_l = cegb_penalty(tree.leaf_count[l].astype(dtype),
                                 coupled_used, lazy_nu[l])
        bounds_l = None
        if has_mono:
            if advanced:
                bounds_l = advanced_bounds(mono_st[3], mono_st[4],
                                           tree.leaf_value,
                                           tree.num_leaves,
                                           mono_st[3][l], mono_st[4][l])
            else:
                bounds_l = (mono_st[0][l], mono_st[1][l])
        return mask_l, pen_l, bounds_l

    def _research_leafwise(tree, hists, branch, cegb_st, mono_st,
                           nmask_st, pool_ctx) -> _BestSplits:
        """Leaf-walking re-search (lax.fori_loop over leaf slots).

        Used (a) under the histogram pool: each leaf's histogram comes
        from its slot or a window recompute — the reference pool's
        recompute-on-miss (HistogramPool::Get, feature_histogram.hpp)
        feeding the stored-candidate patching consumers; and (b) under
        advanced monotone even unpooled: the per-leaf bound tensors are
        [L, F, B] each, so vmapping them over leaves would materialize
        O(L^2*F*B) intermediates (~GBs at 255 leaves x 28 x 256) where
        this walk peaks at O(L*F*B) like the reference's per-leaf
        traversal."""

        def body(l, best):
            if pool_ctx is not None:
                bins2, pay2, leaf_buf, lbegin, lcount, leaf2slot = \
                    pool_ctx
                slot = leaf2slot[l]
                # COLLECTIVE-IN-COND INVARIANT (data-parallel): the
                # miss branch's window_hist ends in hist_psum, i.e. a
                # collective inside lax.cond. This is deadlock-free
                # iff the predicate is bit-identical on every device —
                # which holds because leaf2slot is pool state derived
                # ONLY from the replicated tree/argmax sequence (the
                # hit branch's cached hists are likewise already
                # globally reduced). Never feed device-dependent
                # inputs into the pool bookkeeping: a divergent
                # predicate would hang all hosts, not raise. TPL010
                # holds this invariant at review time.
                # tpulint: replicated-cond leaf2slot is pool state derived only from the replicated tree/argmax sequence
                hist = lax.cond(
                    slot >= 0,
                    lambda: lax.dynamic_index_in_dim(
                        hists, jnp.maximum(slot, 0), keepdims=False),
                    lambda: window_hist(bins2, pay2, leaf_buf[l],
                                        lbegin[l], lcount[l]))
            else:
                hist = lax.dynamic_index_in_dim(hists, l,
                                                keepdims=False)
            hf = hist_f(hist)
            if sharded:
                # leaf totals from the GLOBAL feature-0 row (device
                # 0's chunk), broadcast with one [B, 2] psum so every
                # device sums the bit-identical bin sequence the
                # gathered path sums (hf[0] on a chunk is a different
                # feature per device — same total, different last-ulp)
                row0 = _sums_psum(
                    jnp.where(dev_idx == 0, hf[0], jnp.zeros_like(hf[0])),
                    cfg.axis_name)
                sums = row0.sum(axis=0)
            else:
                sums = hf[0].sum(axis=0)
            mask_l, pen_l, bounds_l = _leaf_mask_pen_bounds(
                tree, branch, cegb_st, mono_st, nmask_st, l)
            r = best_for(hf, sums[0], sums[1],
                         tree.leaf_count[l].astype(dtype),
                         mask_l, pen_l, tree.leaf_value[l],
                         tree.leaf_depth[l], bounds_l)
            active = (l < tree.num_leaves) \
                & depth_ok(tree.leaf_depth[l])
            return best.store(l, r, active)

        return lax.fori_loop(0, L, body, _BestSplits.init(L, B, dtype))

    def research_all(tree, hists, branch, cegb_st, mono_st, nmask_st,
                     pool_ctx=None) -> _BestSplits:
        """Re-search every leaf's best split from the cached histograms
        under the CURRENT penalties / interaction masks / monotone
        bounds. Exact replacement for the reference's stored-candidate
        patching (CEGB UpdateLeafBestSplits,
        cost_effective_gradient_boosting.hpp:100-124; intermediate
        monotone leaves_to_update, monotone_constraints.hpp:560+)."""
        if pooled or advanced:
            return _research_leafwise(tree, hists, branch, cegb_st,
                                      mono_st, nmask_st, pool_ctx)
        hf = jax.vmap(hist_f)(hists)              # [L, F, B, 2]
        if sharded:
            # global feature-0 rows via device 0 (see _research_leafwise)
            row0 = _sums_psum(
                jnp.where(dev_idx == 0, hf[:, 0],
                          jnp.zeros_like(hf[:, 0])), cfg.axis_name)
            sums = row0.sum(axis=1)               # [L, 2]
        else:
            sums = hf[:, 0].sum(axis=1)           # [L, 2]
        in_axes = [0, 0, 0, 0]
        leaf_cnt = tree.leaf_count.astype(dtype)
        args = [hf, sums[:, 0], sums[:, 1], leaf_cnt]
        masks = None if interaction_groups is None \
            else jax.vmap(allowed_features)(branch)
        if use_bynode:
            masks = nmask_st[0] if masks is None else masks & nmask_st[0]
        in_axes.append(None if masks is None else 0)
        args.append(masks)
        if cegb:
            coupled_used, _, lazy_nu = cegb_st
            pens = jax.vmap(cegb_penalty,
                            in_axes=(0, None, 0))(leaf_cnt,
                                                  coupled_used, lazy_nu)
        else:
            pens = None
        in_axes.append(None if pens is None else 0)
        args.append(pens)
        # per-leaf parent_output / depth / bounds
        in_axes.extend([0, 0])
        args.extend([tree.leaf_value, tree.leaf_depth])
        if has_mono:
            # (advanced never reaches here — it re-searches leaf-wise)
            in_axes.append((0, 0))
            args.append((mono_st[0], mono_st[1]))
        else:
            in_axes.append(None)
            args.append(None)
        r = jax.vmap(best_for, in_axes=tuple(in_axes))(*args)
        if cfg.max_depth > 0:
            allowed = tree.leaf_depth < cfg.max_depth
        else:
            allowed = jnp.ones((L,), jnp.bool_)
        # SplitResult and _BestSplits share field order; re-wrap so the
        # while-loop carry keeps a consistent pytree type
        return _BestSplits(jnp.where(allowed, r.gain, NEG_INF),
                           *tuple(r)[1:])

    def do_split(state: _CompactState,
                 leaf_override=None) -> _CompactState:
        (tree, best, hists, bins2, pay2, ord2, leaf_buf,
         lbegin, lcount, branch, ns, cegb_st, mono_st, nmask_st,
         pool_st, comm_ef, pcache) = state
        leaf = jnp.argmax(best.gain).astype(jnp.int32) \
            if leaf_override is None else leaf_override
        R = ns + 1
        start = lbegin[leaf]
        cnt = lcount[leaf]
        src = leaf_buf[leaf]
        f_split = best.feature[leaf]
        t_bin = best.threshold_bin[leaf]
        dl = best.default_left[leaf]
        isc = best.is_cat[leaf]
        cm = best.cat_mask[leaf]
        est_left_small = best.left_count[leaf] <= best.right_count[leaf]
        lazy_arr = cegb_st[1] if cegb else jnp.zeros((1, 1), jnp.bool_)

        # parent histogram BEFORE the partition reorders the window:
        # from the cache (full mode / pool hit) or recomputed from the
        # still-contiguous parent window (pool miss)
        if pooled:
            leaf2slot, slot2leaf, lru = pool_st
            slot_l = leaf2slot[leaf]
            # tpulint: replicated-cond leaf2slot derives only from the replicated tree/argmax sequence (see _research_leafwise)
            parent_hist = lax.cond(
                slot_l >= 0,
                lambda: lax.dynamic_index_in_dim(
                    hists, jnp.maximum(slot_l, 0), keepdims=False),
                lambda: window_hist(bins2, pay2, src, start, cnt))
        elif leaf_override is None:
            # the prefetched parent (see _CompactState.pcache): the
            # only read of `hists` in the main-loop body now happens
            # AFTER the child updates, so they alias in place
            parent_hist = pcache
        else:
            # forced splits run OUTSIDE the while loop (Python
            # unrolled), where the direct read costs one copy at most
            # M times
            parent_hist = hists[leaf]

        # -- partition the leaf's range (DataPartition::Split analog) +
        # child histogram, fused into one streaming pass --
        (bins2, pay2, ord2, lazy_arr, n_left, nl_i, nr_i, left_small,
         est_hist, est_nu, comm_ef) = part_apply(
            bins2, pay2, ord2, lazy_arr, src, start, cnt, f_split, t_bin,
            dl, isc, cm, est_left_small, comm_ef)
        # left child stays in the parent's half; right child was packed
        # into the opposite half
        leaf_buf = leaf_buf.at[R].set(1 - src)
        lbegin = lbegin.at[R].set(start + n_left)
        lcount = lcount.at[leaf].set(n_left).at[R].set(cnt - n_left)

        new_depth = tree.leaf_depth[leaf] + 1
        tree = _apply_split_to_tree(tree, best, leaf, R, ns, p,
                                    nl_i, nr_i)
        nl_ex, nr_ex = nl_i.astype(dtype), nr_i.astype(dtype)

        with scope("grow/hist/subtract"):
            other_hist = subtract_histogram(parent_hist, est_hist)
            left_hist = jnp.where(left_small, est_hist, other_hist)
            right_hist = jnp.where(left_small, other_hist, est_hist)
        if pooled:
            # store the children: the left child inherits the parent's
            # slot when cached; otherwise (and for the right child) the
            # least-recently-used slot is evicted (HistogramPool LRU)
            tick = R

            def alloc(leaf2slot, slot2leaf, lru, forbid, take):
                """Pick the LRU victim slot (skipping ``forbid``) and —
                only when ``take`` — unmap its previous leaf."""
                score = jnp.where(jnp.arange(PS) == forbid,
                                  jnp.int32(2 ** 30), lru)
                victim = jnp.argmin(score).astype(jnp.int32)
                old = slot2leaf[victim]
                oldc = jnp.clip(old, 0, L - 1)
                leaf2slot = leaf2slot.at[oldc].set(
                    jnp.where(take & (old >= 0), -1, leaf2slot[oldc]))
                return leaf2slot, victim

            leaf2slot, victim1 = alloc(leaf2slot, slot2leaf, lru,
                                       jnp.int32(-2), slot_l < 0)
            s_l = jnp.where(slot_l >= 0, slot_l, victim1)
            slot2leaf = slot2leaf.at[s_l].set(leaf)
            lru = lru.at[s_l].set(tick)
            leaf2slot, s_r = alloc(leaf2slot, slot2leaf, lru, s_l,
                                   jnp.asarray(True))
            slot2leaf = slot2leaf.at[s_r].set(R)
            lru = lru.at[s_r].set(tick)
            leaf2slot = leaf2slot.at[leaf].set(s_l).at[R].set(s_r)
            hists = hists.at[s_l].set(left_hist).at[s_r].set(right_hist)
            pool_st = (leaf2slot, slot2leaf, lru)
        else:
            with scope("grow/hist/subtract"):
                hists = hists.at[leaf].set(left_hist).at[R].set(
                    right_hist)

        # context for the pooled re-search paths (hist per leaf from
        # slot or window recompute)
        pool_ctx = (bins2, pay2, leaf_buf, lbegin, lcount,
                    pool_st[0]) if pooled else None

        # -- monotone output-bound entries (BasicLeafConstraints::Update /
        # IntermediateLeafConstraints::UpdateConstraintsWithOutputs) --
        wl_out = best.left_output[leaf]
        wr_out = best.right_output[leaf]
        bounds_l = bounds_r = None
        if has_mono:
            lmin, lmax = mono_st[0], mono_st[1]
            pmin, pmax = lmin[leaf], lmax[leaf]
            mc_f = monotone_constraints[f_split].astype(jnp.int32)
            is_num = ~isc
            inc = is_num & (mc_f > 0)
            dec = is_num & (mc_f < 0)
            if intermediate:
                val_left, val_right = wr_out, wl_out
            else:
                val_left = val_right = (wl_out + wr_out) * 0.5
            new_min_l = jnp.where(dec, jnp.maximum(pmin, val_left), pmin)
            new_max_l = jnp.where(inc, jnp.minimum(pmax, val_left), pmax)
            new_min_r = jnp.where(inc, jnp.maximum(pmin, val_right), pmin)
            new_max_r = jnp.where(dec, jnp.minimum(pmax, val_right), pmax)
            lmin = lmin.at[leaf].set(new_min_l).at[R].set(new_min_r)
            lmax = lmax.at[leaf].set(new_max_l).at[R].set(new_max_r)
            mono_st = (lmin, lmax) + mono_st[2:]
            if intermediate:
                anc = mono_st[2]
                anc = anc.at[R].set(anc[leaf])
                anc = anc.at[leaf, ns].set(1).at[R, ns].set(2)
                mono_st = (lmin, lmax, anc) + mono_st[3:]
            bounds_l = (new_min_l, new_max_l)
            bounds_r = (new_min_r, new_max_r)
            if advanced:
                # split the parent's bin-space box between the children
                # (categorical splits leave both boxes = parent's) and
                # compute each child's per-threshold bounds from the
                # post-split leaf set
                blo, bhi = mono_st[3], mono_st[4]
                fsel = jnp.arange(F_orig) == f_split
                cut_num = fsel & is_num
                l_hi = jnp.where(cut_num,
                                 jnp.minimum(bhi[leaf], t_bin + 1),
                                 bhi[leaf])
                r_lo = jnp.where(cut_num,
                                 jnp.maximum(blo[leaf], t_bin + 1),
                                 blo[leaf])
                blo = blo.at[R].set(r_lo)
                bhi = bhi.at[R].set(bhi[leaf])
                bhi = bhi.at[leaf].set(l_hi)
                mono_st = mono_st[:3] + (blo, bhi)
                bounds_l = advanced_bounds(blo, bhi, tree.leaf_value,
                                           tree.num_leaves,
                                           blo[leaf], bhi[leaf])
                bounds_r = advanced_bounds(blo, bhi, tree.leaf_value,
                                           tree.num_leaves,
                                           blo[R], bhi[R])

        # -- child best splits --
        can_go_deeper = depth_ok(new_depth)
        child_mask = None
        if interaction_groups is not None:
            nb = branch[leaf] | (jnp.arange(F_orig) == f_split)
            branch = branch.at[leaf].set(nb).at[R].set(nb)
            child_mask = allowed_features(nb)
        mask_l = mask_r = child_mask
        if use_bynode:
            nm_l = node_feature_mask(2 * ns + 1)
            nm_r = node_feature_mask(2 * ns + 2)
            nmask_st = (nmask_st[0].at[leaf].set(nm_l).at[R].set(nm_r),)
            mask_l = nm_l if child_mask is None else child_mask & nm_l
            mask_r = nm_r if child_mask is None else child_mask & nm_r
        pen_l = pen_r = None
        if cegb:
            coupled_used, _, lazy_nu = cegb_st
            first_use = ~coupled_used[f_split] & (pen_coupled[f_split] > 0)
            coupled_used = coupled_used | (jnp.arange(F_orig) == f_split)
            # parent rows acquired f_split during the partition pass
            # (before the hist/nu pass read lazy_used), so est_nu[f]
            # is post-acquisition garbage; zero it, and zero the
            # parent's column too so the children's counts follow by
            # subtraction on acquisition-consistent vectors
            est_nu_z = est_nu.at[f_split].set(0.0)
            parent_nu = lazy_nu[leaf].at[f_split].set(0.0)
            big_nu = jnp.maximum(parent_nu - est_nu_z, 0.0)
            left_nu = jnp.where(left_small, est_nu_z, big_nu)
            right_nu = jnp.where(left_small, big_nu, est_nu_z)
            lazy_nu = lazy_nu.at[leaf].set(left_nu).at[R].set(right_nu)
            cegb_st = (coupled_used, lazy_arr, lazy_nu)
            pen_l = cegb_penalty(nl_ex, coupled_used, left_nu)
            pen_r = cegb_penalty(nr_ex, coupled_used, right_nu)
        # both children search in ONE vmapped scan (halves the
        # per-split dispatch/fusion count inside the growth loop)
        def stack2(a, b):
            return jnp.stack([a, b])

        mask2 = None if mask_l is None else stack2(mask_l, mask_r)
        pen2 = None if pen_l is None else stack2(pen_l, pen_r)
        bounds2 = None if bounds_l is None else tuple(
            stack2(a, b) for a, b in zip(bounds_l, bounds_r))
        with scope("grow/split_scan"):
            r2 = jax.vmap(
                best_for,
                in_axes=(0, 0, 0, 0,
                         None if mask2 is None else 0,
                         None if pen2 is None else 0,
                         0, None,
                         None if bounds2 is None
                         else tuple(0 for _ in bounds2)))(
                stack2(hist_f(left_hist), hist_f(right_hist)),
                stack2(best.left_sum_g[leaf], best.right_sum_g[leaf]),
                stack2(best.left_sum_h[leaf], best.right_sum_h[leaf]),
                stack2(nl_ex, nr_ex), mask2, pen2,
                stack2(wl_out, wr_out), new_depth, bounds2)
        rl = jax.tree.map(lambda a: a[0], r2)
        rr = jax.tree.map(lambda a: a[1], r2)
        best = best.store(leaf, rl, can_go_deeper)
        best = best.store(R, rr, can_go_deeper)

        if intermediate:
            # refresh every leaf's bounds to the batch fixed point of
            # the reference's cross-leaf propagation
            # (GoUpToFindLeavesToUpdate): a leaf under a monotone
            # ancestor is bounded by the extreme CURRENT outputs of the
            # sibling subtree — then re-search all stored candidates.
            lmin, lmax, anc = mono_st[:3]
            v = tree.leaf_value
            active = jnp.arange(L) < tree.num_leaves
            node_mc = monotone_constraints[tree.split_feature] \
                .astype(jnp.int32)                          # [L-1]
            node_on = (jnp.arange(L - 1) < ns + 1) \
                & ~tree.split_is_cat & (node_mc != 0)
            in_l = (anc == 1) & active[:, None] & node_on[None, :]
            in_r = (anc == 2) & active[:, None] & node_on[None, :]
            inf_ = jnp.asarray(jnp.inf, dtype)
            lmax_sub = jnp.max(jnp.where(in_l, v[:, None], -inf_), axis=0)
            lmin_sub = jnp.min(jnp.where(in_l, v[:, None], inf_), axis=0)
            rmax_sub = jnp.max(jnp.where(in_r, v[:, None], -inf_), axis=0)
            rmin_sub = jnp.min(jnp.where(in_r, v[:, None], inf_), axis=0)
            inc_n = (node_mc > 0)[None, :]
            # leaf's max bound: right-subtree min (if left of an
            # increasing node) / left-subtree min (if right of a
            # decreasing node); min bound symmetric
            ub = jnp.minimum(
                jnp.min(jnp.where(in_l & inc_n, rmin_sub[None, :], inf_),
                        axis=1),
                jnp.min(jnp.where(in_r & ~inc_n, lmin_sub[None, :], inf_),
                        axis=1))
            lb = jnp.maximum(
                jnp.max(jnp.where(in_r & inc_n, lmax_sub[None, :], -inf_),
                        axis=1),
                jnp.max(jnp.where(in_l & ~inc_n, rmax_sub[None, :], -inf_),
                        axis=1))
            mono_st = (lb, ub, anc) + mono_st[3:]
            best = research_all(tree, hists, branch, cegb_st, mono_st,
                                nmask_st, pool_ctx)

        if cegb_coupled and not intermediate:
            # (when intermediate monotone is on, the unconditional
            # research_all above already re-searched under the updated
            # coupled_used — a second pass would be identical work)
            # First use of a coupled-penalized feature erases its penalty
            # everywhere, which can promote another leaf's non-best
            # candidate to best. The reference patches the stored
            # per-(leaf, feature) candidates (UpdateLeafBestSplits,
            # cost_effective_gradient_boosting.hpp:100-124); we hold the
            # per-leaf histograms in HBM, so an exact re-search of every
            # leaf under the updated penalty is the same result.
            # tpulint: replicated-cond first_use derives from the replicated best-split record on globally-reduced histograms
            best = lax.cond(
                first_use,
                lambda b: research_all(tree, hists, branch, cegb_st,
                                       mono_st, nmask_st, pool_ctx),
                lambda b: b, best)

        if pooled:
            new_pcache = pcache
        else:
            # prefetch the NEXT split's parent from the updated buffer
            # (the argmax here is exactly the next iteration's leaf
            # choice — best is final at this point)
            nl_next = jnp.argmax(best.gain).astype(jnp.int32)
            new_pcache = lax.dynamic_index_in_dim(hists, nl_next,
                                                  keepdims=False)
        return _CompactState(tree=tree, best=best, hists=hists,
                             bins2=bins2, pay2=pay2, ord2=ord2,
                             leaf_buf=leaf_buf,
                             leaf_begin=lbegin, leaf_count=lcount,
                             branch=branch, num_splits=ns + 1,
                             cegb=cegb_st, mono=mono_st,
                             node_masks=nmask_st, pool=pool_st,
                             comm_ef=comm_ef, pcache=new_pcache)

    def forced_result(hist, tc, f, t, p_out, bnds) -> SplitResult:
        """Fixed (feature, bin) split record from a leaf's histogram
        (SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:620 via
        GatherInfoForThresholdNumerical, feature_histogram.hpp:486).
        Missing values route right (default_left=False). ``tc`` is the
        leaf's exact count; child counts are hessian-ratio estimates
        like the regular search (feature_histogram.hpp:528)."""
        if sharded:
            # the GLOBAL feature-0 row lives on device 0's chunk only
            # (see _research_leafwise) — broadcast, then sum the same
            # bin sequence the gathered path sums
            row0 = _sums_psum(
                jnp.where(dev_idx == 0, hist[0], jnp.zeros_like(hist[0])),
                cfg.axis_name)
            totals = jnp.sum(row0, axis=0)
        else:
            totals = jnp.sum(hist[0], axis=0)      # every row hits feat 0
        tg, th = totals[0], totals[1]
        # the histogram COLUMN the forced feature lives in: its own
        # column when plain, its bundle column under EFB
        fcol = bundle_of[f] if bundled else f
        if fp or sharded:
            # the forced column's histogram lives on its owner device
            # only; route it to everyone with one [B, 2] psum
            own = (_fp_owner(fcol) == dev_idx) if fp else \
                (fcol >= f_start) & (fcol < f_start + Fl)
            lf = jnp.clip(fcol - f_start, 0, Fl - 1)
            h_loc = lax.dynamic_index_in_dim(hist, lf, keepdims=False)
            h = _sums_psum(jnp.where(own, h_loc, 0.0), cfg.axis_name)
        elif vp:
            # voting keeps per-device caches local; a forced (feature,
            # bin) needs the GLOBAL row — one [B, 2] psum
            h = _sums_psum(hist[fcol], cfg.axis_name)
            tg = _sums_psum(tg, cfg.axis_name)
            th = _sums_psum(th, cfg.axis_name)
        else:
            h = hist[fcol]                         # [B, 2]
        binsb = jnp.arange(B)
        nanb = feat_nan_bin[f]
        sel = (binsb <= t) & ~((binsb == nanb) & (nanb >= 0))
        left = jnp.sum(h * sel[:, None].astype(h.dtype), axis=0)
        if bundled:
            # multi-member reconstruction (FixHistogram algebra): the
            # member's right side for threshold t is its positions
            # [off+t, off+nb-2] — the NaN position (off+nanb-1) sits
            # inside and routes right, like the plain sel excluding
            # the NaN bin from the left
            off = offset_of[f]
            nb = feat_num_bins[f]
            rsel = (binsb >= off + t) & (binsb <= off + nb - 2)
            right_m = jnp.sum(h * rsel[:, None].astype(h.dtype),
                              axis=0)
            left_m = jnp.stack([tg, th]) - right_m
            left = jnp.where(bundle_is_direct[f], left, left_m)
        lg, lh = left[0], left[1]
        lc = jnp.round(lh * tc / jnp.maximum(th, 1e-15))
        rg, rh, rc = tg - lg, th - lh, tc - lc
        if smoothing or has_mono:
            wl = constrained_output(lg, lh, lc, p_out, bnds, p)
            wr = constrained_output(rg, rh, rc, p_out, bnds, p)
            # GatherInfo evaluates the parent at its stored output
            gain = gain_at_output(lg, lh, wl, p) \
                + gain_at_output(rg, rh, wr, p) \
                - gain_at_output(tg, th, p_out, p)
        else:
            wl = leaf_output(lg, lh, p)
            wr = leaf_output(rg, rh, p)
            gain = leaf_gain(lg, lh, p) + leaf_gain(rg, rh, p) \
                - leaf_gain(tg, th, p)
        false_ = jnp.asarray(False)
        return SplitResult(
            gain=gain.astype(dtype), feature=f, threshold_bin=t,
            default_left=false_, is_cat=false_,
            cat_mask=jnp.zeros((B,), jnp.bool_),
            left_sum_g=lg, left_sum_h=lh, left_count=lc,
            right_sum_g=rg, right_sum_h=rh, right_count=rc,
            left_output=wl, right_output=wr)

    def forced_step(state: _CompactState, ok, leaf, f, t):
        """One forced split. An invalid forced split aborts ALL
        remaining ones (abort_last_forced_split,
        serial_tree_learner.cpp:695-699), not just itself."""
        bnds = None if not has_mono \
            else (state.mono[0][leaf], state.mono[1][leaf])
        if pooled:
            slot = state.pool[0][leaf]
            # tpulint: replicated-cond leaf2slot derives only from the replicated tree/argmax sequence (see _research_leafwise)
            hist_l = lax.cond(
                slot >= 0,
                lambda: lax.dynamic_index_in_dim(
                    state.hists, jnp.maximum(slot, 0), keepdims=False),
                lambda: window_hist(state.bins2, state.pay2,
                                    state.leaf_buf[leaf],
                                    state.leaf_begin[leaf],
                                    state.leaf_count[leaf]))
        else:
            hist_l = state.hists[leaf]
        r = forced_result(hist_f(hist_l),
                          state.tree.leaf_count[leaf].astype(dtype), f, t,
                          state.tree.leaf_value[leaf], bnds)
        valid = ok & (r.left_count > 0) & (r.right_count > 0)
        forced_state = state._replace(best=state.best.store(leaf, r,
                                                            jnp.asarray(True)))
        # tpulint: replicated-cond `valid` derives from the forced-split record on globally-reduced histograms
        return lax.cond(valid,
                        lambda s: do_split(s, leaf_override=leaf),
                        lambda _: state, forced_state), valid

    M = 0
    if forced is not None:
        f_leaf, f_feat, f_bin = forced
        M = min(int(f_leaf.shape[0]), L - 1)
        forced_ok = jnp.asarray(True)
        with scope("grow/fixed"):
            for i in range(M):
                state, forced_ok = forced_step(state, forced_ok, f_leaf[i],
                                               f_feat[i], f_bin[i])

    # growth loop: a while_loop with the stop condition in cond_fn (the
    # reference's early break, serial_tree_learner.cpp:225) — unlike a
    # fori_loop of lax.conds, the body always does real work and XLA
    # aliases the carried buffers in place instead of copying them
    # through conditional branches.
    def can_grow(state: _CompactState):
        return (state.num_splits < L - 1) \
            & (jnp.max(state.best.gain) > 0.0)

    # everything of a split that no inner scope names is its fixed
    # cost: tree and leaf bookkeeping, masks, bounds, the loop itself
    with scope("grow/fixed"):
        state = lax.while_loop(can_grow, do_split, state)
    # once a tree, over every row: the leaf each row ended in (and,
    # quantized, the leaf outputs renewed from it)
    with scope("grow/row_leaf"):
        if bundled:
            # bundle columns can't be re-routed by the predictor (the tree
            # references ORIGINAL features); merge the per-leaf windows
            # (each living in one ping-pong half) into one coherent order
            # vector, then invert
            leaf_of_pos = _leaf_of_positions(state.leaf_begin,
                                             state.leaf_count, n, L)
            in_b1 = _leaf_values_at_positions(
                state.leaf_begin, state.leaf_count, state.leaf_buf, n) == 1
            order_m = jnp.where(in_b1, state.ord2[SEG + PAD: SEG + PAD + n],
                                state.ord2[PAD: PAD + n])
            order_ids = (order_m & ~_IB_BIT).astype(jnp.int32)
            row_leaf = _row_leaf_from_order(order_ids, leaf_of_pos)
        else:
            # re-route rows through the finished tree with the in-order
            # node sweep (ops/predict.py) instead of inverting ord2 with
            # two FULL-LENGTH variadic sorts: the sweep is nn sequential
            # [n] column selects, while an n-row bitonic sort moves
            # ~log^2(n) passes of row data through HBM — at 10.5M rows the
            # sorts dwarf the sweep. Routing semantics are identical to
            # chunk_goleft (same thresholds, NaN bins, cat masks).
            t = state.tree
            row_leaf = predict_leaf_binned(
                t.split_feature, t.threshold_bin, t.default_left,
                t.left_child, t.right_child, feat_nan_bin, bins_T,
                t.split_is_cat if has_cat else None,
                t.split_cat_mask if has_cat else None)
            # an ungrown tree has no internal node 0 to route through
            row_leaf = jnp.where(t.num_leaves > 1, row_leaf, 0)
        tree = state.tree
        if quant and cfg.renew_leaf:
            # RenewIntGradTreeOutput (gradient_discretizer.hpp): replace the
            # quantized leaf outputs with exact float sums per leaf.
            sg = psum(jax.ops.segment_sum(gw2[:, 0], row_leaf, num_segments=L))
            sh = psum(jax.ops.segment_sum(gw2[:, 1], row_leaf, num_segments=L))
            newv = leaf_output(sg, sh, p)
            lv = jnp.where(jnp.arange(L) < tree.num_leaves, newv,
                           tree.leaf_value)
            tree = tree._replace(leaf_value=lv)
    if cegb:
        return tree, row_leaf, state.cegb[0], state.cegb[1]
    return tree, row_leaf


grow_tree = jax.jit(grow_tree_impl, static_argnames=("cfg",))

# recompile telemetry + XLA cost attribution: growth is the hot path
# whose silent recompiles telemetry exists to catch (obs/jit_tracker.py);
# rebinding routes calls through the CostTracked wrapper so each first
# compile per signature emits a {"event": "compile"} record (obs/cost.py)
from ..obs import register_jit  # noqa: E402  (after grow_tree exists)

grow_tree = register_jit("ops/grow_tree", grow_tree, max_signatures=8)
