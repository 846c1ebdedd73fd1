"""GBDT boosting driver.

Re-design of /root/reference/src/boosting/gbdt.cpp (Init :53, Train :237,
TrainOneIter :344, UpdateScore :491, BoostFromAverage :319), dart.hpp,
rf.hpp, bagging.hpp and goss.hpp for TPU:

- The binned matrix, scores, gradients and the growth loop all live in HBM;
  only the finished (small) tree arrays cross back to the host per
  iteration (the CUDA learner's host<->device contract, SURVEY.md §3.5).
- Bagging and GOSS are expressed as a per-row *weight vector* instead of
  index compaction (bagging.hpp:30 builds bag_data_indices_): a row's
  weight multiplies (g, h) and is the unit counted by min_data_in_leaf, so
  out-of-bag rows simply weigh 0. This keeps every shape static and is
  mathematically identical to training on the subset.
- Sampling uses jax.random with a per-iteration folded key -> deterministic
  and device-resident (no host RNG transfer per iteration).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..obs import register_jit
from ..obs.scopes import scope
from ..obs.trace import FUSED_SCAN_PHASE
from ..objectives import Objective
from ..resilience.faults import FaultPlan, is_resource_exhausted
from ..ops.gather import gather_small
from ..ops.grow import (GrowConfig, TreeArrays, compact_plan, grow_tree,
                        grow_tree_impl, last_plan as grow_plan)
from ..ops.predict import predict_leaf_binned
from ..ops.renew import renew_leaf_values
from ..ops.split import SplitParams
from .tree import (CAT_MASK, DEFAULT_LEFT_MASK, Tree, pack_tree_device,
                   tree_from_arrays, unpack_tree_host)

__all__ = ["GBDTBooster", "resolve_hist_method", "resolve_scan_iters"]


def _count_tree(tree: Tree) -> None:
    """Counters ``tree_leaf_count`` / ``tree_splits`` / ``tree_splits_on_missing``
    / ``tree_splits_default_left`` of one tree that has reached the host
    (``tree_from_arrays``' arrays: nothing is fetched for this)."""
    from ..obs.registry import registry
    dt = np.asarray(tree.decision_type[:tree.num_nodes], np.uint8)
    on_missing = ((dt >> 2) & 3 != 0) & (dt & CAT_MASK == 0)
    registry.counter("tree_leaf_count").inc(int(tree.num_leaves))
    registry.counter("tree_splits").inc(len(dt))
    registry.counter("tree_splits_on_missing").inc(int(on_missing.sum()))
    registry.counter("tree_splits_default_left").inc(
        int((on_missing & (dt & DEFAULT_LEFT_MASK != 0)).sum()))


def _rows_streamed(tree: Tree) -> Dict[str, int]:
    """What the ``tree/fetch`` span is stamped with: the rows the tree's
    layers streamed, by the tree's own counts (its arrays are on the
    host). ``hist_rows``: the root's rows and, every split, the smaller
    child's (the child whose histogram is built; the sibling's is
    subtracted); ``partition_rows``: every split's parent's rows."""
    nn = tree.num_nodes
    parent = np.asarray(tree.internal_count[:nn], np.int64)

    def child_rows(child):      # a child >= 0 is a node, else leaf ~child
        child = np.asarray(child[:nn])
        return np.where(child >= 0, parent[np.maximum(child, 0)],
                        tree.leaf_count[np.where(child < 0, ~child, 0)])

    smaller = np.minimum(child_rows(tree.left_child),
                         child_rows(tree.right_child))
    return {"hist_rows": int(parent[0] if nn else tree.leaf_count[0])
            + int(smaller.sum()),
            "partition_rows": int(parent.sum())}


def _donate(*argnums: int):
    """Donation argnums for the fused step/scan wrappers.

    On CPU XLA ignores donation and warns per dispatch, so the
    wrappers normally declare none there — but ``lint --ir`` (TPL013,
    analysis/ircheck.py) must lower the SAME donation contract the TPU
    path runs with to verify input→output aliasing on a CPU-only CI
    host: LIGHTGBM_TPU_FORCE_DONATE=1 keeps the declaration on any
    backend (lowering only — nothing executes under the lint)."""
    import os

    if jax.default_backend() == "cpu" \
            and os.environ.get("LIGHTGBM_TPU_FORCE_DONATE") != "1":
        return ()
    return argnums


def resolve_scan_iters(requested) -> int:
    """Concrete scan-window budget from ``Config.fused_scan_iters``.

    Returns the max number of boosting iterations one fused
    ``lax.scan`` program may cover (1 = stay on the per-iteration
    fused path). Like the pallas flip (``resolve_hist_method``),
    ``auto`` stays at 1 until the Higgs-shaped
    ``benchmarks/fused_iter_bench.py`` scan arm measures an iters/sec
    win on chip — ``LIGHTGBM_TPU_AUTO_SCAN_ITERS=N`` opts auto in for
    that measurement, and ``LIGHTGBM_TPU_DISABLE_SCAN=1`` is the kill
    switch that pins everything (including explicit integers) back to
    per-iteration dispatch."""
    import os

    if os.environ.get("LIGHTGBM_TPU_DISABLE_SCAN") == "1":
        return 1
    if requested == "auto":
        env = os.environ.get("LIGHTGBM_TPU_AUTO_SCAN_ITERS", "")
        if env:
            try:
                # same [1, 1024] ceiling Config validation enforces
                # for an explicit fused_scan_iters (a 100k-slot scan
                # only grows trace time)
                return min(1024, max(1, int(env)))
            except ValueError:
                from ..utils.log import log_warning
                log_warning(
                    f"LIGHTGBM_TPU_AUTO_SCAN_ITERS={env!r} is not an "
                    "integer; keeping the per-iteration fused path")
        return 1
    return max(1, int(requested))


def resolve_hist_method(requested: str, backend: Optional[str] = None,
                        pallas_ok: Optional[bool] = None) -> str:
    """Concrete histogram method from the Config value.

    ``auto`` resolves to scatter on CPU and the MXU nibble matmul on
    accelerators. The Pallas kernel (ops/pallas_hist.py) is preferred
    by ``auto`` on TPU only when ``LIGHTGBM_TPU_AUTO_PALLAS=1``: the
    flip waits for a benchmark cell on each side of the choice
    (ROADMAP D2; docs/PALLAS.md) — its speed against mxu is not
    measured. An explicit ``hist_method="pallas"`` that cannot be
    honoured (Pallas not importable, or the
    ``LIGHTGBM_TPU_DISABLE_PALLAS`` kill switch) raises: a request for
    a specific kernel never resolves to a different program.
    """
    import os

    if backend is None:
        backend = jax.default_backend()

    def _pallas_ok():
        # probed lazily: the default scatter/mxu resolutions must not
        # pay the jax.experimental.pallas import at engine init
        nonlocal pallas_ok
        if pallas_ok is None:
            from ..ops.pallas_hist import pallas_available
            pallas_ok = pallas_available()
        return pallas_ok

    if requested == "pallas" and not _pallas_ok():
        from ..ops.pallas_hist import UNAVAILABLE_MSG
        raise RuntimeError(UNAVAILABLE_MSG)
    if requested != "auto":
        return requested
    if backend == "cpu":
        return "scatter"
    if os.environ.get("LIGHTGBM_TPU_AUTO_PALLAS") == "1" \
            and _pallas_ok():
        return "pallas"
    return "mxu"

# non-finite guard (resilience): flag bits and the clamp ceiling
# (well inside float32 range so downstream sums stay finite)
_NF_GRAD, _NF_HESS, _NF_LEAF = 1, 2, 4
_NF_CLAMP = 1e30


def _nf_clamp(a, lo, hi):
    """NaN -> 0, +/-Inf -> the finite bounds (nonfinite_policy=clamp)."""
    return jnp.clip(jnp.nan_to_num(a, nan=0.0, posinf=hi, neginf=lo),
                    lo, hi)


def _gh_flag_clamp(g, h, policy):
    """Gradient/hessian finiteness flag + clamp policy — pure jnp, so
    the eager guard and the fused step trace the SAME implementation
    (like _leaf_guard; any drift between the two paths would break
    their documented bit-equality)."""
    flag = (jnp.where(jnp.all(jnp.isfinite(g)), 0, _NF_GRAD)
            | jnp.where(jnp.all(jnp.isfinite(h)), 0, _NF_HESS)
            ).astype(jnp.int32)
    if policy == "clamp":
        g = _nf_clamp(g, -_NF_CLAMP, _NF_CLAMP)
        h = _nf_clamp(h, 0.0, _NF_CLAMP)
    return g, h, flag


def _leaf_value_guard(dev_tree, gh_flag, policy):
    """Fitted-leaf-value guard (pure jnp, shared verbatim by the eager
    path, the fused step and the scan body): extend the iteration flag
    with the leaf bit and apply the policy on device — clamp rewrites
    the leaf table, skip_tree demotes the tree to a no-op constant
    (the AsConstantTree path downstream)."""
    lv = dev_tree.leaf_value
    flag = gh_flag | jnp.where(jnp.all(jnp.isfinite(lv)), 0,
                               _NF_LEAF).astype(jnp.int32)
    if policy == "clamp":
        dev_tree = dev_tree._replace(
            leaf_value=_nf_clamp(lv, -_NF_CLAMP, _NF_CLAMP))
    elif policy == "skip_tree":
        ok = flag == 0
        dev_tree = dev_tree._replace(
            num_leaves=jnp.where(ok, dev_tree.num_leaves, 1),
            leaf_value=jnp.where(ok, lv, jnp.zeros_like(lv)))
    return dev_tree, flag


class _StepCtx(NamedTuple):
    """Static context of one fused boosting iteration — everything
    :func:`_fused_iter_step` needs beyond its traced operands. Built
    once per engine state (``GBDTBooster._step_ctx``) and closed over
    by BOTH the per-iteration jitted step and the multi-iteration scan
    body, so the two programs trace the identical ops by
    construction."""
    gcfg: GrowConfig
    K: int
    obj: object
    nf_policy: str
    quant: bool
    bynode: bool
    base_key: object
    bynode_key: object
    inj_grad: object      # fault-injection iteration arrays (or None):
    inj_hess: object      # traced as where(it == N) — zero recompiles


def _fused_iter_step(ctx: _StepCtx, score, it, shrink, row_w, fmask,
                     bins_T, fnb, fnan, label, weight, monotone,
                     feat_is_cat, igroups, forced, bundle):
    """One boosting iteration as pure traced ops: gradients -> guard ->
    K tree grows -> pack -> contrib -> score update. Returns
    ``(new_score, [(vec, cmask, num_leaves)] * K, flags[K])``. The
    per-iteration fused program jits a thin wrapper over this
    (``_get_fused_fn.step``) and the multi-iteration scan
    (``_get_scan_fn``) calls it per window slot — one implementation,
    every fused path."""
    obj, K = ctx.obj, ctx.K
    # device scopes (obs/scopes.py): metadata only, so that a trace's
    # ops can be put down to the iteration's phases
    with scope("boost/gradients"):
        g, h = obj.grad_hess(score if K > 1 else score[0], label, weight)
        if K == 1:
            g, h = g[None, :], h[None, :]
        if ctx.inj_grad is not None:
            g = jnp.where(jnp.any(it == ctx.inj_grad),
                          jnp.float32(jnp.nan), g)
        if ctx.inj_hess is not None:
            h = jnp.where(jnp.any(it == ctx.inj_hess),
                          jnp.float32(jnp.nan), h)
        # non-finite guard, fused into this one program via the same
        # pure-jnp helper the eager path uses: the isfinite reductions
        # cost a single pass; the resulting flag rides back with the
        # tree outputs and is checked one iteration late on the host
        # (no per-iteration device sync)
        g, h, gh_flag = _gh_flag_clamp(g, h, ctx.nf_policy)
    # identical key schedule to the eager path (fold_in is a pure
    # device op, so tracing it keeps streams bit-equal)
    qk_it = jax.random.fold_in(ctx.base_key, it) if ctx.quant else None
    nk_it = jax.random.fold_in(ctx.bynode_key, it) if ctx.bynode \
        else None
    new_score = score
    outs = []
    flags = []
    for k in range(K):
        qk = jax.random.fold_in(qk_it, k) if ctx.quant else None
        nk = jax.random.fold_in(nk_it, k) if ctx.bynode else None
        with scope("boost/grow"):
            dev_tree, row_leaf = grow_tree_impl(
                ctx.gcfg, bins_T, g[k], h[k], row_w, fmask, fnb, fnan,
                monotone, feat_is_cat, qk, igroups, forced, None, nk,
                bundle)
        with scope("boost/tree_pack"):
            dev_tree, flag_k = _leaf_value_guard(dev_tree, gh_flag,
                                                 ctx.nf_policy)
            vec, cmask = pack_tree_device(dev_tree)
        with scope("boost/score_update"):
            contrib = gather_small(dev_tree.leaf_value, row_leaf)
            # a no-growth tree is replaced by a constant at flush
            # (AsConstantTree): contribute nothing now
            contrib = jnp.where(dev_tree.num_leaves > 1, contrib, 0.0)
            new_score = new_score.at[k].add(contrib * shrink)
        outs.append((vec, cmask, dev_tree.num_leaves))
        flags.append(flag_k)
    return new_score, outs, jnp.stack(flags)


@jax.jit
def _tree_values_binned(split_feature, threshold_bin, default_left,
                        left_child, right_child, leaf_value,
                        feat_nan_bin, bins_T, is_cat=None, cat_masks=None):
    """Jitted per-row tree output over binned data (compiled once per
    (num_leaves, n) shape — trees are padded to the configured size)."""
    with scope("valid/score_update"):
        leaves = predict_leaf_binned(split_feature, threshold_bin,
                                     default_left, left_child, right_child,
                                     feat_nan_bin, bins_T, is_cat, cat_masks)
        # gather_small, not leaf_value[leaves]: XLA:TPU runs the
        # [n]-sized small-table gather one element at a time (its cost on
        # this chip: not measured) and valid-set scoring pays it every
        # iteration
        return gather_small(leaf_value, leaves)


@jax.jit
def _tree_leaves_binned(split_feature, threshold_bin, default_left,
                        left_child, right_child,
                        feat_nan_bin, bins_T, is_cat=None, cat_masks=None):
    return predict_leaf_binned(split_feature, threshold_bin, default_left,
                               left_child, right_child, feat_nan_bin,
                               bins_T, is_cat, cat_masks)


@jax.jit
def _linear_eval(const, coef, feats, nfeat, leaf_value, raw, leaves):
    from ..ops.linear import linear_leaf_values
    return linear_leaf_values(const, coef, feats, nfeat, leaf_value, raw,
                              leaves)


# recompile telemetry (obs/jit_tracker.py): a cache miss on any of these
# mid-training is a silent multi-second stall.
# Rebinding routes calls through the cost-attribution wrapper
# (obs/cost.py: one {"event": "compile"} record per first compile per
# signature)
_tree_values_binned = register_jit("gbdt/tree_values_binned",
                                   _tree_values_binned,
                                   max_signatures=8)
_tree_leaves_binned = register_jit("gbdt/tree_leaves_binned",
                                   _tree_leaves_binned,
                                   max_signatures=8)
_linear_eval = register_jit("gbdt/linear_eval", _linear_eval,
                            max_signatures=8)


class _ValidData:
    def __init__(self, dataset, score: jnp.ndarray, name: str):
        self.dataset = dataset
        self.score = score
        self.name = name


class GBDTBooster:
    """The boosting engine behind the public Booster (basic.py)."""

    def __init__(self, cfg: Config, train_set, objective: Optional[Objective],
                 num_model_per_iter: int = 1):
        self.cfg = cfg
        self.train_set = train_set
        self.objective = objective
        self.K = (objective.num_model_per_iteration
                  if objective is not None else num_model_per_iter)
        self._models_store: List[Tree] = []
        self._pending_dev: List[tuple] = []
        self._nl_async: List = []
        self.iter_ = 0
        # iterations contributed by an adopted init_model (the
        # reference's num_init_iteration): continued training adds
        # num_boost_round iterations ON TOP of these, and a
        # checkpoint-resumed continued run needs the offset to know
        # its true end iteration (engine.py, docs/PIPELINE.md)
        self.init_iteration = 0
        self.valid_sets: List[_ValidData] = []
        self._shrinkage = cfg.learning_rate

        # -- resilience state (resilience/): the non-finite guard
        # policy, the deterministic fault-injection plan (test harness;
        # inert without LIGHTGBM_TPU_FAULT_INJECT), guard flags in
        # flight from async device programs, and the fault event log
        # the telemetry recorder drains --
        self._nf_policy = cfg.nonfinite_policy
        self._fault_plan = FaultPlan.from_env()
        self._guard_async: List[tuple] = []
        self._fault_recent = False
        self._resume_stalled = False
        self._finished_natural = False
        self.fault_log: List[dict] = []

        ds = train_set
        self.n = ds.num_data()
        self.F = ds.num_features()
        # NOTE: the [F, n] device upload is deferred until after the
        # EFB bundling decision below — uploading first would pin the
        # full unbundled matrix in HBM alongside the bundled one
        self.bins_T = None
        self.feat_num_bins = ds.device_feat_num_bins()
        self.feat_nan_bin = ds.device_feat_nan_bin()
        self.feat_is_cat = ds.device_feat_is_cat()
        self.label = jnp.asarray(ds.get_label(), jnp.float32)
        w = ds.get_weight()
        self.weight = None if w is None else jnp.asarray(w, jnp.float32)
        mono = ds.monotone_array(cfg)
        self.monotone = None if mono is None else jnp.asarray(mono, jnp.int8)
        self.interaction_groups = self._parse_interaction_constraints(cfg)
        self.forced = self._load_forced_splits(cfg)
        self._init_cegb(cfg)

        # linear trees (LinearTreeLearner): fit leaf-wise linear models on
        # raw numerical values after growth
        self.raw = None
        if cfg.linear_tree:
            if self.monotone is not None:
                raise ValueError(
                    "linear_tree does not support monotone constraints "
                    "(reference config check)")
            rn = ds.raw_numeric()
            if rn is None:
                raise ValueError(
                    "linear_tree requires the Dataset to be constructed "
                    "with the linear_tree parameter (raw data retained)")
            self.raw = jnp.asarray(rn)

        # boost_from_average (gbdt.cpp:319). The average is folded into the
        # first iteration's trees as a leaf-value bias (TrainOneIter's
        # AddBias path) so saved models are self-contained.
        # rf: the prior is folded into EVERY tree (rf.hpp AddBias) and the
        # score is a running average; gbdt/dart: folded into the first
        # iteration's trees only.
        init_score = np.zeros((self.K,), np.float64)
        self._fold_bias = False
        if objective is not None and cfg.boost_from_average \
                and ds.get_init_score() is None:
            self._fold_bias = cfg.boosting != "rf"
            if hasattr(objective, "init_label_weights"):
                objective.init_label_weights(np.asarray(ds.get_label()),
                                             None if w is None
                                             else np.asarray(w))
            init_score = np.asarray(
                objective.boost_from_score(np.asarray(ds.get_label()),
                                           None if w is None
                                           else np.asarray(w)),
                np.float64).reshape(self.K)
        elif objective is not None and hasattr(objective,
                                               "init_label_weights"):
            objective.init_label_weights(np.asarray(ds.get_label()),
                                         None if w is None else np.asarray(w))
        self.init_score = init_score

        score0 = jnp.tile(jnp.asarray(init_score, jnp.float32)[:, None],
                          (1, self.n))
        user_init = ds.get_init_score()
        if user_init is not None:
            score0 = score0 + jnp.asarray(user_init, jnp.float32).reshape(
                self.K, self.n)
        self.score = score0

        hist_method = resolve_hist_method(cfg.hist_method)
        if hist_method == "pallas" and cfg.hist_precision != "default":
            # the multi-pass f32 emulation is MXU-path machinery; the
            # Pallas kernel always runs its single-pass f32-accumulate
            # numerics (docs/PALLAS.md) — say so instead of silently
            # ignoring the knob
            from ..utils.log import log_warning
            log_warning(
                f"hist_precision='{cfg.hist_precision}' applies to "
                "hist_method='mxu' only; the pallas kernel runs its "
                "single-pass f32-accumulation numerics (and an OOM "
                "degradation to mxu would re-enable the multi-pass "
                "emulation mid-run)")
        grower = cfg.grower
        if cfg.use_quantized_grad and grower != "compact":
            grower = "compact"  # quantized histograms are compact-only
        if self.interaction_groups is not None or self.forced is not None \
                or self.cegb_enabled:
            grower = "compact"  # per-leaf masks / forced splits need it
        if cfg.path_smooth > 0.0 or cfg.feature_fraction_bynode < 1.0 \
                or self.monotone is not None:
            # path smoothing, per-node column sampling and monotone
            # output-bound entries live on the compact grower
            grower = "compact"
        if grower == "masked" and self.n * cfg.num_leaves > 50_000_000:
            from ..utils.log import log_warning
            log_warning(
                "grower=masked rebuilds every histogram with a full-row "
                "pass: O(num_leaves * rows * features) per tree "
                f"(~{self.n * cfg.num_leaves / 1e9:.1f}B row-visits "
                "here). Use grower=compact (the default) for data of "
                "this size.")
        self.grow_cfg_extra = {}
        self.grow_cfg = GrowConfig(
            num_leaves=cfg.num_leaves,
            num_bins=ds.num_total_bins(),
            max_depth=cfg.max_depth,
            grower=grower,
            chunk=cfg.chunk_rows,
            hist_method=hist_method,
            hist_precision=cfg.hist_precision,
            quantized=cfg.use_quantized_grad,
            quant_bins=cfg.num_grad_quant_bins,
            renew_leaf=cfg.quant_train_renew_leaf,
            stochastic=cfg.stochastic_rounding,
            cegb=self.cegb_enabled,
            cegb_lazy=self.cegb_lazy,
            cegb_coupled=len(cfg.cegb_penalty_feature_coupled) > 0,
            cegb_tradeoff=cfg.cegb_tradeoff,
            cegb_split=cfg.cegb_penalty_split,
            monotone_method=cfg.monotone_constraints_method,
            bynode=cfg.feature_fraction_bynode,
            split=SplitParams(
                lambda_l1=cfg.lambda_l1,
                lambda_l2=cfg.lambda_l2,
                max_delta_step=cfg.max_delta_step,
                min_data_in_leaf=float(cfg.min_data_in_leaf),
                min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
                min_gain_to_split=cfg.min_gain_to_split,
                cat_smooth=cfg.cat_smooth,
                cat_l2=cfg.cat_l2,
                max_cat_threshold=cfg.max_cat_threshold,
                max_cat_to_onehot=cfg.max_cat_to_onehot,
                min_data_per_group=float(cfg.min_data_per_group),
                path_smooth=cfg.path_smooth,
                monotone_penalty=(cfg.monotone_penalty
                                  if self.monotone is not None else 0.0),
            ),
        )
        # -- Exclusive Feature Bundling (FeatureGroup / EFB,
        # feature_group.h:26): merge mutually-exclusive sparse features
        # into bundle columns so the bin matrix, the histogram work and
        # the per-leaf histogram cache all scale with #bundles ---------
        self.bundle = None
        self._bundle_dev = None
        # single source for the distributed dispatch decision — the
        # EFB gate below and the mesh setup further down must agree.
        # tree_learner="auto" resolves to a concrete mode inside the
        # dp_active block (it needs the post-bundle column count and
        # the world size; parallel/comms.py choose_parallel_mode).
        want_dp = (cfg.tree_learner in ("data", "feature", "voting",
                                        "auto")
                   or cfg.num_devices > 1)
        dp_active = want_dp and len(jax.devices()) > 1
        dp_mode = {"feature": "feature",
                   "voting": "voting"}.get(cfg.tree_learner, "data")
        # bundling is a dataset property that sits below the parallel
        # layer (feature_group.h:26): data-parallel shards bundle
        # columns by rows and psums their histograms; feature-parallel
        # windows/owns bundle columns like plain columns; voting runs
        # its ballot/election/exchange in bundle-column space.
        plain = (not cfg.linear_tree and grower == "compact"
                 # a locally-sharded dataset (distributed_dataset
                 # device residency on a pod) holds only this rank's
                 # rows — per-rank bundle decisions would diverge
                 and getattr(ds, "_local_row_offset", None) is None)
        if cfg.enable_bundle and plain:
            binfo = ds.bundles(cfg)
            if binfo is not None:
                self.bundle = binfo
                self._bundle_dev = (
                    jnp.asarray(binfo.bundle_of),
                    jnp.asarray(binfo.offset_of),
                    jnp.asarray(binfo.is_direct),
                    jnp.asarray(binfo.member_at),
                    jnp.asarray(binfo.tloc_at),
                    jnp.asarray(binfo.end_at),
                    jnp.asarray(binfo.nanpos_at),
                    jnp.asarray(binfo.nan_at))
                self.grow_cfg = self.grow_cfg._replace(
                    bundled=True, num_bins=binfo.num_positions)
        # per-row id/in-bag tracking through the partition is only
        # needed by bagging/GOSS (weight-0 rows), CEGB, or the bundled
        # merge; plain full-data training drops the ord2 sort column
        bag_active = cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0
            or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)
        goss_active = (cfg.data_sample_strategy == "goss"
                       or cfg.boosting == "goss")
        self.grow_cfg = self.grow_cfg._replace(track_rows=(
            bag_active or goss_active or self.cegb_enabled
            or self.bundle is not None))
        self._bag_active = bag_active
        self._goss_active = goss_active
        # fused-iteration fast path state (built lazily; see
        # _train_one_iter_fused)
        self._fused_fn = None
        self._fused_proto = None
        self._grow_plan = {}
        self._row_w_ones = None
        self._fmask_cached = None
        # multi-iteration scan state (docs/FUSED.md): compiled window
        # programs by (W, bag_live), the pending precomputed window,
        # the last committed iteration's window position (telemetry),
        # and the engine-driven lookahead horizon — 1 (scan off) until
        # the train() loop proves how far ahead the window may run
        # without a callback observing mid-window state
        self._scan_fns: Dict[tuple, Callable] = {}
        self._scan_pend: Optional[dict] = None
        self._scan_last: Optional[dict] = None
        self._scan_horizon = 1

        # only ONE training matrix ever reaches HBM: bundled when EFB
        # engaged, the plain [F, n] matrix otherwise. Materialization
        # is DEFERRED below the mesh decision so shard_residency=device
        # can lay each row shard directly into its NamedSharding mesh
        # slice (parallel/placement.py) without first pinning an
        # unsharded device copy — and free the host copy after upload.
        ncols = int(self.bundle.bins_bundled.shape[1]) \
            if self.bundle is not None else self.F

        # -- histogram cache budget (HistogramPool analog;
        # histogram_pool_size in MB, -1 = unlimited like the reference,
        # config.h:301). Slots sized by the post-bundle column count.
        # CEGB / intermediate monotone / forced splits are served by the
        # pooled re-search (recompute-on-miss), like the reference pool
        # serves all consumers. --
        if cfg.histogram_pool_size > 0 and grower == "compact":
            per_leaf = ncols * self.grow_cfg.num_bins * 2 * 4
            slots = int(cfg.histogram_pool_size * 2 ** 20 // per_leaf)
            slots = max(2, slots)
            if slots < cfg.num_leaves:
                self.grow_cfg = self.grow_cfg._replace(
                    hist_pool_slots=slots)

        # -- distributed setup: mesh instead of Network::Init ------------
        # (SURVEY.md §2.6: the socket/MPI linker layer disappears; rows
        # are sharded over a jax Mesh and XLA emits the collectives)
        self.mesh = None
        self._pad = 0
        self._grow_fn = None
        if dp_active and self.cegb_enabled:
            raise ValueError("CEGB is not supported with multi-device "
                             "training yet")
        if dp_active:
            from ..parallel import comms
            from ..parallel.data_parallel import make_dp_grow_fn
            from ..parallel.mesh import make_mesh, pad_rows
            self.mesh = make_mesh(cfg.num_devices)
            D = int(self.mesh.devices.size)
            mode = dp_mode
            if cfg.tree_learner == "auto":
                # payload-adaptive choice (ROADMAP item 2): re-derived
                # per tree from (F, B, rows, world, wire dtype) — all
                # static for a given training run, so the per-tree
                # evaluation constant-folds to one mode; it moves only
                # when the run's shape does (e.g. a reset_parameter
                # rebuild). Forced splits exclude voting before
                # costing (CEGB never reaches here: any multi-device
                # CEGB run raised above).
                mode = comms.choose_parallel_mode(
                    ncols, self.grow_cfg.num_bins, self.n, D,
                    cfg.hist_comm, cfg.top_k)
                if mode == "voting" and self.forced is not None:
                    mode = "data"
                if mode != "data" and self.grow_cfg.grower != "compact":
                    # feature/voting replicate rows and gate their
                    # reductions per-search — only the compact grower
                    # implements that; level raises and masked would
                    # psum D identical replicated histograms
                    mode = "data"
                from ..utils.log import log_info
                log_info(
                    f"tree_learner=auto -> {mode}-parallel "
                    f"(F={ncols}, B={self.grow_cfg.num_bins}, "
                    f"rows={self.n}, world={D}, "
                    f"hist_comm={cfg.hist_comm})")
            # quantized histogram wire (docs/COLLECTIVES.md): resolve
            # "auto" against the histogram payload the CHOSEN mode
            # actually reduces (voting moves the small elected buffer,
            # not the full [F, B, 2] histogram)
            wire = comms.resolve_hist_comm(
                cfg.hist_comm, ncols, self.grow_cfg.num_bins,
                mode, cfg.top_k)
            if cfg.use_quantized_grad or mode == "feature":
                # quantized-gradient training reduces exact int32
                # histograms and feature-parallel reduces no histogram
                # at all — the wire never quantizes (the grower pins
                # it via make_hist_psum_ef(quantize=False)); record
                # f32 so telemetry reports the wire actually used
                wire = "f32"
            self.grow_cfg = self.grow_cfg._replace(hist_comm=wire)
            if mode == "voting" and (self.forced is not None
                                     or self.cegb_enabled):
                raise ValueError(
                    "tree_learner=voting does not support forced splits "
                    "or CEGB (their gathers read the local histogram "
                    "cache as if it were global)")
            if mode == "voting" and self.monotone is not None \
                    and cfg.monotone_constraints_method != "basic":
                # intermediate's all-leaves re-search reads the LOCAL
                # histogram cache; the reference likewise forces basic
                # in distributed mode (config.cpp:443-446)
                from ..utils.log import log_warning
                log_warning(
                    "tree_learner=voting forces "
                    "monotone_constraints_method=basic")
                self.grow_cfg = self.grow_cfg._replace(
                    monotone_method="basic")
            # reduce-scatter sharded split search (docs/SHARDING.md):
            # data-parallel meshes only — feature/voting already shard
            # their searches; EFB-bundled matrices keep the gathered
            # search (grow_tree_impl would raise)
            ss = cfg.split_search
            if ss == "sharded" and (mode != "data"
                                    or self.bundle is not None):
                if self.bundle is not None and mode == "data":
                    from ..utils.log import log_warning
                    log_warning(
                        "split_search=sharded does not cover EFB-"
                        "bundled matrices yet; using the gathered "
                        "search")
                ss = "gathered"
            self.grow_cfg = self.grow_cfg._replace(
                parallel_mode=mode, voting_top_k=cfg.top_k,
                split_search=ss)
            # feature-parallel replicates rows; no shard padding needed
            self._pad = 0 if mode == "feature" else pad_rows(self.n, D)
            self._grow_fn = self._build_grow_fn()

        # -- training-matrix materialization + shard residency ---------
        # (parallel/placement.py, docs/SHARDING.md): "device" lays each
        # mesh slice's rows directly into its device and FREES the host
        # binned matrix afterwards — no host holds the global matrix;
        # "host" keeps the classic host copy + device upload. auto =
        # device only on accelerator meshes (CPU virtual-device worlds
        # keep host so eager consumers stay cheap).
        residency = cfg.shard_residency
        if residency == "auto":
            residency = ("device" if self.mesh is not None
                         and jax.default_backend() != "cpu" else "host")
        local_off = getattr(ds, "_local_row_offset", None)
        if local_off is not None:
            # distributed_dataset kept only this rank's binned shard —
            # the dataset is device-destined by construction
            residency = "device"
            if self.mesh is not None \
                    and self.grow_cfg.parallel_mode == "feature":
                from ..basic import LightGBMError
                raise LightGBMError(
                    "feature-parallel growth replicates the full row "
                    "set on every device, but this rank holds only its "
                    "binned shard (shard_residency=device kept the "
                    "allgather from running) — use tree_learner=data "
                    "or shard_residency=host for feature-parallel")
        if residency == "device" and self.mesh is not None \
                and self.grow_cfg.parallel_mode == "feature":
            # feature-parallel replicates rows on every device — there
            # is no mesh slice to own; keep the host path
            residency = "host"
        self._residency = residency
        host_mat = (self.bundle.bins_bundled if self.bundle is not None
                    else ds.host_bins())             # [n, C] row-major
        from ..parallel import placement
        if residency == "device":
            if self.mesh is not None:
                # per-device slices cut straight from the host rows —
                # the unsharded [C, n] device copy never exists
                if local_off is None:
                    self.bins_T = placement.place_rows(
                        self.mesh, host_mat.T, row_axis=1,
                        pad=self._pad)
                else:
                    plan = placement.ShardPlan(self.mesh,
                                               self.n + self._pad)
                    self.bins_T = plan.place(host_mat.T, row_axis=1,
                                             local_offset=int(local_off),
                                             exclusive_rows=True)
                placement.upload_barrier()
            else:
                self.bins_T = jnp.asarray(host_mat.T)
            ds.free_host_bins()
            if self.bundle is None:
                if not self._pad:
                    # the placed matrix doubles as the dataset's device
                    # view, so binned-traversal consumers (init_model
                    # preload, OOM score rebuild) keep working without
                    # a host copy; with row padding the shapes differ
                    # and those rare paths raise free_host_bins' clear
                    # error instead of silently mixing padded rows in
                    ds._device_bins = self.bins_T
            else:
                # EFB keeps its (post-bundle) host matrix for now —
                # the Dataset-level [n, F] copy (the larger one) is
                # freed above; docs/SHARDING.md records the gap
                placement.host_bytes_gauge(host_mat.nbytes)
        else:
            self.bins_T = jnp.asarray(host_mat.T) \
                if self.bundle is not None else ds.device_bins()
            if self._pad:
                self.bins_T = jnp.pad(self.bins_T,
                                      ((0, 0), (0, self._pad)))
            placement.host_bytes_gauge(host_mat.nbytes)

        # score matrix follows the residency (sharded checkpoint
        # save/restore goes through placement.fetch_global)
        self.score = self._place_score(self.score)
        # ... and so does the rest of the row state, once, here: label,
        # row weights and the no-bagging ones vector, row-sharded like
        # the matrix. Gradients are then born sharded (elementwise on
        # the score and the label), and no round pads and reshards
        # 4 bytes a row of each from device 0 (S4). With row padding
        # the [n] vectors cannot take the matrix's [n + pad] sharding,
        # so such a job keeps the per-round pad
        self._rows_placed = (residency == "device"
                             and self.mesh is not None and not self._pad)
        self._reduction_sites = None
        self._comm_nl: List = []
        if self._rows_placed:
            from ..parallel.mesh import shard_rows
            from ..utils.timer import timed
            with timed("train/place_rows", job=True,
                       attrs={"rows": self.n,
                              "devices": int(self.mesh.devices.size)}):
                self.label = shard_rows(
                    self.mesh, np.asarray(ds.get_label(), np.float32))
                if w is not None:
                    self.weight = shard_rows(
                        self.mesh, np.asarray(w, np.float32))
                self._row_w_ones = shard_rows(
                    self.mesh, np.ones((self.n,), np.float32))

        seed = cfg.seed if cfg.seed is not None else 0
        self._base_key = jax.random.PRNGKey(seed)
        self._init_keys_and_rngs(cfg)

    def _build_grow_fn(self):
        """Distributed grow fn from the CURRENT grow_cfg + capability
        flags — the single source for both engine init and
        reset_parameter rebuilds (the flag list must match the grow
        call's argument assembly in train_one_iter)."""
        from ..parallel.data_parallel import make_dp_grow_fn

        cfg = self.cfg
        self._reduction_sites = None      # re-read from the new trace
        return register_jit("parallel/dp_grow", make_dp_grow_fn(
            self.grow_cfg, self.mesh, self.monotone is not None,
            self.feat_is_cat is not None,
            cfg.use_quantized_grad and cfg.stochastic_rounding,
            self.interaction_groups is not None,
            self.forced is not None,
            self.grow_cfg.bynode < 1.0,
            has_bundle=self.bundle is not None), max_signatures=8)

    def _init_keys_and_rngs(self, cfg):
        # distinct stream for per-node column sampling (ColSampler's
        # feature_fraction_seed, col_sampler.hpp)
        self._bynode_key = jax.random.PRNGKey(cfg.feature_fraction_seed)
        self._feature_rng = np.random.RandomState(cfg.feature_fraction_seed)
        # DART state (dart.hpp)
        self._dart_rng = np.random.RandomState(cfg.drop_seed)
        self._tree_weights: List[float] = []  # per-model weight (DART/RF)

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host Tree objects. Training defers device->host tree
        materialization (per-iteration fetches would stall the device
        pipeline; the copies run async) — first access flushes the
        queue."""
        self._flush_pending()
        return self._models_store

    @models.setter
    def models(self, v) -> None:
        self._pending_dev = []
        self._nl_async = []
        self._guard_async = []
        self._fault_recent = False
        self._finished_natural = False
        # precomputed scan lookahead belongs to the replaced model;
        # callers (preload_models / checkpoint restore) install the
        # matching score right after, so no rebuild here
        self._scan_pend = None
        self._scan_last = None
        self._models_store = list(v)

    # ------------------------------------------------------------------
    # resilience: non-finite guard, OOM degradation, fault events
    # (docs/RESILIENCE.md)
    # ------------------------------------------------------------------
    def _record_fault(self, kind: str, iteration: int, action: str,
                      detail: str) -> None:
        """Append one fault event to this booster's ``fault_log``
        (drained into the telemetry JSONL stream by obs/recorder.py)
        via the shared writer in resilience/faults.py — one schema,
        one cap, one registry counter for both the per-engine and the
        process-level logs."""
        from ..resilience.faults import append_fault_event
        append_fault_event(self.fault_log, kind, iteration, action,
                           detail)

    def _gh_guard(self, it: int, grad, hess):
        """Eager-path gradient/hessian guard: fault injection, one
        fused finiteness reduction -> flag bits, and the clamp policy
        applied in place. The fused fast path traces the identical ops
        inside its single program (_get_fused_fn)."""
        if self._fault_plan.fires("nan_grad", it):
            grad = jnp.full_like(grad, jnp.nan)
        if self._fault_plan.fires("nan_hess", it):
            hess = jnp.full_like(hess, jnp.nan)
        return _gh_flag_clamp(grad, hess, self._nf_policy)

    def _leaf_guard(self, dev_tree, gh_flag):
        """Fitted-leaf-value guard — delegates to the module-level
        pure-jnp :func:`_leaf_value_guard` so the eager path, the fused
        step and the scan body apply the one implementation."""
        return _leaf_value_guard(dev_tree, gh_flag, self._nf_policy)

    # tpulint: hot
    def _push_guard_flags(self, it: int, flags) -> None:
        """Queue a guard flag for the one-iteration-late async check
        (same non-stalling contract as the _nl_async tree queue)."""
        try:
            flags.copy_to_host_async()
        except AttributeError:  # non-jax arrays (tests/cpu)
            pass
        self._guard_async.append((it, flags))

    def _apply_guard_flag(self, it: int, flag: int) -> None:
        """Record + enforce the configured policy for one iteration's
        non-finite guard flag."""
        if not flag:
            return
        kinds = [name for bit, name in ((_NF_GRAD, "gradients"),
                                        (_NF_HESS, "hessians"),
                                        (_NF_LEAF, "leaf values"))
                 if flag & bit]
        detail = "non-finite " + ", ".join(kinds)
        self._record_fault("nonfinite", it, self._nf_policy, detail)
        if self._nf_policy == "raise":
            from ..basic import LightGBMError
            raise LightGBMError(
                f"{detail} detected at iteration {it} "
                "(nonfinite_policy=raise; use skip_tree or clamp to "
                "train through transient numerical faults)")

    # tpulint: hot
    def _drain_guard_flags(self) -> bool:
        """Resolve guard flags from previous async programs. A fired
        fault also sets the STICKY ``_fault_recent`` marker: callers
        other than the train step drain too (checkpoint writes, the
        end-of-training flush), and the next train step must still know
        not to interpret a 1-leaf tree in ``_nl_async`` as natural
        end-of-training — skip_tree demotions look identical to
        no-growth. The train step clears the marker when it consumes
        the matching ``_nl_async`` entries."""
        fired = False
        pending, self._guard_async = self._guard_async, []
        for it, flags in pending:
            fl = int(np.bitwise_or.reduce(
                np.atleast_1d(np.asarray(flags)).ravel()))
            if fl:
                fired = True
                self._apply_guard_flag(it, fl)
        if fired:
            self._fault_recent = True
        return fired

    def finish_faults(self) -> None:
        """Drain guard flags still in flight after the final iteration
        (the fused path checks one iteration late); called by the train
        loop before returning the booster."""
        self._drain_guard_flags()
        self._drain_reduction_counts()

    def _drain_reduction_counts(self) -> None:
        """Count the reductions of the mesh path's finished trees whose
        leaf counts have reached the host (their async copies started a
        round ago, like ``_nl_async``'s)."""
        if self._comm_nl:
            pending, self._comm_nl = self._comm_nl, []
            for nl in pending:
                self._count_reductions(int(np.asarray(nl)))

    def _count_reductions(self, num_leaves: int) -> None:
        """``hist_reductions`` / ``hist_wire_bytes{wire}`` for one
        execution of the grow program that ended with ``num_leaves``
        leaves: each histogram reduction site the program was traced
        with (parallel/comms.py ``traced_reductions``) times how often
        it ran — once a tree, once a split (``num_leaves - 1``), or,
        the level grower's scatter batch, once a level (a balanced
        tree's: the one site whose trips the leaf count does not give
        exactly). A pool-miss recompute is traced but not counted."""
        if not self._reduction_sites:
            return
        import math
        from ..obs.registry import registry
        splits = max(int(num_leaves) - 1, 0)
        runs = {"tree": 1, "split": splits,
                "level": math.ceil(math.log2(splits + 1))}
        for per, wire, nbytes in self._reduction_sites:
            k = runs.get(per, 0)
            if k:
                registry.counter("hist_reductions").inc(k)
                registry.counter("hist_wire_bytes", wire=wire).inc(
                    k * nbytes)

    def _run_with_oom_degrade(self, thunk, what: str):
        """Run a grow/fused dispatch with graceful OOM degradation:
        on RESOURCE_EXHAUSTED, downgrade the histogram strategy
        (Pallas kernel -> MXU matmul -> scatter, then histogram-pool
        halving), rebuild the affected jitted programs and retry;
        re-raise as a clear LightGBMError once nothing is left to
        shed."""
        rungs = 0
        while True:
            try:
                self._fault_plan.maybe_oom(self.iter_)
                return thunk()
            except Exception as e:
                if not is_resource_exhausted(e):
                    raise
                # under a mesh one rung, then the error: every rung is a
                # recompile of the sharded grower (minutes at a chip's
                # fill), and an allocation no rung can fit took 1,354 s
                # to say so on one chip (ROADMAP S10)
                spent = self.mesh is not None and rungs >= 1
                if spent or not self._degrade_after_oom(e, what):
                    from ..basic import LightGBMError
                    raise LightGBMError(
                        f"device RESOURCE_EXHAUSTED in {what} at "
                        f"iteration {self.iter_} and no degradation "
                        f"left to try"
                        + (" under a mesh (one rung)" if spent else "")
                        + f": {e}") from e
                rungs += 1

    def _degrade_after_oom(self, exc, what: str) -> bool:
        """Apply one degradation step; False when exhausted."""
        gcfg = self.grow_cfg
        if gcfg.hist_method == "pallas":
            # first rung of the ladder: shed the VMEM-resident kernel
            # (its one-hot scratch block is the newest allocation) and
            # fall back to the XLA-generated MXU path
            self.grow_cfg = gcfg._replace(hist_method="mxu")
            action = "hist_method pallas -> mxu"
        elif gcfg.hist_method == "mxu":
            self.grow_cfg = gcfg._replace(hist_method="scatter")
            action = "hist_method mxu -> scatter"
        else:
            cur = gcfg.hist_pool_slots if gcfg.hist_pool_slots > 0 \
                else gcfg.num_leaves
            slots = max(2, cur // 2)
            if slots >= cur:
                return False
            self.grow_cfg = gcfg._replace(hist_pool_slots=slots)
            action = f"histogram pool -> {slots} slots"
        # drop every cached program that baked the old grow_cfg in
        self._fused_fn = None
        self._fused_proto = None
        self._scan_fns = {}
        if self.mesh is not None and self._grow_fn is not None:
            self._grow_fn = self._build_grow_fn()
        detail = f"RESOURCE_EXHAUSTED in {what}; retrying after downgrade"
        # the fused program DONATES the score buffer (donate_argnums):
        # a real mid-execution OOM on TPU/GPU leaves self.score deleted
        # and the retry would die on "Array has been deleted" instead
        # of the degraded program. Rebuild the score from the
        # materialized trees — last-ulp different from the incremental
        # accumulation (bit-exact resume vs an uninterrupted run is
        # forfeited past this point, which an OOM'd run already is).
        if getattr(self.score, "is_deleted", lambda: False)():
            self.score = self._place_score(
                self._score_dataset_binned(self.train_set))
            detail += "; score buffer was donated to the failed " \
                      "dispatch — rebuilt from trees"
        # the scan program donates the bagging carry too: a consumed
        # cache is dropped and re-drawn at the next refresh check
        if self._cached_bag is not None and getattr(
                self._cached_bag, "is_deleted", lambda: False)():
            self._cached_bag = None
        self._record_fault("oom", self.iter_, action, detail)
        return True

    def _flush_pending(self) -> None:
        if not self._pending_dev:
            return
        pending, self._pending_dev = self._pending_dev, []
        mappers = self.train_set.mappers
        used = self.train_set.used_feature_indices()
        for vec, cmask, proto, shrink, bias in pending:
            host = unpack_tree_host(vec, cmask, proto)
            tree = tree_from_arrays(host, mappers, used)
            _count_tree(tree)
            if int(host.num_leaves) <= 1:
                # AsConstantTree (gbdt.cpp): a no-growth tree keeps only
                # the folded bias, unshrunk
                tree.leaf_value[:] = bias
            else:
                tree.apply_shrinkage(shrink)
                if bias:
                    tree.leaf_value = tree.leaf_value + bias
                    tree.internal_value = tree.internal_value + bias
            self._models_store.append(tree)

    def telemetry_tree_stats(self) -> Optional[Dict[str, float]]:
        """Leaves grown + split-gain sum of the LAST iteration's trees,
        for the telemetry recorder (obs/recorder.py). Reads the pending
        async device copies when trees are deferred — a small host fetch
        that only happens with telemetry active; the hot path never
        calls this. Returns None before the first iteration."""
        if self.iter_ <= 0:
            return None
        K = self.K
        leaves = 0
        gain = 0.0
        if len(self._pending_dev) >= K:
            for vec, cmask, proto, _, _ in self._pending_dev[-K:]:
                host = unpack_tree_host(np.asarray(vec), cmask, proto)
                nl = int(host.num_leaves)
                leaves += nl
                gain += float(np.sum(
                    np.asarray(host.split_gain)[: max(nl - 1, 0)]))
        elif len(self._models_store) >= K:
            for tree in self._models_store[-K:]:
                nl = int(tree.num_leaves)
                leaves += nl
                gain += float(np.sum(
                    np.asarray(tree.split_gain)[: max(nl - 1, 0)]))
        else:
            return None
        return {"trees": K, "leaves": leaves, "split_gain_sum": gain}

    def telemetry_comm_stats(self,
                             leaves: Optional[int] = None
                             ) -> Optional[Dict[str, object]]:
        """Per-iteration collective-payload accounting for the
        telemetry recorder (obs/recorder.py): bytes MODELED from the
        dtype-aware payload model (parallel/comms.py — the same model
        ``dryrun_multichip`` validates against the lowered StableHLO),
        not a wire measurement: one histogram reduction per split plus
        the root, so reductions == leaves grown — except the level
        grower's scatter path, which reduces the whole ``[L, F, B, 2]``
        level batch once per frontier level (modeled as ~log2 levels of
        a balanced tree, x L slots each). None when training is
        single-device (no collectives). ``leaves`` lets the recorder
        reuse the tree stats it already fetched; defaults to the
        num_leaves budget."""
        if self.mesh is None:
            return None
        from ..parallel import comms
        g = self.grow_cfg
        ncols = int(self.bins_T.shape[0])
        per_reduction = comms.payload_bytes(
            g.parallel_mode, ncols, g.num_bins, g.hist_comm,
            g.voting_top_k)
        if leaves is None:
            leaves = self.cfg.num_leaves * self.K
        if g.grower == "level" and g.hist_method == "scatter" \
                and g.parallel_mode == "data":
            import math
            per_tree = max(int(leaves) // max(self.K, 1), 2)
            levels = max(1, math.ceil(math.log2(per_tree)))
            n_reductions = self.K * levels * self.cfg.num_leaves
        else:
            n_reductions = int(leaves)
        world = int(self.mesh.devices.size)
        # the comm model's reduce-scatter arm: what each device
        # RECEIVES after the reduce phase (full broadcast when
        # gathered, 1/D chunk + O(D) SplitInfo records when sharded)
        post = comms.post_reduction_bytes(
            g.parallel_mode, ncols, g.num_bins, world, g.split_search,
            g.hist_comm, g.voting_top_k)
        return {
            "payload_bytes": int(per_reduction) * n_reductions,
            "post_reduction_bytes": int(post) * n_reductions,
            "hist_comm": g.hist_comm,
            "parallel_mode": g.parallel_mode,
            "split_search": g.split_search,
            "world": world,
        }

    def preload_models(self, trees: List[Tree],
                       score: Optional[np.ndarray] = None) -> None:
        """Continue training from an existing model (the reference's
        init_model / num_init_iteration path, gbdt.cpp Init +
        boosting.h:307): adopt the trees and rebuild the train score by
        binned traversal. boost_from_average stays un-refolded because
        iteration indices continue past 0.

        ``score``: install this [K, n] raw-score matrix verbatim
        instead of re-traversing the trees — the checkpoint-resume path
        (resilience/checkpoint.py) uses it because the incrementally
        accumulated f32 score and a fresh traversal can differ in the
        last ulp, which would break bit-exact resume."""
        self.models = list(trees)
        self._tree_weights = [1.0] * len(self.models)
        self.iter_ = len(self.models) // self.K
        if score is not None:
            self.score = self._place_score(
                np.asarray(score, np.float32).reshape(self.K, self.n))
        else:
            self.score = self._place_score(
                self._score_dataset_binned(self.train_set))

    def _place_score(self, score):
        """Install a [K, n] raw-score matrix per the shard residency:
        column-sharded over the mesh's data axis under device
        residency (a single-controller mesh — every eager consumer
        stays valid; the checkpoint layer saves/restores it through
        placement.fetch_global with per-shard fingerprints), a plain
        device array otherwise."""
        if getattr(self, "_residency", "host") != "device" \
                or self.mesh is None:
            return jnp.asarray(score)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(
            jnp.asarray(score),
            NamedSharding(self.mesh, P(None, self.mesh.axis_names[0])))

    # ------------------------------------------------------------------
    def add_valid(self, dataset, name: str) -> None:
        score = self._score_dataset_binned(dataset)
        self.valid_sets.append(_ValidData(dataset, score, name))

    def _score_dataset_binned(self, dataset) -> jnp.ndarray:
        nv = dataset.num_data()
        is_rf = self.cfg.boosting == "rf"
        if self._fold_bias or is_rf:
            # bias lives inside tree leaf values (first iteration's trees
            # for gbdt/dart; every tree for rf)
            score = jnp.zeros((self.K, nv), jnp.float32)
        else:
            score = jnp.tile(jnp.asarray(self.init_score,
                                         jnp.float32)[:, None], (1, nv))
        ui = dataset.get_init_score()
        if ui is not None:
            score = score + jnp.asarray(ui, jnp.float32).reshape(self.K, nv)
        for i, tree in enumerate(self.models):
            k = i % self.K
            score = score.at[k].add(self._predict_tree_binned_host(
                tree, dataset))
        if is_rf and self.iter_ > 0:
            # rf scores are the running average of unscaled tree outputs
            score = score / self.iter_
        return score

    def _binned_node_arrays(self, tree: Tree):
        """Per-node (threshold_bin, is_cat, cat_bin_mask) in the train
        set's bin space. Numerical nodes loaded from a model file map the
        real threshold onto the current binning; categorical nodes
        reconstruct exact bin membership from the category bitset
        (the inverse of tree_from_arrays' bitset emission). Cached on the
        tree — node structure is immutable after growth."""
        cached = getattr(tree, "_binned_cache", None)
        if cached is not None and cached[0] is self.train_set:
            return cached[1]
        inner = self.train_set.inner_feature_index(tree.split_feature)
        nn = tree.num_nodes
        B = int(self.grow_cfg.num_bins)
        tb = np.zeros(nn, np.int32)
        isc = np.zeros(nn, bool)
        cmask = np.zeros((nn, B), bool)
        for i in range(nn):
            m = self.train_set.mappers[inner[i]]
            if tree.is_categorical_node(i):
                isc[i] = True
                nb = min(len(m.bin_to_cat), B)
                for b in range(nb):
                    cmask[i, b] = tree._cat_decision(
                        i, float(m.bin_to_cat[b]))
            elif tree.threshold_bin[i] >= 0:
                tb[i] = tree.threshold_bin[i]
            else:
                tb[i] = int(np.searchsorted(m.upper_bounds,
                                            tree.threshold[i], side="left"))
        out = (tb, isc, cmask)
        tree._binned_cache = (self.train_set, out)
        return out

    def _add_tree_to_valid_scores(self, tree: Tree, k: int) -> None:
        """The round's new tree added to every validation set's score:
        the host tree's node arrays go back to the device and the tree
        is routed over each resident binned table."""
        from ..obs.registry import registry
        from ..utils.timer import timed
        with timed("valid/score_update"):
            for v in self.valid_sets:
                v.score = v.score.at[k].add(
                    self._predict_tree_binned_host(tree, v.dataset))
                registry.counter("valid_rows_scored").inc(
                    int(v.score.shape[1]))

    def _predict_tree_binned_host(self, tree: Tree,
                                  dataset) -> jnp.ndarray:
        bins_T = dataset.device_bins()
        if tree.num_leaves <= 1:
            base = float(tree.leaf_const[0]) if tree.is_linear \
                and getattr(tree, "leaf_const", None) is not None \
                else float(tree.leaf_value[0])
            return jnp.full((bins_T.shape[1],), base, jnp.float32)
        # map real feature index back to inner (used-feature) index
        inner = self.train_set.inner_feature_index(tree.split_feature)
        tb, isc, cmask = self._binned_node_arrays(tree)
        # pad to the configured num_leaves so the jitted traversal
        # compiles once per dataset, not once per tree
        L = max(self.cfg.num_leaves, tree.num_leaves)
        nn = L - 1

        def pad(a, size, fill, dt):
            out = np.full((size,), fill, dt)
            out[: len(a)] = a
            return out

        if self.feat_is_cat is not None:
            B = cmask.shape[1]
            cm_pad = np.zeros((nn, B), bool)
            cm_pad[: len(cmask)] = cmask
            cat_args = (jnp.asarray(pad(isc, nn, False, bool)),
                        jnp.asarray(cm_pad))
        else:
            cat_args = (None, None)
        node_args = (
            jnp.asarray(pad(inner, nn, 0, np.int32)),
            jnp.asarray(pad(tb, nn, 0, np.int32)),
            jnp.asarray(pad((tree.decision_type & 2) != 0, nn, False, bool)),
            jnp.asarray(pad(tree.left_child, nn, -1, np.int32)),
            jnp.asarray(pad(tree.right_child, nn, -1, np.int32)))
        if tree.is_linear and getattr(tree, "leaf_const", None) is not None:
            leaves = _tree_leaves_binned(*node_args, self.feat_nan_bin,
                                         bins_T, *cat_args)
            return self._linear_values_binned(tree, dataset, leaves)
        return _tree_values_binned(
            *node_args,
            jnp.asarray(pad(tree.leaf_value, L, 0.0, np.float32)),
            self.feat_nan_bin, bins_T, *cat_args)

    def _init_cegb(self, cfg) -> None:
        """CEGB state (cost_effective_gradient_boosting.hpp IsEnable):
        model-level feature-use flags and per-(row, feature) acquisition
        bits persist across trees."""
        enabled = (cfg.cegb_tradeoff < 1.0 or cfg.cegb_penalty_split > 0.0
                   or len(cfg.cegb_penalty_feature_coupled) > 0
                   or len(cfg.cegb_penalty_feature_lazy) > 0)
        self.cegb_enabled = enabled
        self.cegb_lazy = len(cfg.cegb_penalty_feature_lazy) > 0
        if not enabled:
            return
        used = self.train_set.used_feature_indices()

        def per_feature(lst):
            out = np.zeros((self.F,), np.float32)
            for i, r in enumerate(used):
                if int(r) < len(lst):
                    out[i] = lst[int(r)]
            return jnp.asarray(out)

        self._cegb_pen_coupled = per_feature(
            cfg.cegb_penalty_feature_coupled)
        self._cegb_pen_lazy = per_feature(cfg.cegb_penalty_feature_lazy)
        self._cegb_coupled = jnp.zeros((self.F,), jnp.bool_)
        self._cegb_lazy_used = (
            jnp.zeros((self.n, self.F), jnp.bool_) if self.cegb_lazy
            else None)

    def _load_forced_splits(self, cfg) -> Optional[tuple]:
        """forcedsplits_filename JSON -> BFS-ordered (leaf_slot, feature,
        bin) arrays (ForceSplits, serial_tree_learner.cpp:620). Leaf slots
        are precomputable because forced splits run first and in order:
        the split at sequence index i sends its right child to slot
        i + 1."""
        fn = cfg.forcedsplits_filename
        if not fn:
            return None
        import json as _json
        from collections import deque
        with open(fn) as fh:
            root = _json.load(fh)
        if not root:
            return None
        used = self.train_set.used_feature_indices()
        inner_of = {int(r): i for i, r in enumerate(used)}
        from ..ops.binning import BinType
        leafs, feats, bins_ = [], [], []
        q = deque([(root, 0)])
        while q:
            node, slot = q.popleft()
            real = int(node["feature"])
            inner = inner_of.get(real)
            if inner is None or \
                    self.train_set.mappers[inner].bin_type != \
                    BinType.NUMERICAL:
                import warnings
                warnings.warn(
                    f"forced split on unusable/categorical feature {real} "
                    "ignored (with its subtree)")
                continue
            thr = float(node["threshold"])
            t = int(self.train_set.mappers[inner].value_to_bin(
                np.asarray([thr]))[0])
            leafs.append(slot)
            feats.append(inner)
            bins_.append(t)
            right_slot = len(leafs)
            if node.get("left"):
                q.append((node["left"], slot))
            if node.get("right"):
                q.append((node["right"], right_slot))
        if not leafs:
            return None
        return (jnp.asarray(leafs, jnp.int32),
                jnp.asarray(feats, jnp.int32),
                jnp.asarray(bins_, jnp.int32))

    def _parse_interaction_constraints(self, cfg) -> Optional[jnp.ndarray]:
        """interaction_constraints -> [G, F_used] bool group masks
        (config.h interaction_constraints; features outside every group
        are unusable, col_sampler.hpp)."""
        ic = cfg.interaction_constraints
        if ic is None or ic == "" or ic == []:
            return None
        if isinstance(ic, str):
            import ast
            ic = list(ast.literal_eval(ic if ic.startswith("[[")
                                       else "[" + ic + "]"))
        names = list(getattr(self.train_set, "_feature_names", []) or [])
        used = self.train_set.used_feature_indices()
        inner_of = {int(r): i for i, r in enumerate(used)}
        G = np.zeros((len(ic), self.F), bool)
        for gi, grp in enumerate(ic):
            for item in grp:
                real = names.index(item) if isinstance(item, str) \
                    else int(item)
                if real in inner_of:
                    G[gi, inner_of[real]] = True
        return jnp.asarray(G)

    # ------------------------------------------------------------------
    # linear leaves (LinearTreeLearner::CalculateLinear analog)
    # ------------------------------------------------------------------
    def _fit_linear(self, dev_tree, row_leaf, grad, hess, row_w,
                    is_first: bool):
        """Fit per-leaf linear models. Returns (const_dev, coeff_dev,
        pred_dev, feats_inner: list, kmax)."""
        from ..ops.linear import branch_features_per_leaf, fit_leaf_linear
        from ..ops.binning import BinType
        L = self.cfg.num_leaves
        num_leaves = int(np.asarray(dev_tree.num_leaves))
        mappers = self.train_set.mappers

        def is_num(f):
            return mappers[f].bin_type == BinType.NUMERICAL

        if is_first or num_leaves <= 1:
            # first iteration's trees stay constant
            # (linear_tree_learner.cpp:185-190 is_first_tree path)
            return (dev_tree.leaf_value, None,
                    gather_small(dev_tree.leaf_value, row_leaf),
                    [[] for _ in range(L)], 0)
        feats = branch_features_per_leaf(
            np.asarray(dev_tree.split_feature),
            np.asarray(dev_tree.left_child),
            np.asarray(dev_tree.right_child),
            np.asarray(dev_tree.leaf_parent), num_leaves, is_num)
        feats += [[] for _ in range(L - num_leaves)]
        kmax = max((len(f) for f in feats), default=0)
        if kmax == 0:
            return (dev_tree.leaf_value, None,
                    gather_small(dev_tree.leaf_value, row_leaf), feats, 0)
        lf = np.zeros((L, kmax), np.int32)
        nf = np.zeros((L,), np.int32)
        for i, f in enumerate(feats):
            lf[i, : len(f)] = f
            nf[i] = len(f)
        const, coeff, pred = fit_leaf_linear(
            self.raw, row_leaf, grad, hess, row_w,
            jnp.asarray(lf), jnp.asarray(nf), dev_tree.leaf_value,
            self.cfg.linear_lambda)
        return (const, coeff, pred, feats, kmax)

    def _attach_linear(self, tree, lin, shrinkage: float) -> None:
        """Move the device fit into the host Tree (real feature ids;
        near-zero coefficients dropped like the kZeroThreshold filter)."""
        const, coeff, _, feats, kmax = lin
        used = self.train_set.used_feature_indices()
        Lr = tree.num_leaves
        tree.is_linear = True
        tree.leaf_const = np.asarray(const, np.float64)[:Lr] * shrinkage
        coeff_np = None if coeff is None else np.asarray(coeff, np.float64)
        leaf_features, leaf_coeff = [], []
        for i in range(Lr):
            fs, cs = [], []
            for j, f in enumerate(feats[i]):
                c = 0.0 if coeff_np is None else coeff_np[i, j]
                if abs(c) > 1e-35:
                    fs.append(int(used[f]))
                    cs.append(c * shrinkage)
            leaf_features.append(fs)
            leaf_coeff.append(cs)
        tree.leaf_features = leaf_features
        tree.leaf_coeff = leaf_coeff

    def _linear_values_binned(self, tree, dataset, leaves):
        """Per-row outputs of a linear tree over binned leaf assignment
        (AddPredictionToScore's linear path, tree.cpp:120-150). Arrays
        are padded to (cfg.num_leaves, pow2 feature count) so the jitted
        evaluator compiles a handful of shapes, not one per tree."""
        Lr = tree.num_leaves
        L = max(self.cfg.num_leaves, Lr)
        km = max((len(f) for f in tree.leaf_features), default=0)
        const = np.zeros((L,), np.float64)
        const[:Lr] = tree.leaf_const[:Lr]
        if km == 0:
            return jnp.asarray(const, jnp.float32)[leaves]
        kp = 1
        while kp < km:
            kp *= 2
        raw = dataset.device_raw()
        lf = np.zeros((L, kp), np.int32)
        nf = np.zeros((L,), np.int32)
        cf = np.zeros((L, kp), np.float64)
        lv = np.zeros((L,), np.float64)
        lv[:Lr] = tree.leaf_value[:Lr]
        for i in range(Lr):
            inner = dataset.inner_feature_index(
                np.asarray(tree.leaf_features[i], np.int32))
            lf[i, : len(inner)] = inner
            nf[i] = len(inner)
            cf[i, : len(inner)] = tree.leaf_coeff[i]
        return _linear_eval(
            jnp.asarray(const, jnp.float32), jnp.asarray(cf, jnp.float32),
            jnp.asarray(lf), jnp.asarray(nf),
            jnp.asarray(lv, jnp.float32), raw, leaves)

    # ------------------------------------------------------------------
    # sampling strategies (bagging.hpp / goss.hpp analogs)
    # ------------------------------------------------------------------
    def _row_weights(self, it: int, grad: jnp.ndarray,
                     hess: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        n = self.n
        if cfg.data_sample_strategy == "goss":
            # GOSS (goss.hpp:30): keep top |g*h|, sample + amplify the rest
            if it < max(1, int(1.0 / cfg.learning_rate)):
                return jnp.ones((n,), jnp.float32)
            key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.bagging_seed), it)
            metric = jnp.abs(grad) * hess if grad.ndim == 1 else \
                jnp.sum(jnp.abs(grad) * hess, axis=0)
            thresh = jnp.quantile(metric, 1.0 - cfg.top_rate)
            top = metric >= thresh
            rest_prob = cfg.other_rate / max(1e-12, 1.0 - cfg.top_rate)
            amplify = (1.0 - cfg.top_rate) / max(1e-12, cfg.other_rate)
            u = jax.random.uniform(key, (n,))
            other = (~top) & (u < rest_prob)
            return top.astype(jnp.float32) + \
                other.astype(jnp.float32) * amplify
        if cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0
                                     or cfg.pos_bagging_fraction < 1.0
                                     or cfg.neg_bagging_fraction < 1.0):
            if it % cfg.bagging_freq != 0 and self._cached_bag is not None:
                return self._cached_bag
            key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.bagging_seed), it)
            u = jax.random.uniform(key, (n,))
            if (cfg.pos_bagging_fraction < 1.0
                    or cfg.neg_bagging_fraction < 1.0):
                is_pos = self.label > 0
                frac = jnp.where(is_pos, cfg.pos_bagging_fraction,
                                 cfg.neg_bagging_fraction)
                bag = (u < frac).astype(jnp.float32)
            else:
                bag = (u < cfg.bagging_fraction).astype(jnp.float32)
            self._cached_bag = bag
            return bag
        if self._rows_placed:
            return self._row_w_ones          # placed at init, never donated
        return jnp.ones((n,), jnp.float32)

    _cached_bag: Optional[jnp.ndarray] = None

    def _bag_live(self) -> bool:
        """Live bagging gate, re-read from cfg on every call
        (reset_parameter may toggle bagging mid-training): the ONE
        definition of ``_row_weights``' bagging branch condition,
        shared by the fused driver, the scan dispatch and the scan
        abort so the gates can never drift apart."""
        cfg = self.cfg
        return cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0
            or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)

    def _feature_mask(self) -> jnp.ndarray:
        """Per-tree column sampling (ColSampler::ResetByTree analog)."""
        cfg = self.cfg
        usable = self.train_set.usable_feature_mask()
        if cfg.feature_fraction >= 1.0:
            return jnp.asarray(usable)
        idx = np.where(usable)[0]
        k = max(1, int(round(len(idx) * cfg.feature_fraction)))
        chosen = self._feature_rng.choice(idx, size=k, replace=False)
        mask = np.zeros((self.F,), bool)
        mask[chosen] = True
        return jnp.asarray(mask)

    # ------------------------------------------------------------------
    def _gradients(self, score: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        g, h = self.objective.grad_hess(
            score if self.K > 1 else score[0], self.label, self.weight)
        if self.K == 1:
            g, h = g[None, :], h[None, :]
        return g, h

    # ------------------------------------------------------------------
    # fused-iteration fast path: one XLA program per boosting iteration
    # ------------------------------------------------------------------
    def _fused_ok(self) -> bool:
        """The fused step covers exactly the deferred-materialization
        configs (plain gbdt, no valid sets, single mesh-less device) —
        the same gate as ``defer`` in the eager path — minus the
        features whose host-side control flow is data-dependent (CEGB's
        cost-state carry, RenewTreeOutput objectives, GOSS's
        gradient-dependent sampling, linear leaves)."""
        cfg = self.cfg
        return (self.mesh is None
                and cfg.boosting == "gbdt"
                and not self.valid_sets
                and not cfg.linear_tree
                and not self.cegb_enabled
                and not self._goss_active
                and self.objective is not None
                and not getattr(self.objective, "need_renew", False)
                # ranking objectives carry host-side per-iteration state
                # (lambdarank position biases, xendcg's key counter) —
                # inside a traced program those updates would run once
                # at trace time and then freeze
                and not getattr(self.objective, "is_ranking", False))

    def _step_ctx(self) -> _StepCtx:
        """The static per-iteration context both fused programs close
        over (see :class:`_StepCtx`). Rebuilt per program build so an
        OOM downgrade's new ``grow_cfg`` is picked up."""
        gcfg = self.grow_cfg
        # fault injection (test harness): the schedule is static per
        # engine, so the poisoning folds into the traced program as a
        # where(it == N) — zero recompiles, exact device-side replay
        inj_grad = jnp.asarray(self._fault_plan.iters("nan_grad"),
                               jnp.int32) \
            if self._fault_plan.iters("nan_grad") else None
        inj_hess = jnp.asarray(self._fault_plan.iters("nan_hess"),
                               jnp.int32) \
            if self._fault_plan.iters("nan_hess") else None
        return _StepCtx(
            gcfg=gcfg, K=self.K, obj=self.objective,
            nf_policy=self._nf_policy,
            quant=gcfg.quantized and gcfg.stochastic,
            bynode=gcfg.bynode < 1.0,
            base_key=self._base_key, bynode_key=self._bynode_key,
            inj_grad=inj_grad, inj_hess=inj_hess)

    def _fused_tree_proto(self):
        """The pending-tree proto (ShapeDtypeStructs for unpack at
        flush) is config-static: derive it once by abstract eval
        instead of returning the whole dev_tree pytree every call."""
        if self._fused_proto is not None:
            return self._fused_proto
        gcfg = self.grow_cfg
        quant = gcfg.quantized and gcfg.stochastic
        bynode = gcfg.bynode < 1.0
        sds = jax.ShapeDtypeStruct((self.n,), jnp.float32)
        key_sds = jax.ShapeDtypeStruct(self._base_key.shape,
                                       self._base_key.dtype)
        # NB: abstract stand-ins only — _feature_mask() here would
        # consume a host-RNG draw and desync the stream vs eager
        fmask_sds = jax.ShapeDtypeStruct((self.F,), jnp.bool_)
        grow_plan.clear()
        proto, _ = jax.eval_shape(
            functools.partial(grow_tree_impl, gcfg),
            self.bins_T, sds, sds, sds,
            fmask_sds, self.feat_num_bins, self.feat_nan_bin,
            self.monotone, self.feat_is_cat,
            key_sds if quant else None,
            self.interaction_groups, self.forced, None,
            key_sds if bynode else None, self._bundle_dev)
        self._fused_proto = proto
        # what the grower resolved in that trace (empty for the growers
        # that partition nothing): the train/build_step span's attrs
        self._grow_plan = dict(grow_plan)
        return proto

    def _get_fused_fn(self):
        if self._fused_fn is not None:
            return self._fused_fn
        from ..utils.timer import timed
        # job-level span: once an engine (and once more per OOM
        # rebuild). The proto is an abstract evaluation of the whole
        # grower, i.e. a second trace of it before the jit's own
        attrs = {}
        with timed("train/build_step", job=True, attrs=attrs):
            self._fused_tree_proto()
            attrs.update(self._grow_plan)
            ctx = self._step_ctx()

        def step(score, it, shrink, row_w, fmask, bins_T, fnb, fnan,
                 label, weight, monotone, feat_is_cat, igroups, forced,
                 bundle):
            # the whole iteration body lives in the module-level
            # _fused_iter_step — the scan path traces the same ops
            return _fused_iter_step(ctx, score, it, shrink, row_w,
                                    fmask, bins_T, fnb, fnan, label,
                                    weight, monotone, feat_is_cat,
                                    igroups, forced, bundle)

        # donate the old score buffer (it is consumed) — except on CPU,
        # where XLA ignores donation and warns
        self._fused_fn = register_jit(
            "gbdt/fused_iter",
            jax.jit(step, donate_argnums=_donate(0)),
            max_signatures=4)
        return self._fused_fn

    # ------------------------------------------------------------------
    # multi-iteration fused scan: a whole window of boosting iterations
    # as ONE lax.scan program with donated carries (docs/FUSED.md)
    # ------------------------------------------------------------------
    def _scan_ok(self) -> bool:
        """Refinement of ``_fused_ok``: configs whose per-iteration
        host work the scan body can carry on device. Host-RNG
        consumers (``feature_fraction`` draws a np.RandomState mask per
        tree) and mid-window host injections (``oom@N``) fall back to
        the per-iteration fused path; bagging (device fold_in keys),
        pos/neg bagging, bynode sampling, quantized training and every
        grower ride the carry."""
        cfg = self.cfg
        return (cfg.feature_fraction >= 1.0
                and cfg.boosting == "gbdt"
                and not self._fault_plan.iters("oom"))

    def _scan_window(self) -> int:
        """Iterations the next dispatch may cover: the configured
        budget, clamped to the engine-provided lookahead horizon (the
        distance to the next point an outside consumer — checkpoint
        cadence, end of training, an unknown callback — reads
        per-iteration state the window would skate past)."""
        budget = resolve_scan_iters(self.cfg.fused_scan_iters)
        if budget <= 1 or not self._scan_ok():
            return 1
        return max(1, min(budget, self._scan_horizon))

    def _make_bag_refresh(self):
        """Traced twin of ``_row_weights``' bagging branch: draw the
        in-bag weight vector for iteration ``it`` from the identical
        fold_in key schedule, so carry-resident bagging is bit-equal
        to the host-side draws of the eager/fused paths."""
        cfg = self.cfg
        n = self.n
        seed_key = jax.random.PRNGKey(cfg.bagging_seed)
        pos, neg = cfg.pos_bagging_fraction, cfg.neg_bagging_fraction
        frac = cfg.bagging_fraction
        posneg = pos < 1.0 or neg < 1.0

        def fresh(it, label):
            key = jax.random.fold_in(seed_key, it)
            u = jax.random.uniform(key, (n,))
            if posneg:
                is_pos = label > 0
                fr = jnp.where(is_pos, pos, neg)
                return (u < fr).astype(jnp.float32)
            return (u < frac).astype(jnp.float32)

        return fresh

    def _get_scan_fn(self, W: int, bag_live: bool):
        """Build (and cache) the W-iteration scan program: carries are
        the donated score matrix, the bagging weight vector and the
        natural-stop flag; the stacked per-iteration tree packs, leaf
        counts and guard flags come back as the scan's ys — one
        N-slot output buffer fetched per window, not per iteration."""
        key = (W, bag_live)
        fn = self._scan_fns.get(key)
        if fn is not None:
            return fn
        self._fused_tree_proto()
        ctx = self._step_ctx()
        freq = max(1, self.cfg.bagging_freq)
        fresh_bag = self._make_bag_refresh() if bag_live else None
        from jax import lax

        def scan_fn(score, bag, it0, shrink, fmask, bins_T, fnb, fnan,
                    label, weight, monotone, feat_is_cat, igroups,
                    forced, bundle):
            def body(carry, it):
                score, bag, stop = carry
                if bag_live:
                    # refresh cadence traced from the absolute
                    # iteration — identical to _row_weights' host
                    # check; a stopped window never consumes draws
                    refresh = jnp.logical_and(it % freq == 0,
                                              jnp.logical_not(stop))
                    bag = lax.cond(refresh,
                                   lambda b: fresh_bag(it, label),
                                   lambda b: b, bag)
                new_score, outs, flags = _fused_iter_step(
                    ctx, score, it, shrink, bag, fmask, bins_T, fnb,
                    fnan, label, weight, monotone, feat_is_cat,
                    igroups, forced, bundle)
                vecs = jnp.stack([o[0] for o in outs])
                cmasks = jnp.stack([o[1] for o in outs])
                nls = jnp.stack([o[2] for o in outs])
                # natural-stop gating: once an iteration grows nothing
                # (and no fault demoted it — skip_tree leaves look
                # identical), later slots become score no-ops, exactly
                # where the per-iteration driver would have stopped;
                # the host drain discards their emitted trees
                new_score = jnp.where(stop, score, new_score)
                stalled = jnp.logical_and(jnp.all(nls <= 1),
                                          jnp.all(flags == 0))
                return ((new_score, bag, jnp.logical_or(stop, stalled)),
                        (vecs, cmasks, nls, flags))

            its = it0 + jnp.arange(W, dtype=jnp.int32)
            carry0 = (score, bag, jnp.asarray(False))
            (score, bag, _), ys = lax.scan(body, carry0, its)
            return (score, bag) + ys

        # donate the score AND bagging carries (both are consumed) —
        # except on CPU, where XLA ignores donation and warns
        fn = register_jit("gbdt/fused_scan",
                          jax.jit(scan_fn, donate_argnums=_donate(0, 1)),
                          max_signatures=4)
        self._scan_fns[key] = fn
        return fn

    # tpulint: hot
    def _dispatch_scan_window(self, W: int) -> bool:
        """Run the next ``W`` boosting iterations as one scan program
        and queue the results; pops hand them to the driver one
        iteration at a time so callbacks/telemetry keep their
        per-iteration cadence. The batched ``jax.device_get`` below is
        the scan pipeline's ONE window-boundary sync point (tpulint
        TPL002 baseline): every per-iteration fetch, dispatch and
        driver pass between window edges is gone."""
        from ..utils.timer import timed

        cfg = self.cfg
        it0 = self.iter_
        bag_live = self._bag_live()
        with timed("boosting/bagging"):
            if bag_live:
                freq = max(1, cfg.bagging_freq)
                if it0 % freq == 0:
                    # refresh-aligned entry: the body's first slot
                    # redraws the carry unconditionally, so the host
                    # draw would be discarded — donate a placeholder
                    # instead of a wasted [n] uniform pass
                    bag_key_it = None
                    bag0 = jnp.zeros((self.n,), jnp.float32)
                else:
                    # the WINDOW-ENTRY bag follows the eager rule at
                    # it0 (reuse the cache, else draw fresh at it0).
                    # Remember which iteration it was KEYED at — a
                    # sequential cache always came from the last
                    # refresh (checkpoint restore re-derives it there
                    # too) — so the OOM-retry path below can reproduce
                    # the exact draw after a failed dispatch consumed
                    # (donated) it.
                    bag_key_it = (it0 // freq) * freq \
                        if self._cached_bag is not None else it0
                    bag0 = self._row_weights(it0, None, None)
            else:
                # a fresh ones buffer per window: the carry is donated,
                # so the shared _row_w_ones must not be consumed
                bag0 = jnp.ones((self.n,), jnp.float32)
            if self._fmask_cached is None:
                self._fmask_cached = self._feature_mask()
            fmask = self._fmask_cached
        # label defined in obs/trace.py (FUSED_SCAN_PHASE): the
        # jax-free tracing layer, the bench and the per-iteration
        # host-gap derivation all key on this exact phase name
        with timed(FUSED_SCAN_PHASE):
            def dispatch():
                # re-reads _get_scan_fn so an OOM downgrade's rebuilt
                # program is picked up on the retry — and re-derives
                # the bagging carry if the failed dispatch already
                # consumed (donated) it: re-drawn at the iteration the
                # entry bag was KEYED at (not it0 — a cache-served
                # entry bag came from the last refresh iteration, and
                # _row_weights(it0) on the now-empty cache would draw
                # a fresh vector no other path ever uses)
                nonlocal bag0
                if getattr(bag0, "is_deleted", lambda: False)():
                    if not bag_live:
                        bag0 = jnp.ones((self.n,), jnp.float32)
                    elif bag_key_it is None:
                        # refresh-aligned placeholder (overwritten by
                        # the body's first slot)
                        bag0 = jnp.zeros((self.n,), jnp.float32)
                    else:
                        self._cached_bag = None
                        bag0 = self._row_weights(bag_key_it, None,
                                                 None)
                return self._get_scan_fn(W, bag_live)(
                    self.score, bag0, jnp.asarray(it0, jnp.int32),
                    jnp.asarray(self._shrinkage, jnp.float32), fmask,
                    self.bins_T, self.feat_num_bins, self.feat_nan_bin,
                    self.label, self.weight, self.monotone,
                    self.feat_is_cat, self.interaction_groups,
                    self.forced, self._bundle_dev)

            out = self._run_with_oom_degrade(dispatch,
                                             "fused scan window")
            new_score, new_bag, vecs, cmasks, nls, flags = out
            # the ONE legal sync of the window: the whole window's tree
            # packs, leaf counts and guard flags cross device->host as
            # a single batched fetch (docs/FUSED.md)
            vecs_h, cmasks_h, nls_h, flags_h = jax.device_get(
                (vecs, cmasks, nls, flags))
        self.score = new_score
        if bag_live:
            self._cached_bag = new_bag
        # the dispatch-time shrinkage is stamped into the pend: the
        # traced window already scored contrib * THIS value, so pops
        # must flush trees with it even if _shrinkage moves later
        # (a learning_rate reset additionally aborts the pend —
        # basic.py reset_parameter — so the new rate takes effect at
        # the very next iteration like the per-iteration path)
        self._scan_pend = {"it0": it0, "W": W, "pos": 0,
                           "shrink": self._shrinkage,
                           "vec": vecs_h, "cmask": cmasks_h,
                           "nl": nls_h, "flags": flags_h}
        from ..obs.registry import registry as _registry
        _registry.counter("fused_scan_windows").inc()
        return self._pop_scan_iter()

    # tpulint: hot
    def _pop_scan_iter(self) -> bool:
        """Commit ONE precomputed window iteration to the driver state:
        defer its K trees (host numpy slices of the batched pack — no
        device traffic), queue its guard flags for the one-late drain,
        and advance the iteration counter. The no-growth / fault-raise
        decisions stay in ``train_one_iter``'s existing host logic,
        which sees exactly the per-iteration stream it always saw."""
        p = self._scan_pend
        j = p["pos"]
        it = p["it0"] + j
        self._push_guard_flags(it, p["flags"][j])
        fold_now = it == 0 and self._fold_bias
        for k in range(self.K):
            bias = float(self.init_score[k]) if fold_now else 0.0
            self._defer_tree(p["vec"][j, k], p["cmask"][j, k],
                             self._fused_proto, p["nl"][j, k],
                             p["shrink"], bias)
        p["pos"] += 1
        self._scan_last = {"window": int(p["W"]), "pos": int(j),
                           "dispatch": j == 0}
        if p["pos"] >= p["W"]:
            self._scan_pend = None
        self.iter_ += 1
        return False

    def _abort_scan_window(self,
                           next_iter: Optional[int] = None) -> None:
        """Discard precomputed lookahead iterations (rollback, model
        replacement, a custom-gradient update arriving mid-window).
        The window's final score includes the discarded slots, so the
        score is rebuilt from the materialized trees — last-ulp
        different from incremental accumulation, the same forfeit as
        the OOM donation rebuild.

        ``next_iter``: the iteration that will train next —
        ``iter_`` by default, but ``rollback_one_iter`` passes
        ``iter_ - 1`` because it decrements AFTER this abort (an
        on-cadence ``iter_`` would otherwise skip the cache
        re-derivation that the post-rollback off-cadence iteration
        needs)."""
        if self._scan_pend is None:
            return
        self._scan_pend = None
        self._scan_last = None
        self.score = self._place_score(
            self._score_dataset_binned(self.train_set))
        # the carry-resident bag ran ahead with the window; re-derive
        # the cache at the LAST REFRESH iteration so the next
        # _row_weights reuses the same draw the per-iteration path
        # would (checkpoint restore does the identical re-derivation;
        # drawing fresh at an off-cadence iteration would silently
        # fork the bagging stream)
        next_iter = self.iter_ if next_iter is None \
            else max(0, next_iter)
        self._cached_bag = None
        if self._bag_live():
            freq = self.cfg.bagging_freq
            last_refresh = (next_iter // freq) * freq
            if last_refresh < next_iter:
                self._row_weights(last_refresh, None, None)

    def telemetry_scan_stats(self) -> Optional[Dict[str, object]]:
        """Scan-window position of the LAST committed iteration for
        the telemetry recorder (obs/recorder.py): ``window`` size,
        ``pos`` inside it, and whether this iteration carried the
        window dispatch (its event absorbs the whole window's device
        phase time). None when the iteration ran per-iteration."""
        if self._scan_last is None:
            return None
        return dict(self._scan_last)

    # tpulint: hot
    def _train_one_iter_fused(self) -> bool:
        """One boosting iteration as a single device program.

        Host-side RNG consumers (per-tree feature_fraction mask,
        bagging weights) stay OUTSIDE the program and feed it as
        arguments so their streams match the eager path exactly; the
        finished tree comes back the same deferred route
        (_pending_dev + async copies) the eager defer branch uses.

        When a multi-iteration scan window is active (or can start —
        Config.fused_scan_iters, docs/FUSED.md), the iteration is
        popped from / dispatched as one whole-window program
        instead."""
        from ..utils.timer import timed

        if self._scan_pend is not None:
            return self._pop_scan_iter()
        W = self._scan_window()
        if W > 1:
            return self._dispatch_scan_window(W)

        cfg = self.cfg
        it = self.iter_
        with timed("boosting/bagging"):
            # evaluate the bagging gate LIVE (not the __init__-time
            # _bag_active snapshot): reset_parameter may turn bagging
            # on/off mid-training (LGBM_BoosterResetParameter), and the
            # eager path's _row_weights re-reads cfg every iteration
            bag_live = self._bag_live()
            if bag_live:
                row_w = self._row_weights(it, None, None)
            else:
                if self._row_w_ones is None:
                    self._row_w_ones = jnp.ones((self.n,), jnp.float32)
                row_w = self._row_w_ones
            if cfg.feature_fraction < 1.0:
                fmask = self._feature_mask()
            else:
                if self._fmask_cached is None:
                    self._fmask_cached = self._feature_mask()
                fmask = self._fmask_cached
        with timed("boosting/fused_iter"):
            # thunk re-reads _get_fused_fn so an OOM downgrade's
            # rebuilt program is picked up on the retry
            new_score, outs, guard_flags = self._run_with_oom_degrade(
                lambda: self._get_fused_fn()(
                    self.score, jnp.asarray(it, jnp.int32),
                    jnp.asarray(self._shrinkage, jnp.float32), row_w,
                    fmask, self.bins_T, self.feat_num_bins,
                    self.feat_nan_bin, self.label, self.weight,
                    self.monotone, self.feat_is_cat,
                    self.interaction_groups, self.forced,
                    self._bundle_dev),
                "fused iteration")
        self.score = new_score
        with timed("tree/defer"):
            self._push_guard_flags(it, guard_flags)
            fold_now = it == 0 and self._fold_bias
            for k, (vec, cmask, num_leaves) in enumerate(outs):
                bias = float(self.init_score[k]) if fold_now else 0.0
                self._defer_tree(vec, cmask, self._fused_proto,
                                 num_leaves, self._shrinkage, bias)
        self.iter_ += 1
        return False

    # tpulint: hot
    def _defer_tree(self, vec, cmask, proto, num_leaves, shrink,
                    bias) -> None:
        """Queue one finished device tree for lazy host materialization
        (consumed by _flush_pending; shared by the eager defer branch
        and the fused path — keep the pending-tuple shape in one
        place)."""
        try:
            vec.copy_to_host_async()
            cmask.copy_to_host_async()
            num_leaves.copy_to_host_async()
        except AttributeError:  # non-jax arrays (tests/cpu)
            pass
        self._pending_dev.append((vec, cmask, proto, shrink, bias))
        self._tree_weights.append(1.0)
        self._nl_async.append(num_leaves)

    # tpulint: hot
    def train_one_iter(self,
                       custom_grad: Optional[np.ndarray] = None,
                       custom_hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (TrainOneIter, gbdt.cpp:344).
        Returns True if no tree could be grown (training finished)."""
        # phase annotations: the USE_TIMETAG points of GBDT::TrainOneIter
        # (gbdt.cpp:221-492) — see utils/timer.py
        from ..utils.timer import timed

        cfg = self.cfg
        it = self.iter_

        # scan-window bookkeeping: the telemetry marker tracks only the
        # path actually taken this iteration, and precomputed lookahead
        # survives ONLY while _pop_scan_iter will serve this iteration
        # — a custom-gradient update, or a _fused_ok flip mid-pend
        # (add_valid between direct update() calls), would otherwise
        # train eagerly from the window-ahead score with stale packs
        # still queued
        self._scan_last = None
        if self._scan_pend is not None and (custom_grad is not None
                                            or not self._fused_ok()):
            self._abort_scan_window()

        # non-finite guard flags from the previous (async) program,
        # checked one iteration late like the tree queue below —
        # raises/records per nonfinite_policy (resilience/).
        # boosting/drain: the two reads of the PREVIOUS round's results
        # (guard flags here, leaf counts below) — where a host that
        # runs ahead of the device waits for it
        with timed("boosting/drain"):
            self._drain_guard_flags()
            self._drain_reduction_counts()

        # checkpoint-restored no-growth marker: the snapshot's final
        # iteration grew nothing, so an uninterrupted run's next
        # update() would stop BEFORE growing — byte-exact resume must
        # stop at the same point instead of regrowing an extra
        # constant tree (resilience/checkpoint.py "stalled")
        if self._resume_stalled:
            self._resume_stalled = False
            if custom_grad is None:
                self._finished_natural = True
                return True

        # deferred-mode no-growth check, one iteration late: the async
        # copies were started last iteration so this read doesn't stall.
        # Custom gradients always get a fresh attempt (the reference's
        # TrainOneIterCustom never short-circuits on past iterations).
        # A recent fault suppresses the short-circuit: a skip_tree
        # demotion is indistinguishable from natural no-growth in the
        # leaf counts alone. The STICKY marker (not the drain's return
        # value) carries that across out-of-band drains — a checkpoint
        # callback draining between iterations must not eat it.
        if self._nl_async:
            with timed("boosting/drain"):
                nls = [int(np.asarray(x)) for x in self._nl_async]
            self._nl_async = []
            fault_recent, self._fault_recent = self._fault_recent, False
            if custom_grad is None and not fault_recent \
                    and all(nl <= 1 for nl in nls):
                # remembered past the drain: a checkpoint written after
                # this point must still carry the stalled marker
                self._finished_natural = True
                # lookahead iterations a scan window precomputed past
                # the natural stop never happened: the scan body's stop
                # carry already froze the score at this point, so the
                # queued packs are simply dropped
                self._scan_pend = None
                return True

        # Fast path: the whole iteration (gradients -> grow -> tree pack
        # -> contrib gather -> score update) as ONE jitted program:
        # one launch per iteration instead of six, and XLA fuses the
        # small row-vector ops into the grower's program. What a launch
        # costs on a local chip is not measured.
        if custom_grad is None and self._fused_ok():
            return self._train_one_iter_fused()

        # DART: pick and temporarily drop trees (dart.hpp DroppingTrees)
        drop_idx: List[int] = []
        if cfg.boosting == "dart" and self.models:
            drop_idx = self._dart_select_drop()
            if drop_idx:
                self._dart_apply_drop(drop_idx)

        with timed("boosting/gradients"):
            if custom_grad is not None:
                grad = jnp.asarray(custom_grad,
                                   jnp.float32).reshape(self.K, self.n)
                hess = jnp.asarray(custom_hess,
                                   jnp.float32).reshape(self.K, self.n)
            elif cfg.boosting == "rf":
                # RF trees are independent: gradients always from the init
                # score, never the running average (rf.hpp Boosting)
                init = jnp.tile(jnp.asarray(self.init_score,
                                            jnp.float32)[:, None],
                                (1, self.n))
                grad, hess = self._gradients(init)
            else:
                grad, hess = self._gradients(self.score)

        # non-finite guard (+ fault injection) before anything consumes
        # the gradients; GOSS sampling below sees the clamped values
        grad, hess, gh_flag = self._gh_guard(it, grad, hess)

        with timed("boosting/bagging"):
            row_w = self._row_weights(it, grad[0] if self.K == 1 else grad,
                                      hess[0] if self.K == 1 else hess)
            fmask = self._feature_mask()

        shrinkage = self._shrinkage if cfg.boosting != "rf" else 1.0
        grew_any = False
        # loop-invariant defer gate (hoisted from the k loop): guard
        # flags travel async in defer mode, synchronously otherwise
        defer = (not self.valid_sets and cfg.boosting == "gbdt"
                 and not cfg.linear_tree)
        iter_flag = None   # device-side OR of this iteration's flags
        sync_flag = 0      # host-side flags (non-defer path)
        fault_now = False
        quant_key = None
        if cfg.use_quantized_grad and cfg.stochastic_rounding:
            quant_key = jax.random.fold_in(self._base_key, it)
        node_key = None
        if cfg.feature_fraction_bynode < 1.0:
            node_key = jax.random.fold_in(self._bynode_key, it)
        for k in range(self.K):
            if self.mesh is not None:
                gk = grad[k]
                hk = hess[k]
                rwk = row_w
                if self._pad:
                    gk = jnp.pad(gk, (0, self._pad))
                    hk = jnp.pad(hk, (0, self._pad))
                    rwk = jnp.pad(rwk, (0, self._pad))
                args = (self.bins_T, gk, hk, rwk, fmask,
                        self.feat_num_bins, self.feat_nan_bin)
                if self.monotone is not None:
                    args = args + (self.monotone,)
                if self.feat_is_cat is not None:
                    args = args + (self.feat_is_cat,)
                if quant_key is not None:
                    args = args + (jax.random.fold_in(quant_key, k),)
                if self.interaction_groups is not None:
                    args = args + (self.interaction_groups,)
                if self.forced is not None:
                    args = args + self.forced
                if node_key is not None:
                    args = args + (jax.random.fold_in(node_key, k),)
                if self._bundle_dev is not None:
                    args = args + self._bundle_dev
                first = self._reduction_sites is None
                if first:
                    from ..parallel import comms
                    grow_plan.clear()
                    del comms.traced_reductions[:]
                with timed("tree_learner/grow"):
                    dev_tree, row_leaf = self._run_with_oom_degrade(
                        lambda: self._grow_fn(*args), "distributed grow")
                if first:
                    # what the grower resolved and which reductions it
                    # traced, from the trace the first call just made
                    self._grow_plan = dict(grow_plan)
                    self._reduction_sites = tuple(comms.traced_reductions)
                if self._pad:
                    row_leaf = row_leaf[: self.n]
            else:
                cegb_arrays = None
                if self.cegb_enabled:
                    cegb_arrays = (self._cegb_pen_coupled,
                                   self._cegb_pen_lazy,
                                   self._cegb_coupled,
                                   self._cegb_lazy_used)
                with timed("tree_learner/grow"):
                    out = self._run_with_oom_degrade(
                        lambda: grow_tree(
                            self.grow_cfg, self.bins_T, grad[k], hess[k],
                            row_w, fmask, self.feat_num_bins,
                            self.feat_nan_bin,
                            self.monotone, self.feat_is_cat,
                            None if quant_key is None
                            else jax.random.fold_in(quant_key, k),
                            self.interaction_groups, self.forced,
                            cegb_arrays,
                            None if node_key is None
                            else jax.random.fold_in(node_key, k),
                            self._bundle_dev), "grow")
                if not self._grow_plan and self.grow_cfg.grower == "compact":
                    # what the grower resolves for this job (the fused
                    # and mesh paths keep it from their own trace). Not
                    # ``last_plan``: an earlier job of this process may
                    # have compiled the same program, and then nothing
                    # was traced for this one
                    self._grow_plan = compact_plan(
                        self.grow_cfg, int(self.bins_T.shape[1]),
                        int(self.bins_T.shape[0]), self.bins_T.dtype,
                        self._bundle_dev is not None)
                if self.cegb_enabled:
                    dev_tree, row_leaf, self._cegb_coupled, lz = out
                    if self.cegb_lazy:
                        self._cegb_lazy_used = lz
                else:
                    dev_tree, row_leaf = out
            dev_tree, k_flag = self._leaf_guard(dev_tree, gh_flag)
            iter_flag = k_flag if iter_flag is None else iter_flag | k_flag
            if defer:
                # no blocking scalar fetch: the no-growth check runs one
                # iteration late off an async copy (see top of method);
                # constant trees are recognized at flush time
                num_leaves = 2
            else:
                # ONE batched transfer, not two sequential blocking
                # fetches (tpulint TPL002: each np.asarray scalar read
                # is its own full device round trip on this
                # latency-bound eager path)
                nl_host, flag_host = jax.device_get(
                    (dev_tree.num_leaves, k_flag))
                num_leaves = int(nl_host)
                sync_flag |= int(flag_host)
                self._count_reductions(num_leaves)
            if num_leaves <= 1:
                # constant tree; carries the boost_from_average bias when
                # it is the first iteration (gbdt.cpp models_.size() check /
                # rf.hpp AsConstantTree path)
                tree = tree_from_arrays(dev_tree, self.train_set.mappers,
                                        self.train_set.used_feature_indices())
                bias = 0.0
                if it == 0 and (self._fold_bias or cfg.boosting == "rf"):
                    bias = float(self.init_score[k])
                tree.leaf_value[:] = bias
                if cfg.linear_tree:
                    tree.is_linear = True
                    tree.leaf_const = tree.leaf_value.copy()
                    tree.leaf_features = [[] for _ in
                                          range(tree.num_leaves)]
                    tree.leaf_coeff = [[] for _ in range(tree.num_leaves)]
                self.models.append(tree)
                self._tree_weights.append(1.0)
                if cfg.boosting == "rf":
                    self.score = self.score.at[k].set(
                        (self.score[k] * it + bias) / (it + 1))
                    for v in self.valid_sets:
                        v.score = v.score.at[k].set(
                            (v.score[k] * it + bias) / (it + 1))
                elif bias != 0.0:
                    for v in self.valid_sets:
                        v.score = v.score.at[k].add(bias)
                continue
            grew_any = True

            # objective-specific per-leaf refinement (RenewTreeOutput).
            # rf refines against the init score, not the running average
            # (rf.hpp residual_getter uses init_scores_).
            leaf_values = dev_tree.leaf_value
            if (self.objective is not None and self.objective.need_renew
                    and custom_grad is None):
                if cfg.boosting == "rf":
                    base = jnp.full((self.n,), float(self.init_score[k]),
                                    jnp.float32)
                else:
                    base = self.score[k]
                resid = self.objective.renew_residual(base, self.label)
                rw = self.objective.renew_weight(self.label, self.weight)
                rw = row_w if rw is None else row_w * rw
                leaf_values = renew_leaf_values(
                    row_leaf, resid, rw, cfg.num_leaves,
                    self.objective.renew_alpha, leaf_values)
                dev_tree = dev_tree._replace(leaf_value=leaf_values)

            fold_now = (cfg.boosting == "rf") or (it == 0 and self._fold_bias)
            bias = float(self.init_score[k]) if fold_now else 0.0
            lin = None
            if defer:
                # Don't stall the device pipeline on a per-iteration
                # host fetch: pack the tree to one vector, start an
                # async copy, and materialize the host Tree lazily
                # (models property). Bias/shrinkage are re-applied at
                # materialization in the same order as the eager path.
                vec, cmask = pack_tree_device(dev_tree)
                proto = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    dev_tree)
                self._defer_tree(vec, cmask, proto, dev_tree.num_leaves,
                                 shrinkage, bias)
                if self.mesh is not None:
                    self._comm_nl.append(dev_tree.num_leaves)
                tree = None
            else:
                if cfg.linear_tree:
                    lin = self._fit_linear(
                        dev_tree, row_leaf, grad[k], hess[k], row_w,
                        is_first=(len(self.models) < self.K))
                # the finished tree's arrays, device to host: what a
                # validation set (or a linear leaf) makes every round pay
                with timed("tree/fetch") as fetch:
                    tree = tree_from_arrays(
                        dev_tree, self.train_set.mappers,
                        self.train_set.used_feature_indices())
                    _count_tree(tree)
                    if fetch is not None:   # a span only under a capture
                        fetch.attrs = _rows_streamed(tree)
                tree.apply_shrinkage(shrinkage)
                if lin is not None:
                    self._attach_linear(tree, lin, shrinkage)
                if bias != 0.0:
                    # Tree::AddBias: the constant rides inside leaf values
                    # so the model file is self-contained (every tree for
                    # rf)
                    tree.leaf_value = tree.leaf_value + bias
                    tree.internal_value = tree.internal_value + bias
                    if tree.is_linear and getattr(tree, "leaf_const",
                                                  None) is not None:
                        # AddBias updates leaf_const too (tree.cpp:222-227)
                        tree.leaf_const = tree.leaf_const + bias
                self.models.append(tree)
                self._tree_weights.append(1.0)

            contrib_raw = lin[2] if lin is not None \
                else gather_small(leaf_values, row_leaf)
            if defer:
                # a no-growth tree is replaced by a constant at flush
                # (AsConstantTree, gbdt.cpp): contribute nothing here
                contrib_raw = jnp.where(dev_tree.num_leaves > 1,
                                        contrib_raw, 0.0)
            if cfg.boosting == "rf":
                # running average of unscaled tree outputs (rf.hpp
                # MultiplyScore m -> UpdateScore -> MultiplyScore 1/(m+1))
                contrib = contrib_raw + float(self.init_score[k])
                self.score = self.score.at[k].set(
                    (self.score[k] * it + contrib) / (it + 1))
                for v in self.valid_sets:
                    dv = self._predict_tree_binned_host(tree, v.dataset)
                    v.score = v.score.at[k].set(
                        (v.score[k] * it + dv) / (it + 1))
            else:
                # train-score update via the leaf partition — no
                # re-traversal (ScoreUpdater::AddScore, score_updater.hpp)
                with timed("boosting/update_score"):
                    self.score = self.score.at[k].add(
                        contrib_raw * shrinkage)
                if it == 0 and self._fold_bias \
                        and self.init_score[k] != 0.0:
                    # internal score already starts at init; nothing to add
                    pass
                if self.valid_sets:
                    self._add_tree_to_valid_scores(tree, k)

        if defer:
            if iter_flag is not None:
                self._push_guard_flags(it, iter_flag)
        elif sync_flag:
            # non-defer paths already fetched num_leaves, so the flag
            # read cost nothing extra: record/raise at the exact
            # iteration, and keep training through a skip_tree demotion
            # (a fault is not "no more leaves to split")
            fault_now = True
            self._apply_guard_flag(it, sync_flag)

        if cfg.boosting == "dart" and drop_idx and grew_any:
            self._dart_normalize(drop_idx)

        self.iter_ += 1
        finished = not grew_any and not fault_now
        if finished:
            self._finished_natural = True
        return finished

    # ------------------------------------------------------------------
    # DART (dart.hpp)
    # ------------------------------------------------------------------
    def _dart_select_drop(self) -> List[int]:
        cfg = self.cfg
        n_models = len(self.models)
        n_iters = n_models // self.K
        if self._dart_rng.rand() < cfg.skip_drop or n_iters == 0:
            return []
        if cfg.uniform_drop:
            mask = self._dart_rng.rand(n_iters) < cfg.drop_rate
            drop_iters = np.where(mask)[0]
        else:
            k = min(max(1, int(round(n_iters * cfg.drop_rate))), cfg.max_drop)
            drop_iters = self._dart_rng.choice(n_iters, size=min(k, n_iters),
                                               replace=False)
        if len(drop_iters) > cfg.max_drop > 0:
            drop_iters = drop_iters[:cfg.max_drop]
        out = []
        for i in drop_iters:
            out.extend(range(i * self.K, (i + 1) * self.K))
        return sorted(out)

    def _dart_apply_drop(self, drop_idx: List[int]) -> None:
        """Remove dropped trees' contribution from all score vectors."""
        for i in drop_idx:
            k = i % self.K
            tree = self.models[i]
            self.score = self.score.at[k].add(
                -self._predict_tree_binned_host(tree, self.train_set))
            for v in self.valid_sets:
                v.score = v.score.at[k].add(-self._predict_tree_binned_host(
                    tree, v.dataset))

    def _dart_normalize(self, drop_idx: List[int]) -> None:
        """Shrink re-added dropped trees and the new tree (dart.hpp
        Normalize)."""
        cfg = self.cfg
        kd = len(drop_idx) // self.K
        if cfg.xgboost_dart_mode:
            new_w = self._shrinkage / (kd + self._shrinkage)
            old_factor = kd / (kd + self._shrinkage)
        else:
            new_w = 1.0 / (kd + 1.0)
            old_factor = kd / (kd + 1.0)
        # scale the trees added this iteration
        for i in range(len(self.models) - self.K, len(self.models)):
            if self.models[i].num_leaves > 1:
                k = i % self.K
                delta = self._predict_tree_binned_host(self.models[i],
                                                       self.train_set)
                self.score = self.score.at[k].add(delta * (new_w - 1.0))
                for v in self.valid_sets:
                    dv = self._predict_tree_binned_host(
                        self.models[i], v.dataset)
                    v.score = v.score.at[k].add(dv * (new_w - 1.0))
                self.models[i].apply_shrinkage(new_w)
        # scale the dropped trees and re-add
        for i in drop_idx:
            k = i % self.K
            self.models[i].apply_shrinkage(old_factor)
            delta = self._predict_tree_binned_host(self.models[i],
                                                   self.train_set)
            self.score = self.score.at[k].add(delta)
            for v in self.valid_sets:
                dv = self._predict_tree_binned_host(self.models[i],
                                                    v.dataset)
                v.score = v.score.at[k].add(dv)

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """RollbackOneIter (gbdt.cpp:454)."""
        # a pending scan window's score runs ahead of iter_; restore
        # the committed-state score before unwinding one iteration
        # (next_iter: the decrement below happens after this abort)
        self._abort_scan_window(next_iter=self.iter_ - 1)
        self._nl_async = []
        self._guard_async = []
        self._fault_recent = False
        self._finished_natural = False
        if not self.models:
            return
        is_rf = self.cfg.boosting == "rf"
        m = self.iter_ - 1  # iterations remaining after rollback
        for k in reversed(range(self.K)):
            tree = self.models.pop()
            self._tree_weights.pop()
            if is_rf:
                dv = self._predict_tree_binned_host(
                    tree, self.train_set)
                if m > 0:
                    self.score = self.score.at[k].set(
                        (self.score[k] * (m + 1) - dv) / m)
                else:
                    self.score = self.score.at[k].set(jnp.full_like(
                        self.score[k], float(self.init_score[k])))
                for v in self.valid_sets:
                    vv = self._predict_tree_binned_host(
                        tree, v.dataset)
                    if m > 0:
                        v.score = v.score.at[k].set(
                            (v.score[k] * (m + 1) - vv) / m)
                    else:
                        v.score = v.score.at[k].set(
                            jnp.zeros_like(v.score[k]))
                continue
            if tree.num_leaves > 1 or tree.leaf_value[0] != 0.0:
                delta = self._predict_tree_binned_host(
                    tree, self.train_set)
                self.score = self.score.at[k].add(-delta)
                if m == 0 and self._fold_bias:
                    # the popped iter-0 tree carried the folded bias, but
                    # the internal train score starts at init: restore it
                    self.score = self.score.at[k].add(
                        float(self.init_score[k]))
                for v in self.valid_sets:
                    dv = self._predict_tree_binned_host(
                        tree, v.dataset)
                    v.score = v.score.at[k].add(-dv)
        self.iter_ -= 1

    def eval_metrics(self, metrics, data_idx: int) -> Dict[str, float]:
        """data_idx 0 = train, 1.. = valid sets."""
        if data_idx == 0:
            score, ds = self.score, self.train_set
        else:
            v = self.valid_sets[data_idx - 1]
            score, ds = v.score, v.dataset
        from ..obs.registry import registry
        from ..utils.timer import timed
        out = {}
        # NDCG is one registered program for every eval_at
        # (ranking/ndcg); the other metrics run op by op (no registered
        # entry): the device scope names those ops in a trace, the span
        # is the host's wait for every value (float() blocks on the
        # device)
        with timed("metric/eval"), scope("metric/eval"):
            with timed("metric/upload"):
                label = jnp.asarray(ds.get_label(), jnp.float32)
                w = ds.get_weight()
                weight = None if w is None else jnp.asarray(w, jnp.float32)
            convert = (self.objective.convert_output
                       if self.objective is not None else (lambda s: s))
            for m in metrics:
                if hasattr(m, "eval_with_query"):
                    val = m.eval_with_query(score, label, weight, ds,
                                            convert)
                else:
                    val = m.eval(score, label, weight, convert)
                out[m.name] = float(val)
            registry.counter("metric_evals").inc(len(metrics))
        return out

    def current_score(self, data_idx: int) -> np.ndarray:
        if data_idx == 0:
            return np.asarray(self.score)
        return np.asarray(self.valid_sets[data_idx - 1].score)
