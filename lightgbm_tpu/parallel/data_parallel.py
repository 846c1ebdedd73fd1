"""Data-parallel tree growth over a device mesh.

Re-design of DataParallelTreeLearner
(/root/reference/src/treelearner/data_parallel_tree_learner.cpp) for TPU:

reference (socket/MPI)                     ->  TPU (mesh + XLA collectives)
--------------------------------------------------------------------------
rank-strided row shards                    ->  rows sharded over mesh axis
ReduceScatter(histograms, HistogramSum)    ->  lax.psum of [F,B,3] inside
  + per-rank feature ownership (:223-300)      shard_map (XLA lowers to
                                               reduce-scatter+all-gather
                                               on ICI as it sees fit)
SyncUpGlobalBestSplit (allreduce max-gain) ->  not needed: every device
                                               sees the full summed
                                               histogram and computes the
                                               identical argmax
global leaf counts allreduce               ->  psum of root/leaf sums

``tree_learner=feature`` and ``=voting`` build the same shard_map with
the grower's ``parallel_mode`` switched (GrowConfig.parallel_mode):
feature-parallel replicates rows (every in_spec P()) and allreduces the
best SplitInfo across disjoint per-device feature shards
(feature_parallel_tree_learner.cpp:71); voting shards rows but keeps
the histogram cache local, reducing only vote-elected features per
search (voting_parallel_tree_learner.cpp:364).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.scopes import scope
from ..ops.grow import GrowConfig, grow_tree_impl

__all__ = ["make_dp_grow_fn"]


@functools.lru_cache(maxsize=32)
def _build(cfg: GrowConfig, mesh: Mesh, has_monotone: bool, has_cat: bool,
           has_quant_key: bool, has_interaction: bool = False,
           has_forced: bool = False, has_node_key: bool = False,
           has_bundle: bool = False):
    axis = mesh.axis_names[0]
    cfg = cfg._replace(axis_name=axis)
    if cfg.parallel_mode == "feature":
        # rows replicated: every device holds the full dataset and owns
        # a feature shard inside the grower's split search
        rowspec = P()
    else:
        rowspec = P(axis)
    rep = P()

    in_specs = (P(None, axis) if cfg.parallel_mode != "feature"
                else P(None, None),
                rowspec, rowspec, rowspec, rep, rep, rep)
    in_specs = in_specs + (rep,) * (int(has_monotone) + int(has_cat)
                                    + int(has_quant_key)
                                    + int(has_interaction)
                                    + 3 * int(has_forced)
                                    + int(has_node_key)
                                    # bundle metadata (8 host-built
                                    # arrays, ops/bundling.py) is a
                                    # dataset property — replicated,
                                    # like the bin-count metadata
                                    + 8 * int(has_bundle))
    out_specs = (rep, rowspec)  # tree replicated, row_leaf row-layout

    def fn(bins_T, grad, hess, row_w, fmask, fnb, fnan, *rest):
        rest = list(rest)
        mono = rest.pop(0) if has_monotone else None
        cat = rest.pop(0) if has_cat else None
        qkey = rest.pop(0) if has_quant_key else None
        groups = rest.pop(0) if has_interaction else None
        forced = None
        if has_forced:
            forced = tuple(rest[:3])
            rest = rest[3:]
        nkey = rest.pop(0) if has_node_key else None
        bundle = tuple(rest[:8]) if has_bundle else None
        # the fused step's name for the grower as a whole, so that what
        # it does outside its split loop has a scope on this path too
        with scope("boost/grow"):
            return grow_tree_impl(cfg, bins_T, grad, hess, row_w, fmask,
                                  fnb, fnan, mono, cat, qkey, groups,
                                  forced, None, nkey, bundle)

    sharded = shard_map(fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    return jax.jit(sharded)


def make_dp_grow_fn(cfg: GrowConfig, mesh: Mesh,
                    has_monotone: bool = False, has_cat: bool = False,
                    has_quant_key: bool = False,
                    has_interaction: bool = False,
                    has_forced: bool = False,
                    has_node_key: bool = False,
                    has_bundle: bool = False):
    """Returns grow(bins_T, grad, hess, row_w, fmask, fnb, fnan[, mono]
    [, feat_is_cat][, quant_key][, groups][, forced...][, node_key]
    [, bundle x8]) running data-parallel over ``mesh``. Row inputs must
    be padded to a multiple of the device count (pad rows carry
    row_weight 0)."""
    return _build(cfg, mesh, has_monotone, has_cat, has_quant_key,
                  has_interaction, has_forced, has_node_key, has_bundle)
