"""Forest compiler: one trained Booster -> a servable compiled forest.

The Booster keeps trees as per-tree host objects (models/tree.py) and
the library predict path re-stacks them into device tensors on *every*
call — fine for notebooks, fatal for serving. Here the forest is
lowered ONCE into the tensorized SoA layout (ops/predict.py
StackedTrees: level-order feature/threshold/child/leaf-value arrays,
categorical bitsets packed to u32 words, optional linear-tree
coefficients), and batch prediction is a single jitted program over
that layout (the Booster/tensorized-traversal design of
arXiv:2011.02022 applied to this codebase's node-sweep predictor).

Two serving invariants live here:

- **Shape bucketing** (TPL003): the jit cache is keyed on the input
  shape, so arbitrary request sizes would compile forever. Rows are
  padded up to power-of-two buckets between ``min_bucket`` and
  ``max_batch_rows`` — at most ``log2(max/min)+1`` compiles per model,
  all touchable at warmup, and the recompile counter stays flat
  afterwards (contract-tested in tests/test_serve.py).
- **Donated hot swap**: a model swap stages the NEW forest on the host
  (``stack_trees(..., device=False)``) and uploads it FIELD BY FIELD
  through a jitted identity that donates the old field's device buffer
  (``donate_argnums=(0,)``), so the swap's transient HBM overhead is
  one field's staging copy — never a second resident forest. When
  layouts differ (tree count / padded width changed) it falls back to
  a plain whole-forest transfer.
"""

from __future__ import annotations

import hashlib
import warnings
from functools import partial
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs import register_jit
from ..ops.predict import StackedTrees, predict_leaf_raw
from ..prediction import convert_raw_scores, stack_trees

__all__ = ["CompiledForest", "compile_forest", "bucket_rows",
           "n_serve_buckets"]


def bucket_rows(n: int, min_bucket: int = 16,
                max_bucket: int = 16384) -> int:
    """Smallest power-of-two >= ``n`` clamped to [min_bucket,
    max_bucket]. Requests larger than ``max_bucket`` are split by the
    caller; everything else pads up, so the jit cache holds at most
    ``log2(max/min) + 1`` entries per model."""
    if n <= 0:
        raise ValueError(f"batch must have at least one row, got {n}")
    b = 1 << (int(n) - 1).bit_length()
    return max(min_bucket, min(b, max_bucket))


def n_serve_buckets(min_bucket: int = 16,
                    max_bucket: int = 16384) -> int:
    """Number of distinct pow2 row buckets ``bucket_rows`` can emit —
    the per-model compile ceiling of the serving program, and the
    floor ``lint --ir`` (TPL014) holds the ``serve/predict``
    ``max_signatures`` declaration against."""
    import math

    return int(math.log2(max_bucket // min_bucket)) + 1


@partial(jax.jit, static_argnums=(2,))
def _predict_scores_padded(stacked: StackedTrees, X: jnp.ndarray,
                           K: int) -> jnp.ndarray:
    """Raw scores [n, K] for a padded batch — the ONE serving program.

    Leaf routing, (linear-)leaf evaluation and the per-class
    scatter-add all trace into a single XLA computation, so a request
    costs one dispatch instead of the library path's stack + three."""
    T = stacked.leaf_value.shape[0]

    def per_tree(ti):
        return predict_leaf_raw(stacked, ti, X)

    leaves = jax.vmap(per_tree)(jnp.arange(T))           # [T, n]
    if stacked.lin_const is not None:
        from ..ops.linear import linear_leaf_values

        def per_tree_vals(ti):
            return linear_leaf_values(
                stacked.lin_const[ti], stacked.lin_coef[ti],
                stacked.lin_feats[ti], stacked.lin_nfeat[ti],
                stacked.leaf_value[ti], X, leaves[ti])

        vals = jax.vmap(per_tree_vals)(jnp.arange(T))
    else:
        vals = jnp.take_along_axis(stacked.leaf_value, leaves, axis=1)
    # tree i belongs to class i % K and T is a whole number of
    # iterations, so the per-class sum is a reshape + reduce. (The
    # scatter-add form, fused with the gather above, aborts XLA:TPU's
    # compiler at the 128-row bucket: "Check failed: GetGatherType(
    # gather) == GatherType::kSublaneGather", libtpu 0.0.34.)
    scores = vals.reshape(T // K, K, X.shape[0]).sum(axis=0)
    return scores.T                                      # [n, K]


# the declared recompile surface is the full pow2 bucket ladder twice
# over (two live tree-count/K layouts per process — a hot swap staging
# a differently-shaped forest compiles its own ladder)
_predict_scores_padded = register_jit("serve/predict",
                                      _predict_scores_padded,
                                      max_signatures=2 * n_serve_buckets())


@partial(jax.jit, donate_argnums=(0,))
def _adopt_leaf(old: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """Upload ONE field of the new forest into the old field's donated
    buffer. Adoption walks the layout field by field, so the swap's
    transient HBM overhead is a single field's staging copy — never a
    second resident forest. (A whole-tree donating identity would not
    help: every new field would have to be device-resident as an
    input while the full old forest is still alive, i.e. 2x peak.)"""
    return new


def _layouts_match(old: StackedTrees, new: StackedTrees) -> bool:
    old_leaves = jax.tree_util.tree_leaves(old)
    new_leaves = jax.tree_util.tree_leaves(new)
    if len(old_leaves) != len(new_leaves):
        return False
    return all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(old_leaves, new_leaves))


def _model_digest(host_stacked: StackedTrees) -> str:
    """Stable short id of the compiled arrays, for telemetry and the
    daemon protocol ("which model answered this request"). Only the
    prediction-relevant fields are hashed — ``threshold_bin`` is a
    training-side artifact that text-round-tripped models lose, and
    the same forest must keep the same id across a save/load."""
    h = hashlib.sha256()
    for name, leaf in zip(host_stacked._fields, host_stacked):
        if name == "threshold_bin" or leaf is None:
            continue
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()[:16]


class CompiledForest:
    """A forest lowered to device tensors plus its serving metadata.

    Build via :func:`compile_forest` (or ``Booster.compile()``, which
    also routes subsequent ``Booster.predict`` calls through this
    object's shape-bucketed program)."""

    def __init__(self, stacked, *, num_class: int, n_features: int,
                 objective_str: str, avg_output: bool,
                 num_iteration: int, lo: int, hi: int,
                 total_trees: int, model_id: str,
                 min_bucket: int = 16, max_batch_rows: int = 16384):
        self._stacked = stacked           # device StackedTrees (or None)
        self._host = None                 # staged host arrays (stage=True)
        self._dead = False                # buffers donated to a successor
        self.K = int(num_class)
        self.n_features = int(n_features)
        self.objective_str = objective_str
        self.avg_output = bool(avg_output)
        self.num_iteration = int(num_iteration)
        self.lo = int(lo)
        self.hi = int(hi)
        self.total_trees = int(total_trees)
        self.model_id = model_id
        if min_bucket < 1 or (min_bucket & (min_bucket - 1)) != 0:
            raise ValueError(f"min_bucket must be a power of two >= 1, "
                             f"got {min_bucket}")
        if max_batch_rows < min_bucket or \
                (max_batch_rows & (max_batch_rows - 1)) != 0:
            raise ValueError(
                "max_batch_rows must be a power of two >= min_bucket, "
                f"got {max_batch_rows}")
        self.min_bucket = int(min_bucket)
        self.max_batch_rows = int(max_batch_rows)

    @property
    def num_trees(self) -> int:
        return self.hi - self.lo

    def matches(self, lo: int, hi: int, total_trees: int) -> bool:
        """Does this compilation still describe the Booster state a
        predict call wants? (The Booster may have trained more trees,
        or the caller may ask for a different iteration range.) A dead
        forest — one whose buffers a newer compilation took over —
        never matches, so a booster still caching it falls back to the
        eager path instead of serving donated garbage."""
        return not self._dead and \
            (self.lo, self.hi, self.total_trees) == (lo, hi, total_trees)

    def buckets(self) -> List[int]:
        out = []
        b = self.min_bucket
        while b <= self.max_batch_rows:
            out.append(b)
            b *= 2
        return out

    # -- prediction ----------------------------------------------------
    def predict_raw(self, X) -> np.ndarray:
        """Raw scores ``[n, K]`` (f64) for raw-feature rows ``[n, F]``.

        Rows are padded to the enclosing power-of-two bucket (chunked
        at ``max_batch_rows``), so after warmup NO batch size causes a
        compile — the TPL003 invariant the recompile-counter contract
        test pins."""
        X = np.ascontiguousarray(np.asarray(X, np.float32))
        if X.ndim == 1:
            X = X[None, :]
        if X.shape[1] != self.n_features:
            from ..basic import LightGBMError
            raise LightGBMError(
                f"The number of features in data ({X.shape[1]}) is not "
                f"the same as it was in training data "
                f"({self.n_features}).")
        n = X.shape[0]
        if self._dead:
            raise RuntimeError(
                "this forest's device buffers were donated to a newer "
                "compilation (compile_forest(reuse=...)); it must not "
                "predict again")
        if n == 0:
            return np.zeros((0, self.K), np.float64)
        if self._stacked is None:
            if self._host is not None:
                raise RuntimeError(
                    "forest is staged on the host: call attach() "
                    "before predicting")
            return np.zeros((n, self.K), np.float64)  # empty forest
        outs = []
        for lo in range(0, n, self.max_batch_rows):
            chunk = X[lo:lo + self.max_batch_rows]
            rows = chunk.shape[0]
            b = bucket_rows(rows, self.min_bucket, self.max_batch_rows)
            if b > rows:
                chunk = np.concatenate(
                    [chunk, np.zeros((b - rows, X.shape[1]),
                                     np.float32)])
            scores = _predict_scores_padded(self._stacked, chunk, self.K)
            # fetch the PADDED result and slice on the host: a device
            # `scores[:rows]` would trace one lazy-slice executable per
            # (bucket, rows) pair — an unbounded compile-cache leak the
            # bucketing exists to prevent (and invisible to the
            # registered recompile counter)
            outs.append(np.asarray(scores)[:rows].astype(np.float64))
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def finalize(self, raw_scores: np.ndarray,
                 raw_score: bool = False) -> np.ndarray:
        """Objective transform + rf averaging + K==1 squeeze — the
        exact tail of the library predict path, applied host-side."""
        out = raw_scores
        if self.avg_output:
            out = out / max(1, self.num_iteration)
        if not raw_score:
            out = convert_raw_scores(self.objective_str, out)
        return out[:, 0] if self.K == 1 else out

    def predict(self, X, raw_score: bool = False) -> np.ndarray:
        return self.finalize(self.predict_raw(X), raw_score)

    # -- lifecycle -----------------------------------------------------
    def warmup(self, max_rows: Optional[int] = None) -> int:
        """Compile every row bucket up to ``max_rows`` (default: all of
        them) by running zero batches through the program; returns the
        number of buckets touched. After this, serving traffic of ANY
        batch size <= max_rows hits a warm cache."""
        if self._stacked is None:
            return 0
        cap = self.max_batch_rows if max_rows is None \
            else max(self.min_bucket, int(max_rows))
        touched = 0
        for b in self.buckets():
            if b > cap:
                break
            zeros = np.zeros((b, self.n_features), np.float32)
            _predict_scores_padded(self._stacked, zeros,
                                   self.K).block_until_ready()
            touched += 1
        return touched

    def attach(self, reuse: Optional["CompiledForest"] = None) \
            -> "CompiledForest":
        """Upload this forest's STAGED host arrays
        (``compile_forest(..., stage=True)``), donating ``reuse``'s
        device buffers when the layouts match. The daemon's hot-swap
        path runs this on the batcher's worker thread — the one point
        where no batch can still reference the old forest, which is
        what makes the donation safe."""
        if self._host is None:
            return self
        host, self._host = self._host, None
        if reuse is not None:
            reuse.adopt(host)
            self._stacked, reuse._stacked = reuse._stacked, None
            reuse._dead = True
        else:
            self._stacked = jax.tree_util.tree_map(jnp.asarray, host)
        return self

    def adopt(self, host_stacked: Optional[StackedTrees]):
        """Replace the device forest with ``host_stacked`` (host
        arrays), donating the old buffers when the layouts line up.
        Internal: used by :func:`compile_forest` via ``reuse=``."""
        if host_stacked is None:
            self._stacked = None
            return
        old = self._stacked
        if old is not None and _layouts_match(old, host_stacked):
            with warnings.catch_warnings():
                # backends without working donation (CPU on some
                # jaxlibs) warn and copy; the swap is still correct
                warnings.simplefilter("ignore")
                old_leaves, treedef = jax.tree_util.tree_flatten(old)
                new_leaves = jax.tree_util.tree_leaves(host_stacked)
                adopted = [_adopt_leaf(o, n)
                           for o, n in zip(old_leaves, new_leaves)]
                self._stacked = jax.tree_util.tree_unflatten(
                    treedef, adopted)
        else:
            self._stacked = jax.tree_util.tree_map(jnp.asarray,
                                                   host_stacked)


def compile_forest(booster, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   min_bucket: int = 16,
                   max_batch_rows: int = 16384,
                   reuse: Optional[CompiledForest] = None,
                   stage: bool = False) -> CompiledForest:
    """Lower ``booster``'s forest into a :class:`CompiledForest`.

    Tree selection matches ``Booster.predict`` (``start_iteration`` /
    ``num_iteration`` in boosting rounds; <=0 means all remaining).
    ``reuse``: a previous compilation whose device buffers the new
    model may take over (the hot-swap path) — after this call the
    reused forest is dead and must not predict again. ``stage=True``
    keeps the arrays on the HOST (no HBM touched); call
    :meth:`CompiledForest.attach` to upload later — the daemon stages
    on the watcher thread and attaches on the batcher worker.
    """
    trees = booster._models
    K = booster.num_model_per_iteration()
    total_iters = len(trees) // max(K, 1)
    if num_iteration is None or num_iteration <= 0:
        num_iteration = total_iters - start_iteration
    num_iteration = max(0, min(num_iteration,
                               total_iters - start_iteration))
    lo = start_iteration * K
    hi = (start_iteration + num_iteration) * K
    sel = trees[lo:hi]
    host = stack_trees(sel, device=False) if sel else None
    model_id = _model_digest(host) if host is not None else "empty"
    n_features = booster.num_feature()
    if stage:
        stacked = None
    elif reuse is not None:
        reuse.adopt(host)
        stacked = reuse._stacked
        reuse._stacked = None        # ownership moves to the new forest
        reuse._dead = True           # reuse must raise, not serve zeros
    elif host is not None:
        stacked = jax.tree_util.tree_map(jnp.asarray, host)
    else:
        stacked = None
    cf = CompiledForest(
        stacked, num_class=K, n_features=n_features,
        objective_str=booster._objective_str,
        avg_output=booster._avg_output,
        num_iteration=max(1, num_iteration), lo=lo, hi=hi,
        total_trees=len(trees), model_id=model_id,
        min_bucket=min_bucket, max_batch_rows=max_batch_rows)
    if stage:
        cf._host = host
    return cf
